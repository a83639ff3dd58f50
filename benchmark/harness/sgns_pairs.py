"""Skip-gram examples of a Zipf corpus from a seed: generator, text writer,
and the yardstick's own copy of the word id -> table-row layout.

Shape: ``centre context neg_1 ... neg_k`` lines of word ids from 0, what
word2vec.c makes of a corpus before it touches a vector (Mikolov et al.,
arXiv:1310.4546, sections 2.2-2.3). Words are Zipf over the vocabulary, the
hottest first; frequent words are subsampled (a token of frequency f is kept
with probability sqrt(t / f) where f > t), so the stream of kept tokens is
drawn from the subsampled law directly; every token is a centre with a
reach b uniform in 1..window (the dynamic window) and pairs with the kept
tokens within b of it, the pairs in the stream's order, a centre's pairs
together, as word2vec.c trains them; the k negatives of a pair are drawn
from the unigram^0.75 law of the UNsubsampled frequencies and redrawn where
one equals the pair's context (word2vec.c skips those).

Everything here is vectorised NumPy over the file's pairs; the two laws'
cumulative tables (one float64 a word) are made once a process, not a
file. Nothing is imported from the program.
"""

from __future__ import annotations

import functools

import numpy as np

_DIGITS = 7  # word ids are written in up to 7 digits: vocabularies under 10^7


def table_rows(centres: np.ndarray, outputs: np.ndarray, vocab_size: int):
    """(input rows, output rows): word w's input vector at table row 1 + w,
    its output vector at 1 + V + w, row 0 the pad. The layout of the
    program's ``sgns`` format under identity keys, written out again."""
    return np.asarray(centres, np.int64) + 1, np.asarray(outputs, np.int64) + 1 + int(vocab_size)


@functools.lru_cache(maxsize=2)
def _laws(vocab_size: int, zipf_s: float, subsample_t: float, noise_power: float):
    """(cdf of the kept tokens' law, cdf of the noise law) over word ids."""
    f = np.arange(1, vocab_size + 1, dtype=np.float64) ** -zipf_s
    f /= f.sum()
    kept = f * np.minimum(1.0, np.sqrt(subsample_t / f))
    noise = f**noise_power
    return np.cumsum(kept) / kept.sum(), np.cumsum(noise) / noise.sum()


def _draw(cdf: np.ndarray, u: np.ndarray) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, u, side="right"), len(cdf) - 1).astype(np.int64)


def make_pairs(seed: int, n: int, spec: dict, vocab_size: int, window: int, negatives: int, part: int = 0):
    """(centres i64 (n,), contexts i64 (n,), negatives i64 (n, k)) from
    ``seed``; ``part`` numbers the file. ``spec`` is the configuration
    file's ``data`` group: ``zipf_s``, ``subsample_t``, ``noise_power``."""
    rng = np.random.default_rng([int(seed), 0x51, int(part)])
    kept_cdf, noise_cdf = _laws(
        int(vocab_size), float(spec["zipf_s"]), float(spec["subsample_t"]), float(spec["noise_power"])
    )
    # a centre makes 2 E[b] = window + 1 pairs: enough tokens and some over
    tokens = _draw(kept_cdf, rng.random(int(n / (window + 1) * 1.1) + 4 * window))
    reach = rng.integers(1, window + 1, len(tokens))
    at = np.arange(len(tokens))
    centre_at, context_at = [], []
    for off in range(1, window + 1):
        near = at[reach >= off]
        for side in (near - off, near + off):
            ok = (side >= 0) & (side < len(tokens))
            centre_at.append(near[ok])
            context_at.append(side[ok])
    centre_at, context_at = np.concatenate(centre_at), np.concatenate(context_at)
    order = np.lexsort((context_at, centre_at))[:n]  # the stream's order: by centre, then by context
    if len(order) < n:
        raise RuntimeError(f"{len(order)} pairs made of {len(tokens)} tokens, {n} wanted")
    centres, contexts = tokens[centre_at[order]], tokens[context_at[order]]
    neg = _draw(noise_cdf, rng.random((n, negatives)))
    again = neg == contexts[:, None]
    while again.any():
        neg[again] = _draw(noise_cdf, rng.random(int(again.sum())))
        again = neg == contexts[:, None]
    return centres, contexts, neg


def write_text(path: str, centres: np.ndarray, contexts: np.ndarray, negatives: np.ndarray) -> None:
    """One line an example as one byte matrix (``criteo.write_tsv``'s way):
    2 + k ids of up to ``_DIGITS`` digits, leading zeros masked out."""
    ids = np.column_stack([centres, contexts, negatives]).astype(np.int64)
    n, cols = ids.shape
    if ids.max() >= 10**_DIGITS:
        raise ValueError(f"a word id of more than {_DIGITS} digits")
    width = cols * (_DIGITS + 1)
    buf = np.empty((n, width), dtype=np.uint8)
    keep = np.ones((n, width), dtype=bool)
    for c in range(cols):
        at = c * (_DIGITS + 1)
        for k in range(_DIGITS):
            p = 10 ** (_DIGITS - 1 - k)
            buf[:, at + k] = 48 + (ids[:, c] // p) % 10
            if p > 1:
                keep[:, at + k] = ids[:, c] >= p
        buf[:, at + _DIGITS] = 32 if c < cols - 1 else 10
    buf[keep].tofile(path)
