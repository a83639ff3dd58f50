"""Criteo-shaped click logs from a seed: generator, TSV writer, and the
yardstick's own copy of the feature -> table-row mapping.

Shape (not cut): label, 13 integer columns, 26 categorical columns, every
field present, so an example always carries 39 features and a batch of
8192 always lands in the 2^19 entry bucket. Categorical values are drawn
Zipf over per-column vocabularies (the Criteo-Kaggle cardinalities, listed
in the configuration file under ``assumed``) and written as 8 hex digits,
the way the public logs carry them; labels follow a seeded sparse logistic
truth so that AUC means something.

Everything here is vectorised NumPy: 786k examples are made and written in
a few seconds of set-up. Nothing is imported from the program under test:
``hash_rows`` re-implements its splitmix64 slot-salted hash so that the
plain reference addresses the same table rows from the raw columns.
"""

from __future__ import annotations

import numpy as np

N_INT, N_CAT = 13, 26
_INT_DIGITS = 7  # integer columns are drawn below 10**7

_C1 = np.uint64(0x9E3779B97F4A7C15)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)


def splitmix64(x: np.ndarray) -> np.ndarray:
    z = x.astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        z += _C1
        z = (z ^ (z >> np.uint64(30))) * _C2
        z = (z ^ (z >> np.uint64(27))) * _C3
        z = z ^ (z >> np.uint64(31))
    return z


def hash_rows(raw: np.ndarray, slots: np.ndarray, num_keys: int) -> np.ndarray:
    """Raw 64-bit feature id + slot -> table row in [1, num_keys); row 0 is
    the pad row. The contract of ``utils/hashing.hash_keys`` and of the
    native ``hash_localize``, written out again."""
    with np.errstate(over="ignore"):
        mixed = raw.astype(np.uint64) ^ splitmix64(slots.astype(np.uint64) + _C1)
    h = splitmix64(mixed)
    return (h % np.uint64(num_keys - 1) + np.uint64(1)).astype(np.int64)


def _unit(h: np.ndarray) -> np.ndarray:
    """uint64 hash -> float64 in (0, 1)."""
    return ((h >> np.uint64(11)).astype(np.float64) + 0.5) / float(1 << 53)


def _truth_weight(col: np.ndarray, rank: np.ndarray, spec: dict, salt: int) -> np.ndarray:
    """The hidden weight of categorical value ``rank`` of column ``col``:
    zero for most values, Gaussian for a seeded ``truth_density`` share."""
    base = (col.astype(np.uint64) << np.uint64(40)) ^ rank.astype(np.uint64)
    h1 = splitmix64(base ^ np.uint64(salt))
    h2 = splitmix64(h1)
    h3 = splitmix64(h2)
    gauss = np.sqrt(-2.0 * np.log(_unit(h1))) * np.cos(2.0 * np.pi * _unit(h2))
    live = _unit(h3) < spec["truth_density"]
    return np.where(live, spec["truth_scale"] * gauss, 0.0)


def make_examples(seed: int, n: int, spec: dict, part: int = 0):
    """(labels f32 (n,), ints i64 (n, 13), cats u32 (n, 26)) from ``seed``;
    ``part`` numbers the file, so that files are made one at a time (a
    few tens of MB of temporaries, not hundreds).

    ``spec`` is the configuration file's ``data`` group: ``cat_vocab``
    (26 cardinalities), ``zipf_s``, ``int_mu``/``int_sigma`` (log-normal
    integer columns), ``truth_density``, ``truth_scale``, ``base_rate``."""
    rng = np.random.default_rng([int(seed), 0xC7, int(part)])
    vocab = np.asarray(spec["cat_vocab"], dtype=np.float64)
    if vocab.shape != (N_CAT,):
        raise ValueError(f"cat_vocab needs {N_CAT} cardinalities, got {vocab.shape}")
    s = float(spec["zipf_s"])
    u = rng.random((n, N_CAT))
    # inverse CDF of the continuous power law r^-s on [1, V+1): Zipf ranks
    e = 1.0 - s
    ranks = np.floor(((np.power(vocab + 1.0, e) - 1.0) * u + 1.0) ** (1.0 / e))
    ranks = np.minimum(ranks, vocab).astype(np.int64)
    cols = np.broadcast_to(np.arange(N_CAT, dtype=np.int64), ranks.shape)
    # the logs carry a 32-bit hash of the value, not its rank
    cats = (
        splitmix64((cols.astype(np.uint64) << np.uint64(40)) ^ ranks.astype(np.uint64))
        & np.uint64(0xFFFFFFFF)
    ).astype(np.uint32)
    ints = np.floor(
        np.exp(rng.normal(spec["int_mu"], spec["int_sigma"], size=(n, N_INT)))
    ).astype(np.int64)
    ints = np.clip(ints, 0, 10**_INT_DIGITS - 1)
    truth = np.random.default_rng([int(seed), 0x7A])  # one hidden truth per seed
    int_w = truth.normal(0.0, 0.15, N_INT)
    salt = int(truth.integers(1 << 62))
    logit = _truth_weight(cols, ranks, spec, salt=salt).sum(axis=1)
    logit += (np.log1p(ints) - spec["int_mu"]) @ int_w
    logit += np.log(spec["base_rate"] / (1.0 - spec["base_rate"]))
    labels = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    return labels, ints, cats


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)


def write_tsv(path: str, labels: np.ndarray, ints: np.ndarray, cats: np.ndarray) -> None:
    """Criteo TSV, built as one byte matrix: a fixed-width row per example
    with the leading zeros of the integer columns masked out. The matrix
    is filled column by column in its transposed (contiguous) form."""
    n = len(labels)
    width = 2 + N_INT * (_INT_DIGITS + 1) + N_CAT * 9
    buf = np.empty((width, n), dtype=np.uint8)
    keep = np.ones((width, n), dtype=bool)
    buf[0] = 48 + labels.astype(np.uint8)
    buf[1] = 9
    at = 2
    ints32 = np.ascontiguousarray(ints.T.astype(np.int32))
    for j in range(N_INT):
        v = ints32[j]
        for k in range(_INT_DIGITS):
            p = 10 ** (_INT_DIGITS - 1 - k)
            buf[at + k] = 48 + (v // p) % 10
            if p > 1:
                keep[at + k] = v >= p
        buf[at + _INT_DIGITS] = 9
        at += _INT_DIGITS + 1
    cats_t = np.ascontiguousarray(cats.T)
    for j in range(N_CAT):
        v = cats_t[j]
        for k in range(8):
            buf[at + k] = _HEX[(v >> np.uint32(4 * (7 - k))) & np.uint32(15)]
        buf[at + 8] = 9
        at += 9
    buf[width - 1] = 10
    np.ascontiguousarray(buf.T)[np.ascontiguousarray(keep.T)].tofile(path)


def features(ints: np.ndarray, cats: np.ndarray, num_keys: int):
    """What the criteo parser and the hashing builder make of the columns,
    written out for the reference: per example 39 (row, value) pairs.
    Integer column j: raw key j, slot j+1, value log1p(x) in float32 (via
    float64, as the parser does); categorical column j: raw key its id,
    slot j+14, value 1."""
    n = len(ints)
    raw = np.concatenate(
        [np.broadcast_to(np.arange(N_INT, dtype=np.uint64), (n, N_INT)),
         cats.astype(np.uint64)], axis=1,
    )
    slots = np.broadcast_to(
        np.concatenate([np.arange(1, N_INT + 1), np.arange(14, 14 + N_CAT)]).astype(np.uint64),
        raw.shape,
    )
    rows = hash_rows(raw.ravel(), slots.ravel(), num_keys).reshape(n, N_INT + N_CAT)
    vals = np.concatenate(
        [np.log1p(ints.astype(np.float64)).astype(np.float32),
         np.ones((n, N_CAT), dtype=np.float32)], axis=1,
    )
    return rows, vals
