"""Ratings of a Hugewiki-shaped matrix from a seed: generator, text writer,
and the yardstick's own copy of the id -> table-row layout.

Shape: ``user item rating`` lines, ids from 0. A rating names a user in
proportion to its degree, and degrees follow a log-normal law (a few
editors make most edits): the user of a rating is drawn by the closed form
of that law's size-biased tail, so no array of ``num_users`` entries is ever
made, and an affine map of the ids spreads the heavy users over the id
space. Items are Zipf over ``num_items``, the hottest first. The rating is a
planted low-rank model plus noise (non-negative factors from a hash of the
id, so every item has a mean rating of its own that training can find),
kept to four decimals, so that the text, the program's parse of it and the
reference's parse are the same float32.

Everything here is vectorised NumPy; nothing is imported from the program.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.criteo import _unit, splitmix64

_SPREAD = 2654435761  # a prime: rank -> id, one to one where it does not divide num_users
_RATING_MAX = 99999  # ten-thousandths: ratings are written as d.dddd
_USER_DIGITS, _ITEM_DIGITS = 8, 5


def table_rows(users: np.ndarray, items: np.ndarray, num_items: int):
    """(item rows, user rows): items take table rows 1..num_items, users
    the rows behind them, row 0 is the pad. The layout of the program's
    ``rating`` format under identity keys, written out again."""
    return np.asarray(items, np.int64) + 1, np.asarray(users, np.int64) + 1 + int(num_items)


def _planted(ids: np.ndarray, rank: int, salt: int) -> np.ndarray:
    """(len(ids), rank) float64 in (0, 1): the hidden factor of each id."""
    lanes = np.arange(rank, dtype=np.uint64)[None, :]
    return _unit(splitmix64((ids.astype(np.uint64)[:, None] << np.uint64(8)) ^ lanes ^ np.uint64(salt)))


def make_ratings(seed: int, n: int, spec: dict, num_users: int, num_items: int, part: int = 0):
    """(users i64 (n,), items i64 (n,), ratings f32 (n,)) from ``seed``;
    ``part`` numbers the file. ``spec`` is the configuration file's ``data``
    group: ``degree_sigma`` (the log-normal degree law's sigma; its mean, 55
    at Hugewiki, only says how many ratings the whole matrix would hold),
    ``zipf_s``, ``planted_rank``, ``noise``."""
    from scipy.special import ndtr

    rng = np.random.default_rng([int(seed), 0x3F, int(part)])
    # a rating's user, among users sorted by degree: the share of users below
    # it is Phi(z + sigma) for a standard normal z (a log-normal's size-biased
    # law is the same law moved up by sigma^2)
    share = ndtr(rng.standard_normal(n) + float(spec["degree_sigma"]))
    by_degree = np.minimum((share * num_users).astype(np.int64), num_users - 1)
    users = (by_degree * _SPREAD + 12345) % num_users
    cdf = np.cumsum(np.arange(1, num_items + 1, dtype=np.float64) ** -float(spec["zipf_s"]))
    items = np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1]), num_items - 1).astype(np.int64)
    truth = np.random.default_rng([int(seed), 0x7B])  # one hidden model per seed
    salt_u, salt_i = (int(x) for x in truth.integers(1 << 62, size=2))
    k = int(spec["planted_rank"])
    clean = np.sum(_planted(users, k, salt_u) * _planted(items, k, salt_i), axis=1)
    tenk = np.rint((clean + float(spec["noise"]) * rng.standard_normal(n)) * 1e4)
    tenk = np.clip(tenk, 0, _RATING_MAX).astype(np.int64)
    return users, items, (tenk / 10000.0).astype(np.float32)


def _digits(buf, keep, at: int, v: np.ndarray, width: int) -> int:
    """``v`` as decimal digits in rows at..at+width of the transposed byte
    matrix, leading zeros masked out."""
    for k in range(width):
        p = 10 ** (width - 1 - k)
        buf[at + k] = 48 + (v // p) % 10
        if p > 1:
            keep[at + k] = v >= p
    return at + width


def write_text(path: str, users: np.ndarray, items: np.ndarray, ratings: np.ndarray) -> None:
    """``user item d.dddd`` lines as one byte matrix, as ``criteo.write_tsv``
    builds its own."""
    n = len(ratings)
    tenk = np.rint(ratings.astype(np.float64) * 1e4).astype(np.int64)
    width = _USER_DIGITS + 1 + _ITEM_DIGITS + 1 + 6 + 1
    buf = np.empty((width, n), dtype=np.uint8)
    keep = np.ones((width, n), dtype=bool)
    at = _digits(buf, keep, 0, users, _USER_DIGITS)
    buf[at] = 32
    at = _digits(buf, keep, at + 1, items, _ITEM_DIGITS)
    buf[at] = 32
    buf[at + 1] = 48 + tenk // 10000
    buf[at + 2] = 46
    for k in range(4):
        buf[at + 3 + k] = 48 + (tenk // 10 ** (3 - k)) % 10
    buf[width - 1] = 10
    np.ascontiguousarray(buf.T)[np.ascontiguousarray(keep.T)].tofile(path)
