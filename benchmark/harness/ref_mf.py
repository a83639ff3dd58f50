"""The plain reference for matrix factorization: NumPy float32, every
formula written out, nothing imported from the program and nothing taken
that the program made. It parses the rating lines itself, lays the ids out
over the table itself and computes the starting factors itself.

The model (BASELINE.json config 3: rank-64 matrix factorization under plain
SGD): a rating of item i by user u is predicted as <w[row_i], w[row_u]>
over ONE table (items at rows 1..num_items, users behind them). A
parameter-server step over a minibatch: err = prediction - rating; loss =
sum err^2; the pushed gradient of a touched row is the sum over its ratings
in the batch of err x (the other row), that is the gradient of half the
loss, plus l2 x the row, once a batch however often the row repeats; the
update is w -= eta x gradient. With several workers a step, each worker's
gradient is taken at the step's starting table and the pushes land in
worker order. Departures from the config's words: "async push/pull" is
bounded staleness across device calls, which changes no arithmetic.

State lives over a compact index of the rows a check can touch, not over
the table. ``precision`` is for the controls only: ``"bfloat16"`` rounds
the table's state and the pushed gradients to bfloat16, the nearest
precision below the float32 the configuration states.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.ref_ftrl import _round


def parse_ratings(path: str):
    """(users i64, items i64, ratings f32) of a file of ``user item rating``
    lines: the reference's own reading of what the program's parser reads."""
    cols = np.loadtxt(path, dtype=np.float64, ndmin=2)
    return cols[:, 0].astype(np.int64), cols[:, 1].astype(np.int64), cols[:, 2].astype(np.float32)


def _fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer over uint32 arrays (arithmetic wraps)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def init_factors(seed: int, rows: np.ndarray, rank: int, num_keys: int) -> np.ndarray:
    """(len(rows), rank) float32: the table's starting rows from (seed, row,
    lane) alone. Two rounds of a 32-bit mix over the row and the lane, the
    top 24 bits as a multiple of 2^-23 in [0, 2), times half of 1/sqrt(rank):
    uniform in [0, 1/sqrt(rank)); row 0 and rows at or past ``num_keys`` are 0."""
    rows = np.asarray(rows, np.int64)
    with np.errstate(over="ignore"):
        r = rows.astype(np.uint32)[:, None]
        lane = np.arange(rank, dtype=np.uint32)[None, :]
        x = _fmix32(r * np.uint32(0x9E3779B1) + np.uint32(int(seed) & 0xFFFFFFFF))
        x = _fmix32(x ^ (lane * np.uint32(0x85EBCA77) + np.uint32(0xC2B2AE3D)))
    unit = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-23)
    live = (rows > 0) & (rows < num_keys)
    return np.where(live[:, None], unit * np.float32(0.5 / rank**0.5), np.float32(0.0))


def rmse(predictions: np.ndarray, ratings: np.ndarray) -> float:
    d = np.asarray(predictions, np.float64) - np.asarray(ratings, np.float64)
    return float(np.sqrt(np.mean(d * d)))


class RefMf:
    def __init__(self, rows_universe: np.ndarray, hyper: dict, seed: int, num_keys: int,
                 precision: str = "float32"):
        """``rows_universe``: every table row any later batch may name.
        ``hyper``: rank, eta, l2."""
        self.rows = np.unique(np.asarray(rows_universe).ravel())
        self.precision = precision
        self.eta, self.l2 = np.float32(hyper["eta"]), np.float32(hyper["l2"])
        self.w0 = init_factors(seed, self.rows, int(hyper["rank"]), num_keys)
        self.w = self._r(self.w0)

    def _r(self, x: np.ndarray) -> np.ndarray:
        return _round(np.asarray(x, np.float32), self.precision)

    def index(self, table_rows: np.ndarray) -> np.ndarray:
        """Table rows -> positions in this reference's compact state."""
        pos = np.searchsorted(self.rows, table_rows)
        if not np.array_equal(self.rows[np.minimum(pos, len(self.rows) - 1)], table_rows):
            raise KeyError("a row outside the reference's universe")
        return pos

    def predict(self, item_at: np.ndarray, user_at: np.ndarray, block: int = 1 << 18) -> np.ndarray:
        return np.concatenate([
            np.sum(self.w[item_at[i : i + block]] * self.w[user_at[i : i + block]], axis=1, dtype=np.float32)
            for i in range(0, len(item_at), block)
        ])

    def grads(self, item_at: np.ndarray, user_at: np.ndarray, ratings: np.ndarray):
        """Summed squared error of one batch, the positions it touches and
        the gradient pushed to each: per touched row the sum of err x the
        pair's other row, plus l2 x the row."""
        v, u = self.w[item_at], self.w[user_at]
        err = np.sum(u * v, axis=1, dtype=np.float32) - ratings
        at = np.concatenate([item_at, user_at])
        contrib = np.concatenate([err[:, None] * u, err[:, None] * v])
        order = np.argsort(at, kind="stable")
        touched, starts = np.unique(at[order], return_index=True)
        g = np.add.reduceat(contrib[order], starts, axis=0) + self.l2 * self.w[touched]
        return float(np.sum(err.astype(np.float64) ** 2)), touched, g.astype(np.float32)

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (item positions, user
        positions, ratings) batches. Returns the summed squared error."""
        loss, pushes = 0.0, []
        for item_at, user_at, ratings in workers:
            l, touched, g = self.grads(item_at, user_at, ratings)
            loss += l
            pushes.append((touched, g))
        for touched, g in pushes:
            self.w[touched] = self._r(self.w[touched] - self.eta * self._r(g))
        return loss
