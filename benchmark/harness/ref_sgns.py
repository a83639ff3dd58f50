"""The plain reference for skip-gram with negative sampling: NumPy float32,
every formula written out, nothing imported from the program and nothing
taken that the program made. It parses the example lines itself, lays the
word ids out over the table itself and computes the starting vectors
itself.

The model (Mikolov et al., arXiv:1310.4546, eq. 4, negated; BASELINE.json
config 4): ONE table, word w's input vector at row 1 + w and its output
vector at row 1 + V + w. An example is a centre c, a context o_0 and k
negatives o_1..o_k: s_j = <w[in c], w[out o_j]>; loss = sum_j softplus(s_j)
- s_0; err_j = sigmoid(s_j) - [j == 0]; the gradient of the centre's row is
sum_j err_j w[out o_j], of an output row err_j w[in c]. A parameter-server
step over a minibatch: the loss is the SUM over its examples; the pushed
gradient of a touched row is the sum over its occurrences in the batch,
once a batch however often the row repeats; the update is w -= eta x
gradient (word2vec.c applies the gradients pair by pair). With several
workers a step, each worker's gradient is taken at the step's starting
table and the pushes land in worker order.

State lives over a compact index of the rows a check can touch, not over
the table. ``precision`` is for the controls only: ``"bfloat16"`` rounds
the table's state and the pushed gradients to bfloat16, the nearest
precision below the float32 the configuration states.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.ref_ftrl import _round


def parse_examples(path: str):
    """(centres i64 (n,), outputs i64 (n, 1 + k): the context first) of a
    file of ``centre context neg_1 ... neg_k`` lines: the reference's own
    reading of what the program's parser reads."""
    cols = np.loadtxt(path, dtype=np.int64, ndmin=2)
    return cols[:, 0], cols[:, 1:]


def _fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer over uint32 arrays (arithmetic wraps)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def init_vectors(seed: int, rows: np.ndarray, dim: int, vocab_size: int) -> np.ndarray:
    """(len(rows), dim) float32: the table's starting rows from (seed, row,
    lane) alone. Two rounds of a 32-bit mix over the row and the lane, the
    top 24 bits as a multiple of 2^-23 less 1, in [-1, 1), times 0.5 / dim:
    uniform in [-0.5/dim, 0.5/dim) for the input vectors (rows 1..V), as
    word2vec.c starts them; row 0 and the output vectors' rows are 0."""
    rows = np.asarray(rows, np.int64)
    with np.errstate(over="ignore"):
        r = rows.astype(np.uint32)[:, None]
        lane = np.arange(dim, dtype=np.uint32)[None, :]
        x = _fmix32(r * np.uint32(0x9E3779B1) + np.uint32(int(seed) & 0xFFFFFFFF))
        x = _fmix32(x ^ (lane * np.uint32(0x85EBCA77) + np.uint32(0xC2B2AE3D)))
    unit = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-23) - np.float32(1.0)
    live = (rows > 0) & (rows <= vocab_size)
    return np.where(live[:, None], unit * np.float32(0.5 / dim), np.float32(0.0))


def _softplus(x: np.ndarray) -> np.ndarray:
    return np.maximum(x, 0) + np.log1p(np.exp(-np.abs(x)))


class RefSgns:
    def __init__(self, rows_universe: np.ndarray, hyper: dict, seed: int, vocab_size: int,
                 precision: str = "float32"):
        """``rows_universe``: every table row any later batch may name.
        ``hyper``: dim, eta."""
        self.rows = np.unique(np.asarray(rows_universe).ravel())
        self.precision = precision
        self.eta = np.float32(hyper["eta"])
        self.w0 = init_vectors(seed, self.rows, int(hyper["dim"]), vocab_size)
        self.w = self._r(self.w0)

    def _r(self, x: np.ndarray) -> np.ndarray:
        return _round(np.asarray(x, np.float32), self.precision)

    def index(self, table_rows: np.ndarray) -> np.ndarray:
        """Table rows -> positions in this reference's compact state."""
        pos = np.searchsorted(self.rows, table_rows)
        if not np.array_equal(self.rows[np.minimum(pos, len(self.rows) - 1)], table_rows):
            raise KeyError("a row outside the reference's universe")
        return pos

    def _scores(self, in_at: np.ndarray, out_at: np.ndarray):
        u, v = self.w[in_at], self.w[out_at]  # (n, d), (n, 1 + k, d)
        return u, v, np.sum(u[:, None, :] * v, axis=2, dtype=np.float32)

    def example_loss(self, in_at: np.ndarray, out_at: np.ndarray, block: int = 1 << 15) -> np.ndarray:
        """(n,) float64 negative-sampling loss of every example."""
        out = []
        for i in range(0, len(in_at), block):
            s = self._scores(in_at[i : i + block], out_at[i : i + block])[2].astype(np.float64)
            out.append(np.sum(_softplus(s), axis=1) - s[:, 0])
        return np.concatenate(out)

    def grads(self, in_at: np.ndarray, out_at: np.ndarray):
        """Summed loss of one batch, the positions it touches and the
        gradient pushed to each."""
        u, v, s = self._scores(in_at, out_at)
        err = (1.0 / (1.0 + np.exp(-s.astype(np.float64)))).astype(np.float32)
        err[:, 0] -= np.float32(1.0)
        at = np.concatenate([in_at, out_at.ravel()])
        contrib = np.concatenate([
            np.sum(err[:, :, None] * v, axis=1, dtype=np.float32),
            (err[:, :, None] * u[:, None, :]).reshape(-1, u.shape[1]),
        ])
        order = np.argsort(at, kind="stable")
        touched, starts = np.unique(at[order], return_index=True)
        g = np.add.reduceat(contrib[order], starts, axis=0)
        s64 = s.astype(np.float64)
        return float(np.sum(_softplus(s64)) - np.sum(s64[:, 0])), touched, g.astype(np.float32)

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (input positions (n,),
        output positions (n, 1 + k)) batches. Returns the summed loss."""
        loss, pushes = 0.0, []
        for in_at, out_at in workers:
            l, touched, g = self.grads(in_at, out_at)
            loss += l
            pushes.append((touched, g))
        for touched, g in pushes:
            self.w[touched] = self._r(self.w[touched] - self.eta * self._r(g))
        return loss
