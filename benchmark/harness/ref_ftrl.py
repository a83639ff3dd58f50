"""The plain reference: sparse logistic regression under FTRL-proximal in
NumPy float32, a copy of the arithmetic of ``chip_smoke.numpy_ftrl`` /
``bench.bench_numpy_baseline`` extended by the forward pass and the
gradient. It imports nothing of the program and takes nothing the program
made: rows come from ``criteo.features`` over the raw columns.

State lives over a compact index of the rows a check can touch (the union
of its batches' rows), not over the table: the reference never allocates
2^30 rows.

``precision`` is for the controls only: ``"bfloat16"`` keeps ``z``/``n``
and the pushed gradient in bfloat16, the nearest precision below the
float32 the configurations state. A check that passes such a run is too
loose.
"""

from __future__ import annotations

import numpy as np


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return x.astype(np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


class RefFtrl:
    def __init__(self, rows_universe: np.ndarray, hyper: dict, precision: str = "float32"):
        """``rows_universe``: every table row any later batch may name."""
        self.rows = np.unique(np.asarray(rows_universe).ravel())
        self.z = np.zeros(len(self.rows), np.float32)
        self.n = np.zeros(len(self.rows), np.float32)
        self.alpha = np.float32(hyper["alpha"])
        self.beta = np.float32(hyper["beta"])
        self.l1 = np.float32(hyper["lambda_l1"])
        self.l2 = np.float32(hyper["lambda_l2"])
        self.precision = precision

    def index(self, table_rows: np.ndarray) -> np.ndarray:
        """Table rows -> positions in this reference's compact state."""
        pos = np.searchsorted(self.rows, table_rows)
        if not np.array_equal(self.rows[np.minimum(pos, len(self.rows) - 1)], table_rows):
            raise KeyError("a row outside the reference's universe")
        return pos

    def weights(self, idx=slice(None)) -> np.ndarray:
        z, n = self.z[idx], self.n[idx]
        shrunk = np.sign(z) * np.maximum(np.abs(z) - self.l1, np.float32(0.0))
        return (-shrunk / ((self.beta + np.sqrt(n)) / self.alpha + self.l2)).astype(np.float32)

    def logits(self, idx: np.ndarray, vals: np.ndarray) -> np.ndarray:
        """idx, vals: (B, F). One row of features per example."""
        return (self.weights()[idx] * vals).sum(axis=1, dtype=np.float64).astype(np.float32)

    def predict(self, idx: np.ndarray, vals: np.ndarray) -> np.ndarray:
        return _sigmoid(self.logits(idx, vals))

    def push(self, idx: np.ndarray, g: np.ndarray) -> None:
        """One updater step over the unique positions ``idx``."""
        g = _round(np.asarray(g, np.float32), self.precision)
        n_old = self.n[idx]
        n_new = n_old + g * g
        sigma = (np.sqrt(n_new) - np.sqrt(n_old)) / self.alpha
        z_new = self.z[idx] + g - sigma * self.weights(idx)
        self.z[idx] = _round(z_new, self.precision)
        self.n[idx] = _round(n_new, self.precision)

    def step(self, workers: list[tuple[np.ndarray, np.ndarray, np.ndarray]]) -> float:
        """One parameter-server step: every worker's (idx, vals, labels)
        batch is scored at the same pulled weights, then the pushes land in
        worker order, each its own updater step over its unique rows
        (``push_mode = per_worker``). Returns the summed logloss."""
        w = self.weights()
        loss, pushes = 0.0, []
        for idx, vals, y in workers:
            x = (w[idx] * vals).sum(axis=1, dtype=np.float64).astype(np.float32)
            loss += float(np.sum(np.logaddexp(0.0, x.astype(np.float64)) - y * x))
            err = _sigmoid(x) - y
            g = np.bincount(
                idx.ravel(), weights=(err[:, None] * vals).ravel(), minlength=len(self.z)
            )
            touched = np.unique(idx)
            pushes.append((touched, g[touched]))
        for touched, g in pushes:
            self.push(touched, g)
        return loss


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return (1.0 / (1.0 + np.exp(-x.astype(np.float64)))).astype(np.float32)


def auc(y: np.ndarray, p: np.ndarray) -> float:
    """Area under the ROC curve by ranks, ties at their mean rank."""
    y = np.asarray(y) > 0.5
    n1 = int(y.sum())
    n0 = len(y) - n1
    if n0 == 0 or n1 == 0:
        return float("nan")
    order = np.argsort(p, kind="stable")
    ps = np.asarray(p)[order]
    ranks = np.empty(len(p), np.float64)
    edges = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1], True])
    mean_rank = (edges[:-1] + edges[1:] + 1) / 2.0  # 1-based mean rank per tie run
    ranks[order] = np.repeat(mean_rank, np.diff(edges))
    return float((ranks[y].sum() - n1 * (n1 + 1) / 2.0) / (n0 * n1))


def logloss(y: np.ndarray, p: np.ndarray) -> float:
    p = np.clip(np.asarray(p, np.float64), 1e-12, 1 - 1e-12)
    y = np.asarray(y, np.float64)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
