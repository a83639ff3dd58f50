"""The plain reference of the batch solver: delayed block proximal gradient
for L1 logistic regression with the KKT filter (Li et al., OSDI 2014,
Algorithm 3, at delay 0), in NumPy. It imports nothing of the program and
takes nothing the program made: a block's entries come from
``criteo.features`` over the raw columns (or, in the tests, from the arrays
the data was made as).

The algorithm, as the repository states it. K keys in B equal contiguous
blocks; N examples; ``w`` from zero, ``pred`` = Xw from zero, ``active`` all
true. One block step: p = sigmoid(pred), e = p - y, c = p(1 - p); over the
block's entries (i, j, x): g_j = sum x e_i, h_j = sum x^2 c_i; viol_j =
|g_j + sign(w_j) l1| where w_j != 0, else max(|g_j| - l1, 0); skip_j = not
active_j and w_j = 0; with h' = h + l2 + 1e-6 and z = w h' - eta g: d_j =
sign(z) max(|z| - eta l1, 0) / h' - w_j, zero where skipped; Xd_i = sum x
d_j; alpha = the best of {1, 1/2, ..., 1/128} by the true objective over
the block, 0 if none improves on alpha = 0; w_b += alpha d; pred += alpha
Xd. After a pass, with the filter's threshold r > 0: active_j = viol_j > r
x (the pass's largest violation) or w_j != 0, the violation taken from the
gradient at the pass's end.

State (``w``, ``pred``) is float32, as the configuration states; every sum
(g, h, Xd, the objective) is accumulated in float64 over the float32 data
and rounded once. ``precision`` is for the controls only: ``"bfloat16"``
rounds the summed terms and the sums g, h and Xd to bfloat16, the nearest
precision below. A check that passes such a run is too loose.

A block step may be handed the step scale another solver chose
(``alpha=``): the reference then reports what that choice costs by its own
objective (``regret``, 0 for its own choice) and follows it, so that two
scales whose objectives differ by less than float32 can tell apart do not
send the two sides down different trajectories.
"""

from __future__ import annotations

import numpy as np

ALPHAS = 0.5 ** np.arange(8, dtype=np.float64)  # 1, 1/2, ..., 1/128


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return np.asarray(x).astype(np.float32)
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _sigmoid(x: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-x.astype(np.float64)))


def block_order(seed: int, it: int, n_blocks: int) -> np.ndarray:
    """Pass ``it``'s order of the blocks, shuffled from (seed, it)."""
    return np.random.default_rng([int(seed), int(it)]).permutation(n_blocks)


class RefDarlin:
    def __init__(self, labels: np.ndarray, block_size: int, hyper: dict, precision: str = "float32"):
        self.y = np.asarray(labels, np.float32)
        self.pred = np.zeros(len(self.y), np.float32)
        self.block_size = int(block_size)
        self.l1, self.l2, self.eta = (float(hyper[k]) for k in ("lambda_l1", "lambda_l2", "eta"))
        self.precision = precision
        self.w: dict = {}  # block -> (block_size,) float32, zero where absent
        self.active: dict = {}  # block -> (block_size,) bool, true where absent
        self.viol_max = 0.0  # the largest violation since the last refresh

    def weights(self, b: int) -> np.ndarray:
        return self.w.get(b, np.zeros(self.block_size, np.float32))

    # -- the pieces of a block step: a control overrides one ---------------

    def seen_pred(self) -> np.ndarray:
        """The prediction vector a block's gradient is taken against."""
        return self.pred

    def sums(self, index, terms, n: int) -> np.ndarray:
        """sum of ``terms`` by ``index`` into ``n`` bins: float64
        accumulation, rounded once to the stated precision."""
        return _round(np.bincount(index, weights=_round(terms, self.precision), minlength=n), self.precision)

    def gradient(self, feat, rows, vals) -> tuple:
        p = _sigmoid(self.seen_pred())
        e = (p - self.y).astype(np.float32)
        c = (p * (1.0 - p)).astype(np.float32)
        x = vals.astype(np.float64)
        g = self.sums(feat, x * e[rows], self.block_size)
        h = self.sums(feat, x * x * c[rows], self.block_size)
        return g, h

    def violation(self, w: np.ndarray, g: np.ndarray) -> np.ndarray:
        return np.where(w != 0.0, np.abs(g + np.sign(w) * self.l1), np.maximum(np.abs(g) - self.l1, 0.0))

    def direction(self, w, active, g, h) -> np.ndarray:
        w, g, h = (a.astype(np.float64) for a in (w, g, h))
        h_safe = h + self.l2 + 1e-6
        z = w * h_safe - self.eta * g
        cand = np.sign(z) * np.maximum(np.abs(z) - self.eta * self.l1, 0.0) / h_safe
        return np.where(~active & (w == 0.0), 0.0, cand - w).astype(np.float32)

    def penalty(self, w: np.ndarray) -> float:
        w = w.astype(np.float64)
        return self.l1 * np.abs(w).sum(axis=-1) + 0.5 * self.l2 * (w * w).sum(axis=-1)

    def nll(self, z: np.ndarray) -> np.ndarray:
        z = z.astype(np.float64)
        return (np.logaddexp(0.0, z) - self.y * z).sum(axis=-1)

    def update_pred(self, alpha: float, xd: np.ndarray) -> None:
        self.pred = (self.pred.astype(np.float64) + alpha * xd).astype(np.float32)

    # -- the algorithm -------------------------------------------------------

    def block_step(self, b: int, feat, rows, vals, alpha: float | None = None) -> dict:
        """One block step over block ``b``'s entries (local feature, example,
        value), in any order. Returns the scale taken, the reference's own
        choice, and the regret of the one against the other."""
        w = self.weights(b)
        active = self.active.get(b, np.ones(self.block_size, bool))
        g, h = self.gradient(feat, rows, vals)
        self.viol_max = max(self.viol_max, float(self.violation(w, g).max()))
        d = self.direction(w, active, g, h)
        xd = self.sums(rows, vals.astype(np.float64) * d[feat], len(self.y))
        # the true objective over the block at the eight scales, and at 0:
        # only the examples the block touches move with the scale
        hit = np.flatnonzero(xd)
        y, z0, step = self.y[hit], self.pred[hit].astype(np.float64), xd[hit].astype(np.float64)
        obj0 = self.nll(self.pred) + self.penalty(w)
        rest = obj0 - (np.logaddexp(0.0, z0) - y * z0).sum() - self.penalty(w)
        obj = np.array([
            rest + (np.logaddexp(0.0, z0 + a * step) - y * (z0 + a * step)).sum()
            + self.penalty(w.astype(np.float64) + a * d)
            for a in ALPHAS
        ])
        best = int(np.argmin(obj))
        own = float(ALPHAS[best]) if obj[best] < obj0 else 0.0
        taken = own if alpha is None else float(alpha)
        at = {0.0: obj0, **dict(zip(ALPHAS.tolist(), obj))}
        scale = max(abs(obj0), 1e-12)
        regret = (at.get(taken, np.inf) - min(obj0, obj[best])) / scale
        self.w[b] = (w.astype(np.float64) + taken * d).astype(np.float32)
        self.update_pred(taken, xd)
        return {"alpha": taken, "own_alpha": own, "regret": float(regret), "moved": bool(np.any(d != 0))}

    def refresh(self, b: int, feat, rows, vals, threshold: float) -> None:
        """Block ``b``'s active set taken anew from the gradient at the
        state as it stands."""
        w = self.weights(b)
        g, _ = self.gradient(feat, rows, vals)
        self.active[b] = (w != 0.0) | (self.violation(w, g) > threshold)

    def end_pass(self, entries_of, n_blocks: int, kkt_threshold: float) -> None:
        """The filter after a pass: ``entries_of(b)`` gives a block's
        (feat, rows, vals)."""
        if kkt_threshold > 0:
            thr = kkt_threshold * max(self.viol_max, 1e-12)
            for b in range(n_blocks):
                self.refresh(b, *entries_of(b), thr)
        self.viol_max = 0.0

    def objective(self) -> float:
        return float(self.nll(self.pred) + sum(self.penalty(w) for w in self.w.values()))


def xw(w_of_row, rows: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Xw of examples given as (S, F) table rows and values, ``w_of_row`` a
    table read back: float64 accumulation."""
    return (np.asarray(w_of_row, np.float64)[rows] * vals.astype(np.float64)).sum(axis=1)
