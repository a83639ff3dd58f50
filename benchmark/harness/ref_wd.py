"""The plain reference for Wide&Deep: NumPy float32, every formula written
out, nothing imported from the program and nothing taken that the program
made. Rows come from ``criteo.features`` over the raw columns.

The model (Cheng et al., arXiv:1606.07792, section 3, as the repo states
it): a wide logit ``sum_j w[row_j] x_j`` over FTRL-proximal weights, plus a
ReLU tower over the mean of the example's embeddings (one 16-wide row per
feature whose value is not 0), one logistic loss over the sum. A
parameter-server step: every worker's batch is scored at the same pulled
rows and the same tower; the table pushes then land in worker order, each
its own updater step over the batch's unique rows (FTRL on ``wide``,
AdaGrad on ``emb``); the tower takes ONE Adam step (bias-corrected, written
out below) on the workers' summed gradient.

State lives over a compact index of the rows a check can touch, not over
the table. The embedding's starting value is a function of (seed, row,
lane) - ``init_embedding``, a copy of the program's arithmetic - so the
reference computes it for the rows of its universe alone.

``precision`` is for the controls only: ``"bfloat16"`` rounds the pushed
gradients, the tables' state, the tower's parameters and the operands of
its matmuls to bfloat16, the nearest precision below the float32 the
configuration states. A check that passes such a run is too loose.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.ref_ftrl import _round, _sigmoid

EMB_INIT_SCALE = 0.05
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
ADAGRAD_EPS = 1e-8


def _fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer over uint32 arrays (arithmetic wraps)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def init_embedding(seed: int, rows: np.ndarray, vdim: int, num_keys: int) -> np.ndarray:
    """(len(rows), vdim) float32: the embedding table's starting rows, from
    (seed, row, lane) alone. Two rounds of a 32-bit mix over the row and the
    lane, the top 24 bits to [-1, 1), times 0.05 x sqrt(3) (a uniform of
    standard deviation 0.05); row 0 and rows at or past ``num_keys`` are 0."""
    rows = np.asarray(rows, np.int64)
    with np.errstate(over="ignore"):
        r = rows.astype(np.uint32)[:, None]
        lane = np.arange(vdim, dtype=np.uint32)[None, :]
        x = _fmix32(r * np.uint32(0x9E3779B1) + np.uint32(int(seed) & 0xFFFFFFFF))
        x = _fmix32(x ^ (lane * np.uint32(0x85EBCA77) + np.uint32(0xC2B2AE3D)))
    unit = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-23) - np.float32(1.0)
    live = (rows > 0) & (rows < num_keys)
    return np.where(live[:, None], unit * np.float32(EMB_INIT_SCALE * 3.0**0.5), np.float32(0.0))


def init_tower(dim: int, hidden: list, seed: int) -> list:
    """[(W, b)] per layer, He-normal from ``default_rng(seed)`` in layer
    order, float32: the draw the app makes on the host."""
    rng = np.random.default_rng(seed)
    sizes = [dim, *hidden, 1]
    return [
        (rng.normal(scale=np.sqrt(2.0 / i), size=(i, o)).astype(np.float32), np.zeros(o, np.float32))
        for i, o in zip(sizes, sizes[1:])
    ]


class RefWd:
    def __init__(self, rows_universe: np.ndarray, hyper: dict, seed: int, num_keys: int,
                 precision: str = "float32"):
        """``rows_universe``: every table row any later batch may name.
        ``hyper``: alpha, beta, lambda_l1, lambda_l2 (wide, FTRL), emb_dim,
        emb_eta (AdaGrad), hidden, mlp_lr (Adam)."""
        self.rows = np.unique(np.asarray(rows_universe).ravel())
        self.precision = precision
        self.alpha, self.beta = np.float32(hyper["alpha"]), np.float32(hyper["beta"])
        self.l1, self.l2 = np.float32(hyper["lambda_l1"]), np.float32(hyper["lambda_l2"])
        self.eta = np.float32(hyper["emb_eta"])
        self.lr = np.float32(hyper["mlp_lr"])
        self.dim = int(hyper["emb_dim"])
        self.z = np.zeros(len(self.rows), np.float32)
        self.n = np.zeros(len(self.rows), np.float32)
        self.emb_w = self._r(init_embedding(seed, self.rows, self.dim, num_keys))
        self.emb_n = np.zeros((len(self.rows), self.dim), np.float32)
        self.tower = [(self._r(w), b) for w, b in init_tower(self.dim, list(hyper["hidden"]), seed)]
        self.adam_m = [(np.zeros_like(w), np.zeros_like(b)) for w, b in self.tower]
        self.adam_v = [(np.zeros_like(w), np.zeros_like(b)) for w, b in self.tower]
        self.adam_t = 0

    def _r(self, x: np.ndarray) -> np.ndarray:
        return _round(np.asarray(x, np.float32), self.precision)

    def _mm(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """A float32 matmul; the control rounds the operands first, as a
        TPU's default precision would."""
        return (self._r(a) @ self._r(b)).astype(np.float32)

    def index(self, table_rows: np.ndarray) -> np.ndarray:
        """Table rows -> positions in this reference's compact state."""
        pos = np.searchsorted(self.rows, table_rows)
        if not np.array_equal(self.rows[np.minimum(pos, len(self.rows) - 1)], table_rows):
            raise KeyError("a row outside the reference's universe")
        return pos

    def wide_weights(self, idx=slice(None)) -> np.ndarray:
        z, n = self.z[idx], self.n[idx]
        shrunk = np.sign(z) * np.maximum(np.abs(z) - self.l1, np.float32(0.0))
        return (-shrunk / ((self.beta + np.sqrt(n)) / self.alpha + self.l2)).astype(np.float32)

    def tower_flat(self) -> np.ndarray:
        """Every parameter of the tower in one vector, layer by layer, W then b."""
        return np.concatenate([x.ravel() for w, b in self.tower for x in (w, b)])

    # -- forward ------------------------------------------------------------
    def forward(self, idx: np.ndarray, vals: np.ndarray):
        """idx, vals: (B, F), one row of features per example. Returns the
        logits (B,) and what the backward pass needs."""
        wide = (self.wide_weights()[idx] * vals).sum(axis=1, dtype=np.float64).astype(np.float32)
        ones = (vals != 0).astype(np.float32)  # (B, F): the features that are there
        cnt = np.maximum(ones.sum(axis=1), np.float32(1.0))
        pooled = ((self.emb_w[idx] * ones[:, :, None]).sum(axis=1) / cnt[:, None]).astype(np.float32)
        hs, pre = [pooled], []
        for w, b in self.tower[:-1]:
            a = self._mm(hs[-1], w) + b
            pre.append(a)
            hs.append(np.maximum(a, np.float32(0.0)))
        w, b = self.tower[-1]
        deep = (self._mm(hs[-1], w) + b)[:, 0]
        return (wide + deep).astype(np.float32), (ones, cnt, hs, pre)

    def predict(self, idx: np.ndarray, vals: np.ndarray, block: int = 16384) -> np.ndarray:
        out = [self.forward(idx[i : i + block], vals[i : i + block])[0] for i in range(0, len(idx), block)]
        return _sigmoid(np.concatenate(out))

    # -- backward, by hand --------------------------------------------------
    def grads(self, idx: np.ndarray, vals: np.ndarray, y: np.ndarray):
        """Summed logloss of one batch and its gradients: with respect to
        the wide weight of every state position (len(rows),), to the
        embedding rows (len(rows), dim), and to the tower [(gW, gb)]."""
        x, (ones, cnt, hs, pre) = self.forward(idx, vals)
        loss = float(np.sum(np.logaddexp(0.0, x.astype(np.float64)) - y * x))
        err = _sigmoid(x) - y  # dloss/dlogit, (B,)
        g_wide = np.bincount(idx.ravel(), weights=(err[:, None] * vals).ravel(), minlength=len(self.z))
        # the last layer, then back through the ReLU layers
        w, _ = self.tower[-1]
        g_tower = [(self._mm(hs[-1].T, err[:, None]), err.sum(keepdims=True).astype(np.float32))]
        dh = self._mm(err[:, None], w.T)
        for (w, _), a, h_in in zip(self.tower[-2::-1], pre[::-1], hs[-2::-1]):
            da = dh * (a > 0)
            g_tower.append((self._mm(h_in.T, da), da.sum(axis=0).astype(np.float32)))
            dh = self._mm(da, w.T)
        g_tower.reverse()
        # dh is dloss/dpooled (B, dim): each present feature's row takes 1/cnt of it
        per_entry = (dh / cnt[:, None])[:, None, :] * ones[:, :, None]  # (B, F, dim)
        flat = idx.ravel()
        g_emb = np.stack(
            [np.bincount(flat, weights=per_entry[:, :, k].ravel(), minlength=len(self.z)) for k in range(self.dim)],
            axis=1,
        )
        return loss, g_wide.astype(np.float32), g_emb.astype(np.float32), g_tower

    # -- the updaters ---------------------------------------------------------
    def push_wide(self, at: np.ndarray, g: np.ndarray) -> None:
        """FTRL-proximal over the unique positions ``at``."""
        g = self._r(g)
        n_old = self.n[at]
        n_new = n_old + g * g
        sigma = (np.sqrt(n_new) - np.sqrt(n_old)) / self.alpha
        self.z[at] = self._r(self.z[at] + g - sigma * self.wide_weights(at))
        self.n[at] = self._r(n_new)

    def push_emb(self, at: np.ndarray, g: np.ndarray) -> None:
        """AdaGrad over the unique positions ``at``: n += g^2, then
        w -= eta g / (sqrt(n) + eps)."""
        g = self._r(g)
        n_new = self.emb_n[at] + g * g
        self.emb_w[at] = self._r(self.emb_w[at] - self.eta * g / (np.sqrt(n_new) + np.float32(ADAGRAD_EPS)))
        self.emb_n[at] = self._r(n_new)

    def adam(self, g_tower: list) -> None:
        """One Adam step on the tower: m = b1 m + (1 - b1) g, v = b2 v +
        (1 - b2) g^2, both divided by 1 - b^t, p -= lr m^ / (sqrt(v^) + eps)."""
        self.adam_t += 1
        c1 = np.float32(1.0) - np.float32(ADAM_B1) ** np.float32(self.adam_t)
        c2 = np.float32(1.0) - np.float32(ADAM_B2) ** np.float32(self.adam_t)
        new_tower, new_m, new_v = [], [], []
        for ps, gs, ms, vs in zip(self.tower, g_tower, self.adam_m, self.adam_v):
            p_out, m_out, v_out = [], [], []
            for p, g, m, v in zip(ps, gs, ms, vs):
                g = self._r(g)
                m = (np.float32(ADAM_B1) * m + np.float32(1.0 - ADAM_B1) * g).astype(np.float32)
                v = (np.float32(ADAM_B2) * v + np.float32(1.0 - ADAM_B2) * g * g).astype(np.float32)
                step = (m / c1) / (np.sqrt(v / c2) + np.float32(ADAM_EPS)) * self.lr
                p_out.append(self._r(p - step))
                m_out.append(m)
                v_out.append(v)
            new_tower.append(tuple(p_out))
            new_m.append(tuple(m_out))
            new_v.append(tuple(v_out))
        self.tower, self.adam_m, self.adam_v = new_tower, new_m, new_v

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (idx, vals, labels)
        batches (``push_mode = per_worker``). Returns the summed logloss."""
        loss, pushes, g_sum = 0.0, [], None
        for idx, vals, y in workers:
            l, g_wide, g_emb, g_tower = self.grads(idx, vals, y)
            loss += l
            touched = np.unique(idx)
            pushes.append((touched, g_wide[touched], g_emb[touched]))
            g_sum = g_tower if g_sum is None else [
                (a[0] + b[0], a[1] + b[1]) for a, b in zip(g_sum, g_tower)
            ]
        for touched, g_wide, g_emb in pushes:
            self.push_wide(touched, g_wide)
            self.push_emb(touched, g_emb)
        self.adam(g_sum)
        return loss
