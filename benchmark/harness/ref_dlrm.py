"""The plain reference for DLRM: NumPy float32, every formula written out,
nothing imported from the program and nothing taken that the program made.

The model (Naumov et al., arXiv:1906.00091, sections 2-3; the MLPerf
recommendation benchmark's sizes), one example with dense input ``x`` (13
values, ``log(1 + v)`` of the integer columns) and categorical values
``c_f`` (26 of them, 32-bit):

    z0 = MLP_bot(x)                       ReLU after every layer, the last too
    e_f = E[14 + off_f + c_f mod R_f]     one row a field; off_f = R_1 + ... + R_(f-1)
    T = [z0; e_1; ...; e_26]              (27, d)
    Z = T T^t;  p = Z[i, j] for i > j     351 values, row by row
    logit = MLP_top([z0; p])              ReLU after all but the last layer
    loss = log(1 + exp(logit)) - y logit  summed over the minibatch

and plain SGD, ``w -= eta g``: a parameter-server step scores every
worker's batch at the same rows and the same MLPs, pushes each worker's
summed row gradients (one update a touched row and worker), and steps the
MLPs ONCE on the workers' summed gradient. The row layout is this
module's own statement of the per-field layout: row 0 the pad, rows 1..13
the integer columns' (held, never read), the 26 tables one behind the
other from row 14.

State lives over a compact index of the rows a check can touch, not over
the table. A row's starting value is a function of (seed, row, lane) -
``init_rows``, a copy of the program's arithmetic - so the reference
computes it for the rows of its universe alone; the MLPs start from
``default_rng(seed)``, bottom first, W then b a layer.

``precision`` is for the controls only. ``"bfloat16"`` rounds the pushed
gradients, the rows, the MLPs' parameters and the operands of every matrix
product to bfloat16, the nearest precision below the float32 the
configuration states. ``"bfloat16_products"`` rounds the operands of the
matrix products alone (the MLPs' and ``T T^t``) and sums in float32: what
the chip does to a float32 product that is not asked for
``precision=highest``. A check that passes either run is too loose.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.ref_ftrl import _round, _sigmoid
from benchmark.harness.ref_wd import _fmix32  # murmur3's 32-bit finalizer: the references' own copy

N_INT, N_CAT = 13, 26
FIRST_FIELD_ROW = 1 + N_INT
PRECISIONS = ("float32", "bfloat16", "bfloat16_products")


def field_first_rows(field_rows) -> np.ndarray:
    """(26,) the table row of each field's value 0."""
    sizes = np.asarray(field_rows, np.int64)
    return FIRST_FIELD_ROW + np.concatenate([[0], np.cumsum(sizes)[:-1]])


def num_rows(field_rows) -> int:
    return FIRST_FIELD_ROW + int(np.sum(np.asarray(field_rows, np.int64)))


def features(ints: np.ndarray, cats: np.ndarray, field_rows):
    """(rows (n, 26) int64, x (n, 13) float32) of raw columns ``ints``
    (n, 13) and ``cats`` (n, 26, the logs' 32-bit values): field f's row is
    ``14 + off_f + c mod R_f``; the dense input is log1p in float64,
    rounded once, as a text parser computes it."""
    sizes = np.asarray(field_rows, np.int64)
    rows = field_first_rows(sizes)[None, :] + cats.astype(np.int64) % sizes[None, :]
    v = ints.astype(np.float64)
    x = (np.sign(v) * np.log1p(np.abs(v))).astype(np.float32)
    return rows, x


def parse_tsv(path: str):
    """(labels f32 (n,), ints i64 (n, 13), cats i64 (n, 26)) of a criteo
    TSV file in which every field is present: this module's own reading of
    the lines, for the tests that hand the program a file."""
    labels, ints, cats = [], [], []
    with open(path) as f:
        for line in f:
            cols = line.rstrip("\n").split("\t")
            labels.append(1.0 if cols[0] == "1" else 0.0)
            ints.append([int(c) for c in cols[1 : 1 + N_INT]])
            cats.append([int(c, 16) for c in cols[1 + N_INT : 1 + N_INT + N_CAT]])
    return np.asarray(labels, np.float32), np.asarray(ints, np.int64), np.asarray(cats, np.int64)


def init_rows(seed: int, rows: np.ndarray, dim: int, field_rows) -> np.ndarray:
    """(len(rows), dim) float32: the table's starting rows, from (seed,
    row, lane) alone. Two rounds of a 32-bit mix over the row and the lane,
    the top 24 bits to [-1, 1), times the field's bound sqrt(1 / R_f) in
    float32 (field f's rows uniform in +-sqrt(1 / R_f)); rows 0..13 and
    rows past the last table are 0."""
    rows = np.asarray(rows, np.int64)
    with np.errstate(over="ignore"):
        r = rows.astype(np.uint32)[:, None]
        lane = np.arange(dim, dtype=np.uint32)[None, :]
        x = _fmix32(r * np.uint32(0x9E3779B1) + np.uint32(int(seed) & 0xFFFFFFFF))
        x = _fmix32(x ^ (lane * np.uint32(0x85EBCA77) + np.uint32(0xC2B2AE3D)))
    unit = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-23) - np.float32(1.0)
    sizes = np.asarray(field_rows, np.int64)
    first = field_first_rows(sizes)
    field = np.searchsorted(first, rows, side="right") - 1  # -1: rows 0..13
    live = (field >= 0) & (rows < num_rows(sizes))
    bound = np.where(live, np.sqrt(1.0 / sizes[np.maximum(field, 0)]), 0.0).astype(np.float32)
    return unit * bound[:, None]


def init_mlp(rng: np.random.Generator, sizes: list) -> list:
    """[(W, b)] a layer from ``rng`` in layer order, W then b: W normal with
    variance 2 / (in + out), b normal with variance 1 / out, float32."""
    out = []
    for i, o in zip(sizes, sizes[1:]):
        w = rng.normal(scale=np.sqrt(2.0 / (i + o)), size=(i, o)).astype(np.float32)
        out.append((w, rng.normal(scale=np.sqrt(1.0 / o), size=o).astype(np.float32)))
    return out


_LI, _LJ = np.tril_indices(1 + N_CAT, -1)  # the pairs i > j, row by row


class RefDlrm:
    def __init__(self, rows_universe: np.ndarray, hyper: dict, seed: int, field_rows,
                 precision: str = "float32"):
        """``rows_universe``: every table row any later batch may name.
        ``hyper``: emb_dim, bot, top (layer widths), eta."""
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.rows = np.unique(np.asarray(rows_universe).ravel())
        self.precision = precision
        self.eta = np.float32(hyper["eta"])
        self.dim = int(hyper["emb_dim"])
        self.field_rows = [int(r) for r in field_rows]
        self.w0 = init_rows(seed, self.rows, self.dim, self.field_rows)
        self.w = self._r(self.w0).copy()  # updated in place: not the start's array
        rng = np.random.default_rng(seed)
        pairs = (1 + N_CAT) * N_CAT // 2
        self.bot0 = init_mlp(rng, [N_INT, *hyper["bot"]])
        self.top0 = init_mlp(rng, [self.dim + pairs, *hyper["top"]])
        self.bot = [(self._r(w), self._r(b)) for w, b in self.bot0]
        self.top = [(self._r(w), self._r(b)) for w, b in self.top0]

    # -- precision --------------------------------------------------------
    def _r(self, x: np.ndarray) -> np.ndarray:
        """State and pushed gradients: rounded under "bfloat16" alone."""
        x = np.asarray(x, np.float32)
        return _round(x, "bfloat16") if self.precision == "bfloat16" else x

    def _op(self, x: np.ndarray) -> np.ndarray:
        """An operand of a matrix product: rounded under both controls."""
        x = np.asarray(x, np.float32)
        return x if self.precision == "float32" else _round(x, "bfloat16")

    def _mm(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        return np.matmul(self._op(a), self._op(b)).astype(np.float32)

    # -- state ------------------------------------------------------------
    def index(self, table_rows: np.ndarray) -> np.ndarray:
        """Table rows -> positions in this reference's compact state."""
        pos = np.searchsorted(self.rows, table_rows)
        if not np.array_equal(self.rows[np.minimum(pos, len(self.rows) - 1)], table_rows):
            raise KeyError("a row outside the reference's universe")
        return pos

    @staticmethod
    def _flat(*mlps) -> np.ndarray:
        return np.concatenate([x.ravel() for layers in mlps for w, b in layers for x in (w, b)])

    def mlp_flat(self) -> np.ndarray:
        """Every dense parameter in one vector: bottom then top, layer by
        layer, W then b."""
        return self._flat(self.bot, self.top)

    def mlp_flat_start(self) -> np.ndarray:
        return self._flat(self.bot0, self.top0)

    # -- forward ----------------------------------------------------------
    def _mlp_forward(self, layers: list, h: np.ndarray, last_relu: bool):
        """The layers' inputs and pre-activations, and the output."""
        hs, pre = [h], []
        for k, (w, b) in enumerate(layers):
            a = self._mm(hs[-1], w) + b
            pre.append(a)
            relu = last_relu or k < len(layers) - 1
            hs.append(np.maximum(a, np.float32(0.0)) if relu else a)
        return hs, pre

    def forward(self, idx: np.ndarray, x: np.ndarray):
        """idx (B, 26) state positions, x (B, 13). The logits (B,) and what
        the backward pass needs."""
        bot_h, bot_pre = self._mlp_forward(self.bot, x, last_relu=True)
        z0 = bot_h[-1]
        t = np.concatenate([z0[:, None, :], self.w[idx]], axis=1)  # (B, 27, d)
        z = self._mm(t, t.transpose(0, 2, 1))  # (B, 27, 27)
        r = np.concatenate([z0, z[:, _LI, _LJ]], axis=1)
        top_h, top_pre = self._mlp_forward(self.top, r, last_relu=False)
        return top_h[-1][:, 0], (bot_h, bot_pre, t, top_h, top_pre)

    def predict(self, idx: np.ndarray, x: np.ndarray, block: int = 8192) -> np.ndarray:
        out = [self.forward(idx[i : i + block], x[i : i + block])[0] for i in range(0, len(idx), block)]
        return _sigmoid(np.concatenate(out))

    # -- backward, by hand --------------------------------------------------
    def _mlp_backward(self, layers: list, hs: list, pre: list, d_out: np.ndarray, last_relu: bool):
        """d_out: the loss's gradient by the MLP's output. Returns the
        layers' [(gW, gb)] and the gradient by the MLP's input."""
        grads, dh = [], d_out
        for k in range(len(layers) - 1, -1, -1):
            relu = last_relu or k < len(layers) - 1
            da = dh * (pre[k] > 0) if relu else dh
            grads.append((self._mm(hs[k].T, da), da.sum(axis=0, dtype=np.float64).astype(np.float32)))
            dh = self._mm(da, layers[k][0].T)
        grads.reverse()
        return grads, dh

    def grads(self, idx: np.ndarray, x: np.ndarray, y: np.ndarray):
        """Summed logloss of one batch and its gradients: by the rows, as
        (touched positions, (len, d) summed over the batch), and by the two
        MLPs [(gW, gb)]."""
        logits, (bot_h, bot_pre, t, top_h, top_pre) = self.forward(idx, x)
        loss = float(np.sum(np.logaddexp(0.0, logits.astype(np.float64)) - y * logits))
        err = (_sigmoid(logits) - y).astype(np.float32)
        g_top, dr = self._mlp_backward(self.top, top_h, top_pre, err[:, None], last_relu=False)
        dz = np.zeros((len(idx), 1 + N_CAT, 1 + N_CAT), np.float32)
        dz[:, _LI, _LJ] = dr[:, self.dim :]
        dt = self._mm(dz + dz.transpose(0, 2, 1), t)  # Z = T T^t: dT = (dZ + dZ^t) T
        g_bot, _ = self._mlp_backward(self.bot, bot_h, bot_pre, dr[:, : self.dim] + dt[:, 0], last_relu=True)
        # a row's gradient: the sum over the batch's entries that read it
        flat, de = idx.ravel(), dt[:, 1:].reshape(-1, self.dim)
        order = np.argsort(flat, kind="stable")
        touched, starts = np.unique(flat[order], return_index=True)
        g_rows = np.add.reduceat(de[order].astype(np.float64), starts, axis=0).astype(np.float32)
        return loss, (touched, g_rows), g_bot, g_top

    # -- the updates ----------------------------------------------------------
    def push(self, at: np.ndarray, g: np.ndarray) -> None:
        """Plain SGD over the unique positions ``at``."""
        self.w[at] = self._r(self.w[at] - self.eta * self._r(g))

    def sgd(self, layers: list, grads: list) -> list:
        return [
            (self._r(w - self.eta * self._r(gw)), self._r(b - self.eta * self._r(gb)))
            for (w, b), (gw, gb) in zip(layers, grads)
        ]

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (idx, x, labels)
        batches (``push_mode = per_worker``). Returns the summed logloss."""
        loss, pushes, sum_bot, sum_top = 0.0, [], None, None
        for idx, x, y in workers:
            l, push, g_bot, g_top = self.grads(idx, x, y)
            loss += l
            pushes.append(push)
            add = lambda a, b: b if a is None else [(p[0] + q[0], p[1] + q[1]) for p, q in zip(a, b)]  # noqa: E731
            sum_bot, sum_top = add(sum_bot, g_bot), add(sum_top, g_top)
        for at, g in pushes:
            self.push(at, g)
        self.bot, self.top = self.sgd(self.bot, sum_bot), self.sgd(self.top, sum_top)
        return loss
