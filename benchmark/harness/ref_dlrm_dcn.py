"""The plain reference for DLRM-DCNv2 on multi-hot click logs: NumPy
float32, every formula written out, dense tables indexed by row, nothing
imported from the program and nothing taken that the program made.

The model (MLPerf Training's recommendation benchmark since v3.0:
``mlcommons/training`` ``recommendation_v2/torchrec_dlrm``; the cross
network is Wang et al., DCN V2, arXiv:2008.13535, sections 3-4, low-rank
form), one example with dense input ``x`` (13 values, ``sign(v) log(1 +
|v|)`` of the integer columns) and categorical values ``c_f`` (26, 32-bit);
``h_f`` the field's bag size, ``R_f`` its rows, ``off_f`` the rows of the
fields before it, D = 27 d:

    z0  = MLP_bot(x)                              ReLU after every layer, the last too
    bag_f = [r, u(f, r, 1) mod R_f, ..., u(f, r, h_f - 1) mod R_f],  r = c_f mod R_f
    p_f = sum over k in bag_f of E[14 + off_f + k]        a row twice in a bag counts twice
    x_0 = [z0; p_1; ...; p_26]                    in R^D
    x_{l+1} = x_0 * ((x_l V_l) W_l + b_l) + x_l   l = 0..L-1; V_l (D, rank), W_l (rank, D); * elementwise
    logit = MLP_top(x_L)                          ReLU after all but the last layer
    loss = log(1 + exp(logit)) - y logit          summed over the minibatch
    AdaGrad on every touched row and every dense parameter:  n += g^2;  w -= eta g / (sqrt(n) + eps)

``u(f, r, j) = splitmix64(splitmix64(splitmix64(seed + f 2^32) ^ r) + j)``
modulo 2^64 (``bag_rows``; f from 0), ``n`` from 0, ``g`` the summed
gradient of one worker's minibatch (a row's, over every bag and example
that read it, applied once: a parameter-server push; the workers' pushes
one after the other) or, for the dense parameters, of all workers'
minibatches at once.

Departures from ``torchrec_dlrm``, each the configuration's ``assumed``: a
hash in the place of its stored ``(R_f, h_f)`` table of uniform draws (the
same law, a fixed uniform bag an id); the id folded by the cap BEFORE the
bag is drawn; ``id mod R_f`` of the logs' 32-bit value with no dictionary
pass; the loss summed, not averaged (AdaGrad's step does not change when
``g`` is scaled, up to ``eps``); one row space for the 26 tables behind
row 0 (the pad) and rows 1..13 (the integer columns', held, never read);
the starting values (``ref_dlrm.init_rows`` for the rows; the dense
parameters from ``default_rng(seed)``: bottom MLP, cross layers, top MLP,
a layer's weights then its bias, normal with variance 2 / (in + out) and
1 / out).

``precision`` is for the controls only, as in ``ref_dlrm``: ``"bfloat16"``
rounds the pushed gradients, the state and the operands of every matrix
product to bfloat16; ``"bfloat16_products"`` the operands of the products
alone (what the chip does to a float32 product not asked for
``precision=highest``).
"""

from __future__ import annotations

import numpy as np

from benchmark.harness.criteo import splitmix64
from benchmark.harness.ref_dlrm import (  # noqa: F401  (parse_tsv, num_rows: the tests' and the app's)
    FIRST_FIELD_ROW, N_CAT, N_INT, PRECISIONS, field_first_rows, init_mlp, init_rows, num_rows, parse_tsv,
)
from benchmark.harness.ref_ftrl import _round, _sigmoid

INIT_BLOCK = 1 << 18  # rows of the table made at a time
POOL_BLOCK = 2048  # examples whose bags' rows are gathered at a time: (2048, 100, 128) float32 is 105 MB


def bag_rows(seed: int, f: int, r: np.ndarray, hot: int, rows: int) -> np.ndarray:
    """(n, hot) int64: the bag that id ``r`` (n,, already ``mod rows``) of
    field ``f`` (from 0) stands for: ``r`` itself, then ``u(f, r, j) mod
    rows`` for j = 1..hot - 1."""
    r = np.asarray(r, np.uint64)
    with np.errstate(over="ignore"):
        salt = splitmix64(np.asarray([(int(seed) + (f << 32)) & (2**64 - 1)], np.uint64))[0]
        of_id = splitmix64(salt ^ r)
        draws = splitmix64(of_id[:, None] + np.arange(1, hot, dtype=np.uint64)[None, :])
    return np.concatenate([r[:, None], draws % np.uint64(rows)], axis=1).astype(np.int64)


def features(ints: np.ndarray, cats: np.ndarray, field_rows, hot, bag_seed: int):
    """(bags, x): ``bags[f]`` (n, h_f) int32 table rows of field f's bag of
    each example, ``x`` (n, 13) float32 the dense input (log1p in float64,
    rounded once, as a text parser computes it)."""
    first = field_first_rows(field_rows)
    bags = []
    for f, (size, h) in enumerate(zip(field_rows, hot)):
        r = cats[:, f].astype(np.int64) % int(size)
        bags.append((first[f] + bag_rows(bag_seed, f, r, int(h), int(size))).astype(np.int32))
    v = ints.astype(np.float64)
    return bags, (np.sign(v) * np.log1p(np.abs(v))).astype(np.float32)


def rows_of(bags: list) -> np.ndarray:
    """The distinct table rows the bags name, ascending."""
    return np.unique(np.concatenate([b.ravel() for b in bags]))


def cut(bags: list, span: slice) -> list:
    return [b[span] for b in bags]


def init_cross(rng: np.random.Generator, width: int, rank: int, layers: int) -> list:
    """[(V, W, b)] a cross layer from ``rng``: V (width, rank) normal with
    variance 2 / (width + rank), then W (rank, width) the same and b normal
    with variance 1 / width, float32."""
    out = []
    for _ in range(layers):
        scale = np.sqrt(2.0 / (width + rank))
        v = rng.normal(scale=scale, size=(width, rank)).astype(np.float32)
        w = rng.normal(scale=scale, size=(rank, width)).astype(np.float32)
        out.append((v, w, rng.normal(scale=np.sqrt(1.0 / width), size=width).astype(np.float32)))
    return out


class RefDcn:
    def __init__(self, hyper: dict, seed: int, field_rows, precision: str = "float32"):
        """``hyper``: emb_dim, bot, top (layer widths), cross_layers,
        cross_rank, eta, eps. The whole table is held, ``w`` and ``n``."""
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        self.eta, self.eps = np.float32(hyper["eta"]), np.float32(hyper["eps"])
        self.dim = int(hyper["emb_dim"])
        self.seed, self.field_rows = int(seed), [int(r) for r in field_rows]
        total = num_rows(self.field_rows)
        self.w = np.empty((total, self.dim), np.float32)
        for at in range(0, total, INIT_BLOCK):
            rows = np.arange(at, min(at + INIT_BLOCK, total))
            self.w[rows] = self._r(init_rows(seed, rows, self.dim, self.field_rows))
        self.n = np.zeros((total, self.dim), np.float32)
        rng = np.random.default_rng(seed)
        width = (1 + N_CAT) * self.dim
        self.bot0 = init_mlp(rng, [N_INT, *hyper["bot"]])
        self.cross0 = init_cross(rng, width, int(hyper["cross_rank"]), int(hyper["cross_layers"]))
        self.top0 = init_mlp(rng, [width, *hyper["top"]])
        self.dense = [self._r(a) for a in self._leaves(self.bot0, self.cross0, self.top0)]
        self.dense_n = [np.zeros_like(a) for a in self.dense]

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

    # -- the dense parameters: one list of arrays, bottom, cross, top -------
    @staticmethod
    def _leaves(bot, cross, top) -> list:
        return [a for layer in (*bot, *cross, *top) for a in layer]

    def _layers(self):
        """(bot [(W, b)], cross [(V, W, b)], top [(W, b)]) of ``self.dense``."""
        leaf = iter(self.dense)
        bot = [(next(leaf), next(leaf)) for _ in self.bot0]
        cross = [(next(leaf), next(leaf), next(leaf)) for _ in self.cross0]
        return bot, cross, [(next(leaf), next(leaf)) for _ in self.top0]

    def dense_flat(self) -> np.ndarray:
        """Every dense parameter in one vector: bottom MLP, cross layers,
        top MLP, layer by layer (W then b; V, W, b)."""
        return np.concatenate([a.ravel() for a in self.dense])

    def dense_n_flat(self) -> np.ndarray:
        return np.concatenate([a.ravel() for a in self.dense_n])

    def dense_flat_start(self) -> np.ndarray:
        return np.concatenate([a.ravel() for a in self._leaves(self.bot0, self.cross0, self.top0)])

    def start_rows(self, rows: np.ndarray) -> np.ndarray:
        """Where table rows ``rows`` started."""
        return init_rows(self.seed, rows, self.dim, self.field_rows)

    # -- forward ----------------------------------------------------------
    def _mlp_forward(self, layers: list, h: np.ndarray, last_relu: bool):
        hs, pre = [h], []
        for k, (w, b) in enumerate(layers):
            a = self._mm(hs[-1], w) + b
            pre.append(a)
            relu = last_relu or k < len(layers) - 1
            hs.append(np.maximum(a, np.float32(0.0)) if relu else a)
        return hs, pre

    def pool(self, bags: list) -> np.ndarray:
        """(B, 26, d): field f's vector, the sum of its bag's rows."""
        n = len(bags[0])
        out = np.empty((n, N_CAT, self.dim), np.float32)
        for f, b in enumerate(bags):
            for at in range(0, n, POOL_BLOCK):
                got = self.w[b[at : at + POOL_BLOCK]]  # (block, h_f, d)
                out[at : at + POOL_BLOCK, f] = got.sum(axis=1, dtype=np.float64)
        return out

    def forward(self, bags: list, x: np.ndarray):
        bot, cross, top = self._layers()
        bot_h, bot_pre = self._mlp_forward(bot, x, last_relu=True)
        x0 = np.concatenate([bot_h[-1][:, None, :], self.pool(bags)], axis=1).reshape(len(x), -1)
        xs, lows, ys = [x0], [], []
        for v, w, b in cross:
            lows.append(self._mm(xs[-1], v))
            ys.append(self._mm(lows[-1], w) + b)
            xs.append(x0 * ys[-1] + xs[-1])
        top_h, top_pre = self._mlp_forward(top, xs[-1], last_relu=False)
        return top_h[-1][:, 0], (bot_h, bot_pre, xs, lows, ys, top_h, top_pre)

    def predict(self, bags: list, x: np.ndarray, block: int = 8192) -> np.ndarray:
        out = [
            self.forward(cut(bags, slice(i, i + block)), x[i : i + block])[0] for i in range(0, len(x), block)
        ]
        return _sigmoid(np.concatenate(out))

    # -- backward, by hand --------------------------------------------------
    def _mlp_backward(self, layers: list, hs: list, pre: list, d_out: np.ndarray, last_relu: bool):
        grads, dh = [], d_out
        for k in range(len(layers) - 1, -1, -1):
            relu = last_relu or k < len(layers) - 1
            da = dh * (pre[k] > 0) if relu else dh
            grads.append((self._mm(hs[k].T, da), da.sum(axis=0, dtype=np.float64).astype(np.float32)))
            dh = self._mm(da, layers[k][0].T)
        grads.reverse()
        return grads, dh

    def grads(self, bags: list, x: np.ndarray, y: np.ndarray):
        """Summed logloss of one batch and its gradients: by the rows, as
        (touched rows ascending, (len, d) summed over the batch), and by
        the dense parameters in ``self.dense``'s order."""
        bot, cross, top = self._layers()
        logits, (bot_h, bot_pre, xs, lows, ys, top_h, top_pre) = self.forward(bags, x)
        loss = float(np.sum(np.logaddexp(0.0, logits.astype(np.float64)) - y * logits))
        err = (_sigmoid(logits) - y).astype(np.float32)
        g_top, dx = self._mlp_backward(top, top_h, top_pre, err[:, None], last_relu=False)
        x0 = xs[0]
        dx0 = np.zeros_like(x0)
        g_cross = []
        for k in range(len(cross) - 1, -1, -1):  # x_{k+1} = x0 * y_k + x_k,  y_k = (x_k V) W + b
            v, w, _ = cross[k]
            dy = dx * x0
            dx0 += dx * ys[k]
            dlow = self._mm(dy, w.T)
            g_cross.append((
                self._mm(xs[k].T, dlow), self._mm(lows[k].T, dy),
                dy.sum(axis=0, dtype=np.float64).astype(np.float32),
            ))
            dx = dx + self._mm(dlow, v.T)
        g_cross.reverse()
        dx0 += dx  # x_0 is the first layer's input too
        dvec = dx0.reshape(len(x), 1 + N_CAT, self.dim)
        g_bot, _ = self._mlp_backward(bot, bot_h, bot_pre, dvec[:, 0], last_relu=True)
        # a row's gradient: the sum over every entry of every bag that reads it
        touched, g_rows = [], []
        for f, b in enumerate(bags):
            flat = b.ravel()
            order = np.argsort(flat, kind="stable")
            rows, starts = np.unique(flat[order], return_index=True)
            of_entry = dvec[:, 1 + f][order // b.shape[1]]  # the entry's example's dp_f
            touched.append(rows)
            g_rows.append(np.add.reduceat(of_entry, starts, axis=0, dtype=np.float64).astype(np.float32))
        return loss, (np.concatenate(touched), np.concatenate(g_rows)), self._leaves(g_bot, g_cross, g_top)

    # -- the updates ----------------------------------------------------------
    def _adagrad(self, w: np.ndarray, n: np.ndarray, g: np.ndarray):
        g = self._r(g)
        n = self._r(n + g * g)
        return self._r(w - self.eta * g / (np.sqrt(n) + self.eps)), n

    def push(self, at: np.ndarray, g: np.ndarray) -> None:
        """AdaGrad over the distinct rows ``at``."""
        self.w[at], self.n[at] = self._adagrad(self.w[at], self.n[at], g)

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (bags, x, labels)
        batches (``push_mode = per_worker``): every worker's gradient at
        the same state, the pushes one after the other, the dense
        parameters stepped once on the summed gradient. Returns the summed
        logloss."""
        loss, pushes, total = 0.0, [], None
        for bags, x, y in workers:
            l, push, g_dense = self.grads(bags, x, y)
            loss += l
            pushes.append(push)
            total = g_dense if total is None else [a + b for a, b in zip(total, g_dense)]
        for at, g in pushes:
            self.push(at, g)
        stepped = [self._adagrad(w, n, g) for w, n, g in zip(self.dense, self.dense_n, total)]
        self.dense, self.dense_n = [s[0] for s in stepped], [s[1] for s in stepped]
        return loss
