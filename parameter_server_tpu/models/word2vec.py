"""word2vec skip-gram with negative sampling (SGNS) over the KV store.

Reference analog: BASELINE.json's parity config "word2vec skip-gram
negative-sampling (1B-word corpus, bounded-staleness SSP)" - the classic
parameter-server workload: two huge embedding matrices (input and output
vectors), each step touching only the batch's words, pushed with bounded
staleness. The objective is eq. 4 of Mikolov et al., arXiv:1310.4546.

TPU re-expression: ONE table ``sgns`` of ``vdim = dim`` over one key space,
as the reference's KV layer has one: row 0 is the pad, word w's input
vector (word2vec's ``syn0``) is row 1 + w, its output vector (``syn1neg``)
row 1 + V + w. An example is the 2 + k entries of one ``sgns`` line
(``data.libsvm``): the centre c, the context o_0 and the negatives
o_1..o_k, whose ROLE IS THEIR POSITION. With u the centre's input vector
and v_j the output vector of o_j:

    s_j = u . v_j,   y_0 = 1,  y_j = 0 for j > 0
    loss = sum_j [softplus(s_j) - y_j s_j]          (eq. 4, negated)
    err_j = sigmoid(s_j) - y_j
    dL/du = sum_j err_j v_j,   dL/dv_j = err_j u

The loss is the batch's SUM over its unmasked examples; a row's gradient
is summed over its repeats in the batch and applied once by the table's
updater (plain SGD: w <- w - eta g; word2vec.c applies them pair by pair).
No L2. Input vectors start uniform in [-0.5/dim, 0.5/dim), output vectors
and the pad at zero, as word2vec.c starts them.

This module holds the model's description (``sgns_app``) and the
data-layer tools that turn a corpus of word ids into ``sgns`` example
files (``PairStream``, ``NegativeSampler``, ``write_examples``); the step
is ``parallel.spmd``'s and the training loop ``PodTrainer``'s."""

from __future__ import annotations

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.data.libsvm import SGNS
from parameter_server_tpu.kv.store import hashed_unit, live_lanes
from parameter_server_tpu.kv.updaters import Sgd, Updater
from parameter_server_tpu.models.metrics import SGNS_SCORES
from parameter_server_tpu.parallel.spmd import StepApp, Table

TABLE = "sgns"  # the table's name: state entry "sgns.w", scopes "ps.pull/sgns"


def num_keys_of(vocab_size: int) -> int:
    """Rows of the one key space: the pad row, V input and V output vectors."""
    return 1 + 2 * vocab_size


def _scores(pulled, b, k: int):
    """(slots (B, 2+k), u (B, d), v (B, 1+k, d), s (B, 1+k)) of every
    example: the local ids of its 2 + k entries in the order the ``sgns``
    format writes them (read off ``row_splits``: every example has exactly
    2 + k), the centre's pulled input vector, the pulled output vectors of
    the context and the negatives, and their 1 + k inner products, summed
    in float32 on the vector unit. A padded example's entries are whatever
    lies at its split: its error is masked to zero."""
    w = pulled[TABLE]
    at = b["row_splits"][:-1, None] + jnp.arange(2 + k, dtype=jnp.int32)[None, :]
    slots = jnp.take(b["local_ids"], at, mode="clip")
    u = jnp.take(w, slots[:, 0], axis=0)
    v = jnp.take(w, slots[:, 1:], axis=0)
    return slots, u, v, jnp.sum(u[:, None, :] * v, axis=2)


def _example_loss(s: jax.Array) -> jax.Array:
    """(B,) negative-sampling loss of scores (B, 1+k), the context's first."""
    return jnp.sum(jax.nn.softplus(s), axis=1) - s[:, 0]


def _logits(k: int):
    def logits(pulled, dense, b, row_ids) -> jax.Array:
        """An example's log-likelihood under the objective -> (B,)."""
        return -_example_loss(_scores(pulled, b, k)[3])

    return logits


def _grad(k: int):
    def grad(pulled, dense, b, row_ids):
        """Summed negative-sampling loss of the batch's examples and its
        gradient on the pulled rows, summed over a key's repeats in the
        batch (a frequent word's output vector is one row of the push
        however many examples drew it)."""
        slots, u, v, s = _scores(pulled, b, k)
        mask = b["example_mask"].astype(s.dtype)
        y = jnp.zeros_like(s).at[:, 0].set(1.0)
        err = (jax.nn.sigmoid(s) - y) * mask[:, None]
        loss_e = _example_loss(s)
        g_u = jnp.sum(err[:, :, None] * v, axis=1)  # (B, d)
        g_v = err[:, :, None] * u[:, None, :]  # (B, 1+k, d)
        g = jax.ops.segment_sum(
            jnp.concatenate([g_u, g_v.reshape(-1, u.shape[1])]),
            jnp.concatenate([slots[:, 0], slots[:, 1:].reshape(-1)]),
            num_segments=pulled[TABLE].shape[0],
        )
        return jnp.sum(loss_e * mask), -loss_e, {TABLE: g}, None

    return grad


def init_vectors(
    seed: int, rows: jax.Array, dim: int, vocab_size: int, lanes: int | None = None
) -> jax.Array:
    """Starting vectors of table rows ``rows``: the input vectors (rows
    1..V) uniform in [-0.5/dim, 0.5/dim) as a hash of (seed, row, lane)
    (``kv.store.hashed_unit`` times half the width: one rounding); the pad
    row, the output vectors and the rows past them zero. ``lanes`` (the
    slot's stride, ``dim`` unsaid) is the width made, zero past ``dim``
    (``kv.store.live_lanes``)."""
    keep = live_lanes((rows > 0) & (rows <= vocab_size), dim, lanes)
    return jnp.where(keep, hashed_unit(seed, rows, lanes or dim) * jnp.float32(0.5 / dim), 0.0)


def sgns_app(updater: Updater, dim: int, negatives: int, init=None) -> StepApp:
    """The app's description for the shared parameter-server step: table
    ``sgns`` (``vdim`` ``dim``) under ``updater``, the negative-sampling
    loss over examples of 2 + ``negatives`` entries, an example's
    log-likelihood as its prediction (the identity as link), the mean loss
    as the evaluator's score. ``init(rows, lanes)`` makes the table's
    starting ``{"w": ...}`` as the store keeps it, ``lanes`` wide (zeros
    without it: no gradient ever)."""
    return StepApp(
        tables=(Table(TABLE, updater, dim, init),),
        grad=_grad(negatives),
        logits=_logits(negatives),
        link=lambda x: x,
        score=SGNS_SCORES,
    )


def app_from_config(cfg) -> StepApp:
    """The description from a PSConfig's [w2v] section (ref: App::Create on
    the SGNS config): dim, negatives, eta under plain SGD (word2vec keeps
    no optimizer state); the vectors start as ``init_vectors`` of
    ``cfg.seed``, made on the device. The files are ``sgns`` lines and the
    key space is the pad row and the two matrices (``pod_config`` sets
    both)."""
    w = cfg.w2v
    want = num_keys_of(w.vocab_size)
    if cfg.data.format != SGNS or cfg.data.num_keys != want:
        raise ValueError(
            f"app word2vec reads data.format {SGNS!r} into data.num_keys = "
            f"1 + 2 x w2v.vocab_size = {want} rows; the config says "
            f"{cfg.data.format!r} and {cfg.data.num_keys} "
            "(models.word2vec.pod_config fills both in)"
        )
    return sgns_app(
        Sgd(eta=w.eta), w.dim, w.negatives,
        init=lambda rows, lanes: {"w": init_vectors(
            cfg.seed, jnp.arange(rows, dtype=jnp.int32), w.dim, w.vocab_size, lanes
        )},
    )


def pod_config(cfg):
    """A copy of ``cfg`` with [w2v]'s settings where the shared loop reads
    them: ``sgns`` files, the key space's size, 2 + negatives entries an
    example, ``w2v.batch_size`` examples a minibatch."""
    cfg = copy.deepcopy(cfg)
    cfg.app = "word2vec"
    cfg.data.format = SGNS
    cfg.data.num_keys = num_keys_of(cfg.w2v.vocab_size)
    cfg.data.max_nnz_per_example = 2 + cfg.w2v.negatives
    cfg.solver.minibatch = cfg.w2v.batch_size
    return cfg


def write_examples(path, centres, contexts, negatives, append: bool = False) -> None:
    """``centre context neg_1 ... neg_k`` lines, one an example (ids from 0)."""
    rows = np.column_stack([centres, contexts, negatives])
    with open(path, "a" if append else "w") as f:
        np.savetxt(f, rows, fmt="%d")


def vectors(trainer) -> tuple[np.ndarray, np.ndarray]:
    """(input vectors (V, dim), output vectors (V, dim)) of a trainer's
    table, by word id."""
    w = trainer.full_weights(TABLE)
    v = trainer.cfg.w2v.vocab_size
    return w[1 : 1 + v], w[1 + v : 1 + 2 * v]


class NegativeSampler:
    """unigram^0.75 sampler (word2vec's standard trick): inverse-CDF via
    searchsorted — O(log V) per draw, no per-call table rebuild (rng.choice
    with p re-normalizes the whole distribution every call)."""

    def __init__(self, counts: np.ndarray, power: float = 0.75, seed: int = 0):
        p = np.asarray(counts, dtype=np.float64) ** power
        self.p = p / p.sum()
        self._cdf = np.cumsum(self.p)
        self._cdf[-1] = 1.0
        self.rng = np.random.default_rng(seed)

    def sample(self, shape) -> np.ndarray:
        u = self.rng.random(size=shape)
        return np.searchsorted(self._cdf, u, side="right")


# ---------------------------------------------------------------------------
# Streaming corpus path (BASELINE's "1B-word corpus" spec): skip-gram pairs
# are NEVER materialized for the whole corpus. Token files flow through a
# WorkloadPool (the reference's file-shard assignment), each worker stream
# reads blocks of tokens, windows them into pairs, block-shuffles, and
# emits fixed-size batches — host memory is bounded by one block's pairs
# (~ 2 * window * block_tokens), independent of corpus size.
# ---------------------------------------------------------------------------


def _window_pairs(
    tokens: np.ndarray, window: int, skip_prefix: int = 0,
    reach: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(center, context) pairs within ``window``; with skip_prefix = W,
    pairs whose LATER token falls inside the first W tokens are dropped —
    the cross-block carry trick: prepend the previous block's last W
    tokens, and boundary-crossing pairs appear exactly once. ``reach``
    (word2vec's dynamic window): token i, as a centre, keeps only the
    contexts within ``reach[i]`` <= ``window`` of it."""
    cs, xs = [], []
    for off in range(1, window + 1):
        a, b = tokens[:-off], tokens[off:]  # pair i: (i, i + off)
        lo = max(0, skip_prefix - off)  # keep i + off >= skip_prefix
        fwd = bwd = slice(lo, None)
        if reach is not None:
            fwd = lo + np.flatnonzero(reach[lo : len(a)] >= off)
            bwd = lo + np.flatnonzero(reach[off + lo :] >= off)
        cs.append(a[fwd])
        xs.append(b[fwd])
        cs.append(b[bwd])
        xs.append(a[bwd])
    if not cs:
        z = np.zeros(0, dtype=tokens.dtype)
        return z, z
    return np.concatenate(cs), np.concatenate(xs)


def iter_token_blocks(path: str, block_tokens: int = 1 << 20):
    """Stream int token-id blocks from a corpus file: ``.npy`` arrays are
    mmap'd and sliced; anything else is whitespace-separated integer text
    read in bounded chunks (partial tokens carried across chunk reads)."""
    if str(path).endswith(".npy"):
        arr = np.load(path, mmap_mode="r")
        for lo in range(0, len(arr), block_tokens):
            yield np.asarray(arr[lo : lo + block_tokens], dtype=np.int64)
        return
    carry = b""
    pending: list[np.ndarray] = []
    n_pending = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 22)
            if not chunk:
                break
            chunk = carry + chunk
            cut = max(chunk.rfind(b" "), chunk.rfind(b"\n"), chunk.rfind(b"\t"))
            if cut < 0:
                carry = chunk
                continue
            carry = chunk[cut + 1 :]
            toks = chunk[:cut].split()
            if toks:
                pending.append(np.array(toks, dtype=np.int64))
                n_pending += len(pending[-1])
            if n_pending >= block_tokens:
                # concatenate ONCE per read chunk and yield fixed-offset
                # slices (re-concatenating the tail per block would memcpy
                # the remainder O(blocks) times)
                flat = np.concatenate(pending)
                usable = len(flat) // block_tokens * block_tokens
                for off in range(0, usable, block_tokens):
                    yield flat[off : off + block_tokens]
                rest = flat[usable:]
                pending, n_pending = ([rest], len(rest)) if len(rest) else ([], 0)
    if carry.strip():
        pending.append(np.array([int(carry)], dtype=np.int64))
        n_pending += 1
    if n_pending:
        yield np.concatenate(pending)


def count_vocab(
    files: list[str], vocab_size: int, block_tokens: int = 1 << 20
) -> np.ndarray:
    """Streaming unigram counts over corpus files (the sampler's input)."""
    counts = np.zeros(vocab_size, dtype=np.int64)
    for f in files:
        for block in iter_token_blocks(str(f), block_tokens):
            if len(block) and (block.min() < 0 or block.max() >= vocab_size):
                bad = block[(block < 0) | (block >= vocab_size)][0]
                raise ValueError(
                    f"corpus file {f!r} has token id {int(bad)} outside "
                    f"[0, vocab_size={vocab_size})"
                )
            counts += np.bincount(block, minlength=vocab_size)
    return counts


class PairStream:
    """One worker's streaming pair source: drains corpus files from the
    pool, windows token blocks into block-shuffled (center, context) pair
    batches with negatives. Compatible with data.pipeline.PrefetchPipeline
    (``next_batch`` / ``_empty``)."""

    def __init__(
        self,
        worker_id: int,
        pool,  # WorkloadPool of corpus file paths
        *,
        window: int,
        batch_size: int,
        num_negatives: int,
        sampler: NegativeSampler,
        block_tokens: int = 1 << 20,
        seed: int = 0,
        dynamic_window: bool = False,
    ):
        self.dynamic = dynamic_window  # a centre's reach uniform in 1..window
        self.worker_id = worker_id
        self.pool = pool
        self.window = window
        self.batch_size = batch_size
        self.K = num_negatives
        self.sampler = sampler
        self.block_tokens = block_tokens
        self.rng = np.random.default_rng(seed * 100003 + worker_id * 7919)
        self._blocks = None  # token-block iterator of the current file
        self._current: str | None = None
        self._tail: np.ndarray | None = None  # last W tokens of prev block
        self._tail_reach: np.ndarray | None = None  # and their reaches
        self._buf_c = np.zeros(0, dtype=np.int64)
        self._buf_x = np.zeros(0, dtype=np.int64)
        self.max_buffered = 0  # observability: peak pairs held

    def _next_block(self) -> np.ndarray | None:
        while True:
            if self._blocks is not None:
                block = next(self._blocks, None)
                if block is not None:
                    return block
                if self._current is not None:
                    self.pool.finish(self._current)
                self._blocks = None
                self._current = None
                self._tail = self._tail_reach = None  # windows never span files
            w = self.pool.fetch(self.worker_id)
            if w is None:
                return None
            self._current = w
            self._blocks = iter_token_blocks(str(w), self.block_tokens)

    def _fill(self) -> None:
        if len(self._buf_c) >= self.batch_size:
            return
        new_c, new_x = [], []
        n_new = 0
        while len(self._buf_c) + n_new < self.batch_size:
            block = self._next_block()
            if block is None:
                break
            reach = (
                self.rng.integers(1, self.window + 1, len(block))
                if self.dynamic else None
            )
            if self._tail is not None and len(self._tail):
                t = np.concatenate([self._tail, block])
                if reach is not None:
                    reach = np.concatenate([self._tail_reach, reach])
                c, x = _window_pairs(
                    t, self.window, skip_prefix=len(self._tail), reach=reach
                )
            else:
                t = block
                c, x = _window_pairs(block, self.window, reach=reach)
            # carry the last W tokens of the CONCATENATED stream (a block
            # shorter than W must not truncate the window)
            self._tail = t[-self.window :].copy()
            if reach is not None:
                self._tail_reach = reach[-self.window :].copy()
            if len(c):
                new_c.append(c)
                new_x.append(x)
                n_new += len(c)
        if n_new:
            # block shuffle: ONE permutation over (buffer + new pairs) per
            # fill — same uniform shuffle as permuting per appended block,
            # without re-copying the growing buffer k times
            c = np.concatenate([self._buf_c, *new_c])
            x = np.concatenate([self._buf_x, *new_x])
            perm = self.rng.permutation(len(c))
            self._buf_c, self._buf_x = c[perm], x[perm]
            self.max_buffered = max(self.max_buffered, len(self._buf_c))

    def next_batch(self) -> dict | None:
        self._fill()
        n = min(len(self._buf_c), self.batch_size)
        if n == 0:
            return None
        b = self._make(self._buf_c[:n], self._buf_x[:n])
        self._buf_c = self._buf_c[n:]
        self._buf_x = self._buf_x[n:]
        return b

    def _make(self, c: np.ndarray, x: np.ndarray) -> dict:
        bs = self.batch_size
        out = {
            "center": np.zeros(bs, dtype=np.int32),
            "context": np.zeros(bs, dtype=np.int32),
            "negatives": self.sampler.sample((bs, self.K)).astype(np.int32),
            "mask": np.zeros(bs, dtype=np.float32),
        }
        out["center"][: len(c)] = c
        out["context"][: len(c)] = x
        out["mask"][: len(c)] = 1.0
        return out

    def _empty(self) -> dict:
        return {
            "center": np.zeros(self.batch_size, dtype=np.int32),
            "context": np.zeros(self.batch_size, dtype=np.int32),
            "negatives": np.zeros((self.batch_size, self.K), dtype=np.int32),
            "mask": np.zeros(self.batch_size, dtype=np.float32),
        }


def examples_from_corpus(
    files: list[str], out_dir: str, cfg, shards: int = 1
) -> list[str]:
    """Turn corpus files of word ids into ``shards`` files of ``sgns``
    examples under ``out_dir`` by [w2v]'s settings (dynamic window, k
    negatives from the corpus' own unigram^0.75): the data-layer job that
    word2vec.c does inline. Pairs stream through one ``PairStream`` in
    blocks; batches go to the shards in turn."""
    from parameter_server_tpu.parallel.workload import WorkloadPool

    w = cfg.w2v
    counts = count_vocab(files, w.vocab_size, w.block_tokens)
    stream = PairStream(
        0, WorkloadPool([str(f) for f in files]),
        window=w.window, batch_size=w.batch_size, num_negatives=w.negatives,
        sampler=NegativeSampler(counts, seed=cfg.seed),
        block_tokens=w.block_tokens, seed=cfg.seed, dynamic_window=True,
    )
    paths = [os.path.join(out_dir, f"part-{i:05d}.txt") for i in range(shards)]
    for p in paths:
        open(p, "w").close()
    i = 0
    while (b := stream.next_batch()) is not None:
        real = b["mask"] > 0
        write_examples(
            paths[i % shards], b["center"][real], b["context"][real],
            b["negatives"][real], append=True,
        )
        i += 1
    return paths
