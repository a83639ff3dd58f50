"""DARLIN: delayed block proximal gradient for L1 logistic regression.

Reference analog: src/app/linear_method/darlin.* / batch_solver.* — the
reference's batch solver (Li et al., OSDI 2014, Algorithm 3). Its anatomy,
re-expressed for TPU, through the one store:

  reference                                this module
  ---------                                -----------
  SlotReader column-block cache            ``data.blockcache.ColumnBlocks``:
    (parse once, per-slot binary cache)      entries by feature block, sorted
                                             by feature, in fixed-length
                                             chunks resident in HBM, and where
                                             each feature's run of them ends
  servers hold w by key range              a ``spmd.Table`` (slots ``w`` and
                                             ``active``) in the one state
                                             dict, range-sharded over "kv"
  worker keeps prediction vector Xw        pred (N,) over "data", updated
                                             incrementally per block
  per block: pull w_b, push (g_b, u_b)     ``spmd.pull_range`` / the psum of
                                             (g, h) over "data" / ``spmd.
                                             push_range`` of the block's rows
  server proximal (soft-threshold) step    ``kv.updaters.ProxNewton``:
    + KKT filter                             ``direction`` / ``apply`` /
                                             ``refresh``
  bounded-delay block pipelining           ``max_delay`` + 1 device calls in
                                             flight; inside a call groups of
                                             ``max_delay`` + 1 blocks take
                                             their gradients against one
                                             stale pred

ONE program runs on every mesh, 1x1 included. A pass is a sequence of device
calls of a fixed number of blocks (``solver.steps_per_call``; streaming,
``solver.block_chunk`` > 0, uploads just those blocks' chunks for the same
program), dispatched as ``PodTrainer`` dispatches its calls; the objective,
the largest KKT violation and the count of non-zero weights come back as
scalars with each call's retire, never the table.

A block's entries lie sorted by feature, so its two sweeps by feature run
along the entry axis (``ops.sparse._row_scan``'s gated passes, a chunk at a
time, a run that crosses chunks carried over): the sums g and h are running
sums read at each feature's last entry (``_block_grad``), the direction by
entry is placed at each feature's first entry and copied down (``_block_xd``).
The sweeps by example (the gathers of the residual and the curvature, the
scatter-add of X_b d) are element by element.

One departure from the source: upstream bounds each coordinate's step by a
per-coordinate trust region; here one step scale a block comes from an
eight-point search on the true objective (``_line_search_alpha``).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.data.blockcache import ENTRY_ARRAYS, ColumnBlocks
from parameter_server_tpu.kv.updaters import ProxNewton
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.ops import sparse
from parameter_server_tpu.parallel import spmd
from parameter_server_tpu.parallel.ssp import DispatchWindow
from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter, observe_scalar

__all__ = [
    "ColumnBlocks",
    "Darlin",
    "make_darlin_fns",
    "shard_blocks_for_mesh",
    "updater_from_config",
]


def updater_from_config(cfg: PSConfig) -> ProxNewton:
    """The server's half of a block step, from the solver's settings."""
    return ProxNewton(
        eta=cfg.lr.eta,
        lambda_l1=cfg.penalty.lambda_l1,
        lambda_l2=cfg.penalty.lambda_l2,
    )


def _line_search_alpha(pred, Xd, y, mask, w_b, d, updater: ProxNewton):
    """Simultaneous coordinate updates can overshoot when block features
    co-occur (the diagonal model ignores coupling; the reference's bounded
    update is its safeguard). Safeguard here: evaluate the TRUE objective at
    8 geometric step scales in parallel and take the best — one fused (T, N)
    softplus sweep, fully static for XLA, its terms summed over the example
    shards ("data")."""
    alphas = 0.5 ** jnp.arange(8, dtype=jnp.float32)  # 1, 1/2, ..., 1/128
    zs = pred[None, :] + alphas[:, None] * Xd[None, :]  # (T, N)
    terms = (jax.nn.softplus(zs) - y[None, :] * zs) * mask[None, :]
    terms0 = (jax.nn.softplus(pred) - y * pred) * mask
    nll = jax.lax.psum(jnp.sum(terms, axis=1), "data")
    obj_a = nll + updater.penalty(w_b[None, :] + alphas[:, None] * d[None, :])
    obj_0 = jax.lax.psum(jnp.sum(terms0), "data") + updater.penalty(w_b)
    best = jnp.argmin(obj_a)
    return jnp.where(obj_a[best] < obj_0, alphas[best], 0.0)


# ---------------------------------------------------------------------------
# Host-side placement of the column blocks over the "data" axis
# ---------------------------------------------------------------------------


def shard_examples_for_mesh(cb: ColumnBlocks, data_shards: int) -> dict:
    """(labels, mask) of D * per examples — padded up to D equal shards."""
    D = data_shards
    N = cb.num_examples
    per = -(-N // D)
    labels = np.zeros(D * per, dtype=np.float32)
    mask = np.zeros(D * per, dtype=np.float32)
    labels[:N] = np.asarray(cb.labels, dtype=np.float32)
    mask[:N] = 1.0
    return {
        "labels": labels,
        "mask": mask,
        "per_shard_examples": per,
    }


def shard_blocks_for_mesh(
    cb: ColumnBlocks,
    data_shards: int,
    blocks: np.ndarray | None = None,
    pad_pow2: bool = False,
) -> dict:
    """Host-side prep: the selected blocks' chunks, partitioned by example
    shard (contiguous ranges of ``per`` examples, rows LOCAL to the shard).

    blocks: optional subset/order of block indices to pack. The streaming
      solver packs one call's blocks at a time straight from the (possibly
      mmap'd) block cache, so only those chunks are ever read into RAM.
    pad_pow2: round the chunk count up to a power of two, bounding jit
      recompilation across streamed calls to O(log chunks) distinct shapes.

    Returns numpy arrays ready for device_put:
      feat_local/rows/values: (D, n_chunks, C), each shard's chunks of the
        selection back to back, a block's entries still ascending by feature
        and only its last chunk padded (the ``ColumnBlocks`` contract, shard
        by shard)
      spans: (D, B, 3) int32 — block j's chunks [begin, end) in shard d,
        and j, its row of ``ends``
      ends: (D, B, block_size) int32 — where each feature's run of real
        entries ends in shard d's part of block j, counted from the part's
        first entry (``bincount(feat_local).cumsum()``: feature f's entries
        are ``ends[f - 1] .. ends[f]``, none where the two are equal)
      block_idx: (B,) absolute block ids; counts: (B, D) real entry counts.
    On one data shard the whole set in order is the cache's own arrays,
    viewed, not copied.
    """
    D, C = data_shards, cb.chunk_len
    per = -(-cb.num_examples // D)
    sel = (
        np.arange(cb.n_blocks, dtype=np.int64)
        if blocks is None
        else np.asarray(blocks, dtype=np.int64)
    )
    B = len(sel)
    counts = np.zeros((B, D), np.int64)
    ends = np.zeros((D, B, cb.block_size), np.int32)

    def run_ends(feat):
        return np.bincount(feat, minlength=cb.block_size).cumsum()

    if D == 1:
        counts[:, 0] = cb.entries[sel]
        for j, b in enumerate(sel):
            ends[0, j] = run_ends(cb.block(int(b))[0])
        n_chunk = (cb.chunk_begin[sel + 1] - cb.chunk_begin[sel]).astype(np.int64)
        if blocks is None:
            packed = {k: np.asarray(getattr(cb, k))[None] for k in ENTRY_ARRAYS}
            begin = cb.chunk_begin[:-1]
        else:
            at = np.concatenate(
                [np.arange(cb.chunk_begin[b], cb.chunk_begin[b + 1]) for b in sel]
                or [np.zeros(0, np.int64)]
            )
            packed = {k: np.asarray(getattr(cb, k)[at])[None] for k in ENTRY_ARRAYS}
            begin = np.cumsum(n_chunk) - n_chunk
        spans = np.stack([begin, begin + n_chunk], axis=-1)[None]
    else:
        per_shard: list[list] = [[] for _ in range(D)]
        for j, b in enumerate(sel):
            feat, rows, vals = cb.block(int(b))
            s = rows // per
            order = np.argsort(s, kind="stable")  # feature order survives
            counts[j] = np.bincount(s, minlength=D)
            upto = np.cumsum(counts[j])
            for d in range(D):
                part = order[upto[d] - counts[j, d] : upto[d]]
                per_shard[d].append((feat[part], rows[part] - d * per, vals[part]))
                ends[d, j] = run_ends(feat[part])
        n_chunk = -(-counts // C)  # (B, D)
        begin = np.cumsum(n_chunk, axis=0) - n_chunk
        spans = np.stack([begin, begin + n_chunk], axis=-1).transpose(1, 0, 2)
        total = max(int(n_chunk.sum(axis=0).max()), 1)
        packed = {
            k: np.zeros((D, total * C), np.float32 if k == "values" else np.int32)
            for k in ENTRY_ARRAYS
        }
        for d in range(D):
            for j, (feat, rows, vals) in enumerate(per_shard[d]):
                lo, n = int(begin[j, d]) * C, len(feat)
                packed["feat_local"][d, lo : lo + n] = feat
                packed["rows"][d, lo : lo + n] = rows
                packed["values"][d, lo : lo + n] = vals
                if n:  # a block's pad repeats its last feature: still sorted
                    packed["feat_local"][d, lo + n : (int(begin[j, d]) + int(n_chunk[j, d])) * C] = feat[-1]
        packed = {k: v.reshape(D, total, C) for k, v in packed.items()}
    have = packed["values"].shape[1]
    want = max(have, 1)
    if pad_pow2:
        want = 1 << (want - 1).bit_length()
    if want != have:
        packed = {
            k: np.concatenate([v, np.zeros((D, want - have, C), v.dtype)], axis=1)
            for k, v in packed.items()
        }
    row = np.broadcast_to(np.arange(B)[None, :, None], (D, B, 1))
    return {
        **packed,
        "spans": np.concatenate([spans, row], axis=-1).astype(np.int32),
        "ends": ends,
        "block_idx": sel.astype(np.int32),
        "counts": counts,
        "per_shard_examples": per,
    }


def run_carries(cb: ColumnBlocks) -> tuple[float, int]:
    """How far the sweeps by feature carry a run from chunk to chunk, read
    off the cache's own chunks (the layout of one data shard): the share of
    the chunks whose first run continues the chunk before (the same block,
    the same feature at the seam), and the most chunks one run lies in."""
    if not cb.chunk_begin[-1]:
        return 0.0, 0
    first, last = np.asarray(cb.feat_local[:, 0]), np.asarray(cb.feat_local[:, -1])
    carries = np.concatenate([[False], first[1:] == last[:-1]])
    carries[cb.chunk_begin[(cb.chunk_begin > 0) & (cb.chunk_begin < len(first))]] = False  # a block's first chunk
    whole = first == last  # a chunk that is one run hands on what it took up
    longest = run = 1
    for k in range(1, len(first)):
        run = (run + 1 if whole[k - 1] else 2) if carries[k] else 1
        longest = max(longest, run)
    return float(carries.mean()), longest


# ---------------------------------------------------------------------------
# The sweeps of one block's entries on one data shard. ``chunks_l`` holds the
# shard's (n_chunks, C) entry arrays and ``ends`` (B, block_size); ``span`` is
# the block's (first chunk, one past its last, row of ``ends``).
# ---------------------------------------------------------------------------

# Features a window: a chunk's running sums are read at its features' run ends
# (and a direction placed at their run heads) a window of the feature axis at
# a time, those windows alone that hold one of the chunk's features. A block
# of F features in n chunks visits about F / _WINDOW + n windows: smaller
# windows cost launches, larger ones element reads of features the chunk
# does not hold.
_WINDOW = 4096


def _chunk(chunks_l, c):
    return tuple(
        lax.dynamic_index_in_dim(chunks_l[k], c, 0, keepdims=False)
        for k in ENTRY_ARRAYS
    )


def _runs(chunks_l, span):
    """(block_size + 1,) bounds of the features' runs of entries in this
    shard's part of the block, counted from the part's first entry: feature
    f's are ``[bounds[f], bounds[f + 1])``."""
    ends = lax.dynamic_index_in_dim(chunks_l["ends"], span[2], 0, keepdims=False)
    return jnp.concatenate([jnp.zeros(1, ends.dtype), ends])


def _run_scan(x, fl, last, carry):
    """Running sums of ``x`` ((C,) or (lanes, C)) along one chunk that
    start again at every feature (``sparse._row_scan``: a chunk's terms
    are added as a tree), the chunk's first run taking up ``carry``, the
    total of the run the chunk before ended in, where it continues that
    run (``last`` is that chunk's last feature; a feature's entries lie
    next to each other, so it continues iff the ids are equal). Returns
    the sums and the next chunk's (last, carry)."""
    s = sparse._row_scan(x, fl)
    s = s + jnp.where(fl == last, carry[..., None], 0)
    return s, fl[-1], s[..., -1]


def _windows(bounds, fl, base, turn, carry):
    """``carry = turn(f0, at_head, at_tail, here, carry)`` over the windows
    of the feature axis that hold one of the chunk's features (``fl``, the
    chunk's ids, ascending; ``base``, its first entry's place in the
    block): the window's features ``f0 ..``, where in the chunk each one's
    run begins and where its last entry lies, and whether that feature has
    an entry at all. A block that is no whole number of windows has its
    last start early; a feature visited twice is read or placed twice, the
    same."""
    F = bounds.shape[0] - 1
    W = min(_WINDOW, F)

    def body(w, carry):
        f0 = jnp.minimum(w * W, F - W)
        b = lax.dynamic_slice_in_dim(bounds, f0, W + 1)
        return turn(f0, b[:-1] - base, b[1:] - 1 - base, b[1:] > b[:-1], carry)

    return lax.fori_loop(fl[0] // W, fl[-1] // W + 1, body, carry)


def _block_grad(err, h_ex, chunks_l, span, want_h: bool = True):
    """This shard's (lanes, block_size) sums by feature of one block,
    lanes = (g, h) or (g,) alone: a block's entries lie sorted by
    feature, so a feature's sum is a running sum along the entry axis
    read at the feature's last entry, not a scatter-add an entry. Chunk
    by chunk: the terms' running sums (``_run_scan``), then the features
    whose run ends in the chunk read their totals out of them
    (``_windows``); a feature with no entry keeps 0.
    A key that every example holds has 10^7 terms: added one by one
    into one float32 they stop counting near 2^24 (the cell's integer
    columns: h read 1.7% low). Here a chunk's terms are added as a tree
    and a run that crosses chunks adds the chunks' totals one by one
    (the carry), so no sum is longer than a chunk or than the chunks of
    a block; nothing in it is a sum the compiler could fold into a
    longer one."""
    C = chunks_l["values"].shape[-1]
    bounds = _runs(chunks_l, span)
    # a read stays inside what the loop sweeps: a run that a shorter span
    # cuts keeps the sum of its swept part, as a scatter-add an entry would
    bounds = jnp.minimum(bounds, (span[1] - span[0]) * C)
    lanes = 2 if want_h else 1

    def body(c, state):
        g, last, carry = state
        fl, rows, vals = _chunk(chunks_l, c)
        terms = [vals * jnp.take(err, rows)]
        if want_h:
            terms.append(vals * vals * jnp.take(h_ex, rows))
        s, last, carry = _run_scan(jnp.stack(terms), fl, last, carry)

        def read(f0, at_head, at_tail, here, g):
            ends_here = here & (at_tail >= 0) & (at_tail < C)
            old = lax.dynamic_slice_in_dim(g, f0, here.shape[0], axis=-1)
            # a lane at a time: for one gather of both the compiler turns the
            # chunk's sums lanes-minor first, a tile of 128 to each entry
            got = jnp.stack([jnp.take(lane, at_tail, mode="clip") for lane in s])
            new = jnp.where(ends_here, got, old)
            return sparse._entries_minor(lax.dynamic_update_slice_in_dim(g, new, f0, axis=-1))

        return _windows(bounds, fl, (c - span[0]) * C, read, g), last, carry

    g = sparse._entries_minor(jnp.zeros((lanes, bounds.shape[0] - 1), jnp.float32))
    init = (g, jnp.int32(-1), jnp.zeros(lanes, jnp.float32))
    return lax.fori_loop(span[0], span[1], body, init)[0]


def _block_xd(d, chunks_l, span, per: int):
    """This shard's X_b d: the block's entries scattered over its
    examples, chunk by chunk. ``d`` by feature is the gradient's sweep
    turned round: each feature whose run begins in the chunk has its
    value placed at the run's first entry (``_windows``) and ``_run_scan``
    copies it down the run (d + 0 + ... + 0: exact), the value a chunk
    ends in carried into the next."""
    C = chunks_l["values"].shape[-1]
    bounds = _runs(chunks_l, span)

    def body(c, state):
        xd, last, carry = state
        fl, rows, vals = _chunk(chunks_l, c)

        def place(f0, at_head, at_tail, here, heads):
            begins_here = here & (at_head >= 0) & (at_head < C)
            d_w = lax.dynamic_slice_in_dim(d, f0, here.shape[0])
            return heads.at[jnp.where(begins_here, at_head, C)].set(d_w, mode="drop")

        heads = _windows(bounds, fl, (c - span[0]) * C, place, jnp.zeros(C, jnp.float32))
        d_e, last, carry = _run_scan(heads, fl, last, carry)
        return xd.at[rows].add(vals * d_e), last, carry

    init = (jnp.zeros(per, jnp.float32), jnp.int32(-1), jnp.float32(0.0))
    return lax.fori_loop(span[0], span[1], body, init)[0]


# ---------------------------------------------------------------------------
# The solver's programs over the (data, kv) mesh
#
# Reference analog (SURVEY §3.3): workers hold example shards (their column
# blocks + their slice of the prediction vector Xw), servers hold the weight
# by key range. Per block: each worker computes its shard's gradient /
# diag-Hessian contribution (push == psum over "data"), the owning server
# range computes the proximal step, and the direction is broadcast back
# (the range pull over "kv") so every worker can update its Xw slice.
# ---------------------------------------------------------------------------


class DarlinFns:
    """The jitted mesh programs of the solver, each over one call's blocks
    (``spans``/``blk``/``live`` say which chunks and which row of the
    blocks' run ends, which key ranges, and which of the call's slots hold
    a block at all):

    block_call — the blocks' proximal steps in order; returns the state,
      pred and {alphas (G,), obj, viol_max, nnz_w} after the call.
    refresh_call — the KKT filter's active set taken anew for the blocks
      from the gradient at the state as it stands (one sweep of their
      entries where a step makes three; neither weights nor pred move);
      returns the state and {n_active}, the active coordinates among them.
    xw_call — pred += X_b w_b for the blocks (a restart recomputes Xw from
      the table by one sweep).
    objective — (objective, non-zero weights) of (state, pred).
    place / place_blocks — host arrays onto the mesh with the solver's
      shardings.
    """

    def __init__(self, **fns):
        self.__dict__.update(fns)


def make_darlin_fns(
    mesh,
    table: spmd.Table,
    *,
    num_keys: int,
    block_size: int,
    per_shard_examples: int,
    delay: int,
) -> DarlinFns:
    """Build the solver's jitted mesh programs (see DarlinFns).

    Layout: the table's slots P("kv", None); pred/labels/mask (D * per,)
    P("data"); chunk arrays (D * n_chunks, C) and the blocks' run ends
    (D * B, block_size) P("data", None). Requires every block wholly inside
    one kv range (contiguous equal blocks that divide the shard).
    """
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    kv = mesh.shape["kv"]
    shard_size = spmd._shard_size(num_keys, kv)
    if shard_size % block_size:
        raise ValueError(
            f"kv range {shard_size} not aligned to block_size {block_size}: "
            "each feature block must live wholly on one kv shard"
        )
    per = per_shard_examples
    updater: ProxNewton = table.updater

    def _rows_1d(rows):
        return {k: v[:, 0] for k, v in rows.items()}

    def _objective(state_l, pred_l, y_l, mask_l):
        w_l = table.of(state_l)["w"][:, 0]
        nll = lax.psum(jnp.sum(mask_l * (jax.nn.softplus(pred_l) - y_l * pred_l)), "data")
        reg = lax.psum(updater.penalty(w_l), "kv")
        nnz = lax.psum(jnp.sum(w_l != 0.0), "kv")
        return nll + reg, nnz

    def local_block_call(state_l, pred_l, y_l, mask_l, chunks_l, spans_l, blk, live):
        spans_l = spans_l[0]

        def block_step(carry, x):
            state_l, pred_l, stale, viol_max, i = carry
            span, b_idx, on = x
            if delay:  # bounded delay: refresh the stale snapshot every (delay+1) blocks
                stale = jnp.where((i % (delay + 1)) == 0, pred_l, stale)
                seen = stale
            else:
                seen = pred_l
            begin = b_idx * block_size
            with jax.named_scope("ps.grad"):
                p = jax.nn.sigmoid(seen)
                g, h = _block_grad((p - y_l) * mask_l, p * (1.0 - p) * mask_l, chunks_l, span)
            with jax.named_scope("ps.pull"):
                rows = _rows_1d(spmd.pull_range(table, state_l, begin, block_size, shard_size, kv))
            with jax.named_scope("ps.push"):
                g, h = lax.psum(g, "data"), lax.psum(h, "data")  # the workers' push of (g, u)
                viol = jnp.where(on, updater.violation(rows, g).max(), 0.0)
                d = jnp.where(on, updater.direction(rows, g, h), 0.0)
            with jax.named_scope("darlin.xd"):
                xd = _block_xd(d, chunks_l, span, per)
            with jax.named_scope("darlin.linesearch"):
                alpha = _line_search_alpha(pred_l, xd, y_l, mask_l, rows["w"], d, updater)
            with jax.named_scope("ps.push"):
                new = {k: v[:, None] for k, v in updater.apply(rows, d, alpha).items()}
                state_l = spmd.push_range(table, state_l, begin, new, shard_size, kv)
            with jax.named_scope("darlin.xd"):
                # incremental prediction update: pred += alpha * X_b @ d (ref: Xw)
                pred_l = pred_l + alpha * xd
            return (state_l, pred_l, stale, jnp.maximum(viol_max, viol), i + 1), alpha

        stale0 = pred_l if delay else jnp.zeros((), jnp.float32)
        init = (state_l, pred_l, stale0, jnp.float32(0.0), jnp.int32(0))
        (state_l, pred_l, _, viol_max, _), alphas = lax.scan(block_step, init, (spans_l, blk, live))
        with jax.named_scope("darlin.linesearch"):
            obj, nnz = _objective(state_l, pred_l, y_l, mask_l)
        out = {"alphas": alphas, "obj": obj, "viol_max": viol_max, "nnz_w": nnz}
        return state_l, pred_l, out

    @jax.named_scope("darlin.refresh")  # whole under the one name: the step's five scopes stay a step's
    def local_refresh_call(state_l, pred_l, y_l, mask_l, chunks_l, spans_l, blk, live, thr):
        spans_l = spans_l[0]
        err = (jax.nn.sigmoid(pred_l) - y_l) * mask_l

        def block_step(carry, x):
            state_l, n_active = carry
            span, b_idx, on = x
            begin = b_idx * block_size
            (g,) = _block_grad(err, None, chunks_l, span, want_h=False)
            rows = _rows_1d(spmd.pull_range(table, state_l, begin, block_size, shard_size, kv))
            new = updater.refresh(rows, lax.psum(g, "data"), thr)
            new = {k: jnp.where(on, v, rows[k])[:, None] for k, v in new.items()}
            state_l = spmd.push_range(table, state_l, begin, new, shard_size, kv)
            n_active = n_active + jnp.where(on, jnp.sum(new["active"]), 0.0)
            return (state_l, n_active), None

        (state_l, n_active), _ = lax.scan(
            block_step, (state_l, jnp.float32(0.0)), (spans_l, blk, live)
        )
        return state_l, {"n_active": n_active}

    def local_xw_call(state_l, pred_l, chunks_l, spans_l, blk, live):
        spans_l = spans_l[0]

        def block_step(pred_l, x):
            span, b_idx, on = x
            with jax.named_scope("ps.pull"):
                rows = _rows_1d(
                    spmd.pull_range(table, state_l, b_idx * block_size, block_size, shard_size, kv)
                )
            with jax.named_scope("darlin.xd"):
                w_b = jnp.where(on, updater.weights(rows), 0.0)
                return pred_l + _block_xd(w_b, chunks_l, span, per), None

        pred_l, _ = lax.scan(block_step, pred_l, (spans_l, blk, live))
        return pred_l

    state_s = {table.key(k): spmd.state_spec() for k in table.slots()}
    ex_s, dat, span_s = P("data"), P("data", None), P("data", None, None)
    # a shard's chunks are a plain (n_chunks, C) array on its device: the
    # shards' stacked on the first axis (a leading axis of one would have the
    # chip relay the whole set at every call)
    chunks_s = {k: dat for k in (*ENTRY_ARRAYS, "ends")}
    call_s = (chunks_s, span_s, P(None), P(None))  # chunks, spans, blk, live
    scalars = {"alphas": P(), "obj": P(), "viol_max": P(), "nnz_w": P()}

    def program(local, in_specs, out_specs, donate):
        jitted = jax.jit(
            shard_map(local, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False),
            donate_argnums=donate,
        )
        seen: set = set()

        @functools.wraps(local)
        def call(*args):
            spmd.note_program(jitted, seen, frozenset(), *args)
            return jitted(*args)

        call.jitted = jitted
        return call

    def place(arr: np.ndarray):
        """A (D * per,) vector over the examples onto the "data" axis."""
        return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, ex_s))

    def place_blocks(sharded: dict) -> dict:
        sh = NamedSharding(mesh, dat)
        return {
            k: jax.device_put(sharded[k].reshape(-1, sharded[k].shape[-1]), sh)
            for k in (*ENTRY_ARRAYS, "ends")
        }

    return DarlinFns(
        block_call=program(
            local_block_call, (state_s, ex_s, ex_s, ex_s, *call_s), (state_s, ex_s, scalars), (0, 1)
        ),
        refresh_call=program(
            local_refresh_call, (state_s, ex_s, ex_s, ex_s, *call_s, P()),
            (state_s, {"n_active": P()}), (0,),
        ),
        xw_call=program(local_xw_call, (state_s, ex_s, *call_s), ex_s, (1,)),
        objective=program(_objective, (state_s, ex_s, ex_s, ex_s), (P(), P()), ()),
        place=place,
        place_blocks=place_blocks,
    )


class Darlin:
    """Batch L1-LR solver app (scheduler role of the reference's Darlin*)
    over a (data, kv) device mesh — 1x1 unless ``mesh`` or ``cfg.parallel``
    says otherwise: example shards over "data", weight ranges over "kv", the
    reference's worker/server split (SURVEY §3.3).

    Its state is the store's: ``self.state`` holds the slots of
    ``self.table`` (``w``, ``active``) as every app's flat state dict does,
    placed by ``spmd.shard_state``, saved and restored by
    ``utils.checkpoint``; ``pred`` = Xw is the worker's and is recomputed
    from the table on a restart, not saved.

    ``fit_blocks`` is ``begin`` + ``solve``; a caller that wants to look at
    the state between device calls (the benchmark) uses the two, and
    ``run_calls`` for a part of a pass. ``on_retire(record)`` is called
    right after the blocking read of each call's scalars, a step's and a
    refresh's alike."""

    CKPT_ALGO = "darlin"

    def __init__(
        self,
        cfg: PSConfig,
        reporter: ProgressReporter | None = None,
        mesh=None,
    ):
        from parameter_server_tpu.parallel import make_mesh

        self.cfg = cfg
        self.reporter = reporter or ProgressReporter()
        self.mesh = mesh or make_mesh(cfg.parallel.data_shards, cfg.parallel.kv_shards)
        self.updater = updater_from_config(cfg)
        self.table = spmd.Table("", self.updater, 1)
        self.delay = max(cfg.solver.max_delay, 0)
        self.on_retire = None
        self.state: dict | None = None
        self.max_inflight = 0

    # -- set-up ------------------------------------------------------------

    def begin(self, cb: ColumnBlocks, shuffle_blocks: bool = True, resume_dir: str = "") -> None:
        """Place the data, make (or restore) the table, and stand at the
        start of pass ``self.passes_done``."""
        cfg = self.cfg
        self.cb, self.shuffle = cb, shuffle_blocks
        D = self.mesh.shape["data"]
        ex = shard_examples_for_mesh(cb, D)
        self.per = ex["per_shard_examples"]
        self.fns = make_darlin_fns(
            self.mesh, self.table, num_keys=cb.num_keys, block_size=cb.block_size,
            per_shard_examples=self.per, delay=self.delay,
        )
        self.labels = self.fns.place(ex["labels"])
        self.mask = self.fns.place(ex["mask"])
        self.pred = self.fns.place(np.zeros(D * self.per, np.float32))
        # blocks a device call: a streamed call uploads block_chunk blocks'
        # chunks, a resident one walks steps_per_call blocks of the set in HBM
        stream = cfg.solver.block_chunk
        self.call_blocks = stream if stream > 0 else max(cfg.solver.steps_per_call, 1)
        self._resident = None
        if stream <= 0:
            sharded = shard_blocks_for_mesh(cb, D)
            self._resident = (self.fns.place_blocks(sharded), sharded["spans"])
        carry_share, longest_run = run_carries(cb)
        observe_scalar("darlin.carry_share", carry_share)
        observe_scalar("darlin.longest_run_chunks", longest_run)
        self.passes_done, self.history, self.prev_obj, self.converged = 0, [], None, False
        if resume_dir:
            self._load(resume_dir)
        else:
            self.state = spmd.shard_state(self.table.init_slots(cb.num_keys), self.mesh)
        if self.prev_obj is None:
            self.prev_obj = float(self.fns.objective(self.state, self.pred, self.labels, self.mask)[0])

    def block_order(self, it: int) -> np.ndarray:
        """Pass ``it``'s order of the blocks: shuffled from (seed, it), so
        that a restart takes up the sequence where it stopped (ref:
        randomized block order per iteration)."""
        n = self.cb.n_blocks
        if not self.shuffle:
            return np.arange(n)
        return np.random.default_rng([self.cfg.seed, it]).permutation(n)

    def _call_args(self, group: np.ndarray) -> tuple:
        """(chunks, spans, blk, live) of one call over ``group``'s blocks,
        padded to the call's fixed number of blocks with slots that hold
        none."""
        G, n = self.call_blocks, len(group)

        def slots(a):  # (D, n, ...) -> (D, G, ...): the slots behind the group hold no block
            return np.concatenate([a, np.zeros((a.shape[0], G - n, *a.shape[2:]), a.dtype)], axis=1)

        if self._resident is not None:
            chunks, all_spans = self._resident
            spans = all_spans[:, group]
        else:
            sharded = shard_blocks_for_mesh(
                self.cb, self.mesh.shape["data"], blocks=group, pad_pow2=True
            )
            sharded["ends"] = slots(sharded["ends"])  # one shape a call
            chunks, spans = self.fns.place_blocks(sharded), sharded["spans"]
        spans = slots(spans)
        blk = np.concatenate([group, np.zeros(G - n, group.dtype)]).astype(np.int32)
        return chunks, spans, blk, np.arange(G) < n

    def _groups(self, order: np.ndarray) -> list:
        G = self.call_blocks
        return [order[lo : lo + G] for lo in range(0, len(order), G)]

    # -- the loop ----------------------------------------------------------

    def run_calls(
        self, order: np.ndarray, first: int = 0, count: int | None = None,
        refresh_at: float | None = None,
    ) -> list:
        """Dispatch calls ``first .. first + count`` of a pass over
        ``order`` (to its end, unsaid), at most ``max_delay`` + 1 in flight,
        and return their retired records once every one has retired: {call,
        blocks, alphas, obj, viol_max, nnz_w} of a call that steps its
        blocks, {call, blocks, n_active} of one that refreshes their active
        set instead (``refresh_at``: the KKT filter's threshold)."""
        records: list = []
        refresh = refresh_at is not None

        def retire(idx: int, entry) -> None:
            group, out = entry
            with trace.phase("darlin.retire", call=idx):
                # the blocking read: the bound on calls in flight taking effect
                out = {k: np.asarray(v) for k, v in out.items()}
            if refresh:
                rec = {"call": idx, "blocks": group, "n_active": float(out["n_active"])}
            else:
                rec = {
                    "call": idx, "blocks": group, "alphas": out["alphas"][: len(group)],
                    "obj": float(out["obj"]), "viol_max": float(out["viol_max"]),
                    "nnz_w": int(out["nnz_w"]),
                }
            records.append(rec)
            if self.on_retire is not None:
                self.on_retire(rec)

        gate = DispatchWindow(self.delay, retire)
        groups = self._groups(order)
        last = len(groups) if count is None else min(first + count, len(groups))
        for idx in range(first, last):
            gate.gate(idx)
            with trace.phase("darlin.dispatch", call=idx):
                args = (self.state, self.pred, self.labels, self.mask, *self._call_args(groups[idx]))
                if refresh:
                    self.state, out = self.fns.refresh_call(*args, np.float32(refresh_at))
                else:
                    self.state, self.pred, out = self.fns.block_call(*args)
            gate.add(idx, (groups[idx], out))
            self.max_inflight = max(self.max_inflight, gate.max_inflight)
        gate.wait_all()  # the pass's sync point: every dispatched call retired
        return records

    def _refresh(self, order: np.ndarray, viol_max: float) -> float:
        """The KKT filter's active set, taken anew from the violation scale
        of the pass (ref: the filter's adaptive threshold) by one more round
        of calls over the pass's blocks; returns the share of the key space
        left active."""
        thr = self.cfg.solver.kkt_filter_threshold * max(viol_max, 1e-12)
        recs = self.run_calls(order, refresh_at=thr)
        return sum(r["n_active"] for r in recs) / self.cb.num_keys

    def solve(self, first_call: int = 0, ckpt_dir: str = "") -> dict:
        """Passes from ``self.passes_done`` on (the first from its call
        ``first_call``) until the relative fall of the objective is under
        ``solver.epsilon`` or ``solver.block_iters`` passes are done."""
        cfg, cb = self.cfg, self.cb
        for it in range(self.passes_done, 0 if self.converged else cfg.solver.block_iters):
            order = self.block_order(it)
            recs = self.run_calls(order, first_call)
            first_call = 0
            obj, nnz = recs[-1]["obj"], recs[-1]["nnz_w"]
            viol = max(r["viol_max"] for r in recs)
            observe_scalar("darlin.viol_max", viol)
            if cfg.solver.kkt_filter_threshold > 0:
                observe_scalar("darlin.active_share", self._refresh(order, viol))
            rel = (self.prev_obj - obj) / max(abs(self.prev_obj), 1e-12)
            self.reporter.report(
                examples=cb.num_examples, objv=obj / cb.num_examples,
                nnz_w=nnz, auc=float("nan"),
            )
            self.history.append(obj)
            self.passes_done = it + 1
            self.converged = 0 <= rel < cfg.solver.epsilon and it > 0
            self.prev_obj = obj
            if ckpt_dir:
                self.save(ckpt_dir)
            if self.converged:
                break
        probs = 1.0 / (1.0 + np.exp(-self.predictions()))
        return {
            "objv": self.history[-1] / cb.num_examples,
            "iters": len(self.history),
            "nnz_w": int((self.w != 0).sum()),
            "train_auc": M.auc(np.asarray(cb.labels), probs),
            "history": list(self.history),
        }

    def fit_blocks(
        self, cb: ColumnBlocks, shuffle_blocks: bool = True,
        ckpt_dir: str = "", resume: bool = False,
    ) -> dict:
        """Run the solver on prebuilt (possibly disk-cached) column blocks;
        with ``ckpt_dir`` the table is saved after every finished pass, and
        ``resume`` takes the solve up from the last one saved there."""
        self.begin(cb, shuffle_blocks, resume_dir=ckpt_dir if resume else "")
        return self.solve(ckpt_dir=ckpt_dir)

    def fit(self, batches: list[CSRBatch], shuffle_blocks: bool = True) -> dict:
        cb = ColumnBlocks.from_batches(
            batches, self.cfg.data.num_keys, self.cfg.solver.feature_blocks
        )
        return self.fit_blocks(cb, shuffle_blocks=shuffle_blocks)

    # -- the state, off the device -------------------------------------------

    @property
    def w(self) -> np.ndarray:
        """(num_keys,) weights on this host (the table read back whole:
        for the model dump and the tests, never inside the loop)."""
        return np.asarray(self.state[self.table.key("w")])[: self.cb.num_keys, 0]

    def predictions(self) -> np.ndarray:
        """(N,) Xw of the real examples."""
        return np.asarray(self.pred)[np.asarray(self.mask) > 0]

    def save(self, ckpt_dir: str) -> None:
        from parameter_server_tpu.utils.checkpoint import save_checkpoint

        save_checkpoint(
            ckpt_dir,
            {k: np.asarray(v)[: self.cb.num_keys] for k, v in self.state.items()},
            meta={
                "algo": self.CKPT_ALGO, "num_keys": self.cb.num_keys,
                "passes_done": self.passes_done, "history": list(self.history),
                "converged": self.converged,
            },
        )

    def _load(self, ckpt_dir: str) -> None:
        """The table as the last finished pass left it; Xw by one sweep of
        the blocks over it."""
        from parameter_server_tpu.utils.checkpoint import load_checkpoint

        if not os.path.exists(os.path.join(ckpt_dir, "manifest.json")):
            raise FileNotFoundError(f"no checkpoint to resume from in {ckpt_dir!r}")
        host, meta = load_checkpoint(ckpt_dir)
        if meta.get("algo") != self.CKPT_ALGO or meta.get("num_keys") != self.cb.num_keys:
            raise ValueError(f"{ckpt_dir!r} holds no darlin table of {self.cb.num_keys} keys: {meta}")
        self.state = spmd.shard_state(
            {self.table.key(k): jnp.asarray(host[self.table.key(k)]) for k in self.table.slots()},
            self.mesh,
        )
        self.passes_done = int(meta["passes_done"])
        self.history = [float(x) for x in meta["history"]]
        self.converged = bool(meta.get("converged", False))
        self.prev_obj = self.history[-1] if self.history else None
        for group in self._groups(np.arange(self.cb.n_blocks)):
            self.pred = self.fns.xw_call(self.state, self.pred, *self._call_args(group))

    def evaluate_files(self, files: list[str]) -> dict:
        """Score held-out files on the solved table with the one evaluator:
        ``PodTrainer.evaluate_files`` over the linear app on this table."""
        import copy

        from parameter_server_tpu.parallel.trainer import PodTrainer

        cfg = copy.deepcopy(self.cfg)  # the trainer holds cfg.parallel to its mesh
        cfg.parallel.data_shards = self.mesh.shape["data"]
        cfg.parallel.kv_shards = self.mesh.shape["kv"]
        trainer = PodTrainer(
            cfg, mesh=self.mesh, reporter=self.reporter,
            app=spmd.linear_app(self.updater),
        )
        trainer.state = self.state
        return trainer.evaluate_files(files)
