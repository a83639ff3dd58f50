"""DARLIN: delayed block proximal gradient for L1 logistic regression.

Reference analog: src/app/linear_method/darlin.* / batch_solver.* — the
reference's batch solver. Its anatomy, re-expressed for TPU:

  reference                                this module
  ---------                                -----------
  SlotReader column-block cache            ColumnBlocks: entries sorted by
    (parse once, per-slot binary cache)      feature block, padded to a
                                             static per-block size, stacked
                                             into (n_blocks, E) arrays
  worker keeps prediction vector Xw        pred (N,) device-resident, updated
                                             incrementally per block
  per-block grad + diag-Hessian push       segment_sums over block entries
  server proximal (soft-threshold) step    prox_newton_block (elementwise)
  KKT filter active-set bitmap             active (K,) bool array; inactive
                                             coordinates get delta == 0
  bounded-delay block pipelining           ``delay`` blocks compute their
                                             gradients against the same stale
                                             pred inside one lax.scan carry

The whole pass over blocks is ONE jitted lax.scan — block steps are the
reference's unit of work and remain so here, but scheduling is compiled
instead of message-driven.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.data.blockcache import ColumnBlocks
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

__all__ = [
    "ColumnBlocks",
    "Darlin",
    "darlin_pass",
    "make_darlin_spmd_fns",
    "shard_blocks_for_mesh",
]


# ---------------------------------------------------------------------------
# Per-block coordinate math, shared verbatim by the single-device and SPMD
# solvers — the 2e-4 trajectory-parity contract between them depends on the
# formulas living in exactly one place. The distributed path injects its
# cross-shard reduction through ``reduce`` (identity vs psum over "data").
# ---------------------------------------------------------------------------


def _kkt_viol(w_b: jax.Array, g: jax.Array, lambda_l1: float) -> jax.Array:
    """KKT violation per coordinate (ref: the filter score deciding the
    active set)."""
    return jnp.where(
        w_b != 0.0,
        jnp.abs(g + jnp.sign(w_b) * lambda_l1),
        jnp.maximum(jnp.abs(g) - lambda_l1, 0.0),
    )


def _prox_newton_direction(
    w_b: jax.Array,
    g: jax.Array,
    h: jax.Array,
    skip: jax.Array,
    lambda_l1: float,
    lambda_l2: float,
    learning_rate: float,
) -> jax.Array:
    """Proximal Newton direction per coordinate (diagonal model):
    z = w*h - eta*g ; d = soft_threshold(z, eta*lambda_l1)/h - w."""
    h_safe = h + lambda_l2 + 1e-6
    z = w_b * h_safe - learning_rate * g
    w_cand = (
        jnp.sign(z)
        * jnp.maximum(jnp.abs(z) - learning_rate * lambda_l1, 0.0)
        / h_safe
    )
    return jnp.where(skip, 0.0, w_cand - w_b)


def _line_search_alpha(
    pred: jax.Array,
    Xd: jax.Array,
    y: jax.Array,
    w_b: jax.Array,
    d: jax.Array,
    lambda_l1: float,
    lambda_l2: float,
    mask: jax.Array | None = None,
    reduce=lambda x: x,
):
    """Simultaneous coordinate updates can overshoot when block features
    co-occur (the diagonal model ignores coupling; the reference's bounded
    update is its safeguard). Safeguard here: evaluate the TRUE objective at
    8 geometric step scales in parallel and take the best — one fused (T, N)
    softplus sweep, fully static for XLA. ``reduce`` sums nll terms across
    example shards in the distributed solver."""
    alphas = 0.5 ** jnp.arange(8, dtype=jnp.float32)  # 1, 1/2, ..., 1/128
    zs = pred[None, :] + alphas[:, None] * Xd[None, :]  # (T, N)
    terms = jax.nn.softplus(zs) - y[None, :] * zs
    terms0 = jax.nn.softplus(pred) - y * pred
    if mask is not None:
        terms = terms * mask[None, :]
        terms0 = terms0 * mask
    nll = reduce(jnp.sum(terms, axis=1))
    wa = w_b[None, :] + alphas[:, None] * d[None, :]  # (T, block)
    reg = lambda_l1 * jnp.abs(wa).sum(axis=1) + 0.5 * lambda_l2 * (wa * wa).sum(axis=1)
    obj_a = nll + reg
    obj_0 = (
        reduce(jnp.sum(terms0))
        + lambda_l1 * jnp.abs(w_b).sum()
        + 0.5 * lambda_l2 * (w_b * w_b).sum()
    )
    best = jnp.argmin(obj_a)
    return jnp.where(obj_a[best] < obj_0, alphas[best], 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_size", "num_examples", "delay")
)
def darlin_pass(
    w: jax.Array,  # (K,)
    pred: jax.Array,  # (N,)
    active: jax.Array,  # (K,) bool — KKT active set
    blocks: dict[str, jax.Array],  # stacked block arrays + block order
    labels: jax.Array,
    lambda_l1: float,
    lambda_l2: float,
    learning_rate: float,
    kkt_threshold: float,
    block_size: int,
    num_examples: int,
    delay: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One pass over all feature blocks. Returns (w, pred, active, viol_max).

    ``delay`` > 0 reproduces the reference's bounded-delay pipelining: the
    gradient of block t is computed against the prediction vector as of
    block t - (t mod (delay+1)) — i.e. groups of delay+1 consecutive blocks
    all read the same stale pred, then their updates land together.
    """
    y = labels

    def block_step(carry, blk):
        w, pred, stale_pred, active, viol_max, i = carry
        # bounded delay: refresh the stale snapshot every (delay+1) blocks
        refresh = (i % (delay + 1)) == 0
        stale_pred = jnp.where(refresh, pred, stale_pred)

        fl, rows, vals, b_idx = (
            blk["feat_local"],
            blk["rows"],
            blk["values"],
            blk["block_idx"],
        )
        begin = b_idx * block_size
        p = jax.nn.sigmoid(stale_pred)
        err = p - y
        h_ex = p * (1.0 - p)
        g = jax.ops.segment_sum(
            vals * jnp.take(err, rows), fl, num_segments=block_size
        )
        h = jax.ops.segment_sum(
            vals * vals * jnp.take(h_ex, rows), fl, num_segments=block_size
        )
        w_b = jax.lax.dynamic_slice(w, (begin,), (block_size,))
        act_b = jax.lax.dynamic_slice(active, (begin,), (block_size,))

        viol = _kkt_viol(w_b, g, lambda_l1)
        viol_max = jnp.maximum(viol_max, viol.max())
        # inactive zero-weight coords with tiny gradient are skipped
        skip = (~act_b) & (w_b == 0.0)
        d = _prox_newton_direction(
            w_b, g, h, skip, lambda_l1, lambda_l2, learning_rate
        )
        Xd = jax.ops.segment_sum(
            vals * jnp.take(d, fl), rows, num_segments=num_examples
        )
        alpha = _line_search_alpha(
            pred, Xd, y, w_b, d, lambda_l1, lambda_l2
        )

        w = jax.lax.dynamic_update_slice(w, w_b + alpha * d, (begin,))
        # incremental prediction update: pred += alpha * X_b @ d (ref: Xw)
        pred = pred + alpha * Xd
        return (w, pred, stale_pred, active, viol_max, i + 1), None

    init = (w, pred, pred, active, jnp.float32(0.0), jnp.int32(0))
    (w, pred, _, active, viol_max, _), _ = jax.lax.scan(
        block_step, init, blocks
    )
    return w, pred, active, viol_max


@functools.partial(jax.jit, static_argnames=())
def _objective(
    w: jax.Array, pred: jax.Array, labels: jax.Array, lambda_l1: float, lambda_l2: float
) -> jax.Array:
    nll = jnp.sum(jax.nn.softplus(pred) - labels * pred)
    return nll + lambda_l1 * jnp.abs(w).sum() + 0.5 * lambda_l2 * (w * w).sum()


# ---------------------------------------------------------------------------
# Distributed DARLIN over the (data, kv) mesh
#
# Reference analog (SURVEY §3.3): workers hold example shards (their column
# blocks + their slice of the prediction vector Xw), servers hold the weight
# by key range. Per block: each worker computes its shard's gradient /
# diag-Hessian contribution (push == psum over "data"), the owning server
# range computes the proximal step, and the direction is broadcast back
# (pull == masked psum over "kv") so every worker can update its Xw slice.
# ---------------------------------------------------------------------------


def shard_examples_for_mesh(cb: ColumnBlocks, data_shards: int) -> dict:
    """(labels, mask) reshaped to (D, per) — examples padded to D * per."""
    D = data_shards
    N = cb.num_examples
    per = -(-N // D)
    labels = np.zeros(D * per, dtype=np.float32)
    mask = np.zeros(D * per, dtype=np.float32)
    labels[:N] = np.asarray(cb.labels, dtype=np.float32)
    mask[:N] = 1.0
    return {
        "labels": labels.reshape(D, per),
        "mask": mask.reshape(D, per),
        "per_shard_examples": per,
    }


def shard_blocks_for_mesh(
    cb: ColumnBlocks,
    data_shards: int,
    blocks: np.ndarray | None = None,
    pad_pow2: bool = False,
) -> dict:
    """Host-side prep: partition block entries by example shard — fully
    vectorized (one argsort over the selected entries; no per-block Python
    loops).

    blocks: optional subset/order of block indices to pack. The streaming
      solver packs one chunk at a time straight from the (possibly mmap'd)
      block cache, so only the chunk's rows are ever read into RAM.
    pad_pow2: round the entry width E up to a power of two, bounding jit
      recompilation across streamed chunks to O(log E) distinct shapes.

    Returns numpy arrays ready for device_put:
      feat_local/rows/values: (B, D, E) with rows LOCAL to the shard and
        E = the max per-(block, shard) entry count of THIS selection (not
        a global max — padding stays bounded by the selection's own skew)
      block_idx: (B,) absolute block ids; counts: (B, D) real entry counts
    (labels/mask come from ``shard_examples_for_mesh`` — computed once per
    solve, not per packed chunk).
    """
    D = data_shards
    N = cb.num_examples
    per = -(-N // D)  # ceil: examples padded to D * per
    sel = (
        np.arange(cb.n_blocks, dtype=np.int64)
        if blocks is None
        else np.asarray(blocks, dtype=np.int64)
    )
    B = len(sel)
    # fancy-index (mmap-friendly: reads only the selected blocks' rows)
    feat_src = np.asarray(cb.feat_local[sel])
    rows_src = np.asarray(cb.rows[sel])
    vals_src = np.asarray(cb.values[sel])
    E_src = feat_src.shape[1]
    s = rows_src // per  # (B, E_src) example shard per entry (contiguous
    # ranges); cb pad entries (value == 0) sit at row 0 => shard 0, inert
    key = (
        np.arange(B, dtype=np.int64)[:, None] * D + s
    ).ravel()  # group = (block, shard)
    order = np.argsort(key, kind="stable")
    k_sorted = key[order]
    counts = np.bincount(key, minlength=B * D)
    starts = np.zeros(B * D + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(B * E_src, dtype=np.int64) - starts[k_sorted]
    E = max(1, int(counts.max()))
    if pad_pow2:
        E = 1 << (E - 1).bit_length()
    feat = np.zeros((B * D, E), dtype=feat_src.dtype)
    rows = np.zeros((B * D, E), dtype=rows_src.dtype)
    vals = np.zeros((B * D, E), dtype=vals_src.dtype)
    local_rows = rows_src - s * per  # localize BEFORE packing: packed
    # padding slots stay 0 (a valid inert local row), never negative
    feat[k_sorted, pos] = feat_src.ravel()[order]
    rows[k_sorted, pos] = local_rows.ravel()[order]
    vals[k_sorted, pos] = vals_src.ravel()[order]
    return {
        "feat_local": feat.reshape(B, D, E),
        "rows": rows.reshape(B, D, E),
        "values": vals.reshape(B, D, E),
        "block_idx": sel.astype(np.int32),
        "counts": counts.reshape(B, D),
        "per_shard_examples": per,
    }


class DarlinSpmdFns:
    """The jitted mesh programs of the distributed solver.

    pass_resident / kkt_resident — scan over a permutation array, gathering
      each block's entries from DEVICE-RESIDENT stacked arrays (device_put
      once per solve; the per-iteration block shuffle never re-uploads or
      re-materializes the data).
    pass_chunk / kkt_chunk — scan over a streamed chunk of blocks handed in
      as its own (C, D, E) arrays (the bounded-memory path; each distinct
      (C, E) pair compiles once — the streaming driver pads E to powers of
      two to bound that).
    obj — pod-wide objective; place — put host arrays with solver sharding.
    """

    def __init__(self, **fns):
        self.__dict__.update(fns)


def make_darlin_spmd_fns(
    mesh,
    *,
    num_keys: int,
    block_size: int,
    per_shard_examples: int,
    lambda_l1: float,
    lambda_l2: float,
    learning_rate: float,
    delay: int,
) -> DarlinSpmdFns:
    """Build the solver's jitted mesh programs (see DarlinSpmdFns).

    Layout: w/active P("kv"); pred/labels/mask P("data", None); block entry
    arrays P(None, "data", None). Requires num_keys divisible by kv and
    every block wholly inside one kv range (n_blocks % kv_shards == 0 with
    contiguous equal blocks).
    """
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    kv = mesh.shape["kv"]
    if num_keys % kv:
        raise ValueError(f"num_keys {num_keys} not divisible by kv={kv}")
    shard_size = num_keys // kv
    if shard_size % block_size:
        raise ValueError(
            f"kv range {shard_size} not aligned to block_size {block_size}: "
            "each feature block must live wholly on one kv shard"
        )
    per = per_shard_examples

    def _bcast_from_owner(x, is_owner):
        """Broadcast the owning kv shard's value to all (pull)."""
        return lax.psum(jnp.where(is_owner, x, jnp.zeros_like(x)), "kv")

    def _block_grad(pred_l, y_l, mask_l, fl, rows, vals):
        p = jax.nn.sigmoid(pred_l)
        err = (p - y_l) * mask_l
        h_ex = p * (1.0 - p) * mask_l
        g = jax.ops.segment_sum(
            vals * jnp.take(err, rows), fl, num_segments=block_size
        )
        h = jax.ops.segment_sum(
            vals * vals * jnp.take(h_ex, rows), fl, num_segments=block_size
        )
        return lax.psum(g, "data"), lax.psum(h, "data")  # push

    def _block_body(carry, fl, rows, vals, b_idx, y_l, mask_l):
        """One block's proximal step — shared by both pass variants so the
        trajectory-parity contract with the single-device solver lives in
        exactly one place."""
        w_l, pred_l, stale_pred, active_l, viol_max, i = carry
        refresh = (i % (delay + 1)) == 0
        stale_pred = jnp.where(refresh, pred_l, stale_pred)
        my_k = lax.axis_index("kv")
        begin = b_idx * block_size
        owner = begin // shard_size
        is_owner = owner == my_k
        safe_begin = jnp.where(is_owner, begin - owner * shard_size, 0)

        g, h = _block_grad(stale_pred, y_l, mask_l, fl, rows, vals)
        w_b = _bcast_from_owner(
            lax.dynamic_slice(w_l, (safe_begin,), (block_size,)), is_owner
        )
        act_b = (
            _bcast_from_owner(
                lax.dynamic_slice(
                    active_l.astype(jnp.float32), (safe_begin,), (block_size,)
                ),
                is_owner,
            )
            > 0
        )

        viol = _kkt_viol(w_b, g, lambda_l1)
        viol_max = jnp.maximum(viol_max, viol.max())
        skip = (~act_b) & (w_b == 0.0)
        d = _prox_newton_direction(
            w_b, g, h, skip, lambda_l1, lambda_l2, learning_rate
        )
        # my example shard's X_b @ d; the line-search objective is the
        # TRUE pod-wide objective (masked nll psum'd over "data")
        Xd_l = jax.ops.segment_sum(
            vals * jnp.take(d, fl), rows, num_segments=per
        )
        alpha = _line_search_alpha(
            pred_l, Xd_l, y_l, w_b, d, lambda_l1, lambda_l2,
            mask=mask_l, reduce=lambda x: lax.psum(x, "data"),
        )

        new_w_b = w_b + alpha * d
        w_l = jnp.where(
            is_owner,
            lax.dynamic_update_slice(w_l, new_w_b, (safe_begin,)),
            w_l,
        )
        pred_l = pred_l + alpha * Xd_l
        return (w_l, pred_l, stale_pred, active_l, viol_max, i + 1)

    def _kkt_body(active_l, w_l, pred_l, y_l, mask_l, thr, fl, rows, vals, b_idx):
        my_k = lax.axis_index("kv")
        begin = b_idx * block_size
        owner = begin // shard_size
        is_owner = owner == my_k
        safe_begin = jnp.where(is_owner, begin - owner * shard_size, 0)
        g, _ = _block_grad(pred_l, y_l, mask_l, fl, rows, vals)
        w_b = _bcast_from_owner(
            lax.dynamic_slice(w_l, (safe_begin,), (block_size,)), is_owner
        )
        new_act = (w_b != 0.0) | (_kkt_viol(w_b, g, lambda_l1) > thr)
        return jnp.where(
            is_owner,
            lax.dynamic_update_slice(active_l, new_act, (safe_begin,)),
            active_l,
        )

    def _take_block(blocks_l, idx):
        """Gather block ``idx``'s local entries from the device-resident
        stacks (each a local (n_blocks, 1, E) slice under shard_map)."""
        return tuple(
            lax.dynamic_index_in_dim(blocks_l[k], idx, 0, keepdims=False)[0]
            for k in ("feat_local", "rows", "values")
        )

    def local_pass_resident(w_l, pred_l, active_l, blocks_l, order, y_l, mask_l):
        # squeeze this device's singleton data-axis slice
        pred_l, y_l, mask_l = pred_l[0], y_l[0], mask_l[0]

        def block_step(carry, idx):
            fl, rows, vals = _take_block(blocks_l, idx)
            return _block_body(carry, fl, rows, vals, idx, y_l, mask_l), None

        init = (w_l, pred_l, pred_l, active_l, jnp.float32(0.0), jnp.int32(0))
        (w_l, pred_l, _, active_l, viol_max, _), _ = lax.scan(
            block_step, init, order
        )
        return w_l, pred_l[None, :], viol_max

    def local_pass_chunk(w_l, pred_l, active_l, chunk_l, y_l, mask_l):
        pred_l, y_l, mask_l = pred_l[0], y_l[0], mask_l[0]

        def block_step(carry, blk):
            return (
                _block_body(
                    carry,
                    blk["feat_local"][0], blk["rows"][0], blk["values"][0],
                    blk["block_idx"], y_l, mask_l,
                ),
                None,
            )

        init = (w_l, pred_l, pred_l, active_l, jnp.float32(0.0), jnp.int32(0))
        (w_l, pred_l, _, active_l, viol_max, _), _ = lax.scan(
            block_step, init, chunk_l
        )
        return w_l, pred_l[None, :], viol_max

    def local_kkt_resident(w_l, pred_l, active_l, blocks_l, order, y_l, mask_l, thr):
        pred_l, y_l, mask_l = pred_l[0], y_l[0], mask_l[0]

        def block_step(active_l, idx):
            fl, rows, vals = _take_block(blocks_l, idx)
            return (
                _kkt_body(
                    active_l, w_l, pred_l, y_l, mask_l, thr, fl, rows, vals, idx
                ),
                None,
            )

        active_l, _ = lax.scan(block_step, active_l, order)
        return active_l

    def local_kkt_chunk(w_l, pred_l, active_l, chunk_l, y_l, mask_l, thr):
        pred_l, y_l, mask_l = pred_l[0], y_l[0], mask_l[0]

        def block_step(active_l, blk):
            return (
                _kkt_body(
                    active_l, w_l, pred_l, y_l, mask_l, thr,
                    blk["feat_local"][0], blk["rows"][0], blk["values"][0],
                    blk["block_idx"],
                ),
                None,
            )

        active_l, _ = lax.scan(block_step, active_l, chunk_l)
        return active_l

    def local_obj(w_l, pred_l, y_l, mask_l):
        pred_l, y_l, mask_l = pred_l[0], y_l[0], mask_l[0]
        nll = lax.psum(
            jnp.sum(mask_l * (jax.nn.softplus(pred_l) - y_l * pred_l)), "data"
        )
        reg = lax.psum(
            lambda_l1 * jnp.abs(w_l).sum() + 0.5 * lambda_l2 * (w_l * w_l).sum(),
            "kv",
        )
        return nll + reg

    kv_s, dat, blk_s = P("kv"), P("data", None), P(None, "data", None)
    resident_spec = {"feat_local": blk_s, "rows": blk_s, "values": blk_s}
    chunk_spec = {**resident_spec, "block_idx": P(None)}
    pass_resident = jax.jit(
        shard_map(
            local_pass_resident, mesh=mesh,
            in_specs=(kv_s, dat, kv_s, resident_spec, P(None), dat, dat),
            out_specs=(kv_s, dat, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    pass_chunk = jax.jit(
        shard_map(
            local_pass_chunk, mesh=mesh,
            in_specs=(kv_s, dat, kv_s, chunk_spec, dat, dat),
            out_specs=(kv_s, dat, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    kkt_resident = jax.jit(
        shard_map(
            local_kkt_resident, mesh=mesh,
            in_specs=(kv_s, dat, kv_s, resident_spec, P(None), dat, dat, P()),
            out_specs=kv_s,
            check_vma=False,
        )
    )
    kkt_chunk = jax.jit(
        shard_map(
            local_kkt_chunk, mesh=mesh,
            in_specs=(kv_s, dat, kv_s, chunk_spec, dat, dat, P()),
            out_specs=kv_s,
            check_vma=False,
        )
    )
    obj_fn = jax.jit(
        shard_map(
            local_obj, mesh=mesh,
            in_specs=(kv_s, dat, dat, dat),
            out_specs=P(),
            check_vma=False,
        )
    )

    def place(name: str, arr: np.ndarray):
        spec = {"w": kv_s, "active": kv_s, "pred": dat, "labels": dat, "mask": dat}[name]
        return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))

    def place_blocks(sharded: dict, with_idx: bool):
        sh = NamedSharding(mesh, blk_s)
        out = {
            k: jax.device_put(jnp.asarray(sharded[k]), sh)
            for k in ("feat_local", "rows", "values")
        }
        if with_idx:
            out["block_idx"] = jax.device_put(
                jnp.asarray(sharded["block_idx"]), NamedSharding(mesh, P(None))
            )
        return out

    return DarlinSpmdFns(
        pass_resident=pass_resident,
        pass_chunk=pass_chunk,
        kkt_resident=kkt_resident,
        kkt_chunk=kkt_chunk,
        obj=obj_fn,
        place=place,
        place_blocks=place_blocks,
    )


class Darlin:
    """Batch L1-LR solver app (scheduler role of the reference's Darlin*).

    With ``mesh`` (a (data, kv) device mesh) the solver runs distributed:
    example shards over "data", weight ranges over "kv" — the reference's
    worker/server split (SURVEY §3.3)."""

    def __init__(
        self,
        cfg: PSConfig,
        reporter: ProgressReporter | None = None,
        mesh=None,
    ):
        self.cfg = cfg
        self.reporter = reporter or ProgressReporter()
        self.mesh = mesh

    def fit(
        self,
        batches: list[CSRBatch],
        shuffle_blocks: bool = True,
    ) -> dict:
        cb = ColumnBlocks.from_batches(
            batches, self.cfg.data.num_keys, self.cfg.solver.feature_blocks
        )
        return self.fit_blocks(cb, shuffle_blocks=shuffle_blocks)

    def fit_blocks(self, cb: ColumnBlocks, shuffle_blocks: bool = True) -> dict:
        if self.mesh is not None:
            return self._fit_blocks_spmd(cb, shuffle_blocks=shuffle_blocks)
        return self._fit_blocks_single(cb, shuffle_blocks=shuffle_blocks)

    def _fit_blocks_spmd(self, cb: ColumnBlocks, shuffle_blocks: bool = True) -> dict:
        """Distributed solve over the mesh (see module section above).

        Two data-residency modes (cfg.solver.block_chunk):
          0 (default) — resident: the packed (n_blocks, D, E) entry arrays
            are device_put ONCE; the per-iteration block shuffle is just a
            permutation array the on-device scan gathers through.
          C > 0 — streaming: each pass packs+uploads C blocks at a time
            straight from the (possibly mmap'd) block cache, so device and
            host memory hold one chunk, not the dataset (ref: SlotReader's
            stream-per-block design, SURVEY §3.3). Chunk widths pad to
            powers of two to bound recompilation. With delay > 0 the stale
            snapshot refreshes at chunk boundaries (a conservative
            deviation: pick C a multiple of delay+1 to keep parity).
        """
        cfg = self.cfg
        mesh = self.mesh
        D = mesh.shape["data"]
        chunk = cfg.solver.block_chunk
        ex = shard_examples_for_mesh(cb, D)
        per = ex["per_shard_examples"]
        fns = make_darlin_spmd_fns(
            mesh,
            num_keys=cb.num_keys,
            block_size=cb.block_size,
            per_shard_examples=per,
            lambda_l1=cfg.penalty.lambda_l1,
            lambda_l2=cfg.penalty.lambda_l2,
            learning_rate=cfg.lr.eta,
            delay=cfg.solver.max_delay if cfg.solver.max_delay > 0 else 0,
        )
        w = fns.place("w", np.zeros(cb.num_keys, np.float32))
        active = fns.place("active", np.ones(cb.num_keys, bool))
        pred = fns.place("pred", np.zeros((D, per), np.float32))
        labels = fns.place("labels", ex["labels"])
        mask = fns.place("mask", ex["mask"])
        rng = np.random.default_rng(cfg.seed)

        resident_blocks = None
        if chunk <= 0:
            resident_blocks = fns.place_blocks(
                shard_blocks_for_mesh(cb, D), with_idx=False
            )

        def _chunks(order):
            for lo in range(0, len(order), chunk):
                yield fns.place_blocks(
                    shard_blocks_for_mesh(
                        cb, D, blocks=order[lo : lo + chunk], pad_pow2=True
                    ),
                    with_idx=True,
                )

        prev_obj = float(fns.obj(w, pred, labels, mask))
        history = []
        for it in range(cfg.solver.block_iters):
            order = (
                rng.permutation(cb.n_blocks)
                if shuffle_blocks
                else np.arange(cb.n_blocks)
            )
            if resident_blocks is not None:
                w, pred, viol = fns.pass_resident(
                    w, pred, active, resident_blocks,
                    order.astype(np.int32), labels, mask,
                )
            else:
                viol = jnp.float32(0.0)
                for blk in _chunks(order):
                    w, pred, v = fns.pass_chunk(
                        w, pred, active, blk, labels, mask
                    )
                    viol = jnp.maximum(viol, v)
            if cfg.solver.kkt_filter_threshold > 0:
                thr = cfg.solver.kkt_filter_threshold * max(float(viol), 1e-12)
                if resident_blocks is not None:
                    active = fns.kkt_resident(
                        w, pred, active, resident_blocks,
                        order.astype(np.int32), labels, mask, jnp.float32(thr),
                    )
                else:
                    for blk in _chunks(order):
                        active = fns.kkt_chunk(
                            w, pred, active, blk, labels, mask, jnp.float32(thr)
                        )
            obj = float(fns.obj(w, pred, labels, mask))
            rel = (prev_obj - obj) / max(abs(prev_obj), 1e-12)
            nnz = int((np.asarray(w) != 0).sum())
            self.reporter.report(
                examples=cb.num_examples, objv=obj / cb.num_examples,
                nnz_w=nnz, auc=float("nan"),
            )
            history.append(obj)
            if 0 <= rel < cfg.solver.epsilon and it > 0:
                break
            prev_obj = obj

        self.w = np.asarray(w)
        real = np.asarray(mask).ravel() > 0
        self.pred = np.asarray(pred).ravel()[real]
        probs = 1.0 / (1.0 + np.exp(-self.pred))
        return {
            "objv": history[-1] / cb.num_examples,
            "iters": len(history),
            "nnz_w": int((self.w != 0).sum()),
            "train_auc": M.auc(cb.labels, probs),
            "history": history,
        }

    def _fit_blocks_single(self, cb: ColumnBlocks, shuffle_blocks: bool = True) -> dict:
        """Run the solver on prebuilt (possibly disk-cached) column blocks."""
        cfg = self.cfg
        K, N = cb.num_keys, cb.num_examples
        w = jnp.zeros(K, dtype=jnp.float32)
        pred = jnp.zeros(N, dtype=jnp.float32)
        active = jnp.ones(K, dtype=bool)
        labels = jnp.asarray(cb.labels)
        rng = np.random.default_rng(cfg.seed)

        prev_obj = float(_objective(w, pred, labels, cfg.penalty.lambda_l1, cfg.penalty.lambda_l2))
        history = []
        for it in range(cfg.solver.block_iters):
            order = (
                rng.permutation(cb.n_blocks)
                if shuffle_blocks
                else np.arange(cb.n_blocks)
            )  # ref: randomized block order per iteration
            blocks = {
                "feat_local": jnp.asarray(cb.feat_local[order]),
                "rows": jnp.asarray(cb.rows[order]),
                "values": jnp.asarray(cb.values[order]),
                "block_idx": jnp.asarray(order.astype(np.int32)),
            }
            w, pred, active, viol = darlin_pass(
                w,
                pred,
                active,
                blocks,
                labels,
                cfg.penalty.lambda_l1,
                cfg.penalty.lambda_l2,
                cfg.lr.eta,
                cfg.solver.kkt_filter_threshold,
                block_size=cb.block_size,
                num_examples=N,
                delay=cfg.solver.max_delay if cfg.solver.max_delay > 0 else 0,
            )
            if cfg.solver.kkt_filter_threshold > 0:
                # refresh the active set from the violation scale (ref: the
                # KKT filter's adaptive threshold)
                active = self._kkt_active(
                    w, pred, labels, cb, float(viol)
                )
            obj = float(
                _objective(w, pred, labels, cfg.penalty.lambda_l1, cfg.penalty.lambda_l2)
            )
            rel = (prev_obj - obj) / max(abs(prev_obj), 1e-12)
            nnz = int((np.asarray(w) != 0).sum())
            rec = self.reporter.report(
                examples=N, objv=obj / N, nnz_w=nnz, auc=float("nan")
            )
            history.append(obj)
            if 0 <= rel < cfg.solver.epsilon and it > 0:
                break
            prev_obj = obj

        self.w = np.asarray(w)
        self.pred = np.asarray(pred)
        probs = 1.0 / (1.0 + np.exp(-self.pred))
        return {
            "objv": history[-1] / N,
            "iters": len(history),
            "nnz_w": int((self.w != 0).sum()),
            "train_auc": M.auc(cb.labels, probs),
            "history": history,
        }

    def _kkt_active(self, w, pred, labels, cb: ColumnBlocks, viol_max: float):
        """Recompute the active bitmap: keep coords with weight, or with
        gradient violation above threshold * max violation."""
        thr = self.cfg.solver.kkt_filter_threshold * max(viol_max, 1e-12)
        p = jax.nn.sigmoid(pred)
        err = p - labels
        g = np.zeros(cb.num_keys, dtype=np.float32)
        for i in range(cb.n_blocks):
            gi = jax.ops.segment_sum(
                jnp.asarray(cb.values[i])
                * jnp.take(err, jnp.asarray(cb.rows[i])),
                jnp.asarray(cb.feat_local[i]),
                num_segments=cb.block_size,
            )
            g[i * cb.block_size : (i + 1) * cb.block_size] = np.asarray(gi)
        w_np = np.asarray(w)
        viol = np.asarray(
            _kkt_viol(jnp.asarray(w_np), jnp.asarray(g), self.cfg.penalty.lambda_l1)
        )
        return jnp.asarray((w_np != 0.0) | (viol > thr))

    def predict(self, batches: list[CSRBatch]) -> np.ndarray:
        from parameter_server_tpu.models.linear import batch_to_device
        from parameter_server_tpu.ops.sparse import csr_logits

        out = []
        w = jnp.asarray(self.w)[:, None]
        for b in batches:
            dev = batch_to_device(b)
            w_u = jnp.take(w, dev["unique_keys"], axis=0)
            logits = csr_logits(
                w_u, dev["values"], dev["local_ids"], dev["row_ids"],
                num_rows=dev["labels"].shape[0],
            )
            out.append(
                np.asarray(jax.nn.sigmoid(logits))[: b.num_examples]
            )
        return np.concatenate(out)
