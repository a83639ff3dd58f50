"""SPMD pull/push: the reference's wire protocol re-expressed as collectives.

Reference analog, mapped one-to-one:

  Executor::Submit slicing a pulled key set across server ranges
    (src/system/executor.*, parallel_ordered_match)      -> masked local
    gather against this shard's contiguous range + ``psum`` over the "kv"
    axis (out-of-range rows contribute zero).
  Worker Push of per-minibatch gradients to the server group
    (src/parameter/shared_parameter.h kPush)             -> ``all_gather``
    of (keys, grads) over the "data" axis, then each kv shard applies every
    worker's push **sequentially** (a lax.scan), which reproduces the
    reference server's semantics of applying each worker's push as its own
    nonlinear updater step — NOT a pre-averaged BSP step.
  Server updater application (FTRL/AdaGrad/SGD entries)  -> exact additive
    deltas scattered with ``.at[].add`` (deterministic under padding).

State layout: every table is (num_keys, vdim) sharded over "kv" on axis 0.
``num_keys`` need not divide the kv axis size: tables are zero-padded up
to the next axis multiple (``padded_num_keys``) and the pad rows stay
exactly zero under the store's pad-row invariant (batch keys are always
below the real ``num_keys``, so no push ever touches them). Batches are
per-data-shard CSRBatches stacked on a leading axis and sharded over
"data".
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.kv.updaters import Updater
from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

State = dict[str, jax.Array]
Batch = dict[str, jax.Array]

# Phase names of one parameter-server step, as ``jax.named_scope``s in the
# step and predict programs and in ``kv.store``. A contract: the benchmark's
# ``step.*_ms`` readers and PERF.md find device time by these names (through
# ``op_scopes``), so whatever replaces the code underneath keeps them.
# Inside "ps.push" three nested scopes: "gather" (rows read for the
# updater), "update" (``updater.delta``), "scatter" (the ``.at[].add``).
PHASE_SCOPES = ("ps.row_ids", "ps.pull", "ps.grad", "ps.push")
_PUSH_STAGES = ("gather", "update", "scatter")

@dataclasses.dataclass
class _RanProgram:
    """A step or predict program and the abstract arguments of its first
    call with one set of shapes: what ``op_scopes`` compiles again, after
    the run, to read the names (kept in ``scopes`` once read)."""

    jitted: Any
    args: tuple
    scopes: tuple[str, dict[str, str]] | None = None


_ran: list[_RanProgram] = []


def _note_program(jitted, seen: set, *args) -> None:
    """Remember the shapes, dtypes and shardings a step or predict program
    is called with, once a distinct set: a dict lookup on the dispatch
    path, nothing compiled or read here."""
    key = tuple(
        (getattr(x, "shape", None), getattr(x, "dtype", None))
        for x in jax.tree.leaves(args)
    )
    if key in seen:
        return
    seen.add(key)

    def abstract(x):
        if not hasattr(x, "shape"):
            return x  # a Python scalar keeps its weak type
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        )

    _ran.append(_RanProgram(jitted, jax.tree.map(abstract, args)))


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: scope path}) of one optimised HLO
    module's text. The scope path is the ``ps.*`` phase in the
    instruction's ``op_name`` metadata (a fusion carries its root's), with
    the push's nested stage after a slash (``ps.push/scatter``); ``""`` for
    an instruction that carries none (input copies, some custom calls)."""
    module = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    out: dict[str, str] = {}
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", hlo_text, re.M
    ):
        op_name = re.search(r'op_name="([^"]*)"', m.group(2))
        out[m.group(1)] = _scope_of(op_name.group(1)) if op_name else ""
    return (module.group(1) if module else ""), out


def _scope_of(op_name: str) -> str:
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in PHASE_SCOPES:
            if part == "ps.push":
                # the last part is the primitive's own name ("gather")
                for stage in parts[i + 1 : -1]:
                    if stage in _PUSH_STAGES:
                        return f"{part}/{stage}"
            return part
    return ""


def op_scopes() -> dict[str, dict[str, str]]:
    """{HLO module name: {instruction name: scope path}} of every step and
    predict program this process has run, read from the optimised HLO of
    the executable (``hlo_scopes``). A profile names device ops by
    instruction; this says which phase of the parameter-server step each
    belongs to, whatever XLA numbered its fusions this time.

    Derived here, on request, from the shapes the steppers were first
    called with: compiling them again is a fetch from the persistent
    compile cache where one is set, a compile otherwise, so call it after
    the timed part of a run. Programs that share a module name (one step at
    two bucket shapes) share a map; an instruction they scope differently
    reads ``""``, as an unscoped one does."""
    out: dict[str, dict[str, str]] = {}
    for ran in _ran:
        if ran.scopes is None:
            compiled = ran.jitted.lower(*ran.args).compile()
            ran.scopes = hlo_scopes(compiled.as_text())
        module, scopes = ran.scopes
        have = out.setdefault(module, {})
        for name, scope in scopes.items():
            have[name] = scope if have.get(name, scope) == scope else ""
    return out


def state_spec() -> P:
    return P("kv", None)


def batch_spec() -> P:
    return P("data", None)


def shard_state(state: State, mesh: Mesh) -> State:
    """Place a replicated/host state dict range-sharded over the kv axis,
    zero-padding the tables up to the next kv-axis multiple first (the
    pad rows are inert — see ``kv.store.pad_state_rows``)."""
    from parameter_server_tpu.kv.store import pad_state_rows

    rows = next(iter(state.values())).shape[0]
    state = pad_state_rows(state, padded_num_keys(rows, mesh.shape["kv"]))
    sh = NamedSharding(mesh, state_spec())
    return {k: jax.device_put(v, sh) for k, v in state.items()}


def stack_fields(
    batches: list, fields: tuple[str, ...], mesh: Mesh | None = None
) -> Batch:
    """Stack the named attributes of D per-worker batches on a leading axis;
    with a mesh, place the result sharded over the "data" axis. Without a
    mesh the stacks stay host-side numpy — callers either feed them to jit
    directly or hand them to Runtime.globalize_batch (which must not pay a
    device round-trip first)."""
    import numpy as np

    out = {f: np.stack([getattr(b, f) for b in batches]) for f in fields}
    return out if mesh is None else place_stacked(out, mesh)


def place_stacked(stacked: dict, mesh: Mesh) -> dict:
    """Place already-stacked (D, ...) host arrays sharded over "data" —
    the one home for the data-axis placement spec (apps share it)."""
    sh = NamedSharding(mesh, batch_spec())
    return {k: jax.device_put(v, sh) for k, v in stacked.items()}


CSR_FULL_FIELDS = (
    "unique_keys", "local_ids", "row_ids", "values", "labels", "example_mask",
)
# Compact wire format: row structure rides as (B+1,) row_splits instead of
# (NNZ,) row_ids — ~40% fewer host->device bytes at typical densities; the
# device rebuilds row ids by marking the splits and summing along the
# entries (see _row_ids_of).
CSR_COMPACT_FIELDS = (
    "unique_keys", "local_ids", "row_splits", "values", "labels", "example_mask",
)


_F16_MAX = 65504.0  # largest finite float16


def stack_batches(
    batches: list[CSRBatch],
    mesh: Mesh | None = None,
    compact: bool = False,
    values_f16: bool = False,
) -> Batch:
    """Stack D per-worker CSR batches; shard over "data".

    values_f16 (the data.wire_values="f16" knob) halves the value bytes
    on the feed: values are clipped to the finite f16 range (a silent
    inf from an un-scaled count feature would NaN the loss and poison
    the optimizer state) and cast; the device casts back to f32
    (_values_of). One home for the encode so every feed path — train and
    eval — gets the same wire."""
    import numpy as np

    out = stack_fields(
        batches, CSR_COMPACT_FIELDS if compact else CSR_FULL_FIELDS, None
    )
    if values_f16:
        out["values"] = np.clip(out["values"], -_F16_MAX, _F16_MAX).astype(
            np.float16
        )
    return out if mesh is None else place_stacked(out, mesh)


def _row_ids_of(b: Batch) -> jax.Array:
    """Entry -> example-row ids for one shard's batch: passthrough for the
    full wire format; for the compact one, rebuilt from the (B+1,)
    row_splits with no data-dependent loop. An entry's row is the number
    of interior splits at or before it, so the splits are marked in a
    zeroed (NNZ,) vector and summed along it. Empty rows repeat a split
    and their marks add up; a split equal to NNZ (a buffer filled to its
    last entry) falls off the end and is dropped. Padded entries (value
    0) land on the last row and stay inert under the masked loss/grad
    ops."""
    if "row_ids" in b:
        return b["row_ids"]
    nnz = b["values"].shape[0]
    num_rows = b["labels"].shape[0]
    marks = jnp.zeros((nnz,), jnp.int32)
    marks = marks.at[b["row_splits"][1:num_rows]].add(1, mode="drop")
    return _running_sum(marks)


def _running_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sums of a vector as log2(n) shifted adds, each one
    streaming pass. Not jnp.cumsum: the TPU compiler turns that into a
    tree of reduce-windows that is slow to compile inside the scanned step
    and whose pieces lose their op_name, so a profile could not file them
    under ps.row_ids (PERF.md, PR 25)."""
    k = 1
    while k < x.shape[0]:
        x = x + jnp.pad(x[:-k], (k, 0))
        k *= 2
    return x


def _values_of(b: Batch) -> jax.Array:
    """Feature values in compute precision: f16-wire batches (the
    data.wire_values knob — half the value bytes on the feed) cast back
    to f32 on-device; f32 wires pass through."""
    v = b["values"]
    return v.astype(jnp.float32) if v.dtype != jnp.float32 else v


def _local_pull(
    updater: Updater, state_l: State, idx: jax.Array, shard_size: int
) -> jax.Array:
    """This shard's contribution to pulled weights for global ids ``idx``."""
    begin = lax.axis_index("kv") * shard_size
    local = idx - begin
    in_range = (local >= 0) & (local < shard_size)
    safe = jnp.where(in_range, local, 0)
    rows = {k: jnp.take(v, safe, axis=0) for k, v in state_l.items()}
    w = updater.weights(rows)
    return jnp.where(in_range[:, None], w, 0.0)


def _local_push(
    updater: Updater,
    state_l: State,
    all_idx: jax.Array,  # (D, U) pushes from every data shard
    all_grad: jax.Array,  # (D, U, vdim)
    shard_size: int,
) -> State:
    """Apply every worker's push to this kv shard, sequentially (ref: the
    server processes each worker's Push message as its own updater step)."""
    begin = lax.axis_index("kv") * shard_size

    def body(state_l: State, push: tuple[jax.Array, jax.Array]):
        idx, g = push
        local = idx - begin
        in_range = (local >= 0) & (local < shard_size)
        safe = jnp.where(in_range, local, 0)
        with jax.named_scope("gather"):
            rows = {k: jnp.take(v, safe, axis=0) for k, v in state_l.items()}
        with jax.named_scope("update"):
            deltas = updater.delta(rows, g)
        with jax.named_scope("scatter"):
            mask = in_range[:, None].astype(g.dtype)
            new = {
                k: state_l[k].at[safe].add(mask * deltas[k]) for k in state_l
            }
        return new, None

    new_state, _ = lax.scan(body, state_l, (all_idx, all_grad))
    return new_state


def _local_push_aggregate(
    updater: Updater,
    state_l: State,
    idx: jax.Array,  # (U,) this data shard's unique keys
    grad: jax.Array,  # (U, vdim) this data shard's per-key grads
    shard_size: int,
) -> State:
    """Aggregate-then-update push (the BASELINE north star's
    "push ≡ reduce-scatter"): every data shard scatters its grads into a
    dense buffer covering ONLY this device's kv range, a single ``psum``
    over "data" pre-sums them, and the updater applies ONE step to the
    touched rows.

    vs ``_local_push``: O(1) updater applications instead of an O(D)
    serialized scan, and the wire moves 2·S rows (ring psum of the range
    slice) instead of D·U gathered rows — the win grows with data shards.

    Semantic difference (documented, opt-in): the reference server applies
    each worker's push as its own updater step; this mode applies the
    SUMMED gradient once. For linear deltas (plain SGD, lambda_l2=0) the
    two are exactly equal; for FTRL/AdaGrad this is standard synchronous
    minibatch aggregation (same fixed point, different trajectory).
    """
    begin = lax.axis_index("kv") * shard_size
    local = idx - begin
    in_range = (local >= 0) & (local < shard_size)
    safe = jnp.where(in_range, local, 0)
    mask = in_range[:, None].astype(grad.dtype)
    vdim = grad.shape[-1]
    with jax.named_scope("scatter"):
        g_slice = jnp.zeros((shard_size, vdim), grad.dtype).at[safe].add(
            mask * grad
        )
        touched = jnp.zeros((shard_size, 1), grad.dtype).at[safe].add(mask)
    # one collective pre-sums every worker's contribution to this range
    g_slice = lax.psum(g_slice, "data")
    touched = lax.psum(touched, "data")
    # no "gather" here: the updater reads the whole range slice
    with jax.named_scope("update"):
        deltas = updater.delta(state_l, g_slice)
        hit = (touched > 0).astype(grad.dtype)
        return {k: state_l[k] + hit * deltas[k] for k in state_l}


def _local_push_quantized(
    updater: Updater,
    state_l: State,
    idx: jax.Array,  # (U,) this data shard's unique keys
    grad: jax.Array,  # (U, vdim)
    shard_size: int,
    push_seed: jax.Array,  # scalar int32, varies per step
    stream: int = 0,  # static sub-stream tag (multi-table apps: one per table)
) -> State:
    """Per-worker push with int8-quantized gradients on the wire (the
    reference's fixing_float filter re-expressed as a quantized
    COLLECTIVE, cf. EQuARX): each data shard quantizes its gradient
    symmetrically to int8 with one f32 scale and stochastic (unbiased)
    rounding; the all_gather then moves 1 byte per value instead of 4 —
    the payload that dominates cross-slice DCN traffic. Dequantization
    happens after the gather, so server semantics stay exactly
    ``_local_push`` (each worker's push is its own updater step).

    ``stream`` decorrelates the rounding noise between pushes that share
    one push_seed (Wide&Deep pushes two tables per microstep); 0 keeps
    the original key schedule, so single-table trajectories are stable."""
    key = jax.random.fold_in(
        jax.random.key(push_seed), lax.axis_index("data")
    )
    if stream:
        key = jax.random.fold_in(key, stream)
    scale = jnp.max(jnp.abs(grad)) / 127.0 + 1e-30
    t = grad / scale
    floor = jnp.floor(t)
    q = floor + (jax.random.uniform(key, grad.shape) < (t - floor))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    # the wire: indices + int8 payload + one scale per worker
    all_idx = lax.all_gather(idx, "data")  # (D, U)
    all_q = lax.all_gather(q, "data")  # (D, U, vdim) int8
    all_scale = lax.all_gather(scale, "data")  # (D,)
    all_grad = all_q.astype(grad.dtype) * all_scale[:, None, None]
    return _local_push(updater, state_l, all_idx, all_grad, shard_size)


PUSH_MODES = ("per_worker", "aggregate", "quantized")


def padded_num_keys(num_keys: int, kv_size: int) -> int:
    """``num_keys`` rounded up to the next multiple of the kv axis size —
    the table rows the sharded tiers actually allocate. The rows past the
    real ``num_keys`` are pad rows: exactly zero and never touched (the
    data layer only emits keys below ``num_keys``), so arbitrary table
    sizes run on any mesh shape with no semantic change."""
    if num_keys < 1:
        raise ValueError(f"num_keys must be >= 1, got {num_keys}")
    return -(-num_keys // kv_size) * kv_size


def _shard_size(num_keys: int, kv_size: int) -> int:
    return padded_num_keys(num_keys, kv_size) // kv_size


def _wrap_stepper(step, push_mode: str):
    """Shared jit + push_seed contract for the single- and multi-step
    makers (one home for the quantized-seed guard): ``step`` is the
    shard_map'd program (state, batch, seed) -> (state, loss, ex, probs)."""

    @functools.partial(jax.jit, donate_argnums=0)
    def _jitted(state: State, batch: Batch, push_seed):
        new_state, loss, ex, probs = step(state, batch, jnp.int32(push_seed))
        return new_state, {"loss_sum": loss, "examples": ex, "probs": probs}

    seen: set = set()

    def stepper(state: State, batch: Batch, push_seed=None):
        if push_seed is None:
            if push_mode == "quantized":
                # a silently-defaulted seed would reuse the same PRNG key
                # every step, correlating the stochastic rounding noise
                # instead of averaging it out
                raise ValueError(
                    "quantized push mode requires a per-step push_seed: "
                    "call step(state, batch, step_index)"
                )
            push_seed = 0
        _note_program(_jitted, seen, state, batch, push_seed)
        return _jitted(state, batch, push_seed)

    return stepper


def _microstep(
    updater: Updater,
    state_l: State,
    b: Batch,  # one data shard's un-stacked batch fields
    shard_size: int,
    push_mode: str,
    push_seed: jax.Array,
):
    """One parameter-server step on this device: pull -> CSR grad -> push.
    Shared verbatim by the single-step and scanned multi-step programs so
    the wire semantics cannot diverge between them."""
    idx = b["unique_keys"]
    with jax.named_scope("ps.row_ids"):
        row_ids = _row_ids_of(b)
    with jax.named_scope("ps.pull"):
        w_u = lax.psum(
            _local_pull(updater, state_l, idx, shard_size), "kv"
        )  # Pull: slice + merge (ref kv_vector match)
    with jax.named_scope("ps.grad"):
        values = _values_of(b)
        logits = csr_logits(
            w_u, values, b["local_ids"], row_ids,
            num_rows=b["labels"].shape[0],
        )
        loss, err = logistic_loss(logits, b["labels"], b["example_mask"])
        g = csr_grad(
            err, values, b["local_ids"], row_ids, num_unique=idx.shape[0]
        )
        probs = jax.nn.sigmoid(logits)
    with jax.named_scope("ps.push"):
        if push_mode == "aggregate":
            new_state = _local_push_aggregate(
                updater, state_l, idx, g, shard_size
            )
        elif push_mode == "quantized":
            new_state = _local_push_quantized(
                updater, state_l, idx, g, shard_size, push_seed
            )
        else:
            # Push: every data shard's (keys, grads) reach every kv shard.
            all_idx = lax.all_gather(idx, "data")  # (D, U)
            all_grad = lax.all_gather(g, "data")  # (D, U, vdim)
            new_state = _local_push(
                updater, state_l, all_idx, all_grad, shard_size
            )
    loss_sum = lax.psum(loss, "data")
    # pod-wide real-example count: the host-side termination signal
    # (a drained host keeps feeding empty batches; every host stops
    # deterministically after retiring a step with examples == 0 —
    # this rides async dispatch instead of a blocking host barrier)
    examples = lax.psum(jnp.sum(b["example_mask"]), "data")
    return new_state, loss_sum, examples, probs


def make_spmd_train_step(
    updater: Updater, mesh: Mesh, num_keys: int, push_mode: str = "per_worker"
):
    """Build the jitted multi-device train step.

    step(state, batch) -> (state, out) with out keys:
      "loss_sum" — scalar, psum over data
      "examples" — scalar pod-wide real-example count (the host-side
          termination signal; see PodTrainer's drained contract)
      "probs"    — (D, B) per-shard probabilities

    push_mode:
      "per_worker" — faithful reference semantics: each data shard's push is
          its own server updater step (all_gather + sequential scan).
      "aggregate"  — pre-sum per-key grads across data shards with one psum,
          apply one updater step (see ``_local_push_aggregate``; exactly
          equal for linear SGD, standard sync aggregation otherwise).
      "quantized"  — per_worker semantics with int8 gradients on the wire
          (see ``_local_push_quantized``; the fixing_float filter as a
          quantized collective for DCN-limited pods).
    """
    if push_mode not in PUSH_MODES:
        raise ValueError(f"unknown push_mode {push_mode!r}; known: {PUSH_MODES}")
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def local_step(state_l: State, batch: Batch, push_seed: jax.Array):
        b = {k: v[0] for k, v in batch.items()}  # this data shard's batch
        new_state, loss_sum, examples, probs = _microstep(
            updater, state_l, b, shard_size, push_mode, push_seed
        )
        return new_state, loss_sum, examples, probs[None, :]  # -> (D, B)

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec(), batch_spec(), P()),
        out_specs=(state_spec(), P(), P(), batch_spec()),
        check_vma=False,
    )
    return _wrap_stepper(step, push_mode)


def make_spmd_train_multistep(
    updater: Updater, mesh: Mesh, num_keys: int, push_mode: str = "per_worker"
):
    """K parameter-server steps per device call: ``lax.scan`` over a
    leading microstep axis inside ONE jitted shard_map program.

    Why: on a dispatch-bound host, per-step host->device
    round trips (transfer + dispatch + retirement sync) put a hard floor
    under examples/sec no matter how fast the chip is. Scanning K
    microsteps amortizes that floor K-fold: one transfer of K stacked
    batches in, one device program, one retirement out. The TPU idiom for
    the reference's bounded-delay pipelining of many small Push/Pull
    tasks (SURVEY §2.9 SSP): the steps stay SEQUENTIAL — microstep i+1
    pulls weights that include microstep i's push, exactly as if
    dispatched one by one — so the math is the single-step trajectory,
    not a K-times-larger batch.

    batch fields are stacked (D, K, ...): data shard leading (sharded),
    microstep second (scanned). step(state, batch, push_seed) ->
    (state, out) with out keys:
      "loss_sum" — (K,) per-microstep pod-wide loss sums
      "examples" — (K,) per-microstep pod-wide real-example counts (the
          termination contract checks the LAST entry: empties only ever
          trail real batches within a group)
      "probs"    — (D, K, B) per-shard, per-microstep probabilities
    """
    if push_mode not in PUSH_MODES:
        raise ValueError(f"unknown push_mode {push_mode!r}; known: {PUSH_MODES}")
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def local_step(state_l: State, batch: Batch, push_seed: jax.Array):
        b = {k: v[0] for k, v in batch.items()}  # this shard's (K, ...) group
        n_micro = b["labels"].shape[0]

        def body(st: State, micro):
            mb, i = micro
            # quantized mode: a distinct PRNG key per microstep (the
            # same per-step-seed contract as single-step dispatch)
            new_st, loss, ex, probs = _microstep(
                updater, st, mb, shard_size, push_mode, push_seed + i
            )
            return new_st, (loss, ex, probs)

        new_state, (losses, exs, probs) = lax.scan(
            body, state_l, (b, jnp.arange(n_micro, dtype=jnp.int32))
        )
        return new_state, losses, exs, probs[None]  # -> (D, K, B)

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec(), batch_spec(), P()),
        out_specs=(state_spec(), P(), P(), batch_spec()),
        check_vma=False,
    )
    return _wrap_stepper(step, push_mode)


def stack_step_groups(stacked_items: list[Batch]) -> Batch:
    """Stack K per-step stacked dicts — each (D, ...) — into one (D, K, ...)
    multistep group. Bucketed items are first zero-padded to the group max
    on their variable (trailing) axis; buckets are powers of two, so the
    set of group shapes (and compiled programs) stays small."""
    import numpy as np

    from parameter_server_tpu.data.batch import zero_extend

    targets = {
        f: max(d[f].shape[-1] for d in stacked_items)
        for f in stacked_items[0]
    }
    return {
        f: np.stack(
            [zero_extend(d[f], targets[f], axis=-1) for d in stacked_items],
            axis=1,
        )
        for f in stacked_items[0]
    }


def make_spmd_predict_step(updater: Updater, mesh: Mesh, num_keys: int):
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def local_predict(state_l: State, batch: Batch):
        b = {k: v[0] for k, v in batch.items()}
        with jax.named_scope("ps.row_ids"):
            row_ids = _row_ids_of(b)
        with jax.named_scope("ps.pull"):
            w_u = lax.psum(
                _local_pull(updater, state_l, b["unique_keys"], shard_size),
                "kv",
            )
        with jax.named_scope("ps.grad"):
            logits = csr_logits(
                w_u, _values_of(b), b["local_ids"], row_ids,
                num_rows=b["labels"].shape[0],
            )
            return jax.nn.sigmoid(logits)[None, :]

    step = shard_map(
        local_predict,
        mesh=mesh,
        in_specs=(state_spec(), batch_spec()),
        out_specs=batch_spec(),
        check_vma=False,
    )
    jitted = jax.jit(step)
    seen: set = set()

    def predict(state: State, batch: Batch) -> jax.Array:
        _note_program(jitted, seen, state, batch)
        return jitted(state, batch)

    return predict
