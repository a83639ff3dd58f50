"""The SPMD step: an app's description, one parameter-server step over it
and its step and predict programs: the reference's wire protocol as
collectives over ``kv`` and ``data``, around the store's row gather and row
scatter (``kv.store``).

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
    deltas added to the touched rows by ``kv.store.add_rows`` (a row that
    is not this shard's is dropped; deterministic under padding).

State layout: every table is (num_keys, row_stride(vdim)) sharded over "kv" on axis 0.
``num_keys`` need not divide the kv axis size: tables are zero-padded up
to the next axis multiple, in whole tiles (``padded_num_keys``) and the pad rows stay
exactly zero under the store's pad-row invariant (batch keys are always
below the real ``num_keys``, so no push ever touches them). Batches are
per-data-shard CSRBatches stacked on a leading axis and sharded over
"data".
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.kv.store import (
    add_rows,
    ascending_rows,
    held_rows,
    pad_state_rows,
    row_stride,
    take_rows,
)
from parameter_server_tpu.kv.updaters import Updater
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

State = dict[str, jax.Array]
Batch = dict[str, jax.Array]

# Phase names of one parameter-server step, as ``jax.named_scope``s in the
# step and predict programs and in ``kv.store``. A contract: the benchmark's
# ``step.*_ms`` readers and PERF.md find device time by these names (through
# ``op_scopes``), so whatever replaces the code underneath keeps them.
# Inside "ps.push" three nested scopes: "gather" (rows read for the
# updater), "update" (``updater.delta``), "scatter" (the scatter-add).
# An app of several tables names the table innermost ("ps.pull/emb",
# "ps.push/scatter/emb"), so a reader of "ps.pull" sums over tables; its
# dense group's forward and backward lie under "ps.grad/<group>" (and, of
# an app that names them, its phases beneath: "ps.grad/mlp/interact";
# ``StepApp.scopes``) and the group's ``psum`` and optimizer step under
# "ps.dense".
# The step's two collectives have scopes of their own, innermost, beneath
# the table's name: the pull's ``psum`` over "kv" under "ps.pull/<table>/psum"
# and the push's ``all_gather`` over "data" under
# "ps.push/<table>/all_gather" ("ps.pull/psum", "ps.push/all_gather" for an
# unnamed table), so a reader of "ps.pull" or "ps.push" still holds them and
# a reader of a table's ops ("ps.pull/<table>", "ps.push/<stage>/<table>")
# does not.
# The batch solver's call (``models.darlin``) pulls, takes its gradient and
# pushes under the same names, by key RANGE (``pull_range`` / ``push_range``),
# and has phases no online step has, beside them: the scatter of X_b d over
# the examples, the line search's objective terms, and the KKT filter's
# refresh of the active set (a sweep of its own, whole under its one name).
PHASE_SCOPES = (
    "ps.row_ids", "ps.pull", "ps.grad", "ps.push", "ps.dense",
    "darlin.xd", "darlin.linesearch", "darlin.refresh",
)
_PUSH_STAGES = ("gather", "update", "scatter")
_COLLECTIVES = ("psum", "all_gather")


def _sub_scope(name: str):
    """A table's or dense group's ``name`` as a scope under a phase
    (``ps.pull/emb``); no scope at all for the empty name of a
    single-table app."""
    return jax.named_scope(name) if name else contextlib.nullcontext()


@dataclasses.dataclass
class _RanProgram:
    """A step or predict program and the abstract arguments of its first
    call with one set of shapes: what ``op_scopes`` compiles again, after
    the run, to read the names (kept in ``scopes`` once read)."""

    jitted: Any
    args: tuple
    scopes: tuple[str, dict[str, str]] | None = None
    names: frozenset = frozenset()  # the app's tables and dense group


_ran: list[_RanProgram] = []
_forgotten = 0  # calls of ``forget_programs``: part of what counts as seen


def forget_programs() -> None:
    """Drop what ``op_scopes`` knows of the programs run so far; one that
    runs again is remembered again. For a caller that reads a profile of a
    part of the run: programs of one module name share a map there, and
    one that ran only before that part (the short inert calls that end an
    epoch) would blank the names the two scope differently."""
    global _forgotten
    _ran.clear()
    _forgotten += 1


def note_program(jitted, seen: set, names: frozenset, *args) -> None:
    """Remember the shapes, dtypes and shardings a step or predict program
    is called with, once a distinct set: a dict lookup on the dispatch
    path, nothing compiled or read here."""
    key = (_forgotten, *(
        (getattr(x, "shape", None), getattr(x, "dtype", None))
        for x in jax.tree.leaves(args)
    ))
    if key in seen:
        return
    seen.add(key)

    def abstract(x):
        if not hasattr(x, "shape"):
            return x  # a Python scalar keeps its weak type
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)
        )

    _ran.append(_RanProgram(jitted, jax.tree.map(abstract, args), names=names))


def hlo_scopes(hlo_text: str, names: frozenset = frozenset()) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: scope path}) of one optimised HLO
    module's text. The scope path is the ``ps.*`` phase in the
    instruction's ``op_name`` metadata (a fusion carries its root's), with
    what the program nested under it after a slash: the push's stages
    (``ps.push/scatter``) and, of the app that ran it, the ``names`` of its
    tables and dense group (``ps.pull/emb``; ``StepApp.scope_names``) and
    the two collectives (``ps.pull/emb/psum``);
    ``""`` for an instruction that carries none (input copies, some
    custom calls)."""
    module = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    out: dict[str, str] = {}
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", hlo_text, re.M
    ):
        op_name = re.search(r'op_name="([^"]*)"', m.group(2))
        out[m.group(1)] = _scope_of(op_name.group(1), names) if op_name else ""
    return (module.group(1) if module else ""), out


_TRANSFORMED = re.compile(r"^\w+\((.*)\)$")  # "transpose(jvp(mlp))" -> "mlp"


def _scope_of(op_name: str, names: frozenset = frozenset()) -> str:
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if part in PHASE_SCOPES:
            path = [part]
            # the last part is the primitive's own name ("gather")
            for sub in parts[i + 1 : -1]:
                while (m := _TRANSFORMED.match(sub)) is not None:
                    sub = m.group(1)  # a scope inside jax.grad
                if sub in _PUSH_STAGES or sub in _COLLECTIVES or sub in names:
                    path.append(sub)
            return "/".join(path)
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
            ran.scopes = hlo_scopes(compiled.as_text(), ran.names)
        module, scopes = ran.scopes
        have = out.setdefault(module, {})
        for name, scope in scopes.items():
            have[name] = scope if have.get(name, scope) == scope else ""
    return out


@dataclasses.dataclass(frozen=True)
class Table:
    """One key-addressed table of an app: its updater and how many values a
    key holds. ``name`` prefixes its entries in the flat state ("emb.w")
    and is the innermost scope of its pulls and pushes ("ps.pull/emb"); the
    one table of a single-table app goes unnamed ("z", "n"; "ps.pull").
    ``init(rows, lanes)`` makes the slots of ``rows`` rows that the
    updater's zeros will not do for (an embedding's starting ``w``), by the
    updater's names: on the device, inside ``Runtime.init_state``, never as
    a host array of table size. A slot is stored ``row_stride(vdim)`` lanes
    wide, which is ``vdim`` for every width but those the chip can neither
    gather nor scatter in place (100: 128, 300: 384, the lanes past
    ``vdim`` zero for ever), and ``lanes`` is that width: ``init`` makes
    its slots as they are stored. Pulled rows and gradients are ``vdim``
    wide whatever the stride."""

    name: str
    updater: Updater
    vdim: int = 1
    init: Callable[[int, int], State] | None = None

    def key(self, slot: str) -> str:
        return f"{self.name}.{slot}" if self.name else slot

    def slots(self) -> tuple[str, ...]:
        return tuple(jax.eval_shape(lambda: self.updater.init(1, self.vdim)))

    def init_slots(self, rows: int) -> State:
        """The table's slots as the store keeps them, ``row_stride(vdim)``
        lanes wide: the one place that applies the stride. The makers (the
        updater's zeros, then the app's ``init`` over them) are handed that
        width and make a slot at it in one pass, its lanes past ``vdim``
        zero; nothing is padded here (XLA does not fuse a pad into what it
        pads: a chip-filling table's would be a second table)."""
        lanes = row_stride(self.vdim)
        slots = self.updater.init(rows, lanes)
        if self.init is not None:
            slots = {**slots, **self.init(rows, lanes)}
        for k, v in slots.items():
            if v.shape != (rows, lanes):
                raise ValueError(
                    f"slot {self.key(k)!r} of {self.vdim}-lane rows is stored "
                    f"{(rows, lanes)} (kv.store.row_stride), not {v.shape}: its "
                    "init makes it at the width it is handed"
                )
        return {self.key(k): v for k, v in slots.items()}

    def of(self, state: State) -> State:
        """This table's slots out of the flat state, by the updater's names."""
        return {k: state[self.key(k)] for k in self.slots()}


def _named_leaves(prefix: str, tree: Any) -> dict[str, Any]:
    """A pytree's leaves under flat names: ``prefix`` and the leaf's key
    path, dot-joined ("mlp.0.W", "mlp_opt.0.mu.0.W")."""
    def part(k) -> str:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        ".".join([prefix, *(part(k) for k in path)]): leaf
        for path, leaf in leaves
    }


@dataclasses.dataclass(frozen=True)
class DenseGroup:
    """Parameters every device holds whole (a tower over the pulled rows),
    with their optimizer (an optax transformation) stepped inside the
    device step: gradients ``psum``'d over "data", the update gated on the
    microstep having any real example (Adam moves its moments on a zero
    gradient, a KV updater does not). In the flat state the parameters sit
    under ``<name>.`` and the optimizer's state under ``<name>_opt.``."""

    name: str
    init: Callable[[], Any]  # -> the parameters' pytree
    opt: Any

    @functools.cached_property
    def _templates(self):
        """(parameters, optimizer state) as shapes: the flat names' order."""
        params = jax.eval_shape(self.init)
        return params, jax.eval_shape(self.opt.init, params)

    def pack(self, params: Any, opt_state: Any) -> State:
        return {
            **_named_leaves(self.name, params),
            **_named_leaves(self.name + "_opt", opt_state),
        }

    def unpack(self, state: State) -> tuple[Any, Any]:
        """(parameters, optimizer state) as pytrees out of the flat state."""
        def fill(prefix: str, like: Any) -> Any:
            return jax.tree.unflatten(
                jax.tree.structure(like),
                [state[n] for n in _named_leaves(prefix, like)],
            )

        params, opt_state = self._templates
        return fill(self.name, params), fill(self.name + "_opt", opt_state)

    def keys(self) -> list[str]:
        return list(self.pack(*self._templates))

    def init_state(self) -> State:
        params = self.init()
        return self.pack(params, self.opt.init(params))


@dataclasses.dataclass(frozen=True)
class StepApp:
    """What one parameter-server step needs to know of an app: its tables,
    its replicated dense group if it has one, and the two functions of the
    pulled rows that are the model. ``grad(pulled, dense, b, row_ids)`` ->
    (summed loss, logits (B,), {table name: (U, vdim) gradient of the
    pulled rows}, the dense parameters' gradient or None): the loss is the
    app's own (logistic for the CTR apps, squared error for matrix
    factorization); ``logits(pulled, dense, b, row_ids)`` -> (B,).
    ``pulled`` maps a table's name to its (U, vdim) weights for
    ``b["unique_keys"]``; every table is addressed by the batch's one key
    set (an app has one key space).

    ``link`` turns the logits into what the step and the predict program
    return as ``probs`` (the sigmoid of a logistic model, the identity of a
    regression); ``score`` names the evaluator's scores of (labels,
    predictions), the first of them also the progress table's column.

    The state of an app is one flat ``{name: array}`` dict: each table's
    slots (range-sharded over "kv") and the dense group's leaves
    (replicated).

    ``scopes``: names of ``jax.named_scope``s that ``grad`` and ``logits``
    nest under the dense group's, the phases of a dense half that is more
    than one ("ps.grad/mlp/interact"). ``check_batch(batch)``: the app's
    check of a host ``CSRBatch`` before it is stacked, raising on one its
    model cannot read (the device cannot refuse a batch)."""

    tables: tuple[Table, ...]
    grad: Callable
    logits: Callable
    dense: DenseGroup | None = None
    link: Callable = dataclasses.field(kw_only=True)
    score: tuple[tuple[str, Callable], ...] = dataclasses.field(kw_only=True)
    scopes: tuple[str, ...] = dataclasses.field(default=(), kw_only=True)
    check_batch: Callable | None = dataclasses.field(default=None, kw_only=True)

    def table(self, name: str) -> Table:
        (t,) = [t for t in self.tables if t.name == name]
        return t

    def scope_names(self) -> frozenset:
        """The names this app's programs nest under a phase scope: its
        named tables, its dense group and ``scopes`` (what ``hlo_scopes``
        keeps of an op_name besides the push's stages)."""
        names = {t.name for t in self.tables if t.name} | set(self.scopes)
        if self.dense is not None:
            names.add(self.dense.name)
        return frozenset(names)

    def init_tables(self, rows: int) -> State:
        out: State = {}
        for t in self.tables:
            out.update(t.init_slots(rows))
        return out

    def table_keys(self) -> list[str]:
        return [t.key(k) for t in self.tables for k in t.slots()]

    def specs(self) -> dict[str, P]:
        """The state's partition specs, entry by entry."""
        out = {k: state_spec() for k in self.table_keys()}
        if self.dense is not None:
            out.update({k: P() for k in self.dense.keys()})
        return out


def _linear_logits(pulled, dense, b: Batch, row_ids: jax.Array) -> jax.Array:
    return csr_logits(
        pulled[""], _values_of(b), b["local_ids"], row_ids, b["row_splits"]
    )


def _linear_grad(pulled, dense, b: Batch, row_ids: jax.Array):
    logits = _linear_logits(pulled, dense, b, row_ids)
    loss, err = logistic_loss(logits, b["labels"], b["example_mask"])
    g = csr_grad(
        err, _values_of(b), b["local_ids"], row_ids, b["row_splits"],
        num_unique=b["unique_keys"].shape[0],
    )
    return loss, logits, {"": g}, None


def linear_app(updater: Updater) -> StepApp:
    """Sparse logistic regression over one unnamed ``vdim`` 1 table, the
    gradient hand-written (``ops.sparse.csr_grad``): the flagship."""
    return StepApp(
        (Table("", updater, 1),), _linear_grad, _linear_logits,
        link=jax.nn.sigmoid, score=M.BINARY_SCORES,
    )


def _as_app(app: "StepApp | Updater") -> StepApp:
    """The step makers take an app's description; a bare updater stands
    for the linear app over it."""
    return app if isinstance(app, StepApp) else linear_app(app)


def state_spec() -> P:
    return P("kv", None)


def batch_spec() -> P:
    return P("data", None)


def shard_state(state: State, mesh: Mesh) -> State:
    """Place a replicated/host state dict range-sharded over the kv axis,
    zero-padding the tables up to the next kv-axis multiple first (the
    pad rows are inert — see ``kv.store.pad_state_rows``)."""
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


# The batch wire: row structure rides as (B+1,) row_splits, not as the
# batch's (NNZ,) row_ids; the device rebuilds row ids by marking the splits
# and summing along the entries (see _row_ids_of).
CSR_FIELDS = (
    "unique_keys", "local_ids", "row_splits", "values", "labels", "example_mask",
)


_F16_MAX = 65504.0  # largest finite float16


def stack_batches(
    batches: list[CSRBatch],
    mesh: Mesh | None = None,
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

    out = stack_fields(batches, CSR_FIELDS, None)
    if values_f16:
        out["values"] = np.clip(out["values"], -_F16_MAX, _F16_MAX).astype(
            np.float16
        )
    return out if mesh is None else place_stacked(out, mesh)


def _row_ids_of(b: Batch) -> jax.Array:
    """Entry -> example-row ids for one shard's batch, rebuilt from the
    (B+1,) row_splits with no data-dependent loop. An entry's row is the
    number of interior splits at or before it, so the splits are marked in
    a zeroed (NNZ,) vector and summed along it. Empty rows repeat a split
    and their marks add up; a split equal to NNZ (a buffer filled to its
    last entry) falls off the end and is dropped. Padded entries (value
    0) land on the last row and stay inert under the masked loss/grad
    ops."""
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
    updater: Updater, state_l: State, idx: jax.Array, shard_size: int,
    table: str = "", vdim: int | None = None,
) -> jax.Array:
    """This shard's contribution to pulled weights for global ids ``idx``.
    ``table`` (here and in the pushes): the scope a named table's ops go
    under, innermost; ``vdim``: the table's row width where its slots are
    stored wider (``row_stride``), else unsaid."""
    begin = lax.axis_index("kv") * shard_size
    local = idx - begin
    in_range, safe = held_rows(local, shard_size)
    with _sub_scope(table):
        rows = {k: take_rows(v, safe, vdim) for k, v in state_l.items()}
        w = updater.weights(rows)
        return jnp.where(in_range[:, None], w, 0.0)


def _pull(t: Table, state_l: State, idx: jax.Array, shard_size: int) -> jax.Array:
    """Pull: slice + merge (ref kv_vector match). Table ``t``'s (U, vdim)
    weights for global ids ``idx`` on every device: this shard's rows, the
    others' zeros, and the ``psum`` over "kv" that merges them, under a
    scope of its own beneath the table's. Inside ``shard_map``, under the
    scope ``ps.pull``."""
    mine = _local_pull(t.updater, t.of(state_l), idx, shard_size, t.name, t.vdim)
    with _sub_scope(t.name), jax.named_scope("psum"):
        return lax.psum(mine, "kv")


def _range_owner(begin: jax.Array, shard_size: int, kv: int):
    """(this shard owns the range that starts at global row ``begin``, the
    range's first row in the owner's shard - 0 on the other shards, whose
    slice is read and thrown away). A range never straddles two shards: the
    callers cut the key space into ranges that divide ``shard_size``."""
    if kv == 1:
        return True, begin
    owner = begin // shard_size
    is_owner = owner == lax.axis_index("kv")
    return is_owner, jnp.where(is_owner, begin - owner * shard_size, 0)


def pull_range(
    table: Table, state_l: State, begin: jax.Array, size: int,
    shard_size: int, kv: int,
) -> State:
    """The pull of a CONTIGUOUS key range: rows ``[begin, begin + size)`` of
    every slot of ``table``, {slot: (size, stride)}, on every device. What
    the parameter server's key-range design exists for: on the chip a range
    is a slice of the owner's shard (``dynamic_slice``), broadcast over
    ``kv`` by a ``psum`` of the owner's slice and the others' zeros; on one
    kv shard the slice alone, no gather and no collective. Call it inside
    ``shard_map``, under the scope ``ps.pull``."""
    is_owner, at = _range_owner(begin, shard_size, kv)
    with _sub_scope(table.name):
        out = {}
        for k, v in table.of(state_l).items():
            rows = lax.dynamic_slice(v, (at, 0), (size, v.shape[1]))
            if kv > 1:
                rows = lax.psum(jnp.where(is_owner, rows, jnp.zeros_like(rows)), "kv")
            out[k] = rows
        return out


def push_range(
    table: Table, state_l: State, begin: jax.Array, rows: State,
    shard_size: int, kv: int,
) -> State:
    """The push of a contiguous key range: ``rows`` ({slot: (size, stride)},
    the same on every device, as the server's updater left them) written
    over ``[begin, begin + size)`` of the owner's shard in place
    (``dynamic_update_slice``); the other shards write their own rows back.
    Returns the state with ``table``'s slots replaced. Inside ``shard_map``,
    under the scope ``ps.push``."""
    is_owner, at = _range_owner(begin, shard_size, kv)
    out = dict(state_l)
    with _sub_scope(table.name):
        for k, new in rows.items():
            v = state_l[table.key(k)]
            if kv > 1:
                old = lax.dynamic_slice(v, (at, 0), new.shape)
                new = jnp.where(is_owner, new, old)
            out[table.key(k)] = lax.dynamic_update_slice(v, new.astype(v.dtype), (at, 0))
    return out


# called through this global: the benchmark's controls break the push by
# patching ``spmd._add_rows`` (ROADMAP.md D19)
_add_rows = add_rows


def _local_push(
    updater: Updater,
    state_l: State,
    all_idx: jax.Array,  # (D, U) pushes from every data shard
    all_grad: jax.Array,  # (D, U, vdim)
    shard_size: int,
    table: str = "",
    ascending: bool = False,
    vdim: int | None = None,
) -> State:
    """Apply every worker's push to this kv shard, sequentially (ref: the
    server processes each worker's Push message as its own updater step).

    ``ascending``: the caller's promise, set by code that knows it and
    never by configuration, that each worker's ids obey the batch contract
    of ``data.batch`` (slot 0 ``PAD_KEY``, then strictly ascending keys,
    then ``PAD_KEY`` to the end). The scatter then sends the tail's pads
    past the table and tells XLA that its rows ascend where that pays
    (``kv.store.scatter_rows_sorted``). Promised or not, a row that is not this
    shard's is dropped, not added as a zero to row 0. The gathers keep the
    clamped index vector they share with ``_local_pull``: XLA merges the
    two on one chip."""
    begin = lax.axis_index("kv") * shard_size

    def body(state_l: State, push: tuple[jax.Array, jax.Array]):
        idx, g = push
        local = idx - begin
        _, safe = held_rows(local, shard_size)
        with jax.named_scope("gather"), _sub_scope(table):
            rows = {k: take_rows(v, safe, vdim) for k, v in state_l.items()}
        with jax.named_scope("update"), _sub_scope(table):
            deltas = updater.delta(rows, g)
        with jax.named_scope("scatter"), _sub_scope(table):
            to = ascending_rows(idx, local) if ascending else local
            new = {
                k: _add_rows(state_l[k], to, deltas[k], ascending)
                for k in state_l
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
    table: str = "",
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
    in_range, safe = held_rows(local, shard_size)
    mask = in_range[:, None].astype(grad.dtype)
    lanes = next(iter(state_l.values())).shape[1]  # the slots' stride
    if lanes != grad.shape[-1]:
        grad = jnp.pad(grad, ((0, 0), (0, lanes - grad.shape[-1])))
    with jax.named_scope("scatter"), _sub_scope(table):
        g_slice = jnp.zeros((shard_size, lanes), grad.dtype).at[safe].add(
            mask * grad
        )
        touched = jnp.zeros((shard_size, 1), grad.dtype).at[safe].add(mask)
    # one collective pre-sums every worker's contribution to this range
    g_slice = lax.psum(g_slice, "data")
    touched = lax.psum(touched, "data")
    # no "gather" here: the updater reads the whole range slice
    with jax.named_scope("update"), _sub_scope(table):
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
    table: str = "",
    ascending: bool = False,  # forwarded to ``_local_push``
    vdim: int | None = None,  # forwarded to ``_local_push``
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
    with _sub_scope(table), jax.named_scope("all_gather"):
        all_idx = lax.all_gather(idx, "data")  # (D, U)
        all_q = lax.all_gather(q, "data")  # (D, U, vdim) int8
        all_scale = lax.all_gather(scale, "data")  # (D,)
    all_grad = all_q.astype(grad.dtype) * all_scale[:, None, None]
    return _local_push(
        updater, state_l, all_idx, all_grad, shard_size, table, ascending, vdim
    )


PUSH_MODES = ("per_worker", "aggregate", "quantized")


# XLA holds a (rows, 1) table in 128-row tiles at a program's edge and in
# 1024-row tiles under its gathers and scatters: the same bytes, converted
# by a bitcast, only when the rows are whole 1024-row tiles
ROW_TILE = 1024


def padded_num_keys(num_keys: int, kv_size: int) -> int:
    """The table rows the sharded tiers actually allocate for ``num_keys``:
    every kv shard the same number of rows, and a shard of more than one
    ``ROW_TILE`` whole tiles (10^8 rows on one chip would otherwise cost a
    copy of the table at every gather and scatter). The rows past the
    real ``num_keys`` are pad rows: exactly zero and never touched (the
    data layer only emits keys below ``num_keys``), so arbitrary table
    sizes run on any mesh shape with no semantic change."""
    if num_keys < 1:
        raise ValueError(f"num_keys must be >= 1, got {num_keys}")
    shard = -(-num_keys // kv_size)
    if shard > ROW_TILE:
        shard = -(-shard // ROW_TILE) * ROW_TILE
    return shard * kv_size


def _shard_size(num_keys: int, kv_size: int) -> int:
    return padded_num_keys(num_keys, kv_size) // kv_size


def _wrap_stepper(step, push_mode: str, names: frozenset = frozenset()):
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
        note_program(_jitted, seen, names, state, batch, push_seed)
        return _jitted(state, batch, push_seed)

    return stepper


def _dense_step(group: DenseGroup, params, opt_state, grads, active):
    """The dense group's optimizer step on the pod-wide gradient, applied
    only when ``active``: an all-padding microstep (a drained host, the pad
    of a partial group) leaves parameters and optimizer state as they
    were."""
    import optax

    grads = jax.tree.map(lambda g: lax.psum(g, "data"), grads)
    updates, new_opt = group.opt.update(grads, opt_state, params)
    new_params = optax.apply_updates(params, updates)
    keep = lambda new, old: jax.tree.map(  # noqa: E731
        lambda n, o: jnp.where(active, n, o), new, old
    )
    return keep(new_params, params), keep(new_opt, opt_state)


def _microstep(
    app: StepApp,
    state_l: State,
    b: Batch,  # one data shard's un-stacked batch fields
    shard_size: int,
    push_mode: str,
    push_seed: jax.Array,
):
    """One parameter-server step on this device: pull every table's rows
    for the batch's keys -> the app's loss and gradients -> push each
    table's gradient through its updater, step the dense group. Shared
    verbatim by the single-step and scanned multi-step programs and by
    every app, so the wire semantics cannot diverge between them."""
    # the batch contract of ``data.batch``: PAD_KEY, ascending keys, PAD_KEY
    # to the end; the pushes below promise it to ``_local_push``
    idx = b["unique_keys"]
    dense = app.dense.unpack(state_l) if app.dense is not None else (None, None)
    with jax.named_scope("ps.row_ids"):
        row_ids = _row_ids_of(b)
    with jax.named_scope("ps.pull"):
        pulled = {t.name: _pull(t, state_l, idx, shard_size) for t in app.tables}
    with jax.named_scope("ps.grad"):
        loss, logits, grads, g_dense = app.grad(pulled, dense[0], b, row_ids)
        probs = app.link(logits)
    new_state = dict(state_l)
    with jax.named_scope("ps.push"):
        for i, t in enumerate(app.tables):
            g, tab = grads[t.name], t.of(state_l)
            if push_mode == "aggregate":
                new = _local_push_aggregate(
                    t.updater, tab, idx, g, shard_size, t.name
                )
            elif push_mode == "quantized":
                # tables that share a microstep's seed round on streams of
                # their own; a single table keeps the original key schedule
                new = _local_push_quantized(
                    t.updater, tab, idx, g, shard_size, push_seed,
                    stream=i + 1 if len(app.tables) > 1 else 0, table=t.name,
                    ascending=True, vdim=t.vdim,
                )
            else:
                # Push: every data shard's (keys, grads) reach every kv shard.
                with _sub_scope(t.name), jax.named_scope("all_gather"):
                    all_idx = lax.all_gather(idx, "data")  # (D, U)
                    all_grad = lax.all_gather(g, "data")  # (D, U, vdim)
                new = _local_push(
                    t.updater, tab, all_idx, all_grad, shard_size, t.name,
                    ascending=True, vdim=t.vdim,
                )
            new_state.update({t.key(k): v for k, v in new.items()})
    loss_sum = lax.psum(loss, "data")
    # pod-wide real-example count: the host-side termination signal
    # (a drained host keeps feeding empty batches; every host stops
    # deterministically after retiring a step with examples == 0 —
    # this rides async dispatch instead of a blocking host barrier)
    examples = lax.psum(jnp.sum(b["example_mask"]), "data")
    if app.dense is not None:
        with jax.named_scope("ps.dense"):
            new_state.update(
                app.dense.pack(
                    *_dense_step(app.dense, *dense, g_dense, examples > 0)
                )
            )
    return new_state, loss_sum, examples, probs


def make_spmd_train_step(
    app: "StepApp | Updater", mesh: Mesh, num_keys: int,
    push_mode: str = "per_worker",
):
    """Build the jitted multi-device train step of ``app`` (a ``StepApp``;
    a bare updater stands for the linear app over it).

    step(state, batch) -> (state, out) with out keys:
      "loss_sum" — scalar, psum over data
      "examples" — scalar pod-wide real-example count (the host-side
          termination signal; see PodTrainer's drained contract)
      "probs"    — (D, B) per-shard predictions (``app.link`` of the
          logits: probabilities for a logistic app)

    ``batch["unique_keys"]`` obeys the padding contract of ``data.batch``
    on every shard: slot 0 ``PAD_KEY``, then strictly ascending keys, then
    ``PAD_KEY`` to the end. The push tells XLA that its rows ascend
    (``_local_push``), so a key list in any other order is undefined
    behaviour on the chip, and the CPU does not show it. ``BatchBuilder``
    and every path that grows or stacks its batches keep the contract
    (tests/test_push_rows.py); ``CSRBatch.keys_in_order`` checks a batch
    built some other way.

    push_mode, for every table of the app:
      "per_worker" — faithful reference semantics: each data shard's push is
          its own server updater step (all_gather + sequential scan).
      "aggregate"  — pre-sum per-key grads across data shards with one psum,
          apply one updater step (see ``_local_push_aggregate``; exactly
          equal for linear SGD, standard sync aggregation otherwise).
      "quantized"  — per_worker semantics with int8 gradients on the wire
          (see ``_local_push_quantized``; the fixing_float filter as a
          quantized collective for DCN-limited pods).
    """
    return _make_train(app, mesh, num_keys, push_mode, multistep=False)


def make_spmd_train_multistep(
    app: "StepApp | Updater", mesh: Mesh, num_keys: int,
    push_mode: str = "per_worker",
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
    microstep second (scanned); every ``unique_keys[d, k]`` under the key
    order ``make_spmd_train_step`` states. step(state, batch, push_seed) ->
    (state, out) with out keys:
      "loss_sum" — (K,) per-microstep pod-wide loss sums
      "examples" — (K,) per-microstep pod-wide real-example counts (the
          termination contract checks the LAST entry: empties only ever
          trail real batches within a group)
      "probs"    — (D, K, B) per-shard, per-microstep probabilities
    """
    return _make_train(app, mesh, num_keys, push_mode, multistep=True)


def _make_train(app, mesh: Mesh, num_keys: int, push_mode: str, multistep: bool):
    if push_mode not in PUSH_MODES:
        raise ValueError(f"unknown push_mode {push_mode!r}; known: {PUSH_MODES}")
    app = _as_app(app)
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def local_step(state_l: State, batch: Batch, push_seed: jax.Array):
        b = {k: v[0] for k, v in batch.items()}  # this data shard's batch
        if not multistep:
            new_state, loss_sum, examples, probs = _microstep(
                app, state_l, b, shard_size, push_mode, push_seed
            )
            return new_state, loss_sum, examples, probs[None, :]  # -> (D, B)
        n_micro = b["labels"].shape[0]  # b holds this shard's (K, ...) group

        def body(st: State, micro):
            mb, i = micro
            # quantized mode: a distinct PRNG key per microstep (the
            # same per-step-seed contract as single-step dispatch)
            new_st, loss, ex, probs = _microstep(
                app, st, mb, shard_size, push_mode, push_seed + i
            )
            return new_st, (loss, ex, probs)

        new_state, (losses, exs, probs) = lax.scan(
            body, state_l, (b, jnp.arange(n_micro, dtype=jnp.int32))
        )
        return new_state, losses, exs, probs[None]  # -> (D, K, B)

    specs = app.specs()
    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(specs, batch_spec(), P()),
        out_specs=(specs, P(), P(), batch_spec()),
        check_vma=False,
    )
    return _wrap_stepper(step, push_mode, app.scope_names())


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


def make_spmd_predict_step(app: "StepApp | Updater", mesh: Mesh, num_keys: int):
    app = _as_app(app)
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def local_predict(state_l: State, batch: Batch):
        b = {k: v[0] for k, v in batch.items()}
        dense = app.dense.unpack(state_l)[0] if app.dense is not None else None
        with jax.named_scope("ps.row_ids"):
            row_ids = _row_ids_of(b)
        with jax.named_scope("ps.pull"):
            pulled = {
                t.name: _pull(t, state_l, b["unique_keys"], shard_size)
                for t in app.tables
            }
        with jax.named_scope("ps.grad"):
            logits = app.logits(pulled, dense, b, row_ids)
            return app.link(logits)[None, :]

    step = shard_map(
        local_predict,
        mesh=mesh,
        in_specs=(app.specs(), batch_spec()),
        out_specs=batch_spec(),
        check_vma=False,
    )
    jitted = jax.jit(step)
    seen: set = set()

    def predict(state: State, batch: Batch) -> jax.Array:
        note_program(jitted, seen, app.scope_names(), state, batch)
        return jitted(state, batch)

    return predict
