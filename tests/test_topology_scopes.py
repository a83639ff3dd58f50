"""The step and predict programs compiled for a described ``v5e:2x2`` at the
benchmark cells' real sizes (2^30 rows on one chip; 2^31 over ``kv`` 2,
``data`` 2), with no chip: every executed instruction that reads or writes
the table, and every one that rebuilds the row ids (with no loop among
them), sits under a ``ps.*`` scope - the names the ``step.*_ms`` metrics
find device time by.

After the ``on-chip-measurement`` guide, section 2: the topology is
described inside a module-scoped fixture that skips where it cannot be,
nothing here touches the TPU's library at import time, and this is the one
test file that does (a second file would go to another worker, whose
fixture would skip). A compile that passes is not a chip run."""

import math
import os
import re

import numpy as np
import pytest

MINIBATCH, K, NNZ = 8192, 8, 1 << 19  # the cells' one bucket: 39 x 8192 entries
UNIQUE = 1 << 16  # and its key axis' (since PR 31: a batch holds about 40,000 keys)
ROWS_PER_CHIP = 1 << 30
# opcodes that run nothing of their own: a container's time is its body's,
# the others only name a value
_NOT_EXECUTED = {
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant",
    "while", "conditional", "call",
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the library away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_text(topo):
    """(data, kv, program) -> optimised HLO text, compiled once each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from parameter_server_tpu.models.linear import updater_from_config
    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.config import PSConfig

    texts: dict = {}

    def get(data: int, kv: int, program: str) -> str:
        key = (data, kv, program)
        if key in texts:
            return texts[key]
        mesh = Mesh(np.array(topo.devices[: data * kv]).reshape(data, kv), ("data", "kv"))
        rows = ROWS_PER_CHIP * kv
        updater = updater_from_config(PSConfig())  # FTRL: z and n
        table = NamedSharding(mesh, spmd.state_spec())
        feed = NamedSharding(mesh, spmd.batch_spec())
        state = {k: jax.ShapeDtypeStruct((rows, 1), jnp.float32, sharding=table) for k in ("z", "n")}
        lead = (K,) if program == "multistep" else ()
        fields = {
            "unique_keys": ((UNIQUE,), jnp.int32), "local_ids": ((NNZ,), jnp.int32),
            "row_splits": ((MINIBATCH + 1,), jnp.int32), "values": ((NNZ,), jnp.float32),
            "labels": ((MINIBATCH,), jnp.float32), "example_mask": ((MINIBATCH,), jnp.bool_),
        }
        batch = {
            k: jax.ShapeDtypeStruct((data, *lead, *shape), dt, sharding=feed)
            for k, (shape, dt) in fields.items()
        }
        if program == "multistep":
            fn, args = spmd.make_spmd_train_multistep(updater, mesh, rows), (state, batch, 0)
        else:
            fn, args = spmd.make_spmd_predict_step(updater, mesh, rows), (state, batch)
        # the maker returns a plain function around its jitted program
        (jitted,) = [
            c.cell_contents for c in fn.__closure__
            if callable(c.cell_contents) and hasattr(c.cell_contents, "lower")
        ]
        compiled = jitted.lower(*args).compile()
        texts[key] = compiled.as_text()
        texts[key, "memory"] = compiled.memory_analysis()
        return texts[key]

    get.texts = texts
    return get


def instructions(text: str) -> list:
    """(computation, name, result shape, opcode, operand shapes, the rest of
    the line) of every instruction of the module."""
    shape_of, rows, comp = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$", line)
        if m:
            shape_of[m.group(1)] = m.group(2)
            rows.append((comp, *m.groups()))
    return [
        (comp, name, shape, opcode,
         [shape_of.get(o, "") for o in re.findall(r"%([\w.\-]+)", rest.split("), ")[0])], rest)
        for comp, name, shape, opcode, rest in rows
    ]


def executed(text: str) -> list:
    """(name, result shape, opcode, operand shapes) of every instruction a
    profile would show: those outside fused and applied computations, less
    the opcodes that run nothing of their own."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    return [
        (name, shape, opcode, operand_shapes)
        for comp, name, shape, opcode, operand_shapes, _ in instructions(text)
        if comp not in inner and opcode not in _NOT_EXECUTED
    ]


def elements(shape: str) -> int:
    return max(
        (math.prod(int(x) for x in dims.split(",")) for dims in re.findall(r"\[([\d,]+)\]", shape)),
        default=0,
    )


def bare_op_name(op_name: str) -> str:
    """An ``op_name`` with the transformations' wrappers taken off every
    part: ``ps.grad/transpose(jvp(mlp))/interact/dot_general`` ->
    ``ps.grad/mlp/interact/dot_general``."""
    from parameter_server_tpu.parallel import spmd

    parts = []
    for part in op_name.split("/"):
        while (m := spmd._TRANSFORMED.match(part)) is not None:
            part = m.group(1)
        parts.append(part)
    return "/".join(parts)


def copies_of(every: list, n: int) -> list:
    """(name, shape) of the module's ``copy`` instructions of ``n`` elements
    or more: a table relaid."""
    return [(name, shape) for _, name, shape, opcode, _, _ in every if opcode == "copy" and elements(shape) >= n]


CASES = [(1, 1, "multistep"), (1, 1, "predict"), (2, 2, "multistep"), (2, 2, "predict")]


@pytest.mark.parametrize("data,kv,program", CASES)
def test_table_ops_and_the_row_id_loop_are_scoped(compiled_text, data, kv, program):
    from parameter_server_tpu.parallel import spmd

    text = compiled_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text)
    table = re.compile(rf"\[{ROWS_PER_CHIP}(,1)?\]")
    touching, marking, summing, strays = [], [], [], []
    for name, shape, opcode, operand_shapes in executed(text):
        if table.search(shape) or any(table.search(s) for s in operand_shapes):
            touching.append((name, scopes[name]))
        if not shape.startswith(f"s32[{NNZ}]"):
            continue
        # an entry-sized int32 result: the rebuild's, a consumer's under its own
        # phase, or the compiler's move of a buffer; never a nameless computation
        if scopes[name] == "ps.row_ids":
            splits = any(s.startswith(f"s32[{MINIBATCH - 1}]") for s in operand_shapes)
            (marking if splits else summing).append(name)
        elif not scopes[name] and opcode not in ("copy-start", "copy-done", "custom-call"):
            strays.append((name, opcode))
    # two gathers (z, n) at least; a train step adds two scatter-adds a worker's push
    assert len(touching) >= (4 if program == "multistep" else 2), touching
    assert all(scope.startswith("ps.") for _, scope in touching), touching
    found = {scope for _, scope in touching}
    assert "ps.pull" in found
    if program == "multistep":
        assert "ps.push/scatter" in found
    # the rebuild: the interior splits marked in an entry-sized vector (one
    # scatter-add from the MINIBATCH - 1 of them), then shifted adds along it
    assert len(marking) == 1, marking
    assert len(summing) >= math.log2(NNZ) - 2, summing  # XLA may fold an add or two into a neighbour
    assert not strays, strays
    # and no loop: the only ``while``s are the scan over microsteps, across chips
    # the push's loop over workers, under ``ps.push/scatter`` the walk of each
    # slot's scatter (z, n) over the live pieces of the key axis, and under
    # ``ps.grad`` the walks of the sweeps by key slot over the live pieces of the
    # entry axis (the take of ``csr_logits``; a train step adds ``csr_grad``'s sum)
    whiles = {
        m.group(1): scopes[m.group(1)]
        for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? while\(", text, re.M)
    }
    assert "ps.row_ids" not in whiles.values(), whiles
    walks = [name for name, scope in whiles.items() if scope == "ps.push/scatter"]
    assert len(walks) == {"multistep": 2, "predict": 0}[program], whiles
    sweeps = [name for name, scope in whiles.items() if scope == "ps.grad"]
    assert len(sweeps) == {"multistep": 2, "predict": 1}[program], whiles
    rest = len(whiles) - len(walks) - len(sweeps)
    assert rest <= {"multistep": 1 + (data > 1), "predict": 0}[program], whiles


@pytest.mark.parametrize("data,kv,program", CASES)
def test_large_unscoped_instructions_are_the_known_kinds(compiled_text, data, kv, program):
    """What runs at batch size with no scope (PERF.md section 3 lists them
    with the reason): the compiler's own copies between memory spaces and
    buffer allocations, the scan's slicing of its stacked inputs, index
    clamping XLA split from a gather, and the ``all_gather`` of the
    gradients once XLA has rewritten it as an all-reduce."""
    from parameter_server_tpu.parallel import spmd

    _, scopes = spmd.hlo_scopes(compiled_text(data, kv, program))
    known = re.compile(
        r"^(copy|copy-start|copy-done|custom-call|slice-start|slice-done|async-start|async-done"
        r"|reduce|broadcast|dynamic-update-slice|all-reduce|fusion)$"
    )
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, _ in executed(compiled_text(data, kv, program))
        if not scopes[name] and elements(shape) >= NNZ and not known.match(opcode)
    ]
    assert not strays, strays
    # an unscoped fusion at this size is bookkeeping, never a table op
    for name, shape, opcode, operand_shapes in executed(compiled_text(data, kv, program)):
        if opcode == "fusion" and not scopes[name]:
            assert elements(shape) < 4 * NNZ and all(elements(s) < ROWS_PER_CHIP for s in operand_shapes), name


# -- Wide&Deep: two tables and a dense tower through the same step ------------
WD_ROWS = 100_000_000  # the cell wd100m.train: 10^8 rows x (vdim 1 + vdim 16)
WD_CASES = [(1, 1, "multistep"), (1, 1, "predict"), (2, 2, "multistep")]
WD_NAMES = frozenset({"wide", "emb", "mlp"})  # the app's StepApp.scope_names()


@pytest.fixture(scope="module")
def wd_text(topo):
    """(data, kv, program) -> optimised HLO text of the Wide&Deep programs at
    the cell's size (the tower 1024-512-256, ``emb_dim`` 16), compiled once."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from parameter_server_tpu.models import wide_deep
    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.config import PSConfig

    texts: dict = {}

    def get(data: int, kv: int, program: str) -> str:
        key = (data, kv, program)
        if key in texts:
            return texts[key]
        cfg = PSConfig()
        cfg.app, cfg.data.num_keys = "wide_deep", WD_ROWS
        cfg.wd.emb_dim, cfg.wd.hidden = 16, [1024, 512, 256]
        app = wide_deep.app_from_config(cfg)
        mesh = Mesh(np.array(topo.devices[: data * kv]).reshape(data, kv), ("data", "kv"))
        specs = app.specs()
        rows = spmd.padded_num_keys(WD_ROWS, kv)  # whole tiles a shard, as PodTrainer allocates
        shapes = jax.eval_shape(lambda: {**app.init_tables(rows), **app.dense.init_state()})
        state = {
            k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, specs[k]))
            for k, v in shapes.items()
        }
        feed = NamedSharding(mesh, spmd.batch_spec())
        lead = (K,) if program == "multistep" else ()
        fields = {
            "unique_keys": ((UNIQUE,), jnp.int32), "local_ids": ((NNZ,), jnp.int32),
            "row_splits": ((MINIBATCH + 1,), jnp.int32), "values": ((NNZ,), jnp.float32),
            "labels": ((MINIBATCH,), jnp.float32), "example_mask": ((MINIBATCH,), jnp.bool_),
        }
        batch = {
            k: jax.ShapeDtypeStruct((data, *lead, *shape), dt, sharding=feed)
            for k, (shape, dt) in fields.items()
        }
        if program == "multistep":
            fn, args = spmd.make_spmd_train_multistep(app, mesh, WD_ROWS), (state, batch, 0)
        else:
            fn, args = spmd.make_spmd_predict_step(app, mesh, WD_ROWS), (state, batch)
        (jitted,) = [
            c.cell_contents for c in fn.__closure__
            if callable(c.cell_contents) and hasattr(c.cell_contents, "lower")
        ]
        compiled = jitted.lower(*args).compile()
        texts[key] = compiled.as_text()
        texts[key, "memory"] = compiled.memory_analysis()
        return texts[key]

    get.texts = texts
    return get


@pytest.mark.parametrize("data,kv,program", WD_CASES)
def test_wd_table_ops_are_scoped_by_table(wd_text, data, kv, program):
    """Every executed instruction that reads or writes one of the two tables
    sits under a ``ps.*`` scope that names its table innermost, the tower's
    matmuls under ``ps.grad/mlp``, its optimizer under ``ps.dense``; and a
    10^8 x 16 table is held unpadded, so the step fits one chip."""
    from parameter_server_tpu.parallel import spmd

    text = wd_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, WD_NAMES)
    rows = spmd.padded_num_keys(WD_ROWS, kv) // kv
    table = re.compile(rf"\[(1,1,)?{rows}(,1|,16)?\]")  # however XLA views a table
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/(wide|emb)$", scope) for _, scope in touching), touching
    found = set(scopes.values())
    assert {"ps.row_ids", "ps.pull/wide", "ps.pull/emb", "ps.grad", "ps.grad/mlp"} <= found, found
    if program == "multistep":
        assert {"ps.push/scatter/wide", "ps.push/scatter/emb", "ps.dense"} <= found, found
    # what the program and its state take of one chip: z + n + w + n unpadded
    # are 12.67 GiB of it at kv 1 (lane-padded to 128 they would be 96), and
    # no copy of a table is among the temporaries
    mem = wd_text.texts[(data, kv, program), "memory"]
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < (13.5 if kv == 1 else 7.5) * 2**30


@pytest.mark.parametrize("data,kv,program", WD_CASES)
def test_wd_large_unscoped_instructions_are_the_known_kinds(wd_text, data, kv, program):
    from parameter_server_tpu.parallel import spmd

    text = wd_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, WD_NAMES)
    known = re.compile(
        r"^(copy|copy-start|copy-done|custom-call|slice-start|slice-done|async-start|async-done"
        r"|reduce|broadcast|dynamic-update-slice|all-reduce|fusion)$"
    )
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, _ in executed(text)
        if not scopes[name] and elements(shape) >= NNZ and not known.match(opcode)
    ]
    assert not strays, strays
    for name, shape, opcode, operand_shapes in executed(text):
        if opcode == "fusion" and not scopes[name]:
            # bookkeeping at batch size (a (U, 16) buffer at most), never a table op
            assert elements(shape) < 17 * UNIQUE and all(elements(s) < WD_ROWS // kv for s in operand_shapes), name


# -- the push's scatter: told that its rows ascend where that pays (PERF.md section 6, PRs 27 and 35) --
PUSH_PROGRAMS = [("linear", 1, 1), ("linear", 2, 2), ("wd", 1, 1)]


def sorted_hint(scatter_rest: str) -> bool:
    """What a compiled ``scatter`` was told (HLO prints the attribute only when set)."""
    return "indices_are_sorted=true" in scatter_rest


def fusions_calling(every: list, homes: set) -> list:
    """(name, the rest of the line) of the fusions whose computation is one of ``homes``."""
    return [
        (name, rest) for _, name, _, opcode, _, rest in every
        if opcode == "fusion" and re.search(r"calls=%?([\w.\-]+)", rest).group(1) in homes
    ]


def aliases_operand_0(fusion_rest: str) -> bool:
    """Whether operand 0, the table, is the fusion's result buffer."""
    return bool(re.search(r'"aliasing_operands":\{"lists":\[\{"indices":\["0"', fusion_rest))


@pytest.mark.parametrize("app,data,kv", PUSH_PROGRAMS)
def test_table_scatters_take_ascending_rows_in_place(compiled_text, wd_text, app, data, kv):
    """Whether the push's mechanism engages is a property of the compiled
    step: every scatter into a table carries the ``indices_are_sorted`` that
    ``store.scatter_rows_sorted`` gives its (rows a chip, lanes, key slots):
    off at 2^30 one-lane rows under 65,536 slots, where the emitter the
    hint selects would stream the whole table, on at every table of
    Wide&Deep; its fusion sits under ``ps.push/scatter`` and updates the
    table in place; nothing table-sized is among the temporaries; and on
    one chip the push's gathers are still merged with the pull's, two
    table gathers a microstep and table, one index vector."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    get, names = (compiled_text, frozenset()) if app == "linear" else (wd_text, WD_NAMES)
    text = get(data, kv, "multistep")
    mem = get.texts[(data, kv, "multistep"), "memory"]
    _, scopes = spmd.hlo_scopes(text, names)
    rows = ROWS_PER_CHIP if app == "linear" else spmd.padded_num_keys(WD_ROWS, kv) // kv
    table = re.compile(rf"^f32\[(1,1,)?{rows}(,1|,16)?\]")
    slots = 2 if app == "linear" else 4  # z, n; and emb's w, n
    every = instructions(text)
    scatters = [
        (comp, rest, 16 if table.match(shape).group(2) == ",16" else 1)
        for comp, _, shape, opcode, _, rest in every if opcode == "scatter" and table.match(shape)
    ]
    assert len(scatters) == slots, scatters
    for _, rest, lanes in scatters:
        assert sorted_hint(rest) is store.scatter_rows_sorted(rows, lanes, UNIQUE), (lanes, rest)
        assert sorted_hint(rest) is (app == "wd"), (lanes, rest)
    fusions = fusions_calling(every, {comp for comp, _, _ in scatters})
    assert len(fusions) == slots, fusions
    for name, rest in fusions:
        assert scopes[name].startswith("ps.push/scatter"), (name, scopes[name])
        assert aliases_operand_0(rest), (name, rest[-300:])
    table_bytes = sum(4 * rows * vdim for vdim in ((1, 1) if app == "linear" else (1, 1, 16, 16)))
    assert mem.alias_size_in_bytes >= table_bytes  # the whole state is donated through the call
    # the linear step's temporaries are batch-sized (15-18 MiB); W&D's hold its
    # (NNZ, 16) activations too, and stay under its smallest table slot
    assert mem.temp_size_in_bytes < (64 << 20 if app == "linear" else 4 * rows), mem.temp_size_in_bytes
    if kv == 1:
        gathers = [
            operand_shapes[0] for _, _, _, opcode, operand_shapes, _ in every
            if opcode == "gather" and table.match(operand_shapes[0])
        ]
        assert len(gathers) == slots, gathers


# -- matrix factorization: one table at the configured rank through the same step
MF_USERS, MF_ITEMS, MF_RANK = 50_082_603, 39_780, 64  # the cell mfhw.train
MF_RANK_2X2 = 100  # the cell mfhw2x2.train: NOMAD's rank, stored 128 lanes wide, over kv 2
MF_MINIBATCH, MF_SLOTS = 65_536, 131_072  # 2 entries a rating: one 2^17 bucket on both axes
MF_CASES = [(1, 1, "multistep"), (1, 1, "predict")]
MF_NAMES = frozenset({"mf"})


@pytest.fixture(scope="module")
def mf_text(topo):
    """(data, kv, program[, rank]) -> optimised HLO text of the
    matrix-factorization programs at the cells' sizes (5.0e7 rows under SGD:
    x 64 on one chip, x 100 stored 128 wide over ``kv`` 2), compiled once;
    ``program`` "init" is the table made on the devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from parameter_server_tpu.models import matrix_fac
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.config import PSConfig

    texts: dict = {}

    def get(data: int, kv: int, program: str, rank: int = MF_RANK) -> str:
        key = (data, kv, program) if rank == MF_RANK else (data, kv, program, rank)
        if key in texts:
            return texts[key]
        cfg = PSConfig()
        cfg.mf.num_users, cfg.mf.num_items, cfg.mf.rank = MF_USERS, MF_ITEMS, rank
        cfg.mf.algo, cfg.mf.batch_size = "sgd", MF_MINIBATCH
        cfg = matrix_fac.pod_config(cfg)
        app = matrix_fac.app_from_config(cfg)
        mesh = Mesh(np.array(topo.devices[: data * kv]).reshape(data, kv), ("data", "kv"))
        rows = spmd.padded_num_keys(cfg.data.num_keys, kv)
        table = NamedSharding(mesh, spmd.state_spec())
        state = {"mf.w": jax.ShapeDtypeStruct((rows, store.row_stride(rank)), jnp.float32, sharding=table)}
        feed = NamedSharding(mesh, spmd.batch_spec())
        lead = (K,) if program == "multistep" else ()
        fields = {
            "unique_keys": ((MF_SLOTS,), jnp.int32), "local_ids": ((MF_SLOTS,), jnp.int32),
            "row_splits": ((MF_MINIBATCH + 1,), jnp.int32), "values": ((MF_SLOTS,), jnp.float32),
            "labels": ((MF_MINIBATCH,), jnp.float32), "example_mask": ((MF_MINIBATCH,), jnp.bool_),
        }
        batch = {
            k: jax.ShapeDtypeStruct((data, *lead, *shape), dt, sharding=feed)
            for k, (shape, dt) in fields.items()
        }
        if program == "init":
            jitted, args = jax.jit(lambda: app.init_tables(rows), out_shardings=table), ()
        else:
            if program == "multistep":
                fn, args = spmd.make_spmd_train_multistep(app, mesh, cfg.data.num_keys), (state, batch, 0)
            else:
                fn, args = spmd.make_spmd_predict_step(app, mesh, cfg.data.num_keys), (state, batch)
            (jitted,) = [
                c.cell_contents for c in fn.__closure__
                if callable(c.cell_contents) and hasattr(c.cell_contents, "lower")
            ]
        compiled = jitted.lower(*args).compile()
        texts[key] = compiled.as_text()
        texts[key, "memory"] = compiled.memory_analysis()
        return texts[key]

    get.texts = texts
    return get


@pytest.mark.parametrize("data,kv,program", MF_CASES)
def test_mf_table_ops_are_scoped_and_the_table_is_read_where_it_lies(mf_text, data, kv, program):
    """Every executed instruction that reads or writes the 64-lane table,
    as it is stored or through its view as 32-lane blocks, sits under
    ``ps.pull/mf`` or ``ps.push/<stage>/mf``; and the table is held once,
    unpadded (11.95 GiB), and read where it lies: a gather of whole 64-lane
    rows would have XLA copy it into lane-padded row-major tiles every
    microstep, 23.9 GiB more, and the step would not compile. The view
    ``[rows, 2, 32]`` is a ``bitcast`` of that layout, and the gather of
    its blocks the row gather of the narrower tables, not the gather of
    8.4M single elements the step had until PR 33 (``store.take_rows``)."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    text = mf_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, MF_NAMES)
    rows = spmd.padded_num_keys(1 + MF_ITEMS + MF_USERS, kv) // kv
    lanes = store._block_lanes(MF_RANK)
    assert lanes == 32
    stored, view = rf"\[{rows},{MF_RANK}\]", rf"\[{rows},{MF_RANK // lanes},{lanes}\]"
    table = re.compile(rf"{stored}|{view}")
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/mf$", scope) for _, scope in touching), touching
    found = set(scopes.values())
    assert {"ps.pull/mf", "ps.grad"} <= found, found
    every = instructions(text)
    bitcasts = [
        shape for _, _, shape, opcode, operand_shapes, _ in every
        if opcode == "bitcast" and re.search(view, shape) and re.search(stored, operand_shapes[0])
    ]
    assert bitcasts, "the gather's operand is not a view of the table where it lies"
    assert not copies_of(every, rows * MF_RANK)
    element_gathers = [name for _, name, shape, _, _, _ in every if re.match(rf"^f32\[{MF_SLOTS * MF_RANK}\]", shape)]
    assert not element_gathers, element_gathers
    if program == "multistep":
        assert "ps.push/scatter/mf" in found, found
        scatters = [rest for _, _, shape, opcode, _, rest in every if opcode == "scatter" and re.search(stored, shape)]
        assert len(scatters) == 1, scatters
        assert store.scatter_rows_sorted(rows, MF_RANK, MF_SLOTS) and sorted_hint(scatters[0]), scatters
    mem = mf_text.texts[(data, kv, program), "memory"]
    table_bytes = 4 * rows * MF_RANK
    assert table_bytes == 12_831_424_512
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < table_bytes + (512 << 20)


@pytest.mark.parametrize("vdim", [128, 256, 300])
def test_rows_of_whole_tiles_are_taken_from_the_table_where_it_lies(topo, vdim):
    """A table whose rows are whole 128-lane tiles has nothing to pad, so the
    chip keeps it row-major and ``take_rows`` takes whole rows from it: its
    view as 32-lane blocks would be no bitcast there but a copy of the table
    (11.9 GiB beside a table of 11.9: no fit). With the push's scatter in
    the same program, at a table that fills the chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from parameter_server_tpu.kv import store

    one = SingleDeviceSharding(topo.devices[0])
    stride = store.row_stride(vdim)  # 300 lanes are stored in 384: whole tiles too
    rows = 25_000_960 * 128 // stride
    table = jax.ShapeDtypeStruct((rows, stride), jnp.float32, sharding=one)
    idx = jax.ShapeDtypeStruct((MF_SLOTS,), jnp.int32, sharding=one)

    def pull_and_push(v, at):
        got = store.take_rows(v, at, vdim)
        return store.add_rows(v, at, 0.5 * got, True), got

    compiled = jax.jit(pull_and_push, donate_argnums=0).lower(table, idx).compile()
    assert not copies_of(instructions(compiled.as_text()), rows * vdim)
    # batch-sized: at 384 lanes three (slots, 384) buffers, 576 MiB beside a table of 11.9 GiB
    assert compiled.memory_analysis().temp_size_in_bytes < 768 << 20


@pytest.mark.parametrize("data,kv,program", MF_CASES)
def test_mf_large_unscoped_instructions_are_the_known_kinds(mf_text, data, kv, program):
    """The list of unscoped kinds holds for the third app."""
    from parameter_server_tpu.parallel import spmd

    text = mf_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, MF_NAMES)
    known = re.compile(
        r"^(copy|copy-start|copy-done|custom-call|slice-start|slice-done|async-start|async-done"
        r"|reduce|broadcast|dynamic-update-slice|all-reduce|fusion)$"
    )
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, _ in executed(text)
        if not scopes[name] and elements(shape) >= MF_SLOTS and not known.match(opcode)
    ]
    assert not strays, strays
    for name, shape, opcode, operand_shapes in executed(text):
        if opcode == "fusion" and not scopes[name]:
            # bookkeeping at batch size (a (U, 64) buffer at most), never a table op
            assert elements(shape) <= 2 * 64 * MF_SLOTS and all(elements(s) < MF_USERS for s in operand_shapes), name


MF_2X2_CASES = [(2, 2, "multistep"), (2, 2, "predict"), (2, 2, "init")]


@pytest.mark.parametrize("data,kv,program", MF_2X2_CASES)
def test_mf_rank_100_over_kv_is_held_once_and_its_collectives_are_scoped(mf_text, data, kv, program):
    """The cell ``mfhw2x2.train`` at its shapes: 50,122,752 rows of 100 lanes
    stored 128 wide, 25,061,376 a ``kv`` shard (11.95 GiB a chip of 15.75).
    Every executed instruction that reads or writes a shard sits under
    ``ps.pull/mf`` or ``ps.push/<stage>/mf``; the shard is held once (the
    step's two scatters, one a worker, take it in place, unhinted: 24,473
    table elements a slot); the table's start is made at its stride in one
    pass (a ``jnp.pad`` of a 100-lane slot held a second table: 23.9 GiB, no
    fit, which is how the parent fails on the cell); and the step's two
    collectives sit under scopes of their own at the shapes the chip moves:
    the pull's ``psum`` over ``kv`` of every slot of the bucket (52 MB), the
    push's ``all_gather`` over ``data`` of both workers' (105 MB out)."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    text = mf_text(data, kv, program, MF_RANK_2X2)
    mem = mf_text.texts[(data, kv, program, MF_RANK_2X2), "memory"]
    rows = spmd.padded_num_keys(1 + MF_ITEMS + MF_USERS, kv) // kv
    stride = store.row_stride(MF_RANK_2X2)
    assert (rows, stride) == (25_061_376, 128)
    table_bytes = 4 * rows * stride
    assert table_bytes == 12_831_424_512  # a chip's shard: the one-chip cell's table to the byte
    every = instructions(text)
    assert not copies_of(every, rows * MF_RANK_2X2)
    if program == "init":
        assert mem.output_size_in_bytes == table_bytes
        assert mem.temp_size_in_bytes < 512 << 20, mem.temp_size_in_bytes
        assert not [name for _, name, shape, _, _, _ in every if re.search(rf"\[{rows},{MF_RANK_2X2}\]", shape)]
        return
    _, scopes = spmd.hlo_scopes(text, MF_NAMES)
    table = re.compile(rf"\[{rows},{stride}\]")
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/mf$", scope) for _, scope in touching), touching
    found = set(scopes.values())
    assert {"ps.pull/mf", "ps.pull/mf/psum", "ps.grad"} <= found, found
    # the table is row-major wherever it appears: whole tiles, nothing to pad
    assert all("{1,0:" in shape for _, _, shape, _, _, _ in every if table.search(shape) and shape.startswith("f32")), "rows-minor"
    collectives = {
        (opcode, shape.split("{")[0]): scopes[name]
        for _, name, shape, opcode, _, _ in every
        if opcode in ("all-reduce", "all-gather", "all-reduce-start", "all-gather-start") and elements(shape) >= MF_SLOTS
    }
    pulled = ("all-reduce", f"f32[{MF_SLOTS},{MF_RANK_2X2}]")
    assert collectives.get(pulled) == "ps.pull/mf/psum", collectives
    assert mem.temp_size_in_bytes < 512 << 20, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < table_bytes + (512 << 20)
    if program == "predict":
        assert set(collectives) == {pulled}, collectives
        return
    assert {"ps.push/scatter/mf", "ps.push/update/mf", "ps.push/mf/all_gather"} <= found, found
    assert collectives == {
        pulled: "ps.pull/mf/psum",
        ("all-gather", f"f32[{data * MF_SLOTS},{MF_RANK_2X2}]"): "ps.push/mf/all_gather",
        ("all-gather", f"s32[{data},1,{MF_SLOTS}]"): "ps.push/mf/all_gather",
    }, collectives
    # a collective's scope is no table op's: what sums "ps.pull/mf" and "ps.push/*/mf" leaves both out
    assert not any(re.match(r"^ps\.(pull|push/\w+)/mf$", scope) for scope in collectives.values())
    scatters = [(comp, rest) for comp, _, shape, opcode, _, rest in every if opcode == "scatter" and table.search(shape)]
    assert len(scatters) == 1, scatters  # one in the loop over the workers' pushes: two a microstep
    ((home, told),) = scatters
    assert sorted_hint(told) is store.scatter_rows_sorted(rows, stride, MF_SLOTS) is False, told
    ((name, rest),) = fusions_calling(every, {home})
    assert scopes[name] == "ps.push/scatter/mf", (name, scopes[name])
    assert aliases_operand_0(rest), (name, rest[-300:])
    assert mem.alias_size_in_bytes >= table_bytes  # the shard is donated through the call


# -- skip-gram: one 300-lane table, stored 384 wide, through the same step -----
SGNS_VOCAB, SGNS_DIM, SGNS_NEG = 3_000_000, 300, 5  # the cell sgns3m.train
SGNS_MINIBATCH = 16_384
SGNS_ENTRIES, SGNS_SLOTS = 7 * SGNS_MINIBATCH, 7 * SGNS_MINIBATCH + 1  # the builder's caps: 114,688 and one more
SGNS_CASES = [(1, 1, "multistep"), (1, 1, "predict")]
SGNS_NAMES = frozenset({"sgns"})


@pytest.fixture(scope="module")
def sgns_text(topo):
    """(data, kv, program) -> optimised HLO text of the skip-gram programs at
    the cell's size (6,000,640 rows x 300 lanes under SGD), compiled once:
    about 16 s for the step and 3 for predict, one mesh (the 2x2 programs
    are the small tests': ``tests/test_word2vec_pod.py``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from parameter_server_tpu.models import word2vec
    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.config import PSConfig

    texts: dict = {}

    def get(data: int, kv: int, program: str) -> str:
        key = (data, kv, program)
        if key in texts:
            return texts[key]
        cfg = PSConfig()
        cfg.w2v.vocab_size, cfg.w2v.dim, cfg.w2v.negatives = SGNS_VOCAB, SGNS_DIM, SGNS_NEG
        cfg.w2v.batch_size = SGNS_MINIBATCH
        cfg = word2vec.pod_config(cfg)
        app = word2vec.app_from_config(cfg)
        mesh = Mesh(np.array(topo.devices[: data * kv]).reshape(data, kv), ("data", "kv"))
        rows = spmd.padded_num_keys(cfg.data.num_keys, kv)
        shapes = jax.eval_shape(lambda: app.init_tables(rows))
        table = NamedSharding(mesh, spmd.state_spec())
        state = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=table) for k, v in shapes.items()}
        feed = NamedSharding(mesh, spmd.batch_spec())
        lead = (K,) if program == "multistep" else ()
        fields = {
            "unique_keys": ((SGNS_SLOTS,), jnp.int32), "local_ids": ((SGNS_ENTRIES,), jnp.int32),
            "row_splits": ((SGNS_MINIBATCH + 1,), jnp.int32), "values": ((SGNS_ENTRIES,), jnp.float32),
            "labels": ((SGNS_MINIBATCH,), jnp.float32), "example_mask": ((SGNS_MINIBATCH,), jnp.bool_),
        }
        batch = {
            k: jax.ShapeDtypeStruct((data, *lead, *shape), dt, sharding=feed)
            for k, (shape, dt) in fields.items()
        }
        if program == "multistep":
            fn, args = spmd.make_spmd_train_multistep(app, mesh, cfg.data.num_keys), (state, batch, 0)
        else:
            fn, args = spmd.make_spmd_predict_step(app, mesh, cfg.data.num_keys), (state, batch)
        (jitted,) = [
            c.cell_contents for c in fn.__closure__
            if callable(c.cell_contents) and hasattr(c.cell_contents, "lower")
        ]
        compiled = jitted.lower(*args).compile()
        texts[key] = compiled.as_text()
        texts[key, "memory"] = compiled.memory_analysis()
        # the table made on the device at its stride in one pass: no second table to pad the first
        if program == "multistep":
            made = jax.jit(lambda: app.init_tables(rows), out_shardings=table).lower().compile()
            texts["init", "memory"] = made.memory_analysis()
        return texts[key]

    get.texts = texts
    return get


@pytest.mark.parametrize("data,kv,program", SGNS_CASES)
def test_sgns_table_ops_are_scoped_and_the_table_is_worked_on_where_it_lies(sgns_text, data, kv, program):
    """A slot of 300-lane rows is stored ``row_stride(300)`` = 384 lanes
    wide, whole tiles, which the chip keeps row-major: the gather of whole
    rows and the push's scatter both work on it in place. At 300 lanes as
    stored until PR 34 the scatter copied the table to row-major and back
    every microstep (8.98 GiB of temporaries beside 6.8: no fit), whatever
    the gather. Every executed instruction that reads or writes the table
    sits under ``ps.pull/sgns`` or ``ps.push/<stage>/sgns``; nothing with the
    table's elements is copied; the temporaries are batch-sized; and the
    table's start is made at its stride in one pass."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    text = sgns_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, SGNS_NAMES)
    rows = spmd.padded_num_keys(1 + 2 * SGNS_VOCAB, kv) // kv
    stride = store.row_stride(SGNS_DIM)
    assert (rows, stride) == (6_000_640, 384)
    table = re.compile(rf"\[{rows},{stride}\]")
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/sgns$", scope) for _, scope in touching), touching
    found = set(scopes.values())
    assert {"ps.pull/sgns", "ps.grad"} <= found, found
    assert "ps.row_ids" not in found  # the entries' slots are read off row_splits: XLA drops the row ids
    every = instructions(text)
    assert not copies_of(every, rows * SGNS_DIM)
    # the table is row-major wherever it appears, and no instruction makes a 300-lane table of it
    assert all("{1,0:" in shape for _, _, shape, _, _, _ in every if table.search(shape) and shape.startswith("f32")), "rows-minor"
    assert not [name for _, name, shape, _, _, _ in every if re.search(rf"\[{rows},{SGNS_DIM}\]", shape)]
    gathers = [s for _, _, _, opcode, ops, _ in every if opcode == "gather" for s in ops[:1] if table.search(s)]
    assert len(gathers) == 1, gathers  # on one chip the push's gather is the pull's
    if program == "multistep":
        assert {"ps.push/scatter/sgns", "ps.push/update/sgns"} <= found, found
        scatters = [(comp, rest) for comp, _, shape, opcode, _, rest in every if opcode == "scatter" and table.search(shape)]
        assert len(scatters) == 1, scatters
        ((home, told),) = scatters
        # a row-major table of 20,091 elements a slot: no hint, the slots taken in turn
        # (PERF.md section 6, PR 38), the table still updated where it lies
        assert sorted_hint(told) is store.scatter_rows_sorted(rows, stride, SGNS_SLOTS), told
        assert not sorted_hint(told), told
        ((name, rest),) = fusions_calling(every, {home})
        assert scopes[name] == "ps.push/scatter/sgns", (name, scopes[name])
        assert aliases_operand_0(rest), (name, rest[-300:])
        init = sgns_text.texts["init", "memory"]
        assert init.temp_size_in_bytes < 64 << 20, init.temp_size_in_bytes
    mem = sgns_text.texts[(data, kv, program), "memory"]
    table_bytes = 4 * rows * stride
    assert table_bytes == 9_216_983_040
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < table_bytes + (768 << 20)
    assert mem.temp_size_in_bytes < 640 << 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("data,kv,program", SGNS_CASES)
def test_sgns_large_unscoped_instructions_are_the_known_kinds(sgns_text, data, kv, program):
    """The list of unscoped kinds holds for the fourth app."""
    from parameter_server_tpu.parallel import spmd

    text = sgns_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, SGNS_NAMES)
    known = re.compile(
        r"^(copy|copy-start|copy-done|custom-call|slice-start|slice-done|async-start|async-done"
        r"|reduce|broadcast|dynamic-update-slice|all-reduce|fusion)$"
    )
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, _ in executed(text)
        if not scopes[name] and elements(shape) >= SGNS_SLOTS and not known.match(opcode)
    ]
    assert not strays, strays
    for name, shape, opcode, operand_shapes in executed(text):
        if opcode == "fusion" and not scopes[name]:
            # bookkeeping at batch size (a (U, 384) buffer at most), never a table op
            assert elements(shape) <= 384 * SGNS_SLOTS and all(elements(s) < SGNS_VOCAB for s in operand_shapes), name


# -- the batch solver: a block call at the cell darlin1.pass's shapes ----------
DARLIN_KEYS, DARLIN_BLOCKS, DARLIN_N = 1 << 26, 64, 11_460_154
DARLIN_CHUNK, DARLIN_CHUNKS, DARLIN_CALL = 1 << 16, 6_880, 4  # 447M entries in chunks of 2^16
DARLIN_SCOPES = {"ps.pull", "ps.grad", "ps.push", "darlin.xd", "darlin.linesearch"}  # a step's


@pytest.fixture(scope="module")
def darlin_compiled(topo):
    """program -> (optimised HLO text, memory analysis) of the solver's
    block call and KKT refresh on one chip at the cell's shapes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parameter_server_tpu.kv.updaters import ProxNewton
    from parameter_server_tpu.models.darlin import make_darlin_fns
    from parameter_server_tpu.parallel import spmd

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "kv"))
    fns = make_darlin_fns(
        mesh, spmd.Table("", ProxNewton(), 1), num_keys=DARLIN_KEYS,
        block_size=DARLIN_KEYS // DARLIN_BLOCKS, per_shard_examples=DARLIN_N, delay=0,
    )

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    state = {k: arg((DARLIN_KEYS, 1), jnp.float32, spmd.state_spec()) for k in ("w", "active")}
    ex = arg((DARLIN_N,), jnp.float32, P("data"))
    chunks = {
        k: arg((DARLIN_CHUNKS, DARLIN_CHUNK), jnp.float32 if k == "values" else jnp.int32, P("data", None))
        for k in ("feat_local", "rows", "values")
    }
    chunks["ends"] = arg((DARLIN_BLOCKS, DARLIN_KEYS // DARLIN_BLOCKS), jnp.int32, P("data", None))
    call = (
        chunks, arg((1, DARLIN_CALL, 3), jnp.int32, P("data", None, None)),
        arg((DARLIN_CALL,), jnp.int32, P(None)), arg((DARLIN_CALL,), jnp.bool_, P(None)),
    )
    out = {}
    for name, args in (
        ("block_call", (state, ex, ex, ex, *call)),
        ("refresh_call", (state, ex, ex, ex, *call, arg((), jnp.float32, P()))),
    ):
        compiled = getattr(fns, name).jitted.lower(*args).compile()
        out[name] = (compiled.as_text(), compiled.memory_analysis())
    return out


def test_darlin_block_call_carries_the_five_scopes_at_the_cells_shapes(darlin_compiled):
    """Every instruction of the block call that reads the resident entries,
    the table or a vector over the examples sits under one of the five
    scopes the ``step.*`` readers find device time by; the range pull and
    push are slices in place, no gather, scatter or copy of the table. The
    refresh's program lies whole under its one name, so that a window that
    holds both leaves the five a step's."""
    from parameter_server_tpu.parallel import spmd

    _, refresh_scopes = spmd.hlo_scopes(darlin_compiled["refresh_call"][0])
    assert {s for s in refresh_scopes.values() if s} == {"darlin.refresh"}
    text, _ = darlin_compiled["block_call"]
    _, scopes = spmd.hlo_scopes(text)
    found = {s.split("/")[0] for s in scopes.values() if s}
    assert found == DARLIN_SCOPES, found
    big = re.compile(rf"\[({DARLIN_CHUNKS},{DARLIN_CHUNK}|{DARLIN_KEYS}(,1)?|{DARLIN_N}|8,{DARLIN_N})\]")
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, operand_shapes in executed(text)
        if not scopes[name] and opcode not in ("copy-start", "copy-done", "custom-call")
        and (big.search(shape) or any(big.search(s) for s in operand_shapes))
        and opcode != "dynamic-slice"  # the loop's own slicing of a chunk out of the set
    ]
    assert not strays, strays
    every = instructions(text)
    assert not copies_of(every, DARLIN_KEYS), "the table is copied"
    table = re.compile(rf"\[{DARLIN_KEYS}(,1)?\]")
    for _, name, shape, opcode, operand_shapes, _ in every:
        if opcode in ("gather", "scatter"):
            assert not any(table.search(s) for s in operand_shapes), (name, "a range is a slice, not a gather")


def test_darlin_sums_by_feature_are_passes_along_the_entries(darlin_compiled):
    """The mechanism engaged in both programs: neither the block call nor
    the refresh holds a scatter of a chunk's 65,536 entries into a block's
    ``f32[1048576]`` (the sorted scatter-adds the sums by feature were up
    to PR 54), the running sums' passes, (lanes, <= 65,536) values a
    chunk, are entries-minor (left alone XLA keeps the lane-padded layout of
    the gather that made the terms), and the vectors over the examples that
    the gradient gathers from keep their place in the fast memory space."""
    block = DARLIN_KEYS // DARLIN_BLOCKS
    for name, lanes in (("block_call", 2), ("refresh_call", 1)):
        every = instructions(darlin_compiled[name][0])
        chunk_scatters = [
            (iname, shape) for _, iname, shape, opcode, operand_shapes, _ in every
            if opcode == "scatter" and shape.startswith(f"f32[{block}]")
            and any(s.startswith(f"s32[{DARLIN_CHUNK}") for s in operand_shapes)
        ]
        assert not chunk_scatters, (name, chunk_scatters)
        passes = [
            shape for _, _, shape, opcode, _, _ in every
            if opcode in ("add", "select", "pad")
            and (m := re.match(rf"f32\[{lanes},(\d+)\]", shape)) and DARLIN_CHUNK // 2 <= int(m.group(1)) <= DARLIN_CHUNK
        ]
        assert len(passes) >= 3 * 16, (name, len(passes))  # 16 gated shifted adds a chunk
        assert all(re.match(r"f32\[\d+,\d+\]\{1,0[:}]", shape) for shape in passes), (name, passes)
        # the gathers by example read out of the fast memory space (a buffer of
        # a block's running sums once took the refresh's residual's place
        # there: 17 ns an entry for 7.2, on the chip), and nothing is as long
        # as a block's entries but the resident chunks themselves
        sources = [
            operand_shapes[0] for _, _, _, opcode, operand_shapes, _ in every
            if opcode == "gather" and operand_shapes[0].startswith(f"f32[{DARLIN_N}]")
        ]
        assert len(sources) == lanes and all("S(1)" in shape for shape in sources), (name, sources)
        assert darlin_compiled[name][1].temp_size_in_bytes < 64 << 20, name


def test_darlin_programs_hold_the_entries_once(darlin_compiled):
    """Peak at the cell's shapes, by the compiler's own count: the resident
    entries (5.0 GiB), the table and the vectors over the examples, and
    under 1 GiB of temporaries - no second copy of the chunk arrays (a
    leading axis of one on them cost one, 5.3 GiB, in this PR's first
    form)."""
    for name, (_, mem) in darlin_compiled.items():
        assert mem.temp_size_in_bytes < 1 << 30, (name, mem.temp_size_in_bytes)
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30, name


# -- DLRM: 26 per-field tables as one 128-lane table, and a dense half that costs --------
DLRM_CARDS = [
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951, 2953546, 403346, 10,
    2208, 11938, 155, 4, 976, 14, 39979771, 25641295, 39664984, 585935, 12972, 108, 36,
]
DLRM_FIELD_ROWS = [min(c, 4_000_000) for c in DLRM_CARDS]  # the cell dlrm1tb.train: max_ind_range 4,000,000
DLRM_DIM = 128
DLRM_CASES = [(1, 1, "multistep"), (1, 1, "predict")]
DLRM_NAMES = frozenset({"emb", "mlp", "bot", "interact", "top"})  # the app's StepApp.scope_names()


def compile_dlrm(topo, cfg, names, data, kv, program, steps, nnz, unique):
    """(compiled program, app, mesh, rows a chip) of app ``dlrm`` under
    ``cfg``'s [dlrm] settings: the scanned step of ``steps`` microsteps
    (``program`` "multistep") or the predict program, on the first ``data``
    x ``kv`` described devices, at a batch of ``nnz`` entry slots and
    ``unique`` key slots."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding

    from parameter_server_tpu.models import dlrm
    from parameter_server_tpu.parallel import spmd

    cfg = dlrm.pod_config(cfg)
    app = dlrm.app_from_config(cfg)
    assert app.scope_names() == names
    mesh = Mesh(np.array(topo.devices[: data * kv]).reshape(data, kv), ("data", "kv"))
    specs = app.specs()
    rows = spmd.padded_num_keys(cfg.data.num_keys, kv)
    shapes = jax.eval_shape(lambda: {**app.init_tables(rows), **app.dense.init_state()})
    state = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(mesh, specs[k]))
        for k, v in shapes.items()
    }
    feed = NamedSharding(mesh, spmd.batch_spec())
    lead = (steps,) if program == "multistep" else ()
    fields = {
        "unique_keys": ((unique,), jnp.int32), "local_ids": ((nnz,), jnp.int32),
        "row_splits": ((MINIBATCH + 1,), jnp.int32), "values": ((nnz,), jnp.float32),
        "labels": ((MINIBATCH,), jnp.float32), "example_mask": ((MINIBATCH,), jnp.bool_),
    }
    batch = {
        k: jax.ShapeDtypeStruct((data, *lead, *shape), dt, sharding=feed)
        for k, (shape, dt) in fields.items()
    }
    if program == "multistep":
        fn, args = spmd.make_spmd_train_multistep(app, mesh, cfg.data.num_keys), (state, batch, 0)
    else:
        fn, args = spmd.make_spmd_predict_step(app, mesh, cfg.data.num_keys), (state, batch)
    (jitted,) = [
        c.cell_contents for c in fn.__closure__
        if callable(c.cell_contents) and hasattr(c.cell_contents, "lower")
    ]
    return jitted.lower(*args).compile(), app, mesh, rows


@pytest.fixture(scope="module")
def dlrm_text(topo):
    """(data, kv, program) -> optimised HLO text of the DLRM programs at the
    cell's size (24,065,024 rows x 128 lanes under SGD, MLPs 13-512-256-128
    and 479-1024-1024-512-256-1, ``ctr1.train``'s batch shapes), compiled
    once; the 2x2 and 1x4 programs are the small tests'
    (``tests/test_dlrm_pod.py``)."""
    import jax
    from jax.sharding import NamedSharding

    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.config import PSConfig

    texts: dict = {}

    def get(data: int, kv: int, program: str) -> str:
        key = (data, kv, program)
        if key in texts:
            return texts[key]
        cfg = PSConfig()
        cfg.dlrm.field_rows = DLRM_FIELD_ROWS
        compiled, app, mesh, rows = compile_dlrm(topo, cfg, DLRM_NAMES, data, kv, program, K, NNZ, UNIQUE)
        texts[key] = compiled.as_text()
        texts[key, "memory"] = compiled.memory_analysis()
        if program == "multistep":  # the table made on the device in one pass
            table = NamedSharding(mesh, spmd.state_spec())
            made = jax.jit(lambda: app.init_tables(rows), out_shardings=table).lower().compile()
            texts["init", "memory"] = made.memory_analysis()
        return texts[key]

    get.texts = texts
    return get


@pytest.mark.parametrize("data,kv,program", DLRM_CASES)
def test_dlrm_holds_one_table_and_names_its_dense_phases(dlrm_text, data, kv, program):
    """The cell ``dlrm1tb.train`` at its shapes: 24,065,024 rows of 128
    lanes, 11.475 GiB of the chip's 15.75. Every executed instruction that
    reads or writes the table sits under ``ps.pull/emb`` or
    ``ps.push/<stage>/emb``; the table is held once, row-major, with no copy
    of it among the temporaries (the take by ``local_ids``, its transpose
    and the interaction's (8192, 27, 27) are batch-sized: bounded below);
    the scatter is unhinted and in place (46,998 table elements a slot:
    ``store.scatter_rows_sorted`` says taking the slots in turn is the
    cheaper) and walked (``store.scatter_walks``); and the dense half's
    three phases carry their names under ``ps.grad/mlp``, its step
    ``ps.dense``."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    text = dlrm_text(data, kv, program)
    mem = dlrm_text.texts[(data, kv, program), "memory"]
    _, scopes = spmd.hlo_scopes(text, DLRM_NAMES)
    rows = spmd.padded_num_keys(14 + sum(DLRM_FIELD_ROWS), kv) // kv
    assert (14 + sum(DLRM_FIELD_ROWS), rows) == (24_064_006, 24_065_024)
    table_bytes = 4 * rows * DLRM_DIM
    assert table_bytes == 12_321_292_288  # 11.475 GiB
    table = re.compile(rf"\[{rows},{DLRM_DIM}\]")
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/emb$", scope) for _, scope in touching), touching
    found = set(scopes.values())
    assert {"ps.pull/emb", "ps.grad", "ps.grad/mlp/bot", "ps.grad/mlp/interact", "ps.grad/mlp/top"} <= found, found
    every = instructions(text)
    op_name_of = {
        name: (re.search(r'op_name="([^"]*)"', rest) or [None, ""])[1] for _, name, _, _, _, rest in every
    }
    assert not copies_of(every, rows * DLRM_DIM)
    assert all("{1,0:" in shape for _, _, shape, _, _, _ in every if table.search(shape) and shape.startswith("f32")), "rows-minor"
    # the step's temporaries are the batch's: 8192 x 26 rows of 128 lanes each way (109 MB), the
    # pulled rows and their gradient (2 x 34 MB), the MLPs' activations; never a second table
    assert mem.temp_size_in_bytes < (1 << 30), mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < table_bytes + (1280 << 20)
    if program == "predict":
        return
    assert {"ps.push/scatter/emb", "ps.push/update/emb", "ps.dense"} <= found, found
    scatters = [(comp, rest) for comp, _, shape, opcode, _, rest in every if opcode == "scatter" and table.search(shape)]
    assert len(scatters) == 1, scatters
    ((home, told),) = scatters
    assert store.scatter_walks(rows, DLRM_DIM, UNIQUE)
    assert sorted_hint(told) is store.scatter_rows_sorted(rows, DLRM_DIM, store._WALK_SLOTS) is False, told
    ((name, rest),) = fusions_calling(every, {home})
    assert scopes[name] == "ps.push/scatter/emb", (name, scopes[name])
    assert aliases_operand_0(rest), (name, rest[-300:])
    assert mem.alias_size_in_bytes >= table_bytes  # the table is donated through the call
    made = dlrm_text.texts["init", "memory"]
    assert made.output_size_in_bytes == table_bytes
    assert made.temp_size_in_bytes < 512 << 20, made.temp_size_in_bytes
    # the interaction (PR 51): its instructions, the forward's and the hand-written backward
    # pass's, are all found by step.interact_ms; the pairs are cut by a selection product,
    # not by a slice a row; and neither T nor dT is relaid
    inside = [
        (name, shape, opcode)
        for name, shape, opcode, _ in executed(text)
        if "mlp/interact/" in bare_op_name(op_name_of[name])
    ]
    assert len(inside) >= 8, inside  # two products and a selection product each way
    assert all(scopes[name] == "ps.grad/mlp/interact" for name, _, _ in inside), inside
    assert any("transpose(" in op_name_of[name] for name, _, _ in inside), "the backward pass carries the scope"
    # 25 executed slices f32[8192,1,k] of the lane-padded (8192, 27, 27) until PR 51; the one
    # slice of a row left is z0's share of dT, f32[8192,1,128]
    row_slices = [(name, shape) for name, shape, opcode in inside if opcode == "slice" and re.match(r"f32\[8192,1,\d+\]", shape)]
    assert all(shape.startswith("f32[8192,1,128]") for _, shape in row_slices) and len(row_slices) <= 1, row_slices
    # copies of T's or dT's size, (8192, 27, 128) or (8192, 26, 128): 3 in PR 51's parent (the
    # two backward products' results to batch-minor, e's share back to row-major), none now
    relaid = [
        (name, shape) for name, shape, opcode, _ in executed(text)
        if opcode == "copy" and elements(shape) in (MINIBATCH * 27 * DLRM_DIM, MINIBATCH * 26 * DLRM_DIM)
    ]
    assert not relaid, relaid


@pytest.mark.parametrize("data,kv,program", DLRM_CASES)
def test_dlrm_every_large_op_is_under_a_scope(dlrm_text, data, kv, program):
    """The list of unscoped kinds holds for the sixth app, and nothing the
    size of a batch's gathered rows runs outside a ``ps.*`` scope."""
    from parameter_server_tpu.parallel import spmd

    text = dlrm_text(data, kv, program)
    _, scopes = spmd.hlo_scopes(text, DLRM_NAMES)
    known = re.compile(
        r"^(copy|copy-start|copy-done|custom-call|slice-start|slice-done|async-start|async-done"
        r"|reduce|broadcast|dynamic-update-slice|all-reduce|fusion)$"
    )
    strays = [
        (name, opcode, shape)
        for name, shape, opcode, _ in executed(text)
        if not scopes[name] and elements(shape) >= UNIQUE and not known.match(opcode)
    ]
    assert not strays, strays
    for name, shape, opcode, operand_shapes in executed(text):
        if opcode == "fusion" and not scopes[name]:
            # bookkeeping at batch size (a (U, 128) buffer at most), never a table op
            assert elements(shape) <= DLRM_DIM * UNIQUE and all(elements(s) < 10**7 for s in operand_shapes), name


# -- the multi-hot form: bags summed a field, the cross network, AdaGrad; the streamed scatter --------
DCN_VOCAB = [
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000, 3067956, 405282, 10,
    2209, 11938, 155, 4, 976, 14, 40000000, 40000000, 40000000, 590152, 12973, 108, 36,
]
DCN_FIELD_ROWS = [min(c, 1_500_000) for c in DCN_VOCAB]  # the cell dcn1tb.train: max_ind_range 1,500,000
DCN_HOT = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1]
DCN_K, DCN_NNZ, DCN_UNIQUE = 4, 1 << 21, 1 << 20  # 4 microsteps a call; 227 x 8192 entries; about 627,000 keys
DCN_NAMES = frozenset({"emb", "mlp", "bot", "cross", "top", "pool"})


@pytest.fixture(scope="module")
def dcn_compiled(topo):
    """(optimised HLO text, memory analysis) of ``dcn1tb.train``'s scanned
    step at the cell's sizes: 10,117,120 rows x 128 lanes of ``w`` and
    ``n`` under AdaGrad, bags of 1 to 100 ids, three cross layers of rank
    512, a 2^21-slot entry axis and a 2^20-slot key axis. One compile,
    about half a minute."""
    from parameter_server_tpu.utils.config import PSConfig

    cfg = PSConfig()
    d = cfg.dlrm
    d.field_rows, d.hot, d.cross_layers, d.cross_rank, d.updater, d.eta = DCN_FIELD_ROWS, DCN_HOT, 3, 512, "adagrad", 0.004
    cfg.data.max_nnz_per_example = 256
    compiled, *_ = compile_dlrm(topo, cfg, DCN_NAMES, 1, 1, "multistep", DCN_K, DCN_NNZ, DCN_UNIQUE)
    return compiled.as_text(), compiled.memory_analysis()


def test_dcn_step_names_its_phases_streams_both_scatters_and_holds_no_second_table(dcn_compiled):
    """The cell ``dcn1tb.train`` at its shapes. The step names
    ``ps.grad/emb/pool`` (the take by position, the sum a field, their
    backward pass) and ``ps.grad/mlp/cross`` beside ``bot`` and ``top``; both
    of ``emb``'s scatters (``w`` and ``n``) carry ``indices_are_sorted``:
    1,235 table elements a key slot, under ``_STREAM_ELEMENTS_A_SLOT``, so
    ``store.scatter_rows_sorted`` says the streamed emitter is the cheaper,
    the scatter is one whole scatter and is not walked (the first cell on
    this side of the rule), in place; and the step's temporaries (the pulled
    rows, the take of 214 rows an example and its cotangent, the push's
    gathered rows and deltas) stay under 2.26 GiB, which a copy of either slot
    (4.82 GiB) would break, and so would a relaid copy of the bags' rows or of
    their cotangent (0.84 GiB each): under ``ps.grad/emb/pool`` nothing of
    that size is copied, reshaped, transposed or written in a layout other
    than row-major."""
    from parameter_server_tpu.kv import store
    from parameter_server_tpu.parallel import spmd

    text, mem = dcn_compiled
    _, scopes = spmd.hlo_scopes(text, DCN_NAMES)
    rows = spmd.padded_num_keys(14 + sum(DCN_FIELD_ROWS), 1)
    assert (14 + sum(DCN_FIELD_ROWS), rows) == (10_116_646, 10_117_120)
    slot_bytes = 4 * rows * DLRM_DIM
    assert 2 * slot_bytes == 10_359_930_880  # w + n: 9.65 GiB
    found = set(scopes.values())
    assert {
        "ps.pull/emb", "ps.grad/emb/pool", "ps.grad/mlp/bot", "ps.grad/mlp/cross", "ps.grad/mlp/top",
        "ps.push/gather/emb", "ps.push/update/emb", "ps.push/scatter/emb", "ps.dense",
    } <= found, found
    assert "ps.grad/mlp/interact" not in found
    every = instructions(text)
    op_name_of = {
        name: (re.search(r'op_name="([^"]*)"', rest) or [None, ""])[1] for _, name, _, _, _, rest in every
    }
    pooled = [(name, shape, opcode) for name, shape, opcode, _ in executed(text) if scopes[name] == "ps.grad/emb/pool"]
    assert any("transpose(" in op_name_of[name] for name, _, _ in pooled), "the backward pass carries the scope"
    # the bags' rows and their cotangent, sum(hot) x 8192 x 128 = 898 MB each, live position-major
    # (PR 53): what writes one is the take and the one write of the cotangent's planes (the
    # scatter-add reads that in place), each row-major; nothing relays one (five passes did
    # until PR 53: a select, two reshapes, a pad-and-add into batch-minor, a copy back)
    whole = [(name, shape, opcode) for name, shape, opcode in pooled if elements(shape) >= sum(DCN_HOT) * MINIBATCH * DLRM_DIM]
    assert 1 <= len(whole) <= 3, whole
    assert not [w for w in whole if w[2] in ("copy", "reshape", "transpose")], whole
    assert all(re.search(r"\{(2,1,0|1,0):", shape) for _, shape, _ in whole), whole
    # the planes of the cotangent are written in place, a field's run at a time, by fusions
    # around a dynamic-update-slice that XLA builds from the concatenate and leaves without an
    # op_name (so under no scope: 1.35 ms a microstep on the chip, in step.unscoped_share);
    # nothing else the size of the bags' rows runs outside the scope, in any layout
    rows_sized = re.compile(r"^f32\[(214,8192,128|8192,214,128|1753088,128)\]")
    outside = [
        (name, shape, opcode) for name, shape, opcode, _ in executed(text)
        if rows_sized.match(shape) and scopes[name] != "ps.grad/emb/pool"
    ]
    assert all(
        opcode == "fusion" and "dynamic-update-slice" in name and "{2,1,0:" in shape and not scopes[name]
        for name, shape, opcode in outside
    ), outside
    table = re.compile(rf"\[{rows},{DLRM_DIM}\]")
    touching = [
        (name, scopes[name])
        for name, shape, opcode, operand_shapes in executed(text)
        if table.search(shape) or any(table.search(s) for s in operand_shapes)
    ]
    assert touching
    assert all(re.match(r"^ps\.(pull|push/\w+)/emb$", scope) for _, scope in touching), touching
    assert store.scatter_rows_sorted(rows, DLRM_DIM, DCN_UNIQUE) and not store.scatter_walks(rows, DLRM_DIM, DCN_UNIQUE)
    assert rows * DLRM_DIM // DCN_UNIQUE == 1235 < store._STREAM_ELEMENTS_A_SLOT
    scatters = [(comp, rest) for comp, _, shape, opcode, _, rest in every if opcode == "scatter" and table.search(shape)]
    assert len(scatters) == 2, scatters  # w and n
    assert all(sorted_hint(told) for _, told in scatters), scatters
    # the streamed emitter's scatter sits in a fusion inside the executed fusion: climb to that one
    homes, outer = {home for home, _ in scatters}, []
    comp_of = {name: comp for comp, name, _, _, _, _ in every}
    run = {name for name, _, _, _ in executed(text)}
    while homes:
        calling = fusions_calling(every, homes)
        outer += [(name, rest) for name, rest in calling if name in run]
        homes = {comp_of[name] for name, _ in calling if name not in run}
    assert len(outer) == 2, outer
    for name, rest in outer:
        assert scopes[name] == "ps.push/scatter/emb", (name, scopes[name])
        assert aliases_operand_0(rest), (name, rest[-300:])
    assert not copies_of(every, rows * DLRM_DIM)
    assert mem.alias_size_in_bytes >= 2 * slot_bytes  # both slots donated through the call
    # 2.009 GiB since PR 53 (2.25 with the bags' rows relaid), and a quarter GiB of room
    assert mem.temp_size_in_bytes < int(2.26 * 2**30), mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * slot_bytes + int(2.26 * 2**30)
