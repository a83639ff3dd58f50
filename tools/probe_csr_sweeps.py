"""The sweeps of ``ps.grad`` alone, on the chip (step 0 of ISSUE 42; PERF.md
section 6, PR 42): one 8192-example bucket of the benchmark's Criteo-shaped
traffic from the seed, as every CTR cell dispatches it: 524,288 entry slots
of which 319,488 real (39 an example, the 39% pads at the tail, their row
the last: ``spmd._row_ids_of``), 8,192 rows, 65,536 key slots; float32, one
lane (the linear app, Wide&Deep's wide half) and 16 (its pooled embedding).
Host clock, twenty calls back to back, three sets; every form's result is
checked on the chip against the form it would replace (a pad's
entry aside, which the new spread leaves 0).

Forms, at each lane count:
  take_by_slot, sum_by_slot        the two sweeps by ``local_ids`` whole, as
                                   one ``jnp.take`` / ``segment_sum`` (a true
                                   element gather / scatter-add; see --walk)
  sum_by_example_today [_sorted]   ``segment_sum(x, row_ids)`` as the step
                                   had it, and with ``indices_are_sorted``
  take_by_example_today            ``jnp.take(v, row_ids)``, its transpose
  sum_by_example, spread_by_example  ``ops.sparse``'s gated passes, log2(NNZ)
                                   = 19 of them
  sum_by_example_6, spread_by_example_6  the same passes stopped at the
                                   longest row (39 entries: 6 passes)
  sum_by_example_vjp               ``sum_by_example``'s backward pass for a
                                   cotangent (Wide&Deep's: the spread)
  sum_by_example_unpinned          16 lanes only: the passes in whatever
                                   layout XLA gives ``(16, NNZ)`` without
                                   ``with_layout_constraint``
and the whole of ``ps.grad``: ``linear_grad`` / ``wd_grad`` as the step
calls them beside ``*_today``, the same with ``segment_sum`` / ``take`` by
``row_ids`` (``wd_grad_unpinned`` as above). One JSON line a form, also appended to
chiprun_out/probe_csr_sweeps.jsonl.

``--walk [PIECE ...]`` (step 0 of ISSUE 47; PERF.md section 6, PR 47) reads
the two sweeps by key slot alone instead, ``ops.sparse.take_by_slot`` /
``sum_by_slot`` at 1 and 16 lanes: whole (``sparse.sweep_walks`` made to say
no) against the walk over the pieces of the entry axis that hold a real
entry, at pieces of 8,192 to 65,536 entry slots (or those named), at the
bucket's own 319,488 real entries and, for the line through zero, at 0,
131,072 and all 524,288 (one compiled program a piece: the trip count is
read on the chip); each walked result is compared with the whole one on the
chip and a pad's place must read 0. Then the whole of ``ps.grad`` both ways
at each piece (``linear_grad_*``, ``wd_grad_*``). About 3 chip-minutes.
Seed 2470000001, ms a call (the least of three sets of twenty), whole then
pieces of 8,192 / 16,384 / 32,768 / 65,536: take 1 lane 3.784 -> 2.373 /
2.400 / 2.401 / 2.398; sum 1 lane 3.949 -> 2.268 / 2.310 / 2.292 / 2.281;
take 16 lanes 2.240 -> 1.152 / 1.139 / 1.139 / 1.142; sum 16 lanes 8.088
-> 14.349 / 3.935 / 3.878 / 3.850 (at 8,192 XLA emits the unsorted scatter:
the grid and the rule are in the comment above ``sparse._WALK_ENTRIES``);
linear_grad 7.896 -> 4.803 / 4.879 / 4.864 / 4.849; wd_grad 20.006 ->
22.889 / 12.539 / 12.459 / 12.597. A line through zero in the slots visited
(take, 1 lane, 8,192: 0.19 / 0.99 / 2.37 / 3.88 at 0 / 131,072 / 319,488 /
524,288 real), 1-2 us a loop turn.

    chiprun --timeout 900 -- python3 tools/probe_csr_sweeps.py [SEED] [--walk [PIECE ...]]
"""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax, jax.numpy as jnp
from benchmark.harness import criteo
from parameter_server_tpu.models import wide_deep
from parameter_server_tpu.ops import sparse
from parameter_server_tpu.parallel import spmd

SEED = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 2420000001
B, NNZ, U, PER_ROW, EMB = 8192, 1 << 19, 1 << 16, 39, 16
print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
assert jax.devices()[0].platform == "tpu"
spec = json.load(open("benchmark/configs/ctr_ftrl_1chip.json"))["data"]


def bucket():
    """One batch's device fields as ``BatchBuilder`` + ``stack_batches`` ship them."""
    labels, ints, cats = criteo.make_examples(SEED, B, spec)
    rows, vals = criteo.features(ints, cats, 1 << 30)
    uniq = np.unique(rows.ravel())
    assert U // 2 < 1 + len(uniq) <= U, (len(uniq), U)  # the builder's bucket
    real = B * PER_ROW
    local_ids, values = np.zeros(NNZ, np.int32), np.zeros(NNZ, np.float32)
    local_ids[:real] = 1 + np.searchsorted(uniq, rows.ravel())
    values[:real] = np.asarray(vals, np.float32).ravel()
    b = {
        "unique_keys": np.zeros(U, np.int32), "local_ids": local_ids, "values": values,
        "row_splits": (np.arange(B + 1) * PER_ROW).astype(np.int32),
        "labels": labels.astype(np.float32), "example_mask": np.ones(B, bool),
    }
    return {k: jnp.asarray(v) for k, v in b.items()}


def row_scan(x, row_ids, steps):
    """``sparse._row_scan`` stopped after ``steps`` passes."""
    if x.ndim > 1:
        x = sparse.with_layout_constraint(x, sparse.Layout(major_to_minor=(0, 1)))
    lead = [(0, 0)] * (x.ndim - 1)
    for k in (1 << s for s in range(steps)):
        same = row_ids[k:] == row_ids[:-k]
        x = x + jnp.pad(jnp.where(same, x[..., :-k], 0), [*lead, (k, 0)])
    return x


def patched(op, name, value):
    """``op`` traced with ``ops.sparse``'s global ``name`` set to ``value``."""
    def f(*args):
        kept = getattr(sparse, name)
        setattr(sparse, name, value)
        try:
            return op(*args)
        finally:
            setattr(sparse, name, kept)
    return f


def stopped(op, steps):
    """``op`` of ``ops.sparse`` with its passes stopped after ``steps``."""
    return patched(op, "_row_scan", lambda x, ids: row_scan(x, ids, steps))


def unpinned(op):
    """``op`` of ``ops.sparse`` with no layout asked of XLA."""
    return patched(op, "with_layout_constraint", lambda x, layout: x)


def timed(fn, args, n=20, reps=3):
    """Compile seconds and ms a call: n calls back to back, reps sets."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        ms.append((time.perf_counter() - t0) / n * 1e3)
    return compile_s, ms, out


def emit(form, lanes, fn, args, want=None, on=True, **more):
    """Time ``fn``; ``want`` is today's result, compared where ``on``."""
    compile_s, ms, out = timed(jax.jit(fn), args)
    res = {"form": form, "lanes": lanes, "seed": SEED, "ms": ms, "compile_s": compile_s, **more}
    if want is not None:
        gaps = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs((a - b) * on))), out, want)
        res["max_gap_to_today"] = max(jax.tree.leaves(gaps))
        res["max_abs"] = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(want))
    print(json.dumps(res), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_csr_sweeps.jsonl", "a") as fh:
        fh.write(json.dumps(res) + "\n")
    return out


def linear_grad_today(pulled, dense, b, row_ids):
    """``spmd._linear_grad`` as the step had it up to PR 40."""
    values = spmd._values_of(b)
    contrib = values * jnp.take(pulled[""].reshape(-1), b["local_ids"])
    logits = jax.ops.segment_sum(contrib, row_ids, num_segments=B)
    loss, err = sparse.logistic_loss(logits, b["labels"], b["example_mask"])
    g = jax.ops.segment_sum(values * jnp.take(err, row_ids), b["local_ids"], num_segments=U)
    return loss, logits, {"": g[:, None]}, None


def wd_loss_today(pulled, mlp, b, row_ids):
    """``wide_deep._loss`` as the step had it up to PR 40."""
    values = spmd._values_of(b)
    contrib = values * jnp.take(pulled["wide"].reshape(-1), b["local_ids"])
    wide = jax.ops.segment_sum(contrib, row_ids, num_segments=B)
    ones = (values != 0).astype(jnp.float32)
    ent_emb = jnp.take(pulled["emb"], b["local_ids"], axis=0)
    num = jax.ops.segment_sum(ent_emb * ones[:, None], row_ids, num_segments=B)
    cnt = jax.ops.segment_sum(ones, row_ids, num_segments=B)
    logits = wide + wide_deep._mlp_apply(mlp, num / jnp.maximum(cnt, 1.0)[:, None])
    m = b["example_mask"].astype(jnp.float32)
    return jnp.sum(m * (jax.nn.softplus(logits) - b["labels"] * logits)), logits


def wd_grad_today(pulled, mlp, b, row_ids):
    (loss, logits), (g, g_mlp) = jax.value_and_grad(wd_loss_today, argnums=(0, 1), has_aux=True)(
        pulled, mlp, b, row_ids)
    return loss, logits, g, g_mlp


WHOLE = 1 << 30  # a piece no entry axis reaches: ``sparse.sweep_walks`` says no, one whole sweep
PIECES = tuple(int(a) for a in sys.argv[sys.argv.index("--walk") + 1:] if a.isdigit()) if "--walk" in sys.argv else ()


def pieced(op, piece):
    """``op`` traced with ``ops.sparse``'s sweeps by key slot walking pieces of ``piece`` entries."""
    return patched(op, "_WALK_ENTRIES", piece)


def walk_forms(b, row_ids, pieces=(8192, 16384, 32768, 65536)):
    """Step 0 of ISSUE 47: ``sparse.take_by_slot`` / ``sum_by_slot`` whole and
    walked at each piece, at the batch's own 319,488 real entries and, for
    the line through zero, with the real count moved (the program is the
    piece's; the trip count is read on the chip), then the whole of
    ``ps.grad`` both ways at each piece."""
    pieces = PIECES or pieces
    local_ids, real_now = b["local_ids"], B * PER_ROW
    rng = np.random.default_rng(SEED)
    for lanes in (1, EMB):
        shape = () if lanes == 1 else (lanes,)
        x = jnp.asarray(rng.standard_normal((NNZ, *shape)).astype(np.float32))  # pads NOT 0: the op may read none
        w = jnp.asarray(rng.standard_normal((U, *shape)).astype(np.float32))
        for real in (real_now, 0, 131072, NNZ):
            splits = jnp.minimum(b["row_splits"], real) if real < NNZ else b["row_splits"].at[-1].set(NNZ)
            live = (np.arange(NNZ) < real).reshape(-1, *(1,) * len(shape))
            taken = summed = None
            for piece in (WHOLE, *pieces):
                if real != real_now and piece not in (WHOLE, pieces[0], pieces[-1]):
                    continue
                tag = f"{'whole' if piece == WHOLE else f'walk{piece}'}_real{real}"
                visited = int(pieced(sparse.walked_entries, piece)(real, NNZ))
                got = emit(f"take_by_slot_{tag}", lanes, pieced(sparse.take_by_slot, piece), (w, local_ids, splits),
                           taken, visited=visited)
                taken = got if taken is None else taken
                assert not bool(jnp.any(got * ~live)), "a pad's place must read 0"
                got = emit(f"sum_by_slot_{tag}", lanes, pieced(lambda x, i, s: sparse.sum_by_slot(x, i, s, U), piece),
                           (x, local_ids, splits), summed, visited=visited)
                summed = got if summed is None else summed
    pulled, mlp = grad_inputs(rng)
    lin = wd = None
    for piece in (WHOLE, *pieces):
        tag = "whole" if piece == WHOLE else f"walk{piece}"
        got = emit(f"linear_grad_{tag}", 1, pieced(spmd._linear_grad, piece), ({"": pulled["wide"]}, None, b, row_ids), lin)
        lin = got if lin is None else lin
        got = emit(f"wd_grad_{tag}", EMB, pieced(wide_deep._grad, piece), (pulled, mlp, b, row_ids), wd)
        wd = got if wd is None else wd


def grad_inputs(rng):
    pulled = {"wide": jnp.asarray(rng.standard_normal((U, 1)).astype(np.float32) * 0.05),
              "emb": jnp.asarray(rng.standard_normal((U, EMB)).astype(np.float32) * 0.05)}
    return pulled, wide_deep.init_mlp(EMB, [1024, 512, 256], seed=SEED % 1000)


b = bucket()
row_ids = jax.jit(spmd._row_ids_of)(b)
if "--walk" in sys.argv:
    walk_forms(b, row_ids)
    sys.exit(0)
splits, local_ids = b["row_splits"], b["local_ids"]
rng = np.random.default_rng(SEED)
for lanes in (1, EMB):
    shape = () if lanes == 1 else (lanes,)
    real = (np.arange(NNZ) < B * PER_ROW).reshape(-1, *(1,) * len(shape))
    x = jnp.asarray(rng.standard_normal((NNZ, *shape)).astype(np.float32) * real)  # pads 0, as a term's are
    v = jnp.asarray(rng.standard_normal((B, *shape)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((U, *shape)).astype(np.float32))
    emit("take_by_slot", lanes, lambda w, i: jnp.take(w, i, axis=0), (w, local_ids))
    emit("sum_by_slot", lanes, lambda x, i: jax.ops.segment_sum(x, i, num_segments=U), (x, local_ids))
    summed = emit("sum_by_example_today", lanes, lambda x, i: jax.ops.segment_sum(x, i, num_segments=B), (x, row_ids))
    emit("sum_by_example_today_sorted", lanes,
         lambda x, i: jax.ops.segment_sum(x, i, num_segments=B, indices_are_sorted=True), (x, row_ids), summed)
    taken = emit("take_by_example_today", lanes, lambda v, i: jnp.take(v, i, axis=0), (v, row_ids))
    emit("sum_by_example", lanes, sparse.sum_by_example, (x, row_ids, splits), summed)
    emit("sum_by_example_6", lanes, stopped(sparse.sum_by_example, 6), (x, row_ids, splits), summed)
    emit("spread_by_example", lanes, sparse.spread_by_example, (v, row_ids, splits), taken, real)
    emit("spread_by_example_6", lanes, stopped(sparse.spread_by_example, 6), (v, row_ids, splits), taken, real)
    emit("sum_by_example_vjp", lanes,
         lambda x, v, i, s: jax.vjp(lambda t: sparse.sum_by_example(t, i, s), x)[1](v)[0], (x, v, row_ids, splits), taken, real)
    if lanes > 1:
        emit("sum_by_example_unpinned", lanes, unpinned(sparse.sum_by_example), (x, row_ids, splits), summed)

pulled, mlp = grad_inputs(rng)
today = emit("linear_grad_today", 1, linear_grad_today, ({"": pulled["wide"]}, None, b, row_ids))
emit("linear_grad", 1, spmd._linear_grad, ({"": pulled["wide"]}, None, b, row_ids), today)
today = emit("wd_grad_today", EMB, wd_grad_today, (pulled, mlp, b, row_ids))
emit("wd_grad", EMB, wide_deep._grad, (pulled, mlp, b, row_ids), today)
emit("wd_grad_unpinned", EMB, unpinned(wide_deep._grad), (pulled, mlp, b, row_ids), today)
