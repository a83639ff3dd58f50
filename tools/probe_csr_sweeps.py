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

``--by-feature [WINDOW ...]`` (step 0 of ISSUE 55; PERF.md section 6, PR 55)
reads the batch solver's two sweeps by feature alone instead (no gather or
scatter by example: the terms are given): 64 chunks of 65,536 entries sorted
by feature into a block of 2^20 features, ``hot`` (30% of the entries one
feature, a run of 19 chunks, the rest skewed) and ``short`` (uniform, no run
over 17 entries), every form checked on the chip against today's. Forms, at
1 lane (g alone, the refresh) and 2 (g and h, the step):
  chunk_sum_today                  a sorted scatter-add a chunk and lane into a
                                   zeroed ``f32[1048576]``, as
                                   ``models/darlin.py`` had it up to PR 54
  passes16 [scan_alone, read_alone]  a chunk's 16 gated passes
                                   (``sparse._row_scan``) with the carry,
                                   written into a buffer of the block's
                                   running sums, and one gather of the 2^20
                                   run ends out of it (the first form of PR
                                   55; its two halves alone)
  passes17_piece2 .. passes19_piece8  the same, 2 / 4 / 8 chunks a loop turn
  passes5 (short only)             the passes the longest run needs
  windowedW                        the program's form: no buffer, a chunk's
                                   run ends read out of its own sums W
                                   features at a time (``darlin._windows``)
  take_d_today, spread_d, place_d_alone, spread_d_windowedW
                                   d by feature: a take an entry; placed at
                                   the run heads of a block-length buffer
                                   and copied down; the placement alone; the
                                   program's form, placed a window at a time
Seed 2550000001, ms for the 64 chunks (4.19M entries; the least of three
sets of twenty), 1 lane / 2 lanes, ``hot`` (``short`` within 0.3%):
chunk_sum_today 37.31 / 74.33 (8.9 ns an entry and lane); passes16 13.20 /
10.55 (scan_alone 5.81 / 6.11: 91-95 us a chunk, 2 us a launch of its 48;
read_alone 7.53 / 4.59 with the 17-33 MB buffer in the fast memory space:
in the cell, 218 MB in HBM, the read cost 19.6 ms a block); piece2 10.24 /
7.47, piece4 10.67 / 8.00, piece8 10.77 / 8.35; passes5 10.03 / 7.35;
windowed1024 14.90 / 23.62, 2048 14.98 / 23.71, 4096 15.72 / 25.14, 8192
17.47 / 28.61, 16384 21.11 / 35.85 (a window costs about 12 us and 5.8 ns a
feature and lane: 2^20 / W + 64 of them here, + 107 in a block of the cell,
where 2,048 and 4,096 come to 22 ms and 1,024 to 27); take_d_today 30.28
(7.2 ns an entry), spread_d 13.47 (place_d_alone 9.24: 8.8 ns a feature),
spread_d_windowed1024 10.94, 2048 10.78, 4096 11.15, 8192 12.26, 16384
14.70. About 4.5 chip-minutes.

    chiprun --timeout 900 -- python3 tools/probe_csr_sweeps.py [SEED] [--walk [PIECE ...] | --by-feature [WINDOW ...]]
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


def patched(op, name, value, module=sparse):
    """``op`` traced with ``module``'s (``ops.sparse``'s) global ``name`` set to ``value``."""
    def f(*args):
        kept = getattr(module, name)
        setattr(module, name, value)
        try:
            return op(*args)
        finally:
            setattr(module, name, kept)
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


def by_feature_forms(C=1 << 16, BS=1 << 20, N=64):
    """Step 0 of ISSUE 55: the batch solver's sweeps by feature alone, over N
    chunks of C entries sorted by feature into a block of BS features (no
    gather or scatter by example: the terms are given). ``hot``: 30% of the
    entries one feature (a run of 19 chunks, as an integer column's key),
    the rest skewed; ``short``: uniform features, no run over a few entries."""
    from jax import lax
    from parameter_server_tpu.models import darlin
    WINDOWS = tuple(int(a) for a in sys.argv[sys.argv.index("--by-feature") + 1:] if a.isdigit()) or (1024, 2048, 4096, 8192, 16384)
    rng = np.random.default_rng(SEED)
    L, LOG = N * C, C.bit_length() - 1  # the passes a chunk takes: 16

    def data(hot_share):
        n_hot = int(L * hot_share)
        m = L - n_hot
        rest = rng.integers(0, BS, m)
        if hot_share:  # half of the rest skewed towards the low ids
            rest = np.where(rng.random(m) < 0.5, rest, (BS * rng.random(m) ** 4).astype(np.int64))
        feat = np.sort(np.concatenate([np.full(n_hot, BS // 3), rest])).astype(np.int32)
        ends = np.bincount(feat, minlength=BS).cumsum().astype(np.int32)
        runs = np.diff(np.concatenate([[0], ends]))
        return jnp.asarray(feat.reshape(N, C)), jnp.asarray(ends), int(runs.max())

    t = jnp.asarray(rng.standard_normal((2, N, C)).astype(np.float32))
    d = jnp.asarray(rng.standard_normal(BS).astype(np.float32))

    def today(lanes):
        """``chunk_sum`` as ``models/darlin.py`` had it up to PR 54."""
        def f(feat, t):
            zero = jnp.zeros(BS, jnp.float32)

            def body(c, gh):
                return tuple(
                    a + lax.optimization_barrier(zero.at[feat[c]].add(t[i, c], indices_are_sorted=True))
                    for i, a in enumerate(gh))
            return jnp.stack(lax.fori_loop(0, N, body, (zero,) * lanes))
        return f

    def read(buf, ends):
        starts = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
        return jnp.where(ends > starts, jnp.take(buf, ends - 1, axis=-1, mode="clip"), 0)

    def scan_buf(lanes, steps, piece):
        """The running sums of every chunk in one buffer: ``piece`` chunks a loop turn."""
        P = piece * C

        def f(feat, t):
            f2, t2 = feat.reshape(-1, P), t[:lanes].reshape(lanes, -1, P)

            def body(c, st):
                buf, last, carry = st
                fl = f2[c]
                s = row_scan(t2[:, c], fl, steps)
                s = s + jnp.where(fl == last, carry[:, None], 0)
                return lax.dynamic_update_slice_in_dim(buf, s, c * P, axis=-1), fl[-1], s[:, -1]
            buf = sparse._entries_minor(jnp.zeros((lanes, L), jnp.float32))
            return lax.fori_loop(0, N // piece, body, (buf, jnp.int32(-1), jnp.zeros(lanes, jnp.float32)))[0]
        return f

    def passes(lanes, steps=LOG, piece=1):
        buf = scan_buf(lanes, steps, piece)
        return lambda feat, t, ends: read(buf(feat, t), ends)

    def place(d, ends):
        starts = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
        return jnp.zeros(L, jnp.float32).at[starts].add(jnp.where(ends > starts, d, 0), indices_are_sorted=True, mode="drop")

    def take_today(feat, d):
        def body(c, out):
            return lax.dynamic_update_slice_in_dim(out, jnp.take(d, feat[c]), c * C, axis=0)
        return lax.fori_loop(0, N, body, jnp.zeros(L, jnp.float32))

    def spread(feat, d, ends):
        heads = place(d, ends)

        def body(c, st):
            out, last, carry = st
            fl = feat[c]
            s = sparse._row_scan(lax.dynamic_slice_in_dim(heads, c * C, C), fl)
            s = s + jnp.where(fl == last, carry, 0)
            return lax.dynamic_update_slice_in_dim(out, s, c * C, axis=0), fl[-1], s[-1]
        return lax.fori_loop(0, N, body, (jnp.zeros(L, jnp.float32), jnp.int32(-1), jnp.float32(0.0)))[0]

    def at_window(fn, window):
        return patched(fn, "_WINDOW", window, darlin)

    def windowed(lanes):
        """The program's form (``models.darlin._block_grad`` less its gathers by
        example): no buffer, a chunk's run ends read out of its own running
        sums a window of the feature axis at a time."""
        def f(feat, t, ends):
            bounds = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])

            def body(c, st):
                g, last, carry = st
                fl = feat[c]
                s, last, carry = darlin._run_scan(t[:lanes, c], fl, last, carry)

                def read(f0, at_head, at_tail, here, g):
                    ends_here = here & (at_tail >= 0) & (at_tail < C)
                    old = lax.dynamic_slice_in_dim(g, f0, here.shape[0], axis=-1)
                    got = jnp.stack([jnp.take(lane, at_tail, mode="clip") for lane in s])
                    return sparse._entries_minor(lax.dynamic_update_slice_in_dim(g, jnp.where(ends_here, got, old), f0, axis=-1))
                return darlin._windows(bounds, fl, c * C, read, g), last, carry
            g = sparse._entries_minor(jnp.zeros((lanes, BS), jnp.float32))
            return lax.fori_loop(0, N, body, (g, jnp.int32(-1), jnp.zeros(lanes, jnp.float32)))[0]
        return f

    def spread_windowed(feat, d, ends):
        """``models.darlin._block_xd`` less its scatter-add by example."""
        bounds = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])

        def body(c, st):
            out, last, carry = st
            fl = feat[c]

            def place(f0, at_head, at_tail, here, heads):
                begins_here = here & (at_head >= 0) & (at_head < C)
                return heads.at[jnp.where(begins_here, at_head, C)].set(lax.dynamic_slice_in_dim(d, f0, here.shape[0]), mode="drop")
            heads = darlin._windows(bounds, fl, c * C, place, jnp.zeros(C, jnp.float32))
            s, last, carry = darlin._run_scan(heads, fl, last, carry)
            return lax.dynamic_update_slice_in_dim(out, s, c * C, axis=0), last, carry
        return lax.fori_loop(0, N, body, (jnp.zeros(L, jnp.float32), jnp.int32(-1), jnp.float32(0.0)))[0]

    for kind, hot_share in (("hot", 0.3), ("short", 0.0)):
        feat, ends, longest = data(hot_share)
        more = {"data": kind, "chunks": N, "longest_run": longest}
        for lanes in (1, 2):
            want = emit(f"chunk_sum_today_{kind}", lanes, today(lanes), (feat, t), **more)
            emit(f"passes{LOG}_{kind}", lanes, passes(lanes), (feat, t, ends), want, **more)
            for window in WINDOWS:
                emit(f"windowed{window}_{kind}", lanes, at_window(windowed(lanes), window), (feat, t, ends), want, **more)
            if kind == "short":  # the passes a chunk's longest run needs, were they counted on the host
                steps = max(int(longest - 1).bit_length(), 1)
                emit(f"passes{steps}_{kind}", lanes, passes(lanes, steps), (feat, t, ends), want, **more)
            else:
                for piece in (2, 4, 8):
                    steps = LOG + piece.bit_length() - 1
                    emit(f"passes{steps}_piece{piece}_{kind}", lanes, passes(lanes, steps, piece), (feat, t, ends), want, **more)
                buf = jax.jit(scan_buf(lanes, LOG, 1))(feat, t)
                emit(f"scan_alone_{kind}", lanes, scan_buf(lanes, LOG, 1), (feat, t), **more)
                emit(f"read_alone_{kind}", lanes, read, (buf, ends), want, **more)
        taken = emit(f"take_d_today_{kind}", 1, take_today, (feat, d), **more)
        emit(f"spread_d_{kind}", 1, spread, (feat, d, ends), taken, **more)
        emit(f"place_d_alone_{kind}", 1, place, (d, ends), **more)
        for window in WINDOWS:
            emit(f"spread_d_windowed{window}_{kind}", 1, at_window(spread_windowed, window), (feat, d, ends), taken, **more)


if "--by-feature" in sys.argv:
    by_feature_forms()
    sys.exit(0)
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
