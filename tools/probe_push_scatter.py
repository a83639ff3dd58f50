"""The table ops of a push alone, on the chip (step 0 of ISSUEs 27 and 35;
PERF.md section 6, PRs 27 and 35): one slot, host clock, ten calls back to
back, three sets. Scatter-add of one real bucket's key slots (one
8192-example batch of the benchmark's Criteo-shaped traffic from the seed,
keys as BatchBuilder writes them: 65,536 slots since PR 31, 524,289 when PR
27's numbers were read) into f32[2^30,1], f32[100000768,1] and
f32[100000768,16], into f32[2^30,1] as either kv shard of a 2^31-key table,
into one lane at 2^26 to 2^29 rows, and of the 2048 pad slots of the call
that ends an epoch into f32[2^30,1]. Three forms a case: ``today``, the
scatter the step had up to PR 26 (rows clamped to 0, deltas masked, XLA
told nothing); ``ascending``, the step's from PR 27 to PR 34
(``spmd._ascending_rows`` + ``spmd._add_rows`` with ``indices_are_sorted``
whatever the shapes); ``unhinted``, the same rows with pads and other
shards' keys dropped and XLA told nothing, which is what the step takes
since PR 35 where ``spmd.scatter_rows_sorted`` says the hint does not pay
(``rule_sorted`` on the case's line is what it says there; since PR 38 it
says so of whole-tile tables too, by their elements a slot). ``--gathers``
adds ``jnp.take`` as the step calls it against sorted rows with
``mode="fill"``, on the first and the third (a dead lead: PERF.md section
7 (b)). Every form's result is checked on the chip against the deltas row
by row, with the count of non-zero rows and the absolute sum. One JSON
line a case, also appended to chiprun_out/probe_push_scatter.jsonl.

What PR 35 read (seed 2350000001, 40,058 real slots; ms a scatter, hinted /
unhinted / today): f32[2^30,1] 13.27 / 5.79 / 6.19, as a kv shard 13.26 /
5.55 / 6.40; 2^29 rows 6.91 / 5.93 / 5.97; 2^28 3.68 / 5.91 / 6.16; 2^27
2.08 / 5.72 / 6.12; 2^26 1.28 / 1.31 / 1.37; f32[100000768,1] 1.67 / 1.70
/ 1.76; f32[100000768,16] 6.46 / 6.46 / 6.56; the inert call 12.88 / 0.21 /
0.25. ``--wide``: f32[50122752,64] 23.92 hinted, 23.92 not; f32[6000640,300]
stored 384 wide 33.05 hinted, 12.28 not.

What PR 38 read (``SEED --wide VDIM ROWS SLOTS REAL --no-element``, seeds
2380000001-26 in the order given, 114,689 slots of which 72,100 real unless
said; ms a scatter, hinted / unhinted; every line ``rows_equal_numpy``): the
hinted scatter streams a table of whole 128-lane tiles as it streams a
one-lane one, 12-13.5 ps a table element, and the unhinted one takes the
slots in turn, 70-100 ns each. f32[6000640,300] stored 384 wide 33.04 /
12.26; f32[6000640,384] 33.03 / 11.27; f32[6000640,256] 21.01 / 9.74;
f32[6000640,128] 10.23 / 8.16. 384 lanes by rows: 2097152 12.77 / 11.14,
524288 4.51 / 5.20, 131072 2.47 / 3.16 (the same with 114,688 real).
f32[6000640,384] by slots and real slots: 2048 / 0 (the inert call) 30.28 /
0.25; 65536 / 39300 31.01 / 6.25; 65536 / 65535 31.03 / 6.56; 114689 /
114688 33.03 / 11.74; 131072 / 78600 31.77 / 12.40; 131072 / 131071 31.79
/ 13.00. f32[131072,384] under 2048 pads 0.75 / 0.24. f32[131072,128] 0.98
/ 1.35; f32[18000896,128] 29.24 / 8.23. The control, f32[50122752,64]
under 131072 / 75528: 23.92 / 23.92. Around the crossing: f32[1048576,384]
7.24 / 11.12, f32[1572864,384] 9.98 / 11.23; f32[2097152,256] 8.17 / 9.52,
f32[2621440,256] 9.88 / 9.69; f32[4194304,128] 7.38 / 7.97,
f32[5242880,128] 9.02 / 8.16; f32[1048576,512] 9.60 / 11.99,
f32[2097152,512] 17.15 / 12.03: between 5,266 and 5,851 table elements a
slot at every width, which is ``spmd._STREAM_ELEMENTS_A_SLOT``'s bracket.

``--wide VDIM ROWS [SLOTS REAL]`` (PERF.md section 6, PRs 33 to 35) runs
one wide table alone: the pull of REAL ascending keys in SLOTS key slots
(131,072 and 75,528, ``mfhw.train``'s, unless given; ``sgns3m.train``'s are
114,689 and 72,100) out of f32[ROWS,VDIM], the gather of single elements
the step had in PR 32 (``--no-element`` leaves it out) against
``spmd._take_rows`` on the slot as the store keeps it, and the push's
``spmd._add_rows`` into it as the step calls it and then with the promise
withheld (no hint), every form's rows checked against NumPy's; one JSON
line a form. The seed comes first: ``SEED --wide 300 6000640 114689 72100``.

    chiprun --timeout 1500 -- python3 tools/probe_push_scatter.py [SEED] [--rest | --gathers | --wide VDIM ROWS]
"""
import contextlib, json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
from benchmark.harness import criteo
from parameter_server_tpu.parallel import spmd

SEED = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 2270000001
U = 1 << 16  # BatchBuilder's bucket for a batch's about 40,000 keys
print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, flush=True)
assert jax.devices()[0].platform == "tpu"
spec = json.load(open("benchmark/configs/ctr_ftrl_1chip.json"))["data"]


def bucket_keys(num_keys):
    """unique_keys of one 8192-example batch as BatchBuilder writes them."""
    _, ints, cats = criteo.make_examples(SEED, 8192, spec)
    rows, _ = criteo.features(ints, cats, num_keys)
    uniq = np.unique(rows.ravel())
    assert U // 2 < 1 + len(uniq) <= U, (len(uniq), U)  # the builder's bucket
    keys = np.zeros(U, np.int32)
    keys[1 : 1 + len(uniq)] = uniq
    return keys, 1 + len(uniq)


def today(table, idx, d, begin, shard):  # the push's scatter up to PR 26
    local = idx - begin
    in_range = (local >= 0) & (local < shard)
    safe = jnp.where(in_range, local, 0)
    return table.at[safe].add(in_range[:, None].astype(d.dtype) * d)


@contextlib.contextmanager
def rule(says):
    """``spmd.scatter_rows_sorted`` answering ``says`` while a form is traced."""
    kept, spmd.scatter_rows_sorted = spmd.scatter_rows_sorted, lambda *shape: says
    try:
        yield
    finally:
        spmd.scatter_rows_sorted = kept


def ascending(table, idx, d, begin, shard):  # the step's from PR 27 to PR 34: the hint whatever the shapes
    local = idx - begin
    with rule(True):
        return spmd._add_rows(table, spmd._ascending_rows(idx, local), d, True)


def unhinted(table, idx, d, begin, shard):  # the same rows, pads and foreign keys dropped, XLA told nothing
    local = idx - begin
    return spmd._add_rows(table, spmd._ascending_rows(idx, local), d, False)


def take_today(table, idx, begin, shard):
    local = idx - begin
    in_range = (local >= 0) & (local < shard)
    return jnp.take(table, jnp.where(in_range, local, 0), axis=0)


def take_sorted(table, idx, begin, shard):
    local = idx - begin
    rows = spmd._ascending_rows(idx, local)
    return jnp.take(table, rows, axis=0, indices_are_sorted=True, mode="fill", fill_value=0)


def timed(fn, first, rest, n=10, reps=3, chain=True):
    """ms a call: n calls back to back, the best-of-reps mean (steady)."""
    out = []
    x = first
    x = fn(x, *rest)  # compile + warm
    jax.block_until_ready(x)
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(n):
            y = fn(x if chain else first, *rest)
            if chain:
                x = y
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) / n * 1e3)
    return out, (x if chain else first)


def emit(res):
    print(json.dumps(res), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_push_scatter.jsonl", "a") as fh:
        fh.write(json.dumps(res) + "\n")


def case(rows, vdim, num_keys, begin, label, gathers, inert_slots=0):
    """``inert_slots``: the key vector of the call that ends an epoch
    (``data.batch.inert_like``: that many slots, every one ``PAD_KEY``)."""
    keys, n_uniq = (np.zeros(inert_slots, np.int32), 1) if inert_slots else bucket_keys(num_keys)
    rng = np.random.default_rng(SEED)
    d = rng.standard_normal((len(keys), vdim)).astype(np.float32)
    d[n_uniq:] = 0.0  # a pad's gradient is 0 and so is its delta
    d[0] = 0.0
    local = keys.astype(np.int64) - begin
    mine = (local >= 0) & (local < rows)
    mine[n_uniq:] = False
    mine[0] = begin == 0
    idx, dd = jnp.asarray(keys), jnp.asarray(d)
    res = {"case": label, "rows": rows, "vdim": vdim, "begin": begin, "slots": len(keys), "real_slots": int(n_uniq),
           "slots_in_range": int(mine.sum()), "seed": SEED, "rule_sorted": spmd.scatter_rows_sorted(rows, vdim, len(keys))}
    for name, form in (("today", today), ("ascending", ascending), ("unhinted", unhinted)):
        f = jax.jit(lambda t, i, x, form=form: form(t, i, x, begin, rows), donate_argnums=0)
        table = jnp.zeros((rows, vdim), jnp.float32)
        # correctness on the chip: from zeros one add; every in-range real row == its delta, the rest untouched
        table = f(table, idx, dd)
        got = np.asarray(jnp.take(table, jnp.asarray(np.where(mine, local, 0).astype(np.int32)), axis=0))
        ok_rows = bool(np.array_equal(got[mine], d[mine]))
        total = float(jax.jit(lambda t: jnp.sum(jnp.abs(t), dtype=jnp.float32))(table))
        want_total = float(np.abs(d[mine]).astype(np.float64).sum())
        nz = int(jax.jit(lambda t: jnp.sum((t != 0).astype(jnp.int32)))(table))
        want_nz = int(np.count_nonzero(d[mine]))
        res[f"{name}_rows_equal"] = ok_rows
        res[f"{name}_nonzero"] = [nz, want_nz]
        res[f"{name}_abs_sum"] = [total, want_total]
        ms, table = timed(f, table, (idx, dd))
        res[f"scatter_{name}_ms"] = ms
        if gathers:
            for gname, gform in (("today", take_today), ("sorted", take_sorted)):
                if name != "today":
                    break
                g = jax.jit(lambda t, i, gform=gform: gform(t, i, begin, rows))
                gms, _ = timed(g, table, (idx,), chain=False)
                res[f"gather_{gname}_ms"] = gms
                a = np.asarray(jax.jit(lambda t, i: take_today(t, i, begin, rows))(table, idx))
                b = np.asarray(g(table, idx))
                res[f"gather_{gname}_equal_on_mine"] = bool(np.array_equal(a[mine], b[mine]))
        del table
    emit(res)


def element_rows(v, rows):  # the pull of rows wider than 32 lanes in PR 32
    lanes = jnp.arange(v.shape[1], dtype=rows.dtype)
    at = jnp.stack(jnp.broadcast_arrays(rows[:, None], lanes[None, :]), axis=-1)
    dnums = lax.GatherDimensionNumbers(offset_dims=(), collapsed_slice_dims=(0, 1), start_index_map=(0, 1))
    return lax.gather(v, at, dnums, slice_sizes=(1, 1), mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def wide_case(vdim, rows, slots, real):
    """Pull and push of ``real`` ascending keys in ``slots`` key slots at a
    (rows, vdim) table filled from the seed's hash: the gather of single
    elements from the table as XLA lays out (rows, vdim) against
    ``spmd._take_rows`` on the slot as the store keeps it (``row_stride``
    lanes wide), rows checked against NumPy's copy of the hash to the bit;
    then ``spmd._add_rows`` into the stored slot, checked row by row."""
    from benchmark.harness import ref_mf
    from parameter_server_tpu.kv import store

    rng = np.random.default_rng(SEED)
    keys = np.zeros(slots, np.int32)  # slot 0 and the tail: PAD_KEY
    keys[1 : 1 + real] = np.sort(rng.choice(np.arange(1, rows, dtype=np.int64), real, replace=False))
    with np.errstate(over="ignore"):
        r = keys.astype(np.uint32)[:, None]
        lane = np.arange(vdim, dtype=np.uint32)[None, :]
        x = ref_mf._fmix32(r * np.uint32(0x9E3779B1) + np.uint32(SEED & 0xFFFFFFFF))
        x = ref_mf._fmix32(x ^ (lane * np.uint32(0x85EBCA77) + np.uint32(0xC2B2AE3D)))
    want = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0**-23) - np.float32(1.0)
    idx = jnp.asarray(keys)
    stride = spmd.row_stride(vdim)
    fill = lambda lanes: jnp.where(  # noqa: E731 - the lanes past the row's width zero, as the store keeps them
        jnp.arange(lanes)[None, :] < vdim, store.hashed_unit(SEED, jnp.arange(rows, dtype=jnp.int32), lanes), 0.0
    )
    head = {"case": f"f32[{rows},{vdim}] stored {stride} wide", "slots": slots, "real_slots": real, "seed": SEED}
    forms = [("take_rows", stride, lambda v, i: spmd._take_rows(v, i, vdim))]
    if "--no-element" not in sys.argv:
        forms.insert(0, ("element", vdim, element_rows))
    for name, lanes, form in forms:
        res = {**head, "form": name, "op": "pull"}
        table = jax.jit(lambda lanes=lanes: fill(lanes))()
        t0 = time.perf_counter()
        g = jax.jit(form).lower(table, idx).compile()
        res["compile_s"] = time.perf_counter() - t0
        got = np.asarray(g(table, idx))
        res["rows_equal_numpy"] = bool(np.array_equal(got, want)) and bool(want[1 : 1 + real].any())
        res["gather_ms"] = timed(g, table, (idx,), chain=False)[0]  # no second name for the table
        emit(res)
        if name != "take_rows":
            del table, got, g
    # the push into the stored slot: every real row moves by its delta, no other
    d = rng.standard_normal((slots, vdim)).astype(np.float32)
    d[0], d[1 + real :] = 0.0, 0.0
    dd = jnp.asarray(d)
    # ``_add_rows`` as the step calls it (the hint by ``scatter_rows_sorted``), then with the promise withheld
    for name, promise in (("add_rows", True), ("add_rows_unhinted", False)):
        push = jax.jit(lambda t, i, x, p=promise: spmd._add_rows(t, spmd._ascending_rows(i, i), x, p), donate_argnums=0)
        res = {**head, "form": name, "op": "push", "sorted_hint": promise and spmd.scatter_rows_sorted(rows, stride, slots)}
        table = push(table, idx, dd)
        got = np.asarray(jax.jit(lambda v, i: spmd._take_rows(v, i, vdim))(table, idx))
        want = want + d  # every real row has moved by its delta once more, no other
        res["rows_equal_numpy"] = bool(np.array_equal(got[1 : 1 + real], want[1 : 1 + real]))
        res["pad_lanes_zero"] = bool(float(jnp.abs(table[:, vdim:]).sum()) == 0.0) if stride > vdim else None
        res["scatter_ms"], table = timed(push, table, (idx, dd))
        want = np.asarray(jax.jit(lambda v, i: spmd._take_rows(v, i, vdim))(table, idx))  # after the timed adds
        emit(res)


if "--wide" in sys.argv:
    nums = [int(a) for a in sys.argv[sys.argv.index("--wide") + 1 :] if a.isdigit()]
    wide_case(*nums[:2], *(nums[2:4] or (131_072, 75_528)))
    sys.exit(0)
gathers = "--gathers" in sys.argv  # PERF.md section 7 (b): dead, so only on request
if "--rest" not in sys.argv:
    case(1 << 30, 1, 1 << 30, 0, "f32[2^30,1]", gathers)
    case(100_000_768, 1, 100_000_000, 0, "f32[100000768,1]", False)
case(100_000_768, 16, 100_000_000, 0, "f32[100000768,16]", gathers)
# what a kv shard of ctr2x2 sees: 2^31 keys, this shard's rows [2^30, 2^31) and then [0, 2^30)
case(1 << 30, 1, 1 << 31, 1 << 30, "f32[2^30,1] as kv shard 1 of 2", False)
case(1 << 30, 1, 1 << 31, 0, "f32[2^30,1] as kv shard 0 of 2", False)
# one lane between 10^8 and 2^30 rows: where streaming the table passes taking the slots in turn
for log2 in (26, 27, 28, 29):
    case(1 << log2, 1, 1 << log2, 0, f"f32[2^{log2},1]", False)
# the 2048 pad slots of the call that ends an epoch
case(1 << 30, 1, 1 << 30, 0, "f32[2^30,1] inert call", False, inert_slots=2048)
