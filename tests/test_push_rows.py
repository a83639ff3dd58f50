"""The promise ``_microstep`` makes to ``_local_push`` (``ascending=True``,
so the table scatter carries ``indices_are_sorted``): whatever the feed
hands the step, the rows the scatter receives are non-decreasing on every
kv shard, a shard's own keys land on their rows, and every pad and every
other shard's key lies outside ``[0, shard_size)`` and is dropped. On the
CPU the hint is ignored, so a false promise would not show in any number
here; on the chip it is undefined behaviour. Hence this test of the batch
contract itself (``data/batch.py``: slot 0 ``PAD_KEY``, then strictly
ascending keys, then ``PAD_KEY`` to the end)."""

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.data.batch import (
    BatchBuilder,
    inert_like,
    pad_batch,
    pad_group,
)
from parameter_server_tpu.parallel import spmd
from parameter_server_tpu.utils.hashing import PAD_KEY

BATCH, F = 16, 6
# (key_mode, num_keys): hashed and identity keys; int32 on the host and, at
# 2^31 rows (the 2x2 cell's table: only key vectors are made here), int64
KEY_SPACES = [("hash", 4096), ("identity", 4096), ("hash", 100_000), ("hash", 1 << 31)]


def build(key_mode, num_keys, bucket_nnz, n_examples=BATCH, seed=0):
    rng = np.random.default_rng(seed)
    top = num_keys - 2 if key_mode == "identity" else 1 << 40
    keys = [rng.integers(0, top, F).astype(np.uint64) for _ in range(n_examples)]
    if key_mode == "identity" and n_examples:
        keys[0][:2] = (0, top - 1)  # the first and the last real row of the table
    vals = [np.ones(F, np.float32)] * n_examples
    builder = BatchBuilder(
        num_keys=num_keys, batch_size=BATCH, max_nnz_per_example=256,
        key_mode=key_mode, bucket_nnz=bucket_nnz,
    )
    return builder.build(rng.integers(0, 2, n_examples).astype(np.float32), keys, vals)


def key_vectors(source, key_mode, num_keys):
    """(U,) ``unique_keys`` vectors as ``source`` hands them to the step."""
    b = build(key_mode, num_keys, bucket_nnz=True)
    big = build(key_mode, num_keys, bucket_nnz=False, seed=1)  # the static worst-case shape
    if source == "builder":
        out = [big.unique_keys]
    elif source == "bucketed":
        out = [b.unique_keys, build(key_mode, num_keys, True, n_examples=0).unique_keys]
    elif source == "inert_like":
        out = [inert_like(b).unique_keys, inert_like(big).unique_keys]
    elif source == "pad_batch":
        out = [pad_batch(b, len(b.values) * 2, len(b.unique_keys) * 2 + 3).unique_keys]
    elif source == "pad_group":
        out = [g.unique_keys for g in pad_group([b, big, inert_like(b)])]
    elif source == "stack_batches":
        stacked = spmd.stack_batches(pad_group([b, big]))["unique_keys"]
        out = list(stacked)
    elif source == "stack_step_groups":
        small = spmd.stack_batches([b, inert_like(b)])
        large = spmd.stack_batches([big, big])
        grown = spmd.stack_step_groups([small, large, small])["unique_keys"]  # (D, K, U)
        out = list(grown.reshape(-1, grown.shape[-1]))
    else:
        raise AssertionError(source)
    want_dtype = np.int64 if num_keys > np.iinfo(np.int32).max else np.int32
    assert all(v.dtype == want_dtype for v in out), [v.dtype for v in out]
    return out


SOURCES = [
    "builder", "bucketed", "inert_like", "pad_batch", "pad_group", "stack_batches",
    "stack_step_groups",
]


# a 2^31-row shard is past what int32 keys address (the step does not trace
# there: ``local < shard_size`` overflows), so that table starts at kv 2
SPACES_AND_SHARDS = [
    (mode, n, kv) for mode, n in KEY_SPACES for kv in (1, 2, 4) if n // kv < 1 << 31
]


@pytest.mark.parametrize("key_mode,num_keys,kv", SPACES_AND_SHARDS)
@pytest.mark.parametrize("source", SOURCES)
def test_scatter_rows_ascend_and_foreign_keys_fall_outside(source, key_mode, num_keys, kv):
    shard = spmd._shard_size(num_keys, kv)
    for keys in key_vectors(source, key_mode, num_keys):
        assert keys[0] == PAD_KEY
        real = keys != PAD_KEY
        n_real = int(real.sum())
        # the contract of data/batch.py, of which the rest follows
        assert real[1 : 1 + n_real].all() and not real[1 + n_real :].any()
        assert (np.diff(keys[1 : 1 + n_real].astype(np.int64)) > 0).all()
        idx = jnp.asarray(keys.astype(np.int32))  # what the device holds of either host dtype
        for shard_index in {0, kv - 1}:
            begin = shard_index * shard
            rows = np.asarray(spmd._ascending_rows(idx, idx - begin)).astype(np.int64)
            assert (np.diff(rows) >= 0).all(), (source, shard_index)
            local = keys.astype(np.int64) - begin
            mine = real & (local >= 0) & (local < shard)
            np.testing.assert_array_equal(rows[mine], local[mine])
            dropped = ~mine
            dropped[0] = False  # slot 0 is row 0 of the first shard, before every other
            assert ((rows[dropped] < 0) | (rows[dropped] >= shard)).all()
            assert rows[0] == -begin


@pytest.mark.parametrize("key_mode,num_keys", KEY_SPACES)
def test_keys_in_order_accepts_the_feed_and_refuses_any_other_order(key_mode, num_keys):
    """``CSRBatch.keys_in_order``: the check for a batch built some other
    way, which ``PodTrainer._prepare`` asserts before it stacks."""
    import dataclasses

    b = build(key_mode, num_keys, bucket_nnz=True)
    grown = pad_batch(b, len(b.values) * 2, len(b.unique_keys) * 2 + 3)
    for ok in (b, grown, inert_like(b), build(key_mode, num_keys, False, n_examples=0)):
        assert ok.keys_in_order()
    keys, n = b.unique_keys, b.num_unique
    assert n > 3

    def with_keys(edit):
        out = keys.copy()
        edit(out)
        return dataclasses.replace(b, unique_keys=out)

    def swap(k):
        k[1], k[2] = k[2], k[1]

    def repeat(k):
        k[2] = k[1]

    def pad_inside(k):
        k[1] = PAD_KEY

    def key_in_tail(k):
        k[-1] = k[n - 1]

    def no_pad_slot(k):
        k[0] = 1

    for edit in (swap, repeat, pad_inside, key_in_tail, no_pad_slot):
        assert not with_keys(edit).keys_in_order(), edit.__name__


def test_trainer_refuses_a_batch_out_of_order():
    from parameter_server_tpu.parallel.trainer import PodTrainer

    b = build("hash", 4096, bucket_nnz=True)
    b.unique_keys[[1, 2]] = b.unique_keys[[2, 1]]
    with pytest.raises(AssertionError, match="out of order"):
        PodTrainer._prepare(None, [b])


# -- whether XLA is told: ``spmd.scatter_rows_sorted`` (PERF.md section 6, PRs 35 and 38) --
# (rows a chip, stored lanes, key slots) of every table scatter the cells'
# steps hold, and what the rule says there
CELL_SCATTERS = {
    "ctr1.train, ctr2x2.train: z, n": ((1 << 30, 1, 1 << 16), False),
    "wd100m.train: wide.z, wide.n": ((100_000_768, 1, 1 << 16), True),
    "wd100m.train: emb.w, emb.n": ((100_000_768, 16, 1 << 16), True),
    "mfhw.train: mf.w": ((50_122_752, 64, 131_072), True),
    "sgns3m.train: sgns.w": ((6_000_640, 384, 114_689), False),
    # the call that ends an epoch (``builder.build`` of no example: 2048 slots, all pads)
    "linear, the inert call": ((1 << 30, 1, 2048), False),
    "wide_deep, the inert call: wide": ((100_000_768, 1, 2048), False),
    "wide_deep, the inert call: emb": ((100_000_768, 16, 2048), True),
    "word2vec, the inert call": ((6_000_640, 384, 2048), False),
    # what the step scattered until PR 30, and PR 27 measured the hint three times faster at
    "2^30 rows under 524,289 slots": ((1 << 30, 1, (1 << 19) + 1), True),
    # PR 38's grid of whole-tile tables under sgns3m.train's slots: the corners, and
    # the two shapes at each width that the crossing lies between
    "6,000,640 x 128": ((6_000_640, 128, 114_689), False),
    "6,000,640 x 256": ((6_000_640, 256, 114_689), False),
    "18,000,896 x 128": ((18_000_896, 128, 114_689), False),
    "a small table of whole tiles: 131,072 x 128": ((131_072, 128, 114_689), True),
    "a small table of whole tiles: 131,072 x 384": ((131_072, 384, 114_689), True),
    "131,072 x 384, the inert call": ((131_072, 384, 2048), False),
    "4,194,304 x 128 (hinted 7.38 ms, unhinted 7.97)": ((4_194_304, 128, 114_689), True),
    "5,242,880 x 128 (9.02, 8.16)": ((5_242_880, 128, 114_689), False),
    "2,097,152 x 256 (8.17, 9.52)": ((2_097_152, 256, 114_689), True),
    "2,621,440 x 256 (9.88, 9.69)": ((2_621_440, 256, 114_689), False),
    "1,572,864 x 384 (9.98, 11.23)": ((1_572_864, 384, 114_689), True),
    "2,097,152 x 384 (12.77, 11.14)": ((2_097_152, 384, 114_689), False),
    "1,048,576 x 512 (9.60, 11.99)": ((1_048_576, 512, 114_689), True),
    "2,097,152 x 512 (17.15, 12.03)": ((2_097_152, 512, 114_689), False),
    # one lane between the two shapes PR 35 measured (3.68 / 5.91 and 6.91 / 5.93)
    "2^28 x 1 under 65,536 slots": ((1 << 28, 1, 1 << 16), True),
    "2^29 x 1 under 65,536 slots": ((1 << 29, 1, 1 << 16), False),
}


@pytest.mark.parametrize("name", list(CELL_SCATTERS))
def test_sorted_hint_on_the_shapes_the_cells_have(name):
    shape, sorted_hint = CELL_SCATTERS[name]
    assert spmd.scatter_rows_sorted(*shape) is sorted_hint


def streamed(lanes):
    """Whether the chip keeps a table of such rows row-major, where the
    hinted scatter streams it: one lane, or whole 128-lane tiles."""
    return lanes == 1 or lanes % 128 == 0


@pytest.mark.parametrize("slots", [2048, 1 << 16, 131_072, (1 << 19) + 1])
@pytest.mark.parametrize("lanes", [1, 16, 64, 128, 256, 384])
def test_sorted_hint_is_monotone_in_rows(lanes, slots):
    """At fixed lanes and slots the hint goes with the smaller tables and
    never comes back as the rows grow; only a table the chip keeps
    row-major ever loses it, and where it does is one count of table
    elements a slot whatever the width."""
    says = [spmd.scatter_rows_sorted(1 << log2, lanes, slots) for log2 in range(10, 32)]
    assert says[0] and says == sorted(says, reverse=True), says
    assert all(says) or streamed(lanes)
    if streamed(lanes):
        last = (spmd._STREAM_ELEMENTS_A_SLOT * slots) // lanes  # the largest table still hinted
        assert spmd.scatter_rows_sorted(last, lanes, slots)
        assert not spmd.scatter_rows_sorted(last + 1, lanes, slots)


@pytest.mark.parametrize("log2_rows", [10, 23])
@pytest.mark.parametrize("vdim", [100, 128, 300, 384, 640])
def test_add_rows_asks_the_rule_at_the_stored_width(vdim, log2_rows):
    """``_add_rows`` asks with the slot's stored width (``row_stride``: 100
    -> 128, 300 -> 384), which is the table XLA lays out, and what it
    lowers carries the answer: the hint at a small table of whole tiles,
    none at a large one."""
    import jax

    stride, rows, slots = spmd.row_stride(vdim), 1 << log2_rows, 1 << 16
    assert streamed(stride)
    text = jax.jit(lambda t, i, d: spmd._add_rows(t, i, d, True)).lower(
        jax.ShapeDtypeStruct((rows, stride), jnp.float32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((slots, vdim), jnp.float32),
    ).as_text()
    want = spmd.scatter_rows_sorted(rows, stride, slots)
    assert want is (log2_rows == 10)
    assert ("indices_are_sorted = true" in text) is want


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
@pytest.mark.parametrize("vdim", [1, 16, 128, 300])
def test_push_is_the_same_to_the_bit_whatever_the_rule_says(vdim, mesh_name, monkeypatch):
    """``_local_push`` with the promise, the rule forced either way: the
    same rows in the same order, other shards' rows and the pads dropped in
    both, so both tables are equal bit for bit (the CPU ignores the hint:
    what is shown here is that nothing but the hint hangs on the rule)."""
    import jax
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from parameter_server_tpu.kv.updaters import Adagrad
    from parameter_server_tpu.parallel import make_mesh

    data, kv = {"1x1": (1, 1), "2x2": (2, 2)}[mesh_name]
    mesh = make_mesh(data, kv)
    rows, slots = 4096, 64
    shard = spmd._shard_size(rows, kv)
    rng = np.random.default_rng(vdim + kv)
    idx = np.zeros((data, slots), np.int32)  # the batch contract: pad, ascending keys, pads
    for w in range(data):
        real = np.sort(rng.choice(np.arange(1, rows), 40, replace=False))
        real[:4] = (1, shard - 1, shard, rows - 1) if kv > 1 else (1, 2, rows - 2, rows - 1)
        idx[w, 1:41] = np.sort(real)
    grad = rng.normal(size=(data, slots, vdim)).astype(np.float32)
    grad[idx == 0] = 0.0
    stride = spmd.row_stride(vdim)  # 300 is stored 384 lanes wide, the pad lanes zero
    start = {
        "w": rng.normal(size=(rows, stride)).astype(np.float32),
        "n": rng.random(size=(rows, stride)).astype(np.float32),
    }
    for v in start.values():
        v[:, vdim:] = 0.0

    def local(state_l, idx_l, grad_l):
        return spmd._local_push(
            Adagrad(eta=0.05), state_l, lax.all_gather(idx_l[0], "data"),
            lax.all_gather(grad_l[0], "data"), shard, "emb", ascending=True, vdim=vdim,
        )

    def pushed(says):
        monkeypatch.setattr(spmd, "scatter_rows_sorted", lambda *shape: says)
        push = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(spmd.state_spec(), P("data"), P("data")),
            out_specs=spmd.state_spec(), check_vma=False,
        ))
        state = {k: jax.device_put(v, NamedSharding(mesh, spmd.state_spec())) for k, v in start.items()}
        hlo = push.lower(state, jnp.asarray(idx), jnp.asarray(grad)).as_text()
        assert hlo.count("indices_are_sorted = true") == (2 if says else 0), hlo
        return {k: np.asarray(v) for k, v in push(state, jnp.asarray(idx), jnp.asarray(grad)).items()}

    hinted = pushed(True)
    unhinted = pushed(False)
    for k in start:
        np.testing.assert_array_equal(hinted[k], unhinted[k])
        assert not np.array_equal(hinted[k], start[k])
    touched = np.zeros(rows, bool)
    touched[idx.ravel()] = True
    touched[0] = False  # the pad's row takes zeros
    np.testing.assert_array_equal(hinted["n"][~touched], start["n"][~touched])
    # every touched row moved (an element whose squared gradient rounds away may not)
    assert (hinted["n"][touched, :vdim] != start["n"][touched, :vdim]).any(axis=1).all()
    assert not hinted["n"][:, vdim:].any() and not hinted["w"][:, vdim:].any()
