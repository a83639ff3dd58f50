"""The unique-key axis of a bucketed batch is sized by the keys the batch
holds (power-of-two buckets, ``BUCKET_FLOOR``), not by its entries: the
shape the builder gives, what the grow paths make of a group of such
batches, that the extra pads a larger shape carries are inert to the bit,
and what the trainer counts as a new shape."""

import jax
import numpy as np
import pytest

from parameter_server_tpu.data.batch import (
    BUCKET_FLOOR,
    BatchBuilder,
    pad_batch,
    pad_group,
)
from parameter_server_tpu.kv.updaters import Ftrl
from parameter_server_tpu.parallel import (
    make_mesh,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_state,
    stack_batches,
    stack_step_groups,
)
from parameter_server_tpu.parallel.trainer import PodTrainer
from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter, timers

NUM_KEYS = 1 << 16
B, MAX_NNZ = 1024, 16


def _builder(num_keys=NUM_KEYS, **kw) -> BatchBuilder:
    return BatchBuilder(
        num_keys=num_keys, batch_size=B, max_nnz_per_example=MAX_NNZ,
        key_mode="identity", **kw,
    )


def _batch(builder: BatchBuilder, distinct: int, per_example: int = 8, seed: int = 0):
    """B examples of ``per_example`` entries over exactly ``distinct`` raw
    keys (every one of them used: the first ``distinct`` entries walk them,
    the rest draw among them)."""
    nnz = B * per_example
    assert distinct <= nnz
    rng = np.random.default_rng(seed)
    flat = np.concatenate(
        [np.arange(distinct), rng.integers(0, distinct, nnz - distinct)]
    ).astype(np.uint64)
    return builder.build_flat(
        (rng.random(B) < 0.5).astype(np.float32),
        np.arange(B + 1, dtype=np.int64) * per_example,
        flat,
        rng.normal(size=nnz).astype(np.float32),
    )


def _old_cap(builder: BatchBuilder, b) -> int:
    """The key axis before it had a bucket of its own."""
    return min(len(b.values) + 1, builder.unique_capacity, builder.num_keys)


# (distinct raw keys, builder settings, expected slots); n_uniq is one more
# than the distinct keys, for the PAD_KEY row; 8192 entries a batch
CASES = {
    "few_keys_floor": (10, {}, BUCKET_FLOOR),
    "one_under_a_bucket": (4095, {}, 4096),
    "one_over_a_bucket": (4096, {}, 8192),
    "all_entries_distinct_old_cap": (8192, {}, 8193),
    "cap_is_unique_capacity": (3000, {"unique_capacity": 3500}, 3500),
    "cap_is_num_keys": (2500, {"num_keys": 3000}, 3000),
}


@pytest.mark.parametrize("case", CASES)
def test_key_axis_is_bucketed_by_the_batchs_keys(case):
    distinct, settings, want = CASES[case]
    builder = _builder(bucket_nnz=True, **settings)
    b = _batch(builder, distinct)
    n_uniq, slots = b.num_unique, len(b.unique_keys)
    assert n_uniq == distinct + 1
    assert slots == want
    assert n_uniq <= slots <= _old_cap(builder, b)
    if slots < _old_cap(builder, b):  # the power of two above n_uniq
        assert slots & (slots - 1) == 0 and slots >= BUCKET_FLOOR
        assert slots // 2 < n_uniq or slots == BUCKET_FLOOR
    assert len(b.values) == 8192  # the entries' bucket is its own
    assert b.keys_in_order()
    assert not b.unique_keys[n_uniq:].any() and b.local_ids.max() == distinct


@pytest.mark.parametrize("distinct", [10, 4096, 8192])
def test_unbucketed_builder_keeps_unique_capacity(distinct):
    builder = _builder(bucket_nnz=False)
    b = _batch(builder, distinct)
    assert len(b.unique_keys) == builder.unique_capacity == B * MAX_NNZ + 1
    assert len(b.values) == builder.nnz_capacity
    assert b.keys_in_order()


def _trainer(kv: int = 1) -> PodTrainer:
    cfg = PSConfig()
    cfg.data.num_keys = NUM_KEYS
    cfg.data.bucket_nnz = True
    cfg.solver.minibatch = B
    cfg.solver.epochs = 1
    cfg.parallel.data_shards, cfg.parallel.kv_shards = 1, kv
    return PodTrainer(cfg, reporter=ProgressReporter(print_fn=lambda *_: None))


class TestGroupOfDifferentKeyBuckets:
    """Every grow path brings the key axis to the group's maximum on its
    own, whatever the entries' axis is."""

    @pytest.fixture(scope="class")
    def batches(self):
        builder = _builder(bucket_nnz=True)
        out = [_batch(builder, d, seed=i) for i, d in enumerate((10, 3000, 5000))]
        assert [len(b.unique_keys) for b in out] == [2048, 4096, 8192]
        assert {len(b.values) for b in out} == {8192}
        return out

    def test_pad_group(self, batches):
        padded = pad_group(batches)
        assert {b.shape for b in padded} == {(B, 8192, 8192)}
        assert all(b.keys_in_order() for b in padded)
        for a, b in zip(batches, padded):
            assert b.num_unique == a.num_unique
            np.testing.assert_array_equal(b.unique_keys[: a.num_unique], a.unique_keys[: a.num_unique])

    def test_stack_step_groups(self, batches):
        items = [stack_batches([b], None) for b in batches]
        group = stack_step_groups(items)
        assert group["unique_keys"].shape == (1, 3, 8192)
        assert group["values"].shape == (1, 3, 8192)
        for k, b in enumerate(batches):
            keys = group["unique_keys"][0, k]
            np.testing.assert_array_equal(keys[: b.num_unique], b.unique_keys[: b.num_unique])
            assert not keys[b.num_unique :].any()

    def test_agree_bucket(self, batches, monkeypatch):
        t = _trainer()
        stacked = stack_batches(batches[:1], None)
        same = t._agree_bucket(stacked, "t/0")  # one process: its own shape
        assert same["unique_keys"].shape == (1, 2048)
        # another host holds a batch of more keys and no more entries
        monkeypatch.setattr(
            type(t.runtime), "cp_allmax", lambda self, tag, local: (local[0], 8192)
        )
        grown = t._agree_bucket(stacked, "t/1")
        assert grown["unique_keys"].shape == (1, 8192)
        assert grown["values"].shape == grown["local_ids"].shape == (1, 8192)
        n = batches[0].num_unique
        np.testing.assert_array_equal(grown["unique_keys"][0, :n], batches[0].unique_keys[:n])
        assert not grown["unique_keys"][0, n:].any()


class TestPadsAreInert:
    """The same batch at its own bucket and padded to the shape it had
    before (entries + 1 slots): same state, losses and probabilities, to
    the bit."""

    @pytest.fixture(scope="class")
    def setup(self):
        builder = _builder(bucket_nnz=True)
        groups = [
            [_batch(builder, 300 + 40 * i, seed=10 * s + i) for i in range(2)]
            for s in range(2)
        ]
        mesh = make_mesh(2, 2)
        updater = Ftrl(alpha=0.1, beta=1.0, lambda_l1=0.01, lambda_l2=0.0)
        return groups, mesh, updater

    @staticmethod
    def _stacks(groups, old_shape: bool):
        out = []
        for group in groups:
            group = pad_group(group)
            if old_shape:
                nnz = len(group[0].values)
                group = [pad_batch(b, nnz, nnz + 1) for b in group]
            out.append(stack_batches(group, None))
        return out

    def test_train_step(self, setup):
        groups, mesh, updater = setup
        step = make_spmd_train_step(updater, mesh, NUM_KEYS)
        results = {}
        for old_shape in (False, True):
            state = shard_state(updater.init(NUM_KEYS, 1), mesh)
            outs = []
            for i, stacked in enumerate(self._stacks(groups, old_shape)):
                assert stacked["unique_keys"].shape[-1] == (8193 if old_shape else 2048)
                state, out = step(state, stacked, i)
                outs.append(jax.tree.map(np.asarray, out))
            results[old_shape] = (jax.tree.map(np.asarray, state), outs)
        (state_a, outs_a), (state_b, outs_b) = results[False], results[True]
        assert any(np.asarray(v).any() for v in state_a.values())  # it trained
        for k in state_a:
            np.testing.assert_array_equal(state_a[k], state_b[k], err_msg=k)
        for a, b in zip(outs_a, outs_b):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)

    def test_predict_step(self, setup):
        groups, mesh, updater = setup
        step = make_spmd_train_step(updater, mesh, NUM_KEYS)
        predict = make_spmd_predict_step(updater, mesh, NUM_KEYS)
        state = shard_state(updater.init(NUM_KEYS, 1), mesh)
        state, _ = step(state, self._stacks(groups[:1], False)[0], 0)
        (own,), (old,) = (self._stacks(groups[1:], f) for f in (False, True))
        p_own, p_old = np.asarray(predict(state, own)), np.asarray(predict(state, old))
        assert np.unique(p_own).size > 100  # a trained state, not 0.5 everywhere
        np.testing.assert_array_equal(p_own, p_old)


def _new_shapes() -> int:
    return timers.snapshot().get("trainer.new_shapes", {"count": 0})["count"]


@pytest.mark.parametrize(
    "distinct, shapes",
    [((500, 600, 700, 800), 1), ((500, 3000, 600, 3500), 2)],
    ids=["one_shape_stream", "two_shape_stream"],
)
def test_new_shapes_counts_key_buckets(distinct, shapes):
    """Batches of one entry bucket whose key counts lie in two key buckets
    are two device shapes, and are counted so."""
    builder = _builder(bucket_nnz=True)
    batches = [_batch(builder, d, seed=i) for i, d in enumerate(distinct)]
    assert len({len(b.values) for b in batches}) == 1
    assert len({len(b.unique_keys) for b in batches}) == shapes
    t = _trainer()
    n0 = _new_shapes()
    t.train_batches(batches, report_every=100)
    n1 = _new_shapes()
    t.train_batches(batches, report_every=100)  # the same shapes again
    assert n1 - n0 == shapes and _new_shapes() == n1
    assert {(nnz, u) for _, nnz, u in t._dispatched_shapes} == {
        (8192, len(b.unique_keys)) for b in batches
    }


def test_unique_fill_counter(tmp_path):
    """``feed.unique_fill``: real keys over the slots of the group's shape,
    one sample a batch; nothing with the tracer off."""
    builder = _builder(bucket_nnz=True)
    batches = [_batch(builder, 1023, seed=1), _batch(builder, 3071, seed=2)]
    assert not trace.enabled()
    pad_group(batches)  # tracer off: a no-op
    tracer = trace.configure(str(tmp_path), process_name="fill")
    try:
        pad_group(batches)
        fills = [
            e["args"]["value"] for e in tracer.events()
            if e["ph"] == "C" and e["name"] == "feed.unique_fill"
        ]
    finally:
        trace.configure(None)
    assert fills == [1024 / 4096, 3072 / 4096]


@pytest.mark.parametrize("kv", [1, 2])
def test_walk_share_counter(tmp_path, monkeypatch, kv):
    """``push.walk_share``: the slots the push's scatters visit over the
    slots they are handed, a sample a batch where the trainer stacks a call:
    the whole pieces over the live run of the key axis, on a mesh the busier
    kv shard's; nothing with the tracer off."""
    from parameter_server_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "_WALK_SLOTS", 256)
    monkeypatch.setattr(spmd, "_STREAM_ELEMENTS_A_SLOT", 1)  # a 2^16-row table is not streamed
    builder = _builder(bucket_nnz=True)
    # identity keys: raw key r is row r + 1, so the batches hold rows 1..1023 and 1..3071
    batches = [_batch(builder, 1023, seed=1), _batch(builder, 3071, seed=2)]
    assert [b.num_unique for b in batches] == [1024, 3072]
    assert [len(b.unique_keys) for b in batches] == [2048, 4096]
    t = _trainer(kv)
    assert not trace.enabled()
    t._prepare(batches[:1])  # tracer off: a no-op
    tracer = trace.configure(str(tmp_path), process_name="walk")
    try:
        t._prepare(batches[:1])
        t._prepare(batches[1:])
        shares = [
            e["args"]["value"] for e in tracer.events()
            if e["ph"] == "C" and e["name"] == "push.walk_share"
        ]
    finally:
        trace.configure(None)
    # every key lies on the first kv shard (2^15 rows of it): the busier one. 1,024 live
    # slots are 4 pieces of 256, 3,072 are 12; the slot behind them starts no piece
    assert shares == [1024 / 2048, 3072 / 4096]
    # a run that ends inside a piece pays for the whole piece
    assert spmd.push_walk_share(t.app.tables, batches[0].unique_keys[:1000], 1 << 15, kv, 2048) == 1024 / 2048
    # keys of both shards: the busier shard's pieces (rows 1..599 are 3 pieces, the 400 behind them 2)
    keys = np.concatenate([np.arange(600), (1 << 15) + np.arange(400)])
    assert spmd.push_walk_share(t.app.tables, keys, 1 << 15, 2, 2048) == 3 * 256 / 2048
    # a table whose scatter stays whole visits every slot it is handed
    monkeypatch.setattr(spmd, "_STREAM_ELEMENTS_A_SLOT", 1 << 20)
    assert spmd.push_walk_share(t.app.tables, keys, 1 << 15, 2, 2048) == 1.0


def test_grad_walk_share_counter(tmp_path, monkeypatch):
    """``grad.walk_share``: the entry slots a sweep by key slot of
    ``ps.grad`` visits over those it is handed, a sample a batch beside
    ``push.walk_share``: the whole pieces up to the batch's last real entry
    of the group's entry axis; 1.0 where that axis is one piece; nothing
    with the tracer off."""
    from parameter_server_tpu.ops import sparse

    monkeypatch.setattr(sparse, "_WALK_ENTRIES", 1024)
    builder = _builder(bucket_nnz=True)
    # 1024 examples of 3 and of 8 entries: 3,072 real entries in a 4,096 bucket, 8,192 in 8,192
    batches = [_batch(builder, 1023, per_example=3, seed=1), _batch(builder, 3071, seed=2)]
    assert [(b.num_entries, len(b.values)) for b in batches] == [(3072, 4096), (8192, 8192)]
    t = _trainer()
    assert not trace.enabled()
    t._prepare(batches[:1])  # tracer off: a no-op
    tracer = trace.configure(str(tmp_path), process_name="walk")
    try:
        t._prepare(batches[:1])
        t._prepare(batches)  # one group: the first batch is padded to the second's 8,192
        monkeypatch.setattr(sparse, "_WALK_ENTRIES", 8192)
        t._prepare(batches[:1])  # 4,096 entry slots are a single piece: one whole sweep
        shares = [
            e["args"]["value"] for e in tracer.events()
            if e["ph"] == "C" and e["name"] == "grad.walk_share"
        ]
    finally:
        trace.configure(None)
    assert shares == [3072 / 4096, 3072 / 8192, 1.0, 1.0]
    # a run that ends inside a piece pays for the whole piece
    monkeypatch.setattr(sparse, "_WALK_ENTRIES", 1024)
    assert sparse.walked_entries(3073, 8192) == 4096 and sparse.walked_entries(0, 8192) == 0


@pytest.mark.parametrize("kv", [1, 2])
def test_training_with_the_scatter_walked_is_training_to_the_bit(monkeypatch, kv):
    """The trainer's own step over batches of two key buckets, its push's
    scatters whole (a 2^16-row table is streamed) and walked in pieces of
    256 slots: the same state bit for bit on one chip and over kv 2, where
    the two shards take different numbers of turns."""
    from parameter_server_tpu.parallel import spmd

    builder = _builder(bucket_nnz=True)
    batches = [_batch(builder, d, seed=i) for i, d in enumerate((700, 3000, 1500, 2500))]

    def trained() -> dict:
        t = _trainer(kv)
        t.train_batches(batches, report_every=100)
        return {k: np.asarray(v) for k, v in t.state.items()}

    assert not spmd.scatter_walks((NUM_KEYS // kv), 1, 4096)
    whole = trained()
    monkeypatch.setattr(spmd, "_WALK_SLOTS", 256)
    monkeypatch.setattr(spmd, "_STREAM_ELEMENTS_A_SLOT", 1)
    assert spmd.scatter_walks((NUM_KEYS // kv), 1, 2048)
    walked = trained()
    assert any(v.any() for v in whole.values())  # it trained
    for k in whole:
        np.testing.assert_array_equal(whole[k], walked[k], err_msg=k)
