"""Multi-device SPMD tests on the 8-device virtual CPU mesh.

Reference test analog: the reference's integration harness is multi-process
on one host (script/local.sh); ours is multi-device on one host. The key
property: the sharded pull/push/updater path must match the single-device
path bit-for-bit (same math, different layout)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.synthetic import make_sparse_logistic
from parameter_server_tpu.kv.updaters import Ftrl, make_updater
from parameter_server_tpu.models.linear import train_step
from parameter_server_tpu.parallel import (
    SSPClock,
    WorkloadPool,
    make_mesh,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_state,
    stack_batches,
)

NUM_KEYS = 512


def make_worker_batches(n_workers, seed=0, n_per=64):
    labels, keys, vals, _ = make_sparse_logistic(
        n_workers * n_per, NUM_KEYS - 2, nnz_per_example=8, seed=seed
    )
    builder = BatchBuilder(
        num_keys=NUM_KEYS, batch_size=n_per, max_nnz_per_example=32,
        key_mode="identity",
    )
    out = []
    for w in range(n_workers):
        s = slice(w * n_per, (w + 1) * n_per)
        out.append(builder.build(labels[s], keys[s], vals[s]))
    return out


@pytest.mark.parametrize("mesh_shape", [(1, 8), (8, 1), (4, 2), (2, 4)])
def test_spmd_matches_single_device(mesh_shape):
    """The sharded step must equal the single-device semantics of one pod
    step: every worker's gradient is computed against step-start weights
    (delay-1 bounded staleness — the documented SSP-over-SPMD design), then
    each worker's push is applied to the servers sequentially."""
    from parameter_server_tpu.kv.store import pull as kv_pull, push as kv_push
    from parameter_server_tpu.models.linear import batch_to_device
    from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

    d, k = mesh_shape
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    mesh = make_mesh(d, k)
    batches = make_worker_batches(d)

    # single-device reference with the same staleness semantics
    state_ref = up.init(NUM_KEYS, 1)
    pushes = []
    for b in batches:
        dev = batch_to_device(b)
        w_u = kv_pull(up, state_ref, dev["unique_keys"])
        logits = csr_logits(
            w_u, dev["values"], dev["local_ids"], dev["row_ids"],
            dev["row_splits"],
        )
        _, err = logistic_loss(logits, dev["labels"], dev["example_mask"])
        g = csr_grad(
            err, dev["values"], dev["local_ids"], dev["row_ids"],
            dev["row_splits"], num_unique=dev["unique_keys"].shape[0],
        )
        pushes.append((dev["unique_keys"], g))
    for idx, g in pushes:
        state_ref = kv_push(up, state_ref, idx, g)

    step = make_spmd_train_step(up, mesh, NUM_KEYS)
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    state, out = step(state, stack_batches(batches, mesh))

    for key in state_ref:
        np.testing.assert_allclose(
            np.asarray(state[key]), np.asarray(state_ref[key]), atol=1e-5,
            err_msg=f"{mesh_shape} {key}",
        )

    # and one-worker meshes must match the fused single-device train_step too
    if d == 1:
        state2, _ = train_step(up, up.init(NUM_KEYS, 1), batch_to_device(batches[0]))
        for key in state2:
            np.testing.assert_allclose(
                np.asarray(state[key]), np.asarray(state2[key]), atol=1e-5
            )


def test_spmd_multiple_steps_learn():
    mesh = make_mesh(2, 4)
    up = make_updater("ftrl", alpha=0.5, lambda_l1=0.01)
    step = make_spmd_train_step(up, mesh, NUM_KEYS)
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    losses = []
    for epoch in range(6):
        batches = make_worker_batches(2, seed=0)
        stacked = stack_batches(batches, mesh)
        state, out = step(state, stacked)
        losses.append(float(out["loss_sum"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_spmd_predict_matches_train_probs():
    mesh = make_mesh(2, 4)
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    train = make_spmd_train_step(up, mesh, NUM_KEYS)
    predict = make_spmd_predict_step(up, mesh, NUM_KEYS)
    batches = make_worker_batches(2)
    stacked = stack_batches(batches, mesh)
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    p0 = predict(state, stacked)
    assert np.allclose(np.asarray(p0), 0.5)  # all-zero model
    state, _ = train(state, stacked)
    p1 = np.asarray(predict(state, stacked))
    assert p1.shape == (2, 64)
    assert not np.allclose(p1, 0.5)


@pytest.mark.parametrize("mesh_shape", [(4, 2), (8, 1), (2, 4)])
def test_aggregate_push_sgd_exactly_matches_per_worker(mesh_shape):
    """For a linear delta (plain SGD, no L2) aggregate-then-update is
    EXACTLY the sum of per-worker updates — the documented equivalence
    that makes the reduce-scatter fast path safe to opt into."""
    d, k = mesh_shape
    up = make_updater("sgd", eta=0.2)
    mesh = make_mesh(d, k)
    batches = make_worker_batches(d, seed=5)
    stacked = stack_batches(batches, mesh)

    states = {}
    for mode in ("per_worker", "aggregate"):
        step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode=mode)
        state = shard_state(up.init(NUM_KEYS, 1), mesh)
        state, out = step(state, stacked)
        states[mode] = {kk: np.asarray(v) for kk, v in state.items()}
        assert np.isfinite(float(out["loss_sum"]))
    np.testing.assert_allclose(
        states["aggregate"]["w"], states["per_worker"]["w"], atol=1e-6
    )


def test_aggregate_push_ftrl_learns():
    """FTRL under aggregate mode is standard synchronous aggregation —
    different trajectory than per-worker pushes, same ability to learn."""
    mesh = make_mesh(4, 2)
    up = make_updater("ftrl", alpha=0.5, lambda_l1=0.01)
    step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode="aggregate")
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    losses = []
    for _ in range(6):
        batches = make_worker_batches(4, seed=0)
        state, out = step(state, stack_batches(batches, mesh))
        losses.append(float(out["loss_sum"]))
    assert losses[-1] < losses[0] * 0.8, losses


def test_aggregate_push_untouched_rows_unchanged():
    """Only pushed keys may change (the touched mask): rows outside every
    batch's key set must stay exactly zero under aggregate mode."""
    mesh = make_mesh(2, 4)
    up = make_updater("adagrad", eta=0.2, lambda_l2=0.5)
    step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode="aggregate")
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    batches = make_worker_batches(2, seed=1)
    touched = np.zeros(NUM_KEYS, dtype=bool)
    for b in batches:
        touched[b.unique_keys[: b.num_unique]] = True
    state, _ = step(state, stack_batches(batches, mesh))
    w = np.asarray(state["w"]).ravel()
    assert np.all(w[~touched] == 0.0)


def test_push_mode_validated():
    with pytest.raises(ValueError, match="push_mode"):
        make_spmd_train_step(Ftrl(), make_mesh(2, 4), NUM_KEYS, push_mode="bsp")


def test_aggregate_traffic_estimate():
    from parameter_server_tpu.parallel.traffic import linear_step_traffic

    per = linear_step_traffic(
        unique_capacity=4096, vdim=1, data_shards=8, kv_shards=4
    )
    agg = linear_step_traffic(
        unique_capacity=4096, vdim=1, data_shards=8, kv_shards=4,
        push_mode="aggregate", num_keys=1 << 14,
    )
    # per_worker push grows with D*U; aggregate is bound by the range slice
    assert per.push_bytes == int(7 / 8 * 8 * 4096 * (4 + 4))
    assert agg.push_bytes == int(2 * 7 / 8 * (1 << 12) * 2 * 4)
    assert agg.push_bytes < per.push_bytes
    with pytest.raises(ValueError, match="num_keys"):
        linear_step_traffic(4096, 1, 8, 4, push_mode="aggregate")


def test_num_keys_padded_to_kv_axis():
    """Arbitrary table sizes on any mesh shape: a num_keys that does not
    divide the kv axis is padded up to the next multiple (pad rows stay
    exactly zero — the store's pad-row invariant) and the trained state
    matches the single-device trajectory on the real rows."""
    from parameter_server_tpu.kv.store import pull as kv_pull, push as kv_push
    from parameter_server_tpu.models.linear import batch_to_device
    from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss
    from parameter_server_tpu.parallel.spmd import padded_num_keys

    assert padded_num_keys(510, 8) == 512
    assert padded_num_keys(512, 8) == 512
    assert padded_num_keys(1, 8) == 8
    with pytest.raises(ValueError, match="num_keys"):
        padded_num_keys(0, 8)

    num_keys = 510  # not a multiple of the 8-wide kv axis
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    mesh = make_mesh(1, 8)
    labels, keys, vals, _ = make_sparse_logistic(
        64, num_keys - 2, nnz_per_example=8, seed=3
    )
    builder = BatchBuilder(
        num_keys=num_keys, batch_size=64, max_nnz_per_example=32,
        key_mode="identity",
    )
    b = builder.build(labels, keys, vals)

    state_ref = up.init(num_keys, 1)
    dev = batch_to_device(b)
    w_u = kv_pull(up, state_ref, dev["unique_keys"])
    logits = csr_logits(
        w_u, dev["values"], dev["local_ids"], dev["row_ids"],
        dev["row_splits"],
    )
    _, err = logistic_loss(logits, dev["labels"], dev["example_mask"])
    g = csr_grad(
        err, dev["values"], dev["local_ids"], dev["row_ids"],
        dev["row_splits"], num_unique=dev["unique_keys"].shape[0],
    )
    state_ref = kv_push(up, state_ref, dev["unique_keys"], g)

    step = make_spmd_train_step(up, mesh, num_keys)
    state = shard_state(up.init(num_keys, 1), mesh)
    state, out = step(state, stack_batches([b], mesh))
    assert np.isfinite(float(out["loss_sum"]))
    for key in state_ref:
        got = np.asarray(state[key])
        assert got.shape[0] == 512  # padded to the kv multiple
        np.testing.assert_allclose(
            got[:num_keys], np.asarray(state_ref[key]), atol=1e-5,
            err_msg=key,
        )
        assert np.all(got[num_keys:] == 0.0)  # pad rows exactly zero

    # predict over the padded table works and matches shapes
    predict = make_spmd_predict_step(up, mesh, num_keys)
    p = np.asarray(predict(state, stack_batches([b], mesh)))
    assert p.shape == (1, 64)


def test_make_mesh_too_small():
    with pytest.raises(ValueError, match="needs"):
        make_mesh(4, 4)


class TestSSPClock:
    def test_bsp_blocks_until_all_finish(self):
        c = SSPClock(num_workers=2, max_delay=0)
        assert c.ready(0, 0)  # step 0 always allowed
        c.finish(0, 0)
        assert not c.ready(0, 1)  # worker 1 hasn't finished step 0
        c.finish(1, 0)
        assert c.ready(0, 1)

    def test_bounded_delay(self):
        c = SSPClock(num_workers=2, max_delay=2)
        c.finish(0, 0)
        c.finish(0, 1)
        c.finish(0, 2)
        # worker 0 wants step 3: needs min_finished >= 0; worker 1 at -1
        assert not c.ready(0, 3)
        c.finish(1, 0)
        assert c.ready(0, 3)
        assert not c.ready(0, 4)

    def test_async_never_blocks(self):
        c = SSPClock(num_workers=4, max_delay=-1)
        assert c.wait(0, 10**9)

    def test_wait_unblocks_from_other_thread(self):
        import threading

        c = SSPClock(num_workers=2, max_delay=0)
        c.finish(0, 0)
        done = []

        def slow_worker():
            c.finish(1, 0)

        t = threading.Timer(0.05, slow_worker)
        t.start()
        assert c.wait(0, 1, timeout=5.0)
        t.join()

    def test_wait_timeout(self):
        c = SSPClock(num_workers=2, max_delay=0)
        assert not c.wait(0, 5, timeout=0.01)

    def test_state_roundtrip(self):
        c = SSPClock(3, 1)
        c.finish(0, 4)
        c2 = SSPClock(3, 1)
        c2.load_state_dict(c.state_dict())
        assert c2.progress() == c.progress()


class TestWorkloadPool:
    def test_fetch_finish_cycle(self):
        p = WorkloadPool(["a", "b", "c"])
        w1 = p.fetch(worker=0)
        w2 = p.fetch(worker=1)
        assert {w1, w2} == {"a", "b"}
        p.finish(w1)
        p.finish(w2)
        p.finish(p.fetch(0))
        assert p.fetch(0) is None
        assert p.all_done

    def test_unknown_finish_raises(self):
        p = WorkloadPool(["a"])
        with pytest.raises(KeyError):
            p.finish("zzz")

    def test_straggler_reassignment(self):
        p = WorkloadPool(["a"])
        p.fetch(worker=0)
        assert p.reassign_stragglers(older_than_s=0.0) == ["a"]
        assert p.fetch(worker=1) == "a"

    def test_slow_worker_finish_after_reassign_counts(self):
        p = WorkloadPool(["a"])
        p.fetch(worker=0)
        p.reassign_stragglers(older_than_s=0.0)
        p.finish("a")  # the slow worker did complete: don't redo the shard
        assert p.all_done
        p.finish("a")  # idempotent

    def test_dead_worker_reassignment(self):
        p = WorkloadPool(["a", "b"])
        p.fetch(worker=0)
        p.fetch(worker=1)
        assert p.reassign_worker(0) == ["a"]
        stats = p.stats()
        assert stats["pending"] == 1 and stats["active"] == 1


def test_quantized_push_tracks_per_worker():
    """int8-on-the-wire push (fixing_float as a quantized collective):
    same per-worker server semantics, bounded rounding noise — the
    trajectory must track the full-precision per_worker run closely and
    learn equally well."""
    mesh = make_mesh(4, 2)
    up = make_updater("ftrl", alpha=0.5, lambda_l1=0.01)
    finals = {}
    losses = {}
    for mode in ("per_worker", "quantized"):
        step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode=mode)
        state = shard_state(up.init(NUM_KEYS, 1), mesh)
        ls = []
        batches = make_worker_batches(4, seed=0)
        stacked = stack_batches(batches, mesh)
        for i in range(6):
            state, out = step(state, stacked, i)
            ls.append(float(out["loss_sum"]))
        finals[mode] = np.asarray(up.weights(state)).ravel()
        losses[mode] = ls
    assert losses["quantized"][-1] < losses["quantized"][0] * 0.8
    # weights close to the exact run (int8 rounding is the only delta)
    ref = finals["per_worker"]
    err = np.abs(finals["quantized"] - ref).max()
    scale = np.abs(ref).max()
    assert err < 0.05 * scale + 1e-3, (err, scale)


def test_quantized_push_seed_varies_rounding():
    """Different push seeds must produce different stochastic rounding
    (a reused key would correlate the rounding noise across steps)."""
    mesh = make_mesh(2, 2)
    up = make_updater("sgd", eta=0.5)
    step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode="quantized")
    batches = make_worker_batches(2, seed=3)
    stacked = stack_batches(batches, mesh)
    outs = []
    for seed in (0, 1):
        state = shard_state(up.init(NUM_KEYS, 1), mesh)
        state, _ = step(state, stacked, seed)
        outs.append(np.asarray(state["w"]).ravel())
    assert not np.array_equal(outs[0], outs[1])
    # ...but only by rounding noise
    assert np.abs(outs[0] - outs[1]).max() < 0.05 * np.abs(outs[0]).max() + 1e-3


def test_quantized_traffic_estimate():
    from parameter_server_tpu.parallel.traffic import linear_step_traffic

    per = linear_step_traffic(
        unique_capacity=1000, vdim=1, data_shards=8, kv_shards=1
    )
    qt = linear_step_traffic(
        unique_capacity=1000, vdim=1, data_shards=8, kv_shards=1,
        push_mode="quantized",
    )
    assert qt.push_bytes < per.push_bytes  # int8 payload beats f32
    # indices dominate what's left: payload share shrank ~4x
    assert qt.push_bytes == pytest.approx(per.push_bytes * 5 / 8, rel=0.01)
