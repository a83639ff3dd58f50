"""The batch wire of the pod path: int32 keys + (B+1,) row_splits, from
which the device rebuilds the (NNZ,) row ids. Held to ``CSRBatch.row_ids``,
which stays on the host: the rebuild against it, and the step over the wire
against the host arithmetic on it.

Reference analog: the reference attacks wire bytes with its filter
pipeline (src/filter/ key-caching, compression, fixed-point floats); on a
TPU host feed the same scarce resource is host->device bandwidth and the
transfer LAYOUT itself is the filter."""

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder, pad_group
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.kv.updaters import Ftrl
from parameter_server_tpu.parallel import (
    make_mesh,
    make_spmd_train_multistep,
    make_spmd_train_step,
    shard_state,
    stack_batches,
    stack_step_groups,
)
from parameter_server_tpu.parallel.trainer import PodTrainer
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

NUM_KEYS = 512


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def _batches(d, n_steps, n_per=64, bucket=False, seed=0):
    labels, keys, vals, _ = make_sparse_logistic(
        d * n_steps * n_per, NUM_KEYS - 2, nnz_per_example=8, seed=seed
    )
    builder = BatchBuilder(
        num_keys=NUM_KEYS, batch_size=n_per, max_nnz_per_example=32,
        key_mode="identity", bucket_nnz=bucket,
    )
    out = []
    for s in range(n_steps):
        group = []
        for w in range(d):
            i = (s * d + w) * n_per
            group.append(
                builder.build(
                    labels[i : i + n_per], keys[i : i + n_per],
                    vals[i : i + n_per],
                )
            )
        out.append(pad_group(group))
    return out


def test_row_splits_match_row_ids():
    """The builder's row_splits carry exactly row_ids' information over
    real entries (including empty rows and the padded tail)."""
    (group,) = _batches(1, 1, n_per=16)
    b = group[0]
    # real entries: row_ids non-decreasing; splits bracket each row
    for r in range(b.num_examples):
        lo, hi = b.row_splits[r], b.row_splits[r + 1]
        np.testing.assert_array_equal(b.row_ids[lo:hi], r)
    assert b.row_splits[0] == 0
    assert b.row_splits[b.num_examples] == b.num_entries
    np.testing.assert_array_equal(
        b.row_splits[b.num_examples :], b.num_entries
    )


def _splits(lens, batch_size):
    """(batch_size + 1,) row_splits as data/batch.py writes them: the
    cumulative real entries per row, the total repeated over a padded
    tail."""
    out = np.zeros(batch_size + 1, dtype=np.int32)
    np.cumsum(lens, out=out[1 : len(lens) + 1])
    out[len(lens) + 1 :] = out[len(lens)]
    return out


def _ragged_lens():
    lens = np.random.default_rng(3).integers(1, 9, 64)
    lens[[5, 6, 7, 30]] = 0  # empty rows in the middle, three in a run
    lens[-4:] = 0  # and at the end
    return lens


ROW_ID_CASES = {
    # the benchmark cells' own shape: every row 39 entries, buffer full
    "39_a_row_at_8192_by_2p19": (_splits(np.full(8192, 39), 8192), 1 << 19),
    "empty_rows_in_the_middle_and_at_the_end": (_splits(_ragged_lens(), 64), 512),
    "padded_tail_batch": (_splits(np.full(40, 5), 64), 512),
    "buffer_filled_to_its_last_entry": (_splits(np.full(64, 8), 64), 512),
    "last_row_alone_fills_the_buffer": (_splits([0] * 63 + [512], 64), 512),
    "empty_batch": (_splits([], 64), 512),
    "one_row": (_splits([7], 1), 16),
    "one_row_empty": (_splits([0], 1), 16),
    "nnz_not_a_power_of_two": (_splits(np.full(100, 3), 100), 390),
}


@pytest.mark.parametrize("case", sorted(ROW_ID_CASES))
def test_row_ids_rebuilt_from_row_splits(case):
    """_row_ids_of on a stacked batch against np.repeat, equal at every
    position, the padding included: padded entries sit on the last row."""
    import jax

    from parameter_server_tpu.parallel.spmd import _row_ids_of

    splits, nnz = ROW_ID_CASES[case]
    num_rows = len(splits) - 1
    want = np.full(nnz, num_rows - 1, dtype=np.int32)
    want[: splits[-1]] = np.repeat(np.arange(num_rows, dtype=np.int32), np.diff(splits))
    b = {
        "row_splits": splits,
        "values": np.zeros(nnz, np.float32),
        "labels": np.zeros(num_rows, np.float32),
    }
    got = np.asarray(jax.jit(_row_ids_of)(b))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_unique_keys_dtype_tracks_key_space():
    small = BatchBuilder(num_keys=1 << 20, batch_size=4)
    big = BatchBuilder(num_keys=(1 << 33), batch_size=4, key_mode="identity")
    labels = np.ones(2, dtype=np.float32)
    keys = [np.array([3, 5], dtype=np.uint64), np.array([7], dtype=np.uint64)]
    vals = [np.ones(2, dtype=np.float32), np.ones(1, dtype=np.float32)]
    assert small.build(labels, keys, vals).unique_keys.dtype == np.int32
    assert big.build(labels, keys, vals).unique_keys.dtype == np.int64


def _host_step(up, state, group, push_mode):
    """One pod step in host arithmetic over each batch's own ``row_ids``
    (``ops.sparse`` on one device, ``kv.store`` pull and push): every
    worker's gradient against the step-start weights, then the pushes in
    worker order, or their sum in one push under ``aggregate``. Returns
    (state, loss_sum)."""
    import jax.numpy as jnp

    from parameter_server_tpu.kv.store import pull as kv_pull, push as kv_push
    from parameter_server_tpu.models.linear import batch_to_device
    from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

    pushes, loss_sum = [], 0.0
    for b in group:
        dev = batch_to_device(b)
        w_u = kv_pull(up, state, dev["unique_keys"])
        logits = csr_logits(
            w_u, dev["values"], dev["local_ids"], dev["row_ids"],
            dev["row_splits"],
        )
        loss, err = logistic_loss(logits, dev["labels"], dev["example_mask"])
        g = csr_grad(
            err, dev["values"], dev["local_ids"], dev["row_ids"],
            dev["row_splits"], num_unique=dev["unique_keys"].shape[0],
        )
        pushes.append((dev["unique_keys"], g))
        loss_sum += float(loss)
    if push_mode == "aggregate":
        dense = jnp.zeros((NUM_KEYS, 1), jnp.float32)
        for idx, g in pushes:
            dense = dense.at[idx].add(g)
        touched = np.unique(np.concatenate([np.asarray(idx) for idx, _ in pushes]))
        pushes = [(jnp.asarray(touched), dense[touched])]
    for idx, g in pushes:
        state = kv_push(up, state, idx, g)
    return state, loss_sum


@pytest.mark.parametrize("bucket", [False, True])
@pytest.mark.parametrize("push_mode", ["per_worker", "aggregate"])
def test_step_matches_host_arithmetic_on_row_ids(push_mode, bucket):
    """The step over the wire (row ids rebuilt on the device from
    ``row_splits``) against the host arithmetic on the batches' own
    ``row_ids``: same losses and weights, step after step."""
    d, k = 4, 2
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    mesh = make_mesh(d, k)
    groups = _batches(d, 4, bucket=bucket)
    step = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode=push_mode)

    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    ref = up.init(NUM_KEYS, 1)
    for g in groups:
        stacked = stack_batches(g, None)
        assert "row_ids" not in stacked
        state, out = step(state, stacked)
        ref, ref_loss = _host_step(up, ref, g, push_mode)
        assert float(out["loss_sum"]) == pytest.approx(ref_loss, rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(up.weights(state))[:NUM_KEYS], np.asarray(up.weights(ref)),
        rtol=1e-5, atol=1e-6,
    )


def test_multistep_group_matches_host_arithmetic_on_row_ids():
    """The wire composes with K-microstep scanned dispatch (row_splits is
    fixed-size, so group stacking needs no variable-axis padding): the
    scanned program against K host steps on the batches' own row_ids."""
    d, K = 2, 3
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    mesh = make_mesh(d, 2)
    groups = _batches(d, K, bucket=True)
    stepK = make_spmd_train_multistep(up, mesh, NUM_KEYS)

    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    state, out = stepK(state, stack_step_groups([stack_batches(g, None) for g in groups]))
    ref, ref_losses = up.init(NUM_KEYS, 1), []
    for g in groups:
        ref, loss = _host_step(up, ref, g, "per_worker")
        ref_losses.append(loss)
    np.testing.assert_allclose(np.asarray(out["loss_sum"]), ref_losses, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(up.weights(state))[:NUM_KEYS], np.asarray(up.weights(ref)),
        rtol=1e-5, atol=1e-6,
    )


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("compact")
    labels, keys, vals, _ = make_sparse_logistic(
        3600, 800, nnz_per_example=10, noise=0.3, seed=13
    )
    paths = []
    for i in range(4):
        p = d / f"part-{i}.svm"
        s = slice(i * 900, (i + 1) * 900)
        write_libsvm(p, labels[s], keys[s], vals[s])
        paths.append(str(p))
    return paths


def test_wire_values_f16_preserves_quality(files):
    """data.wire_values='f16' (half the value bytes on the feed, cast
    back to f32 on-device) must not cost model quality: AUC within 0.01
    of the exact f32 wire on the same run."""
    aucs = {}
    for wv in ("f32", "f16"):
        cfg = PSConfig()
        cfg.data.num_keys = 1 << 12
        cfg.data.wire_values = wv
        cfg.data.bucket_nnz = True
        cfg.solver.minibatch = 128
        cfg.solver.steps_per_call = 2
        cfg.solver.epochs = 2
        cfg.penalty.lambda_l1 = 0.05
        cfg.parallel.data_shards = 4
        cfg.parallel.kv_shards = 2
        t = PodTrainer(cfg, reporter=quiet())
        t.train_files(files, key_mode="identity", report_every=100)
        aucs[wv] = t.evaluate_files(files[:1], key_mode="identity")["auc"]
    assert aucs["f16"] == pytest.approx(aucs["f32"], abs=0.01), aucs


def test_wire_values_rejects_unknown():
    cfg = PSConfig()
    cfg.data.wire_values = "bf16"
    with pytest.raises(ValueError, match="wire_values"):
        PodTrainer(cfg, reporter=quiet())


def test_wire_values_f16_clips_overflow():
    """Values beyond the finite f16 range clip instead of becoming inf
    (a silent inf would NaN the loss and poison the optimizer state)."""
    from parameter_server_tpu.data.batch import BatchBuilder as BB

    b = BB(num_keys=NUM_KEYS, batch_size=4, max_nnz_per_example=4,
           key_mode="identity").build(
        np.ones(2, np.float32),
        [np.array([1], np.uint64), np.array([2], np.uint64)],
        [np.array([1e6], np.float32), np.array([-1e6], np.float32)],
    )
    stacked = stack_batches([b], None, values_f16=True)
    assert stacked["values"].dtype == np.float16
    assert np.isfinite(stacked["values"].astype(np.float32)).all()
    assert stacked["values"].max() == np.float16(65504.0)
