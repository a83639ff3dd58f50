"""The plain reference against the program's store at a tiny table: the
same pushes give the same rows (float32), and the copied hash addresses
the same rows as the program's."""

import numpy as np
import pytest

import tiny  # noqa: F401
from benchmark.harness import criteo
from benchmark.harness.ref_ftrl import RefFtrl, auc, logloss

HYPER = {"alpha": 0.1, "beta": 1.0, "lambda_l1": 1.0, "lambda_l2": 0.0}


def test_push_matches_kv_store_push():
    import jax.numpy as jnp

    from parameter_server_tpu.kv import store
    from parameter_server_tpu.kv.updaters import Ftrl

    rng = np.random.default_rng(3)
    n_keys = 4096
    upd = Ftrl(**HYPER)
    state = upd.init(n_keys, 1)
    ref = RefFtrl(np.arange(n_keys), HYPER)
    for _ in range(6):  # overlapping key sets: rows are updated more than once
        keys = np.unique(rng.integers(1, 600, 300))
        g = (3.0 * rng.normal(size=len(keys))).astype(np.float32)
        state = store.push(upd, state, jnp.asarray(keys), jnp.asarray(g[:, None]))
        ref.push(keys, g)
    np.testing.assert_allclose(np.asarray(state["z"])[:, 0], ref.z, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state["n"])[:, 0], ref.n, rtol=1e-5, atol=1e-6)
    w = np.asarray(upd.weights(state))[:, 0]
    np.testing.assert_allclose(w, ref.weights(), rtol=1e-5, atol=1e-7)
    assert np.count_nonzero(w) > 50


def test_hash_matches_the_programs():
    from parameter_server_tpu.utils.hashing import hash_keys

    rng = np.random.default_rng(4)
    raw = rng.integers(0, 1 << 40, 5000).astype(np.uint64)
    slots = rng.integers(1, 40, 5000).astype(np.uint64)
    for num_keys in (1 << 20, 1 << 30, (1 << 31) - 1):
        np.testing.assert_array_equal(
            criteo.hash_rows(raw, slots, num_keys), hash_keys(raw, num_keys, slot_ids=slots)
        )


def test_tsv_is_what_the_parser_reads(tmp_path):
    """Generator -> TSV -> the program's criteo parser and builder gives the
    rows and values ``criteo.features`` computes from the raw columns."""
    from parameter_server_tpu.data.batch import BatchBuilder
    from parameter_server_tpu.data.reader import MinibatchReader

    spec = tiny.mf.load_json(
        tiny.os.path.join(tiny.ROOT, "benchmark/configs/ctr_ftrl_1chip.json"), "config")["data"]
    labels, ints, cats = criteo.make_examples(2**31 + 17, 512, spec, part=1)
    path = str(tmp_path / "p.tsv")
    criteo.write_tsv(path, labels, ints, cats)
    num_keys = 1 << 22
    (batch,) = list(MinibatchReader([path], "criteo", BatchBuilder(num_keys, 512, 64, bucket_nnz=True)))
    assert batch.num_examples == 512 and batch.num_entries == 512 * 39
    rows, vals = criteo.features(ints, cats, num_keys)
    got_rows = batch.unique_keys[batch.local_ids[: batch.num_entries]].reshape(512, 39)
    np.testing.assert_array_equal(got_rows, rows)
    np.testing.assert_array_equal(batch.values[: batch.num_entries].reshape(512, 39), vals)
    np.testing.assert_array_equal(batch.labels, labels)


def test_auc_and_logloss_match_the_programs():
    from parameter_server_tpu.models import metrics as M

    rng = np.random.default_rng(5)
    y = (rng.random(4000) < 0.3).astype(np.float32)
    p = np.round(rng.random(4000), 2).astype(np.float32)  # many ties
    assert auc(y, p) == pytest.approx(M.auc(y, p), abs=1e-12)
    assert logloss(y, p) == pytest.approx(M.logloss(y, p), rel=1e-12)


def test_same_seed_same_data_other_seed_other_data():
    spec = tiny.mf.load_json(
        tiny.os.path.join(tiny.ROOT, "benchmark/configs/ctr_ftrl_1chip.json"), "config")["data"]
    a = criteo.make_examples(9, 256, spec)
    b = criteo.make_examples(9, 256, spec)
    c = criteo.make_examples(10, 256, spec)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[2], c[2])
