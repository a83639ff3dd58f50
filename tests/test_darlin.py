"""DARLIN batch solver tests: against liblinear (same objective) on synthetic
data, against the plain NumPy reference (``tests/ref_darlin.py``, a copy of
the benchmark's) block step by block step, and one mesh against another.

One program runs on every mesh: what used to be the single-device tests are
the ``(1, 1)`` cases of the mesh tests here.

Reference test analog: the reference's batch solver demo on rcv1 (L1-LR to
convergence)."""

import os

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.synthetic import make_sparse_logistic
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.models.darlin import (
    ColumnBlocks,
    Darlin,
    make_darlin_fns,
    shard_blocks_for_mesh,
)
from parameter_server_tpu.parallel import make_mesh
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

import ref_darlin

NUM_KEYS = 256
N = 2000


def _batches(labels, keys, vals, num_keys, batch):
    builder = BatchBuilder(num_keys=num_keys, batch_size=batch, key_mode="identity")
    return [
        builder.build(labels[i : i + batch], keys[i : i + batch], vals[i : i + batch])
        for i in range(0, len(labels), batch)
    ]


@pytest.fixture(scope="module")
def data():
    labels, keys, vals, _ = make_sparse_logistic(
        N, NUM_KEYS - 2, nnz_per_example=12, noise=0.3, seed=5
    )
    return _batches(labels, keys, vals, NUM_KEYS, 500), labels, keys, vals


def make_cfg(**kw):
    cfg = PSConfig()
    cfg.data.num_keys = kw.pop("num_keys", NUM_KEYS)
    cfg.solver.algo = "darlin"
    cfg.solver.feature_blocks = kw.pop("blocks", 8)
    cfg.solver.block_iters = kw.pop("iters", 30)
    cfg.solver.epsilon = kw.pop("epsilon", 1e-5)
    cfg.solver.max_delay = kw.pop("max_delay", 0)
    cfg.solver.steps_per_call = kw.pop("steps_per_call", 1)
    cfg.solver.kkt_filter_threshold = kw.pop("kkt", 0.0)
    cfg.penalty.lambda_l1 = kw.pop("lambda_l1", 1.0)
    cfg.lr.eta = kw.pop("eta", 1.0)
    assert not kw
    return cfg


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def solver(cfg, mesh_shape=(1, 1)):
    return Darlin(cfg, reporter=quiet(), mesh=make_mesh(*mesh_shape))


def padded_form(cb: ColumnBlocks):
    """The layout the cache had before: every block padded to the longest."""
    e_max = max(1, int(cb.entries.max()))
    out = [np.zeros((cb.n_blocks, e_max), dt) for dt in (np.int32, np.int32, np.float32)]
    for b in range(cb.n_blocks):
        for arr, part in zip(out, cb.block(b)):
            arr[b, : len(part)] = part
    return out


class TestColumnBlocks:
    def test_layout_roundtrip(self, data):
        batches, labels, keys, vals = data
        cb = ColumnBlocks.from_batches(batches, NUM_KEYS, 8)
        assert cb.num_examples == N
        assert cb.n_blocks == 8
        # every real entry is there once (padding is value==0)
        assert int(cb.entries.sum()) == sum(b.num_entries for b in batches)
        # reconstruct X @ 1 (row sums) and compare with direct computation
        rowsum = np.zeros(N)
        np.add.at(rowsum, cb.rows.ravel(), cb.values.ravel())
        direct = np.array([v.sum() for v in vals])
        np.testing.assert_allclose(rowsum, direct, rtol=1e-4)

    def test_divisibility(self, data):
        with pytest.raises(ValueError, match="n_blocks"):
            ColumnBlocks.from_batches(data[0], NUM_KEYS, 7)

    def test_entries_of_a_block_are_sorted_by_feature(self, data):
        cb = ColumnBlocks.from_batches(data[0], NUM_KEYS, 8, chunk_len=64)
        for b in range(cb.n_blocks):
            lo, hi = int(cb.chunk_begin[b]), int(cb.chunk_begin[b + 1])
            feat = cb.feat_local[lo:hi].ravel()  # the pad included
            assert (np.diff(feat) >= 0).all()
            f, r, _ = cb.block(b)
            # ties in example order: (feature, row) ascends strictly
            assert (np.diff(f.astype(np.int64) * N + r) > 0).all()

    def _skewed(self, n=4096, num_keys=4096):
        """One key in every example, beside a sparse tail: the skew of a
        click log's integer columns."""
        labels, keys, vals, _ = make_sparse_logistic(n, num_keys - 2, nnz_per_example=6, seed=3)
        keys = [np.concatenate([[7], k[k != 7]]).astype(k.dtype) for k in keys]
        vals = [np.concatenate([[1.0], v[: len(k) - 1]]).astype(np.float32) for k, v in zip(keys, vals)]
        return _batches(labels, keys, vals, num_keys, 512)

    def test_skewed_set_is_stored_within_5_percent(self):
        batches = self._skewed()
        cb = ColumnBlocks.from_batches(batches, 4096, 8)
        real = int(cb.entries.sum())
        assert real == sum(b.num_entries for b in batches)
        assert cb.values.size <= 1.05 * real, (cb.values.size, real, cb.chunk_len)
        # where padding every block to the longest holds about twice the entries
        assert cb.n_blocks * int(cb.entries.max()) > 1.9 * real

    def test_block_sums_equal_the_padded_forms(self):
        cb = ColumnBlocks.from_batches(self._skewed(), 4096, 8)
        feat, rows, vals = padded_form(cb)
        err = np.random.default_rng(0).normal(size=cb.num_examples)
        for b in range(cb.n_blocks):
            want = np.bincount(feat[b], weights=vals[b] * err[rows[b]], minlength=cb.block_size)
            lo, hi = int(cb.chunk_begin[b]), int(cb.chunk_begin[b + 1])
            got = np.zeros(cb.block_size)
            for c in range(lo, hi):  # accumulated over the block's chunks, as the device does
                got += np.bincount(
                    cb.feat_local[c], weights=cb.values[c] * err[cb.rows[c]], minlength=cb.block_size
                )
            np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_built_shard_by_shard_equals_one_shard(self, data):
        batches = data[0]
        whole = _batches(data[1], data[2], data[3], NUM_KEYS, N)
        a = ColumnBlocks.from_batches(batches, NUM_KEYS, 8, chunk_len=32)
        b = ColumnBlocks.from_batches(whole, NUM_KEYS, 8, chunk_len=32)
        for k in ("feat_local", "rows", "values", "labels", "chunk_begin", "entries"):
            np.testing.assert_array_equal(getattr(a, k), getattr(b, k))


@pytest.fixture(scope="module")
def sklearn_ref(data):
    """liblinear on the same objective — shared by the convergence tests on
    every mesh."""
    from scipy.sparse import csr_matrix
    from sklearn.linear_model import LogisticRegression

    batches, labels, keys, vals = data
    rows = np.repeat(np.arange(N), [len(k) for k in keys])
    cols = np.concatenate(keys).astype(int) + 1  # identity mode offset
    X = csr_matrix(
        (np.concatenate(vals), (rows, cols)), shape=(N, NUM_KEYS)
    )
    lam = 1.0
    clf = LogisticRegression(
        penalty="l1", C=1.0 / lam, solver="liblinear", max_iter=500, tol=1e-8,
        fit_intercept=False,
    )
    clf.fit(X, labels)
    w = np.zeros(NUM_KEYS)
    w[: clf.coef_.shape[1]] = clf.coef_[0]
    z = X @ w
    obj = float(
        np.sum(np.logaddexp(0, z) - labels * z) + lam * np.abs(w).sum()
    )
    p = 1 / (1 + np.exp(-z))
    return {"obj": obj, "auc": M.auc(labels, p), "nnz": (w != 0).sum(), "X": X}


class TestDarlinConvergence:
    @pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
    def test_matches_liblinear_objective(self, data, sklearn_ref, mesh_shape):
        res = solver(make_cfg(iters=60), mesh_shape).fit(data[0], shuffle_blocks=False)
        ours = res["history"][-1]
        ref = sklearn_ref["obj"]
        # within 1% of liblinear's optimum
        assert ours < ref * 1.01, (ours, ref)
        assert res["train_auc"] > sklearn_ref["auc"] - 0.01

    def test_objective_decreases(self, data):
        res = solver(make_cfg(iters=10)).fit(data[0], shuffle_blocks=False)
        h = res["history"]
        assert all(b <= a * 1.001 for a, b in zip(h, h[1:])), h

    def test_l1_sparsifies(self, data):
        res_small = solver(make_cfg(lambda_l1=0.1, iters=15)).fit(data[0])
        res_big = solver(make_cfg(lambda_l1=10.0, iters=15)).fit(data[0])
        assert res_big["nnz_w"] < res_small["nnz_w"]

    @pytest.mark.parametrize("mesh_shape", [(1, 1), (4, 2)])
    def test_bounded_delay_still_converges(self, data, sklearn_ref, mesh_shape):
        cfg = make_cfg(iters=60, max_delay=2, steps_per_call=3)
        app = solver(cfg, mesh_shape)
        res = app.fit(data[0], shuffle_blocks=False)
        assert res["history"][-1] < sklearn_ref["obj"] * 1.02
        assert app.max_inflight == 3  # max_delay + 1 calls in flight

    @pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
    def test_kkt_filter_converges_same(self, data, sklearn_ref, mesh_shape):
        res = solver(make_cfg(iters=60, kkt=0.1), mesh_shape).fit(data[0], shuffle_blocks=False)
        assert res["history"][-1] < sklearn_ref["obj"] * 1.02

    def test_early_stop_epsilon(self, data):
        res = solver(make_cfg(iters=200, epsilon=1e-3)).fit(data[0])
        assert res["iters"] < 200

    def test_a_call_retires_scalars_and_never_the_table(self, data):
        app = solver(make_cfg(iters=1, steps_per_call=4))
        cb = ColumnBlocks.from_batches(data[0], NUM_KEYS, 8)
        app.begin(cb, shuffle_blocks=False)
        seen = []
        app.on_retire = seen.append
        recs = app.run_calls(app.block_order(0))
        assert [r["call"] for r in recs] == [0, 1] and seen == recs
        assert all(len(r["alphas"]) == 4 and np.isfinite(r["obj"]) for r in recs)
        assert recs[1]["obj"] <= recs[0]["obj"]  # the objective never rises across a step
        assert set(recs[0]) == {"call", "blocks", "alphas", "obj", "viol_max", "nnz_w"}
        assert recs[1]["nnz_w"] == int((app.w != 0).sum())


def test_the_refresh_is_calls_of_the_pass_retired_like_its_steps(data):
    """A pass with the filter on: its blocks' steps, then the same groups of
    blocks again as refresh calls, each retired through ``on_retire`` with
    its count of active coordinates and neither weights nor pred moved."""
    app = solver(make_cfg(iters=3, kkt=0.1, epsilon=0.0, steps_per_call=4))
    seen = []
    app.on_retire = seen.append
    res = app.fit(data[0])
    assert len(seen) == 3 * (2 + 2)  # 8 blocks in calls of 4: two step calls, two refresh calls a pass
    for it in range(3):
        steps, refreshes = seen[4 * it : 4 * it + 2], seen[4 * it + 2 : 4 * it + 4]
        assert all(set(r) == {"call", "blocks", "n_active"} for r in refreshes)
        assert [list(r["blocks"]) for r in refreshes] == [list(r["blocks"]) for r in steps]
        assert steps[-1]["obj"] == res["history"][it]
    active = np.asarray(app.state["active"])[:NUM_KEYS, 0]
    assert sum(r["n_active"] for r in seen[-2:]) == active.sum() < NUM_KEYS


def test_a_hot_keys_sums_are_taken_chunk_by_chunk():
    """A key every example holds: its first chunk's terms sum to 2^25, and
    the 1,024 terms of 1 behind them, added one by one into that float32,
    would each be lost (half a unit in the last place). By chunks of 64
    they count. Read off the call's largest violation, |g| - lambda_l1."""
    from parameter_server_tpu.data.blockcache import ColumnBlocksBuilder

    n, chunk = 64 * 17, 64
    builder = ColumnBlocksBuilder(num_keys=32, n_blocks=2, chunk_len=chunk)
    values = np.where(np.arange(n) < chunk, 2.0**20, 2.0).astype(np.float32)
    # labels 0: e = sigmoid(0) - 0 = 1/2, so g = sum x / 2
    builder.add(np.full(n, 3), np.arange(n), values, np.zeros(n, np.float32))
    app = solver(make_cfg(num_keys=32, blocks=2, iters=1))
    app.begin(builder.finish(), shuffle_blocks=False)
    rec = app.run_calls(app.block_order(0), 0, 1)[0]
    assert rec["viol_max"] == pytest.approx(2.0**25 + (n - chunk) - 1.0, abs=4.0)


def _write_libsvm(path, labels, keys, vals):
    from parameter_server_tpu.data.synthetic import write_libsvm

    write_libsvm(path, labels, keys, vals)
    return str(path)


def test_evaluate_files_on_the_solved_table_equals_the_old_predict(data, tmp_path):
    """Held-out files are scored by ``PodTrainer.evaluate_files`` over the
    linear app on the solver's table: the scores equal those of the
    probabilities ``Darlin.predict`` used to compute, sigmoid(Xw)."""
    batches, labels, keys, vals = data
    cfg = make_cfg(iters=20)
    cfg.data.format = "libsvm"
    cfg.solver.minibatch = 512
    # libsvm keys hash into the table: solve on the file the evaluator reads
    path = _write_libsvm(tmp_path / "held.svm", labels, keys, vals)
    cfg.data.files = [path]
    from parameter_server_tpu.data.blockcache import cached_column_blocks

    app = solver(cfg, (2, 2))
    app.fit_blocks(cached_column_blocks(cfg))
    ev = app.evaluate_files([path])
    probs = 1.0 / (1.0 + np.exp(-app.predictions().astype(np.float64)))
    assert ev["auc"] == pytest.approx(M.auc(labels, probs), abs=1e-6)
    assert ev["logloss"] == pytest.approx(M.logloss(labels, probs), rel=1e-5)
    assert ev["auc"] > 0.85


class TestMeshes:
    """Distributed DARLIN over the (data, kv) mesh (SURVEY §3.3: example
    shards on workers, weight ranges on servers): the same program, held
    to the 1x1 mesh's trajectory."""

    @pytest.mark.parametrize("mesh_shape", [(2, 2), (4, 2), (2, 4), (1, 4)])
    def test_matches_the_1x1_trajectory(self, data, mesh_shape):
        batches = data[0]
        cfg = make_cfg(iters=12)
        ref = solver(cfg).fit(batches, shuffle_blocks=False)
        app = solver(cfg, mesh_shape)
        res = app.fit(batches, shuffle_blocks=False)
        # same math, different layout: objective trajectories must agree
        assert len(res["history"]) == len(ref["history"])
        np.testing.assert_allclose(
            np.array(res["history"]), np.array(ref["history"]), rtol=2e-4
        )
        assert app.w.shape == (NUM_KEYS,) and app.predictions().shape == (N,)

    def test_shuffled_blocks_same_trajectory_as_1x1(self, data):
        """Same seed => same block order => matching trajectories even
        with shuffling on."""
        cfg = make_cfg(iters=8)
        ref = solver(cfg).fit(data[0], shuffle_blocks=True)
        res = solver(cfg, (2, 2)).fit(data[0], shuffle_blocks=True)
        np.testing.assert_allclose(
            np.array(res["history"]), np.array(ref["history"]), rtol=2e-4
        )

    def test_block_alignment_enforced(self, data):
        from parameter_server_tpu.kv.updaters import ProxNewton
        from parameter_server_tpu.parallel import spmd

        with pytest.raises(ValueError, match="aligned"):
            make_darlin_fns(
                make_mesh(2, 4), spmd.Table("", ProxNewton(), 1), num_keys=NUM_KEYS,
                block_size=48, per_shard_examples=100, delay=0,
            )

    def test_state_is_a_tables_slots_in_the_one_state_dict(self, data):
        from parameter_server_tpu.parallel import spmd

        app = solver(make_cfg(iters=2), (2, 2))
        app.fit(data[0])
        assert isinstance(app.table, spmd.Table) and app.table.updater.name == "prox_newton"
        assert set(app.state) == {"w", "active"} == set(app.table.slots())
        for v in app.state.values():
            assert v.shape == (NUM_KEYS, 1) and v.sharding.spec == spmd.state_spec()


# ---------------------------------------------------------------------------
# Against the plain reference, block step by block step
# ---------------------------------------------------------------------------

SMALL_N, SMALL_K, SMALL_B = 4096, 1 << 12, 8


@pytest.fixture(scope="module")
def small():
    labels, keys, vals, _ = make_sparse_logistic(
        SMALL_N, SMALL_K - 2, nnz_per_example=12, noise=0.3, seed=11
    )
    gid = np.concatenate(keys).astype(np.int64) + 1
    rows = np.repeat(np.arange(SMALL_N), [len(k) for k in keys])
    x = np.concatenate(vals).astype(np.float32)
    bs = SMALL_K // SMALL_B

    def entries_of(b):
        m = gid // bs == b
        return (gid[m] - b * bs).astype(np.int64), rows[m], x[m]

    return _batches(labels, keys, vals, SMALL_K, 512), labels, entries_of


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2), (1, 4)])
def test_three_passes_with_the_filter_on_against_the_plain_reference(small, mesh_shape):
    """w, pred, the scale of every block step and the active set after each
    of three passes. The program sums in float32 in the device's order, the
    reference in float64: 1e-5 of the largest value is the float32 sums'
    room; the scales are discrete and have to cost nothing by the reference's
    own objective."""
    batches, labels, entries_of = small
    cfg = make_cfg(num_keys=SMALL_K, blocks=SMALL_B, iters=3, kkt=0.1, steps_per_call=4)
    cfg.seed = 5
    app = solver(cfg, mesh_shape)
    app.begin(ColumnBlocks.from_batches(batches, SMALL_K, SMALL_B))
    hyper = {"lambda_l1": 1.0, "lambda_l2": 0.0, "eta": 1.0}
    ref = ref_darlin.RefDarlin(labels, SMALL_K // SMALL_B, hyper)
    bs = SMALL_K // SMALL_B
    for it in range(3):
        order = ref_darlin.block_order(cfg.seed, it, SMALL_B)
        np.testing.assert_array_equal(order, app.block_order(it))
        recs = app.run_calls(order)
        got_alpha = np.concatenate([r["alphas"] for r in recs])
        # the reference follows the program's scale and prices it by its own
        # objective: two scales closer than float32 can tell apart (a regret
        # under 1e-6 of the objective) are the same choice, any other is not
        steps = [
            ref.block_step(int(b), *entries_of(int(b)), alpha=float(a))
            for b, a in zip(order, got_alpha)
        ]
        assert max(s["regret"] for s in steps) < 1e-6, steps
        assert np.mean([s["alpha"] == s["own_alpha"] for s in steps]) >= 0.75
        viol = max(r["viol_max"] for r in recs)
        assert viol == pytest.approx(ref.viol_max, rel=1e-5)
        app._refresh(order, viol)
        ref.end_pass(entries_of, SMALL_B, 0.1)
        w = app.w
        for b in range(SMALL_B):
            np.testing.assert_allclose(
                w[b * bs : (b + 1) * bs], ref.weights(b), atol=1e-5 * np.abs(w).max()
            )
        np.testing.assert_allclose(app.predictions(), ref.pred, atol=1e-5 * np.abs(ref.pred).max())
        active = np.asarray(app.state["active"])[:SMALL_K, 0] > 0
        want_active = np.concatenate([ref.active[b] for b in range(SMALL_B)])
        # a violation within float32's room of the threshold may fall either side
        assert (active != want_active).mean() < 2e-3
        assert recs[-1]["obj"] == pytest.approx(ref.objective(), rel=1e-6)
    assert 0 < want_active.mean() < 1  # the filter did set coordinates aside


def test_the_tests_reference_is_the_benchmarks():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "harness", "ref_darlin.py")) as a:
        with open(os.path.join(root, "tests", "ref_darlin.py")) as b:
            assert a.read() == b.read()


@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_resume_after_pass_2_equals_the_uninterrupted_solve(small, mesh_shape, tmp_path):
    batches = small[0]
    cb = ColumnBlocks.from_batches(batches, SMALL_K, SMALL_B)

    def cfg_of(iters):
        cfg = make_cfg(num_keys=SMALL_K, blocks=SMALL_B, iters=iters, kkt=0.1, epsilon=0.0)
        cfg.solver.steps_per_call = 2
        return cfg

    whole = solver(cfg_of(4), mesh_shape)
    want = whole.fit_blocks(cb)
    ckpt = str(tmp_path / "ckpt")
    first = solver(cfg_of(2), mesh_shape)
    first.fit_blocks(cb, ckpt_dir=ckpt)
    second = solver(cfg_of(4), mesh_shape)
    got = second.fit_blocks(cb, ckpt_dir=ckpt, resume=True)
    assert got["iters"] == 4 and second.passes_done == 4
    np.testing.assert_allclose(got["history"], want["history"], rtol=1e-6)
    # pred is recomputed from w by one sweep, not saved: it comes back float32's
    # room apart (1e-6), and two more passes carry that into the weights the
    # data hardly determines (4e-3 seen, on 14 of 4096)
    np.testing.assert_allclose(second.w, whole.w, atol=2e-2)
    assert np.mean(np.abs(second.w - whole.w) > 1e-4) < 0.05
    np.testing.assert_allclose(second.predictions(), whole.predictions(), atol=2e-2)
    with pytest.raises(FileNotFoundError):
        solver(cfg_of(4), mesh_shape).fit_blocks(cb, ckpt_dir=str(tmp_path / "none"), resume=True)


class TestRangePullPush:
    """The store's pull and push of a contiguous key range against NumPy
    slices: at any range size, on 1, 2 and 4 kv shards."""

    @pytest.mark.parametrize("kv", [1, 2, 4])
    @pytest.mark.parametrize("size", [8, 24, 64])
    def test_against_numpy_slices(self, kv, size):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from parameter_server_tpu.kv.updaters import ProxNewton
        from parameter_server_tpu.parallel import spmd

        rows = 192 * 4  # every size divides a shard of every kv
        mesh = make_mesh(8 // kv if kv > 1 else 1, kv)
        table = spmd.Table("", ProxNewton(), 1)
        rng = np.random.default_rng(size * kv)
        host = {k: rng.normal(size=(rows, 1)).astype(np.float32) for k in table.slots()}
        state = spmd.shard_state({k: jax.numpy.asarray(v) for k, v in host.items()}, mesh)
        shard = rows // kv
        specs = {k: spmd.state_spec() for k in host}

        def local(state_l, begin, new):
            pulled = spmd.pull_range(table, state_l, begin, size, shard, kv)
            return pulled, spmd.push_range(table, state_l, begin, new, shard, kv)

        fn = jax.jit(shard_map(
            local, mesh=mesh, in_specs=(specs, P(), {k: P() for k in host}),
            out_specs=({k: P() for k in host}, specs), check_vma=False,
        ))
        for at in (0, size, shard - size, rows - size):
            new = {k: rng.normal(size=(size, 1)).astype(np.float32) for k in host}
            pulled, state = fn(state, np.int32(at), new)
            for k in host:
                np.testing.assert_array_equal(np.asarray(pulled[k]), host[k][at : at + size])
                host[k][at : at + size] = new[k]
                np.testing.assert_array_equal(np.asarray(state[k]), host[k])


class TestShardBlocksPacking:
    """The (block, shard) packer behind distributed DARLIN's data prep."""

    def _naive(self, cb, D, sel):
        """Per shard, per selected block: the block's entries of the shard's
        examples in the block's own (feature-sorted) order, rows local."""
        per = -(-cb.num_examples // D)
        out = {}
        for j, b in enumerate(sel):
            feat, rows, vals = cb.block(int(b))
            for d in range(D):
                m = rows // per == d
                out[j, d] = (feat[m], rows[m] - d * per, vals[m])
        return out

    def _unpack(self, packed, j, d, C):
        lo, hi, at = packed["spans"][d, j]
        n = int(packed["counts"][j, d])
        assert hi - lo == -(-n // C) and at == j
        flat = lambda k: packed[k][d, lo:hi].ravel()  # noqa: E731
        assert not flat("values")[n:].any()  # only the last chunk's tail is padding
        # feature f's run of real entries is ends[f - 1] .. ends[f] of the part
        runs = np.searchsorted(flat("feat_local")[:n], np.arange(packed["ends"].shape[-1]), side="right")
        np.testing.assert_array_equal(packed["ends"][d, at], runs)
        return tuple(flat(k)[:n] for k in ("feat_local", "rows", "values")), flat("feat_local")

    @pytest.mark.parametrize("D", [1, 2, 4])
    def test_matches_naive_pack(self, data, D):
        cb = ColumnBlocks.from_batches(data[0], NUM_KEYS, 8, chunk_len=64)
        sel = np.arange(cb.n_blocks)
        want = self._naive(cb, D, sel)
        out = shard_blocks_for_mesh(cb, D)
        np.testing.assert_array_equal(out["block_idx"], sel)
        for (j, d), parts in want.items():
            got, with_pad = self._unpack(out, j, d, 64)
            for g, w in zip(got, parts):
                np.testing.assert_array_equal(g, w)
            assert (np.diff(with_pad) >= 0).all()  # the pad keeps the order

    @pytest.mark.parametrize("D", [1, 2])
    def test_subset_and_pow2(self, data, D):
        cb = ColumnBlocks.from_batches(data[0], NUM_KEYS, 8, chunk_len=64)
        sel = np.array([5, 1, 6])
        out = shard_blocks_for_mesh(cb, D, blocks=sel, pad_pow2=True)
        n_chunks = out["feat_local"].shape[1]
        assert n_chunks & (n_chunks - 1) == 0  # power of two
        np.testing.assert_array_equal(out["block_idx"], sel)
        want = self._naive(cb, D, sel)
        for (j, d), parts in want.items():
            got, _ = self._unpack(out, j, d, 64)
            for g, w in zip(got, parts):
                np.testing.assert_array_equal(g, w)


class TestDarlinStreaming:
    """block_chunk > 0: blocks streamed to device per call in bounded
    memory (ref: SlotReader's stream-per-block design, SURVEY §3.3): the
    resident program at the streamed chunks' shapes."""

    @pytest.mark.parametrize("chunk", [3, 8])
    def test_chunked_matches_resident_trajectory(self, data, chunk):
        ref_cfg = make_cfg(iters=8, kkt=0.1)
        ref = solver(ref_cfg, (2, 2)).fit(data[0], shuffle_blocks=True)
        cfg = make_cfg(iters=8, kkt=0.1)
        cfg.solver.block_chunk = chunk
        res = solver(cfg, (2, 2)).fit(data[0], shuffle_blocks=True)
        np.testing.assert_allclose(
            np.array(res["history"]), np.array(ref["history"]), rtol=1e-5
        )

    def test_10x_scale_streaming_parity(self):
        """>= 10x the module's base fixture (N=2000, 256 keys; 4096 here: whole row tiles a kv shard): the
        streamed solver must match the resident trajectory while holding
        only block_chunk blocks on device per call."""
        n, num_keys = 20000, 4096
        labels, keys, vals, _ = make_sparse_logistic(
            n, num_keys - 2, nnz_per_example=12, noise=0.3, seed=9
        )
        batches = _batches(labels, keys, vals, num_keys, 2000)
        histories = {}
        for chunk in (0, 4):
            cfg = make_cfg(iters=4, blocks=16, num_keys=num_keys)
            cfg.solver.block_chunk = chunk
            histories[chunk] = solver(cfg, (2, 2)).fit(batches, shuffle_blocks=True)["history"]
        np.testing.assert_allclose(
            np.array(histories[4]), np.array(histories[0]), rtol=1e-5
        )


# -- the sweeps by feature: running sums along the entry axis -----------------
SWEEP_CHUNK, SWEEP_BLOCK = 16, 32


_FEW = [3] * 5 + [4] * 2 + [9] * 7 + [20] * 1  # 0-2, 5-8, 10-19, 21-31 hold no entry
# case -> (feature ids, block to sweep, data shards, want_h): block 0 or 1 of two
# blocks of ``SWEEP_BLOCK`` features in chunks of ``SWEEP_CHUNK``
SWEEP_CASES = {
    # feature 5's run of 40 lies in chunks 0..2, of 90 in chunks 0..5
    "a_run_crosses_two_chunk_boundaries": ([1] * 3 + [5] * 40 + [6] * 2 + [31] * 3, 0, 1, True),
    "a_run_crosses_five_chunk_boundaries": ([1] * 3 + [5] * 90 + [7] * 3, 0, 1, True),
    "no_entry_at_head_middle_and_tail": (_FEW, 0, 1, True),
    "the_last_chunk_is_padded": (_FEW + [25] * 20, 0, 1, True),  # 35 entries: 13 pads
    "an_empty_block": (_FEW, 1, 1, True),
    "the_refresh_takes_g_alone": ([1] * 3 + [5] * 40 + [31] * 3, 0, 1, False),
    "two_data_shards": ([1] * 3 + [5] * 90 + [6] * 2 + [31] * 3, 0, 2, True),
}


def _sweep_blocks(feats, seed=3):
    """A built ``ColumnBlocks`` with one entry an example (so that X_b d lands
    each entry's value of d in a row of its own) and seeded values."""
    from parameter_server_tpu.data.blockcache import ColumnBlocksBuilder

    rng = np.random.default_rng(seed)
    n = len(feats)
    builder = ColumnBlocksBuilder(num_keys=2 * SWEEP_BLOCK, n_blocks=2, chunk_len=SWEEP_CHUNK)
    order = rng.permutation(n)  # the builder sorts by feature itself
    builder.add(np.asarray(feats)[order], np.arange(n), rng.standard_normal(n).astype(np.float32),
                np.zeros(n, np.float32))
    return builder.finish()


def _shard_args(cb, D):
    """Per data shard: (the chunk arrays and run ends as the programs take
    them, the spans row of each block), and the examples a shard."""
    sharded = shard_blocks_for_mesh(cb, D)
    parts = [
        ({k: sharded[k][d] for k in ("feat_local", "rows", "values", "ends")}, sharded["spans"][d])
        for d in range(D)
    ]
    return parts, sharded["per_shard_examples"]


# features a window of a chunk's read: the whole block's 32 at once, and 5 (a
# chunk's features lie in several windows, and the block's last starts early)
WINDOWS = [4096, 5]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("case", SWEEP_CASES)
def test_a_blocks_sums_by_feature_equal_float64_segment_sums(case, window, monkeypatch):
    """(g, h) of one block as the programs take them, every data shard's
    part added up (the psum), against ``bincount`` in float64 over the
    block's real entries."""
    import jax

    from parameter_server_tpu.models import darlin
    from parameter_server_tpu.models.darlin import _block_grad

    monkeypatch.setattr(darlin, "_WINDOW", window)
    feats, b, D, want_h = SWEEP_CASES[case]
    cb = _sweep_blocks(feats)
    rng = np.random.default_rng(11)
    parts, per = _shard_args(cb, D)
    err = rng.standard_normal(D * per).astype(np.float32)
    h_ex = rng.random(D * per).astype(np.float32)
    fn = jax.jit(_block_grad, static_argnames="want_h")
    got = sum(
        np.asarray(fn(err[d * per : (d + 1) * per], h_ex[d * per : (d + 1) * per], chunks, spans[b],
                      want_h=want_h), np.float64)
        for d, (chunks, spans) in enumerate(parts)
    )
    feat, rows, vals = (np.asarray(a) for a in cb.block(b))
    v = vals.astype(np.float64)
    want = [np.bincount(feat, weights=v * err[rows], minlength=SWEEP_BLOCK)]
    if want_h:
        want.append(np.bincount(feat, weights=v * v * h_ex[rows], minlength=SWEEP_BLOCK))
    assert got.shape == (len(want), SWEEP_BLOCK)
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-5, atol=1e-5)
    absent = np.bincount(feat, minlength=SWEEP_BLOCK) == 0
    assert not got[:, absent].any()  # no entry: exactly 0, not a neighbour's total


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("case", [c for c in SWEEP_CASES if c != "the_refresh_takes_g_alone"])
def test_d_by_feature_is_copied_down_each_run_exactly(case, window, monkeypatch):
    """X_b d where every entry is an example of its own and every value 1:
    row i reads d of entry i's feature, to the bit what ``take(d, feat)``
    gives (a run's head + 0 + ... + 0), and a row with no entry of the
    block reads 0."""
    import jax

    from parameter_server_tpu.models import darlin
    from parameter_server_tpu.models.darlin import _block_xd

    monkeypatch.setattr(darlin, "_WINDOW", window)
    feats, b, D, _ = SWEEP_CASES[case]
    cb = _sweep_blocks(feats)
    cb.values[...] = np.where(cb.values != 0, 1.0, 0.0)  # the pads stay 0
    d_vec = np.random.default_rng(13).standard_normal(SWEEP_BLOCK).astype(np.float32)
    parts, per = _shard_args(cb, D)
    fn = jax.jit(_block_xd, static_argnames="per")
    got = np.concatenate([
        np.asarray(fn(d_vec, chunks, spans[b], per=per))
        for chunks, spans in parts
    ])
    feat, rows, _ = cb.block(b)
    want = np.zeros(D * per, np.float32)
    want[rows] = d_vec[feat]
    np.testing.assert_array_equal(got, want)


def test_begin_observes_how_far_the_runs_carry():
    """Feature 5's 90 entries lie in all 6 chunks of the block: 5 of the 6
    chunks take up the run of the chunk before, and the longest run lies in
    6 chunks; ``begin`` leaves both as scalars beside ``darlin.viol_max``."""
    from parameter_server_tpu.models.darlin import run_carries
    from parameter_server_tpu.utils.metrics import latency_histograms

    cb = _sweep_blocks([1] * 3 + [5] * 90 + [7] * 3)
    assert run_carries(cb) == (5 / 6, 6)
    assert run_carries(_sweep_blocks([3] * 5 + [4] * 2 + [9] * 7)) == (0.0, 1)
    latency_histograms.reset()
    solver(make_cfg(num_keys=2 * SWEEP_BLOCK, blocks=2, iters=1)).begin(cb, shuffle_blocks=False)
    hists = latency_histograms.snapshot()
    assert hists["darlin.carry_share"]["sum_s"] * 1e6 == pytest.approx(5 / 6)
    assert hists["darlin.longest_run_chunks"]["sum_s"] * 1e6 == pytest.approx(6)
