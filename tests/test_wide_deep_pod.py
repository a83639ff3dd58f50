"""Wide&Deep through ``PodTrainer`` - two tables and a dense tower in the
shared parameter-server step - against the benchmark's plain NumPy
reference (``benchmark/harness/ref_wd.py``, which imports nothing of the
program), on the CPU at small sizes; the reference's hand-written backward
pass against ``jax.grad``; the store's pull and push at ``vdim`` 1, 8, 16
and 64 against NumPy, bit for bit; the device-made starting embedding
against the reference's function; checkpoints of tables, tower and
optimizer state."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import ref_wd  # noqa: E402
from parameter_server_tpu.data.batch import BatchBuilder  # noqa: E402
from parameter_server_tpu.kv import store  # noqa: E402
from parameter_server_tpu.kv.updaters import Adagrad, Sgd  # noqa: E402
from parameter_server_tpu.parallel import make_mesh  # noqa: E402
from parameter_server_tpu.parallel.trainer import PodTrainer  # noqa: E402
from parameter_server_tpu.utils.config import PSConfig  # noqa: E402
from parameter_server_tpu.utils.metrics import ProgressReporter  # noqa: E402

F = 6  # features an example
BATCH = 32


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def make_cfg(num_keys, emb_dim, data=1, kv=1, steps_per_call=1, seed=5, push_mode="per_worker"):
    cfg = PSConfig()
    cfg.app, cfg.seed = "wide_deep", seed
    cfg.data.num_keys = num_keys
    cfg.data.max_nnz_per_example = 8
    cfg.solver.minibatch = BATCH
    cfg.solver.steps_per_call = steps_per_call
    cfg.solver.max_delay = 1
    cfg.lr.alpha, cfg.penalty.lambda_l1 = 0.1, 0.05
    cfg.wd.emb_dim, cfg.wd.hidden = emb_dim, [16]
    cfg.wd.emb_eta, cfg.wd.mlp_lr = 0.05, 1e-2
    cfg.parallel.data_shards, cfg.parallel.kv_shards = data, kv
    cfg.parallel.push_mode = push_mode
    return cfg


def hyper_of(cfg):
    return {
        "alpha": cfg.lr.alpha, "beta": cfg.lr.beta, "lambda_l1": cfg.penalty.lambda_l1,
        "lambda_l2": cfg.penalty.lambda_l2, "emb_dim": cfg.wd.emb_dim, "hidden": cfg.wd.hidden,
        "emb_eta": cfg.wd.emb_eta, "mlp_lr": cfg.wd.mlp_lr,
    }


def make_data(num_keys, n_batches, seed=0):
    """(rows (n, F) distinct within an example, values (n, F) with some
    zeros, labels (n,)) and the CSRBatches a builder makes of them."""
    rng = np.random.default_rng(seed)
    n = n_batches * BATCH
    rows = np.stack([rng.choice(np.arange(1, num_keys), F, replace=False) for _ in range(n)])
    vals = rng.normal(size=(n, F)).astype(np.float32)
    vals[rng.random((n, F)) < 0.1] = 0.0  # a feature that is absent from the pool
    labels = (rng.random(n) < 0.4).astype(np.float32)
    builder = BatchBuilder(num_keys=num_keys, batch_size=BATCH, max_nnz_per_example=8, key_mode="identity")
    batches = [
        builder.build(
            labels[i : i + BATCH], [(r - 1).astype(np.uint64) for r in rows[i : i + BATCH]],  # identity: row = key + 1
            list(vals[i : i + BATCH]),
        )
        for i in range(0, n, BATCH)
    ]
    return rows, vals, labels, batches


def reference_run(cfg, rows, vals, labels, n_steps, data_shards):
    ref = ref_wd.RefWd(np.arange(cfg.data.num_keys), hyper_of(cfg), cfg.seed, cfg.data.num_keys)
    losses = []
    for s in range(n_steps):
        workers = []
        for d in range(data_shards):  # a microstep consumes D consecutive batches
            sl = slice((s * data_shards + d) * BATCH, (s * data_shards + d + 1) * BATCH)
            workers.append((rows[sl], vals[sl], labels[sl]))
        losses.append(ref.step(workers))
    return ref, np.asarray(losses)


def tower_flat(trainer):
    return np.concatenate([np.asarray(x).ravel() for layer in trainer.dense()[0] for x in (layer["W"], layer["b"])])


MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("steps_per_call", [1, 3], ids=["single", "scanned"])
def test_pod_trainer_matches_the_plain_reference(mesh_name, steps_per_call):
    """Losses, every row of all four tables and the tower's weights after 6
    microsteps. Tolerances: the program and the reference do the same
    float32 arithmetic in different orders of summation (segment sums
    against bincounts over float64, XLA's dot against BLAS), so a loss
    agrees to a few 1e-6 relative; a table row or a tower weight is compared
    to 2e-4 of the values' scale because AdaGrad's and Adam's early steps
    are eta x g / (|g| + eps): an element whose gradient is a near-cancelled
    sum moves by a visible fraction of eta on the sum's last bits (measured
    worst here: 6e-5 of the scale; a wrong update, a missed push or a
    worker order swapped moves rows by whole multiples of eta, 100 times
    the tolerance)."""
    data, kv = MESHES[mesh_name]
    num_keys, emb_dim = (64, 8) if steps_per_call == 1 else (4096, 16)
    n_steps = 6
    cfg = make_cfg(num_keys, emb_dim, data, kv, steps_per_call)
    rows, vals, labels, batches = make_data(num_keys, n_steps * data, seed=3)
    trainer = PodTrainer(cfg, mesh=make_mesh(data, kv), reporter=quiet())
    got_losses = []
    step_fn = trainer.step_fn

    def recorded(state, batch, seed):
        state, out = step_fn(state, batch, seed)
        got_losses.append(out["loss_sum"])
        return state, out

    trainer.step_fn = recorded
    trainer.train_batches(batches, report_every=10**6)
    got_losses = np.concatenate([np.atleast_1d(np.asarray(x)) for x in got_losses])[:n_steps]
    ref, ref_losses = reference_run(cfg, rows, vals, labels, n_steps, data)
    np.testing.assert_allclose(got_losses, ref_losses, rtol=2e-5)
    assert trainer.examples_seen == n_steps * data * BATCH
    for name, want in (("wide", {"z": ref.z[:, None], "n": ref.n[:, None]}),
                       ("emb", {"w": ref.emb_w, "n": ref.emb_n})):
        got = trainer.runtime.state_to_host(trainer.table_state(name))
        for slot, w in want.items():
            g = got[slot]
            assert not g[num_keys:].any()  # the kv-axis pad rows stay zero
            scale = max(float(np.abs(w).max()), 1e-6)
            np.testing.assert_allclose(g[:num_keys], w, rtol=0, atol=2e-4 * scale, err_msg=f"{name}.{slot}")
    want = ref.tower_flat()
    np.testing.assert_allclose(tower_flat(trainer), want, rtol=0, atol=2e-4 * float(np.abs(want).max()))


def test_other_push_modes_run_every_table():
    """Every push mode of the shared step works for both tables: aggregate
    (one updater step on the workers' summed gradient) equals per_worker on
    one data shard, where there is one worker to sum; quantized trains."""
    rows, vals, labels, batches = make_data(64, 4, seed=4)
    out = {}
    for mode in ("per_worker", "aggregate", "quantized"):
        trainer = PodTrainer(make_cfg(64, 8, 1, 2, 2, push_mode=mode), mesh=make_mesh(1, 2), reporter=quiet())
        trainer.train_batches(batches, report_every=10**6)
        out[mode] = {k: np.asarray(v) for k, v in trainer.state.items()}
    for k in out["per_worker"]:
        np.testing.assert_allclose(out["aggregate"][k], out["per_worker"][k], rtol=1e-5, atol=1e-7, err_msg=k)
    assert np.abs(out["quantized"]["emb.n"]).sum() > 0 and np.abs(out["quantized"]["wide.n"]).sum() > 0


def test_reference_backward_pass_against_jax_grad():
    """``RefWd.grads`` (written out by hand) against ``jax.grad`` of a
    ``jax.numpy`` transcription of ``RefWd.forward``."""
    cfg = make_cfg(64, 8)
    rows, vals, labels, _ = make_data(64, 1, seed=9)
    ref = ref_wd.RefWd(np.arange(64), hyper_of(cfg), cfg.seed, 64)
    # off the flat start: FTRL weights that are not all zero
    rng = np.random.default_rng(1)
    ref.z = rng.normal(scale=2.0, size=64).astype(np.float32)
    ref.n = rng.random(64).astype(np.float32)

    def loss_fn(wide_w, emb_w, tower):
        wide = (wide_w[rows] * vals).sum(axis=1)
        ones = (vals != 0).astype(jnp.float32)
        cnt = jnp.maximum(ones.sum(axis=1), 1.0)
        h = (emb_w[rows] * ones[:, :, None]).sum(axis=1) / cnt[:, None]
        for w, b in tower[:-1]:
            h = jax.nn.relu(h @ w + b)
        w, b = tower[-1]
        x = wide + (h @ w + b)[:, 0]
        return jnp.sum(jax.nn.softplus(x) - labels * x)

    want_loss, (g_wide, g_emb, g_tower) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        jnp.asarray(ref.wide_weights()), jnp.asarray(ref.emb_w), [(jnp.asarray(w), jnp.asarray(b)) for w, b in ref.tower]
    )
    loss, r_wide, r_emb, r_tower = ref.grads(rows, vals, labels)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    np.testing.assert_allclose(r_wide, g_wide, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(r_emb, g_emb, rtol=1e-4, atol=1e-7)
    for (rw, rb), (gw, gb) in zip(r_tower, g_tower):
        np.testing.assert_allclose(rw, gw, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(rb, gb, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("vdim", [1, 8, 16, 64])
def test_store_rows_of_any_width_against_numpy(vdim):
    """The store holds a ``(rows, vdim)`` table as that and no other form
    (on the chip XLA keeps it unpadded: PERF.md, PR 26), so there is no
    packed layout to compare; what a packed one would have had to get right
    is checked of the one form there is, against NumPy and bit for bit: keys
    that are neighbours in one 128-lane row of a packed table, the pad row 0
    named many times with zero gradient, the last row."""
    rows = 1024
    rng = np.random.default_rng(vdim)
    idx = np.array([0, 1, 2, 3, 7, 8, 9, 127, 128, 129, 500, rows - 1, 0, 0, 0, 0], np.int32)
    grad = rng.normal(size=(len(idx), vdim)).astype(np.float32)
    grad[idx == 0] = 0.0
    up = Sgd(eta=0.5)
    state = {"w": jnp.asarray(rng.normal(size=(rows, vdim)).astype(np.float32))}
    want = np.asarray(state["w"]).copy()
    np.testing.assert_array_equal(np.asarray(store.pull(up, state, jnp.asarray(idx))), want[idx])
    new = store.push(up, state, jnp.asarray(idx), jnp.asarray(grad))
    np.add.at(want, idx, np.float32(-0.5) * (grad + np.float32(0.0) * want[idx]))
    np.testing.assert_array_equal(np.asarray(new["w"]), want)
    # AdaGrad's two slots through the same push
    ada = Adagrad(eta=0.05)
    st = {"w": jnp.asarray(want), "n": jnp.zeros((rows, vdim), jnp.float32)}
    out = store.push(ada, st, jnp.asarray(idx), jnp.asarray(grad))
    n_want = np.zeros((rows, vdim), np.float32)
    np.add.at(n_want, idx, grad * grad)
    np.testing.assert_array_equal(np.asarray(out["n"]), n_want)


PUSH_ROWS, PUSH_SLOTS = 4096, 12  # 4 / 2 / 1 whole 1024-row tiles a kv shard
PUSH_CASES = ["shard_edges", "one_shard_owns_all", "all_pads", "same_keys_from_every_worker", "unsorted_no_promise"]


def push_keys(case, workers):
    """(workers, PUSH_SLOTS) key lists under the batch contract (slot 0 the
    pad, ascending keys, pads to the end), but for the caller that makes
    no promise."""
    q = PUSH_ROWS // 4  # a kv shard's rows on 1x4; 2q on 2x2
    lists = {
        # this shard's last row and the next one's first, on kv 2 and on kv 4; the table's last
        "shard_edges": [[1, q - 1, q, q + 1, 2 * q - 1, 2 * q, 3 * q - 1, 3 * q, PUSH_ROWS - 1],
                        [2, q - 2, q, 2 * q - 1, 2 * q + 1, 3 * q, PUSH_ROWS - 2, PUSH_ROWS - 1]],
        # the last shard owns every key: the others receive slot 0 or nothing
        "one_shard_owns_all": [[3 * q, 3 * q + 1, 3 * q + 7, PUSH_ROWS - 1], [3 * q + 1, 3 * q + 2]],
        "all_pads": [[], []],  # what data.batch.inert_like hands the step
        "same_keys_from_every_worker": [[5, q, 2 * q + 3, PUSH_ROWS - 1]] * 2,
        "unsorted_no_promise": [[9, 3, 3, PUSH_ROWS - 1, q, 7, 2 * q, q - 1], [q, 9, 9, 9, 1, 3 * q]],
    }[case]
    out = np.zeros((workers, PUSH_SLOTS), np.int32)
    for w in range(workers):
        real = lists[w % 2]
        at = 0 if case == "unsorted_no_promise" else 1  # its ids are no batch's: no pad slot
        out[w, at : at + len(real)] = real
    return out


@pytest.mark.parametrize("case", PUSH_CASES)
@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("vdim", [1, 8, 16, 64, 300])
def test_mesh_push_of_any_width_against_numpy(vdim, mesh_name, case):
    """``_local_push`` over the mesh, as ``_microstep`` calls it (every
    worker's keys and gradients gathered, applied in worker order, the
    scatter promised ascending rows), against NumPy: AdaGrad's ``n`` bit
    for bit (each push applied once, to its row), ``w`` to an ulp (it
    depends on the order of two workers' pushes of one key), every row
    no key names untouched bit for bit. The caller that does not promise
    (unsorted ids, repeats among them) goes through the same scatter, told
    nothing about the order, and matches too. A slot of 300-lane rows is
    stored 384 lanes wide (``spmd.row_stride``): the gradients stay 300
    wide, the lanes past them stay zero."""
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from parameter_server_tpu.parallel import spmd

    data, kv = MESHES[mesh_name]
    mesh = make_mesh(data, kv)
    shard = spmd._shard_size(PUSH_ROWS, kv)
    rng = np.random.default_rng(vdim)
    idx = push_keys(case, data)
    grad = rng.normal(size=(data, PUSH_SLOTS, vdim)).astype(np.float32)
    grad[idx == 0] = 0.0  # a pad's gradient is zero
    ada = Adagrad(eta=0.05)
    start = {
        "w": rng.normal(size=(PUSH_ROWS, vdim)).astype(np.float32),
        "n": rng.random(size=(PUSH_ROWS, vdim)).astype(np.float32),
    }
    pad = ((0, 0), (0, spmd.row_stride(vdim) - vdim))  # the slots as stored

    def local(state_l, idx_l, grad_l):
        return spmd._local_push(
            ada, state_l, lax.all_gather(idx_l[0], "data"), lax.all_gather(grad_l[0], "data"),
            shard, ascending=case != "unsorted_no_promise", vdim=vdim,
        )

    push = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(spmd.state_spec(), P("data"), P("data")),
        out_specs=spmd.state_spec(), check_vma=False,
    ))
    state = {k: jax.device_put(np.pad(v, pad), NamedSharding(mesh, spmd.state_spec())) for k, v in start.items()}
    got = push(state, jnp.asarray(idx), jnp.asarray(grad))
    assert all(not np.asarray(v)[:, vdim:].any() for v in got.values())
    got = {k: np.asarray(v)[:, :vdim] for k, v in got.items()}

    want = {k: v.copy() for k, v in start.items()}
    for w in range(data):  # the server applies each worker's push as its own step
        g = grad[w]
        dn = g * g
        dw = np.float32(-0.05) * g / (np.sqrt(want["n"][idx[w]] + dn) + np.float32(1e-8))
        np.add.at(want["w"], idx[w], dw)
        np.add.at(want["n"], idx[w], dn)
    np.testing.assert_array_equal(np.asarray(got["n"]), want["n"])
    # XLA's division is an ulp from NumPy's here and there; a push applied in
    # the other order, twice, or to a neighbour's row is percents away
    np.testing.assert_allclose(np.asarray(got["w"]), want["w"], rtol=1e-5, atol=1e-7)
    untouched = np.ones(PUSH_ROWS, bool)
    untouched[idx.ravel()] = False
    np.testing.assert_array_equal(np.asarray(got["w"])[untouched], start["w"][untouched])


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_device_made_embedding_is_the_reference_function(seed):
    rows = np.array([0, 1, 2, 63, 64, 999, 4095, 4096, 5000, 2**31 - 1], np.int64)
    for vdim in (8, 16):
        got = np.asarray(store.hashed_uniform(seed, jnp.asarray(rows.astype(np.int32)), vdim, 0.05, 4096))
        want = ref_wd.init_embedding(seed, rows, vdim, 4096)
        np.testing.assert_array_equal(got, want)
        assert not got[0].any() and not got[rows >= 4096].any()
    big = np.asarray(store.hashed_uniform(seed, jnp.arange(1, 4096), 16, 0.05, 4096))
    assert abs(float(big.std()) - 0.05) < 2e-3 and abs(float(big.mean())) < 2e-3
    assert len(np.unique(big)) > 0.99 * big.size  # a draw per (row, lane), not a pattern


def test_trainer_makes_the_embedding_on_the_device_and_pads_with_zeros():
    cfg = make_cfg(1000, 8, 1, 4)  # 1000 rows over kv 4: no pad; 1001 would pad
    cfg.data.num_keys = 1001
    trainer = PodTrainer(cfg, mesh=make_mesh(1, 4), reporter=quiet())
    w = trainer.runtime.state_to_host(trainer.table_state("emb"))["w"]
    assert w.shape == (1004, 8)
    np.testing.assert_array_equal(w[:1001], ref_wd.init_embedding(cfg.seed, np.arange(1001), 8, 1001))
    assert not w[1001:].any()
    np.testing.assert_array_equal(trainer.full_weights("emb"), w[:1001])


def test_checkpoint_round_trip_of_tables_tower_and_optimizer(tmp_path):
    """Save after 4 microsteps, load into a fresh trainer on another mesh
    shape: every entry of the flat state (both tables' slots, the tower,
    Adam's moments and count) comes back, and 2 more microsteps from the
    loaded state equal 6 straight through."""
    rows, vals, labels, batches = make_data(1001, 6, seed=8)
    straight = PodTrainer(make_cfg(1001, 8, 1, 2, 2), mesh=make_mesh(1, 2), reporter=quiet())
    straight.train_batches(batches, report_every=10**6)
    first = PodTrainer(make_cfg(1001, 8, 1, 2, 2), mesh=make_mesh(1, 2), reporter=quiet())
    first.train_batches(batches[:4], report_every=10**6)
    first.save(tmp_path / "ck")
    resumed = PodTrainer(make_cfg(1001, 8, 1, 4, 2), mesh=make_mesh(1, 4), reporter=quiet())
    meta = resumed.load(tmp_path / "ck")
    assert meta["examples_seen"] == 4 * BATCH and resumed.examples_seen == 4 * BATCH
    assert set(resumed.state) == set(first.state)
    for k in first.state:
        a, b = np.asarray(first.state[k]), np.asarray(resumed.state[k])
        np.testing.assert_array_equal(a[:1001] if a.ndim == 2 and a.shape[0] >= 1001 else a,
                                      b[:1001] if b.ndim == 2 and b.shape[0] >= 1001 else b, err_msg=k)
    assert int(np.asarray(resumed.state["mlp_opt.0.count"])) == 4
    resumed.train_batches(batches[4:], report_every=10**6)
    for k in straight.state:
        a, b = np.asarray(straight.state[k]), np.asarray(resumed.state[k])
        if a.ndim == 2 and a.shape[0] >= 1001:
            a, b = a[:1001], b[:1001]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7, err_msg=k)
