"""Multi-step (scanned) dispatch tests: K parameter-server steps per
device call must reproduce the single-step trajectory exactly.

Reference analog: the bounded-delay pipelining of many small Push/Pull
tasks (SURVEY §2.9 SSP / §3.3 DARLIN's block pipeline) — on TPU the
pipelining moves INTO the compiled program as a lax.scan so dispatch and
host<->device round trips are paid once per K steps, not per step."""

import jax
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.kv.updaters import Ftrl, Sgd
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


def make_step_stacks(d, n_steps, seed=0, n_per=64, bucket=False):
    """n_steps stacked (D, ...) step items (host numpy, as the trainer
    builds them)."""
    labels, keys, vals, _ = make_sparse_logistic(
        d * n_steps * n_per, NUM_KEYS - 2, nnz_per_example=8, seed=seed
    )
    builder = BatchBuilder(
        num_keys=NUM_KEYS, batch_size=n_per, max_nnz_per_example=32,
        key_mode="identity", bucket_nnz=bucket,
    )
    items = []
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
        from parameter_server_tpu.data.batch import pad_group

        items.append(stack_batches(pad_group(group), None))
    return items


@pytest.mark.parametrize("mesh_shape", [(4, 2), (2, 4)])
@pytest.mark.parametrize("push_mode", ["per_worker", "aggregate", "quantized"])
def test_multistep_matches_sequential_single_steps(mesh_shape, push_mode):
    """Quantized included: microstep i of call c derives seed c*K + i, so
    feeding the single-step run seeds 0..n-1 makes the stochastic
    rounding draws — and hence the trajectory — match exactly."""
    d, k = mesh_shape
    K, n_calls = 4, 2
    up = Ftrl(alpha=0.3, lambda_l1=0.1)
    mesh = make_mesh(d, k)
    items = make_step_stacks(d, K * n_calls)

    # reference: K * n_calls sequential single-step dispatches
    step1 = make_spmd_train_step(up, mesh, NUM_KEYS, push_mode=push_mode)
    state_ref = shard_state(up.init(NUM_KEYS, 1), mesh)
    ref_losses = []
    for i, it in enumerate(items):
        state_ref, out = step1(state_ref, it, i)
        ref_losses.append(float(out["loss_sum"]))
    ref_w = np.asarray(up.weights(state_ref))

    # scanned: n_calls dispatches of K microsteps each
    stepK = make_spmd_train_multistep(up, mesh, NUM_KEYS, push_mode=push_mode)
    state = shard_state(up.init(NUM_KEYS, 1), mesh)
    got_losses = []
    for c in range(n_calls):
        group = stack_step_groups(items[c * K : (c + 1) * K])
        state, out = stepK(state, group, c * K)
        assert out["loss_sum"].shape == (K,)
        assert out["examples"].shape == (K,)
        assert out["probs"].shape[:2] == (d, K)
        got_losses.extend(float(x) for x in np.asarray(out["loss_sum"]))
    got_w = np.asarray(up.weights(state))

    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-5)
    np.testing.assert_allclose(got_w, ref_w, rtol=1e-5, atol=1e-6)


def test_multistep_pads_bucketed_groups():
    """Bucketed items of different (nnz, U) shapes stack into one group at
    the group max; padding stays inert (same final state as unbucketed)."""
    d, K = 2, 3
    up = Sgd(eta=0.2)
    mesh = make_mesh(d, 2)
    plain = make_step_stacks(d, K, seed=5)
    bucketed = make_step_stacks(d, K, seed=5, bucket=True)
    stepK = make_spmd_train_multistep(up, mesh, NUM_KEYS)

    out_w = []
    for items in (plain, bucketed):
        state = shard_state(up.init(NUM_KEYS, 1), mesh)
        state, _ = stepK(state, stack_step_groups(items))
        out_w.append(np.asarray(up.weights(state)))
    np.testing.assert_allclose(out_w[0], out_w[1], rtol=1e-6, atol=1e-7)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("multistep")
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


def make_cfg(steps_per_call=1, max_delay=0, pipeline_depth=0):
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 12
    # depth 0 = serial ingest; the stream->file assignment is static, so
    # the item sequence (and hence the trajectory) is deterministic at
    # ANY depth — threaded runs must reproduce serial ones exactly
    cfg.data.pipeline_depth = pipeline_depth
    cfg.solver.minibatch = 128
    cfg.solver.epochs = 1
    cfg.solver.max_delay = max_delay
    cfg.solver.steps_per_call = steps_per_call
    cfg.penalty.lambda_l1 = 0.05
    cfg.parallel.data_shards = 4
    cfg.parallel.kv_shards = 2
    return cfg


class TestPodTrainerMultistep:
    def test_same_weights_as_single_step(self, files):
        """steps_per_call=3 (stream length NOT divisible by 3: the tail
        group pads with inert empties) reproduces the K=1 run exactly —
        both with serial ingest and with the threaded pipeline doing the
        group assembly on its stacker thread."""
        runs = {}
        for name, cfg in (
            ("k1", make_cfg(steps_per_call=1)),
            ("k3", make_cfg(steps_per_call=3)),
            ("k3_piped", make_cfg(steps_per_call=3, pipeline_depth=2)),
        ):
            t = PodTrainer(cfg, reporter=quiet())
            last = t.train_files(files, key_mode="identity", report_every=100)
            runs[name] = (t.full_weights(), t.examples_seen, last)
        for other in ("k3", "k3_piped"):
            np.testing.assert_allclose(
                runs["k1"][0], runs[other][0], rtol=1e-5, atol=1e-6
            )
            assert runs[other][1] == 3600
            # the merged progress reports agree too (same windows, order)
            assert runs[other][2]["auc"] == pytest.approx(
                runs["k1"][2]["auc"], abs=1e-6
            )
            assert runs[other][2]["objv"] == pytest.approx(
                runs["k1"][2]["objv"], rel=1e-5
            )

class TestWideDeepMultistep:
    def _batches(self, n_batches=7, n_per=64):
        labels, keys, vals, _ = make_sparse_logistic(
            n_batches * n_per, 60, nnz_per_example=6, noise=0.3, seed=9
        )
        builder = BatchBuilder(
            num_keys=64, batch_size=n_per, max_nnz_per_example=16,
            key_mode="identity",
        )
        return [
            builder.build(
                labels[i : i + n_per], keys[i : i + n_per], vals[i : i + n_per]
            )
            for i in range(0, n_batches * n_per, n_per)
        ]

    def test_wd_multistep_matches_single_step(self):
        """steps_per_call=3 over 7 batches (tail group padded with inert
        microsteps, which must not advance Adam's moment decay) reproduces
        the K=1 trajectory exactly."""
        from parameter_server_tpu.models.wide_deep import WideDeep

        batches = self._batches()
        outs = []
        for k in (1, 3):
            wd = WideDeep(
                num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3, seed=0,
                reporter=quiet(), steps_per_call=k,
            )
            last = wd.train(batches, report_every=100)
            p, y = wd.predict(batches[:2])
            outs.append((last, p))
        assert outs[0][0]["objv"] == pytest.approx(outs[1][0]["objv"], rel=1e-5)
        np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-4, atol=1e-6)
        assert outs[0][0]["auc"] == pytest.approx(outs[1][0]["auc"], abs=1e-6)

    def test_wd_spmd_multistep_matches_single_step(self):
        """The mesh multistep program matches K-1 sequential mesh steps
        when the K-th microstep is all-inert (the padded-tail case): the
        pod-wide activity gate must keep Adam's moments AND count frozen
        on the pad, or mlp/opt state silently diverges."""
        from parameter_server_tpu.data.batch import inert_like as _inert_like
        from parameter_server_tpu.models.wide_deep import (
            WideDeep,
            make_wd_spmd_train_step,
            make_wd_spmd_train_multistep,
        )
        from parameter_server_tpu.parallel.spmd import shard_state

        d, K = 2, 3
        mesh = make_mesh(d, 2)
        batches = self._batches(n_batches=d * (K - 1))
        groups = [
            stack_batches(batches[s * d : (s + 1) * d], None)
            for s in range(K - 1)
        ]
        inert = stack_batches([_inert_like(batches[0]) for _ in range(d)], None)

        outs = []
        for multi in (False, True):
            app = WideDeep(
                num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3, seed=0,
                reporter=quiet(),
            )
            wide = shard_state(app.wide_state, mesh)
            emb = shard_state(app.emb_state, mesh)
            mlp, opt_state = app.mlp_params, app.opt_state
            if multi:
                stepK = make_wd_spmd_train_multistep(
                    app.wide_up, app.emb_up, app.opt, mesh, 64
                )
                grouped = stack_step_groups(groups + [inert])
                wide, emb, mlp, opt_state, losses, probs = stepK(
                    wide, emb, mlp, opt_state, grouped
                )
                losses = [float(x) for x in np.asarray(losses)]
                assert losses[-1] == 0.0  # the inert microstep
                losses = losses[:-1]
                assert probs.shape[:2] == (d, K)
            else:
                step1 = make_wd_spmd_train_step(
                    app.wide_up, app.emb_up, app.opt, mesh, 64
                )
                losses = []
                for g in groups:
                    wide, emb, mlp, opt_state, loss, _ = step1(
                        wide, emb, mlp, opt_state, g
                    )
                    losses.append(float(loss))
            outs.append(
                (
                    losses,
                    np.asarray(app.wide_up.weights(wide)),
                    jax.tree.leaves((mlp, opt_state)),
                )
            )
        np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-5)
        np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-4, atol=1e-6)
        # MLP params and full Adam state (count included) agree leaf-wise
        for a, b in zip(outs[0][2], outs[1][2]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )


class TestPodTrainerMultistepOverlap:
    @pytest.mark.parametrize("max_delay", [0, 2])
    def test_multistep_with_dispatch_overlap(self, files, max_delay):
        """K > 1 composes with SSP run-ahead (gate counts device calls)."""
        cfg = make_cfg(steps_per_call=2, max_delay=max_delay)
        cfg.solver.epochs = 2
        t = PodTrainer(cfg, reporter=quiet())
        last = t.train_files(files, key_mode="identity", report_every=3)
        assert last["auc"] > 0.75
        assert t.examples_seen == 2 * 3600
