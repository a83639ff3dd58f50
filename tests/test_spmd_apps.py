"""SPMD tests for the embedding apps (Wide&Deep, SGNS) on the CPU mesh:
server-sharded embedding tables over the kv axis, batches over data."""

import jax
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.models.wide_deep import WideDeep, make_wd_spmd_train_step
from parameter_server_tpu.parallel import make_mesh, shard_state, stack_batches
from parameter_server_tpu.utils.metrics import ProgressReporter


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


class TestWideDeepSPMD:
    def _xor_batches(self, builder, n=2048, bs=256, seed=0):
        rng = np.random.default_rng(seed)
        a, b = rng.integers(0, 2, n), rng.integers(0, 2, n)
        y = (a ^ b).astype(np.float32)
        keys = [np.array([ai, 2 + bi], dtype=np.uint64) for ai, bi in zip(a, b)]
        vals = [np.ones(2, dtype=np.float32)] * n
        return [
            builder.build(y[i : i + bs], keys[i : i + bs], vals[i : i + bs])
            for i in range(0, n, bs)
        ], y

    @pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
    def test_learns_xor_on_mesh(self, mesh_shape):
        d, k = mesh_shape
        mesh = make_mesh(d, k)
        app = WideDeep(num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3,
                       reporter=quiet())
        step = make_wd_spmd_train_step(
            app.wide_up, app.emb_up, app.opt, mesh, app.num_keys
        )
        builder = BatchBuilder(num_keys=64, batch_size=256, key_mode="identity")
        batches, _ = self._xor_batches(builder)
        wide = shard_state(app.wide_state, mesh)
        emb = shard_state(app.emb_state, mesh)
        mlp, opt_state = app.mlp_params, app.opt_state
        losses = []
        for epoch in range(40):
            for s in range(0, len(batches) - d + 1, d):
                stacked = stack_batches(batches[s : s + d], mesh)
                wide, emb, mlp, opt_state, loss, probs = step(
                    wide, emb, mlp, opt_state, stacked
                )
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.3, losses[::8]
        # push the trained sharded state back into the app and evaluate
        app.wide_state = {k2: jax.device_get(v) for k2, v in wide.items()}
        app.emb_state = {k2: jax.device_get(v) for k2, v in emb.items()}
        app.wide_state = {k2: jax.numpy.asarray(v) for k2, v in app.wide_state.items()}
        app.emb_state = {k2: jax.numpy.asarray(v) for k2, v in app.emb_state.items()}
        app.mlp_params = mlp
        ev = app.evaluate(batches)
        assert ev["auc"] > 0.9, ev


class TestWideDeepAggregatePush:
    def test_learns_xor_aggregate(self):
        mesh = make_mesh(2, 2)
        app = WideDeep(num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3,
                       reporter=quiet())
        step = make_wd_spmd_train_step(
            app.wide_up, app.emb_up, app.opt, mesh, app.num_keys,
            push_mode="aggregate",
        )
        builder = BatchBuilder(num_keys=64, batch_size=256, key_mode="identity")
        batches, _ = TestWideDeepSPMD()._xor_batches(builder)
        wide = shard_state(app.wide_state, mesh)
        emb = shard_state(app.emb_state, mesh)
        mlp, opt_state = app.mlp_params, app.opt_state
        losses = []
        for epoch in range(40):
            for s in range(0, len(batches) - 1, 2):
                stacked = stack_batches(batches[s : s + 2], mesh)
                wide, emb, mlp, opt_state, loss, _ = step(
                    wide, emb, mlp, opt_state, stacked
                )
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.3, losses[::8]


class TestWideDeepQuantizedPush:
    def test_quantized_tracks_per_worker_on_xor(self):
        """int8 stochastic-rounding push on BOTH W&D tables (the embedding
        push is the app's dominant traffic): the quantized trajectory must
        reach the same XOR solution as per_worker — convergence parity,
        not bitwise equality (the rounding noise is real). Runs through
        the WideDeep app itself so the per-call seed threading and the
        scanned per-microstep seed fold (steps_per_call=2) are what's
        under test, not a hand-driven step."""
        mesh = make_mesh(2, 2)
        builder = BatchBuilder(num_keys=64, batch_size=256, key_mode="identity")
        batches, _ = TestWideDeepSPMD()._xor_batches(builder)
        aucs = {}
        for mode in ("per_worker", "quantized"):
            app = WideDeep(num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3,
                           reporter=quiet(), mesh=mesh, push_mode=mode,
                           steps_per_call=2)
            for _ in range(40):
                app.train(batches, report_every=10**6)
            aucs[mode] = app.evaluate(batches)["auc"]
        assert aucs["quantized"] > 0.9, aucs
        assert abs(aucs["quantized"] - aucs["per_worker"]) < 0.05, aucs

    def test_quantized_seed_advances_per_call(self):
        """Two dispatches must not reuse one PRNG stream: the app's base
        seed advances by K per device call (a silently-frozen seed would
        correlate the rounding noise across steps instead of averaging
        it out): the trainer's count of trained calls, times K, is the
        seed the shared step is handed."""
        mesh = make_mesh(2, 2)
        app = WideDeep(num_keys=64, emb_dim=8, hidden=[16], reporter=quiet(),
                       mesh=mesh, push_mode="quantized", steps_per_call=2)
        builder = BatchBuilder(num_keys=64, batch_size=256, key_mode="identity")
        batches, _ = TestWideDeepSPMD()._xor_batches(builder, n=1024)
        app.train(batches, report_every=10**6)
        assert app.trainer.calls_trained == len(batches) // (2 * 2)


class TestWideDeepQuantizedFromConfig:
    def test_factory_accepts_quantized_and_trains(self, tmp_path):
        """The config path (TOML [parallel] push_mode = quantized ->
        WideDeep.from_config) must construct AND train — the factory
        used to raise on this schema-valid value."""
        from parameter_server_tpu.utils.config import load_config

        cfg_p = tmp_path / "wd.toml"
        cfg_p.write_text(
            '[data]\nnum_keys = 64\n'
            '[wd]\nemb_dim = 8\nhidden = [16]\n'
            '[solver]\nsteps_per_call = 2\n'
            '[parallel]\npush_mode = "quantized"\n'
        )
        cfg = load_config(cfg_p)
        mesh = make_mesh(2, 2)
        app = WideDeep.from_config(cfg, mesh=mesh, reporter=quiet())
        builder = BatchBuilder(num_keys=64, batch_size=256, key_mode="identity")
        batches, _ = TestWideDeepSPMD()._xor_batches(builder, n=1024)
        app.train(batches, report_every=10**6)
        assert app.push_mode == "quantized"
        assert app.trainer.calls_trained == len(batches) // (2 * 2)
