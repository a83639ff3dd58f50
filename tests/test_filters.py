"""Filter tests: fixed-point codec round trips + unbiasedness, count-min
sketch admission, heartbeats, traffic accounting.

Reference test analog: filter encode/decode round-trip tests with
fixed-point error bounds."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.filters import CountMinSketch, FixedPointCodec
from parameter_server_tpu.parallel.traffic import (
    linear_step_traffic,
    quantization_savings,
)
from parameter_server_tpu.utils.heartbeat import (
    HeartbeatMonitor,
    HeartbeatReporter,
    host_stats,
)


class TestFixedPointCodec:
    @pytest.mark.parametrize("nbytes", [1, 2])
    def test_roundtrip_error_bound(self, nbytes, rng):
        codec = FixedPointCodec(num_bytes=nbytes)
        x = jnp.asarray(rng.normal(size=1000).astype(np.float32)) * 5
        enc = codec.encode(jax.random.key(0), x)
        dec = codec.decode(enc)
        levels = (1 << (8 * nbytes)) - 1
        max_err = float(jnp.max(jnp.abs(x)) * 2 - (-jnp.max(jnp.abs(x)) * 2))
        step = float(enc.scale)
        assert float(jnp.max(jnp.abs(dec - x))) <= step + 1e-6

    def test_stochastic_rounding_unbiased(self):
        codec = FixedPointCodec(num_bytes=1)
        x = jnp.full((2000,), 0.3)  # sits strictly between two levels
        decs = []
        for i in range(50):
            e = codec.encode(jax.random.key(i), jnp.concatenate([x, jnp.array([0.0, 1.0])]))
            decs.append(float(codec.decode(e)[:2000].mean()))
        assert abs(np.mean(decs) - 0.3) < 2e-3, np.mean(decs)

    def test_payload_dtype(self):
        codec = FixedPointCodec(num_bytes=2)
        e = codec.encode(jax.random.key(0), jnp.arange(8.0))
        assert e.q.dtype == jnp.int16
        assert codec.bytes_saved(jnp.arange(8.0)) == 0.5

    def test_constant_array(self):
        codec = FixedPointCodec()
        x = jnp.full((16,), 3.5)
        dec = codec.decode(codec.encode(jax.random.key(0), x))
        np.testing.assert_allclose(np.asarray(dec), 3.5, atol=1e-6)

    def test_bad_bytes(self):
        with pytest.raises(ValueError):
            FixedPointCodec(num_bytes=4)


class TestCountMinSketch:
    def test_counts_never_underestimate(self, rng):
        cms = CountMinSketch(width=1 << 12, depth=4)
        keys = rng.integers(0, 2**62, 500, dtype=np.uint64)
        reps = rng.integers(1, 10, 500)
        all_keys = np.repeat(keys, reps)
        cms.add(all_keys)
        est = cms.count(keys)
        assert (est >= reps).all()
        # with this load factor, estimates should mostly be exact
        assert (est == reps).mean() > 0.95

    def test_admission_threshold(self):
        cms = CountMinSketch(width=1 << 10, depth=2)
        hot = np.full(10, 7, dtype=np.uint64)
        cms.add(hot)
        cms.add(np.array([123], dtype=np.uint64))
        mask = cms.admit(np.array([7, 123, 999], dtype=np.uint64), min_count=5)
        assert mask.tolist() == [True, False, False]

    def test_state_roundtrip(self):
        cms = CountMinSketch(width=64, depth=2)
        cms.add(np.array([5, 5], dtype=np.uint64))
        cms2 = CountMinSketch(width=64, depth=2)
        cms2.load_state_dict(cms.state_dict())
        assert cms2.count(np.array([5], dtype=np.uint64))[0] >= 2
        bad = CountMinSketch(width=32, depth=2)
        with pytest.raises(ValueError):
            bad.load_state_dict(cms.state_dict())


class TestHeartbeat:
    def test_alive_dead_transitions(self):
        mon = HeartbeatMonitor(timeout_s=0.05)
        mon.beat(0, host_stats())
        mon.beat(1)
        assert mon.alive() == [0, 1] and mon.dead() == []
        time.sleep(0.08)
        mon.beat(1)
        assert mon.alive() == [1]
        assert mon.dead() == [0]

    def test_reporter_thread(self):
        mon = HeartbeatMonitor(timeout_s=5.0)
        rep = HeartbeatReporter(mon, node_id=3, interval_s=0.01).start()
        time.sleep(0.05)
        rep.stop()
        assert mon.alive() == [3]
        assert "node" in mon.dashboard()

    def test_host_stats_fields(self):
        s = host_stats()
        assert "pid" in s and s.get("max_rss_mb", 1) > 0


class TestTraffic:
    def test_single_device_moves_nothing(self):
        t = linear_step_traffic(1024, 1, data_shards=1, kv_shards=1)
        assert t.total_bytes == 0

    def test_scaling_shapes(self):
        t = linear_step_traffic(1 << 16, 1, data_shards=4, kv_shards=8)
        assert t.pull_bytes > 0 and t.push_bytes > 0
        t2 = linear_step_traffic(1 << 16, 1, data_shards=8, kv_shards=8)
        assert t2.push_bytes > t.push_bytes

    def test_quantization_savings(self):
        assert quantization_savings(1) == 0.75
        assert quantization_savings(2) == 0.5


class TestFrequencyFilterIngest:
    """The admission path (ref: frequency_filter.h wired into ingest):
    keys below the count threshold never enter batches."""

    def test_streaming_admission_across_batches(self):
        from parameter_server_tpu.data.batch import BatchBuilder

        builder = BatchBuilder(
            num_keys=1 << 12, batch_size=4, key_mode="identity",
            freq_min_count=2,
        )
        keys = [np.array([7, 8], dtype=np.uint64)]
        vals = [np.ones(2, dtype=np.float32)]
        b1 = builder.build(np.ones(1, dtype=np.float32), keys, vals)
        assert b1.num_entries == 0  # first sighting: below threshold
        b2 = builder.build(np.ones(1, dtype=np.float32), keys, vals)
        assert b2.num_entries == 2  # second sighting reaches the count

    def test_within_batch_repeats_admit(self):
        from parameter_server_tpu.data.batch import BatchBuilder

        builder = BatchBuilder(
            num_keys=1 << 12, batch_size=4, key_mode="identity",
            freq_min_count=2,
        )
        # key 5 twice in one batch -> counted to 2 before admission
        b = builder.build(
            np.ones(2, dtype=np.float32),
            [np.array([5], dtype=np.uint64), np.array([5], dtype=np.uint64)],
            [np.ones(1, dtype=np.float32)] * 2,
        )
        assert b.num_entries == 2

    def test_tail_gets_no_weight_auc_preserved(self):
        """Heavy-tail synthetic: signal lives in 40 head keys; every example
        also carries a unique tail key (pure noise). With admission, tail
        rows must stay exactly zero and AUC must not degrade."""
        from parameter_server_tpu.data.synthetic import make_sparse_logistic
        from parameter_server_tpu.models.linear import LinearMethod
        from parameter_server_tpu.utils.config import PSConfig

        n_all, n, n_head = 3600, 3000, 40
        labels, keys, vals, _ = make_sparse_logistic(
            n_all, n_head, nnz_per_example=6, noise=0.3, seed=3
        )
        keys = [
            np.concatenate([k, [np.uint64(n_head + 2 + i)]]).astype(np.uint64)
            for i, k in enumerate(keys)
        ]
        vals = [np.concatenate([v, [1.0]]).astype(np.float32) for v in vals]

        def run(min_count):
            cfg = PSConfig()
            cfg.data.num_keys = 1 << 13
            cfg.solver.minibatch = 256
            cfg.solver.algo = "ftrl"
            cfg.penalty.lambda_l1 = 0.001
            cfg.data.freq_min_count = min_count
            app = LinearMethod(cfg)
            builder = app.make_builder("identity")
            for ep in range(3):
                batches = [
                    builder.build(
                        labels[s : s + 256], keys[s : s + 256], vals[s : s + 256]
                    )
                    for s in range(0, n, 256)
                ]
                app.train(batches, report_every=10**9)
            w = np.asarray(app.store.weights())[:, 0]
            # held-out eval through an UNFILTERED builder (eval sees every
            # key; unadmitted ones carry zero weight anyway)
            from parameter_server_tpu.data.batch import eval_builder

            ev_builder = eval_builder(cfg, "identity")
            ev = app.evaluate(
                ev_builder.build(
                    labels[s : s + 200], keys[s : s + 200], vals[s : s + 200]
                )
                for s in range(n, n_all, 200)
            )
            return w, ev["auc"]

        # 3 epochs give every tail key a streaming count of 3; the
        # threshold must exceed that to keep them out for the whole run
        w_filt, auc_filt = run(min_count=5)
        w_raw, auc_raw = run(min_count=0)
        tail_rows = np.arange(n_head + 2, n_head + 2 + n) + 1  # identity +1
        assert np.all(w_filt[tail_rows] == 0.0), "tail keys got weight"
        assert np.count_nonzero(w_raw[tail_rows]) > 0  # unfiltered does
        assert auc_filt > auc_raw - 0.02, (auc_filt, auc_raw)
