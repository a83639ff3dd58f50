"""End-to-end quantized push/pull wire with error feedback (fast tier-1).

Covers the ISSUE 6 tentpole: the per-segment-scale int8/int16 codec
(filters/quant.py — symmetric zero, stochastic rounding, numpy/jax
parity), per-connection "qwire" feature negotiation (a quantized client
against a non-quant server degrades to the float path), client-side
error-feedback accumulators whose folds happen exactly once per LOGICAL
push however chaotic the transport (drop/disconnect/duplicate with W>1
in flight), the quantized pull path, the >=3x push wire-bytes reduction,
and convergence parity of a quantized training run.

The load-bearing identity used throughout: with SGD(eta=1) the server
weight is w = -sum(decoded pushes), and error feedback telescopes
``sum(decoded) = sum(grads) - residual_final`` — so
``w == -(sum(grads) - residual)`` holds EXACTLY iff every logical push
folded and applied exactly once. A double-fold or double-apply breaks it
by a quantization step, far above float tolerance.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

from parameter_server_tpu.filters.quant import (
    SegmentQuantizer,
    dequantize_segments,
    quantize_segments,
)
from parameter_server_tpu.kv.updaters import Sgd
from parameter_server_tpu.parallel.chaos import FaultPlan
from parameter_server_tpu.parallel.multislice import ServerHandle, ShardServer
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.keyrange import KeyRange
from parameter_server_tpu.utils.metrics import wire_counters


@pytest.fixture(autouse=True)
def _fresh_counters():
    wire_counters.reset()
    yield
    wire_counters.reset()


class TestSegmentQuantizer:
    def test_roundtrip_error_bounded_by_segment_scale(self, rng):
        qz = SegmentQuantizer(1, 64)
        x = (rng.normal(size=1000) * 0.01).astype(np.float32)
        q, qs = qz.encode(3, x)
        assert q.dtype == np.int8 and q.shape == (1000,)
        assert qs.shape == (16,) and qs.dtype == np.float32
        dec = qz.decode(q, qs)
        # per-segment: each coordinate's error is bounded by ITS segment's
        # step, not the whole array's
        for s in range(15):
            seg = slice(64 * s, 64 * (s + 1))
            assert np.abs(dec[seg] - x[seg]).max() <= qs[s] + 1e-12

    def test_int16(self, rng):
        qz = SegmentQuantizer(2, 256)
        x = rng.normal(size=500).astype(np.float32)
        q, qs = qz.encode(1, x)
        assert q.dtype == np.int16
        assert np.abs(qz.decode(q, qs) - x).max() <= qs.max() + 1e-12

    def test_zero_maps_to_exact_zero(self):
        """The store's pad-row invariant (zero grad => zero update) must
        survive quantization bit-exactly: symmetric scaling guarantees
        it, the old affine fixed-point codec did not."""
        qz = SegmentQuantizer(1, 128)
        q, qs = qz.encode(9, np.zeros(300, np.float32))
        assert not q.any()
        assert not qz.decode(q, qs).any()
        # zeros embedded in a nonzero array stay exactly zero too
        x = np.zeros(256, np.float32)
        x[7] = 1.0
        q, qs = qz.encode(4, x)
        assert qz.decode(q, qs)[8:100].max() == 0.0

    def test_stochastic_rounding_is_unbiased(self, rng):
        qz = SegmentQuantizer(1, 256)
        x = (rng.normal(size=256) * 0.05).astype(np.float32)
        acc = np.zeros_like(x)
        n = 300
        for s in range(n):
            q, qs = qz.encode(s, x)
            acc += qz.decode(q, qs)
        step = qs.max()
        # mean of n unbiased draws concentrates ~ step/sqrt(n)
        assert np.abs(acc / n - x).max() < 5 * step / np.sqrt(n)

    def test_outlier_does_not_destroy_other_segments(self, rng):
        """The whole point of per-segment scales: one huge coordinate
        only coarsens ITS segment."""
        qz = SegmentQuantizer(1, 64)
        x = (rng.normal(size=256) * 0.01).astype(np.float32)
        x[0] = 1000.0
        q, qs = qz.encode(5, x)
        dec = qz.decode(q, qs)
        assert np.abs(dec[64:] - x[64:]).max() < 0.01  # fine segments fine

    def test_jax_parity(self, rng):
        import jax

        x = (rng.normal(size=512) * 0.1).astype(np.float32)
        qj, sj = quantize_segments(jax.random.key(0), x, num_bytes=1, seg=256)
        dj = np.asarray(dequantize_segments(qj, sj, num_bytes=1, seg=256))
        assert np.asarray(qj).dtype == np.int8
        assert np.abs(dj - x).max() <= np.asarray(sj).max() + 1e-12

    def test_wire_bytes_ratio(self):
        # int8 + one f32 scale per 256 coords: >= 3.7x under float32
        qz = SegmentQuantizer(1, 256)
        n = 1 << 16
        assert 4 * n / qz.wire_bytes(n) > 3.7

    def test_rejects_bad_geometry(self):
        with pytest.raises(ValueError):
            SegmentQuantizer(3)
        with pytest.raises(ValueError):
            SegmentQuantizer(1, 0)

    def test_encode_nearest_is_deterministic_and_tighter(self, rng):
        """The pull-side form: no seed, bit-identical across calls, and
        worst-case error half a quantization step (vs a full step for
        the stochastic encode)."""
        qz = SegmentQuantizer(1, 128)
        x = (rng.normal(size=700) * 0.2).astype(np.float32)
        q1, s1 = qz.encode_nearest(x)
        q2, s2 = qz.encode_nearest(x)
        np.testing.assert_array_equal(q1, q2)
        np.testing.assert_array_equal(s1, s2)
        dec = qz.decode(q1, s1)
        for seg in range(5):
            sl = slice(128 * seg, 128 * (seg + 1))
            assert np.abs(dec[sl] - x[sl]).max() <= s1[seg] / 2 + 1e-12


def _server_and_handle(
    quant="int8", server_quant=True, fault_plan=None, quant_pull=False,
    range_size=2048, window=4,
):
    srv = ShardServer(
        Sgd(eta=1.0), KeyRange(0, range_size), fault_plan=fault_plan
    )
    if not server_quant:
        # simulate an old (pre-quant) server: it never acks "qwire"
        srv.server._features = frozenset()
    srv.start()
    cfg = PSConfig()
    cfg.wire.quant = quant
    cfg.wire.quant_pull = quant_pull
    cfg.wire.window = window
    handle = ServerHandle(srv.address, 0, 0, cfg, range_size=range_size)
    return srv, handle


def _expected_weights(handle, keys, total):
    """-(sum grads - residual at keys): exact iff exactly-once (see
    module docstring)."""
    return -(total - handle.residual_rows(keys).ravel())


class TestQuantNegotiation:
    def test_first_push_floats_then_quant_engages(self):
        srv, handle = _server_and_handle()
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            assert handle.client.peer_features == frozenset()
            handle.push(keys, np.full(256, 0.5, np.float32))
            # the first push's reply acked the advert
            assert "qwire" in handle.client.peer_features
            handle.push(keys, np.full(256, 0.5, np.float32))
            assert wire_counters.get("wire_quant_bytes_saved") > 0
        finally:
            handle.shutdown()
            handle.close()

    def test_config_rejects_unknown_mode(self):
        cfg = PSConfig()
        cfg.wire.quant = "int4"
        with pytest.raises(ValueError, match="quant"):
            ServerHandle("127.0.0.1:1", 0, 0, cfg)


class TestQuantExactlyOnceUnderChaos:
    @pytest.mark.parametrize(
        "spec",
        ["drop,every=3", "disconnect,every=3", "duplicate,every=2"],
    )
    def test_residuals_never_double_fold(self, spec, rng):
        """Chaos on a quantized window: transport resends reuse the
        already-encoded payload and the server dedups, so the
        telescoping identity holds exactly — a double-fold (client) or
        double-apply (server) would break it by a quantization step."""
        srv, handle = _server_and_handle(
            fault_plan=FaultPlan.parse(spec, seed=11)
        )
        try:
            keys = np.arange(1, 513, dtype=np.int64)
            # one round trip first: its reply acks "qwire", so the window
            # below starts quantized. Without it every push could be
            # encoded while a healed connection was still un-acked (each
            # reconnect renegotiates), all 16 rode floats, and the last
            # assert failed one loaded run in a few (ROADMAP D13)
            handle.pull(keys)
            total = np.zeros(512, np.float64)
            futs = []
            for i in range(16):
                g = (rng.normal(size=512) * 0.1).astype(np.float32)
                total += g
                futs.append(handle.push_async(keys, g))
            for f in futs:
                f.result(timeout=60)
            w = handle.pull(keys).astype(np.float64)
            exp = _expected_weights(handle, keys, total)
            np.testing.assert_allclose(w, exp, atol=1e-5)
            # quant actually engaged
            assert srv.counters["pushes"] == 16
            assert wire_counters.get("wire_quant_bytes_saved") > 0
            if spec.startswith(("disconnect", "drop")):
                assert wire_counters.get("rpc_reconnects") >= 1
        finally:
            handle.shutdown()
            handle.close()

    def test_mixed_chaos_soak(self, rng):
        plan = FaultPlan.parse(
            "drop,prob=0.05;disconnect,prob=0.05;duplicate,prob=0.05",
            seed=321,
        )
        srv, handle = _server_and_handle(fault_plan=plan, window=8)
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            total = np.zeros(256, np.float64)
            futs = []
            for i in range(40):
                g = (rng.normal(size=256) * 0.05).astype(np.float32)
                total += g
                futs.append(handle.push_async(keys, g))
            for f in futs:
                f.result(timeout=60)
            w = handle.pull(keys).astype(np.float64)
            np.testing.assert_allclose(
                w, _expected_weights(handle, keys, total), atol=1e-5
            )
            stats = srv.server.fault_stats()
            assert sum(v for k, v in stats.items() if k != "frames") >= 3
        finally:
            handle.shutdown()
            handle.close()


class TestMixedClusterFallback:
    @pytest.mark.parametrize(
        "spec", [None, "disconnect,every=3", "duplicate,every=2"]
    )
    def test_quant_client_against_old_server(self, spec, rng):
        """Acceptance: a quantized client against a non-quant server
        negotiates down to the float path with exactly-once semantics
        intact — results bit-match the float protocol, no residual ever
        accumulates, and no quantized payload reaches the wire."""
        plan = FaultPlan.parse(spec, seed=5) if spec else None
        srv, handle = _server_and_handle(server_quant=False, fault_plan=plan)
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            total = np.zeros(256, np.float64)
            futs = []
            for i in range(12):
                g = (rng.normal(size=256) * 0.1).astype(np.float32)
                total += g
                futs.append(handle.push_async(keys, g))
            for f in futs:
                f.result(timeout=60)
            w = handle.pull(keys).astype(np.float64)
            np.testing.assert_allclose(w, -total, atol=1e-5)  # float-exact
            assert handle.client.peer_features == frozenset()
            assert handle.residual_norm() == 0.0
            assert wire_counters.get("wire_quant_bytes_saved") == 0
            assert srv.counters["pushes"] == 12  # exactly once
        finally:
            handle.shutdown()
            handle.close()


class TestQuantPull:
    def test_quantized_pull_roundtrip(self):
        srv, handle = _server_and_handle(quant="int16", quant_pull=True)
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            g = np.linspace(-1.0, 1.0, 256).astype(np.float32)
            handle.push(keys, g)  # pre-negotiation: float, exact
            w = handle.pull(keys)
            # int16 per-segment: error bounded by ~|w|max/32767 per segment
            assert np.abs(w + g).max() < 4.0 / 32767
            assert w.dtype == np.float32
        finally:
            handle.shutdown()
            handle.close()

    def test_quantized_pull_is_deterministic_per_snapshot(self):
        """Nearest rounding server-side: two pulls of one unchanged RCU
        snapshot must be bit-identical (serving caches/diffs depend on
        it)."""
        srv, handle = _server_and_handle(quant="int8", quant_pull=True)
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            handle.push(keys, np.linspace(-1, 1, 256).astype(np.float32))
            w1 = handle.pull(keys)
            w2 = handle.pull(keys)
            np.testing.assert_array_equal(w1, w2)
        finally:
            handle.shutdown()
            handle.close()

    def test_quant_pull_async(self):
        srv, handle = _server_and_handle(quant="int8", quant_pull=True)
        try:
            keys = np.arange(1, 129, dtype=np.int64)
            handle.push(keys, np.full(128, 2.0, np.float32))
            w = handle.pull_async(keys).result(timeout=30)
            assert np.abs(w + 2.0).max() < 2 * 2.0 / 127
        finally:
            handle.shutdown()
            handle.close()

    def test_pull_against_old_server_stays_float(self):
        srv, handle = _server_and_handle(
            quant="int8", quant_pull=True, server_quant=False
        )
        try:
            keys = np.arange(1, 65, dtype=np.int64)
            handle.push(keys, np.full(64, 1.0, np.float32))
            w = handle.pull(keys)
            np.testing.assert_allclose(w, -1.0, atol=1e-6)  # exact floats
        finally:
            handle.shutdown()
            handle.close()


class TestWireBytesReduction:
    def _payload_bytes(self, quant: str, pushes: int = 8, n: int = 4096):
        srv, handle = _server_and_handle(quant=quant)
        try:
            keys = np.arange(1, n + 1, dtype=np.int64)
            rng = np.random.default_rng(7)
            handle.push(keys, np.zeros(n, np.float32))  # negotiate first
            wire_counters.reset()
            for _ in range(pushes):
                handle.push(
                    keys, (rng.normal(size=n) * 0.1).astype(np.float32)
                )
            return wire_counters.get("wire_push_payload_bytes")
        finally:
            handle.shutdown()
            handle.close()

    def test_int8_payload_is_3x_smaller(self):
        """The tentpole acceptance number on the wire's own counter:
        >= 3x push payload reduction at int8 vs the float path."""
        f32 = self._payload_bytes("off")
        q8 = self._payload_bytes("int8")
        assert f32 / q8 >= 3.0, (f32, q8)


class TestConvergenceParity:
    def _train_auc(self, quant: str) -> float:
        """Tiny logistic-regression run over the wire tier; AUC on the
        training stream's second half (seed-pinned, both arms identical
        except the wire codec)."""
        from parameter_server_tpu.kv.updaters import Ftrl
        from parameter_server_tpu.models import metrics as M

        rng = np.random.default_rng(42)
        n_keys, nnz, n_batches, bsz = 256, 16, 48, 512
        w_true = rng.normal(size=n_keys) * 1.5
        srv = ShardServer(
            Ftrl(alpha=1.0, beta=1.0, lambda_l1=0.001),
            KeyRange(0, n_keys + 1),
        ).start()
        cfg = PSConfig()
        cfg.wire.quant = quant
        handle = ServerHandle(srv.address, 0, 0, cfg, range_size=n_keys + 1)
        try:
            ys, ps = [], []
            for b in range(n_batches):
                kb = rng.integers(0, n_keys, size=(bsz, nnz))
                logits = w_true[kb].sum(axis=1) / np.sqrt(nnz)
                y = (rng.random(bsz) < 1 / (1 + np.exp(-logits))).astype(
                    np.float64
                )
                uniq, inv = np.unique(kb, return_inverse=True)
                keys = (uniq + 1).astype(np.int64)  # row 0 is the pad row
                w = handle.pull(keys).astype(np.float64)
                logit_hat = w[inv.reshape(bsz, nnz)].sum(axis=1)
                p = 1 / (1 + np.exp(-logit_hat))
                err = p - y
                g = np.zeros(len(uniq))
                np.add.at(g, inv.reshape(bsz, nnz).ravel(),
                          np.repeat(err, nnz))
                handle.push(keys, (g / bsz).astype(np.float32))
                if b >= n_batches // 2:
                    ys.append(y)
                    ps.append(p)
            return float(M.auc(np.concatenate(ys), np.concatenate(ps)))
        finally:
            handle.shutdown()
            handle.close()

    def test_int8_error_feedback_holds_auc(self):
        """Convergence provably unchanged in the measurable sense: the
        quantized+error-feedback arm's AUC tracks the float arm's on an
        identical seed-pinned stream."""
        auc_f = self._train_auc("off")
        auc_q = self._train_auc("int8")
        assert auc_f > 0.7  # the run actually learns
        assert abs(auc_f - auc_q) <= 0.02, (auc_f, auc_q)


class TestEncodeOncePerLogicalPush:
    def test_need_keys_bounce_reuses_encoded_payload(self):
        """The key-cache bounce path re-sends the SAME arrays dict: the
        residual fold must not run twice for one logical push."""
        from parameter_server_tpu.parallel.multislice import _LruSigs

        srv, handle = _server_and_handle()
        srv._key_cache = _LruSigs(cap=1)
        try:
            sets = [
                np.arange(1 + 64 * s, 1 + 64 * (s + 1), dtype=np.int64)
                for s in range(3)
            ]
            # prime sigs client-side while the server's 1-entry cache
            # forgets all but the last; also completes negotiation
            for s in sets:
                handle.push(s, np.zeros(64, np.float32))
            total = np.zeros(64 * 3, np.float64)
            futs = []
            for i, s in enumerate(sets):
                g = np.full(64, float(i + 1), np.float32)
                total[64 * i: 64 * (i + 1)] += g
                futs.append(handle.push_async(s, g))  # sets 0..1 bounce
            for f in futs:
                f.result(timeout=30)
            allk = np.arange(1, 1 + 64 * 3, dtype=np.int64)
            w = handle.pull(allk).astype(np.float64)
            np.testing.assert_allclose(
                w, _expected_weights(handle, allk, total), atol=1e-5
            )
            assert srv.counters["need_keys"] >= 1
        finally:
            handle.shutdown()
            handle.close()

    def test_push_does_not_alias_callers_gradient_buffer(self):
        """Float-path pushes must OWN their payload: the pipeline
        serializes at send/resend time, so a caller reusing its gradient
        buffer after push_async must not corrupt the in-flight frame."""
        srv, handle = _server_and_handle(quant="off")
        try:
            keys = np.arange(1, 129, dtype=np.int64)
            g = np.full(128, 1.0, np.float32)
            f = handle.push_async(keys, g)
            g[:] = 99.0  # caller reuses its buffer immediately
            f.result(timeout=30)
            w = handle.pull(keys)
            np.testing.assert_allclose(w, -1.0, atol=1e-6)
        finally:
            handle.shutdown()
            handle.close()

    def test_sparse_residual_on_huge_or_unknown_ranges(self, rng):
        """range_size unknown (0) or huge: the accumulator must be a
        compact touched-keys map, never a dense range-sized array —
        and the telescoping identity still holds through it."""
        srv, handle = _server_and_handle(range_size=1 << 10)
        handle._res_range = 1 << 40  # pretend a 10^12-key shard
        try:
            keys = np.arange(1, 257, dtype=np.int64)
            total = np.zeros(256, np.float64)
            for i in range(8):
                g = (rng.normal(size=256) * 0.1).astype(np.float32)
                total += g
                handle.push(keys, g)
            w = handle.pull(keys).astype(np.float64)
            np.testing.assert_allclose(
                w, _expected_weights(handle, keys, total), atol=1e-5
            )
            # memory bounded by TOUCHED keys, not the range
            assert handle._res_map is not None
            assert len(handle._res_map) == 256
            assert len(handle._residual) < 4096
            # residual_rows is READ-ONLY: sweeping untouched keys must
            # not allocate map entries or grow the buffer
            probe = np.arange(10_000, 11_000, dtype=np.int64)
            assert not handle.residual_rows(probe).any()
            assert len(handle._res_map) == 256
        finally:
            handle.shutdown()
            handle.close()

    def test_concurrent_pushers_share_residual_safely(self, rng):
        """_res_lock: concurrent pushes of disjoint key sets from N
        threads keep the telescoping identity per key set."""
        srv, handle = _server_and_handle(range_size=4096, window=8)
        try:
            handle.push(
                np.arange(1, 5, dtype=np.int64), np.zeros(4, np.float32)
            )  # negotiate
            totals = {}
            lock = threading.Lock()

            def worker(t):
                keys = np.arange(
                    1 + 512 * t, 1 + 512 * (t + 1), dtype=np.int64
                )
                tot = np.zeros(512, np.float64)
                r = np.random.default_rng(t)
                for _ in range(6):
                    g = (r.normal(size=512) * 0.1).astype(np.float32)
                    tot += g
                    handle.push(keys, g)
                with lock:
                    totals[t] = (keys, tot)

            ts = [
                threading.Thread(target=worker, args=(t,)) for t in range(4)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            for keys, tot in totals.values():
                w = handle.pull(keys).astype(np.float64)
                np.testing.assert_allclose(
                    w, _expected_weights(handle, keys, tot), atol=1e-5
                )
        finally:
            handle.shutdown()
            handle.close()
