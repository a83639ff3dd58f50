"""Tests for the cross-process tier: control plane, shard servers, and the
multi-process launcher (the reference's script/local.sh integration test,
run for real: separate OS processes joined only by TCP)."""

import json
import socket
import threading

import numpy as np
import pytest

from parameter_server_tpu.parallel.control import (
    ControlClient,
    Coordinator,
    recv_frame,
    send_frame,
)
from parameter_server_tpu.parallel.multislice import ServerHandle, ShardServer
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.keyrange import KeyRange


class TestFrameCodec:
    def _roundtrip(self, header, arrays):
        a, b = socket.socketpair()
        try:
            send_frame(a, header, arrays)
            return recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_arrays_roundtrip(self, rng):
        arrays = {
            "keys": rng.integers(0, 1 << 31, 100).astype(np.uint32),
            "vals": rng.normal(size=(10, 3)).astype(np.float32),
            "empty": np.zeros(0, dtype=np.int64),
        }
        h, out = self._roundtrip({"cmd": "x", "n": 7}, arrays)
        assert h["cmd"] == "x" and h["n"] == 7
        for k, v in arrays.items():
            np.testing.assert_array_equal(out[k], v)
            assert out[k].dtype == v.dtype

    def test_zip_roundtrip(self, rng):
        x = np.zeros(10000, dtype=np.float32)  # compressible
        h, out = self._roundtrip({"cmd": "x", "zip": True}, {"x": x})
        np.testing.assert_array_equal(out["x"], x)

    def test_zip_shrinks_wire_bytes(self):
        class Sink:  # just count: a socket would block unread at this size
            def sendall(self, data):
                self.n = len(data)

        x = np.zeros(100000, dtype=np.float32)
        sizes = {}
        for zip_flag in (False, True):
            sink = Sink()
            sizes[zip_flag] = send_frame(sink, {"cmd": "x", "zip": zip_flag}, {"x": x})
        assert sizes[True] < sizes[False] / 50


class TestCoordinator:
    @pytest.fixture
    def coord(self):
        c = Coordinator()
        yield c
        c.stop()

    def test_register_and_kv(self, coord):
        c1 = ControlClient(coord.address)
        c2 = ControlClient(coord.address)
        assert {c1.register("worker"), c2.register("server")} == {0, 1}
        c1.kv_set("addr/0", arrays={"x": np.arange(4)}, port=99)
        fields, arrays = c2.kv_get("addr/0", block=True, timeout=5)
        assert fields["port"] == 99
        np.testing.assert_array_equal(arrays["x"], np.arange(4))
        assert c2.kv_get("missing") is None
        c1.close()
        c2.close()

    def test_barrier_blocks_until_count(self, coord):
        results = []

        def arrive():
            c = ControlClient(coord.address)
            c.barrier("b1", count=3, timeout=30)
            results.append(1)
            c.close()

        threads = [threading.Thread(target=arrive) for _ in range(3)]
        threads[0].start()
        threads[1].start()
        import time

        time.sleep(0.2)
        assert len(results) == 0  # two arrivals: still parked
        threads[2].start()
        for t in threads:
            t.join(timeout=30)
        assert len(results) == 3

    def test_workload_pool_over_wire(self, coord):
        c = ControlClient(coord.address)
        c.workload_init(["a", "b"])
        assert c.workload_fetch(0) == "a"
        assert c.workload_fetch(1) == "b"
        assert c.workload_fetch(0) is None
        assert not c.workload_all_done()
        c.workload_finish("a")
        c.workload_finish("b")
        assert c.workload_all_done()
        c.close()

    def test_ssp_gate_and_retire(self, coord):
        c = ControlClient(coord.address)
        c.ssp_init(num_workers=2, max_delay=0)
        # worker 0 may start step 0 (gate: min_finished >= -1)
        assert c.ssp_wait(0, 0, timeout=1)
        # but not step 1 until worker 1 finishes step 0
        assert not c.ssp_wait(0, 1, timeout=0.2)
        c.ssp_finish(0, 0)
        c.ssp_finish(1, 0)
        assert c.ssp_wait(0, 1, timeout=5)
        # a retired worker stops gating
        c.ssp_retire(1)
        c.ssp_finish(0, 1)
        assert c.ssp_wait(0, 5, timeout=0.5) is False  # own counter still gates
        c.ssp_finish(0, 4)
        assert c.ssp_wait(0, 5, timeout=5)
        c.close()

    def test_progress_merge_and_heartbeats(self, coord):
        c = ControlClient(coord.address)
        c.progress(0, {"examples": 100, "objv": 0.5, "ex_per_sec": 10.0})
        c.progress(1, {"examples": 300, "objv": 0.3, "ex_per_sec": 30.0})
        m = c.progress_merged()
        assert m["examples"] == 400
        assert m["objv"] == pytest.approx(0.35)  # example-weighted
        assert m["ex_per_sec"] == pytest.approx(40.0)
        c.beat(0, {"max_rss_mb": 1.0})
        rep, _ = c.call("dead")
        assert rep["alive"] == [0]
        c.close()


def _mini_cfg(num_keys=4096, max_delay=0, **filter_kw):
    cfg = PSConfig()
    cfg.data.num_keys = num_keys
    cfg.solver.algo = "ftrl"
    cfg.solver.minibatch = 64
    cfg.solver.max_delay = max_delay
    cfg.lr.alpha = 0.1
    cfg.penalty.lambda_l1 = 0.01
    for k, v in filter_kw.items():
        setattr(cfg.filter, k, v)
    return cfg


class TestShardServer:
    """In-process servers (threads), real sockets: push/pull semantics must
    match the single-program KV path bit-for-bit on the same batch stream."""

    def _start(self, cfg, num_servers):
        from parameter_server_tpu.models.linear import updater_from_config

        ranges = KeyRange(0, cfg.data.num_keys).even_divide(num_servers)
        servers = [
            ShardServer(updater_from_config(cfg), r).start() for r in ranges
        ]
        handles = [
            ServerHandle(s.address, i, worker=0, cfg=cfg)
            for i, s in enumerate(servers)
        ]
        return servers, handles, ranges

    def _batches(self, cfg, rng, n=12):
        from parameter_server_tpu.data.batch import BatchBuilder
        from parameter_server_tpu.data.synthetic import make_sparse_logistic

        bs = cfg.solver.minibatch
        labels, keys, vals, _ = make_sparse_logistic(
            bs * n, 512, nnz_per_example=8, seed=3
        )
        builder = BatchBuilder(
            num_keys=cfg.data.num_keys, batch_size=bs, max_nnz_per_example=64
        )
        return [
            builder.build(labels[i : i + bs], keys[i : i + bs], vals[i : i + bs])
            for i in range(0, bs * n, bs)
        ]

    def _drive(self, cfg, handles, ranges, batches):
        """Minimal worker inner loop (pull -> grad -> push) over the wire."""
        import jax

        from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

        begins = np.array([r.begin for r in ranges] + [cfg.data.num_keys])
        for b in batches:
            real = b.unique_keys[1 : b.num_unique]
            bounds = np.searchsorted(real, begins)
            segs = [
                (real[bounds[s] : bounds[s + 1]] - ranges[s].begin).astype(np.uint32)
                for s in range(len(handles))
            ]
            w_u = np.zeros(len(b.unique_keys), dtype=np.float32)
            w_u[1 : b.num_unique] = np.concatenate(
                [h.pull(s) for h, s in zip(handles, segs)]
            )
            logits = csr_logits(
                jax.numpy.asarray(w_u), b.values, b.local_ids, b.row_ids,
                b.row_splits,
            )
            _, err = logistic_loss(
                logits, jax.numpy.asarray(b.labels), jax.numpy.asarray(b.example_mask)
            )
            g = csr_grad(
                err, b.values, b.local_ids, b.row_ids, b.row_splits,
                num_unique=len(b.unique_keys),
            )
            g_real = np.asarray(g).ravel()[1 : b.num_unique]
            for s, h in enumerate(handles):
                h.push(segs[s], g_real[bounds[s] : bounds[s + 1]])

    def _single_process_weights(self, cfg, batches):
        from parameter_server_tpu.kv.updaters import Ftrl
        from parameter_server_tpu.models.linear import batch_to_device, train_step

        up = Ftrl(
            alpha=cfg.lr.alpha, beta=cfg.lr.beta,
            lambda_l1=cfg.penalty.lambda_l1, lambda_l2=cfg.penalty.lambda_l2,
        )
        state = up.init(cfg.data.num_keys, 1)
        for b in batches:
            state, _ = train_step(up, state, batch_to_device(b))
        return np.asarray(up.weights(state)).ravel()

    def test_matches_single_program_path(self, rng):
        cfg = _mini_cfg()
        servers, handles, ranges = self._start(cfg, num_servers=3)
        try:
            batches = self._batches(cfg, rng)
            self._drive(cfg, handles, ranges, batches)
            w_wire = np.zeros(cfg.data.num_keys, dtype=np.float32)
            for h in handles:
                begin, w_range = h.dump()
                w_wire[begin : begin + len(w_range)] = w_range.ravel()
            w_ref = self._single_process_weights(cfg, batches)
            # identical math, identical order; only eager-vs-jit rounding
            np.testing.assert_allclose(w_wire, w_ref, rtol=1e-5, atol=1e-6)
            assert np.count_nonzero(w_wire) > 0
        finally:
            for h in handles:
                h.shutdown()
                h.close()

    def test_key_caching_filter(self, rng):
        cfg = _mini_cfg(key_caching=True)
        servers, handles, ranges = self._start(cfg, num_servers=1)
        try:
            batches = self._batches(cfg, rng, n=2)
            # same batch twice: pull+push of batch 0 again must hit the cache
            self._drive(cfg, handles, ranges, [batches[0], batches[0]])
            stats = handles[0].stats()
            # 4 keyed calls (2 pulls + 2 pushes), keys sent only on the first
            assert stats["cache_hits"] == 3
            assert stats["need_keys"] == 0
        finally:
            for h in handles:
                h.shutdown()
                h.close()

    def test_fixed_point_push_converges_close(self, rng):
        cfg_fp = _mini_cfg(fixing_float_bytes=2, compressing=True)
        cfg_ref = _mini_cfg()
        batches = self._batches(cfg_ref, rng)
        w = {}
        for name, cfg in (("fp", cfg_fp), ("ref", cfg_ref)):
            servers, handles, ranges = self._start(cfg, num_servers=2)
            try:
                self._drive(cfg, handles, ranges, batches)
                acc = np.zeros(cfg.data.num_keys, dtype=np.float32)
                for h in handles:
                    begin, w_range = h.dump()
                    acc[begin : begin + len(w_range)] = w_range.ravel()
                w[name] = acc
            finally:
                for h in handles:
                    h.shutdown()
                    h.close()
        # int16 stochastic rounding: unbiased, small per-key error
        err = np.abs(w["fp"] - w["ref"]).max()
        scale = np.abs(w["ref"]).max()
        assert err < 0.1 * scale


class TestDurablePushDedup:
    """Exactly-once pushes across server LIVES: the reply cache dies with
    the process, so a push that was applied and checkpointed but whose
    reply was lost to a kill must be recognized by the restarted server's
    durable ledger instead of re-applied."""

    def _mk(self):
        from parameter_server_tpu.models.linear import updater_from_config

        cfg = _mini_cfg(num_keys=16)
        return ShardServer(updater_from_config(cfg), KeyRange(0, 16))

    def _push_header(self, seq, cid="worker-0"):
        return {
            "cmd": "push", "worker": 0, "sig": "s", "codec": 0,
            "_cid": cid, "_seq": seq,
        }

    def _state(self, srv):
        return {k: np.asarray(v).copy() for k, v in srv.state.items()}

    def test_ledger_survives_checkpoint_and_skips_replay(self, tmp_path):
        arrays = {
            "keys": np.array([1, 2], dtype=np.uint32),
            "g": np.array([5.0, -2.5], dtype=np.float32),
        }
        srv1 = self._mk()
        try:
            rep, _ = srv1._handle(self._push_header("k0"), dict(arrays))
            assert rep == {"ok": True}
            srv1.save_state(str(tmp_path))
            s1 = self._state(srv1)
        finally:
            srv1.server.stop()
        srv2 = self._mk()
        try:
            assert srv2.load_state(str(tmp_path))
            # replay of the SAME (cid, seq): srv1's reply cache is gone —
            # only the checkpointed ledger can stop the double-apply
            rep, _ = srv2._handle(self._push_header("k0"), dict(arrays))
            assert rep == {"ok": True}
            assert srv2.counters["push_replays"] == 1
            assert srv2.counters["pushes"] == 0
            for k, v in self._state(srv2).items():
                np.testing.assert_array_equal(v, s1[k])
            # a FRESH seq from the same client applies normally
            rep, _ = srv2._handle(self._push_header("k1"), dict(arrays))
            assert rep == {"ok": True}
            assert srv2.counters["pushes"] == 1
            assert any(
                not np.array_equal(v, s1[k])
                for k, v in self._state(srv2).items()
            )
        finally:
            srv2.server.stop()

    def test_need_keys_bounce_not_cached_same_seq_applies(self):
        """The key-caching two-phase exchange under one dedup identity: the
        need_keys bounce is non-committing (not pinned in the reply cache),
        the keyed follow-up with the SAME seq applies, and a resend of the
        applied push replays instead of re-applying."""
        from parameter_server_tpu.parallel.control import RpcClient

        srv = self._mk().start()
        g = {"g": np.array([1.0, 1.0], dtype=np.float32)}
        keyed = {"keys": np.array([1, 2], dtype=np.uint32), **g}
        cli = RpcClient(srv.address)
        try:
            rep, _ = cli.call("push", arrays=g, worker=0, sig="s", codec=0,
                              _seq="p0")
            assert rep.get("need_keys")
            assert srv.counters["pushes"] == 0
            rep, _ = cli.call("push", arrays=keyed, worker=0, sig="s",
                              codec=0, _seq="p0")
            assert "need_keys" not in rep
            assert srv.counters["pushes"] == 1
            # resend of the applied push: answered from the reply cache
            rep, _ = cli.call("push", arrays=keyed, worker=0, sig="s",
                              codec=0, _seq="p0")
            assert rep["ok"]
            assert srv.counters["pushes"] == 1
        finally:
            cli.close()
            srv.server.stop()


class TestLaunchLocalDevices:
    """launch_local starts 1 + servers + workers processes that each
    initialise a JAX backend: letting them inherit an accelerator must be
    a named error before anything is spawned, not a hang."""

    def test_inherit_refuses_an_unpinned_environment(self, monkeypatch):
        from parameter_server_tpu.parallel.multislice import launch_local

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        spawned = []
        monkeypatch.setattr(
            "subprocess.Popen", lambda *a, **k: spawned.append(a)
        )
        with pytest.raises(ValueError, match="4 processes.*one process at a time"):
            launch_local("unused.json", 2, 1, devices="inherit")
        assert spawned == []

    def test_unknown_devices_value(self):
        from parameter_server_tpu.parallel.multislice import launch_local

        with pytest.raises(ValueError, match="'cpu' or 'inherit'"):
            launch_local("unused.json", 1, 1, devices="tpu")


@pytest.mark.slow
class TestLaunchLocal:
    """The reference's local.sh run, for real: 1 scheduler + 2 servers +
    2 workers as OS processes over TCP on synthetic libsvm shards."""

    def test_end_to_end(self, tmp_path, rng):
        from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
        from parameter_server_tpu.parallel.multislice import launch_local

        labels, keys, vals, _ = make_sparse_logistic(
            3000, 800, nnz_per_example=10, noise=0.3, seed=11
        )
        files = []
        for i in range(4):  # 4 shards -> the workload pool has real work
            sl = slice(i * 700, (i + 1) * 700)
            f = tmp_path / f"part-{i}.libsvm"
            write_libsvm(f, labels[sl], keys[sl], vals[sl])
            files.append(str(f))
        val = tmp_path / "val.libsvm"
        write_libsvm(val, labels[2800:], keys[2800:], vals[2800:])

        cfg = {
            "app": "linear_method",
            "data": {
                "files": files,
                "format": "libsvm",
                "num_keys": 1 << 15,
                "val_files": [str(val)],
                "max_nnz_per_example": 64,
            },
            "solver": {"algo": "ftrl", "minibatch": 256, "max_delay": 1, "epochs": 3},
            "lr": {"alpha": 0.3, "beta": 1.0},
            "penalty": {"lambda_l1": 0.005},
            "filter": {"key_caching": True, "compressing": True},
        }
        app_file = tmp_path / "app.json"
        app_file.write_text(json.dumps(cfg))
        model_out = tmp_path / "model.txt"

        out = launch_local(
            str(app_file), num_servers=2, num_workers=2,
            model_out=str(model_out), timeout=420,
        )
        assert out["val_auc"] > 0.85, out
        assert out["nnz_w"] > 0
        assert model_out.exists()
        merged = out["merged"]
        assert merged["examples"] > 0
        # both servers did real work
        for st in out["server_stats"]:
            assert st["pushes"] > 0 and st["pulls"] > 0
        # nothing stranded, nobody died
        assert out["workloads"] == {
            "pending": 0, "active": 0, "done": 12,
            "attempts": 12, "reassigned": 0,  # each shard handed out once
        }
        assert out["dead_workers"] == []

    def test_worker_killed_mid_run_recovers(self, tmp_path, rng):
        """Fault injection (SURVEY §5.3): SIGKILL a worker mid-run; the
        scheduler's dead-node monitor must requeue its shards and retire
        its SSP clock so the survivor finishes ALL workloads."""
        from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
        from parameter_server_tpu.parallel.multislice import launch_local

        labels, keys, vals, _ = make_sparse_logistic(
            3000, 800, nnz_per_example=10, noise=0.3, seed=13
        )
        files = []
        for i in range(4):
            sl = slice(i * 700, (i + 1) * 700)
            f = tmp_path / f"part-{i}.libsvm"
            write_libsvm(f, labels[sl], keys[sl], vals[sl])
            files.append(str(f))
        val = tmp_path / "val.libsvm"
        write_libsvm(val, labels[2800:], keys[2800:], vals[2800:])

        n_epochs = 6  # enough work that the kill always lands mid-run
        cfg = {
            "app": "linear_method",
            "data": {
                "files": files,
                "format": "libsvm",
                "num_keys": 1 << 15,
                "val_files": [str(val)],
                "max_nnz_per_example": 64,
            },
            "solver": {
                "algo": "ftrl", "minibatch": 256, "max_delay": 1,
                "epochs": n_epochs,
            },
            "lr": {"alpha": 0.3, "beta": 1.0},
            "penalty": {"lambda_l1": 0.005},
            "fault": {"heartbeat_interval_s": 0.5, "heartbeat_timeout_s": 2.5},
        }
        app_file = tmp_path / "app.json"
        app_file.write_text(json.dumps(cfg))

        out = launch_local(
            str(app_file), num_servers=2, num_workers=2,
            timeout=420, fault_kill="worker:1@1.5",
        )
        assert out["dead_workers"] == [1], out
        # every workload finished despite the death — requeue worked; the
        # attempts ledger balances (each hand-out completed or was requeued
        # exactly once: no lost shard, no double assignment)
        wl = out["workloads"]
        assert (wl["pending"], wl["active"], wl["done"]) == (0, 0, 4 * n_epochs), out
        assert wl["attempts"] == wl["done"] + wl["reassigned"], out
        assert out["val_auc"] > 0.85, out


class TestTrafficReconciliation:
    """Measured wire bytes (RpcClient counters) vs the static
    traffic.wire_step_traffic estimate — the observability contract that
    the estimates reported in progress are real (VERDICT r2 weak #5/#6)."""

    def test_measured_matches_estimate(self):
        from parameter_server_tpu.parallel.traffic import wire_step_traffic

        cfg = _mini_cfg(num_keys=1 << 16, key_caching=True)
        servers, handles, ranges = self._pair(cfg)
        h = handles[0]
        try:
            u = 30000
            keys = np.arange(u, dtype=np.int64)
            grads = np.ones(u, dtype=np.float32)

            # round 1: cold key cache — keys ride the wire twice
            out0, in0 = h.client.bytes_out, h.client.bytes_in
            h.pull(keys)
            h.push(keys, grads)
            est = wire_step_traffic(u, send_keys=True)
            d_out = h.client.bytes_out - out0
            d_in = h.client.bytes_in - in0
            assert abs(d_out - est.out_bytes) / est.out_bytes < 0.02, (
                d_out, est.out_bytes,
            )
            assert abs(d_in - est.in_bytes) / est.in_bytes < 0.02, (
                d_in, est.in_bytes,
            )

            # round 2: key-caching filter — only the signature rides
            out0, in0 = h.client.bytes_out, h.client.bytes_in
            h.pull(keys)
            h.push(keys, grads)
            est2 = wire_step_traffic(u, send_keys=False)
            d_out2 = h.client.bytes_out - out0
            assert abs(d_out2 - est2.out_bytes) / est2.out_bytes < 0.02, (
                d_out2, est2.out_bytes,
            )
            # the filter's measured saving matches its advertised saving
            # (one key list per cold step)
            assert d_out2 < d_out - u * 4 + 2048
        finally:
            for hh in handles:
                hh.shutdown()
                hh.close()

    def _pair(self, cfg):
        from parameter_server_tpu.models.linear import updater_from_config

        ranges = KeyRange(0, cfg.data.num_keys).even_divide(1)
        servers = [
            ShardServer(updater_from_config(cfg), r).start() for r in ranges
        ]
        handles = [
            ServerHandle(s.address, i, worker=0, cfg=cfg, range_size=r.size)
            for i, (s, r) in enumerate(zip(servers, ranges))
        ]
        return servers, handles, ranges


class TestServerRecovery:
    """Checkpoint-backed server recovery (SURVEY §5.3/§5.4): SIGKILL a
    shard server mid-run; a replacement relaunches from its periodic range
    dump, re-registers under the same rank, workers reconnect, and
    training completes with quality parity (pushes since the last dump
    are lost — the bounded price of checkpoint recovery)."""

    def test_server_killed_and_restarted_completes(self, tmp_path, rng):
        from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
        from parameter_server_tpu.parallel.multislice import launch_local

        labels, keys, vals, _ = make_sparse_logistic(
            3000, 800, nnz_per_example=10, noise=0.3, seed=17
        )
        files = []
        for i in range(4):
            sl = slice(i * 700, (i + 1) * 700)
            f = tmp_path / f"part-{i}.libsvm"
            write_libsvm(f, labels[sl], keys[sl], vals[sl])
            files.append(str(f))
        val = tmp_path / "val.libsvm"
        write_libsvm(val, labels[2800:], keys[2800:], vals[2800:])

        n_epochs = 6
        cfg = {
            "app": "linear_method",
            "data": {
                "files": files,
                "format": "libsvm",
                "num_keys": 1 << 15,
                "val_files": [str(val)],
                "max_nnz_per_example": 64,
            },
            "solver": {
                "algo": "ftrl", "minibatch": 256, "max_delay": 1,
                "epochs": n_epochs,
            },
            "lr": {"alpha": 0.3, "beta": 1.0},
            "penalty": {"lambda_l1": 0.005},
            "fault": {
                "heartbeat_interval_s": 0.5,
                "heartbeat_timeout_s": 2.5,
                "server_ckpt_interval_s": 0.5,
                "server_restart_grace_s": 60.0,
                "reconnect_timeout_s": 60.0,
            },
        }
        app_file = tmp_path / "app.json"
        app_file.write_text(json.dumps(cfg))

        out = launch_local(
            str(app_file), num_servers=2, num_workers=2,
            timeout=420, fault_kill="server:1@2.0",
            fault_restart_after=0.5, ckpt_dir=str(tmp_path / "sckpt"),
        )
        # no worker died; all workloads completed through the outage
        assert out["dead_workers"] == [], out
        assert out["workloads"] == {
            "pending": 0, "active": 0, "done": 4 * n_epochs,
            "attempts": 4 * n_epochs, "reassigned": 0,
        }, out
        # quality parity with the no-fault run of this family (>0.85):
        # a sub-checkpoint-interval slice of rank 1's pushes may be lost
        assert out["val_auc"] > 0.83, out
        assert out["nnz_w"] > 0


@pytest.mark.slow
class TestChaosSoak:
    """The headline recovery drill (ISSUE 1 acceptance): SIGKILL + restart
    a shard server mid-training WHILE a seeded FaultPlan drops/delays well
    over 5% of control frames (plus lost replies and duplicated frames) on
    every RpcServer in the process tree. The run must still converge to
    the no-fault objective (within the checkpoint-restart tolerance), with
    zero double-applied workload_fetch effects and the retry/reconnect/
    dedup counters proving the self-healing machinery actually engaged."""

    def test_server_kill_plus_frame_chaos_converges(self, tmp_path, rng):
        from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
        from parameter_server_tpu.parallel.multislice import launch_local

        labels, keys, vals, _ = make_sparse_logistic(
            3000, 800, nnz_per_example=10, noise=0.3, seed=17
        )
        files = []
        for i in range(4):
            sl = slice(i * 700, (i + 1) * 700)
            f = tmp_path / f"part-{i}.libsvm"
            write_libsvm(f, labels[sl], keys[sl], vals[sl])
            files.append(str(f))
        val = tmp_path / "val.libsvm"
        write_libsvm(val, labels[2800:], keys[2800:], vals[2800:])

        n_epochs = 6
        cfg = {
            "app": "linear_method",
            "data": {
                "files": files,
                "format": "libsvm",
                "num_keys": 1 << 15,
                "val_files": [str(val)],
                "max_nnz_per_example": 64,
            },
            "solver": {
                "algo": "ftrl", "minibatch": 256, "max_delay": 1,
                "epochs": n_epochs,
            },
            "lr": {"alpha": 0.3, "beta": 1.0},
            "penalty": {"lambda_l1": 0.005},
            "fault": {
                "heartbeat_interval_s": 0.5,
                "heartbeat_timeout_s": 5.0,  # dropped beats must not kill
                "server_ckpt_interval_s": 0.5,
                "server_restart_grace_s": 60.0,
                "reconnect_timeout_s": 60.0,
            },
        }
        app_file = tmp_path / "app.json"
        app_file.write_text(json.dumps(cfg))

        # deterministic cadences: 1/6 of frames dropped or delayed (>= 5%
        # by construction), plus occasional lost replies and duplicates to
        # drive the reply-cache dedup path
        plan = (
            "drop,every=12;delay,every=12,delay_s=0.01;"
            "disconnect,every=31;duplicate,every=37"
        )
        out = launch_local(
            str(app_file), num_servers=2, num_workers=2,
            timeout=420, fault_kill="server:1@2.0",
            fault_restart_after=0.5, ckpt_dir=str(tmp_path / "sckpt"),
            fault_plan=plan, fault_seed=4242,
            # ISSUE 9 satellite: the soak runs with the black box armed,
            # so ANY failure of this drill leaves a postmortem behind
            blackbox_dir=str(tmp_path / "bb"),
        )
        # completion through the outage: no worker declared dead, every
        # (epoch, file) shard finished, and the attempts ledger balances —
        # a resent workload_fetch that re-popped (double-applied) would
        # break attempts == done + reassigned
        assert out["dead_workers"] == [], out
        wl = out["workloads"]
        assert (wl["pending"], wl["active"], wl["done"]) == (0, 0, 4 * n_epochs), out
        assert wl["attempts"] == wl["done"] + wl["reassigned"], out
        # the plan genuinely engaged on the control plane: >= 5% of the
        # coordinator's frames were perturbed (1/6 by cadence)
        ch = out["chaos"]
        frames = out["control_frames"]
        assert frames > 100, out
        assert ch["drop"] + ch["delay"] >= 0.05 * frames, out
        # self-healing observability: clients retried/reconnected through
        # the drops, and at least one lost reply or duplicated frame was
        # answered from the reply cache instead of re-applied
        merged = out["merged"]
        assert merged["rpc_retries"] >= 1, merged
        dedup_total = out["wire"].get("rpc_dedup_hits", 0) + sum(
            st.get("rpc_dedup_hits", 0) for st in out["server_stats"]
        )
        assert dedup_total >= 1, out
        # converged to the same final objective as the no-fault run of
        # this family (>0.85), within the checkpoint-restart tolerance
        assert out["val_auc"] > 0.83, out
        assert out["nnz_w"] > 0
        # the black boxes survived the drill — including the SIGKILL'd
        # server's (periodic flush), and the postmortem merges a
        # cross-process-stitched timeline out of the wreckage
        from parameter_server_tpu.utils import postmortem as pm_mod

        res = pm_mod.postmortem(str(tmp_path / "bb"))
        # scheduler + 2 servers + 2 workers (+ the replacement server)
        assert res["processes"] >= 5, res["report"][:2000]
        assert res["cross_process_calls"] >= 1, res["report"][:2000]
