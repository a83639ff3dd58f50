"""Telemetry plane unit + integration tests: log-bucketed Histograms and
their cluster merge, thread-safe counters/timers, merge_progress edge
cases, the ProgressReporter table satellites, heartbeat-piggybacked
telemetry through the coordinator's ``telemetry`` command, and the
``cli stats`` dashboard."""

import json
import threading

import numpy as np
import pytest

from parameter_server_tpu.utils.metrics import (
    CounterSet,
    Histogram,
    HistogramSet,
    ProgressReporter,
    Timer,
    TimerRegistry,
    format_cluster_stats,
    format_latency_table,
    hist_percentile,
    merge_hist_snapshots,
    merge_progress,
    merge_telemetry,
    telemetry_snapshot,
)


class TestHistogram:
    def test_percentiles_log_bucketed(self):
        h = Histogram()
        for _ in range(99):
            h.observe(100e-6)  # 100 us -> bucket upper edge 128 us
        h.observe(50e-3)  # one 50 ms outlier
        assert h.percentile(0.5) == pytest.approx(128e-6)
        assert h.percentile(0.99) == pytest.approx(128e-6)
        assert h.percentile(1.0) == pytest.approx((1 << 16) / 1e6)  # 65.5 ms
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["sum_s"] == pytest.approx(99 * 100e-6 + 50e-3)

    def test_empty_and_zero(self):
        h = Histogram()
        assert h.percentile(0.5) == 0.0
        h.observe(0.0)  # sub-microsecond -> bucket 0 (upper edge 1 us)
        assert h.percentile(0.5) == pytest.approx(1e-6)

    def test_merge_is_bucketwise_exact(self):
        a, b = Histogram(), Histogram()
        for _ in range(10):
            a.observe(1e-3)
        for _ in range(10):
            b.observe(1e-1)
        m = merge_hist_snapshots([a.snapshot(), b.snapshot()])
        assert m["count"] == 20
        # p50 lands at the slow half's boundary, p99 inside the slow half
        assert hist_percentile(m, 0.25) == pytest.approx(
            hist_percentile(a.snapshot(), 0.5)
        )
        assert hist_percentile(m, 0.99) == pytest.approx(
            hist_percentile(b.snapshot(), 0.99)
        )

    def test_concurrent_observe(self):
        h = Histogram()

        def worker():
            for _ in range(1000):
                h.observe(1e-4)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert h.snapshot()["count"] == 8000

    def test_histogram_set_named(self):
        hs = HistogramSet()
        hs.observe("client.push", 1e-3)
        hs.observe("client.push", 1e-3)
        hs.observe("server.pull", 1e-4)
        snap = hs.snapshot()
        assert snap["client.push"]["count"] == 2
        assert snap["server.pull"]["count"] == 1
        hs.reset()
        assert hs.snapshot() == {}


class TestCounterSetConcurrency:
    def test_concurrent_inc_many_threads(self):
        c = CounterSet()

        def worker(i):
            for _ in range(2500):
                c.inc("shared")
                c.inc(f"mine_{i}", 2)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.get("shared") == 8 * 2500  # no lost updates
        for i in range(8):
            assert c.get(f"mine_{i}") == 5000
        snap = c.snapshot()
        assert snap["shared"] == 20000 and len(snap) == 9


class TestTimerThreadSafety:
    def test_tic_toc_from_many_threads(self):
        # the checkpoint thread and serve threads tic/toc the same Timer
        # concurrently: per-thread t0 means no "toc without tic" races and
        # no lost counts
        t = Timer()
        errs = []

        def worker():
            try:
                for _ in range(500):
                    t.tic()
                    t.toc()
            except AssertionError as e:  # pragma: no cover - the old race
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [th.start() for th in ts]
        [th.join() for th in ts]
        assert not errs
        assert t.count == 8 * 500
        assert t.total >= 0
        assert t.snapshot() == {"total_s": t.total, "count": t.count}

    def test_toc_without_tic_still_asserts(self):
        with pytest.raises(AssertionError):
            Timer().toc()

    def test_registry_shared_and_snapshotted(self):
        reg = TimerRegistry()
        with reg.timer("a"):
            pass
        with reg.timer("a"):
            pass
        with reg.timer("b"):
            pass
        snap = reg.snapshot()
        assert snap["a"]["count"] == 2 and snap["b"]["count"] == 1
        assert reg.timer("a") is reg.timer("a")
        reg.reset()
        assert set(reg.snapshot()) == {"process.cpu"}  # no named timer left: the process's clock alone


class TestMergeProgressEdges:
    def test_zero_example_weight_falls_back_to_unweighted(self):
        m = merge_progress(
            [
                {"examples": 0, "objv": 1.0},
                {"examples": 0, "objv": 3.0},
            ]
        )
        assert m["objv"] == pytest.approx(2.0)  # unweighted mean, no 0-div

    def test_mixed_zero_and_positive_weights(self):
        m = merge_progress(
            [
                {"examples": 100, "auc": 0.9},
                {"auc": 0.5},  # no examples key at all
            ]
        )
        assert m["auc"] == pytest.approx(0.7)  # fallback path
        assert m["examples"] == 100

    def test_missing_keys_simply_absent(self):
        m = merge_progress([{"examples": 10}, {"examples": 20}])
        assert m["examples"] == 30
        for k in ("objv", "auc", "nnz_w", "rpc_retries"):
            assert k not in m

    def test_recovery_counters_summed(self):
        m = merge_progress(
            [
                {"examples": 1, "rpc_retries": 2, "rpc_reconnects": 1,
                 "rpc_dedup_hits": 3},
                {"examples": 1, "rpc_retries": 5, "rpc_dedup_hits": 4},
            ]
        )
        assert m["rpc_retries"] == 7
        assert m["rpc_reconnects"] == 1
        assert m["rpc_dedup_hits"] == 7

    def test_empty_reports(self):
        assert merge_progress([]) == {}


class TestProgressReporterTable:
    def test_header_reprinted_every_25_rows(self):
        lines = []
        rep = ProgressReporter(print_fn=lines.append)
        for i in range(60):
            rep.report(examples=i, objv=1.0)
        headers = [ln for ln in lines if "examples" in ln and "objv" in ln
                   and "sec" in ln and not ln.strip()[0].isdigit()]
        # 60 rows -> header at rows 0, 25, 50
        assert len(headers) == 3
        assert len(lines) == 63

    def test_recovery_columns_in_header_and_rows(self):
        lines = []
        rep = ProgressReporter(print_fn=lines.append)
        rep.report(examples=5, objv=1.0, rpc_retries=7, rpc_reconnects=2,
                   rpc_dedup_hits=9)
        header, row = lines[0], lines[1]
        for col in ("rpc_retries", "rpc_reconnects", "rpc_dedup_hits"):
            assert col in header
        assert "7" in row and "9" in row


class TestTelemetrySnapshotMerge:
    def test_merge_sums_counters_and_timers_merges_hists(self):
        a = {
            "counters": {"x": 1, "y": 2},
            "hists": {"client.push": {"count": 2, "sum_s": 0.2,
                                      "buckets": {"10": 2}}},
            "timers": {"t": {"total_s": 1.0, "count": 3}},
        }
        b = {
            "counters": {"x": 5},
            "hists": {"client.push": {"count": 1, "sum_s": 0.1,
                                      "buckets": {"12": 1}},
                      "server.pull": {"count": 1, "sum_s": 0.0,
                                      "buckets": {"3": 1}}},
            "timers": {"t": {"total_s": 0.5, "count": 1}},
        }
        m = merge_telemetry([a, b])
        assert m["counters"] == {"x": 6, "y": 2}
        # high-watermark gauges (*_peak) merge as a MAX, not a sum: the
        # cluster view must never report a window depth nothing reached
        mp = merge_telemetry([
            {"counters": {"rpc_inflight_peak": 8, "n": 1}},
            {"counters": {"rpc_inflight_peak": 3, "n": 2}},
        ])
        assert mp["counters"] == {"rpc_inflight_peak": 8, "n": 3}
        assert m["hists"]["client.push"]["count"] == 3
        assert m["hists"]["client.push"]["buckets"] == {"10": 2, "12": 1}
        assert m["hists"]["server.pull"]["count"] == 1
        assert m["timers"]["t"] == {"total_s": 1.5, "count": 4}

    def test_snapshot_shape(self):
        s = telemetry_snapshot()
        # key_heat rides along only once some shard server counted keys
        # (ISSUE 9), slow only once an RPC completion recorded a
        # slowest-op entry (ISSUE 15), prof only under an armed
        # profiler (ISSUE 13) — all optional in the shape contract
        assert {"counters", "hists", "timers"} <= set(s) <= {
            "counters", "hists", "timers", "key_heat", "slow", "prof"
        }
        json.dumps(s)  # wire-serializable

    def test_format_tables_render(self):
        hists = {"client.push": {"count": 4, "sum_s": 0.004,
                                 "buckets": {"10": 4}}}
        table = format_latency_table(hists)
        assert "client.push" in table and "p99_ms" in table
        rep = {
            "nodes": {
                "1": {"role": "worker", "rank": 0,
                      "stats": {"max_rss_mb": 12.0},
                      "telemetry": {"counters": {"wire_bytes_out": 7}}},
            },
            "merged": {"counters": {"wire_bytes_out": 7}, "hists": hists},
        }
        out = format_cluster_stats(rep)
        assert "worker" in out and "wire_bytes_out" in out
        assert "client.push" in out


class TestCoordinatorTelemetry:
    def test_beats_piggyback_and_merge(self):
        from parameter_server_tpu.parallel.control import (
            ControlClient,
            Coordinator,
        )

        coord = Coordinator()
        try:
            c = ControlClient(coord.address)
            nid = c.register("worker", rank=0)
            c.beat(nid, {
                "max_rss_mb": 5.0,
                "telemetry": {
                    "counters": {"pulls": 11, "wire_bytes_out": 100},
                    "hists": {"client.pull": {"count": 3, "sum_s": 0.3,
                                              "buckets": {"17": 3}}},
                    "timers": {},
                },
            })
            rep = c.telemetry()
            node = rep["nodes"][str(nid)]
            assert node["role"] == "worker" and node["rank"] == 0
            assert node["stats"]["max_rss_mb"] == 5.0
            assert node["telemetry"]["counters"]["pulls"] == 11
            # merged = node snapshot + the coordinator's own process
            # (which has live wire counters from this very conversation)
            merged = rep["merged"]
            assert merged["counters"]["pulls"] == 11
            assert merged["hists"]["client.pull"]["count"] >= 3
            assert merged["counters"]["wire_bytes_out"] > 100  # node + local
            c.close()
        finally:
            coord.stop()

    def test_ssp_blocked_time_accounted(self):
        from parameter_server_tpu.parallel.ssp import SSPClock

        clock = SSPClock(num_workers=2, max_delay=0)
        clock.finish(0, 0)

        def unblock():
            clock.finish(1, 0)

        t = threading.Timer(0.05, unblock)
        t.start()
        assert clock.wait(0, 1, timeout=5.0)
        t.join()
        p = clock.progress()
        assert p["blocked_n"][0] == 1 and p["blocked_n"][1] == 0
        assert p["blocked_s"][0] >= 0.03
        # an open gate books no blocked time
        clock.finish(0, 1)
        clock.finish(1, 1)
        assert clock.wait(0, 2, timeout=1.0)
        assert clock.progress()["blocked_n"][0] == 1


class TestCliStats:
    def test_stats_subcommand_prints_dashboard(self, capsys):
        from parameter_server_tpu import cli
        from parameter_server_tpu.parallel.control import (
            ControlClient,
            Coordinator,
        )

        coord = Coordinator()
        try:
            c = ControlClient(coord.address)
            nid = c.register("server", rank=1)
            c.beat(nid, {
                "max_rss_mb": 3.0,
                "telemetry": {
                    "counters": {"pushes": 4},
                    "hists": {"server.push": {"count": 4, "sum_s": 0.004,
                                              "buckets": {"10": 4}}},
                    "timers": {},
                },
            })
            rc = cli.main(["stats", "--scheduler", coord.address])
            assert rc == 0
            out = capsys.readouterr().out
            # the dashboard table printed, then the JSON result line
            assert "per-command latency" in out
            assert "server.push" in out
            last = json.loads(out.strip().splitlines()[-1])
            assert last["counters"]["pushes"] == 4
            # >= : latency_histograms is process-global, so earlier
            # in-process ShardServer tests may have observed server.push
            # in the coordinator's own snapshot too
            assert last["latency_ms"]["server.push"]["count"] >= 4
            assert last["latency_ms"]["server.push"]["p50"] > 0
            c.close()
        finally:
            coord.stop()


class TestFrameLayerByteCounters:
    def test_control_traffic_counted(self):
        from parameter_server_tpu.parallel.control import (
            ControlClient,
            Coordinator,
        )
        from parameter_server_tpu.utils.metrics import wire_counters

        before_out = wire_counters.get("wire_bytes_out")
        before_in = wire_counters.get("wire_bytes_in")
        coord = Coordinator()
        try:
            c = ControlClient(coord.address)
            c.register("worker", rank=0)
            c.kv_set("k", arrays={"x": np.arange(100)})
            assert c.kv_get("k") is not None
            c.close()
        finally:
            coord.stop()
        # both directions counted at the frame layer — coordinator and
        # client run in this process, so both sides land here
        assert wire_counters.get("wire_bytes_out") - before_out > 400
        assert wire_counters.get("wire_bytes_in") - before_in > 400
