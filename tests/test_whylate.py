"""ISSUE 15 — tail-latency forensics: always-on slow-trace capture,
cross-process critical-path attribution and `cli whylate`.

Covers the tentpole's three layers and the satellites:

- promotion-policy units (slowest-K, anomaly-bearing, p99-breach) and
  the bounded pending/limbo memory of utils/trace.py:TailCapture;
- the head-sampling hole regression: under ``sample=16`` the slowest
  push is ALWAYS exported — promotion overrides the head drop;
- critical-path engine units over synthetic stitched chains (trace and
  blackbox modes, retry/heal/withheld variants) plus the clock-skew
  hardening (negative segments clamp + flag, never report negative
  attribution);
- the server-timing echo (``_svc_us``/``_apw_us``/``_apl_us``) feeding
  live SlowOps records, the coordinator merge, `cli top`'s slowest-push
  line and `cli whylate --scheduler`;
- the committed segment-budget baseline as a tier-1 contract
  (``whylate_baseline.json``, pslint-style tiered exits);
- the acceptance drill: a live 2-process cluster with an injected
  per-cmd delay fault — `cli whylate` attributes >= 90% of the slowest
  push's wall time to named segments and names the wire segment as the
  culprit, and the slowest push's full trace is exported under
  ``sample=16``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from parameter_server_tpu.analysis import critpath
from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.metrics import (
    SlowOps,
    latency_histograms,
    slow_ops,
    wire_counters,
)

HERE = Path(__file__).resolve().parent
REPO = HERE.parent


class _DropAll(trace.Tracer):
    """A tracer whose head sampler drops EVERY trace — promotion is the
    only way into the ring, so the policy tests are deterministic."""

    def _keep(self, trace_id: str) -> bool:
        return False


def _mk_dropall(tmp_path, **tail_kw) -> trace.Tracer:
    return _DropAll(
        str(tmp_path), process_name="tail-test",
        tail=trace.TailCapture(**tail_kw),
    )


class TestPromotionPolicy:
    def test_slowest_k_promotes_and_fast_drops(self, tmp_path):
        t = _mk_dropall(tmp_path, k=2, min_window_count=10_000)
        with t.span("rpc.push"):
            time.sleep(0.005)
        with t.span("rpc.push"):
            time.sleep(0.005)
        assert len(t.events()) == 2  # top-K not full: both promote
        n0 = len(t.events())
        d0 = wire_counters.get("trace_tail_dropped")
        with t.span("rpc.push"):
            pass  # ~0 ms: below the window's top-K floor
        assert len(t.events()) == n0  # not promoted
        assert wire_counters.get("trace_tail_dropped") == d0 + 1
        assert t.tail.limbo_events()  # ...but retained for the sidecar

    def test_anomaly_bearing_trace_promotes(self, tmp_path):
        # k=0 disables slowest-K; the window has no p99 yet — only the
        # anomaly gate can promote
        t = _mk_dropall(tmp_path, k=0, min_window_count=10_000)
        with t.span("rpc.push"):
            pass
        assert t.events() == []
        with t.span("rpc.push") as sp:
            t.instant("rpc.retry", cat="rpc")
        evs = t.events()
        assert evs, "anomaly-bearing trace must promote"
        assert {e["args"]["trace_id"] for e in evs} == {sp.trace_id}
        # the promoted buffer carries the WHOLE trace: span + instant
        assert {e["name"] for e in evs} == {"rpc.push", "rpc.retry"}

    def test_errored_span_promotes(self, tmp_path):
        t = _mk_dropall(tmp_path, k=0, min_window_count=10_000)
        with pytest.raises(ValueError):
            with t.span("rpc.push"):
                raise ValueError("boom")
        assert t.events(), "errored trace must promote"

    def test_p99_breach_promotes(self, tmp_path):
        t = _mk_dropall(tmp_path, k=0, min_window_count=32)
        # build the window's distribution: ~1 ms ops
        for _ in range(64):
            t.tail.observe_root("rpc.push", 0.001)
        with t.span("rpc.push"):
            pass  # ~0 ms: below p99
        assert t.events() == []
        with t.span("rpc.push"):
            time.sleep(0.01)  # 10 ms >> windowed p99 (~1 ms)
        assert t.events(), "p99-breaching trace must promote"

    def test_pending_stays_bounded_under_leaked_roots(self, tmp_path):
        t = _mk_dropall(
            tmp_path, k=0, min_window_count=10_000, max_pending=8,
        )
        # 30 distinct traces buffer a child event (lazy pending entry)
        # and their roots never exit: the pending table caps at 8 — the
        # oldest seal unpromoted instead of accumulating forever
        for i in range(30):
            root = t.span(f"leak.{i}")
            root.__enter__()
            with t.span("child"):
                pass
            trace._current.span = None  # abandon the root: it leaks
        assert len(t.tail._pending) <= 8

    def test_limbo_ring_stays_bounded(self, tmp_path):
        t = _mk_dropall(
            tmp_path, k=0, min_window_count=10_000, limbo_events=64,
        )
        for i in range(60):  # 60 unpromoted traces x 2 events = 120
            with t.span("rpc.push"):
                with t.span("child"):
                    pass
        assert t.events() == []  # nothing promoted, ring untouched
        assert len(t.tail.limbo_events()) <= 64

    def test_sealing_root_event_survives_max_events(self, tmp_path):
        # a trace that overflows its per-trace buffer must still keep
        # its ROOT span event: a promoted trace without its root is
        # unstitchable by the critical-path engine
        t = _mk_dropall(
            tmp_path, k=1, min_window_count=10_000, max_events=4,
        )
        with t.span("rpc.push") as root:
            for i in range(10):  # overflow the buffer with children
                with t.span(f"child.{i}"):
                    pass
            time.sleep(0.002)
        evs = t.events()
        assert evs, "overflowed trace still promotes"
        assert any(
            e["name"] == "rpc.push"
            and e["args"]["span_id"] == root.span_id
            for e in evs
        ), [e["name"] for e in evs]

    def test_heal_retry_instant_reaches_pending_traces(self, tmp_path):
        # the heal runs on a span-less reader thread: the explicit-ctx
        # instant must still mark the stranded trace anomalous
        t = _mk_dropall(tmp_path, k=0, min_window_count=10_000)
        with t.span("rpc.push") as sp:
            ctx = {"tid": sp.trace_id, "sid": sp.span_id}
            # emitted from "another thread": no live span bound
            prev = trace._current.span
            trace._current.span = None
            try:
                t.instant("rpc.retry", cat="rpc", ctx=ctx)
            finally:
                trace._current.span = prev
        assert t.events(), "ctx-bound anomaly instant must promote"

    def test_promotion_fires_flightrec_event(self, tmp_path):
        from parameter_server_tpu.utils import flightrec

        flightrec.configure(str(tmp_path), process_name="tail-fr")
        try:
            t = _mk_dropall(tmp_path, k=1, min_window_count=10_000)
            with t.span("rpc.push"):
                time.sleep(0.002)
            assert any(
                e[2] == "trace.promote" for e in flightrec.events()
            )
        finally:
            flightrec.configure(None)


class TestHeadSamplingRescue:
    """Satellite regression: ``[trace] sample=16`` decides keep/drop at
    trace START; without tail capture the slowest push dies before it
    can matter. With it, the slowest push is ALWAYS exported."""

    def test_slowest_push_always_exported_under_sample_16(self, tmp_path):
        t = trace.configure(
            str(tmp_path), process_name="rescue", sample=16, tail=True,
        )
        try:
            for _ in range(100):
                with trace.span("rpc.push", cat="rpc"):
                    pass
            with trace.span("rpc.push", cat="rpc") as slow:
                time.sleep(0.02)
            slow_tid = slow.trace_id
            assert any(
                e["args"].get("trace_id") == slow_tid
                for e in t.events()
            ), "the slowest push must be in the export ring"
            # and it survives to the exported file
            path = t.flush()
            doc = json.loads(Path(path).read_text())
            assert any(
                (e.get("args") or {}).get("trace_id") == slow_tid
                for e in doc["traceEvents"]
            )
        finally:
            trace.configure(None)

    def test_tail_off_keeps_the_old_head_sampling(self, tmp_path):
        # the pre-ISSUE-15 contract is still selectable: tail=False
        # brings back pure head sampling (dropped stays dropped)
        t = trace.configure(
            str(tmp_path), process_name="plain", sample=4, tail=False,
        )
        try:
            sp = t.span("rpc.push")
            while t._keep(sp.trace_id):
                sp = t.span("rpc.push")
            assert isinstance(sp, trace._DroppedSpan)
        finally:
            trace.configure(None)


def _tev(name, ph, ts, dur=None, pid=100, tid=None, span=None,
         parent=None, **args):
    a = dict(args)
    if tid is not None:
        a["trace_id"] = tid
    if span is not None:
        a["span_id"] = span
    if parent is not None:
        a["parent_id"] = parent
    e = {"name": name, "cat": "t", "ph": ph, "ts": ts, "pid": pid,
         "tid": 1, "args": a}
    if dur is not None:
        e["dur"] = dur
    if ph == "f":
        e["id"] = "f-" + (tid or "x")
        e["bp"] = "e"
    return e


def _push_chain(tid, t0=0.0, wire_us=7000.0, skew_us=0.0):
    """One synthetic cross-process push: 10 ms total, ``wire_us`` on the
    forward wire, batched apply, withheld reply. ``skew_us`` shifts the
    server clock (positive = server clock behind the client's)."""
    sk = -skew_us
    return [
        _tev("ps.push", "X", t0, dur=300, tid=tid, span="root"),
        _tev("rpc.push", "X", t0 + 50, dur=150, tid=tid, span="rpc",
             parent="root"),
        _tev("rpc.serve.push", "X", t0 + 200 + wire_us + sk, dur=400,
             pid=200, tid=tid, span="srv", parent="rpc"),
        _tev("server.updater", "X", t0 + 1100 + wire_us + sk, dur=200,
             pid=200, tid=tid, span="upd"),
        _tev("ps.push.inflight", "f", t0 + 10000, tid=tid,
             parent="root"),
    ]


class TestCritpathTrace:
    def test_segments_and_attribution_cover_the_op(self):
        ops = critpath.ops_from_trace(_push_chain("t1"))
        assert len(ops) == 1
        op = ops[0]
        assert op["cmd"] == "push" and not op["skewed"]
        assert op["dur_ms"] == pytest.approx(10.0)
        seg = op["segments"]
        assert seg["wire"] == pytest.approx(7.0, abs=0.3)
        assert seg["server"] == pytest.approx(0.4)
        assert seg["apply_wait"] == pytest.approx(0.5)
        assert seg["apply"] == pytest.approx(0.2)
        assert seg["reply_lane"] > 0  # the withheld-reply tail
        # the acceptance bar: >= 90% of wall time lands in NAMED
        # segments (the 'other' honesty column stays small)
        named = sum(v for k, v in seg.items() if k != "other")
        assert named / op["dur_ms"] >= 0.90
        assert op["pct"]["wire"] == max(op["pct"].values())

    def test_retry_trace_still_segmentable(self):
        # a healed push: retry instant + a second serve span (the
        # resend); the engine picks the critical (last-ending) chain
        tid = "t-retry"
        evs = _push_chain(tid)
        evs.append(_tev("rpc.retry", "i", 300, tid=tid, parent="rpc"))
        evs.append(
            _tev("rpc.serve.push", "X", 8200, dur=300, pid=200,
                 tid=tid, span="srv2", parent="rpc")
        )
        ops = critpath.ops_from_trace(evs)
        assert len(ops) == 1
        assert ops[0]["segments"]["wire"] >= 7.0  # resend chain's wire
        assert not ops[0]["skewed"]

    def test_clock_skew_clamps_and_flags(self):
        # server clock 50 ms behind: serve.ts < rpc end -> raw wire
        # negative. The satellite contract: clamp + flag, never report
        # negative attribution.
        ops = critpath.ops_from_trace(
            _push_chain("t-skew", skew_us=50_000.0)
        )
        assert len(ops) == 1
        op = ops[0]
        assert op["skewed"] is True
        assert all(v >= 0 for v in op["segments"].values())
        agg = critpath.aggregate(ops)
        assert agg["push"]["skewed"] == 1

    def test_step_op_carries_ssp_wait(self):
        tid = "t-step"
        evs = [
            _tev("step", "X", 0, dur=10_000, tid=tid, span="stp"),
            _tev("step.ssp_wait", "X", 100, dur=6_000, tid=tid,
                 span="w", parent="stp"),
            _tev("step.pull", "X", 6_200, dur=2_000, tid=tid,
                 span="p", parent="stp"),
            _tev("step.compute", "X", 8_300, dur=1_500, tid=tid,
                 span="c", parent="stp"),
        ]
        ops = critpath.ops_from_trace(evs)
        assert len(ops) == 1 and ops[0]["cmd"] == "step"
        assert ops[0]["segments"]["ssp_wait"] == pytest.approx(6.0)

    def test_sidecar_rescue_completes_the_cross_process_op(self, tmp_path):
        # client promoted (main file); server only limbo'd (sidecar):
        # the loader rescues the server half, segmentation is complete
        chain = _push_chain("t-resc")
        client = [e for e in chain if e["pid"] == 100]
        server = [e for e in chain if e["pid"] == 200]
        (tmp_path / "trace-worker-0-100.json").write_text(
            json.dumps({"traceEvents": client})
        )
        (tmp_path / "tracetail-server-0-200.json").write_text(
            json.dumps({"traceEvents": server})
        )
        s = critpath.analyze_dir(str(tmp_path))
        assert s["mode"] == "trace" and s["ops"] == 1
        assert "server" in s["cmds"]["push"]["slowest"][0]["segments"]
        # an unrelated sidecar trace is NOT pulled in
        evs = critpath.load_trace_dir(str(tmp_path))
        assert {e["args"]["trace_id"] for e in evs} == {"t-resc"}


def _bb_ev(ts, proc, pid, etype, **args):
    return {"ts": ts, "proc": proc, "pid": pid, "tid": 1,
            "etype": etype, "args": args}


class TestCritpathBlackbox:
    def _chain(self, skew_s=0.0):
        return [
            _bb_ev(10.000, "worker-0", 1, "rpc.issue", cmd="push",
                   cid="c1", seq=1),
            _bb_ev(10.004 - skew_s, "server-0", 2, "rpc.in", cmd="push",
                   cid="c1", seq=1, n=64),
            _bb_ev(10.006 - skew_s, "server-0", 2, "apply.commit",
                   ver=2, pushes=1, pairs=[["c1", 1]]),
            _bb_ev(10.010, "worker-0", 1, "rpc.reply", cmd="push",
                   cid="c1", seq=1, ok=True),
        ]

    def test_cid_seq_chain_segments(self):
        ops = critpath.ops_from_blackbox(self._chain())
        assert len(ops) == 1
        op = ops[0]
        assert op["cmd"] == "push" and op["procs"] == 2
        assert op["dur_ms"] == pytest.approx(10.0)
        assert op["segments"]["wire"] == pytest.approx(4.0)
        assert op["segments"]["server"] == pytest.approx(2.0)
        assert op["segments"]["reply_lane"] == pytest.approx(4.0)
        assert not op["skewed"]

    def test_skewed_dumps_clamp_and_flag(self):
        """The satellite's skewed-dumps unit: a server clock 50 ms ahead
        reorders the chain (rpc.in before rpc.issue) — segments clamp
        to zero and the op is flagged, with no negative durations."""
        ops = critpath.ops_from_blackbox(self._chain(skew_s=0.05))
        assert len(ops) == 1
        op = ops[0]
        assert op["skewed"] is True
        assert all(v >= 0 for v in op["segments"].values())
        assert sum(
            op["segments"].values()
        ) == pytest.approx(op["dur_ms"], abs=0.01)

    def test_healed_resend_chain_does_not_crash(self):
        # heal resends deliver a second rpc.in; the reply is the LAST
        # one — the chain still segments (first-in to commit)
        evs = self._chain()
        evs.insert(2, _bb_ev(10.005, "server-0", 2, "rpc.in",
                             cmd="push", cid="c1", seq=1, n=64))
        ops = critpath.ops_from_blackbox(evs)
        assert len(ops) == 1
        assert ops[0]["segments"]["wire"] == pytest.approx(4.0)

    def test_analyze_dir_detects_blackbox(self, tmp_path):
        dump = {
            "schema": "psbb/1", "process": "worker-0", "pid": 1,
            "reason": "exit", "wall_time": 10.0,
            "events": [
                [e["ts"], 1, e["etype"], e["args"]]
                for e in self._chain() if e["proc"] == "worker-0"
            ],
            "threads": [],
        }
        dump2 = dict(dump, process="server-0", pid=2, events=[
            [e["ts"], 1, e["etype"], e["args"]]
            for e in self._chain() if e["proc"] == "server-0"
        ])
        (tmp_path / "blackbox-worker-0-1.json").write_text(
            json.dumps(dump)
        )
        (tmp_path / "blackbox-server-0-2.json").write_text(
            json.dumps(dump2)
        )
        s = critpath.analyze_dir(str(tmp_path))
        assert s["mode"] == "blackbox"
        assert s["cmds"]["push"]["n"] == 1


class TestSlowOps:
    def test_svc_echo_splits_wall_time(self):
        so = SlowOps(k=4, window_s=60.0)
        so.observe("push", 0.010, svc_us=2000, apw_us=500, apl_us=300,
                   tid="abc")
        rec = so.snapshot()["push"][0]
        assert rec["seg"]["wire"] == pytest.approx(8.0)
        assert rec["seg"]["server"] == pytest.approx(1.2)
        assert rec["seg"]["apply_wait"] == pytest.approx(0.5)
        assert rec["seg"]["apply"] == pytest.approx(0.3)
        assert rec["tid"] == "abc"

    def test_topk_bound_and_expiry(self):
        so = SlowOps(k=2, window_s=0.2)
        for i in range(10):
            so.observe("push", 0.001 * (i + 1))
        snap = so.snapshot()
        assert len(snap["push"]) == 2
        assert snap["push"][0]["dur_ms"] == pytest.approx(10.0)
        time.sleep(0.25)
        assert so.snapshot() == {}  # the window moved on

    def test_stale_giants_do_not_hold_slots(self):
        # records are duration-sorted, so expiry must scan the whole
        # list: expired slow records must neither evict live ones nor
        # fast-reject new in-window records against a dead floor
        so = SlowOps(k=2, window_s=0.2)
        so.observe("push", 0.5)
        so.observe("push", 0.5)  # two giants fill the top-K
        time.sleep(0.25)  # ...and expire
        so.observe("push", 0.002)  # would lose to the dead floor
        snap = so.snapshot()
        assert len(snap["push"]) == 1
        assert snap["push"][0]["dur_ms"] == pytest.approx(2.0)

    def test_rpc_reply_echo_feeds_global_slow_ops(self):
        """End-to-end over a real loopback RPC: the reply's _svc_us
        echo lands in the process-global slow_ops records."""
        from parameter_server_tpu.parallel.control import (
            RpcClient,
            RpcServer,
        )

        def handler(h, arrays):
            time.sleep(0.002)
            return {"ok": True}, {}

        slow_ops.reset()
        srv = RpcServer(handler).start()
        cli = RpcClient(srv.address)
        try:
            cli.call("echo")
            recs = slow_ops.snapshot().get("echo")
            assert recs, "completion must record a slow-op entry"
            seg = recs[0].get("seg") or {}
            # the echoed service time covers the handler's 2 ms sleep
            assert seg.get("server", 0.0) >= 1.5
        finally:
            cli.close()
            srv.stop()
            slow_ops.reset()


class TestLiveWhylate:
    def _cluster_with_slow_block(self):
        from parameter_server_tpu.parallel.control import (
            ControlClient,
            Coordinator,
        )

        # the coordinator merges its OWN process snapshot too — clear
        # any slow-op records earlier tests' RPCs left in this process
        slow_ops.reset()
        coord = Coordinator()
        ctl = ControlClient(coord.address)
        nid = ctl.register("worker", rank=0)
        tel = {
            "counters": {}, "hists": {}, "timers": {},
            "slow": {"push": [{
                "cmd": "push", "dur_ms": 42.0, "ts": time.time(),
                "tid": "feedface00000000",
                "seg": {"wire": 39.0, "server": 2.0, "apply_wait": 0.6,
                        "apply": 0.4},
            }]},
        }
        ctl.beat(nid, {"telemetry": tel})
        return coord, ctl

    def test_merged_slow_block_and_top_line(self):
        from parameter_server_tpu.utils.slo import format_top

        coord, ctl = self._cluster_with_slow_block()
        try:
            rep = ctl.telemetry()
            slow = rep["merged"].get("slow") or {}
            assert slow["push"][0]["dur_ms"] == 42.0
            frame = format_top(rep, 30.0)
            assert "slowest push: 42.0ms" in frame
            assert "wire=39.0ms" in frame
            assert "tid=feedface00000000" in frame
        finally:
            ctl.close()
            coord.stop()

    def test_live_mode_rejects_baseline_flags(self):
        # live records have no per-segment p99 population: a baseline
        # gate there would silently pass everything (and
        # --update-baseline would vacate the committed budgets)
        from parameter_server_tpu.cli import main as cli_main

        with pytest.raises(SystemExit):
            cli_main([
                "whylate", "--scheduler", "127.0.0.1:1",
                "--baseline", "whylate_baseline.json",
            ])

    def test_cli_whylate_scheduler_mode(self, capsys):
        from parameter_server_tpu.cli import main as cli_main

        coord, ctl = self._cluster_with_slow_block()
        try:
            rc = cli_main([
                "whylate", "--scheduler", coord.address, "--json",
            ])
            assert rc == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["mode"] == "live"
            push = doc["cmds"]["push"]
            assert push["slowest"][0]["dur_ms"] == 42.0
            # the wire segment dominates the attribution
            att = push["attribution_pct"]
            assert max(att, key=att.get) == "wire"
        finally:
            ctl.close()
            coord.stop()


class TestExemplarsEndToEnd:
    def test_client_histogram_carries_trace_exemplar(self, tmp_path):
        """Latency histograms record the trace id of the max-latency
        observation (the metrics->trace link): a traced RPC's trace id
        appears as the client.<cmd> exemplar."""
        from parameter_server_tpu.kv.updaters import Sgd
        from parameter_server_tpu.parallel.multislice import (
            ServerHandle,
            ShardServer,
        )
        from parameter_server_tpu.utils.config import PSConfig
        from parameter_server_tpu.utils.keyrange import KeyRange

        # consume any exemplar window earlier armed-tracing tests left
        latency_histograms.snapshot(roll_exemplars=True)
        trace.configure(str(tmp_path), process_name="ex-test")
        try:
            srv = ShardServer(Sgd(eta=0.1), KeyRange(0, 1024)).start()
            handle = ServerHandle(
                srv.address, 0, 0, PSConfig(), range_size=1024
            )
            keys = np.arange(1, 9, dtype=np.int64)
            handle.push(keys, np.ones(8, dtype=np.float32))
            handle.shutdown()
            handle.close()
            snap = latency_histograms.snapshot()
            ex = snap["client.push"].get("ex")
            assert ex and ex.get("tid"), snap.get("client.push")
            # the exemplar's trace is a real recorded trace
            assert any(
                e["args"].get("trace_id") == ex["tid"]
                for e in trace.tracer.events()
            )
        finally:
            trace.configure(None)


class TestBaselineGate:
    """The CI contract: a capture gated by the COMMITTED baseline passes;
    a regression fails naming the segment, at the right tier."""

    def _capture(self, tmp_path) -> str:
        from parameter_server_tpu.kv.updaters import Sgd
        from parameter_server_tpu.parallel.multislice import (
            ServerHandle,
            ShardServer,
        )
        from parameter_server_tpu.utils.config import PSConfig
        from parameter_server_tpu.utils.keyrange import KeyRange

        tdir = tmp_path / "cap"
        tdir.mkdir()
        t = trace.configure(str(tdir), process_name="gate", tail=True)
        try:
            srv = ShardServer(Sgd(eta=0.1), KeyRange(0, 1024)).start()
            handle = ServerHandle(
                srv.address, 0, 0, PSConfig(), range_size=1024
            )
            keys = np.arange(1, 17, dtype=np.int64)
            g = np.ones(16, dtype=np.float32)
            for _ in range(8):
                handle.push(keys, g)
                handle.pull(keys)
            handle.shutdown()
            handle.close()
            t.flush()
        finally:
            trace.configure(None)
        return str(tdir)

    def test_committed_baseline_gates_green(self, tmp_path, capsys):
        from parameter_server_tpu.cli import main as cli_main

        cap = self._capture(tmp_path)
        rc = cli_main([
            "whylate", cap,
            "--baseline", str(REPO / "whylate_baseline.json"),
        ])
        out = capsys.readouterr().out
        assert "push" in out
        assert rc == 0, out

    def test_tight_baseline_fails_naming_the_segment(
        self, tmp_path, capsys
    ):
        from parameter_server_tpu.cli import main as cli_main

        cap = self._capture(tmp_path)
        tight = tmp_path / "tight.json"
        tight.write_text(json.dumps({
            "version": 1, "hard_factor": 2.0,
            "budgets_ms": {"push": {"wire": 0.00001}},
        }))
        rc = cli_main([
            "whylate", cap, "--baseline", str(tight), "--json",
        ])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 1  # hard tier: way past hard_factor x budget
        f = doc["baseline_findings"][0]
        assert (f["cmd"], f["segment"]) == ("push", "wire")
        assert f["tier"] == "error"

    def test_empty_capture_cannot_pass_the_gate(self, tmp_path):
        # zero stitched ops means the export broke — exiting 0 would
        # silently disarm the CI contract forever
        from parameter_server_tpu.cli import main as cli_main

        empty = tmp_path / "empty"
        empty.mkdir()
        with pytest.raises(SystemExit):
            cli_main([
                "whylate", str(empty),
                "--baseline", str(REPO / "whylate_baseline.json"),
            ])

    def test_update_baseline_requires_a_file(self, tmp_path):
        from parameter_server_tpu.cli import main as cli_main

        with pytest.raises(SystemExit):
            cli_main(["whylate", str(tmp_path), "--update-baseline"])

    def test_update_baseline_roundtrip(self, tmp_path, capsys):
        from parameter_server_tpu.cli import main as cli_main

        cap = self._capture(tmp_path)
        bl = tmp_path / "bl.json"
        rc = cli_main([
            "whylate", cap, "--baseline", str(bl), "--update-baseline",
        ])
        assert rc == 0
        doc = json.loads(bl.read_text())
        assert doc["budgets_ms"]["push"]
        # the capture that wrote the baseline passes it (2x slack)
        rc = cli_main(["whylate", cap, "--baseline", str(bl)])
        capsys.readouterr()
        assert rc == 0


class TestAcceptanceDrill:
    """The ISSUE 15 acceptance: live 2-process cluster, injected per-cmd
    delay fault, sample=16 — `cli whylate` attributes >= 90% of the
    slowest push's wall time to named segments, names the wire segment
    dominant, and the slowest push's FULL trace is exported."""

    def test_two_process_delay_fault_whylate_names_wire(
        self, tmp_path, capsys
    ):
        from parameter_server_tpu.cli import main as cli_main
        from parameter_server_tpu.parallel.multislice import ServerHandle
        from parameter_server_tpu.utils.config import PSConfig

        trace_dir = tmp_path / "traces"
        trace_dir.mkdir()
        env = dict(os.environ)
        env["PYTHONPATH"] = (
            str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
        )
        env[trace.TRACE_DIR_ENV] = str(trace_dir)
        env[trace.TRACE_SAMPLE_ENV] = "16"
        # every 5th push frame sleeps 600 ms server-side BEFORE
        # dispatch: client-observed latency blows up, server spans stay
        # fast — the signature of a wire/straggler fault. 600 ms also
        # dominates the first batch's jit compile (~130 ms on an idle
        # CPU, 217 ms seen beside five loaded xdist workers, where a
        # 200 ms delay lost to it), so the slowest push is a FAULTED one.
        env["PS_FAULT_PLAN"] = "delay,cmd=push,every=5,delay_s=0.6"
        child = subprocess.Popen(
            [sys.executable,
             str(HERE / "_whylate_child_server.py")],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        try:
            line = child.stdout.readline()
            assert line.startswith("ADDR "), line
            addr = line.split()[1]
            trace.configure(
                str(trace_dir), process_name="worker-0",
                sample=16, tail=True,
            )
            try:
                handle = ServerHandle(
                    addr, 0, 0, PSConfig(), range_size=4096
                )
                keys = np.arange(1, 33, dtype=np.int64)
                g = np.full(32, 0.1, dtype=np.float32)
                for _ in range(20):
                    handle.push(keys, g)
                handle.shutdown()
                handle.close()
                child.wait(timeout=60)
                trace.tracer.flush()
            finally:
                trace.configure(None)

            rc = cli_main(["whylate", str(trace_dir), "--json"])
            assert rc == 0
            doc = json.loads(capsys.readouterr().out)
            push = doc["cmds"]["push"]
            slowest = push["slowest"][0]
            # the slowest push is a delayed one (~600 ms vs ~1 ms)
            assert slowest["dur_ms"] >= 450.0
            seg = slowest["segments"]
            named = sum(v for k, v in seg.items() if k != "other")
            # >= 90% of its wall time attributed to NAMED segments
            assert named / slowest["dur_ms"] >= 0.90, seg
            # ...and the faulted segment is dominant
            assert max(seg, key=seg.get) == "wire", seg

            # the slowest push's FULL trace was exported under
            # sample=16: client AND server spans in the merged file
            merged = Path(trace.merge_trace_dir(str(trace_dir)))
            evs = [
                e for e in json.loads(
                    merged.read_text()
                )["traceEvents"]
                if (e.get("args") or {}).get("trace_id")
                == slowest["tid"]
            ]
            names = {e["name"] for e in evs}
            assert "ps.push" in names, names
            assert "rpc.serve.push" in names, names
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            child.stdout.close()


class TestConfigKnobs:
    def test_trace_tail_knobs_exist(self):
        from parameter_server_tpu.utils.config import TraceConfig

        cfg = TraceConfig()
        assert cfg.tail is True  # always-on where tracing is armed
        assert cfg.tail_k == 4
        assert cfg.tail_limbo == 8192

    def test_tail_is_a_noop_at_sample_1(self, tmp_path):
        # nothing is ever head-dropped at sample=1, so arming the layer
        # would only add per-event routing cost — configure gates it
        t = trace.configure(str(tmp_path), process_name="g", tail=True)
        assert t.tail is None
        t = trace.configure(
            str(tmp_path), process_name="g", sample=2, tail=True
        )
        assert t.tail is not None
        trace.configure(None)

    def test_env_tail_parsing(self, monkeypatch):
        monkeypatch.setenv(trace.TRACE_TAIL_ENV, "0")
        assert trace._env_tail_k() == 0
        monkeypatch.setenv(trace.TRACE_TAIL_ENV, "9")
        assert trace._env_tail_k() == 9
        monkeypatch.delenv(trace.TRACE_TAIL_ENV)
        assert trace._env_tail_k() == trace.DEFAULT_TAIL_K
