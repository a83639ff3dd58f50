"""Trace-event schema validation from a LIVE 2-process run (the tentpole
acceptance test): a worker process (this one) pushes/pulls against a shard
server spawned as a real OS child with tracing armed via PS_TRACE_DIR.
Both processes export Chrome trace-event JSON; the suite asserts strict
schema (monotonic ts, valid ph types, X durations) and that one logical
``push`` carries ONE trace id through the client span (worker file) and
the server dispatch + updater spans (server file)."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from parameter_server_tpu.utils import trace

_VALID_PH = {"X", "i", "M", "s", "f", "C"}


def _validate_chrome_trace(path: Path) -> list[dict]:
    """Strict-JSON Chrome trace-event checks; returns the event list."""
    doc = json.loads(path.read_text())  # strict JSON or die
    assert isinstance(doc, dict) and "traceEvents" in doc
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    last_ts = None
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in _VALID_PH, ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            continue
        assert ev["ts"] >= 0
        if last_ts is not None:  # export sorts: ts must be monotonic
            assert ev["ts"] >= last_ts
        last_ts = ev["ts"]
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] in ("s", "f"):  # flow arrows carry a linking id
            assert isinstance(ev["id"], str) and ev["id"]
        if ev["ph"] == "f":
            assert ev["bp"] == "e"  # enclosing-slice binding
        if ev["ph"] == "C":  # counter-track samples carry a numeric value
            assert isinstance(ev["args"]["value"], (int, float))
    return events


def _spans(events: list[dict], name: str) -> list[dict]:
    return [e for e in events if e.get("ph") == "X" and e["name"] == name]


class TestTwoProcessTrace:
    def test_push_trace_id_spans_both_processes(self, tmp_path):
        from parameter_server_tpu.parallel.multislice import ServerHandle
        from parameter_server_tpu.utils.config import PSConfig

        trace_dir = tmp_path / "traces"
        trace_dir.mkdir()
        repo = Path(__file__).resolve().parent.parent
        env = dict(os.environ)
        env["PYTHONPATH"] = str(repo) + os.pathsep + env.get("PYTHONPATH", "")
        env[trace.TRACE_DIR_ENV] = str(trace_dir)
        child = subprocess.Popen(
            [sys.executable, str(Path(__file__).parent / "_trace_child_server.py")],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        try:
            line = child.stdout.readline()  # "ADDR host:port"
            assert line.startswith("ADDR "), line
            addr = line.split()[1]

            trace.configure(str(trace_dir), process_name="worker-0")
            try:
                handle = ServerHandle(addr, 0, 0, PSConfig(), range_size=4096)
                keys = np.arange(1, 65, dtype=np.int64)
                g = np.full(len(keys), 0.5, dtype=np.float32)
                handle.push(keys, g)
                w = handle.pull(keys)
                np.testing.assert_allclose(w, -0.1 * g, rtol=1e-6)
                handle.shutdown()
                handle.close()
                child.wait(timeout=60)
                worker_path = Path(trace.tracer.flush())
            finally:
                trace.configure(None)  # restore the disabled default

            server_files = [
                p for p in trace_dir.glob("trace-server-0-*.json")
            ]
            assert server_files, list(trace_dir.iterdir())
            worker_ev = _validate_chrome_trace(worker_path)
            server_ev = _validate_chrome_trace(server_files[0])

            # the two processes export distinct pids (separate Perfetto
            # tracks when merged)
            wpids = {e["pid"] for e in worker_ev if e["ph"] == "X"}
            spids = {e["pid"] for e in server_ev if e["ph"] == "X"}
            assert wpids and spids and wpids.isdisjoint(spids)

            # one logical push = one trace id across processes:
            # ps.push (worker) -> rpc.push (worker) -> rpc.serve.push
            # (server) -> server.updater (server)
            push_spans = _spans(worker_ev, "ps.push")
            assert push_spans, [e["name"] for e in worker_ev]
            tid = push_spans[0]["args"]["trace_id"]
            client_rpc = [
                e for e in _spans(worker_ev, "rpc.push")
                if e["args"]["trace_id"] == tid
            ]
            assert client_rpc, "client rpc.push span missing from trace"
            serve = [
                e for e in _spans(server_ev, "rpc.serve.push")
                if e["args"]["trace_id"] == tid
            ]
            assert serve, "server dispatch span did not join the trace"
            updater = [
                e for e in _spans(server_ev, "server.updater")
                if e["args"]["trace_id"] == tid
            ]
            assert updater, "updater span did not join the trace"
            # parent chain: dispatch's parent is the client rpc span
            assert serve[0]["args"]["parent_id"] == client_rpc[0]["args"]["span_id"]

            # the merged file is itself schema-valid and holds both pids
            merged = Path(trace.merge_trace_dir(str(trace_dir)))
            merged_ev = _validate_chrome_trace(merged)
            assert {e["pid"] for e in merged_ev if e["ph"] == "X"} >= wpids | spids
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            child.stdout.close()


class TestFlowEvents:
    """Span links for in-flight push futures (the PR-2 ROADMAP item):
    every async push emits a flow-start inside its issue span and a
    flow-end at completion — same id, so Perfetto draws the arrow across
    the in-flight window (and across threads)."""

    def test_push_async_emits_matched_flow_pairs(self, tmp_path):
        import numpy as np

        from parameter_server_tpu.kv.updaters import Sgd
        from parameter_server_tpu.parallel.multislice import (
            ServerHandle,
            ShardServer,
        )
        from parameter_server_tpu.utils.config import PSConfig
        from parameter_server_tpu.utils.keyrange import KeyRange

        trace.configure(str(tmp_path), process_name="flow-test")
        try:
            srv = ShardServer(Sgd(eta=0.1), KeyRange(0, 1024)).start()
            handle = ServerHandle(
                srv.address, 0, 0, PSConfig(), range_size=1024
            )
            keys = np.arange(1, 33, dtype=np.int64)
            g = np.ones(32, dtype=np.float32)
            futs = [handle.push_async(keys, g) for _ in range(5)]
            for f in futs:
                f.result(timeout=30)
            w = handle.pull_async(keys).result(timeout=30)
            assert w.shape == (32,)
            handle.shutdown()
            handle.close()
            path = Path(trace.tracer.flush())
        finally:
            trace.configure(None)
        events = _validate_chrome_trace(path)
        starts = [e for e in events if e["ph"] == "s"]
        ends = [e for e in events if e["ph"] == "f"]
        push_starts = [e for e in starts if e["name"] == "ps.push.inflight"]
        assert len(push_starts) == 5
        # every flow start has exactly one matching end: same id AND name
        end_ids = {(e["name"], e["id"]) for e in ends}
        for s in starts:
            assert (s["name"], s["id"]) in end_ids, s
        assert len(end_ids) == len(starts)
        # the flow start rides the issue span's trace (args carry its ids)
        issue_spans = {
            e["args"]["span_id"]: e["args"]["trace_id"]
            for e in _spans(events, "ps.push")
        }
        for s in push_starts:
            assert s["args"]["parent_id"] in issue_spans
            assert s["args"]["trace_id"] == issue_spans[s["args"]["parent_id"]]

    def test_flow_api_disabled_is_free(self):
        t = trace.Tracer(None)
        fid = t.flow_start("nope", cat="x")
        assert fid is None
        t.flow_end("nope", cat="x", flow_id=fid)  # no-op on the None id
        assert t.events() == []


class TestDisabledTracingIsFree:
    def test_noop_path_allocates_no_spans(self):
        t = trace.Tracer(None)
        s1 = t.span("hot.path", cat="step", keys=128)
        s2 = t.span("other")
        # ONE process-global singleton — no Span object, no args dict kept
        assert s1 is s2 is trace._NOOP
        with s1 as s:
            s.set(bytes=4096)  # no-op, no storage
        assert t.events() == []
        assert t.wire_context() is None
        assert t.activate({"tid": "x", "sid": "y"}) is trace._NOOP
        t.instant("nope")
        assert t.events() == []
        assert t.flush() is None

    def test_noop_is_reference_stable_across_calls(self):
        # the disabled global tracer hands out the identical object every
        # time: the hot-path cost is one method call, zero allocations of
        # spans (the "tracing disabled is free" contract the hot path relies on)
        t = trace.Tracer(None)
        assert len({id(t.span(f"s{i}")) for i in range(100)}) == 1

    def test_traced_decorator_free_when_disabled(self):
        calls = []

        @trace.traced("decorated.fn")
        def fn(x):
            calls.append(x)
            return x + 1

        assert fn(1) == 2 and calls == [1]
        assert trace.tracer.events() == []


class TestTracerEnabled:
    @pytest.fixture
    def armed(self, tmp_path):
        t = trace.configure(str(tmp_path), process_name="t")
        yield t
        trace.configure(None)

    def test_nesting_and_parent_ids(self, armed):
        with trace.span("outer", cat="a") as o:
            with trace.span("inner", cat="b") as i:
                assert i.trace_id == o.trace_id
                assert i.parent_id == o.span_id
        evs = armed.events()
        names = [e["name"] for e in evs]
        assert names == ["inner", "outer"]  # recorded at exit

    def test_wire_context_roundtrip_in_process(self, armed):
        with trace.span("client.side") as c:
            ctx = trace.wire_context()
            assert ctx == {"tid": c.trace_id, "sid": c.span_id}
        with trace.activate(ctx), trace.span("server.side") as s:
            assert s.trace_id == c.trace_id
            assert s.parent_id == c.span_id

    def test_ring_buffer_bounded(self, tmp_path):
        t = trace.Tracer(str(tmp_path), capacity=8)
        for i in range(50):
            with t.span(f"s{i}"):
                pass
        assert len(t.events()) == 8
        assert t.events()[-1]["name"] == "s49"  # newest kept

    def test_export_schema_and_error_annotation(self, armed, tmp_path):
        with pytest.raises(ValueError):
            with trace.span("boom"):
                raise ValueError("x")
        with trace.span("ok", answer=42):
            time.sleep(0.001)
        path = Path(armed.flush())
        evs = _validate_chrome_trace(path)
        by_name = {e["name"]: e for e in evs if e["ph"] == "X"}
        assert "error" in by_name["boom"]["args"]
        assert by_name["ok"]["args"]["answer"] == 42
        assert by_name["ok"]["dur"] >= 900  # ~the 1ms sleep, in us

    def test_instant_rides_current_trace(self, armed):
        with trace.span("call") as c:
            trace.instant("rpc.retry", attempt=1)
        inst = [e for e in armed.events() if e["ph"] == "i"]
        assert inst and inst[0]["args"]["trace_id"] == c.trace_id

    def test_counter_events_export_as_perfetto_counter_track(
        self, armed
    ):
        """The PR-2 ROADMAP leftover: numeric series (queue depth, batch
        size) export as Chrome ``"C"`` counter events so Perfetto draws
        them as stepped counter tracks next to the spans."""
        for v in (1, 4, 2):
            trace.counter("server.apply_queue_depth", v)
        path = Path(armed.flush())
        evs = _validate_chrome_trace(path)  # validator checks C shape
        cs = [e for e in evs if e["ph"] == "C"]
        assert [e["args"]["value"] for e in cs] == [1.0, 4.0, 2.0]
        assert all(e["name"] == "server.apply_queue_depth" for e in cs)

    def test_counter_disabled_is_free(self):
        # no buffer append, no error, when tracing is off
        trace.configure(None)
        trace.counter("x", 1)
        assert trace.tracer.events() == []

    def test_step_context_carries_onto_pool_threads(self, armed):
        # thread locals don't cross ThreadPoolExecutor: a captured wire
        # context re-activated on another thread (trace.activate — the
        # mechanism the async completion callbacks use) makes spans there
        # join the originating trace instead of starting their own
        from concurrent.futures import ThreadPoolExecutor

        def pool_side(ctx=None):
            with trace.activate(ctx), trace.span("ps.pull"):
                return True

        with ThreadPoolExecutor(max_workers=2) as pool:
            with trace.span("step") as stp:
                ctx = trace.wire_context()
                bare = pool.submit(pool_side).result()
                linked = pool.submit(pool_side, ctx).result()
            assert bare and linked
        pulls = _spans(armed.events(), "ps.pull")
        assert len(pulls) == 2
        tids = {e["args"]["trace_id"] for e in pulls}
        # one joined the step's trace, the bare one started its own
        assert stp.trace_id in tids and len(tids) == 2
        joined = [
            e for e in pulls if e["args"]["trace_id"] == stp.trace_id
        ]
        assert joined[0]["args"]["parent_id"] == stp.span_id


class TestHeadSampling:
    """[trace] sample = 1/N (ISSUE 6 satellite): head-based, keyed off
    the trace id — whole traces are kept or dropped, never fragments,
    and the decision is reproducible across processes."""

    def _root_ids(self, t):
        return {
            e["args"]["trace_id"]
            for e in t.events()
            if e.get("ph") == "X"
        }

    def test_sample_one_records_everything(self, tmp_path):
        t = trace.configure(str(tmp_path), process_name="s1", sample=1)
        try:
            for _ in range(20):
                with trace.span("root", cat="t"):
                    pass
            assert len(t.events()) == 20
        finally:
            trace.configure(None)

    def test_sample_n_drops_whole_traces(self, tmp_path):
        t = trace.configure(str(tmp_path), process_name="s4", sample=4)
        try:
            kept = 0
            for _ in range(200):
                with trace.span("root", cat="t"):
                    with trace.span("child", cat="t"):
                        trace.instant("tick", cat="t")
                before = kept
                kept = len(t.events())
                # a trace contributes all three events or none: sampling
                # never fragments one logical operation
                assert kept - before in (0, 3)
            # ~1/4 of 200 traces kept; generous bounds, id hash is uniform
            assert 0 < kept // 3 < 150
            # every recorded child belongs to a recorded root's trace
            roots = {
                e["args"]["trace_id"]
                for e in t.events()
                if e.get("ph") == "X" and e["name"] == "root"
            }
            for e in t.events():
                assert e["args"]["trace_id"] in roots
        finally:
            trace.configure(None)

    def test_decision_is_keyed_off_trace_id(self, tmp_path):
        """The same trace id gets the same verdict in any process: a
        remote child span under an activated context from a KEPT trace
        records; under a DROPPED trace's context it does not."""
        t = trace.configure(str(tmp_path), process_name="sk", sample=3)
        try:
            kept_ctx = dropped_ctx = None
            while kept_ctx is None or dropped_ctx is None:
                with trace.span("probe", cat="t") as sp:
                    ctx = trace.wire_context()
                if t._keep(sp.trace_id):
                    kept_ctx = kept_ctx or ctx
                else:
                    dropped_ctx = dropped_ctx or ctx
            n0 = len(t.events())
            with trace.activate(dropped_ctx):
                with trace.span("server.side", cat="t"):
                    pass
            assert len(t.events()) == n0  # dropped stays dropped remotely
            with trace.activate(kept_ctx):
                with trace.span("server.side", cat="t"):
                    pass
            assert len(t.events()) == n0 + 1
        finally:
            trace.configure(None)

    def test_dropped_trace_flow_api_returns_none(self, tmp_path):
        t = trace.configure(str(tmp_path), process_name="sf", sample=2)
        try:
            while True:
                sp = trace.span("root", cat="t")
                with sp:
                    fid = trace.flow_start("f", cat="t")
                    trace.flow_end("f", cat="t", flow_id=fid)
                if not t._keep(sp.trace_id):
                    break
            assert all(
                e["name"] != "f" or t._keep(e["args"]["trace_id"])
                for e in t.events()
            )
        finally:
            trace.configure(None)

    def test_env_var_arms_sampling(self, monkeypatch):
        monkeypatch.setenv(trace.TRACE_SAMPLE_ENV, "8")
        assert trace._env_sample() == 8
        monkeypatch.setenv(trace.TRACE_SAMPLE_ENV, "junk")
        assert trace._env_sample() == 1

    def test_config_knob_exists(self):
        from parameter_server_tpu.utils.config import TraceConfig

        assert TraceConfig().sample == 1
