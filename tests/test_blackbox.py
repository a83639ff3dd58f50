"""ISSUE 9: black-box flight recorder, stall watchdog, postmortem plane.

Covers, tier-1:

- the disabled recorder is an identity-pinned no-op (the overhead-guard
  contract: always-on instrumentation is free until armed);
- the armed ring is bounded and dumps atomically with thread stacks;
- the watchdog's busy-without-progress policy (fires once per episode,
  re-arms on progress) driven deterministically via ``poll(now=...)``;
- the ACCEPTANCE drills: an induced stall (patched-stuck apply thread)
  produces a dump whose postmortem names the stalled source and thread,
  and a SIGKILL'd 2-process cluster mid-window under frame chaos leaves
  boxes whose merged timeline stitches the same (cid, seq) across the
  client and server dumps and flags the induced anomaly;
- the anomaly detectors on synthetic dumps (acked-but-unapplied,
  version regression, shed storm, reconnect-without-heal);
- the per-key heat sketch (count-min + candidates, heartbeat merge,
  ``cli stats`` rendering) and the peak-gauge roll (peaks decay per
  telemetry snapshot instead of latching since boot).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from parameter_server_tpu.utils import flightrec
from parameter_server_tpu.utils import postmortem as pm
from parameter_server_tpu.utils.metrics import (
    KeyHeatSketch,
    format_cluster_stats,
    heat_top,
    key_heat,
    merge_heat_snapshots,
    merge_telemetry,
    telemetry_snapshot,
    wire_counters,
)


@pytest.fixture(autouse=True)
def _disarm_after():
    """Every test leaves the recorder exactly as tier-1 expects it:
    disarmed, with the identity-pinned no-op re-bound."""
    yield
    flightrec.configure(None)


def _wait_for(pred, what: str, timeout: float = 10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


class TestRecorder:
    def test_disabled_is_identity_pinned_noop(self):
        """The overhead-guard contract (ISSUE 9 satellite): while
        disarmed, the module-level ``record`` IS the no-op function —
        no event tuple, no ring, nothing allocated on the hot path —
        so permanent instrumentation on the wire/apply paths is free."""
        flightrec.configure(None)
        assert flightrec.record is flightrec._noop_record
        assert flightrec._buf is None
        flightrec.record("rpc.in", cmd="push", cid="c", seq=1)
        assert flightrec.events() == []
        assert not flightrec.enabled()

    def test_armed_ring_is_bounded_and_swaps_record(self, tmp_path):
        flightrec.configure(
            str(tmp_path), capacity=16, process_name="t-0",
            flush_interval_s=0, watchdog_interval_s=60,
        )
        assert flightrec.record is flightrec._live_record
        for i in range(100):
            flightrec.record("x", i=i)
        evs = flightrec.events()
        assert len(evs) == 16  # ring: newest 16 survive
        assert evs[-1][3] == {"i": 99}
        # disarm restores the pinned no-op
        flightrec.configure(None)
        assert flightrec.record is flightrec._noop_record

    def test_dump_schema_threads_and_telemetry(self, tmp_path):
        flightrec.configure(
            str(tmp_path), process_name="t-0",
            flush_interval_s=0, watchdog_interval_s=60,
        )
        flightrec.record("rpc.in", cmd="push", cid="c1", seq="k0")
        path = flightrec.dump("unit-test")
        assert path and os.path.exists(path)
        doc = json.loads(Path(path).read_text())
        assert doc["schema"] == "psbb/1"
        assert doc["process"] == "t-0" and doc["pid"] == os.getpid()
        assert doc["reason"] == "unit-test"
        assert "unit-test" in doc["trigger_reasons"]
        assert ["rpc.in"] == [e[2] for e in doc["events"]]
        assert doc["events"][0][3] == {"cmd": "push", "cid": "c1", "seq": "k0"}
        # thread stacks: the dumping (main) thread must be present with
        # a real stack — the "name the stalled thread" raw material
        names = {t["name"] for t in doc["threads"]}
        assert "MainThread" in names
        main = next(t for t in doc["threads"] if t["name"] == "MainThread")
        assert main["stack"] and "dump" in "".join(main["stack"])
        assert "counters" in doc["telemetry"]

    def test_periodic_flusher_persists_without_triggers(self, tmp_path):
        """The SIGKILL-survival property: the box lands on disk on the
        flush cadence, no trigger required."""
        flightrec.configure(
            str(tmp_path), process_name="t-0",
            flush_interval_s=0.05, watchdog_interval_s=60,
        )
        flightrec.record("x", i=1)
        path = tmp_path / f"blackbox-t-0-{os.getpid()}.json"
        _wait_for(path.exists, "periodic flush", timeout=5)
        doc = json.loads(path.read_text())
        assert doc["reason"] == "periodic"
        # the flusher's cadence never pollutes the trigger history
        assert "periodic" not in doc["trigger_reasons"]

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_thread_exception_dumps(self, tmp_path):
        flightrec.configure(
            str(tmp_path), process_name="t-0",
            flush_interval_s=0, watchdog_interval_s=60,
        )

        def boom():
            raise RuntimeError("induced")

        t = threading.Thread(target=boom, name="ps-test-boom")
        t.start()
        t.join()
        path = tmp_path / f"blackbox-t-0-{os.getpid()}.json"
        _wait_for(path.exists, "excepthook dump", timeout=5)
        doc = json.loads(path.read_text())
        assert any(
            r.startswith("thread-exception:ps-test-boom")
            for r in doc["trigger_reasons"]
        ), doc["trigger_reasons"]
        assert any(e[2] == "thread.exception" for e in doc["events"])


class TestWatchdog:
    def test_busy_without_progress_fires_once_then_rearms(self):
        wd = flightrec.Watchdog()
        wd.stall_timeout_s = 10.0
        state = {"busy": True, "prog": 0}
        wd.register("src", lambda: (state["busy"], state["prog"]))
        try:
            assert wd.poll(now=0.0) == []  # first sample establishes marks
            assert wd.poll(now=5.0) == []  # within the window
            before = wire_counters.get("watchdog_stalls")
            assert wd.poll(now=11.0) == ["src"]  # stalled past the window
            assert wire_counters.get("watchdog_stalls") == before + 1
            assert wd.poll(now=20.0) == []  # once per episode
            state["prog"] = 1  # progress resumes: episode over
            assert wd.poll(now=21.0) == []
            assert wd.poll(now=40.0) == ["src"]  # a NEW stall fires again
        finally:
            wd.unregister("src")
        assert wd.sources() == []

    def test_idle_and_advancing_sources_never_fire(self):
        wd = flightrec.Watchdog()
        wd.stall_timeout_s = 1.0
        state = {"busy": False, "prog": 0}
        wd.register("src", lambda: (state["busy"], state["prog"]))
        try:
            assert wd.poll(now=0.0) == []
            assert wd.poll(now=100.0) == []  # idle forever is not a stall
            state["busy"] = True
            for i, now in enumerate((101.0, 105.0, 109.0)):
                state["prog"] = i + 1  # busy but moving
                assert wd.poll(now=now) == []
        finally:
            wd.unregister("src")

    def test_dying_probe_is_skipped_not_fatal(self):
        wd = flightrec.Watchdog()

        def bad():
            raise ValueError("probe died")

        wd.register("bad", bad)
        try:
            assert wd.poll(now=0.0) == []
        finally:
            wd.unregister("bad")


class TestInducedStall:
    """Acceptance: a patched-stuck apply thread produces a dump and the
    postmortem names the stalled source and thread."""

    def test_stuck_apply_thread_dumped_and_named(self, tmp_path):
        from parameter_server_tpu.kv.updaters import Sgd
        from parameter_server_tpu.parallel.multislice import (
            ServerHandle,
            ShardServer,
        )
        from parameter_server_tpu.utils.config import PSConfig
        from parameter_server_tpu.utils.keyrange import KeyRange

        flightrec.configure(
            str(tmp_path), process_name="server-0",
            flush_interval_s=0,  # trigger dumps only: deterministic reason
            watchdog_interval_s=0.05, stall_timeout_s=0.25,
        )
        srv = ShardServer(Sgd(eta=0.1), KeyRange(0, 256))
        release = threading.Event()
        real_apply = srv._apply_batch

        def wedged(batch):
            release.wait(timeout=30)  # the induced stall
            real_apply(batch)

        srv._apply_batch = wedged
        srv.start()
        handle = ServerHandle(srv.address, 0, 0, PSConfig(), range_size=256)
        try:
            assert any(
                s.startswith("apply:") for s in flightrec.watchdog.sources()
            )
            keys = np.arange(1, 9, dtype=np.int64)
            fut = handle.push_async(keys, np.ones(len(keys), np.float32))
            path = tmp_path / f"blackbox-server-0-{os.getpid()}.json"
            doc = _wait_for(
                lambda: (
                    json.loads(path.read_text())
                    if path.exists() else None
                ),
                "stall dump", timeout=15,
            )
            _wait_for(
                lambda: any(
                    r.startswith("stall:apply:")
                    for r in json.loads(path.read_text())["trigger_reasons"]
                ),
                "apply stall reason", timeout=15,
            )
            release.set()
            fut.result(timeout=30)  # the wedge released: push still lands
        finally:
            release.set()
            handle.close()
            srv.server.stop()
        # the postmortem names the stalled source AND its thread
        out = pm.postmortem(str(tmp_path))
        stalls = [a for a in out["anomalies"] if a["kind"] == "stall"]
        assert any(
            a["source"].startswith("apply:0-256") and a["thread"] == "ps-apply"
            for a in stalls
        ), out["anomalies"]
        assert "stall" in out["report"] and "ps-apply" in out["report"]
        # the stalled thread's stack is in the box, parked in the wedge
        doc = json.loads(
            (tmp_path / f"blackbox-server-0-{os.getpid()}.json").read_text()
        )
        # several ps-apply threads may exist process-wide (other tests'
        # servers); the box must hold at least OURS, parked in the wedge
        apply_t = [t for t in doc["threads"] if t["name"] == "ps-apply"]
        assert apply_t
        assert any("wedged" in "".join(t["stack"]) for t in apply_t)


class TestCrashPostmortem:
    """Acceptance + satellite: SIGKILL a live 2-process cluster
    mid-window under frame chaos; the surviving boxes merge into one
    timeline that stitches the same (cid, seq) across the client and
    server dumps and flags the induced anomaly."""

    def test_killed_server_boxes_stitch_and_flag(self, tmp_path):
        from parameter_server_tpu.parallel.multislice import ServerHandle
        from parameter_server_tpu.utils.config import PSConfig

        box = tmp_path / "bb"
        box.mkdir()
        repo = Path(__file__).resolve().parent.parent
        env = dict(os.environ)
        env["PYTHONPATH"] = str(repo) + os.pathsep + env.get("PYTHONPATH", "")
        env[flightrec.BLACKBOX_DIR_ENV] = str(box)
        # frame chaos on the victim: delayed + duplicated frames while
        # the window is live (dedup keeps the applies exactly-once)
        env["PS_FAULT_PLAN"] = "delay,prob=0.2,delay_s=0.002;duplicate,every=7"
        env["PS_FAULT_SEED"] = "99"
        child = subprocess.Popen(
            [
                sys.executable,
                str(Path(__file__).parent / "_blackbox_child_server.py"),
            ],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        handle = None
        try:
            line = child.stdout.readline()
            assert line.startswith("ADDR "), (
                line, (child.stderr.read() or "")[-800:]
                if child.poll() is not None else "",
            )
            addr = line.split()[1]
            flightrec.configure(
                str(box), process_name="worker-0",
                flush_interval_s=0, watchdog_interval_s=60,
            )
            handle = ServerHandle(
                addr, 0, 0, PSConfig(), range_size=4096,
                reconnect_timeout_s=2.0,
            )
            keys = np.arange(1, 65, dtype=np.int64)
            g = np.full(len(keys), 0.5, dtype=np.float32)
            futs = [handle.push_async(keys, g) for _ in range(8)]
            for f in futs:
                f.result(timeout=30)
            handle.pull(keys)
            # let the child's periodic flusher persist the window
            time.sleep(0.3)
            child.send_signal(signal.SIGKILL)
            child.wait(timeout=30)
            # mid-window loss: the next push dies — conn_died, a heal
            # that never lands, ConnectionError (no resolver here)
            with pytest.raises((ConnectionError, OSError)):
                handle.push(keys, g)
            flightrec.dump("test-exit")
        finally:
            if handle is not None:
                handle.close()
            if child.poll() is None:
                child.kill()
                child.wait()
            child.stdout.close()
            child.stderr.close()
        out = pm.postmortem(str(box))
        assert out["processes"] == 2, out
        # cross-process stitching: the same (cid, seq) in BOTH boxes
        assert out["cross_process_calls"] >= 1, out
        dumps = pm.load_dumps(str(box))
        timeline = pm.merge_timeline(dumps)
        calls = pm.stitch_calls(timeline)
        cid = handle.client._cid
        stitched = [
            (k, {(e["proc"]) for e in evs})
            for k, evs in calls.items()
            if k[0] == cid and len({e["proc"] for e in evs}) >= 2
        ]
        assert stitched, sorted(calls)
        procs = set.union(*(s for _, s in stitched))
        assert procs == {"worker-0", "server-0"}, stitched
        # a stitched push shows the full causal chain: client issue ->
        # server frame in -> server commit -> client ack
        k, _ = stitched[0]
        etypes = {e["etype"] for e in calls[k]}
        assert "rpc.issue" in etypes and "rpc.in" in etypes, etypes
        applied = any(
            e["etype"] == "apply.commit"
            and [k[0], k[1]] in [list(map(str, p)) for p in e["args"].get("pairs", [])]
            for e in timeline
        )
        assert applied, "no apply.commit ledger for a stitched push"
        # the induced anomaly is flagged: the survivor's heal never landed
        kinds = {a["kind"]: a for a in out["anomalies"]}
        assert "reconnect-without-heal" in kinds, out["anomalies"]
        assert kinds["reconnect-without-heal"]["proc"] == "worker-0"
        # ... and the report names it
        assert "reconnect-without-heal" in out["report"]


def _mk_dump(proc, pid, events, reasons=("exit",), stall=None):
    return {
        "schema": "psbb/1", "process": proc, "pid": pid,
        "reason": reasons[-1], "trigger_reasons": list(reasons),
        "wall_time": 0.0,
        "events": events, "telemetry": {}, "threads": [], "stall": stall,
        "_file": f"blackbox-{proc}-{pid}.json",
    }


class TestAnomalyDetectors:
    def test_acked_but_unapplied_flagged(self):
        client = _mk_dump("worker-0", 1, [
            [1.0, 11, "rpc.issue", {"cmd": "push", "cid": "c1", "seq": "k0"}],
            [1.2, 11, "rpc.reply", {"cmd": "push", "cid": "c1", "seq": "k0",
                                    "ok": True}],
        ])
        server = _mk_dump("server-0", 2, [
            [1.1, 21, "rpc.in", {"cmd": "push", "cid": "c1", "seq": "k1"}],
            [1.15, 21, "apply.commit", {"ver": 7, "pushes": 1,
                                        "pairs": [["c1", "k1"]]}],
        ])
        tl = pm.merge_timeline([client, server])
        an = pm.find_anomalies([client, server], tl)
        flagged = [a for a in an if a["kind"] == "acked-but-unapplied"]
        assert flagged and flagged[0]["cid"] == "c1" and flagged[0]["seq"] == "k0"

    def test_applied_push_not_flagged(self):
        client = _mk_dump("worker-0", 1, [
            [1.0, 11, "rpc.reply", {"cmd": "push", "cid": "c1", "seq": "k0",
                                    "ok": True}],
        ])
        server = _mk_dump("server-0", 2, [
            [0.9, 21, "apply.commit", {"ver": 7, "pushes": 1,
                                       "pairs": [["c1", "k0"]]}],
        ])
        tl = pm.merge_timeline([client, server])
        an = pm.find_anomalies([client, server], tl)
        assert not [a for a in an if a["kind"] == "acked-but-unapplied"]

    def test_no_server_box_means_no_verdict(self):
        """Absence of the server's box is absence of evidence, not an
        anomaly — only judged when a surviving server dump saw the cid."""
        client = _mk_dump("worker-0", 1, [
            [1.0, 11, "rpc.reply", {"cmd": "push", "cid": "c1", "seq": "k0",
                                    "ok": True}],
        ])
        tl = pm.merge_timeline([client])
        an = pm.find_anomalies([client], tl)
        assert not [a for a in an if a["kind"] == "acked-but-unapplied"]

    def test_version_regression_flagged(self):
        server = _mk_dump("server-0", 2, [
            [1.0, 21, "rcu.publish", {"ver": 100}],
            [1.1, 21, "rcu.publish", {"ver": 101}],
            [1.2, 21, "rcu.publish", {"ver": 99}],
        ])
        an = pm.find_anomalies([server], pm.merge_timeline([server]))
        reg = [a for a in an if a["kind"] == "version-regression"]
        assert reg and reg[0]["from"] == 101 and reg[0]["to"] == 99

    def test_shed_storm_flagged(self):
        events = [
            [1.0 + i * 0.01, 21, "serve.shed", {"sig": "s"}]
            for i in range(12)
        ]
        server = _mk_dump("server-0", 2, events)
        an = pm.find_anomalies([server], pm.merge_timeline([server]))
        storm = [a for a in an if a["kind"] == "shed-storm"]
        assert storm and storm[0]["count"] >= 10
        # a slow trickle is not a storm
        slow = _mk_dump("server-0", 2, [
            [1.0 + i * 0.5, 21, "serve.shed", {"sig": "s"}] for i in range(12)
        ])
        an2 = pm.find_anomalies([slow], pm.merge_timeline([slow]))
        assert not [a for a in an2 if a["kind"] == "shed-storm"]

    def test_reconnect_without_heal_flagged(self):
        w = _mk_dump("worker-0", 1, [
            [1.0, 11, "rpc.conn_died", {"addr": "a", "cid": "c1", "gen": 1}],
            [1.1, 11, "rpc.heal.begin", {"addr": "a", "cid": "c1"}],
            [3.1, 11, "rpc.heal.failed", {"addr": "a", "cid": "c1"}],
        ])
        an = pm.find_anomalies([w], pm.merge_timeline([w]))
        flagged = [a for a in an if a["kind"] == "reconnect-without-heal"]
        assert flagged and flagged[0]["failed"] == 1
        # a heal that LANDED is healthy self-healing, not an anomaly
        healed = _mk_dump("worker-0", 1, [
            [1.1, 11, "rpc.heal.begin", {"addr": "a", "cid": "c1"}],
            [1.3, 11, "rpc.healed", {"addr": "a", "cid": "c1", "resent": 4}],
        ])
        an2 = pm.find_anomalies([healed], pm.merge_timeline([healed]))
        assert not [a for a in an2 if a["kind"] == "reconnect-without-heal"]

    def test_stall_dump_surfaces(self):
        d = _mk_dump(
            "server-0", 2, [], reasons=("stall:apply:0-4096",),
            stall={"source": "apply:0-4096", "thread": "ps-apply",
                   "stalled_s": 1.5},
        )
        an = pm.find_anomalies([d], [])
        assert an and an[0]["kind"] == "stall"
        assert an[0]["source"] == "apply:0-4096"
        assert an[0]["thread"] == "ps-apply"


class TestPostmortemRendering:
    def test_trace_export_is_perfetto_loadable_shape(self, tmp_path):
        d1 = _mk_dump("worker-0", 1, [
            [1.0, 11, "rpc.issue", {"cmd": "push", "cid": "c", "seq": 1}],
        ])
        d2 = _mk_dump("server-0", 2, [
            [1.05, 21, "rpc.in", {"cmd": "push", "cid": "c", "seq": 1}],
        ])
        d2["threads"] = [{"name": "ps-apply", "ident": 21, "native_id": 9,
                          "daemon": True, "stack": []}]
        out = tmp_path / "bb-trace.json"
        path = pm.export_trace([d1, d2], pm.merge_timeline([d1, d2]), str(out))
        doc = json.loads(Path(path).read_text())
        evs = doc["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert {"worker-0", "server-0"} <= {
            m["args"]["name"] for m in metas if m["name"] == "process_name"
        }
        # the server thread keeps its dump-recovered name
        assert any(
            m["name"] == "thread_name" and m["args"]["name"] == "ps-apply"
            for m in metas
        )
        insts = [e for e in evs if e["ph"] == "i"]
        assert len(insts) == 2
        assert all(e["cat"] == "blackbox" and "ts" in e for e in insts)
        # ts ascending (the exporter's contract)
        ts = [e["ts"] for e in insts]
        assert ts == sorted(ts)

    def test_cli_postmortem_subcommand(self, tmp_path, capsys):
        from parameter_server_tpu.cli import main as cli_main

        flightrec.configure(
            str(tmp_path), process_name="t-0",
            flush_interval_s=0, watchdog_interval_s=60,
        )
        flightrec.record("rpc.in", cmd="push", cid="c", seq=1)
        flightrec.dump("exit")
        flightrec.configure(None)
        rc = cli_main(["postmortem", str(tmp_path)])
        assert rc == 0  # no anomalies
        got = capsys.readouterr().out
        assert "postmortem over 1 process box(es)" in got
        summary = json.loads(got.strip().splitlines()[-1])
        assert summary["processes"] == 1 and summary["anomalies"] == []


class TestKeyHeat:
    def test_sketch_counts_and_candidates(self):
        sk = KeyHeatSketch(width=256, depth=2, hot_min=4, hot_cap=8)
        sk.add(np.array([3] * 10 + [9] * 2, np.int64))
        assert int(sk.count(np.array([3]))[0]) >= 10
        snap = sk.snapshot()
        assert snap["n"] == 12
        assert "3" in snap["hot"] and "9" not in snap["hot"]
        top = heat_top(snap, 5)
        assert top[0][0] == 3 and top[0][1] >= 10

    def test_merge_sums_and_requeries(self):
        a = KeyHeatSketch(width=256, depth=2, hot_min=4)
        b = KeyHeatSketch(width=256, depth=2, hot_min=4)
        a.add(np.array([7] * 6, np.int64))
        b.add(np.array([7] * 5 + [11] * 4, np.int64))
        m = merge_heat_snapshots([a.snapshot(), b.snapshot()])
        assert m["n"] == 15
        top = dict(heat_top(m, 5))
        assert top[7] >= 11  # count-min never under-counts the merge
        assert top.get(11, 0) >= 4

    def test_server_pull_push_feed_the_global_sketch(self):
        from parameter_server_tpu.kv.updaters import Sgd
        from parameter_server_tpu.parallel.multislice import (
            ServerHandle,
            ShardServer,
        )
        from parameter_server_tpu.utils.config import PSConfig
        from parameter_server_tpu.utils.keyrange import KeyRange

        key_heat.reset()
        srv = ShardServer(Sgd(eta=0.1), KeyRange(100, 612))
        srv.start()
        handle = ServerHandle(srv.address, 0, 0, PSConfig(), range_size=512)
        try:
            keys = np.arange(0, 8, dtype=np.int64)  # range-relative
            for _ in range(5):
                handle.push(keys, np.ones(len(keys), np.float32))
                handle.pull(keys)
        finally:
            handle.close()
            srv.server.stop()
        # heat is keyed by GLOBAL ids: range begin + relative key
        assert int(key_heat.count(np.array([100]))[0]) >= 5
        assert int(key_heat.count(np.array([0]))[0]) == 0
        snap = telemetry_snapshot()
        assert snap.get("key_heat", {}).get("n", 0) > 0
        # the heartbeat merge + dashboard path renders hot keys
        merged = merge_telemetry([snap, snap])
        txt = format_cluster_stats({"nodes": {}, "merged": merged})
        assert "hot keys" in txt
        key_heat.reset()

    def test_saturated_snapshot_degrades_to_candidates(self):
        sk = KeyHeatSketch(width=64, depth=2, hot_min=2)
        sk._SNAP_MAX_NNZ = 8
        sk.add(np.arange(1000, dtype=np.int64))
        sk.add(np.arange(1000, dtype=np.int64))
        snap = sk.snapshot()
        assert snap.get("saturated") and "rows" not in snap
        m = merge_heat_snapshots([snap, snap])
        assert m.get("saturated")
        assert heat_top(m, 3)  # candidates still answer


class TestPeakGaugeRoll:
    @pytest.fixture(autouse=True)
    def _fresh_counters(self):
        """wire_counters is process-global and its cumulative peaks outlive
        the test that made them: whatever file this worker ran before may
        have left a deeper one. Pin each test to a zero baseline."""
        wire_counters.reset()
        yield
        wire_counters.reset()

    def test_peaks_decay_per_telemetry_snapshot(self):
        """ISSUE 9 satellite: max-merging gauges must show
        peak-since-last-snapshot in cli stats, not peak-since-boot."""
        wire_counters.observe_max("wire_withheld_bytes_peak", 12345)
        s1 = wire_counters.snapshot(roll_peaks=True)
        assert s1["wire_withheld_bytes_peak"] == 12345
        s2 = wire_counters.snapshot(roll_peaks=True)
        assert s2["wire_withheld_bytes_peak"] == 0  # decayed: spike is over
        wire_counters.observe_max("wire_withheld_bytes_peak", 77)
        s3 = wire_counters.snapshot(roll_peaks=True)
        assert s3["wire_withheld_bytes_peak"] == 77  # fresh window's peak
        # cumulative view (tests, process-exit reporting) is untouched
        assert wire_counters.get("wire_withheld_bytes_peak") == 12345
        assert wire_counters.snapshot()["wire_withheld_bytes_peak"] == 12345

    def test_telemetry_snapshot_is_the_rolling_consumer(self):
        wire_counters.observe_max("wire_quant_residual_peak", 555)
        t1 = telemetry_snapshot()
        assert t1["counters"]["wire_quant_residual_peak"] == 555
        t2 = telemetry_snapshot()
        assert t2["counters"]["wire_quant_residual_peak"] == 0

    def test_merge_still_takes_max_across_nodes(self):
        m = merge_telemetry([
            {"counters": {"wire_withheld_bytes_peak": 9, "x": 1}},
            {"counters": {"wire_withheld_bytes_peak": 40, "x": 2}},
        ])
        assert m["counters"]["wire_withheld_bytes_peak"] == 40
        assert m["counters"]["x"] == 3
