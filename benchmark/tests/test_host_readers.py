"""The readers of host time where the work happens (the reader's thread, the
evaluator's leaves) on made-up timers, and ``tools/host_gaps.py``'s two
reductions on the recorded TPU trace and on a made-up profile."""

import os
import sys

import pytest

import tiny
from benchmark import layer_metrics_host as lmh
from benchmark.harness import manifest as mf
from benchmark.harness import xtrace
from test_xtrace import RECORDED, plane, profile_of

sys.path.insert(0, os.path.join(tiny.ROOT, "tools"))
import host_gaps  # noqa: E402

TRAIN_CELLS = ["ctr1.train", "ctr2x2.train", "wd100m.train", "mfhw.train", "sgns3m.train"]
HOST_READERS = {
    "feed.parse_ms": TRAIN_CELLS, "feed.batch_build_ms": TRAIN_CELLS, "feed.reader_busy_share": TRAIN_CELLS,
    "eval.read_wait_ms": ["ctr1.eval"], "eval.stack_ms": ["ctr1.eval"], "eval.enqueue_ms": ["ctr1.eval"],
    "eval.retire_ms": ["ctr1.eval"], "eval.unnamed_share": ["ctr1.eval"],
}


def host_reader(name: str):
    return mf.load_module(mf.metric_path(name), "reader")


def timer(total_s: float, count: int) -> dict:
    return {"total_s": total_s, "count": count}


def windowed(timers: dict, data_shards: int = 1) -> dict:
    return {"timers": timers, "facts": {"data_shards": data_shards}, "window": {"elapsed_s": 20.0}}


@pytest.mark.parametrize("name", sorted(HOST_READERS))
def test_host_reader_is_listed_with_its_cells(name):
    manifest = mf.load_manifest()
    entry = mf.entry(manifest["per_layer"], name, "per-layer metric")
    assert entry["workloads"] == HOST_READERS[name]
    assert entry["moves"] == "ex_rate" and entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["layer"] == ("host feed" if name.startswith("feed.") else "evaluator")
    assert callable(host_reader(name).read)
    for cell in (w["name"] for w in manifest["workloads"]):
        listed = name in {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}
        assert listed == (cell in HOST_READERS[name]), cell


def test_reader_thread_timers_between_the_windows_snapshots():
    run = windowed({
        "reader.parse": timer(2.0, 50), "reader.build": timer(8.0, 400), "reader.put_wait": timer(1.0, 7),
        "feed.build": timer(9.0, 400),
    })
    assert host_reader("feed.parse_ms").read(run) == pytest.approx(5.0)  # a built batch, not a chunk
    assert host_reader("feed.batch_build_ms").read(run) == pytest.approx(20.0)
    assert host_reader("feed.reader_busy_share").read(run) == pytest.approx(50.0)
    two = windowed(run["timers"], data_shards=2)
    assert host_reader("feed.reader_busy_share").read(two) == pytest.approx(25.0)  # two streams, a reader thread each
    assert host_reader("feed.batch_build_ms").read(two) == pytest.approx(20.0)


@pytest.mark.parametrize("name", ["feed.parse_ms", "feed.batch_build_ms", "feed.reader_busy_share"])
def test_reader_thread_readers_read_nothing_from_a_parent(name):
    assert host_reader(name).read(windowed({"feed.build": timer(9.0, 400)})) is None
    if name != "feed.reader_busy_share":  # a window that built no batch has no mean
        assert host_reader(name).read(windowed({"reader.parse": timer(0.0, 0), "reader.build": timer(0.0, 0)})) is None


def process_timers(monkeypatch, snap: dict) -> None:
    """Stand-in for the program's process-wide timers (no fixture: tier 1
    brings this module's tests, not its fixtures, into ``tests/test_yardstick.py``)."""
    from parameter_server_tpu.utils.metrics import timers

    monkeypatch.setattr(timers, "snapshot", lambda: snap)


def test_evaluator_leaves_over_the_process(monkeypatch):
    process_timers(monkeypatch, {
        "eval.read": timer(3.3, 330), "eval.stack": timer(0.66, 330), "eval.retire": timer(0.33, 330),
        "eval.enqueue": timer(2.165, 330), "eval.new_shapes": timer(2.0, 1),
    })
    assert host_reader("eval.read_wait_ms").read({}) == pytest.approx(10.0)
    assert host_reader("eval.stack_ms").read({}) == pytest.approx(2.0)
    assert host_reader("eval.retire_ms").read({}) == pytest.approx(1.0)
    assert host_reader("eval.enqueue_ms").read({}) == pytest.approx(0.5)  # less the warm pass's compile


@pytest.mark.parametrize("name", [n for n in sorted(HOST_READERS) if n.startswith("eval.")])
def test_evaluator_readers_read_nothing_from_a_parent(name, monkeypatch):
    process_timers(monkeypatch, {"eval.open": timer(1.0, 10), "eval.score": timer(1.0, 10), "eval.new_shapes": timer(2.0, 1)})
    assert host_reader(name).read({}) is None


def test_unnamed_share_at_both_ends(monkeypatch):
    leaves = {leaf: timer(1.0, 10) for leaf in lmh.EVAL_LEAVES}
    assert lmh.unnamed_share({**leaves, "eval.pass": timer(6.0, 10)}) == pytest.approx(0.0)
    nothing = {leaf: timer(0.0, 10) for leaf in lmh.EVAL_LEAVES}
    assert lmh.unnamed_share({**nothing, "eval.pass": timer(6.0, 10)}) == pytest.approx(100.0)
    assert lmh.unnamed_share({**leaves, "eval.pass": timer(8.0, 10)}) == pytest.approx(25.0)
    # the warm pass's compile lies inside eval.enqueue and eval.pass, and comes off both
    compiled = {**leaves, "eval.enqueue": timer(3.0, 10), "eval.new_shapes": timer(2.0, 1), "eval.pass": timer(10.0, 10)}
    assert lmh.unnamed_share(compiled) == pytest.approx(25.0)
    assert lmh.unnamed_share({k: v for k, v in compiled.items() if k != "eval.read"}) is None
    process_timers(monkeypatch, compiled)
    assert host_reader("eval.unnamed_share").read({}) == pytest.approx(25.0)


MS = 1_000_000


def made_up_profile():
    """100 ms: the chip works 0-20 and 70-100 and idles 20-70. The caller's
    thread is inside phases for 46 of the gap's 50 ms; a reader's thread is
    in ``reader.build`` until 30, then in plain Python calls and no phase."""
    return profile_of(
        plane("/device:TPU:0", {"XLA Ops": [
            ("%fusion.1 = f32[8]{0} fusion(", 0, 20 * MS), ("%fusion.1 = f32[8]{0} fusion(", 70 * MS, 30 * MS),
        ]})
        + plane("/host:CPU", {
            "python3": [
                ("bench.window_open", 0, 1), ("$run.py:1 main", 0, 100 * MS),
                ("eval.pass", 1 * MS, 25 * MS), ("eval.score", 15 * MS, 11 * MS), ("$numpy concatenate", 16 * MS, 4 * MS),
                ("eval.pass", 30 * MS, 60 * MS), ("eval.open", 30 * MS, 42 * MS), ("eval.read", 31 * MS, 38 * MS),
                ("bench.window_close", 100 * MS, 1),
            ],
            "python3 ": [
                ("reader.parse", 2 * MS, 8 * MS), ("reader.build", 10 * MS, 20 * MS),
                ("$queue.py:1 put", 30 * MS, 60 * MS), ("$threading.py:1 wait", 32 * MS, 50 * MS),
            ],
            "tf_worker": [("ThunkExecutor::Execute", 0, 100 * MS)],  # XLA's own: carries no phase
        })
    )


def test_seconds_by_phase_and_thread_role():
    prof = made_up_profile()
    marks = xtrace.collect_marks(prof)
    t0, t1 = marks["bench.window_open"][0], marks["bench.window_close"][-1]
    threads = host_gaps.host_threads(prof, t0, t1)
    assert sorted(th.line for th in threads) == ["bench+eval#0", "reader#0"]
    roles = host_gaps.by_role(threads, t0, t1)
    caller, reader = roles["bench+eval"], roles["reader"]
    assert caller["threads"] == reader["threads"] == 1
    assert caller["phases"]["eval.pass"] == [pytest.approx(0.085), 2]
    assert caller["phases"]["eval.read"] == [pytest.approx(0.038), 1]
    assert caller["covered_s"] == pytest.approx(0.085, abs=1e-6)  # nested phases once
    assert reader["phases"] == {"reader.parse": [pytest.approx(0.008), 1], "reader.build": [pytest.approx(0.020), 1]}
    assert reader["covered_s"] == pytest.approx(0.028)  # the reader thread's busy share: 28% of the window


def test_a_gap_is_put_down_to_phases_or_to_the_thread_in_no_span():
    prof = made_up_profile()
    marks = xtrace.collect_marks(prof)
    t0, t1 = marks["bench.window_open"][0], marks["bench.window_close"][-1]
    reduced = xtrace.reduce_window(prof, t0, t1)
    assert reduced.gaps[0] == (pytest.approx(0.020), pytest.approx(0.050))
    (row,) = host_gaps.gap_table(host_gaps.host_threads(prof, t0, t1), reduced.gaps[:1], t0)
    caller, reader = sorted(row["threads"], key=lambda th: th["thread"])
    assert caller["thread"] == "bench+eval#0" and not caller["in_no_span"]
    assert caller["covered_share"] == pytest.approx(0.92)  # 20-26 and 30-70 of 20-70
    assert [n for n, _ in caller["phases"][:2]] == ["eval.pass", "eval.open"]
    assert dict(caller["phases"])["eval.read"] == pytest.approx(0.038)
    itself = dict(caller["doing"])
    assert itself["eval.read"] == pytest.approx(0.038) and itself["$run.py:1 main"] == pytest.approx(0.004)
    assert itself["eval.score"] == pytest.approx(0.006)  # 20-26, its numpy call over by then
    assert reader["thread"] == "reader#0" and reader["in_no_span"]
    assert reader["covered_share"] == pytest.approx(0.2)  # reader.build until 30
    assert reader["phases"] == [("reader.build", pytest.approx(0.010))]
    assert reader["doing"][0] == ("$threading.py:1 wait", pytest.approx(0.038))  # what it was really in
    assert "(IN NO SPAN for 0.040000 s)" in "\n".join(host_gaps.report(prof, 1, 3))


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_host_reductions_on_the_recorded_tpu_trace():
    prof = xtrace.load(RECORDED)
    marks = xtrace.collect_marks(prof)
    t0 = marks["bench.window_open"][0]
    t1 = [m for m in marks["bench.retire"] if m > t0][1]  # two whole device calls
    threads = host_gaps.host_threads(prof, t0, t1)
    assert [th.line for th in threads] == ["bench#0"]  # the recording kept the benchmark's marks alone
    roles = host_gaps.by_role(threads, t0, t1)
    # the retire between the two calls; the one that closes the window is its edge
    assert roles["bench"]["phases"]["bench.retire"][1] == 1 and roles["bench"]["covered_s"] < 1e-4
    reduced = xtrace.reduce_window(prof, t0, t1)
    rows = host_gaps.gap_table(threads, reduced.gaps[:3], t0)
    assert [r["seconds"] for r in rows] == [d for _, d in reduced.gaps[:3]]
    first = rows[0]["threads"][0]  # the window's opening edge: 1 ms, the marks cover none of it
    assert first["in_no_span"] and first["doing"][0][0] == host_gaps.NO_EVENT
    assert host_gaps.report(prof, 3, 3)[0].startswith("window 3.1653 s, 1 chip(s)")
