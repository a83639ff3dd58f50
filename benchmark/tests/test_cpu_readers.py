"""The readers of CPU seconds beside wall seconds (``<name>.cpu`` and
``process.cpu``) on made-up timers, their entries in ``BENCHMARK.json``, and
that the program keeps a twin for just the phases they read."""

import pytest

from benchmark import layer_metrics_cpu as lmc
from benchmark.harness import manifest as mf

READER_CELLS = [
    "ctr1.train", "ctr1.eval", "ctr2x2.train", "wd100m.train", "mfhw.train", "sgns3m.train",
    "mfhw2x2.train", "dlrm1tb.train", "dcn1tb.train",
]
TRAIN_CELLS = [c for c in READER_CELLS if c != "ctr1.eval"]
SNAPSHOT_CELLS = TRAIN_CELLS[:5] + ["darlin1.pass"] + TRAIN_CELLS[5:]  # every kind but ``eval``
# name -> (unit, layer, cells, better), in the order they were appended
CPU_READERS = {
    "feed.parse_offcpu_share": ("%", "host feed", READER_CELLS, "lower"),
    "feed.build_offcpu_share": ("%", "host feed", READER_CELLS, "lower"),
    "feed.stack_offcpu_share": ("%", "host feed", TRAIN_CELLS, "lower"),
    "dispatch.offcpu_share": ("%", "dispatch", TRAIN_CELLS, "lower"),
    "eval.caller_offcpu_share": ("%", "evaluator", ["ctr1.eval"], "lower"),
    "feed.cpu_ms": ("ms", "host feed", READER_CELLS, "lower"),
    "host.cpu_cores": ("cores", "host feed", SNAPSHOT_CELLS, "lower"),
    "host.named_cpu_share": ("%", "host feed", TRAIN_CELLS, "higher"),
}


def cpu_reader(name: str):
    return mf.load_module(mf.metric_path(name), "reader")


def twin(wall_s: float, cpu_s: float, count: int) -> dict:
    """A phase's two timers as a snapshot holds them."""
    return {"wall": {"total_s": wall_s, "count": count}, "cpu": {"total_s": cpu_s, "count": count}}


def snap_of(**phases) -> dict:
    """{"reader_parse": twin(...)} -> {"reader.parse": ..., "reader.parse.cpu": ...}"""
    out = {}
    for name, both in phases.items():
        name = name.replace("_", ".", 1)
        out[name], out[name + ".cpu"] = both["wall"], both["cpu"]
    return out


def cpu_run(timers: dict, mode: str = "train", elapsed_s: float = 20.0) -> dict:
    return {"timers": timers, "facts": {"mode": mode}, "window": {"elapsed_s": elapsed_s}}


def whole_process(monkeypatch, snap: dict) -> None:
    """Stand-in for the program's process-wide timers (no fixture: tier 1 brings
    this module's tests, not its fixtures, into ``tests/test_yardstick.py``)."""
    from parameter_server_tpu.utils.metrics import timers

    monkeypatch.setattr(timers, "snapshot", lambda: snap)


TRAIN_WINDOW = {
    **snap_of(
        reader_parse=twin(18.0, 11.7, 300), reader_build=twin(8.0, 6.0, 2000), feed_stack=twin(2.0, 1.5, 250),
        trainer_dispatch=twin(1.0, 0.8, 250), trainer_retire=twin(17.0, 0.1, 250),
    ),
    "process.cpu": {"total_s": 50.0, "count": 1},
}


@pytest.mark.parametrize("name", list(CPU_READERS))
def test_cpu_reader_is_listed_with_its_cells(name):
    manifest = mf.load_manifest()
    entry = mf.entry(manifest["per_layer"], name, "per-layer metric")
    unit, layer, cells, better = CPU_READERS[name]
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": "program_span", "layer": layer,
        "moves": "ex_rate", "workloads": cells,
    }
    assert callable(cpu_reader(name).read)
    for cell in (w["name"] for w in manifest["workloads"]):
        listed = name in {m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)}
        assert listed == (cell in cells), cell


def test_cpu_readers_over_a_train_window(monkeypatch):
    whole_process(monkeypatch, {})  # a kind that snapshots is never read from the process's totals
    run = cpu_run(TRAIN_WINDOW)
    assert cpu_reader("feed.parse_offcpu_share").read(run) == pytest.approx(35.0)
    assert cpu_reader("feed.build_offcpu_share").read(run) == pytest.approx(25.0)
    assert cpu_reader("feed.stack_offcpu_share").read(run) == pytest.approx(25.0)
    assert cpu_reader("dispatch.offcpu_share").read(run) == pytest.approx(20.0)
    assert cpu_reader("feed.cpu_ms").read(run) == pytest.approx(10.0)  # 20 s of CPU over 2000 built batches
    assert cpu_reader("host.cpu_cores").read(run) == pytest.approx(2.5)
    assert cpu_reader("host.named_cpu_share").read(run) == pytest.approx(40.0)  # the waits' CPU is not named work
    assert cpu_reader("eval.caller_offcpu_share").read(run) is None  # no evaluator ran


def test_cpu_readers_of_a_window_that_built_no_batch(monkeypatch):
    whole_process(monkeypatch, {})
    idle = {
        **snap_of(
            reader_parse=twin(0.0, 0.0, 0), reader_build=twin(0.0, 0.0, 0), feed_stack=twin(0.0, 0.0, 0),
            trainer_dispatch=twin(0.0, 0.0, 0),
        ),
        "process.cpu": {"total_s": 0.4, "count": 1},
    }
    run = cpu_run(idle)
    for name in ("feed.parse_offcpu_share", "feed.build_offcpu_share", "feed.stack_offcpu_share",
                 "dispatch.offcpu_share", "feed.cpu_ms"):
        assert cpu_reader(name).read(run) is None, name  # no seconds, no batch: no share and no mean
    assert cpu_reader("host.cpu_cores").read(run) == pytest.approx(0.02)
    assert cpu_reader("host.named_cpu_share").read(run) == pytest.approx(0.0)


def test_cpu_readers_fall_back_to_the_process_where_the_kind_took_no_snapshots(monkeypatch):
    whole_process(monkeypatch, {
        **snap_of(
            reader_parse=twin(20.0, 16.0, 400), reader_build=twin(10.0, 9.0, 3000),
            eval_open_reader=twin(0.5, 0.25, 10), eval_stack=twin(2.0, 1.5, 375), eval_score=twin(1.5, 1.25, 10),
            eval_enqueue=twin(4.0, 3.5, 375), eval_new_shapes=twin(2.0, 1.5, 1),
            eval_read=twin(15.0, 0.2, 375), eval_retire=twin(0.3, 0.1, 375),
            feed_stack=twin(0.2, 0.1, 1), trainer_dispatch=twin(0.1, 0.1, 1),  # set-up trained the prefix
        ),
        "process.cpu": {"total_s": 90.0, "count": 7},
    })
    run = cpu_run({}, mode="eval")  # the ``eval`` kind: timers_delta(None, None)
    assert cpu_reader("feed.parse_offcpu_share").read(run) == pytest.approx(20.0)
    assert cpu_reader("feed.build_offcpu_share").read(run) == pytest.approx(10.0)
    # the working leaves alone, the warm pass's compile off both sides: 5.0 of 6.0 s
    assert cpu_reader("eval.caller_offcpu_share").read(run) == pytest.approx(100.0 * (1 - 5.0 / 6.0))
    # parse + build + stack + enqueue less the compile: 16 + 9 + 1.5 + 2.0 s over 3000 batches
    assert cpu_reader("feed.cpu_ms").read(run) == pytest.approx(9.5)
    # a window's numbers come from the window's snapshots or not at all
    assert cpu_reader("host.cpu_cores").read(run) is None
    assert cpu_reader("host.named_cpu_share").read(run) is None


@pytest.mark.parametrize("name", list(CPU_READERS))
def test_cpu_readers_read_nothing_from_a_parent(name, monkeypatch):
    """The parent under this PR's benchmark files: every phase, no twin, no
    ``process.cpu``, in the window's timers and in the process's."""
    parent = {k: v for k, v in TRAIN_WINDOW.items() if not k.endswith(".cpu")}
    evaluator = {n: {"total_s": 1.0, "count": 10} for n in (
        "reader.parse", "reader.build", "eval.open_reader", "eval.stack", "eval.enqueue", "eval.score",
        "eval.new_shapes", "eval.read", "eval.retire",
    )}
    whole_process(monkeypatch, {**parent, **evaluator})
    assert cpu_reader(name).read(cpu_run(parent)) is None
    assert cpu_reader(name).read(cpu_run({}, mode="eval")) is None
    from benchmark import layer_metrics_host

    monkeypatch.setattr(layer_metrics_host, "process_timers", lambda: None)  # no program beside the benchmark
    monkeypatch.setattr(lmc, "process_timers", lambda: None)
    assert cpu_reader(name).read(cpu_run({}, mode="eval")) is None


def test_cpu_seconds_of_phases_and_what_comes_off():
    snap = snap_of(eval_enqueue=twin(4.0, 3.5, 375), eval_new_shapes=twin(2.0, 1.5, 1), eval_stack=twin(2.0, 1.0, 375))
    assert lmc.seconds(snap, ("eval.enqueue", "eval.stack")) == (6.0, 4.5)
    assert lmc.seconds(snap, ("eval.enqueue", "eval.stack"), less=lmc.EVAL_SETUP) == (4.0, 3.0)
    assert lmc.seconds(snap, ("eval.enqueue",), less={"eval.enqueue": "eval.never_ran"}) == (4.0, 3.5)
    assert lmc.seconds(snap, ("eval.enqueue", "eval.score")) is None
    del snap["eval.stack.cpu"]
    assert lmc.seconds(snap, ("eval.stack",)) is None
    # a thread that ran the whole time reads 0, one that never ran reads 100
    assert lmc.offcpu_share(cpu_run(snap_of(a_b=twin(2.0, 2.0, 1))), ("a.b",)) == pytest.approx(0.0)
    assert lmc.offcpu_share(cpu_run(snap_of(a_b=twin(2.0, 0.0, 1))), ("a.b",)) == pytest.approx(100.0)


def test_wall_seconds_are_taken_for_the_units_the_twin_counted():
    """The ``eval`` kind's session starts after the prefix and the warm pass:
    the process's wall timers hold their units, the twins do not."""
    wall = {"total_s": 8.0, "count": 400}
    snap = {
        "reader.build": wall, "reader.build.cpu": {"total_s": 4.5, "count": 300},  # 300 of 400 batches in the session
        "eval.enqueue": {"total_s": 3.0, "count": 400}, "eval.enqueue.cpu": {"total_s": 0.6, "count": 300},
        "eval.new_shapes": {"total_s": 1.0, "count": 1},  # the warm pass's compile: before the session, no twin
    }
    assert lmc.seconds(snap, ("reader.build",)) == pytest.approx((6.0, 4.5))
    assert lmc.seconds(snap, ("eval.enqueue",), less=lmc.EVAL_SETUP) == pytest.approx((1.5, 0.6))
    assert lmc.offcpu_share(cpu_run(snap), ("reader.build",)) == pytest.approx(25.0)
    idle = {"reader.build": {"total_s": 0.0, "count": 0}, "reader.build.cpu": {"total_s": 0.0, "count": 0}}
    assert lmc.offcpu_share(cpu_run(idle), ("reader.build",)) is None


def test_cpu_readers_were_appended_in_one_run():
    names = [m["name"] for m in mf.load_manifest()["per_layer"]]
    first = names.index(next(iter(CPU_READERS)))
    assert names[first : first + len(CPU_READERS)] == list(CPU_READERS)  # in the order of the table


def test_the_program_keeps_a_twin_for_what_a_reader_reads_and_nothing_else():
    """A twin costs its phase two system calls with the interpreter lock
    held: the program's list is the readers' list."""
    from parameter_server_tpu.utils import trace

    read = {*lmc.TRAIN_LEAVES, *lmc.EVAL_LEAVES, *lmc.EVAL_CALLER, *lmc.EVAL_SETUP.values()}
    assert trace._CPU_TWINS == read
