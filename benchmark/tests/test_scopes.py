"""The readers that find device time by the program's phase names and host
time by its named timers: on made-up ``run`` dicts, on the recorded TPU
trace with a hand-written scope map for its fusion numbers, and once
against the program's own ``op_scopes()`` on a tiny trainer."""

import os

import pytest

import tiny  # noqa: F401
from benchmark import layer_metrics_scopes as lms
from benchmark.harness import manifest as mf
from benchmark.harness import xtrace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "ctr1_train.xplane.pb")
NEW = (
    "step.row_ids_ms", "step.pull_ms", "step.grad_ms", "step.push_ms", "step.push_scatter_ms",
    "step.unscoped_share", "feed.build_busy_share", "feed.stack_busy_share",
    "dispatch.retire_wait_share", "dispatch.new_shapes", "eval.open_ms", "eval.score_ms",
)
# ctr1.train's fusions as PR 23's trace numbered them (PERF.md section 5)
BY_HAND = {
    "fusion.71": "ps.row_ids", "fusion.70": "ps.row_ids", "select_select_fusion.4": "ps.row_ids",
    "select_select_fusion.5": "ps.row_ids",
    "fusion.58": "ps.pull", "fusion.59": "ps.pull",
    "fusion.62": "ps.grad", "fusion.63": "ps.grad", "fusion.65": "ps.grad", "fusion.66": "ps.grad",
    "fusion.68": "ps.push/scatter", "fusion.69": "ps.push/scatter", "fusion.67": "ps.push/update",
}


def reader(name: str):
    return mf.load_module(mf.metric_path(name), "reader")


def made_up(ops: dict, scopes, **over) -> dict:
    run = {
        "trace": xtrace.Reduced(window_s=2.0, ops=ops, chips=2, modules={"jit__jitted(77)": [2.0, 1]}),
        "facts": {"microsteps": 4, "data_shards": 2, "pushes_per_step": 2},
        "timers": {}, "window": {"elapsed_s": 2.0},
    }
    run.update(over)
    if scopes is not None:
        run["_phase_seconds"] = lms.seconds_by_scope(ops, scopes)
    return run


def test_manifest_names_a_reader_each():
    listed = {m["name"] for m in mf.load_manifest()["per_layer"]}
    for name in NEW:
        assert name in listed and callable(reader(name).read)


def test_device_readers_on_a_made_up_run():
    ops = {
        "%fusion.1 = s32[64]{0} fusion(s32[9]{0} %a)": [0.8, 8],
        "%fusion.2 = f32[65]{0} fusion(f32[1024]{0} %z)": [0.4, 8],
        "%fusion.3 = f32[64]{0} fusion(f32[65]{0} %w)": [0.16, 8],
        "%fusion.4 = f32[1024]{0} fusion(f32[1024]{0} %z)": [1.6, 8],
        "%all-gather.1 = s32[2,65]{1,0} all-gather(s32[65]{0} %i)": [0.08, 8],
        "%copy.9 = s32[64]{0} copy(s32[64]{0} %b)": [0.04, 8],
        "not an instruction": [0.02, 1],
    }
    scopes = {"fusion.1": "ps.row_ids", "fusion.2": "ps.pull", "fusion.3": "ps.grad",
              "fusion.4": "ps.push/scatter", "all-gather.1": "ps.push", "copy.9": ""}
    run = made_up(ops, scopes)
    per = 1e3 / 2 / 4  # seconds over both chips -> ms a chip and microstep
    assert reader("step.row_ids_ms").read(run) == pytest.approx(0.8 * per)
    assert reader("step.pull_ms").read(run) == pytest.approx(0.4 * per)
    assert reader("step.grad_ms").read(run) == pytest.approx(0.16 * per)
    assert reader("step.push_ms").read(run) == pytest.approx(1.68 * per)  # nested scopes included
    assert reader("step.push_scatter_ms").read(run) == pytest.approx(1.6 * per)
    assert reader("step.unscoped_share").read(run) == pytest.approx(100 * 0.06 / 3.1)
    # nothing pushed (the eval kind): no push metric, the others as they were
    run["facts"]["pushes_per_step"] = 0
    assert reader("step.push_ms").read(run) is None and reader("step.push_scatter_ms").read(run) is None
    assert reader("step.pull_ms").read(run) == pytest.approx(0.4 * per)


def test_a_program_without_names_reads_nothing(monkeypatch):
    """The parent commit under this PR's benchmark files: no ``op_scopes``,
    no ``feed.*`` / ``eval.*`` / ``trainer.new_shapes`` timers - every new
    reader returns None and none raises."""
    from parameter_server_tpu.parallel import spmd
    from parameter_server_tpu.utils.metrics import timers

    monkeypatch.delattr(spmd, "op_scopes", raising=False)
    timers.reset()
    run = made_up({"%fusion.1 = s32[64]{0} fusion(": [1.0, 1]}, None, timers={"trainer.fetch": {"total_s": 0.1, "count": 3}})
    for name in NEW:
        assert reader(name).read(run) is None, name


def test_a_program_that_knows_none_of_the_windows_modules_reads_nothing(monkeypatch):
    from parameter_server_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "op_scopes", lambda: {"jit_other": {"fusion.1": "ps.pull"}})
    run = made_up({"%fusion.1 = s32[64]{0} fusion(": [1.0, 1]}, None)
    assert reader("step.pull_ms").read(run) is None and reader("step.unscoped_share").read(run) is None


def test_two_programs_of_one_window_that_disagree(monkeypatch):
    from parameter_server_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "op_scopes", lambda: {
        "jit__jitted": {"fusion.1": "ps.pull", "fusion.2": "ps.grad"},
        "jit_local_predict": {"fusion.1": "ps.row_ids", "fusion.2": "ps.grad"},
        "jit_elsewhere": {"fusion.2": "ps.push"},  # not in the window: not asked
    })
    run = made_up({}, None)
    run["trace"].modules = {"jit__jitted(1)": [1.0, 1], "jit_local_predict(2)": [1.0, 1]}
    assert lms.scope_map(run) == {"fusion.1": "", "fusion.2": "ps.grad"}


def test_host_readers_on_made_up_timers(monkeypatch):
    run = made_up({}, {}, timers={
        "feed.build": {"total_s": 0.6, "count": 30}, "feed.stack": {"total_s": 0.05, "count": 4},
        "trainer.retire": {"total_s": 1.9, "count": 4}, "trainer.new_shapes": {"total_s": 0.0, "count": 0},
    })
    assert reader("feed.build_busy_share").read(run) == pytest.approx(100 * 0.6 / (2.0 * 2))  # two producer threads
    assert reader("feed.stack_busy_share").read(run) == pytest.approx(2.5)
    assert reader("dispatch.retire_wait_share").read(run) == pytest.approx(95.0)
    assert reader("dispatch.new_shapes").read(run) == 0.0
    run["timers"]["trainer.new_shapes"]["count"] = 2
    assert reader("dispatch.new_shapes").read(run) == 2.0

    from parameter_server_tpu.utils.metrics import timers

    timers.reset()
    for _ in range(3):  # three passes: the mean over the process
        with timers.timer("eval.open"):
            pass
    snap = timers.snapshot()["eval.open"]
    assert reader("eval.open_ms").read(run) == pytest.approx(1e3 * snap["total_s"] / 3)
    with timers.timer("eval.new_shapes"):  # the warm pass's compile, inside its eval.open
        pass
    first = timers.snapshot()["eval.new_shapes"]["total_s"]
    assert reader("eval.open_ms").read(run) == pytest.approx(1e3 * (snap["total_s"] - first) / 3)
    assert reader("eval.score_ms").read(run) is None  # no such timer yet
    timers.reset()


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_recorded_trace_with_a_hand_written_scope_map():
    """Phases and the unscoped rest add up to the trace's op seconds, and
    the three that lead read what PERF.md section 5 says of them."""
    prof = xtrace.load(RECORDED)
    marks = xtrace.collect_marks(prof)
    t0 = marks["bench.window_open"][0]
    t1 = [m for m in marks["bench.retire"] if m > t0][1]  # two whole device calls
    reduced = xtrace.reduce_window(prof, t0, t1)
    run = {"trace": reduced, "facts": {"microsteps": 16, "pushes_per_step": 1},
           "_phase_seconds": lms.seconds_by_scope(reduced.ops, BY_HAND)}
    total = sum(sec for sec, _ in reduced.ops.values())
    assert sum(run["_phase_seconds"].values()) == pytest.approx(total, rel=1e-12)
    parts = [reader(n).read(run) for n in ("step.row_ids_ms", "step.pull_ms", "step.grad_ms", "step.push_ms")]
    unscoped_ms = 1e3 * run["_phase_seconds"][""] / 16
    assert sum(parts) + unscoped_ms == pytest.approx(1e3 * total / 16, rel=1e-9)
    assert sum(parts) + unscoped_ms == pytest.approx(1e3 * reduced.busy_s / 16, rel=2e-2)  # step.device_ms
    assert parts[0] == pytest.approx(52.3, rel=0.05)
    assert parts[1] == pytest.approx(26.0, rel=0.05)
    assert reader("step.push_scatter_ms").read(run) == pytest.approx(103.2, rel=0.05)
    assert reader("step.unscoped_share").read(run) < 2.0


def test_against_the_programs_own_op_scopes(tmp_path, monkeypatch):
    """A tiny ``ctr1.train`` on the CPU: the names ``op_scopes()`` returns
    join with op texts shaped like the trace's, module by module."""
    from parameter_server_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "_ran", [])
    ctx, kind, app = tiny.tiny_ctx("ctr1.train", workdir=str(tmp_path), seconds=0.2)
    kind.run(ctx, app)
    by_module = spmd.op_scopes()
    assert set(by_module) == {"jit__jitted", "jit_local_predict"}  # train kind: the window's step, the held-out pass
    step = by_module["jit__jitted"]
    for phase in ("ps.row_ids", "ps.pull", "ps.grad", "ps.push/scatter"):
        assert phase in step.values(), phase
    ops = {f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)": [1.0, 1] for name in step}
    run = made_up(ops, None)
    run["trace"].chips = 1
    by_scope = lms.phase_seconds(run)
    assert sum(by_scope.values()) == pytest.approx(len(step))
    assert by_scope["ps.row_ids"] == sum(1 for s in step.values() if s == "ps.row_ids")
    assert reader("step.push_ms").read(run) == pytest.approx(
        1e3 * sum(1 for s in step.values() if s.startswith("ps.push")) / 4
    )
