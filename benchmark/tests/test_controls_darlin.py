"""``darlin1.pass``'s ``correct`` has been shown to fail. (1) Broken
references put in the program's place (``apps/darlin.control``), each failing
a NAMED limit of ``traffic/pass_darlin.json``: one that drops a block's last
chunk of entries, one that skips the update of ``pred``, one that sums in
bfloat16, one that takes every gradient against the ``pred`` the call
started with, one whose KKT filter leaves every coordinate active and one
whose filter leaves none. (2) The program itself, at a tiny size on the
CPU: a sound run is correct, and runs with the solver broken underneath (a
block's last chunk never swept; the weights moved by another scale than
``pred``; an active set the refresh never touches) are not. (3) The new readers find their scopes in the program's block call and
read nothing, without raising, where there is no such program.
``tests/test_yardstick.py`` brings the test functions alone into tier 1, so
no test here leans on a fixture of this module."""

import copy
import os

import numpy as np
import pytest

import tiny
from benchmark.harness import manifest as mf
from benchmark.harness import ref_darlin
from control import control_of

CELL = "darlin1.pass"
TINY = {"num_keys": 1 << 14, "num_examples": 2048, "minibatch": 2048, "feature_blocks": 16,
        "steps_per_call": 4, "epsilon": 0.0}
CHUNK = 64  # entries the chunk-dropping control loses from every block


class DropsLastChunk(ref_darlin.RefDarlin):
    def block_step(self, b, feat, rows, vals, alpha=None):
        order = np.argsort(feat, kind="stable")[: max(len(feat) - CHUNK, 0)]
        return super().block_step(b, feat[order], rows[order], vals[order], alpha)


class SkipsPredUpdate(ref_darlin.RefDarlin):
    def update_pred(self, alpha, xd):
        pass


class ReusesStalePred(ref_darlin.RefDarlin):
    """Every block of the call takes its gradient against the ``pred`` the
    call started with."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self._at_start = self.pred.copy()

    def seen_pred(self):
        return self._at_start


class FreezesActiveSet(ref_darlin.RefDarlin):
    """The filter never sets a coordinate aside."""

    def refresh(self, b, feat, rows, vals, threshold):
        pass


class BlanksActiveSet(ref_darlin.RefDarlin):
    """The filter sets every coordinate aside."""

    def refresh(self, b, feat, rows, vals, threshold):
        self.active[b] = np.zeros(self.block_size, bool)


def _small(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update(TINY)
    return found


CONTROLS = {
    "drops_last_chunk": ("float32", DropsLastChunk, "prefix.w_gap_max"),
    "skips_pred_update": ("float32", SkipsPredUpdate, "window.pred_gap_q99"),
    "sums_in_bfloat16": ("bfloat16", None, "prefix.w_gap_q99"),
    "reuses_stale_pred": ("float32", ReusesStalePred, "prefix.w_gap_q99"),
    "freezes_active_set": ("float32", FreezesActiveSet, "prefix.active_mismatch"),
    "blanks_active_set": ("float32", BlanksActiveSet, "prefix.active_mismatch"),
}


def _control(seed, precision, cls):
    found = _small(mf.resolve(tiny.manifest_of(CELL), CELL))
    from benchmark.harness.context import Ctx
    import time

    ctx = Ctx(cell=found["cell"], config=found["config"], traffic=found["traffic"], seed=seed,
              seconds=0.0, trace=False, t0=time.perf_counter(),
              workdir=os.path.join(mf.ROOT, ".bench_work", "control." + CELL))
    app = mf.load_module(found["app_path"], "app")
    return app.control(ctx, precision, cls=cls), found["traffic"]["limits"]


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_darlin_control_fails_its_named_limit(fault, seed):
    precision, cls, named = CONTROLS[fault]
    numbers, limits = _control(seed, precision, cls)
    assert not numbers[named] <= limits[named], f"{fault} passes {named}: {numbers}"


def test_darlin_float32_reference_in_the_programs_place_passes_every_limit():
    numbers, limits = _control(21, "float32", None)
    assert all(v <= limits[n] for n, v in numbers.items())
    assert set(numbers) <= set(limits)


def test_darlin_every_limit_has_its_reason():
    traffic = mf.resolve(tiny.manifest_of(CELL), CELL)["traffic"]
    assert set(traffic["reasons"]) == set(traffic["limits"])


def _run(**kw):
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{CELL}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(CELL, seed=31, seconds=0.3, workdir=workdir, **{**TINY, **kw})
    rec = kind.run(ctx, app)
    return rec, {c.name: c for c in rec["checks"]}


def test_darlin_sound_run_is_correct():
    rec, checks = _run()
    assert all(c.ok for c in checks.values()) and rec["failed"] == 0, [c.line() for c in checks.values()]
    assert rec["window"]["units"] >= 1 and rec["attempted"] > 0
    assert rec["facts"]["inflight_peak"] == 1  # max_delay + 1
    # the prefix is the first step call and the refresh of its blocks; the
    # window opens at the retire of the next call, the solve's warm call
    assert rec["window"]["open_at"] == 2
    work = [row["work"] for row in rec["stamps"]]
    assert work[1] == pytest.approx(work[0] / 3, rel=1e-12)
    # a pass's steps sweep N: 4 calls of 4 of 16 blocks claim N examples
    # between them, and the 4 refresh calls behind them a third of that
    assert sum(work[j] for j in (0, 2, 3, 4)) == pytest.approx(TINY["num_examples"], rel=1e-9)
    assert sum(work[5:9]) == pytest.approx(TINY["num_examples"] / 3, rel=1e-9)


def test_darlin_window_takes_steps_and_refreshes_as_they_come():
    """0.3 s at the tiny size is many passes: the window holds step calls
    and refresh calls, each a unit with its work, and the readers of the
    refresh find them."""
    rec, checks = _run()
    facts, win = rec["facts"], rec["window"]
    assert facts["microsteps"] > 0 and facts["refresh_steps"] > 0
    assert facts["microsteps"] + facts["refresh_steps"] == 4 * win["units"]
    n, entries = TINY["num_examples"], 39 * TINY["num_examples"]
    assert win["work"] == pytest.approx(n * (facts["entries_swept"] + facts["refresh_entries"] / 3) / entries, rel=1e-9)
    assert facts["refresh_ms"] > 0
    reader = mf.load_module(mf.metric_path("step.refresh_ms"), "reader")
    assert reader.read(rec) == facts["refresh_ms"]
    for alias in ("trainer.dispatch", "trainer.retire"):
        assert rec["timers_close"][alias]["count"] > rec["timers_open"][alias]["count"]


def test_darlin_a_blocks_last_chunk_never_swept_is_not_correct(monkeypatch):
    from parameter_server_tpu.models import darlin

    real = darlin.shard_blocks_for_mesh

    def short(cb, data_shards, blocks=None, pad_pow2=False):
        out = real(cb, data_shards, blocks, pad_pow2)
        spans = out["spans"].copy()
        spans[..., 1] = np.maximum(spans[..., 1] - 1, spans[..., 0])
        return {**out, "spans": spans}

    monkeypatch.setattr(darlin, "shard_blocks_for_mesh", short)
    rec, checks = _run()
    bad = {n for n, c in checks.items() if not c.ok}
    assert "prefix.w_gap_max" in bad and "window.pred_gap_max" in bad, bad


def test_darlin_weights_moved_by_another_scale_than_pred_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv.updaters import ProxNewton

    real = ProxNewton.apply
    monkeypatch.setattr(ProxNewton, "apply", lambda self, rows, d, alpha: real(self, rows, d, 0.5 * alpha))
    rec, checks = _run()
    bad = {n for n, c in checks.items() if not c.ok}
    assert {"prefix.w_gap_q99", "window.pred_gap_q99"} <= bad, bad


def test_darlin_an_active_set_the_refresh_never_touches_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv.updaters import ProxNewton

    monkeypatch.setattr(ProxNewton, "refresh", lambda self, rows, g, threshold: dict(rows))
    rec, checks = _run()
    bad = {n for n, c in checks.items() if not c.ok}
    assert bad == {"prefix.active_mismatch"}, bad


def test_darlin_block_call_carries_the_five_scopes_and_the_readers_find_them():
    """On the CPU: the program's ``op_scopes`` over the block call a tiny
    run dispatched names the five scopes, and over its refresh call the
    refresh's one; the scope readers sum a made-up trace over them. (The TPU
    compiler's word at the cell's shapes: ``tests/test_topology_scopes.py``.)"""
    from parameter_server_tpu.parallel import spmd

    spmd.forget_programs()
    rec, _ = _run()
    by_module = spmd.op_scopes()
    assert {s for s in by_module["jit_local_refresh_call"].values() if s} == {"darlin.refresh"}
    scopes = by_module["jit_local_block_call"]
    found = {s.split("/")[0] for s in scopes.values() if s}
    assert found == {"ps.pull", "ps.grad", "ps.push", "darlin.xd", "darlin.linesearch"}
    from benchmark import layer_metrics_scopes as lms

    ops = {f"%{name} = f32[] fusion()": [1.0, 1] for name in scopes}
    by_scope = lms.seconds_by_scope(ops, scopes)
    assert by_scope["darlin.xd"] > 0 and by_scope["darlin.linesearch"] > 0


@pytest.mark.parametrize("name", ["step.xd_ms", "step.linesearch_ms", "store.darlin_hbm_share", "step.refresh_ms"])
def test_darlin_readers_read_nothing_where_there_is_no_such_program(name):
    """A parent without the solver's scopes, another app's run: None, and
    nothing raised."""
    reader = mf.load_module(mf.metric_path(name), "reader")

    class Trace:
        busy_s, window_s, chips, modules, ops = 1.0, 2.0, 1, [], {}

    run = {"trace": Trace(), "facts": {"microsteps": 8}, "peaks": {"hbm_bytes_per_s": 819e9},
           "_phase_seconds": {"ps.pull": 1.0}}
    assert reader.read(run) is None
    run["_phase_seconds"] = None
    assert reader.read(run) is None


def test_darlin_hbm_share_reads_the_whole_step_against_the_bytes_model():
    from benchmark import bytes_model_darlin as bm

    reader = mf.load_module(mf.metric_path("store.darlin_hbm_share"), "reader")

    class Trace:
        busy_s, window_s, chips = 2.0, 2.0, 1

    facts = {"microsteps": 4, "entries_swept": 4e6, "examples": 1000, "block_size": 256}
    per = bm.step_bytes(1e6, 1000, 256)
    assert per == 1e6 * 24 + 1000 * 32 + 256 * 36
    got = reader.read({"trace": Trace(), "facts": facts, "peaks": {"hbm_bytes_per_s": 1e9}})
    assert got == pytest.approx(100.0 * 4 * per / 1e9 / 2.0)
    # a window that holds refresh calls: their bytes beside the steps'
    facts.update(refresh_steps=2, refresh_entries=2e6)
    refresh = bm.refresh_bytes(1e6, 1000, 256)
    assert refresh == 1e6 * 12 + 1000 * 8 + 256 * 20
    got = reader.read({"trace": Trace(), "facts": facts, "peaks": {"hbm_bytes_per_s": 1e9}})
    assert got == pytest.approx(100.0 * (4 * per + 2 * refresh) / 1e9 / 2.0)
