"""A train cell's ``correct`` does not depend on how fast the program
trains (``traffic_kinds/train.py``): ``heldout.auc_below_reference`` holds
the program's state right after the prefix against the reference's after
the same prefix, ``trained.auc_below_reference`` scores the table as the
window left it on files the window trained. So a window of ten passes and
more over the cycled files stays correct (at the cells' own size the old
number, the held-out AUC after the window against the reference's after the
prefix, refused Wide&Deep from the fourth pass on; the tiny tables here do
not overfit that way, their reference has seen 512 examples and the old
number reads 0.005 on Wide&Deep after 33 passes and -0.07 on the linear
cell after 178, so the pass count is pinned instead), a window whose
pushes carry the wrong sign is caught by the second number and by it alone,
and the scoring at the prefix is no part of ``setup_s``. Tiny sizes, CPU.
``tests/test_yardstick.py`` brings the test functions alone into tier 1, so
no test here leans on a fixture of this module."""

import functools
import json
import os
import time

import pytest

import tiny

CELLS = ["ctr1.train", "wd100m.train"]


def _run(cell, seconds=0.5, session=None, **settings):
    """One tiny run. ``session(app)`` may give a Session class to run in
    the app's place. Returns (ctx, rec, {check name: check})."""
    # a directory of this process's own: pytest-xdist runs files side by side
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{cell}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(cell, seed=31, seconds=seconds, workdir=workdir, **settings)
    ctx.traffic["train_files"] = 2  # a pass is two device calls
    if session is not None:
        app.Session = session(app)  # ``app`` is this run's own copy of the module
    rec = kind.run(ctx, app)
    return ctx, rec, {c.name: c for c in rec["checks"]}


def _passes(ctx, rec) -> float:
    st = ctx.config["settings"]
    return rec["window"]["work"] / (ctx.traffic["train_files"] * st["minibatch"] * st["steps_per_call"])


@functools.lru_cache(maxsize=None)
def _long_window(cell):
    """A sound run whose window trained ten passes or more: the window is
    set in seconds, so it is doubled until the host got that far."""
    for seconds in (1.0, 2.0, 4.0, 8.0):
        ctx, rec, checks = _run(cell, seconds=seconds)
        if _passes(ctx, rec) >= 10:
            break
    return ctx, rec, checks


@pytest.mark.parametrize("cell", CELLS)
def test_window_of_ten_passes_and_more_stays_correct(cell):
    ctx, rec, checks = _long_window(cell)
    assert _passes(ctx, rec) >= 10
    assert all(c.ok for c in checks.values()) and rec["failed"] == 0, [c.line() for c in checks.values()]
    # on files it trained, every pass of a sound program gains on the prefix's state
    assert checks["trained.auc_below_reference"].value < 0


@pytest.mark.parametrize("cell", CELLS)
def test_both_numbers_compare_equal_training(cell):
    """At the prefix the two sides have trained the same examples from the
    same start and score alike; the note says which states were scored."""
    _, _, checks = _long_window(cell)
    held, trained = checks["heldout.auc_below_reference"], checks["trained.auc_below_reference"]
    assert abs(held.value) < 1e-4
    assert held.note.startswith("both after the prefix")
    assert "program after the window" in trained.note and "reference after the prefix" in trained.note


@pytest.mark.parametrize("cell", CELLS)
def test_window_whose_pushes_carry_the_wrong_sign_is_not_correct(cell, monkeypatch):
    """The prefix is sound; from the window on every table push applies
    -g. Every ``prefix.*`` number and the held-out one, which are read at
    the prefix's state, pass: the window's guard alone says no."""
    import jax

    from parameter_server_tpu.kv.updaters import Adagrad, Ftrl

    def flipped(app):
        class Flipped(app.Session):
            def prefix(self, **kw):
                super().prefix(**kw)
                for updater in (Ftrl, Adagrad):
                    real = updater.delta
                    monkeypatch.setattr(updater, "delta", lambda self, rows, g, real=real: real(self, rows, -g))
                jax.clear_caches()  # the warm call traces the step again

        return Flipped

    try:
        _, rec, checks = _run(cell, seconds=1.0, session=flipped)
    finally:
        jax.clear_caches()  # and the next test traces its own
    assert {n for n, c in checks.items() if not c.ok} == {"trained.auc_below_reference"}, [
        c.line() for c in checks.values()
    ]
    assert checks["trained.auc_below_reference"].value > 0.1


def test_wide_deep_left_untrained_fails_both_numbers(monkeypatch):
    """``test_controls.py`` has the linear cell's step that returns its
    state unchanged; here neither table nor the tower moves."""
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Adagrad, Ftrl
    from parameter_server_tpu.parallel import spmd

    for updater in (Ftrl, Adagrad):
        monkeypatch.setattr(updater, "delta", lambda self, rows, g: {k: jnp.zeros_like(v) for k, v in rows.items()})
    monkeypatch.setattr(spmd, "_dense_step", lambda group, params, opt_state, grads, active: (params, opt_state))
    _, _, checks = _run("wd100m.train")
    bad = {n for n, c in checks.items() if not c.ok}
    assert {"heldout.auc_below_reference", "trained.auc_below_reference"} <= bad, [c.line() for c in checks.values()]


def test_scoring_at_the_prefix_is_not_set_up():
    """The seconds of the held-out scoring go into ``ctx.excluded_s``, which
    the kind takes off ``setup_s``: a scoring made slower moves nothing."""
    def slow(app):
        class Slow(app.Session):
            def evaluate(self, files):
                if self.heldout_auc is None:  # the scoring at the prefix
                    time.sleep(0.4)
                return super().evaluate(files)

        return Slow

    ctx, rec, _ = _run("ctr1.train", session=slow)
    assert ctx.excluded_s >= 0.4
    opened = rec["window"]["t_open"] - ctx.t0
    assert rec["end_to_end"]["setup_s"] == pytest.approx(opened - ctx.excluded_s, abs=1e-6)


def test_eval_kind_scores_nothing_at_the_prefix():
    ctx, _, checks = _run("ctr1.eval")
    assert ctx.excluded_s == 0.0
    assert "heldout.auc_below_reference" not in checks and "trained.auc_below_reference" not in checks


@pytest.mark.parametrize("cell", ["ctr1.train", "ctr1.eval", "wd100m.train"])
def test_bucket_rows_is_read_off_the_dispatched_batches(cell):
    """Without ``bucket_nnz`` the builder hands every batch its static
    worst case, 200 entries an example here: the unique-key slots the
    byte models multiply by are those, not 39 x minibatch rounded up."""
    ctx, rec, _ = _run(cell, bucket_nnz=False, max_nnz_per_example=200)
    minibatch = ctx.config["settings"]["minibatch"]
    assert rec["facts"]["bucket_rows"] == 200 * minibatch + 1


def test_result_line_ends_in_the_numbers_compared(capsys):
    """Each number compared, beside its limit: the last lines of stderr,
    and the last key of the result's line."""
    import jax

    from benchmark import run as bench_run
    from benchmark.harness import device as dev

    ctx, rec, checks = _long_window("ctr1.train")
    wanted = [{"name": "ex_rate", "unit": "examples/s"}, {"name": "setup_s", "unit": "s"}]
    capsys.readouterr()
    assert bench_run.report(ctx, rec, wanted, {}, jax.devices()[:1], None, dev.CompileLog()) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert set(line["checks"]) == set(checks)
    got = line["checks"]["trained.auc_below_reference"]
    assert got == {"value": checks["trained.auc_below_reference"].value, "limit": 0.02}
    assert err.strip().splitlines()[-len(checks):] == [c.line() for c in checks.values()]
    # where the window's time went by the program's own phases: what names a stall of the host
    spent = json.loads(next(l for l in out.splitlines() if l.startswith("[timers] "))[len("[timers] "):])
    assert spent["trainer.retire"] > 0 and spent["trainer.dispatch"] > 0
