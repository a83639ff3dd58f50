"""Traffic kind ``solve``: a batch solve from zero weights, whole device
calls back to back.

Set-up makes the worker's data and its column blocks (parsed and cached on a
machine's first run of a seed, mapped on a later one), places them, and runs
the correctness prefix: the first ``prefix_calls`` device calls from the
fresh table, then the KKT filter's refresh of those calls' blocks, after
each of which the state is read back (the call's blocks' weights, ``pred``,
the step scales, the objective; their active set: those seconds are the
harness's own checking, ``ctx.excluded_s``). Those two kinds of call compile
the solver's two programs. The solve then goes on from the next call. The window opens at
the retire of the solve's warm call ``warm_calls - 1`` and closes at the
first retire at or after ``--seconds``, as ``traffic_kinds/train.py`` stamps
it; the solve is stopped once the window is closed and one refresh call of
the solve has retired (``step.refresh_ms`` reads those), which where the
window's close falls among the pass's steps is up to a pass's end later.

A unit is a retired device call, a step call or a refresh call alike: at the
cell's size the rest of the first pass is 17 to 21 s by the seed's block
order, so a window of 20 s ends among its last steps or among the refresh
calls behind them. A step call's work is N x (its blocks' real entries / all
real entries), so ``ex_rate`` is examples swept a second by block steps and
a pass's steps sweep N. A refresh call's is a third of that: a block step
streams each entry of its block three times (a gather and a segment sum or
scatter each for g, for h and for X_b d), the refresh once (for g), and the
chip's time goes by the stream (22.0 s a pass's steps, 7.3 s its refresh:
my chip runs, PR 40). Priced at nothing or at a whole sweep the refresh made
``ex_rate`` read 383k or 521k by the seed. So a whole pass is 4/3 N of work
in its 29.3 s, and what a user who solves to convergence gets a pass,
N / 29.3 s, is three quarters of ``ex_rate``; a change to either phase's
cost moves ``ex_rate`` in the runs whose window holds that phase.

What decides ``correct``: the ``prefix.*`` numbers hold the program's state
right after the prefix against the plain reference's after the same block
steps and the same refresh (the reference follows the program's step scales
and prices them by its own objective; ``prefix.active_mismatch`` is the
filter's rule, by the reference's own gradient and threshold); at the end
the objective is no higher than after the prefix and never rose from one
call to the next, ``pred`` equals Xw recomputed by the reference from the
table read back on a seeded sample of examples, and the entries the
window's calls claim to have swept are those the reference counts in the
same blocks.

The dispatch layer's readers know the pod path's timer names. The solver's
loop has the same two phases under its own names: ``darlin.dispatch`` and
``darlin.retire`` are handed to ``dispatch.call_ms`` and
``dispatch.retire_wait_share`` as ``trainer.dispatch`` and
``trainer.retire`` (``ALIASES``).

Parameters (the mix's JSON): ``prefix_calls``, ``warm_calls``, ``limits``.
"""

from __future__ import annotations

import math

from benchmark.harness import window

ALIASES = {"darlin.dispatch": "trainer.dispatch", "darlin.retire": "trainer.retire"}


def run(ctx, app) -> dict:
    import jax

    from parameter_server_tpu.utils.metrics import timers

    sess = app.Session(ctx)
    sess.prefix()
    open_at = len(sess.records) + int(ctx.traffic["warm_calls"]) - 1
    snap: dict = {}

    def snapshot() -> dict:
        s = timers.snapshot()
        for ours, theirs in ALIASES.items():
            if ours in s:
                s[theirs] = s[ours]  # the solver's loop is the run's only dispatch loop
        return s

    def on_retire(stamp: float, i: int) -> None:
        ctx.mark("bench.retire")
        if i == open_at:
            ctx.mark("bench.window_open")
            snap.update(open=snapshot(), setup_s=stamp - ctx.t0 - ctx.excluded_s)
        elif i > open_at and "close" not in snap and stamp - sess.stamps[open_at] >= ctx.seconds:
            ctx.mark("bench.window_close")
            snap["close"] = snapshot()
        if "close" in snap and any(app.is_refresh(r) for r in sess.records[open_at:]):
            raise app.StopWindow

    sess.on_retire = on_retire
    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir)
    try:
        ended = sess.solve()
    finally:
        sess.on_retire = None
        jax.block_until_ready(sess.solver.state)
        if ctx.trace:
            jax.profiler.stop_trace()
    if ended:
        raise RuntimeError(
            f"the solve converged after {len(sess.records)} calls, before the window closed"
        )

    ctx.stage("window closed")
    work = sess.call_work()
    win = window.summarize(sess.stamps, work, open_at, ctx.seconds)
    units = range(open_at + 1, win["close_at"] + 1)
    steps = [sess.records[i] for i in units if not app.is_refresh(sess.records[i])]
    refreshes = [sess.records[i] for i in units if app.is_refresh(sess.records[i])]
    attempted = int(round(win["work"]))
    failed = int(round(sum(work[i] for i in units if not math.isfinite(sess.records[i].get("obj", 0.0)))))
    # wall time a refreshed block over the solve's refresh calls, the one or
    # more behind the window's close too: one call in flight, so the time
    # between two retires is the later call's own
    after = [i for i in range(open_at + 1, len(sess.records)) if app.is_refresh(sess.records[i])]
    refresh_ms = 1e3 * sum(sess.stamps[i] - sess.stamps[i - 1] for i in after) / sum(
        len(sess.records[i]["blocks"]) for i in after
    )
    checks = sess.prefix_checks() + sess.close_checks(open_at, win["close_at"])
    ctx.stage("reference compared")
    sess.close()
    entries = sess.cb.entries
    return {
        "end_to_end": {"ex_rate": win["rate"], "setup_s": snap["setup_s"]},
        "attempted": attempted,
        "failed": failed,
        "checks": checks,
        "window": win,
        "stamps": window.stamp_lines(sess.stamps, work, open_at, win["close_at"]),
        "timers_open": snap["open"],
        "timers_close": snap["close"],
        "facts": {
            "inflight_peak": sess.solver.max_inflight,
            "microsteps": sum(len(r["blocks"]) for r in steps),  # a block step is the batch solver's microstep
            "entries_swept": float(sum(entries[r["blocks"]].sum() for r in steps)),
            "refresh_steps": sum(len(r["blocks"]) for r in refreshes),
            "refresh_entries": float(sum(entries[r["blocks"]].sum() for r in refreshes)),
            "refresh_ms": refresh_ms,
            "examples": sess.problem.n,
            "block_size": sess.problem.block_size,
            "data_shards": sess.data_shards,
            "kv_shards": sess.kv_shards,
            "pushes_per_step": 1,
            "mode": "train",
        },
    }
