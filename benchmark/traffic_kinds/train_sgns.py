"""Traffic kind ``train_sgns``: ``train``'s one long epoch of whole device
calls, for an app that is scored by its mean loss an example (skip-gram
with negative sampling: there is no label to rank or to regress on).

The window, the stamps and the counts are ``train``'s, line for line
(``traffic_kinds/train.py``: the prefix from the fresh table, ONE epoch over
a file list long enough for any window, the window from the retire of warm
call ``warm_calls - 1`` to the first retire at or after ``--seconds``,
pairs retired between the two stamps over the time between them as
``ex_rate``). What decides ``correct`` compares states that had the same
training, or can only get better with more of it: the ``prefix.*`` gaps and
``heldout.loss_above_reference`` hold the program's state right after the
prefix against the reference's after the same prefix;
``trained.loss_above_reference`` scores the table as the window left it on
files the window trained, where every further pass of a sound program only
lowers the loss, and a window that damaged the table (or a rate that lets
it diverge) rises over the reference's state after the prefix.

Parameters (the mix's JSON): as ``train``'s.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import window
from benchmark.harness.checks import Check


def run(ctx, app) -> dict:
    import jax

    from parameter_server_tpu.utils.metrics import timers

    t = ctx.traffic
    sess = app.Session(ctx)
    build_rate = sess.measure_build_rate()
    sess.prefix(score_heldout=True)

    warm = int(t["warm_calls"])
    cap_calls = warm + math.ceil(ctx.seconds / float(t["min_call_s"])) + 1
    files = sess.file_list(cap_calls * sess.data_shards, start=sess.prefix_files)
    open_at = warm - 1
    stamps: list = []
    snap: dict = {}

    def on_retire(stamp: float, i: int) -> None:
        stamps.append(stamp)
        ctx.mark("bench.retire")
        if i == open_at:
            ctx.mark("bench.window_open")
            snap["open"] = timers.snapshot()
            snap["setup_s"] = stamp - ctx.t0 - ctx.excluded_s
        elif i > open_at and stamp - stamps[open_at] >= ctx.seconds:
            ctx.mark("bench.window_close")
            snap["close"] = timers.snapshot()
            raise app.StopWindow

    sess.on_retire = on_retire
    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir)
    try:
        ran_out = sess.train(files)
    finally:
        sess.on_retire = None
        jax.block_until_ready(sess.trainer.state)
        if ctx.trace:
            jax.profiler.stop_trace()
    if ran_out:
        raise RuntimeError(
            f"the epoch of {cap_calls} calls ended before the window closed: "
            f"lower min_call_s ({t['min_call_s']}) in the traffic file"
        )

    ctx.stage("window closed")
    work = sess.call_work()
    win = window.summarize(stamps, work[: len(stamps)], open_at, ctx.seconds)
    # unique-key slots a worker's microstep carried, over the window's calls
    slots = sess.call_slots()[open_at + 1 : win["close_at"] + 1]
    bucket_rows = sum(slots) / len(slots)
    losses, dev_examples = sess.call_outputs()  # every dispatched call, in flight ones too
    inside = range(open_at + 1, len(work))  # dispatched after the window opened
    attempted = int(sum(work[i] for i in inside))
    done = int(sum(dev_examples[i].sum() for i in inside if np.isfinite(losses[i]).all()))
    nonfinite = int(sum((~np.isfinite(l)).sum() for l in losses))

    # after the window: the table as the window left it scores files it
    # trained; then the reference, which has had the prefix and no more
    ev = sess.evaluate(sess.trained_paths)
    ref, ref_losses, scored = sess.reference("float32", score=("heldout", "trained"))
    ref_loss = {k: app.mean_loss(ref, s) for k, s in scored.items()}
    lim = t["limits"]
    checks = sess.prefix_checks(ref, ref_losses) + [
        Check("window.nonfinite_losses", nonfinite, 0),
        Check("window.unretired_examples", attempted - done, 0),
        Check(
            "heldout.loss_above_reference", sess.heldout_loss - ref_loss["heldout"],
            lim["heldout.loss_above_reference"],
            note=f"both after the prefix: program {sess.heldout_loss:.6f}, reference {ref_loss['heldout']:.6f}",
        ),
        Check(
            "trained.loss_above_reference", float(ev["sgns_loss"]) - ref_loss["trained"],
            lim["trained.loss_above_reference"],
            note=f"on {len(sess.trained_paths)} training files: program after the window "
                 f"{ev['sgns_loss']:.6f}, reference after the prefix {ref_loss['trained']:.6f}",
        ),
    ]
    ctx.stage("reference compared")
    real_keys = sess.problem.real_keys()
    sess.close()
    return {
        "end_to_end": {"ex_rate": win["rate"], "setup_s": snap["setup_s"]},
        "attempted": attempted,
        "failed": attempted - done,
        "checks": checks,
        "window": win,
        "stamps": window.stamp_lines(stamps, work[: len(stamps)], open_at, win["close_at"]),
        "timers_open": snap["open"],
        "timers_close": snap["close"],
        "facts": {
            "build_rate": build_rate,
            "inflight_peak": sess.trainer.max_inflight,
            "microsteps": win["units"] * sess.steps_per_call,
            "data_shards": sess.data_shards,
            "kv_shards": sess.kv_shards,
            "bucket_rows": bucket_rows,
            "real_keys": real_keys,
            "pushes_per_step": sess.data_shards,
            "mode": "train",
        },
    }
