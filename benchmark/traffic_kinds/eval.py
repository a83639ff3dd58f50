"""Traffic kind ``eval``: whole ``evaluate_files`` passes over a held-out
set, pull and predict and no push.

Set-up trains the correctness prefix (so that the table holds weights
worth scoring) and runs one warm pass, which compiles the predict shape.
The window opens when that pass returns and closes at the first pass
boundary at or after ``--seconds``. Each pass carries its own start-up
(reader, builder, first dispatch) and its drain-and-AUC: users pay both
after every epoch, so both are inside.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import window
from benchmark.harness.checks import Check


def run(ctx, app) -> dict:
    import jax

    from parameter_server_tpu.utils.metrics import timers

    t = ctx.traffic
    sess = app.Session(ctx)
    build_rate = sess.measure_build_rate()
    sess.prefix()
    held = sess.heldout_paths
    sess.evaluate(held)  # the warm pass

    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir)
    try:
        ctx.mark("bench.window_open")
        stamps, work, results = [time.perf_counter()], [0], []
        setup_s = stamps[0] - ctx.t0 - ctx.excluded_s
        while stamps[-1] - stamps[0] < ctx.seconds:
            r = sess.evaluate(held)
            stamps.append(time.perf_counter())
            ctx.mark("bench.pass_end")
            work.append(int(r["examples"]))
            results.append(r)
        ctx.mark("bench.window_close")
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    ctx.stage("window closed")
    first_probs = np.asarray(sess.eval_first)  # (D, B): the last pass's first batch
    win = window.summarize(stamps, work, 0, ctx.seconds)
    n_held = len(held) * sess.file_examples
    attempted = n_held * len(results)
    scored = int(sum(work))

    ref, ref_losses, ref_spans = sess.reference("float32")
    lim = t["limits"]
    aucs = np.array([r["auc"] for r in results])
    lls = np.array([r["logloss"] for r in results])
    got = app.Problem.eval_numbers(
        first_probs[0], float(lls[-1]), float(aucs[-1]), app.heldout_scores(ref, ref_spans["heldout"])
    )
    checks = sess.prefix_checks(ref, ref_losses) + [
        Check(name, value, lim[name], note="the last pass against the reference's held-out scores")
        for name, value in got.items()
    ] + [
        Check("eval.passes_differ", float(np.ptp(aucs) + np.ptp(lls)), 0,
              note="the table does not change between passes"),
        Check("eval.unscored_examples", attempted - scored, 0),
    ]
    ctx.stage("reference compared")
    sess.close()
    return {
        "end_to_end": {"ex_rate": win["rate"], "setup_s": setup_s},
        "attempted": attempted,
        "failed": attempted - scored,
        "checks": checks,
        "window": win,
        "stamps": window.stamp_lines(stamps, work, 0, win["close_at"]),
        "facts": {
            "build_rate": build_rate,
            "microsteps": win["units"] * (n_held // (sess.minibatch * sess.data_shards)),
            "data_shards": sess.data_shards,
            "kv_shards": sess.kv_shards,
            "bucket_rows": sum(sess.eval_slots) / len(sess.eval_slots),  # the last pass's calls
            "pushes_per_step": 0,
            "mode": "eval",
        },
    }
