"""Traffic kind ``workers``: a fixed number of closed-loop client processes
against one server (the wire tier).

Set-up: the server's table on the chip, the clients started (CPU-pinned),
client 0's single-client prefix, the apply's coalesced shapes compiled, then
``GO``; every client makes ``warm_trips`` round trips. The window opens when
the last client has finished its warm-up round trips and closes at the
first round-trip boundary of the slowest client (the one with the longest
mean round trip) at or after ``--seconds``. ``wire_rate`` is the keys of
the operations acknowledged or answered between the two stamps - pushes
acknowledged, pulls returned - over the time between them. Stamps are
``time.perf_counter`` in every process: CLOCK_MONOTONIC, one clock per host.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.harness.checks import Check, worst_gap
from benchmark.harness.ref_ftrl import RefFtrl


def run(ctx, app) -> dict:
    sess = app.Session(ctx)
    try:
        return _run(ctx, sess)
    finally:
        sess.close()  # clients and server stopped whatever happened


def _run(ctx, sess) -> dict:
    import jax

    t = ctx.traffic
    clients = sess.clients
    print(
        f"[bench] {clients} client processes, 1 server process with {clients} "
        f"connection threads and 1 apply thread, {os.cpu_count()} host cores", flush=True,
    )
    sess.spawn()
    sess.expect(0, "PREFIX")
    sess.warm_shapes()
    sess.rows(np.arange(1, 2, dtype=np.int64))  # compile the read-back
    counters0 = dict(sess.srv.counters)
    sess.tell("GO")
    trips = [float(sess.expect(c, "READY")[1]) for c in range(clients)]
    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir)
        ctx.mark("bench.window_open")
    t_ready = time.perf_counter()
    setup_s = t_ready - ctx.t0 - ctx.excluded_s
    # the clients run on; stop them once the slowest has had time for a
    # round trip past the window's end
    time.sleep(ctx.seconds + 2.0 * max(trips))
    if ctx.trace:
        ctx.mark("bench.window_close")
        jax.profiler.stop_trace()
    ctx.stage("window closed")
    sess.tell("STOP")
    logs = sess.collect()
    counters = {k: sess.srv.counters[k] - counters0.get(k, 0) for k in sess.srv.counters}

    warm = int(t["warm_trips"])
    ops = [lg["ops"] for lg in logs]  # rows: kind, t_start, t_end, keys
    ends = [o[1::2, 2] for o in ops]  # round-trip boundaries per client
    t_open = max(float(e[warm - 1]) for e in ends)
    if ctx.trace:
        t_open = max(t_open, t_ready)  # a traced window starts with the trace
    slowest = int(np.argmax([np.mean(np.diff(e)) for e in ends]))
    after = ends[slowest][ends[slowest] >= t_open + ctx.seconds]
    if len(after) == 0:
        raise RuntimeError("the slowest client made no round trip past the window's end")
    t_close = float(after[0])
    allops = np.concatenate(ops)
    inside = allops[(allops[:, 2] > t_open) & (allops[:, 2] <= t_close)]
    keys_done = float(inside[:, 3].sum())
    elapsed = t_close - t_open
    lat = {k: 1e3 * (inside[inside[:, 0] == c, 2] - inside[inside[:, 0] == c, 1]) for k, c in (("push", 0), ("pull", 1))}

    # correctness, after the window
    lim = t["limits"]
    n_pull = int(t["pull_keys"])
    pre = logs[0]
    rounds = int(t["prefix_trips"])
    ref = RefFtrl(np.concatenate([pre[f"prefix_keys{r}"] for r in range(rounds)]), sess.hyper)
    gaps = []
    for r in range(rounds):
        idx = ref.index(pre[f"prefix_keys{r}"])
        ref.push(idx, pre[f"prefix_grad{r}"])
        gaps.append(worst_gap(pre[f"prefix_pull{r}"], ref.weights(idx[:n_pull])))
    wkeys = np.concatenate([lg["witness_keys"] for lg in logs])
    wgrad = np.concatenate([lg["witness_grad"] for lg in logs]).astype(np.float32)
    got = sess.rows(wkeys)
    attempted = int(sum(int(lg["trips"]) for lg in logs)) * 2
    unseen = int(sum(int(lg["unseen"]) for lg in logs))
    wrong = int(np.sum(~np.isclose(got["n"], wgrad * wgrad, rtol=1e-6, atol=0)))
    checks = [
        Check("prefix.pull_gap", max(gaps), lim["prefix.pull_gap"],
              note="single client, every pulled row against the NumPy FTRL"),
        Check("witness.z_gap", worst_gap(got["z"], wgrad), lim["witness.z_gap"],
              note=f"{len(wkeys)} rows, each named by one acknowledged push: z = g"),
        Check("witness.n_gap", worst_gap(got["n"], wgrad * wgrad), lim["witness.n_gap"],
              note="n = g^2: a push applied twice, or not at all, shows here"),
        Check("witness.unseen_by_next_pull", unseen, 0,
              note="an acknowledged push is visible to any later pull"),
    ]
    ctx.stage("reference compared")
    stamps = [
        {"client": c, "trips": int(lg["trips"]), "mean_trip_s": float(np.mean(np.diff(ends[c]))),
         "max_trip_s": float(np.max(np.diff(ends[c]))), "slowest": c == slowest,
         "trip_ends": [round(float(x - t_open), 4) for x in ends[c]]}
        for c, lg in enumerate(logs)
    ]
    win = {
        "open_at": warm - 1, "close_at": int(np.searchsorted(ends[slowest], t_close)),
        "t_open": t_open, "t_close": t_close, "elapsed_s": elapsed,
        "units": int(len(inside)), "work": keys_done, "rate": keys_done / elapsed,
    }
    return {
        "end_to_end": {"wire_rate": win["rate"], "setup_s": setup_s},
        "attempted": attempted,
        "failed": unseen + wrong,
        "checks": checks,
        "window": win,
        "stamps": stamps,
        "counters": counters,
        "facts": {
            "mode": "wire", "kv_shards": 1, "data_shards": 1,
            "push_ms": lat["push"], "pull_ms": lat["pull"],
            "pushes_in_window": int(np.sum(inside[:, 0] == 0)),
            "pulls_in_window": int(np.sum(inside[:, 0] == 1)),
            "push_keys": int(t["push_keys"]), "pull_keys": n_pull,
        },
    }
