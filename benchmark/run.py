#!/usr/bin/env python3
"""The benchmark's one entry.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell in ``BENCHMARK.json`` to its configuration, its traffic
mix, the mix's kind and the configuration's app - all files found by name -
runs it on the machine it is started on, prints the per-unit stamps and
each number compared beside its limit, and as the last line of stdout one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``. Without the chips the cell asks for
it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, for setup_s

import argparse
import json
import math
from concurrent.futures import ThreadPoolExecutor
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device as dev  # noqa: E402
from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.context import Ctx  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="benchmark/parked.json runs a cell that is kept out of BENCHMARK.json")
    ap.add_argument("--keep-trace", action="store_true", help="leave the .xplane.pb under .bench_work")
    args = ap.parse_args(argv)

    manifest = mf.load_manifest(path=args.manifest)
    found = mf.resolve(manifest, args.workload)
    kind = mf.load_module(found["kind_path"], "traffic kind")
    app = mf.load_module(found["app_path"], "app")
    group = "per_layer" if args.trace else "end_to_end"
    wanted = mf.metrics_of(manifest, group, args.workload)
    readers = {m["name"]: mf.load_module(mf.metric_path(m["name"]), "reader") for m in wanted} if args.trace else {}

    if not os.path.isdir(os.path.join(ROOT, "parameter_server_tpu")):
        print("no program beside the benchmark: nothing to measure", file=sys.stderr)
        return 4

    from parameter_server_tpu.utils import hostenv

    cache_dir = hostenv.init_compile_cache()
    ctx = Ctx(
        cell=found["cell"], config=found["config"], traffic=found["traffic"],
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace), keep_trace=args.keep_trace, t0=T0,
        workdir=os.path.join(ROOT, ".bench_work", args.workload),
    )
    os.makedirs(ctx.workdir, exist_ok=True)
    ctx.stage("imports done")
    # the TPU runtime takes its ten seconds or so to start whatever we do:
    # let the app make its data (NumPy and files only) meanwhile
    chips = int(found["cell"]["chips"])
    with ThreadPoolExecutor(1) as pool:
        looking = pool.submit(dev.require_chips, chips)
        if hasattr(app, "prepare"):
            ctx.prepared = app.prepare(ctx)
        try:
            devs = looking.result()
        except dev.NoAccelerator as e:
            print(f"refusing to run: {e}", file=sys.stderr)
            shutil.rmtree(ctx.workdir, ignore_errors=True)
            return 3
    ctx.devices = devs
    ctx.stage("devices found")
    peaks = dev.peaks_for(devs[0].device_kind)
    compiles = dev.CompileLog()
    print(
        f"[bench] {args.workload}: {devs[0].device_kind} x {len(devs)}, "
        f"{os.cpu_count()} host cores, compile cache {cache_dir}", flush=True,
    )
    rec = kind.run(ctx, app)
    return report(ctx, rec, wanted, readers, devs, peaks, compiles)


def report(ctx, rec, wanted, readers, devs, peaks, compiles) -> int:
    from benchmark.harness import trace_report

    win = rec["window"]
    for row in rec["stamps"]:
        print("[stamp] " + json.dumps(row))
    print(f"[window] {json.dumps({k: v for k, v in win.items()})}")
    if rec.get("timers_close"):
        # seconds each named phase of the program took between the window's stamps:
        # where a stall of the host went (fetch: the feed; retire: the chip; none: outside the loop)
        spent = trace_report.timers_delta(rec.get("timers_open"), rec["timers_close"])
        print("[timers] " + json.dumps({k: round(v["total_s"], 4) for k, v in sorted(spent.items()) if v["count"]}))
    for c in rec["checks"]:
        print(c.line())
    device = dev.describe(devs)
    metrics, breakdown = {}, None
    if not ctx.trace:
        for m in wanted:
            metrics[m["name"]] = {"value": rec["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        run = trace_report.assemble(ctx, rec, device, peaks, compiles)
        device["busy_s"] = run["trace"].busy_s
        device["window_s"] = run["trace"].window_s
        breakdown = trace_report.breakdown(run)
        for m in wanted:
            value = readers[m["name"]].read(run)
            if value is None:
                continue
            if (m["name"].endswith("_roofline") or "hbm_share" in m["name"]) and value > 105.0:
                raise RuntimeError(
                    f"{m['name']} reads {value:.1f}% of the peak: the bytes are "
                    "counted too high or the time leaves out part of the work"
                )
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(c.ok for c in rec["checks"]) and rec["failed"] == 0
    out = {
        "correct": bool(correct),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = compiles.count_between(win["t_open"], win["t_close"])
    out["compile_s"] = compiles.compile_seconds()
    # each number compared beside its limit: last in the line, and the last lines of stderr
    out["checks"] = {
        c.name: {"value": c.value if math.isfinite(c.value) else repr(c.value), "limit": c.limit}
        for c in rec["checks"]
    }
    sys.stdout.flush()
    print("\n".join(c.line() for c in rec["checks"]), file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
