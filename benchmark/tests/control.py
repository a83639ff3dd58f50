#!/usr/bin/env python3
"""The control of a cell at the cell's own size: the plain reference in the
nearest precision below the one the configuration states (bfloat16 for
float32), put in the program's place. Prints every compared number as the
control reads it, beside the cell's limit; a number it does not fail is
marked. The program is not in it, so it needs no chip.

    python3 benchmark/tests/control.py ctr1.train 11 12 13
"""

import os
import sys
import time

import tiny
from benchmark.harness import manifest as mf
from benchmark.harness.context import Ctx


def control_of(workload: str, seed: int, precision: str = "bfloat16", overrides=None) -> tuple:
    found = mf.resolve(tiny.manifest_of(workload), workload)
    if overrides:
        found = overrides(found)
    ctx = Ctx(
        cell=found["cell"], config=found["config"], traffic=found["traffic"], seed=seed,
        seconds=0.0, trace=False, t0=time.perf_counter(),
        workdir=os.path.join(mf.ROOT, ".bench_work", "control." + workload),
    )
    os.makedirs(ctx.workdir, exist_ok=True)
    app = mf.load_module(found["app_path"], "app")
    return app.control(ctx, precision), found["traffic"]["limits"]


def main() -> int:
    workload, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]] or [11, 12, 13]
    failed_every_seed = True
    for seed in seeds:
        numbers, limits = control_of(workload, seed)
        fails = [n for n, v in numbers.items() if n in limits and not v <= limits[n]]
        for n, v in numbers.items():
            if n in limits:
                print(f"[control] {workload} seed {seed} {n}: {v:.6g} (limit {limits[n]:.6g}) "
                      f"{'fails, as it must' if n in fails else 'passes'}")
        failed_every_seed &= bool(fails)
    print("control comes out as not correct on every seed" if failed_every_seed else "CONTROL PASSED ON SOME SEED")
    return 0 if failed_every_seed else 1


if __name__ == "__main__":
    sys.exit(main())
