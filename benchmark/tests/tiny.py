"""Tiny copies of the configurations for the CPU rehearsals and tests:
same code paths, a table and batches a CPU holds. Such a run prints no
device metric and no result line."""

from __future__ import annotations

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from benchmark.harness.context import Ctx  # noqa: E402


def all_cells() -> list:
    """(cell name, its manifest): BENCHMARK.json's cells and the parked ones."""
    out = []
    for path in ("BENCHMARK.json", mf.PARKED):
        m = mf.load_manifest(path=path)
        out += [(w["name"], m) for w in m["workloads"]]
    return out


def manifest_of(workload: str) -> dict:
    return dict(all_cells())[workload]


def tiny_ctx(workload: str, seed: int = 7, seconds: float = 1.0, workdir: str | None = None,
             trace: bool = False, **settings) -> tuple:
    """(ctx, kind module, app module) of ``workload`` cut to CPU size."""
    found = mf.resolve(manifest_of(workload), workload)
    config = copy.deepcopy(found["config"])
    config["settings"].update({"num_keys": 1 << 20, "minibatch": 256, "steps_per_call": 2})
    config["settings"].update(settings)
    traffic = copy.deepcopy(found["traffic"])
    for k in ("train_files", "heldout_files"):
        if k in traffic:
            traffic[k] = min(traffic[k], 4)
    traffic["min_call_s"] = 0.001
    if "eval.auc_gap" in traffic.get("limits", {}):
        # a tiny held-out set has few pairs: one swapped pair moves AUC by 1e-6
        traffic["limits"]["eval.auc_gap"] = 1e-4
    if traffic["kind"] == "workers":
        config["settings"]["num_keys"] = 1 << 22
        traffic.update(clients=2, push_keys=4096, pull_keys=2048, trips_cap=512, prefix_trips=3)
    ctx = Ctx(
        cell=found["cell"], config=config, traffic=traffic, seed=seed, seconds=seconds,
        trace=trace, t0=time.perf_counter(),
        workdir=workdir or os.path.join(ROOT, ".bench_work", "tiny." + workload),
    )
    os.makedirs(ctx.workdir, exist_ok=True)
    return ctx, mf.load_module(found["kind_path"], "kind"), mf.load_module(found["app_path"], "app")
