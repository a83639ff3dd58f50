"""What a traffic kind is handed for one run."""

from __future__ import annotations

import os
from dataclasses import dataclass, field


@dataclass
class Ctx:
    cell: dict  # the workload's entry in BENCHMARK.json
    config: dict  # the configuration's file
    traffic: dict  # the mix's file
    seed: int
    seconds: float
    trace: bool
    t0: float  # perf_counter at process start
    workdir: str  # inside the checkout, ignored by git
    devices: list = field(default_factory=list)
    keep_trace: bool = False
    prepared: object = None  # what the app's prepare() made while the runtime started
    excluded_s: float = 0.0  # reference time spent before the window: not set-up

    def stage(self, name: str) -> None:
        """One line per set-up stage: seconds since the process started."""
        import time

        print(f"[setup] {time.perf_counter() - self.t0:8.3f} s  {name}", flush=True)

    def mark(self, name: str) -> None:
        """An instant ``bench.*`` mark in the profiler's trace (traced runs
        only): what the reduction cuts the window by and labels gaps with."""
        if self.trace:
            from jax.profiler import TraceAnnotation

            with TraceAnnotation(name):
                pass

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.workdir, "trace")
