"""The device as JAX reports it, the gate that refuses anything but the
chips a cell asks for, the peaks table, and a log of compilations."""

from __future__ import annotations

import json
import os
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoAccelerator(RuntimeError):
    pass


def require_chips(chips: int, allow_cpu: bool = False):
    """The devices a cell runs on, or NoAccelerator. ``allow_cpu`` is for
    the rehearsals and tests under ``benchmark/tests`` only: such a run
    prints no result line."""
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and not allow_cpu:
        raise NoAccelerator(f"JAX found platform {plat!r}, not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": peak,
    }


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in benchmark/peaks.json: add it with its source")
    return table[kind]


class CompileLog:
    """Every program JAX builds or fetches from its persistent cache, with
    the host-clock stamp at which it was ready: what may not happen inside
    a window."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        import jax.monitoring

        self.stamps: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.stamps.append((time.perf_counter(), event, duration))

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.stamps if t0 <= t <= t1)

    def compile_seconds(self) -> float:
        return sum(d for _, _, d in self.stamps)
