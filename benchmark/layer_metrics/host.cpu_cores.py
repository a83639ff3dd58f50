"""Cores the whole process kept busy over the window: ``process.cpu``
(``time.process_time()`` at the window's two snapshots: every thread, the
runtime's transfer threads, the collector and a profiler's tracer
included) over the window's seconds."""

from benchmark.layer_metrics_cpu import cpu_cores


def read(run):
    return cpu_cores(run)
