"""The predict call itself, ms a call: the program's timer ``eval.enqueue``,
total over count across all passes of the process, after taking off
``eval.new_shapes`` (the warm pass's first call, which compiles or fetches
the program and belongs to set-up)."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.enqueue", less="eval.new_shapes")
