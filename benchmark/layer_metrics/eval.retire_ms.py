"""The evaluator's thread blocked on the chip, ms a call: the program's
timer ``eval.retire`` (the blocking read of the oldest call's result),
total over count across all passes of the process."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.retire")
