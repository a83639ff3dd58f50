"""Mean scoring time of an ``evaluate_files`` pass, ms: the program's timer
``eval.score`` (concatenate, AUC, logloss over the pass), during which the
device idles. Total over count across ALL passes of the process, the warm
one included: the ``eval`` kind takes no timer snapshots at its stamps, and
the cell's passes are identical by construction (``eval.passes_differ`` is 0)."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.score")
