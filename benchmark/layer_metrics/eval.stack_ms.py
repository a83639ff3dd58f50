"""Host stack + H2D of a predict call's batches, ms a call: the program's
timer ``eval.stack`` (``pad_group`` + ``stack_batches``), total over count
across all passes of the process, as ``eval.open_ms`` is."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.stack")
