"""The evaluator's wait for its reader's thread, ms a group: the program's
timer ``eval.read`` (one group of ``data_shards`` batches taken off the
``MinibatchReader``'s queue), total over count across all passes of the
process, as ``eval.open_ms`` is."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.read")
