"""% of the evaluator's passes (``eval.pass``) that none of the six leaves
covers (``eval.open_reader``, ``eval.read``, ``eval.stack``,
``eval.enqueue``, ``eval.retire``, ``eval.score``): loop overhead, and
whatever a later change puts into the pass without naming it. Over the
whole process, as ``eval.open_ms`` is."""

from benchmark.layer_metrics_host import process_timers, unnamed_share


def read(run):
    snap = process_timers()
    return None if snap is None else unnamed_share(snap)
