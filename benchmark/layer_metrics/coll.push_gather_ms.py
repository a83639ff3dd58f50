"""Device time of the push's ``all_gather`` over ``data`` (the scope
``ps.push/<table>/all_gather``: every worker's key slots and gradients
brought to every server shard), ms a chip and microstep. Part of
``step.push_ms``. None where the program names no such scope."""

from benchmark.layer_metrics_coll import collective_ms


def read(run):
    return collective_ms(run, "ps.push", "all_gather")
