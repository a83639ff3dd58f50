"""Device time of the pull's ``psum`` over ``kv`` (the scope
``ps.pull/<table>/psum``: every slot of the bucket, the non-owner's zeros
included, all-reduced over the server shards), ms a chip and microstep.
Part of ``step.pull_ms``. None where the program names no such scope."""

from benchmark.layer_metrics_coll import collective_ms


def read(run):
    return collective_ms(run, "ps.pull", "psum")
