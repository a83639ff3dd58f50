"""Device time under the scope ``ps.pull/emb`` (the gather from the
embedding table and its ``psum`` over ``kv``), ms a chip and microstep. None
where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.pull/emb")
