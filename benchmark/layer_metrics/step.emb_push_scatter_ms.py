"""Device time under the scope ``ps.push/scatter/emb`` (the ``.at[].add``
into the embedding table's ``w`` and ``n``), ms a chip and microstep. None
where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.push/scatter/emb")
