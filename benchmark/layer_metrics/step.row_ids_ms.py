"""Device time under the scope ``ps.row_ids`` (the rebuild of entry -> row
ids from the compact wire's ``row_splits``), ms a chip and microstep."""

from benchmark.layer_metrics_scopes import phase_ms


def read(run):
    return phase_ms(run, "ps.row_ids")
