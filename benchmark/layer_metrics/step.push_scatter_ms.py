"""Device time under the scope ``ps.push/scatter`` (the ``.at[].add`` into
the table), ms a chip and microstep. None where nothing is pushed."""

from benchmark.layer_metrics_scopes import phase_ms


def read(run):
    if not run["facts"].get("pushes_per_step", 0):
        return None
    return phase_ms(run, "ps.push/scatter")
