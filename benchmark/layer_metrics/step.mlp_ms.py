"""Device time under the scope ``ps.grad/mlp`` (the tower's forward and
backward), ms a chip and microstep. None
where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.grad/mlp")
