"""Device time under the scope ``ps.grad`` (logits, loss, gradient, sigmoid:
the worker's arithmetic), ms a chip and microstep."""

from benchmark.layer_metrics_scopes import phase_ms


def read(run):
    return phase_ms(run, "ps.grad")
