"""Device time under the scope ``darlin.xd`` (the batch solver's scatter of
X_b d over the examples and the update of ``pred``), ms a chip and block
step. None where the window's programs name no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "darlin.xd")
