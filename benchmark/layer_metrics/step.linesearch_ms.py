"""Device time under the scope ``darlin.linesearch`` (the eight objective
terms of a block's step scale, and the call's closing objective), ms a chip
and block step. None where the window's programs name no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "darlin.linesearch")
