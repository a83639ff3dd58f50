"""Device time under the scope ``ps.dense`` (the dense group's ``psum``
over ``data`` and its optimizer step), ms a chip and microstep. None
where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.dense")
