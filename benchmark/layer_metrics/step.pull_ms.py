"""Device time under the scope ``ps.pull`` (the gathers from the table and
the ``psum`` over ``kv``), ms a chip and microstep. XLA may merge the push's
gather of the same rows into the pull's: the survivor carries one scope."""

from benchmark.layer_metrics_scopes import phase_ms


def read(run):
    return phase_ms(run, "ps.pull")
