"""Device time under the scope ``ps.grad/emb/pool`` (the multi-hot DLRM's
take of the pulled rows by an example's 214 bag positions, the sum of each
field's bag into ``(B, 26, d)``, and their backward pass: the scatter-add
of the fields' cotangents into the pulled rows' gradient), ms a chip and
microstep. None where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.grad/emb/pool")
