"""Device time under the scope ``ps.grad/mlp/interact`` (DLRM's pairwise
dots ``T T^t`` of an example's 27 vectors, the cut of the pairs under the
diagonal, and their backward pass), ms a chip and microstep. None where the
program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.grad/mlp/interact")
