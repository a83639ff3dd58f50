"""Device time under the scope ``ps.grad/mlp/cross`` (DLRM-DCNv2's low-rank
cross network, ``x_{l+1} = x_0 * ((x_l V_l) W_l + b_l) + x_l`` over the
3,456-wide ``[z0; p_1; ...; p_26]``, forward and backward), ms a chip and
microstep. None where the program names no such scope."""

from benchmark.layer_metrics_named import named_phase_ms


def read(run):
    return named_phase_ms(run, "ps.grad/mlp/cross")
