"""DLRM-DCNv2's dense half as a share of the matrix unit's peak, in %: the
operations the two MLPs and the cross network need a microstep, forward and
backward (benchmark/bytes_model_dcn.py, from the configuration's widths:
787.8 GFLOP at the MLPerf sizes), over the device seconds under
``ps.grad/mlp`` (bottom MLP, cross network, top MLP), over the chip's peak
bfloat16 operations/s. The configuration states float32 at
``precision=highest``, six bfloat16 passes a product: the share cannot pass
a sixth of the peak, 16.7%. None where the program names no such scope or
the configuration states no cross network."""

from benchmark import bytes_model_dcn
from benchmark.layer_metrics_scopes import phase_seconds


def read(run):
    by_scope = phase_seconds(run)
    n, settings = run["facts"].get("microsteps"), run["config"]["settings"]
    if not by_scope or not n or not settings.get("cross_layers"):
        return None
    seconds = sum(
        s for scope, s in by_scope.items() if scope == "ps.grad/mlp" or scope.startswith("ps.grad/mlp/")
    ) / max(run["trace"].chips, 1)
    if seconds <= 0:
        return None
    flops = n * bytes_model_dcn.step_flops(settings)
    return 100.0 * (flops / run["peaks"]["bf16_flops_per_s"]) / seconds
