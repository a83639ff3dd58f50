"""The skip-gram table's share of the HBM roofline, in %: the bytes the
algorithm needs for it a microstep (benchmark/bytes_model_sgns.py: three
row-widths a key the minibatch really holds, 3,600 B at 300 dimensions)
over the device seconds under ``ps.pull/sgns`` and ``ps.push/*/sgns``
(every op that addresses the table: the pull's gather, the push's gather,
update and scatter), over the chip's peak bytes/s. It is the store's gather
and scatter at 1,200-byte rows reporting their share of the roofline: there
is no kernel. None where the program names no such scopes."""

from benchmark import bytes_model_sgns
from benchmark.layer_metrics_scopes import phase_seconds


def read(run):
    by_scope = phase_seconds(run)
    f = run["facts"]
    n, keys = f.get("microsteps"), f.get("real_keys")
    if not by_scope or not n or not keys:
        return None
    seconds = sum(
        s for scope, s in by_scope.items()
        if scope == "ps.pull/sgns" or (scope.startswith("ps.push/") and scope.endswith("/sgns"))
    ) / max(run["trace"].chips, 1)
    if seconds <= 0:
        return None
    dim = int(run["config"]["settings"]["dim"])
    per_step = bytes_model_sgns.step_bytes(keys, dim, f.get("pushes_per_step", 1) or 1)
    return 100.0 * (n * per_step / run["peaks"]["hbm_bytes_per_s"]) / seconds
