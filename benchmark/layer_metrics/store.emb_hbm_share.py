"""The embedding table's share of the HBM roofline, in %: the bytes the
algorithm needs for it a microstep (benchmark/bytes_model_wd.py: five
row-widths a touched row and push, 320 B at ``emb_dim`` 16, rows as the
bucket carries them) over the device seconds under ``ps.pull/emb`` and
``ps.push/*/emb`` (every op that addresses the table), over the chip's
peak bytes/s. None where the program names no such scopes."""

from benchmark import bytes_model_wd
from benchmark.layer_metrics_scopes import phase_seconds


def read(run):
    by_scope = phase_seconds(run)
    f = run["facts"]
    n = f.get("microsteps")
    if not by_scope or not n:
        return None
    seconds = sum(
        s for scope, s in by_scope.items()
        if scope == "ps.pull/emb" or (scope.startswith("ps.push/") and scope.endswith("/emb"))
    ) / max(run["trace"].chips, 1)
    if seconds <= 0:
        return None
    emb_dim = int(run["config"]["settings"]["emb_dim"])
    per_step = bytes_model_wd.emb_step_bytes(f["bucket_rows"], emb_dim, f.get("pushes_per_step", 1) or 1)
    return 100.0 * (n * per_step / run["peaks"]["hbm_bytes_per_s"]) / seconds
