"""The multi-hot DLRM's table as a share of the HBM roofline, in %: the
bytes the algorithm needs of it a microstep (benchmark/bytes_model_dcn.py:
five row-widths a row the minibatch really touches under AdaGrad, 2,560 B
at 128 lanes) over the device seconds under ``ps.pull/emb`` and
``ps.push/*/emb`` (every op that addresses the table: the pull's gather,
the push's two gathers, update and two scatters), over the chip's peak
bytes/s. It is the store's gather and scatter at 512-byte rows reporting
their share of the roofline: there is no kernel. The streamed scatter
reads and writes the WHOLE table whatever the batch touches, and those
bytes are not the algorithm's: the share says how far that is from need.
None where the program names no such scopes or nothing was counted.

The row count is the app's, as ``store.dlrm_hbm_share``'s is:
``apps/dlrm_dcn.py`` ``Session._build`` counts the distinct rows of each
minibatch of the four training files' bags, takes the mean, and writes it
as ``counted.real_keys`` into the configuration dict the run's record
carries."""

from benchmark import bytes_model_dcn
from benchmark.layer_metrics_scopes import phase_seconds


def read(run):
    by_scope = phase_seconds(run)
    f = run["facts"]
    n, keys = f.get("microsteps"), run["config"].get("counted", {}).get("real_keys")
    if not by_scope or not n or not keys:
        return None
    seconds = sum(
        s for scope, s in by_scope.items()
        if scope == "ps.pull/emb" or (scope.startswith("ps.push/") and scope.endswith("/emb"))
    ) / max(run["trace"].chips, 1)
    if seconds <= 0:
        return None
    emb_dim = int(run["config"]["settings"]["emb_dim"])
    per_step = bytes_model_dcn.step_bytes(keys, emb_dim, f.get("pushes_per_step", 1) or 1)
    return 100.0 * (n * per_step / run["peaks"]["hbm_bytes_per_s"]) / seconds
