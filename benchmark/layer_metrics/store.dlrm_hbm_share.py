"""DLRM's table as a share of the HBM roofline, in %: the bytes the
algorithm needs of it a microstep (benchmark/bytes_model_dlrm.py: three
row-widths a row the minibatch really touches, 1,536 B at 128 lanes) over
the device seconds under ``ps.pull/emb`` and ``ps.push/*/emb`` (every op
that addresses the table: the pull's gather, the push's gather, update and
scatter), over the chip's peak bytes/s. It is the store's gather and
scatter at 512-byte rows reporting their share of the roofline: there is
no kernel. None where the program names no such scopes or nothing was
counted.

**Where the row count comes from: the app, not the window.** The ``train``
kind's facts hold the bucket's key slots (65,536), not the keys, and only
a ``benchmark`` PR may give that kind a ``real_keys`` fact as ``train_mf``
has one. Until then ``apps/dlrm.py`` ``Session._build`` counts the distinct
categorical rows of each minibatch of the four TRAINING FILES (the data
``--seed`` makes, before any step runs), takes their mean, and writes it as
``counted.real_keys`` into the configuration dict the run's record
carries: so that record's ``config`` holds one key its file does not. The
window cycles those same files, every minibatch as often as every other up
to the last partial pass, so the mean is the window's to within a pass's
share of it (45,029 to 45,611 a minibatch: 1.3% end to end); it is not a
count of the rows the device calls of the window pulled."""

from benchmark import bytes_model_dlrm
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
    per_step = bytes_model_dlrm.step_bytes(keys, emb_dim, f.get("pushes_per_step", 1) or 1)
    return 100.0 * (n * per_step / run["peaks"]["hbm_bytes_per_s"]) / seconds
