"""store.hbm_share for the wire tier, in %: 20 B a pushed row and 8 B a
pulled row over the window's acknowledged operations, over the time of the
ops that address the table (whole-table copies included: they are what the
server's apply costs), over the chip's peak bytes/s."""

from benchmark import bytes_model
from benchmark.layer_metrics_common import table_op_seconds


def read(run):
    f = run["facts"]
    seconds = table_op_seconds(run)
    if seconds <= 0:
        return None
    need = (
        f["pushes_in_window"] * bytes_model.train_step_bytes(f["push_keys"])
        + f["pulls_in_window"] * bytes_model.pull_bytes(f["pull_keys"])
    )
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
