"""The store's share of the HBM roofline, in %: the bytes the algorithm
needs for the window's microsteps (benchmark/bytes_model.py: 20 B a
touched row and push, 8 B a pulled row when nothing is pushed) over the
time of the ops that address the table (those whose HLO names an operand of
the table's per-chip rows), over the chip's peak bytes/s."""

from benchmark import bytes_model
from benchmark.layer_metrics_common import table_op_seconds


def read(run):
    f = run["facts"]
    n = f.get("microsteps")
    if not n:
        return None
    seconds = table_op_seconds(run)
    if seconds <= 0:
        return None
    rows = f["bucket_rows"]
    if f.get("pushes_per_step", 0):
        per_step = bytes_model.train_step_bytes(rows, f["pushes_per_step"])
    else:
        per_step = bytes_model.pull_bytes(rows)
    least_s = n * per_step / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
