"""The batch solver's block step as a share of the HBM roofline, in %: the
bytes a block step has to move (benchmark/bytes_model_darlin.py: the block's
entries twice, the vectors over the examples, the block's rows) over the
device's busy seconds a block step, over the chip's peak bytes/s. The whole
step, not one scope of it: whatever implements the step is held to the same
bytes. The busy seconds are the window's, so where the window holds calls
that refresh the KKT filter's active set, the bytes those have to move (the
entries once) are counted beside the steps'. None where the window ran no
block step of the solver."""

from benchmark import bytes_model_darlin


def read(run):
    f = run["facts"]
    n, entries = f.get("microsteps"), f.get("entries_swept")
    if not n or not entries or not f.get("block_size"):
        return None
    seconds = run["trace"].busy_s
    if seconds <= 0:
        return None
    moved = n * bytes_model_darlin.step_bytes(entries / n, int(f["examples"]), int(f["block_size"]))
    r = f.get("refresh_steps")
    if r:
        moved += r * bytes_model_darlin.refresh_bytes(
            f["refresh_entries"] / r, int(f["examples"]), int(f["block_size"])
        )
    return 100.0 * (moved / run["peaks"]["hbm_bytes_per_s"]) / seconds
