"""Bytes the algorithm needs for the skip-gram table, from shapes.

Plain SGD at ``vdim`` ``dim``, float32, keeps ``w`` alone. A microstep
reads every touched row once for the pull (the scores and the gradient need
it), and the push reads it again and writes it: three row-widths a touched
row, 3 x 1,200 B at 300 dimensions. Rows are the keys a minibatch really
holds (its distinct centres' input vectors and its distinct contexts' and
negatives' output vectors), not the padded slots of its bucket, and a row
is its 300 lanes, not the lanes the store keeps it in: neither a pad slot
nor a pad lane is anything the algorithm needs.
"""

from __future__ import annotations

VALUE_BYTES = 4
ROW_PASSES = 3  # read by the pull; read and written by the push


def step_bytes(real_keys: float, dim: int, pushes: int = 1) -> float:
    """One microstep on one chip: one pull of ``real_keys`` rows, and
    ``pushes`` updater steps (per_worker mode applies every data shard's
    push in turn) that each read and write as many."""
    row = dim * VALUE_BYTES
    return real_keys * row * (1 + 2 * pushes)
