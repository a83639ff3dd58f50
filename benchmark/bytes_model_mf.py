"""Bytes the algorithm needs for the matrix-factorization table, from shapes.

Plain SGD at ``vdim`` ``rank``, float32, keeps ``w`` alone. A microstep
reads every touched row once for the pull (the prediction and the gradient
need it), and the push reads it again and writes it: three row-widths a
touched row, 3 x 256 B at rank 64. Rows are the keys a minibatch really
holds (its distinct items and users), not the padded slots of its bucket:
a pad slot is no row the algorithm needs.
"""

from __future__ import annotations

VALUE_BYTES = 4
ROW_PASSES = 3  # read by the pull; read and written by the push


def step_bytes(real_keys: float, rank: int, pushes: int = 1) -> float:
    """One microstep on one chip: one pull of ``real_keys`` rows, and
    ``pushes`` updater steps (per_worker mode applies every data shard's
    push in turn) that each read and write as many."""
    row = rank * VALUE_BYTES
    return real_keys * row * (1 + 2 * pushes)
