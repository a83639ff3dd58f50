"""Bytes the algorithm needs for Wide&Deep's embedding table, from shapes.

AdaGrad at ``vdim`` ``emb_dim``, float32: a step over U touched rows reads
``w`` and ``n`` (2 x 4 x emb_dim B a row), writes both, and reads the
gradient: five row-widths, 320 B a row at ``emb_dim`` 16. The pull's read
of ``w`` is the same read as the push's. Rows are counted as the program's
shapes carry them (the padded unique slots of a bucket), because that is
what the gather and the scatter move.
"""

from __future__ import annotations

VALUE_BYTES = 4
ROW_PASSES = 5  # w read, n read, w written, n written, gradient read


def emb_step_bytes(rows: int, emb_dim: int, pushes: int = 1) -> int:
    """One microstep on one chip: ``pushes`` updater steps (per_worker mode
    applies every data shard's push in turn) over ``rows`` rows each."""
    return pushes * rows * ROW_PASSES * emb_dim * VALUE_BYTES
