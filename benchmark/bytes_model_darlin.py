"""Bytes one block step of the batch solver has to move, from shapes.

Whatever implements the step, it has to read the block's entries once each
for the gradient and for X_b d (12 B an entry as the column blocks hold
them: local feature, example, value - an implementation that kept the
gathered terms between the two would still read them once), and the vectors
over the worker's N examples: ``pred`` and the labels read for e and c (8 B
an example), Xd written and read by the line search's sweep with ``pred``
and the labels (16 B), ``pred`` read and written by the update (8 B). The
block's rows of the table are pulled and pushed: ``w`` read and written,
``active`` read, and g, h, d written and read once each (36 B a row). No
kernel is assumed: a gather that reads a whole line for every 4-byte element
moves more than this, and that is the implementation's cost.

The KKT filter's refresh of a block reads its entries once (the gradient
alone), ``pred`` and the labels for e (8 B an example), and of the block's
rows ``w`` and ``active`` read, ``active`` written, g written and read (20 B
a row).
"""

from __future__ import annotations

ENTRY_BYTES = 12  # int32 local feature, int32 example, float32 value
ENTRY_PASSES = 2  # the gradient's sweep, and X_b d's
EXAMPLE_BYTES = 8 + 16 + 8  # e and c; the line search; the update of pred
ROW_BYTES = 36  # w in and out, active, g, h, d written and read
REFRESH_EXAMPLE_BYTES = 8
REFRESH_ROW_BYTES = 20


def step_bytes(entries: float, examples: int, block_rows: int) -> float:
    """One block step on one chip over ``entries`` real entries."""
    return entries * ENTRY_BYTES * ENTRY_PASSES + examples * EXAMPLE_BYTES + block_rows * ROW_BYTES


def refresh_bytes(entries: float, examples: int, block_rows: int) -> float:
    """The filter's refresh of one block on one chip over ``entries`` real entries."""
    return entries * ENTRY_BYTES + examples * REFRESH_EXAMPLE_BYTES + block_rows * REFRESH_ROW_BYTES
