"""Bytes the algorithm needs, from shapes: the yardstick's copy of the
20 B/touched-row model at ``bench.py:child_scale`` and of
``parallel/traffic.py``'s collective and wire accounting. A later PR may
change the program's copies; it may not change these.

FTRL at ``vdim`` 1, float32: a step over U touched rows reads ``z`` and
``n`` (8 B), writes both (8 B) and reads the gradient (4 B): 20 B a row.
A pull alone (evaluation, the wire's pull) reads ``z`` and ``n``: 8 B.
Rows are counted as the program's shapes carry them (the padded unique
slots of a bucket), because that is what the gather and the scatter move.
"""

from __future__ import annotations

TRAIN_BYTES_PER_ROW = 20
PULL_BYTES_PER_ROW = 8


def train_step_bytes(rows: int, pushes: int = 1) -> int:
    """One microstep on one chip: ``pushes`` updater steps over ``rows``
    rows each (per_worker mode applies every data shard's push in turn).
    The pull's read of ``z`` and ``n`` is the same read: a step that
    gathers them twice moves more than the algorithm needs."""
    return pushes * rows * TRAIN_BYTES_PER_ROW


def pull_bytes(rows: int) -> int:
    return rows * PULL_BYTES_PER_ROW


def collective_bytes(unique_capacity: int, data_shards: int, kv_shards: int,
                     vdim: int = 1, value_bytes: int = 4, index_bytes: int = 4) -> int:
    """Per chip and microstep, push_mode per_worker: the ring psum of the
    pulled rows over ``kv`` and the ring all_gather of (index, gradient)
    over ``data`` (``parallel/traffic.py:linear_step_traffic``)."""
    u = unique_capacity
    pull = int(2 * (kv_shards - 1) / kv_shards * u * vdim * value_bytes) if kv_shards > 1 else 0
    push = 0
    if data_shards > 1:
        full = data_shards * u * (index_bytes + vdim * value_bytes)
        push = int((data_shards - 1) / data_shards * full)
    return pull + push
