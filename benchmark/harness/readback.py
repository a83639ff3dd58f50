"""Read a sample of table rows off the device(s), shard by shard.

A gather on the whole sharded array would leave the partitioner free to
replicate a table that fills the chips; here each shard is asked for its
own rows on its own device, at one fixed shape, and the host puts the
answers together."""

from __future__ import annotations

import numpy as np


def read_rows(state: dict, rows: np.ndarray, block: int) -> dict:
    """``state``: name -> (num_rows, 1) jax array, sharded or not.
    Returns name -> float32 (len(rows),)."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda v, i: jnp.take(v, i, axis=0)[:, 0])
    rows = np.asarray(rows, np.int64)
    out = {k: np.zeros(len(rows), np.float32) for k in state}
    for name, arr in state.items():
        seen = set()
        for shard in arr.addressable_shards:
            sl = shard.index[0]
            lo = sl.start or 0
            hi = sl.stop if sl.stop is not None else arr.shape[0]
            if (lo, hi) in seen:  # a replica over the data axis
                continue
            seen.add((lo, hi))
            mine = np.flatnonzero((rows >= lo) & (rows < hi))
            for at in range(0, len(mine), block):
                part = mine[at : at + block]
                idx = np.zeros(block, np.int32)
                idx[: len(part)] = rows[part] - lo
                out[name][part] = np.asarray(take(shard.data, idx))[: len(part)]
    return out
