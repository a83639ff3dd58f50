"""Sparse CSR compute: sweeps by example and by key slot.

Reference analog: the two hot loops of the async SGD worker
(src/app/linear_method/async_sgd.h): the CSR sparse matvec ``p = X w`` and
its transpose ``g = X^T (sigma(p) - y)``. Each is two sweeps of the
flattened entry list. The sweep by key slot (``local_ids``) is a true
element gather / ``segment_sum``; padding entries (value 0 -> slot 0)
vanish arithmetically instead of via masks. The sweep by example is
neither: an example's entries lie next to each other in the CSR buffer, so
its sum and its transpose are running passes along the entry axis
(``sum_by_example`` / ``spread_by_example``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint


def _row_scan(x: jax.Array, row_ids: jax.Array) -> jax.Array:
    """Inclusive running sums along the LAST axis, the entries', that start
    again at every row: log2(NNZ) shifted adds, each gated on the two
    entries sharing a row id, each one streaming pass
    (``spmd._running_sum``'s form; not ``jnp.cumsum``, whose reduce-window
    tree loses its op_name). Nothing is summed across rows and
    differenced, so nothing cancels; a row's terms are added as a tree.
    ``x`` is (NNZ,) or (d, NNZ), pinned entries-minor: left alone XLA
    keeps the layout of the gather that made the terms, every entry's 16
    lanes padded to a tile's 128, and each pass moves eight times the
    bytes (PERF.md section 6, PR 42)."""
    if x.ndim > 1:
        x = with_layout_constraint(x, Layout(major_to_minor=(0, 1)))
    lead = [(0, 0)] * (x.ndim - 1)
    k = 1
    while k < x.shape[-1]:
        same = row_ids[k:] == row_ids[:-k]
        x = x + jnp.pad(jnp.where(same, x[..., :-k], 0), [*lead, (k, 0)])
        k *= 2
    return x


@jax.custom_vjp
def sum_by_example(
    x: jax.Array,  # (NNZ,) or (NNZ, d) per-entry terms
    row_ids: jax.Array,  # (NNZ,) entry -> example row
    row_splits: jax.Array,  # (B+1,) cumulative real entries per row
) -> jax.Array:
    """out[i] = sum of x over example i's entries -> (B,) or (B, d).

    The promise it rests on (``data.batch.CSRBatch``; a test holds it, no
    run-time check): row i's real entries are ``row_splits[i]`` to
    ``row_splits[i + 1]``, so their ``row_ids`` never decrease, and the
    pads lie behind the last real entry. Which row a pad names does not
    matter (the device's ``spmd._row_ids_of`` says the last, the host's
    ``CSRBatch.row_ids`` row 0): a row's total is read at its own last
    real entry, which no pad precedes, and an empty row reads 0.

    Differentiable in ``x``: a ``custom_vjp`` names ``spread_by_example``
    as the transpose (and this op as that one's), so a backward pass is
    the other op as written and measured, with the row structure its only
    residual, and not whatever autodiff makes of the passes."""
    ends = row_splits[1:]
    totals = jnp.take(_row_scan(x.T, row_ids), jnp.maximum(ends - 1, 0), axis=-1)
    return jnp.where(ends > row_splits[:-1], totals, 0).T


@jax.custom_vjp
def spread_by_example(
    v: jax.Array,  # (B,) or (B, d) per-example values
    row_ids: jax.Array,
    row_splits: jax.Array,
) -> jax.Array:
    """out[j] = v[row of entry j] on real entries, 0 on pads -> (NNZ,) or
    (NNZ, d): ``sum_by_example``'s transpose, on the same promise. Each
    filled row's value is placed at the row's first entry and the passes
    of ``_row_scan`` copy it down the row (v + 0 + ... + 0: exact)."""
    nnz = row_ids.shape[0]
    starts = row_splits[:-1]
    # an empty row places nothing: its start is the next row's first entry
    at = jnp.where(row_splits[1:] > starts, starts, nnz)
    heads = jnp.zeros((*v.shape[1:], nnz), v.dtype).at[..., at].add(v.T, mode="drop")
    real = jnp.arange(nnz) < row_splits[-1]
    return jnp.where(real, _row_scan(heads, row_ids), 0).T


sum_by_example.defvjp(
    lambda x, ids, splits: (sum_by_example(x, ids, splits), (ids, splits)),
    lambda rows, ct: (spread_by_example(ct, *rows), None, None),
)
spread_by_example.defvjp(
    lambda v, ids, splits: (spread_by_example(v, ids, splits), (ids, splits)),
    lambda rows, ct: (sum_by_example(ct, *rows), None, None),
)


def csr_logits(
    w_u: jax.Array,  # (U,) or (U, 1) weights for the batch's unique keys
    values: jax.Array,  # (NNZ,)
    local_ids: jax.Array,  # (NNZ,) entry -> unique slot
    row_ids: jax.Array,  # (NNZ,) entry -> example row
    row_splits: jax.Array,  # (B+1,)
) -> jax.Array:
    """p[i] = sum_j X[i,j] * w[j] over the batch's CSR entries -> (B,)."""
    w_flat = w_u.reshape(-1)
    contrib = values * jnp.take(w_flat, local_ids)
    return sum_by_example(contrib, row_ids, row_splits)


def csr_grad(
    err: jax.Array,  # (B,) per-example residual, already masked
    values: jax.Array,
    local_ids: jax.Array,
    row_ids: jax.Array,
    row_splits: jax.Array,
    num_unique: int,
) -> jax.Array:
    """g[u] = sum_i X[i,u] * err[i] -> (U, 1), aligned with unique_keys.

    This is the pre-aggregation (segment sum over duplicate keys) that the
    kv push contract requires."""
    contrib = values * spread_by_example(err, row_ids, row_splits)
    g = jax.ops.segment_sum(contrib, local_ids, num_segments=num_unique)
    return g[:, None]


def logistic_loss(
    logits: jax.Array, labels: jax.Array, mask: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Masked summed logloss and the residual (p - y) * mask.

    Ref: logit loss in src/app/linear_method/loss.h. Stable formulation:
    log(1+e^x) - y*x = softplus(x) - y*x."""
    m = mask.astype(logits.dtype)
    loss = jnp.sum(m * (jax.nn.softplus(logits) - labels * logits))
    err = (jax.nn.sigmoid(logits) - labels) * m
    return loss, err
