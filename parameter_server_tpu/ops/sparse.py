"""Sparse CSR compute: sweeps by example and by key slot.

Reference analog: the two hot loops of the async SGD worker
(src/app/linear_method/async_sgd.h): the CSR sparse matvec ``p = X w`` and
its transpose ``g = X^T (sigma(p) - y)``. Each is two sweeps of the
flattened entry list. The sweep by key slot (``local_ids``) is a true
element gather / scatter-add that costs by the entry slot, so it visits
only the head of the entry axis that holds real entries (``take_by_slot``
/ ``sum_by_slot``); a pad reads 0. The sweep by example is neither: an
example's entries lie next to each other in the CSR buffer, so its sum and
its transpose are running passes along the entry axis (``sum_by_example``
/ ``spread_by_example``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint


def _entries_minor(x: jax.Array) -> jax.Array:
    """(NNZ,) or (d, NNZ) pinned entries-minor: left alone XLA keeps the
    layout of the gather that made the terms, every entry's 16 lanes padded
    to a tile's 128, and each pass over them moves eight times the bytes
    (PERF.md section 6, PR 42)."""
    if x.ndim > 1:
        x = with_layout_constraint(x, Layout(major_to_minor=(0, 1)))
    return x


def _row_scan(x: jax.Array, row_ids: jax.Array) -> jax.Array:
    """Inclusive running sums along the LAST axis, the entries', that start
    again at every row: log2(NNZ) shifted adds, each gated on the two
    entries sharing a row id, each one streaming pass
    (``spmd._running_sum``'s form; not ``jnp.cumsum``, whose reduce-window
    tree loses its op_name). Nothing is summed across rows and
    differenced, so nothing cancels; a row's terms are added as a tree.
    ``x`` is (NNZ,) or (d, NNZ), pinned entries-minor."""
    x = _entries_minor(x)
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


# The entry slots of one piece of a walked sweep by key slot (``sweep_walks``).
# A take or a sum by ``local_ids`` costs by the entry slot it is handed, 6.9 ns
# at one lane, whatever the slot holds, and a bucketed batch's real entries
# are the head of its entry axis (524,288 slots for 319,488 entries in every
# CTR cell): the walk sweeps that head alone, a piece a loop turn. Alone on a
# v5e, ms a sweep of one batch's entry axis into or out of 65,536 key slots,
# whole and walked (tools/probe_csr_sweeps.py 2470000001 --walk; PERF.md
# section 6, PR 47):
#
#     sweep, real entries of 524,288      whole     8,192   16,384   32,768   65,536
#     take, 1 lane, 319,488               3.784     2.373    2.400    2.401    2.398
#       0 / 131,072 / 524,288             3.78  0.19 / 0.99 / 3.88        0.20 / 0.98 / 3.81
#     sum, 1 lane, 319,488                3.949     2.268    2.310    2.292    2.281
#       0 / 131,072 / 524,288             3.95  0.19 / 0.95 / 4.11        0.19 / 0.93 / 4.01
#     take, 16 lanes, 319,488             2.240     1.152    1.139    1.139    1.142
#       0 / 131,072 / 524,288             2.24  0.20 / 0.54 / 1.81        0.22 / 0.52 / 1.76
#     sum, 16 lanes, 319,488              8.088    14.349    3.935    3.878    3.850
#       0 / 131,072 / 524,288             8.09  0.20 / 5.93 / 23.49       0.21 / 1.57 / 6.27
#     the linear app's whole ps.grad      7.896     4.803    4.879    4.864    4.849
#     Wide&Deep's whole ps.grad          20.006    22.889   12.539   12.459   12.597
#
# A line through zero in the slots visited (0.19 ms is a call's floor) at
# the whole sweep's price by the slot, and 1-2 us a loop turn. The 16-lane
# sum is the one that minds its piece: XLA's TPU compiler sorts a scatter's
# indices first where the accumulator has 32,768 to 262,144 rows AND the
# scatter holds more than an eighth as many entries as that (its word at
# ``f32[U,16]``, no chip), and a piece under that is the plain scatter at
# three times the price by the entry. 65,536 is the least piece that sorts
# at every key bucket where the whole sum does; its price is its rounding,
# the batch's last piece visited whole (at 319,488 every piece from 16,384
# up visits 327,680), and that an entry axis of 65,536 slots is one sweep.
_WALK_ENTRIES = 65_536


def sweep_walks(entries: int) -> bool:
    """Whether the two sweeps by key slot of an entry axis of ``entries``
    slots walk it in pieces of ``_WALK_ENTRIES``, those alone that hold a
    real entry: where there is more than one piece. Static shape in, as
    ``spmd.scatter_walks`` reads its own."""
    return entries > _WALK_ENTRIES


def walked_entries(real: int, entries: int) -> int:
    """On the host: the entry slots a sweep by key slot visits of an entry
    axis of ``entries`` slots whose first ``real`` hold an entry: the whole
    ``_WALK_ENTRIES`` pieces up to the last real entry (``_walk``'s trip
    count times the piece: a last piece that starts early still visits
    every slot of it), and the whole axis where no sweep walks."""
    if not sweep_walks(entries):
        return entries
    return -(-real // _WALK_ENTRIES) * _WALK_ENTRIES


def _walk(row_splits: jax.Array, entries: int, piece, carry):
    """``carry = piece(at, real, fresh, carry)`` over the ``_WALK_ENTRIES``
    pieces of the entry axis that hold a real entry, ``[0,
    row_splits[-1])`` by the promise ``sum_by_example`` states. ``at`` is
    the piece's first entry slot; ``real`` marks its slots that hold an
    entry, ``fresh`` those of them no earlier piece has visited: an entry
    axis that is no whole number of pieces has its last piece start early
    (``dynamic_slice`` would clamp it anyway). The trip count is read off
    the batch on each chip, so a ``piece`` holds no collective."""
    n = row_splits[-1]
    slot = lax.iota(jnp.int32, _WALK_ENTRIES)

    def turn(i, carry):
        first = i * _WALK_ENTRIES
        if entries % _WALK_ENTRIES == 0:
            real = first + slot < n
            return piece(first, real, real, carry)
        at = jnp.minimum(first, entries - _WALK_ENTRIES)
        j = at + slot
        return piece(at, j < n, (j < n) & (j >= first), carry)

    trips = lax.div(n + (_WALK_ENTRIES - 1), _WALK_ENTRIES)
    return lax.fori_loop(0, trips, turn, carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_by_slot(w, local_ids, row_splits, num_slots):
    """``take_by_slot``; ``num_slots`` is ``w.shape[0]``, static for the
    transpose (a cotangent's shape does not say it)."""
    entries = local_ids.shape[0]
    if not sweep_walks(entries):
        real = jnp.arange(entries) < row_splits[-1]
        return jnp.where(real, jnp.take(w, local_ids, axis=0).T, 0).T

    def piece(at, real, fresh, out):
        ids = lax.dynamic_slice(local_ids, (at,), (_WALK_ENTRIES,))
        got = jnp.where(real, jnp.take(w, ids, axis=0).T, 0)  # written twice: the same
        return lax.dynamic_update_slice_in_dim(out, got, at, axis=-1)

    out = _entries_minor(jnp.zeros((*w.shape[1:], entries), w.dtype))
    return _walk(row_splits, entries, piece, out).T


def take_by_slot(
    w: jax.Array,  # (U,) or (U, d) per-slot values
    local_ids: jax.Array,  # (NNZ,) entry -> unique slot
    row_splits: jax.Array,  # (B+1,)
) -> jax.Array:
    """out[j] = w[local_ids[j]] on real entries, 0 on pads -> (NNZ,) or
    (NNZ, d), on the promise ``sum_by_example`` states: the real entries
    are ``[0, row_splits[-1])``, the pads behind them.

    A true element gather, which costs by the entry whatever the entry
    holds: where ``sweep_walks`` says so it is a loop over the
    ``_WALK_ENTRIES`` pieces of the entry axis that hold a real entry
    (``_walk``), a gather of one piece a turn written into the output the
    loop carries (entries minor at ``d`` lanes, as ``_row_scan`` wants its
    terms), and the pads behind them are never gathered; one piece or less
    is one ``jnp.take``. A loop whose trip count is read on the chip is a
    ``while``, which autodiff cannot reverse: a ``custom_vjp`` names
    ``sum_by_slot`` as the transpose (and this op as that one's)."""
    return _take_by_slot(w, local_ids, row_splits, w.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def sum_by_slot(
    x: jax.Array,  # (NNZ,) or (NNZ, d) per-entry terms
    local_ids: jax.Array,
    row_splits: jax.Array,
    num_slots: int,
) -> jax.Array:
    """g[u] = sum of x over the real entries with ``local_ids[j] == u`` ->
    (U,) or (U, d): ``take_by_slot``'s transpose, on the same promise and
    by the same walk, one scatter-add of a piece's entries a turn into the
    accumulator the loop carries; one piece or less is one
    ``segment_sum``. A slot's terms are added piece by piece."""
    entries = local_ids.shape[0]
    if not sweep_walks(entries):
        real = jnp.arange(entries) < row_splits[-1]
        return jax.ops.segment_sum(
            jnp.where(real, x.T, 0).T, local_ids, num_segments=num_slots
        )
    terms = _entries_minor(x.T)

    def piece(at, real, fresh, acc):
        ids = lax.dynamic_slice(local_ids, (at,), (_WALK_ENTRIES,))
        t = lax.dynamic_slice_in_dim(terms, at, _WALK_ENTRIES, axis=-1)
        return acc.at[ids].add(jnp.where(fresh, t, 0).T)

    return _walk(row_splits, entries, piece, jnp.zeros((num_slots, *x.shape[1:]), x.dtype))


_take_by_slot.defvjp(
    lambda w, ids, splits, n: (_take_by_slot(w, ids, splits, n), (ids, splits)),
    lambda n, res, ct: (sum_by_slot(ct, *res, n), None, None),
)
sum_by_slot.defvjp(
    lambda x, ids, splits, n: (sum_by_slot(x, ids, splits, n), (ids, splits)),
    lambda n, res, ct: (take_by_slot(ct, *res), None, None),
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
    contrib = values * take_by_slot(w_flat, local_ids, row_splits)
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
    return sum_by_slot(contrib, local_ids, row_splits, num_unique)[:, None]


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
