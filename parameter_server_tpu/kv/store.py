"""The KV store core: functional pull/push over dense state tables.

Reference analog: src/parameter/shared_parameter.h (the Push/Pull protocol)
+ src/parameter/kv_vector.h (worker-side match) + the server KV map. In the
TPU re-expression there is no wire: ``pull`` is a row gather and ``push``
is gather -> updater -> scatter over the touched rows only (never the full
table, mirroring the reference's touch-only server updates).

Invariants (enforced by the data layer's localizer, ref: Localizer in
src/app/linear_method/localizer.h):
  - ``idx`` passed to ``push`` contains each real key at most once; padding
    slots carry ``idx == PAD_KEY (0)`` and ``grad == 0``. Duplicate real
    keys must be pre-aggregated (segment-summed) by the caller: the updater
    computes one *delta* per (key, grad) pair, so double-counting a key
    would apply the nonlinear update twice.
  - Row 0 is the pad row: it absorbs zero-gradient updates and is excluded
    from dumps and nnz counts.

The SPMD (multi-device) pull/push live in parameter_server_tpu.parallel —
same updater objects, rows gathered from the local ``kv`` shard instead.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.kv.updaters import Updater

State = dict[str, jax.Array]


def pad_state_rows(state: State, num_rows: int) -> State:
    """Zero-extend every table of ``state`` on axis 0 up to ``num_rows``
    (identity when already there). Pad rows obey the store's pad-row
    invariant — exactly zero, never pushed (the data layer only emits
    keys below the real ``num_keys``), so they are invisible to pulls,
    dumps and nnz counts. This is what lets the sharded tiers accept an
    arbitrary ``num_keys`` on any kv-axis size: the table is padded up
    to the next axis multiple and the extra rows stay inert."""
    have = next(iter(state.values())).shape[0]
    if have == num_rows:
        return state
    if have > num_rows:
        raise ValueError(f"cannot pad {have} rows down to {num_rows}")
    return {
        k: jnp.concatenate(
            [v, jnp.zeros((num_rows - have, *v.shape[1:]), v.dtype)], axis=0
        )
        for k, v in state.items()
    }


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit finalizer over uint32 (arithmetic wraps)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def hashed_unit(seed: int, rows: jax.Array, vdim: int) -> jax.Array:
    """(len(rows), vdim) float32 in [-1, 1) as a function of (seed, row,
    lane) alone: a counter-based draw - two rounds of a 32-bit mix over the
    row and the lane, the top 24 bits as a multiple of 2^-23 - so that a
    table is made on the device slice by slice, no host array of its size
    ever exists, and anything that knows the seed can compute any row
    (the benchmark's references do, bit for bit: every step is exact in
    uint32 and float32)."""
    r = rows.astype(jnp.uint32)[:, None]
    lane = jnp.arange(vdim, dtype=jnp.uint32)[None, :]
    x = _fmix32(r * jnp.uint32(0x9E3779B1) + jnp.uint32(seed & 0xFFFFFFFF))
    x = _fmix32(x ^ (lane * jnp.uint32(0x85EBCA77) + jnp.uint32(0xC2B2AE3D)))
    return (x >> 8).astype(jnp.float32) * jnp.float32(2.0**-23) - jnp.float32(1.0)


def live_lanes(live: jax.Array, vdim: int, lanes: int | None = None) -> jax.Array:
    """The mask of a slot's elements that hold a value: rows ``live`` (N,),
    lanes below ``vdim`` of the ``lanes`` the slot is stored at
    (``spmd.row_stride``; ``vdim`` unsaid). (N, 1) where the slot is as wide
    as its rows, (N, lanes) where it is stored wider: a slot made ``lanes``
    wide under this mask in one elementwise pass equals the narrow one to
    the bit in its first ``vdim`` lanes (``hashed_unit`` is a function of
    the lane) and is zero past them, and no pad makes a second table."""
    keep = live[:, None]
    if lanes is None or lanes == vdim:
        return keep
    return keep & (jnp.arange(lanes) < vdim)[None, :]


def hashed_uniform(
    seed: int, rows: jax.Array, vdim: int, scale: float, live_rows: int,
    lanes: int | None = None,
) -> jax.Array:
    """Starting values of an embedding table: ``hashed_unit`` scaled to a
    uniform of standard deviation ``scale`` (one product, which IEEE rounds
    one way), exactly zero for the pad row 0 and for rows at or past
    ``live_rows`` (the kv-axis pad tail). ``lanes`` (``vdim`` unsaid) is
    the width made, zero past ``vdim`` (``live_lanes``)."""
    keep = live_lanes((rows > 0) & (rows < live_rows), vdim, lanes)
    unit = hashed_unit(seed, rows, lanes or vdim)
    return jnp.where(keep, unit * jnp.float32(scale * 3.0**0.5), 0.0)


@functools.partial(jax.jit, static_argnums=0)
def pull(updater: Updater, state: State, idx: jax.Array) -> jax.Array:
    """Gather weights for (unique, padded) key indices: (U,) -> (U, vdim)."""
    # phase names shared with the SPMD step (parallel.spmd.PHASE_SCOPES)
    with jax.named_scope("ps.pull"):
        rows = {k: jnp.take(v, idx, axis=0) for k, v in state.items()}
        return updater.weights(rows)


@functools.partial(jax.jit, static_argnums=0)
def push(updater: Updater, state: State, idx: jax.Array, grad: jax.Array) -> State:
    """Apply the server updater to the touched rows; returns new state.

    grad: (U, vdim) pre-aggregated gradient aligned with ``idx``.
    """
    with jax.named_scope("ps.push"):
        with jax.named_scope("gather"):
            rows = {k: jnp.take(v, idx, axis=0) for k, v in state.items()}
        with jax.named_scope("update"):
            deltas = updater.delta(rows, grad)
        with jax.named_scope("scatter"):
            return {k: state[k].at[idx].add(deltas[k]) for k in state}


@functools.partial(jax.jit, static_argnums=0)
def materialize_weights(updater: Updater, state: State) -> jax.Array:
    """Full (K, vdim) weight table (FTRL: lazily derived from z, n)."""
    return updater.weights(state)


def coalesce_pushes(
    idx_list: list[np.ndarray],
    grad_list: list[np.ndarray],
    pad_to_pow2: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Pre-aggregate several concurrent pushes into ONE (idx, grad) pair
    honoring the store invariant: each real key at most once, duplicate
    keys segment-summed. This is the host-side half of the server's
    batched apply engine — N pushes (possibly from N different clients,
    with overlapping key sets) collapse into one updater apply, and a
    nonlinear updater (FTRL) sees each gradient contribution exactly once
    in the aggregate, matching the paper's aggregated server updates.

    ``pad_to_pow2`` pads the union with PAD_KEY (0) rows carrying zero
    gradient — the same slot semantics the data layer's localizer
    guarantees (row 0 absorbs zero-gradient updates). Coalesced unions
    otherwise have a DIFFERENT length every batch, and on the eager
    server tier each fresh shape re-dispatches/compiles the whole updater
    chain — the pow-2 bucket pins batches to a handful of shapes (the
    ``bucket_nnz`` idiom applied to the server's apply path).

    ``grad_list`` entries are (U_i, vdim) (or (U_i,), normalized here);
    returns (unique_idx, (U, vdim) summed grads) as numpy host arrays.
    """
    if len(idx_list) == 1:
        uniq = np.asarray(idx_list[0])
        summed = np.asarray(grad_list[0]).reshape(len(uniq), -1)
        # a single push carries no duplicates (the localizer contract) —
        # pass through, padding only if asked
    else:
        idx = np.concatenate([np.asarray(i) for i in idx_list])
        g = np.concatenate(
            [
                np.asarray(x).reshape(len(i), -1)
                for i, x in zip(idx_list, grad_list)
            ]
        )
        uniq, inv = np.unique(idx, return_inverse=True)
        summed = np.zeros((len(uniq), g.shape[1]), dtype=g.dtype)
        np.add.at(summed, inv, g)
    if pad_to_pow2:
        u = len(uniq)
        cap = 1 << max(u - 1, 0).bit_length()
        if cap > u:
            uniq = np.concatenate([uniq, np.zeros(cap - u, uniq.dtype)])
            summed = np.concatenate(
                [summed, np.zeros((cap - u, summed.shape[1]), summed.dtype)]
            )
    return uniq, summed


def push_multi(
    updater: Updater,
    state: State,
    idx_list: list[np.ndarray],
    grad_list: list[np.ndarray],
    pad_to_pow2: bool = False,
) -> State:
    """Batched multi-push: coalesce N pushes (segment-summing duplicate
    keys across them) and apply the updater ONCE over the union of
    touched rows — one dispatch instead of N. Semantics are the paper's
    server-side aggregation: deltas are computed from the pre-batch rows
    and the summed gradient.

    This is the single-program (KVStore) batched entry point. The wire
    tier's ``ShardServer`` apply engine composes the SAME two primitives
    (``coalesce_pushes`` + ``push``) directly, because its durable push
    ledger and RCU publish must share one critical section with the
    apply — semantics changes to batching belong in those primitives,
    where both paths pick them up."""
    idx, grad = coalesce_pushes(idx_list, grad_list, pad_to_pow2)
    return push(updater, state, jnp.asarray(idx), jnp.asarray(grad))


class KVStore:
    """Stateful convenience wrapper an app holds (one sharded "server group").

    The reference app holds a KVVector bound to a SharedParameter customer id;
    here the app holds a KVStore bound to an updater + state pytree.
    """

    def __init__(
        self,
        updater: Updater,
        num_keys: int,
        vdim: int = 1,
        dtype: Any = jnp.float32,
    ):
        self.updater = updater
        self.num_keys = int(num_keys)
        self.vdim = int(vdim)
        self.state: State = updater.init(self.num_keys, self.vdim, dtype)

    def pull(self, idx: jax.Array) -> jax.Array:
        return pull(self.updater, self.state, idx)

    def push(self, idx: jax.Array, grad: jax.Array) -> None:
        self.state = push(self.updater, self.state, idx, grad)

    def push_multi(
        self, idx_list: list[np.ndarray], grad_list: list[np.ndarray]
    ) -> None:
        """Apply N pushes as one coalesced, segment-summed update (the
        batched server apply; see module-level ``push_multi``)."""
        self.state = push_multi(self.updater, self.state, idx_list, grad_list)

    def weights(self) -> jax.Array:
        return materialize_weights(self.updater, self.state)

    def nnz(self, tol: float = 0.0) -> int:
        """Count of nonzero weights excluding the pad row (ref: nnz(w) in
        the scheduler's progress table)."""
        w = np.asarray(self.weights())[1:]
        return int((np.abs(w) > tol).sum())
