"""Fixed-point quantization codec with stochastic (unbiased) rounding.

Reference analog: src/filter/fixing_float.h — quantize floats into n-byte
fixed point with randomized rounding and per-array min/max scaling, applied
symmetrically on send/receive. Here encode/decode are jit-able functions
meant to wrap **DCN** (cross-slice) gradient collectives: encode before the
wire, decode after, e.g.

    enc = codec.encode(key, grads)            # int8/int16 + scale
    agg = lax.psum(enc.q.astype(f32), 'dcn')  # cheap wire format
    grads = codec.decode_sum(enc.scale, agg)

Stochastic rounding keeps E[decode(encode(x))] == x, which is what makes
low-bit gradient pushes safe for FTRL/AdaGrad (the reference's motivation
for randomized rounding)."""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Encoded(NamedTuple):
    q: jax.Array  # integer payload
    lo: jax.Array  # per-array min (scalar)
    scale: jax.Array  # (hi - lo) / levels (scalar)


@dataclass(frozen=True)
class FixedPointCodec:
    """num_bytes in {1, 2}: int8 or int16 payloads (ref: FilterConfig
    num_bytes)."""

    num_bytes: int = 1

    @property
    def _levels(self) -> int:
        return (1 << (8 * self.num_bytes)) - 1

    @property
    def _dtype(self):
        return jnp.int8 if self.num_bytes == 1 else jnp.int16

    def __post_init__(self) -> None:
        if self.num_bytes not in (1, 2):
            raise ValueError("num_bytes must be 1 or 2")

    def encode(self, key: jax.Array, x: jax.Array) -> Encoded:
        """Quantize to [lo, hi] with stochastic rounding. ``key`` is a JAX
        PRNG key (the randomness source for unbiased rounding)."""
        lo = jnp.min(x)
        hi = jnp.max(x)
        scale = jnp.maximum(hi - lo, 1e-30) / self._levels
        t = (x - lo) / scale  # in [0, levels]
        floor = jnp.floor(t)
        frac = t - floor
        up = jax.random.uniform(key, x.shape) < frac
        q = floor + up.astype(t.dtype)
        zero = self._levels // 2
        return Encoded(
            (q - zero).astype(self._dtype),
            lo.astype(jnp.float32),
            scale.astype(jnp.float32),
        )

    def decode(self, e: Encoded) -> jax.Array:
        zero = self._levels // 2
        return (e.q.astype(jnp.float32) + zero) * e.scale + e.lo

    def bytes_saved(self, x: jax.Array) -> float:
        """Wire-size ratio vs float32 (ref: the Postoffice per-filter byte
        counters reporting compression savings)."""
        return 1.0 - self.num_bytes / 4.0
