"""ReLU multilayer perceptrons, for the apps whose dense group is one or
more of them (Wide&Deep's tower; DLRM's bottom and top MLPs): the
parameters as a list of ``{"W", "b"}`` layers made on the host from a
seeded generator, and the forward pass. And the low-rank cross network
that DLRM-DCNv2 puts between the two (``init_cross``, ``cross_apply``).

Every product runs at float32 (``Precision.HIGHEST``): the TPU's default
would round the operands to bfloat16, and the apps state float32
throughout."""

from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Layer = dict[str, Any]  # {"W": (fan_in, fan_out), "b": (fan_out,)}


def he_normal(rng: np.random.Generator, fan_in: int, fan_out: int):
    """(W, b): He-normal weights (variance 2 / fan_in), zero biases."""
    return rng.normal(scale=np.sqrt(2.0 / fan_in), size=(fan_in, fan_out)), np.zeros(fan_out)


def xavier_normal(rng: np.random.Generator, fan_in: int, fan_out: int):
    """(W, b): weights normal with variance 2 / (fan_in + fan_out), biases
    normal with variance 1 / fan_out (the DLRM reference implementation's)."""
    return (
        rng.normal(scale=np.sqrt(2.0 / (fan_in + fan_out)), size=(fan_in, fan_out)),
        rng.normal(scale=np.sqrt(1.0 / fan_out), size=fan_out),
    )


def init_mlp(
    sizes: list[int], rng: "np.random.Generator | int" = 0, init: Callable = he_normal
) -> list[Layer]:
    """The layers ``sizes[0] -> sizes[1] -> ... -> sizes[-1]`` in float32,
    drawn layer by layer from ``rng`` (a seed, or a generator that several
    MLPs of one app draw from in turn) by ``init(rng, fan_in, fan_out)``."""
    rng = np.random.default_rng(rng)
    params = []
    for fan_in, fan_out in zip(sizes, sizes[1:]):
        w, b = init(rng, fan_in, fan_out)
        params.append(
            {"W": jnp.asarray(w, dtype=jnp.float32), "b": jnp.asarray(b, dtype=jnp.float32)}
        )
    return params


def mlp_apply(params: list[Layer], x: jax.Array, last: Callable | None = None) -> jax.Array:
    """(B, sizes[0]) -> (B, sizes[-1]): ReLU after every layer but the
    last, whose activation is ``last`` (none unsaid: a logit)."""
    hi = jax.lax.Precision.HIGHEST
    for layer in params[:-1]:
        x = jax.nn.relu(jnp.dot(x, layer["W"], precision=hi) + layer["b"])
    out = jnp.dot(x, params[-1]["W"], precision=hi) + params[-1]["b"]
    return out if last is None else last(out)


def init_cross(width: int, rank: int, layers: int, rng: "np.random.Generator | int") -> list[Layer]:
    """``layers`` low-rank cross layers ``{"V": (width, rank), "W": (rank,
    width), "b": (width,)}`` in float32, drawn layer by layer from ``rng``:
    V normal with variance 2 / (width + rank), then W and b as
    ``xavier_normal`` draws a layer's."""
    rng = np.random.default_rng(rng)
    params = []
    for _ in range(layers):
        v = rng.normal(scale=np.sqrt(2.0 / (width + rank)), size=(width, rank))
        w, b = xavier_normal(rng, rank, width)
        params.append({k: jnp.asarray(a, dtype=jnp.float32) for k, a in (("V", v), ("W", w), ("b", b))})
    return params


def cross_apply(params: list[Layer], x0: jax.Array) -> jax.Array:
    """(B, width) -> (B, width): the cross network of DCN V2 in its
    low-rank form (Wang et al., arXiv:2008.13535, sections 3-4),
    ``x_{l+1} = x_0 * ((x_l V_l) W_l + b_l) + x_l``, ``*`` elementwise."""
    hi = jax.lax.Precision.HIGHEST
    x = x0
    for layer in params:
        low = jnp.dot(x, layer["V"], precision=hi)
        x = x0 * (jnp.dot(low, layer["W"], precision=hi) + layer["b"]) + x
    return x
