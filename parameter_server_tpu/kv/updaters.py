"""Server-side updaters: SGD, AdaGrad, FTRL-proximal.

Reference analog: the Entry types applied by the server KV store on push —
SGD/AdaGrad/FTRL entries in src/app/linear_method/async_sgd.h (server side)
and the proximal operator in src/app/linear_method/penalty.h.

Each updater is a frozen dataclass of hyperparameters with three pure
methods over *row slices* (the touched keys' state), so the same code runs:
  - single-device (rows gathered by ``jnp.take``),
  - SPMD (rows gathered from the local ``kv`` shard under ``shard_map``).

State layout per table (vdim = values per key, reference's "value segments"):
  sgd:     {"w": (K, vdim)}
  adagrad: {"w": (K, vdim), "n": (K, vdim)}
  ftrl:    {"z": (K, vdim), "n": (K, vdim)}   -- w is DERIVED lazily
FTRL stores no w: the weight is materialized from (z, n) on pull, which is
exactly the reference's lazy L1 sparsification (untouched keys stay exactly
zero without ever being written).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Protocol

import jax.numpy as jnp

Rows = dict[str, Any]  # name -> (U, vdim) array slice of touched keys


class Updater(Protocol):
    """All updaters express their step as an exact additive ``delta`` so the
    sharded push can be a deterministic scatter-ADD (duplicate/out-of-range
    slots contribute zero) rather than a row write. ``apply`` == rows + delta.
    """

    name: str

    def init(self, num_keys: int, vdim: int, dtype: Any) -> Rows: ...

    def delta(self, rows: Rows, grad: Any) -> Rows: ...

    def weights(self, rows: Rows) -> Any: ...


def apply_update(updater: "Updater", rows: Rows, grad: Any) -> Rows:
    d = updater.delta(rows, grad)
    return {k: rows[k] + d[k] for k in rows}


@dataclass(frozen=True)
class Sgd:
    """Plain SGD with optional L2: w -= eta * (g + l2 * w)."""

    eta: float = 0.1
    lambda_l2: float = 0.0
    name: str = "sgd"

    def init(self, num_keys: int, vdim: int = 1, dtype: Any = jnp.float32) -> Rows:
        return {"w": jnp.zeros((num_keys, vdim), dtype)}

    def delta(self, rows: Rows, grad: Any) -> Rows:
        return {"w": -self.eta * (grad + self.lambda_l2 * rows["w"])}

    def weights(self, rows: Rows) -> Any:
        return rows["w"]


@dataclass(frozen=True)
class Adagrad:
    """AdaGrad: n += g^2; w -= eta * g / (sqrt(n) + eps)."""

    eta: float = 0.1
    eps: float = 1e-8
    lambda_l2: float = 0.0
    name: str = "adagrad"

    def init(self, num_keys: int, vdim: int = 1, dtype: Any = jnp.float32) -> Rows:
        # distinct buffers: donation requires state leaves not to alias
        return {
            "w": jnp.zeros((num_keys, vdim), dtype),
            "n": jnp.zeros((num_keys, vdim), dtype),
        }

    def delta(self, rows: Rows, grad: Any) -> Rows:
        g = grad + self.lambda_l2 * rows["w"]
        dn = g * g
        n = rows["n"] + dn
        return {"w": -self.eta * g / (jnp.sqrt(n) + self.eps), "n": dn}

    def weights(self, rows: Rows) -> Any:
        return rows["w"]


@dataclass(frozen=True)
class Ftrl:
    """FTRL-proximal (McMahan et al.), the reference's flagship updater.

    Per touched key (ref: FTRLEntry in async_sgd.h server side):
        w      = prox(z, n)                      # current weight, derived
        sigma  = (sqrt(n + g^2) - sqrt(n)) / alpha
        z     += g - sigma * w
        n     += g^2
    and the lazy weight:
        w(z,n) = 0                                   if |z| <= lambda_l1
               = -(z - sign(z)*lambda_l1)
                 / ((beta + sqrt(n))/alpha + lambda_l2)   otherwise
    """

    alpha: float = 0.1
    beta: float = 1.0
    lambda_l1: float = 1.0
    lambda_l2: float = 0.0
    name: str = "ftrl"

    def init(self, num_keys: int, vdim: int = 1, dtype: Any = jnp.float32) -> Rows:
        return {
            "z": jnp.zeros((num_keys, vdim), dtype),
            "n": jnp.zeros((num_keys, vdim), dtype),
        }

    def delta(self, rows: Rows, grad: Any) -> Rows:
        n = rows["n"]
        w = self.weights(rows)
        n_new = n + grad * grad
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / self.alpha
        return {"z": grad - sigma * w, "n": grad * grad}

    def weights(self, rows: Rows) -> Any:
        z, n = rows["z"], rows["n"]
        shrunk = jnp.sign(z) * jnp.maximum(jnp.abs(z) - self.lambda_l1, 0.0)
        denom = (self.beta + jnp.sqrt(n)) / self.alpha + self.lambda_l2
        return -shrunk / denom


def make_updater(algo: str, **kw: Any) -> Updater:
    """Factory by config name (ref: solver/penalty fields of the app proto)."""
    table = {"sgd": Sgd, "adagrad": Adagrad, "ftrl": Ftrl}
    if algo not in table:
        raise ValueError(f"unknown updater '{algo}'; known: {sorted(table)}")
    cls = table[algo]
    valid = {f.name for f in dataclasses.fields(cls)} - {"name"}
    bad = set(kw) - valid
    if bad:
        raise ValueError(f"unknown {algo} hyperparameter(s) {sorted(bad)}")
    return cls(**kw)
