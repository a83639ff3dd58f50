"""Server-side updaters: SGD, AdaGrad, FTRL-proximal, and the batch
solver's block proximal-Newton step.

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
  prox_newton: {"w": (K, 1), "active": (K, 1)}  -- the batch solver's
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


@dataclass(frozen=True)
class ProxNewton:
    """The server's half of one DARLIN block step (Li et al., OSDI 2014,
    Algorithm 3; ref: the proximal update of src/app/linear_method/darlin.h
    server side and its KKT filter). Not an online updater: the worker
    pushes a block's gradient AND curvature ``(g, h)`` for a contiguous key
    range, the server answers with a direction, and the step's scale comes
    back from the worker's line search before the weights move - so the
    step is the pair ``direction`` / ``apply`` over the range's rows, not
    one ``delta``.

    Per coordinate j of the block, with h' = h + lambda_l2 + 1e-6:
        z = w h' - eta g
        d = sign(z) max(|z| - eta lambda_l1, 0) / h' - w   (0 where skipped)
        skipped: outside the active set and w == 0
        violation = |g + sign(w) lambda_l1|       where w != 0
                  = max(|g| - lambda_l1, 0)       where w == 0
    ``active`` is kept as 1.0 / 0.0 in the table's dtype, so the solver's
    table is two float32 slots like every other updater's."""

    eta: float = 1.0
    lambda_l1: float = 1.0
    lambda_l2: float = 0.0
    name: str = "prox_newton"

    def init(self, num_keys: int, vdim: int = 1, dtype: Any = jnp.float32) -> Rows:
        return {
            "w": jnp.zeros((num_keys, vdim), dtype),
            "active": jnp.ones((num_keys, vdim), dtype),
        }

    def weights(self, rows: Rows) -> Any:
        return rows["w"]

    def delta(self, rows: Rows, grad: Any) -> Rows:
        raise NotImplementedError(
            "prox_newton steps a key range from (g, h) through direction / "
            "apply (models.darlin); it has no minibatch delta"
        )

    def violation(self, rows: Rows, g: Any) -> Any:
        """KKT violation per coordinate: the filter's score."""
        w = rows["w"]
        return jnp.where(
            w != 0.0,
            jnp.abs(g + jnp.sign(w) * self.lambda_l1),
            jnp.maximum(jnp.abs(g) - self.lambda_l1, 0.0),
        )

    def direction(self, rows: Rows, g: Any, h: Any) -> Any:
        """Proximal Newton direction per coordinate (diagonal model)."""
        w = rows["w"]
        h_safe = h + self.lambda_l2 + 1e-6
        z = w * h_safe - self.eta * g
        w_cand = (
            jnp.sign(z)
            * jnp.maximum(jnp.abs(z) - self.eta * self.lambda_l1, 0.0)
            / h_safe
        )
        skip = (rows["active"] == 0.0) & (w == 0.0)
        return jnp.where(skip, 0.0, w_cand - w)

    def apply(self, rows: Rows, d: Any, alpha: Any) -> Rows:
        """The rows after a step of scale ``alpha`` along ``d``."""
        return {"w": rows["w"] + alpha * d, "active": rows["active"]}

    def refresh(self, rows: Rows, g: Any, threshold: Any) -> Rows:
        """The rows with the active set taken anew: a coordinate stays in
        while it holds a weight or violates by more than ``threshold``."""
        keep = (rows["w"] != 0.0) | (self.violation(rows, g) > threshold)
        return {"w": rows["w"], "active": keep.astype(rows["active"].dtype)}

    def penalty(self, w: Any) -> Any:
        """lambda_l1 |w|_1 + lambda_l2 / 2 |w|^2 summed over ``w``'s last
        axis (a leading axis, where given, is the line search's scales)."""
        return self.lambda_l1 * jnp.abs(w).sum(axis=-1) + 0.5 * self.lambda_l2 * (
            w * w
        ).sum(axis=-1)


def dense_adagrad(eta: float, eps: float = 1e-8):
    """``Adagrad``'s rule (no L2) as an optax transformation, for an app's
    dense group: ``n += g^2; w -= eta g / (sqrt(n) + eps)``, ``n`` from 0
    and ``eps`` outside the root (``optax.adagrad`` puts it inside and
    starts ``n`` at 0.1: another rule). A zero gradient moves nothing."""
    import jax
    import optax

    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    def update(grads, n, params=None):
        del params
        n = jax.tree.map(lambda n_, g: n_ + g * g, n, grads)
        return jax.tree.map(lambda g, n_: -eta * g / (jnp.sqrt(n_) + eps), grads, n), n

    return optax.GradientTransformation(init, update)


def make_updater(algo: str, **kw: Any) -> Updater:
    """Factory by config name (ref: solver/penalty fields of the app proto)."""
    table = {"sgd": Sgd, "adagrad": Adagrad, "ftrl": Ftrl, "prox_newton": ProxNewton}
    if algo not in table:
        raise ValueError(f"unknown updater '{algo}'; known: {sorted(table)}")
    cls = table[algo]
    valid = {f.name for f in dataclasses.fields(cls)} - {"name"}
    bad = set(kw) - valid
    if bad:
        raise ValueError(f"unknown {algo} hyperparameter(s) {sorted(bad)}")
    return cls(**kw)
