"""Matrix factorization over the KV store.

Reference analog: the reference's matrix-factorization app (rank-r factors
on a bipartite rating graph; workers hold rating blocks and Push/Pull the
row/column factor vectors they touch — named in BASELINE.json's north star
alongside linear_method; parity config 3: SGD at a configured rank, 64
there and 100 in NOMAD's Hugewiki runs, async push/pull).

TPU re-expression: ONE table of ``vdim = rank`` over one key space, as the
reference's KV layer has one: items take keys 1..num_items, users the keys
behind them, row 0 is the pad. A rating is an example of two entries (its
item, its user; the ``rating`` format of ``data.libsvm``), so a rating
minibatch is localized, bucketed and fed exactly like a sparse-LR batch,
and trained by the step and the loop every app shares.

This module holds the model and its description (``mf_app``); the step is
``parallel.spmd``'s and the training loop ``PodTrainer``'s."""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.data.libsvm import RATING
from parameter_server_tpu.kv.store import hashed_unit, live_lanes
from parameter_server_tpu.kv.updaters import Adagrad, Sgd, Updater
from parameter_server_tpu.models.metrics import REGRESSION_SCORES
from parameter_server_tpu.parallel.spmd import StepApp, Table

TABLE = "mf"  # the table's name: state entry "mf.w", scopes "ps.pull/mf"


def num_keys_of(num_users: int, num_items: int) -> int:
    """Rows of the one key space: the pad row, the items, the users."""
    return 1 + num_items + num_users


def _pair(pulled, b):
    """(item slot, user slot, item rows, user rows) of every example: the
    local ids of its two entries, in the order the ``rating`` format writes
    them, each (B,), and the pulled (B, rank) rows they name. A padded
    example's pair is whatever lies at its split (the entries' pad, or the
    last entry of a full buffer): its error is masked to zero."""
    w = pulled[TABLE]
    first = b["row_splits"][:-1]
    item = jnp.take(b["local_ids"], first, mode="clip")
    user = jnp.take(b["local_ids"], first + 1, mode="clip")
    return item, user, jnp.take(w, item, axis=0), jnp.take(w, user, axis=0)


def _logits(pulled, dense, b, row_ids) -> jax.Array:
    """The pair's inner product -> (B,): the predicted rating."""
    _, _, v, u = _pair(pulled, b)
    return jnp.sum(u * v, axis=1)


def _grad(l2: float):
    def grad(pulled, dense, b, row_ids):
        """Summed squared error of the batch's ratings and the gradient of
        half of it on the pulled rows, summed over a key's repeats in the
        batch (a hot item's thousands of ratings are one row of the push),
        plus L2 on the rows the batch touches (the pad slot pulls zeros)."""
        w = pulled[TABLE]  # (U, rank)
        item, user, v, u = _pair(pulled, b)
        pred = jnp.sum(u * v, axis=1)
        err = (pred - b["labels"]) * b["example_mask"].astype(pred.dtype)
        loss = jnp.sum(err * err)
        g = jax.ops.segment_sum(
            jnp.concatenate([err[:, None] * u, err[:, None] * v]),
            jnp.concatenate([item, user]),
            num_segments=w.shape[0],
        )
        return loss, pred, {TABLE: g + l2 * w}, None

    return grad


def init_factors(
    seed: int, rows: jax.Array, rank: int, live_rows: int, lanes: int | None = None
) -> jax.Array:
    """Starting factors of table rows ``rows``: uniform in [0, 1/sqrt(rank))
    as a hash of (seed, row, lane) (``kv.store.hashed_unit`` moved to [0, 2),
    which is exact, times half the width: one rounding), so that a product
    of two fresh rows is about 1/4 and has a gradient; the pad row and the
    rows at or past ``live_rows`` are zero. ``lanes`` (the slot's stride,
    ``rank`` unsaid) is the width made, zero past ``rank``
    (``kv.store.live_lanes``)."""
    keep = live_lanes((rows > 0) & (rows < live_rows), rank, lanes)
    unit = hashed_unit(seed, rows, lanes or rank) + jnp.float32(1.0)
    return jnp.where(keep, unit * jnp.float32(0.5 / rank**0.5), 0.0)


def mf_app(updater: Updater, rank: int, l2: float, init=None) -> StepApp:
    """The app's description for the shared parameter-server step: table
    ``mf`` (``vdim`` ``rank``) under ``updater``, squared error over
    real-valued labels, the identity as link, RMSE as the evaluator's
    score. ``init(rows, lanes)`` makes the table's starting ``{"w": ...}``
    as the store keeps it, ``lanes`` wide (zeros without it: a product of
    zeros has no gradient)."""
    return StepApp(
        tables=(Table(TABLE, updater, rank, init),),
        grad=_grad(l2),
        logits=_logits,
        link=lambda x: x,
        score=REGRESSION_SCORES,
    )


def app_from_config(cfg) -> StepApp:
    """The description from a PSConfig's [mf] section (ref: App::Create on
    the MF config): rank, eta, l2 and the updater (``algo``: sgd | adagrad);
    the factors start as ``init_factors`` of ``cfg.seed``, made on the
    device. The files are ``rating`` lines and the key space is the pad
    row, the items and the users (``pod_config`` sets both)."""
    m = cfg.mf
    make = {"adagrad": Adagrad, "sgd": Sgd}
    if m.algo not in make:
        raise ValueError(f"mf algo must be one of {sorted(make)}")
    want = num_keys_of(m.num_users, m.num_items)
    if cfg.data.format != RATING or cfg.data.num_keys != want:
        raise ValueError(
            f"app matrix_fac reads data.format {RATING!r} into data.num_keys "
            f"= 1 + mf.num_items + mf.num_users = {want} rows; the config "
            f"says {cfg.data.format!r} and {cfg.data.num_keys} "
            "(models.matrix_fac.pod_config fills both in)"
        )
    return mf_app(
        make[m.algo](eta=m.eta), m.rank, m.l2,
        init=lambda rows, lanes: {"w": init_factors(
            cfg.seed, jnp.arange(rows, dtype=jnp.int32), m.rank, want, lanes
        )},
    )


def pod_config(cfg):
    """A copy of ``cfg`` with [mf]'s settings where the shared loop reads
    them: ``rating`` files, the key space's size, two entries an example,
    ``mf.batch_size`` ratings a minibatch."""
    cfg = copy.deepcopy(cfg)
    cfg.app = "matrix_fac"
    cfg.data.format = RATING
    cfg.data.num_keys = num_keys_of(cfg.mf.num_users, cfg.mf.num_items)
    cfg.data.max_nnz_per_example = 2
    cfg.solver.minibatch = cfg.mf.batch_size
    return cfg


def write_ratings(path, users, items, ratings) -> None:
    """``user item rating`` lines, one a triple (ids from 0)."""
    with open(path, "w") as f:
        f.writelines(
            f"{u} {i} {float(r):.9g}\n" for u, i, r in zip(users, items, ratings)
        )


def factors(trainer) -> tuple[np.ndarray, np.ndarray]:
    """(user factors (num_users, rank), item factors (num_items, rank)) of
    a trainer's table, by id."""
    w = trainer.full_weights(TABLE)
    n_items = trainer.cfg.mf.num_items
    return w[1 + n_items :], w[1 : 1 + n_items]


def predict(trainer, users, items) -> np.ndarray:
    """Predicted ratings of (user, item) id pairs over the trained table."""
    user_f, item_f = factors(trainer)
    return np.sum(user_f[np.asarray(users)] * item_f[np.asarray(items)], axis=1)


def rmse(trainer, users, items, ratings) -> float:
    p = predict(trainer, users, items)
    return float(np.sqrt(np.mean((p - np.asarray(ratings)) ** 2)))
