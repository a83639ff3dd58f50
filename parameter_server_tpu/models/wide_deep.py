"""Wide & Deep CTR model with a server-sharded embedding table.

Reference analog: BASELINE.json parity config "Wide-&-Deep CTR with
100M-row embedding table (server-sharded embeddings)". The wide half IS the
reference's sparse linear model (FTRL over the hashed key space); the deep
half is an embedding table living in the same KV store (vdim = embedding
dim) feeding a small MLP.

Design note vs the reference: the reference hand-writes worker gradients;
here the whole forward is one differentiable function and ``jax.grad``
produces the pulled-row gradients, which are then pushed through the same
server updaters (FTRL for wide, AdaGrad for embeddings, Adam for the dense
MLP). Pull/push stay the only interface to model state.

This module holds the model and its description (``wide_deep_app``); the
step is ``parallel.spmd``'s and the training loop ``PodTrainer``'s, the
ones the linear app runs through."""

from __future__ import annotations

import copy
from collections.abc import Iterable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.kv.store import State, hashed_uniform
from parameter_server_tpu.kv.updaters import Adagrad, Ftrl, Updater
from parameter_server_tpu.models import mlp
from parameter_server_tpu.models.metrics import BINARY_SCORES
from parameter_server_tpu.ops.sparse import csr_logits, sum_by_example, take_by_slot
from parameter_server_tpu.parallel.spmd import (
    DenseGroup,
    StepApp,
    Table,
    _sub_scope,
    _values_of,
)
from parameter_server_tpu.utils.metrics import ProgressReporter


def init_mlp(dim: int, hidden: list[int], seed: int = 0) -> list[dict[str, Any]]:
    """The tower ``dim -> hidden... -> 1``, He-normal from ``seed``."""
    return mlp.init_mlp([dim, *hidden, 1], seed)


def _mlp_apply(params, x):
    """The tower: ReLU layers, one logit out (``models.mlp``)."""
    return mlp.mlp_apply(params, x)[:, 0]


def _logits(pulled, mlp_params, b, row_ids):
    """Wide logits over the pulled ``wide`` rows + the tower over the
    examples' mean-pooled ``emb`` rows -> (B,)."""
    values, row_splits = _values_of(b), b["row_splits"]
    wide = csr_logits(pulled["wide"], values, b["local_ids"], row_ids, row_splits)
    # mean-pool the batch's unique-key embeddings per example
    ent_emb = take_by_slot(pulled["emb"], b["local_ids"], row_splits)  # (NNZ, d)
    ones = (values != 0).astype(jnp.float32)
    num = sum_by_example(ent_emb * ones[:, None], row_ids, row_splits)
    cnt = sum_by_example(ones, row_ids, row_splits)
    pooled = num / jnp.maximum(cnt, 1.0)[:, None]
    with _sub_scope("mlp"):
        deep = _mlp_apply(mlp_params, pooled)
    return wide + deep


def _loss(pulled, mlp_params, b, row_ids):
    logits = _logits(pulled, mlp_params, b, row_ids)
    m = b["example_mask"].astype(jnp.float32)
    loss = jnp.sum(m * (jax.nn.softplus(logits) - b["labels"] * logits))
    return loss, logits


def _grad(pulled, mlp_params, b, row_ids):
    """The whole forward is one differentiable function; ``jax.grad`` gives
    the pulled rows' gradients (pushed through the tables' updaters) and
    the tower's (the dense group's optimizer)."""
    (loss, logits), (g_pulled, g_mlp) = jax.value_and_grad(
        _loss, argnums=(0, 1), has_aux=True
    )(pulled, mlp_params, b, row_ids)
    return loss, logits, g_pulled, g_mlp


EMB_INIT_SCALE = 0.05


def wide_deep_app(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    emb_dim: int,
    mlp_init,
    emb_init=None,
) -> StepApp:
    """The app's description for the shared parameter-server step
    (``parallel.spmd``): table ``wide`` (``vdim`` 1), table ``emb``
    (``vdim`` ``emb_dim``), both over the batch's one hashed key space, and
    the tower ``mlp`` as the replicated dense group under ``opt``.
    ``mlp_init()`` makes the tower's parameters; ``emb_init(rows,
    lanes)`` the embedding's starting ``{"w": ...}`` as the store keeps it,
    ``lanes`` wide (zeros without it)."""
    return StepApp(
        tables=(
            Table("wide", wide_up, 1),
            Table("emb", emb_up, emb_dim, emb_init),
        ),
        grad=_grad,
        logits=_logits,
        dense=DenseGroup("mlp", mlp_init, opt),
        link=jax.nn.sigmoid,
        score=BINARY_SCORES,
    )


def app_from_config(cfg) -> StepApp:
    """The description from a PSConfig (ref: App::Create on the W&D
    config): wide half from the [lr]/[penalty] FTRL fields, deep half from
    [wd]; the embedding starts as ``kv.store.hashed_uniform`` of
    ``cfg.seed``, made on the device."""
    num_keys, dim, seed = cfg.data.num_keys, cfg.wd.emb_dim, cfg.seed
    return wide_deep_app(
        Ftrl(
            alpha=cfg.lr.alpha, beta=cfg.lr.beta,
            lambda_l1=cfg.penalty.lambda_l1, lambda_l2=cfg.penalty.lambda_l2,
        ),
        Adagrad(eta=cfg.wd.emb_eta),
        optax.adam(cfg.wd.mlp_lr),
        dim,
        mlp_init=lambda: init_mlp(dim, list(cfg.wd.hidden), seed=seed),
        emb_init=lambda rows, lanes: {"w": hashed_uniform(
            seed, jnp.arange(rows, dtype=jnp.int32), dim, EMB_INIT_SCALE, num_keys, lanes
        )},
    )


def _wd_stepper(wide_up, emb_up, opt, mesh, num_keys, push_mode, multistep):
    """The shared step (``parallel.spmd``) over W&D's description, behind
    the six-argument call the mesh tests drive: the four pieces of state
    are packed into the step's flat state and unpacked from it."""
    from parameter_server_tpu.parallel.spmd import (
        PUSH_MODES,
        make_spmd_train_multistep,
        make_spmd_train_step,
    )

    if push_mode not in PUSH_MODES:
        raise ValueError(
            f"unknown push_mode {push_mode!r}; known: {PUSH_MODES}"
        )
    built: list = []
    whole = NamedSharding(mesh, PartitionSpec())

    def stepper(wide_state, emb_state, mlp_params, opt_state, batch,
                push_seed=None):
        if not built:  # the tower's shapes arrive with the first call
            like = jax.eval_shape(lambda: mlp_params)
            app = wide_deep_app(
                wide_up, emb_up, opt, emb_state["w"].shape[1],
                mlp_init=lambda: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, x.dtype), like
                ),
            )
            maker = make_spmd_train_multistep if multistep else make_spmd_train_step
            built.extend([app, maker(app, mesh, num_keys, push_mode)])
        app, step = built
        wide, emb = app.table("wide"), app.table("emb")
        state = {
            **{wide.key(k): v for k, v in wide_state.items()},
            **{emb.key(k): v for k, v in emb_state.items()},
            # the tower may come from another mesh (an app's own trainer)
            **jax.device_put(app.dense.pack(mlp_params, opt_state), whole),
        }
        state, out = step(state, batch, push_seed)
        return (
            wide.of(state), emb.of(state), *app.dense.unpack(state),
            out["loss_sum"], out["probs"],
        )

    return stepper


def make_wd_spmd_train_step(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    mesh,
    num_keys: int,
    push_mode: str = "per_worker",
):
    """Multi-device Wide&Deep step: both KV tables range-sharded over the
    ``kv`` mesh axis (BASELINE.json: "server-sharded embeddings"), batches
    over ``data``; MLP params replicated with psum'd gradients. The step
    is ``parallel.spmd``'s, with every push mode it has, on both tables
    (quantized mode requires a per-call push_seed).
    step(wide, emb, mlp, opt_state, batch[, seed]) ->
    (wide, emb, mlp, opt_state, loss, probs)."""
    return _wd_stepper(
        wide_up, emb_up, opt, mesh, num_keys, push_mode, multistep=False
    )


def make_wd_spmd_train_multistep(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    mesh,
    num_keys: int,
    push_mode: str = "per_worker",
):
    """K sequential Wide&Deep steps per device call over the (data, kv)
    mesh: batch fields stacked (D, K_steps, ...). Returns per-microstep
    losses (K,) and probs (D, K, B)."""
    return _wd_stepper(
        wide_up, emb_up, opt, mesh, num_keys, push_mode, multistep=True
    )


class WideDeep:
    """The Wide&Deep app: shared hashed key space for wide + embedding.
    A ``PodTrainer`` over ``app_from_config``'s description with the
    pieces of its state under the names the app has always had."""

    def __init__(
        self,
        num_keys: int,
        emb_dim: int = 16,
        hidden: list[int] | None = None,
        ftrl_kw: dict | None = None,
        emb_eta: float = 0.1,
        mlp_lr: float = 1e-3,
        seed: int = 0,
        reporter: ProgressReporter | None = None,
        steps_per_call: int = 1,
        mesh=None,
        push_mode: str = "per_worker",
        max_delay: int = 0,
    ):
        from parameter_server_tpu.utils.config import PSConfig

        cfg = PSConfig()
        cfg.seed = seed
        cfg.data.num_keys = num_keys
        cfg.wd.emb_dim, cfg.wd.hidden = emb_dim, list(hidden or [32, 16])
        cfg.wd.emb_eta, cfg.wd.mlp_lr = emb_eta, mlp_lr
        ftrl = {"alpha": 0.1, "lambda_l1": 0.5, **(ftrl_kw or {})}
        cfg.lr.alpha = ftrl["alpha"]
        cfg.lr.beta = ftrl.get("beta", cfg.lr.beta)
        cfg.penalty.lambda_l1 = ftrl["lambda_l1"]
        cfg.penalty.lambda_l2 = ftrl.get("lambda_l2", 0.0)
        cfg.solver.steps_per_call = steps_per_call
        cfg.solver.max_delay = max_delay
        cfg.parallel.push_mode = push_mode
        self._build(cfg, mesh, reporter)

    @classmethod
    def from_config(cls, cfg, mesh=None, reporter=None) -> "WideDeep":
        """Build the app from a PSConfig (ref: App::Create on the W&D
        config): see ``app_from_config``; dispatch shape from
        [solver]/[parallel], the mesh's shape over [parallel]'s."""
        self = cls.__new__(cls)
        self._build(copy.deepcopy(cfg), mesh, reporter)
        return self

    def _build(self, cfg, mesh, reporter) -> None:
        from parameter_server_tpu.parallel.trainer import PodTrainer

        cfg.app = "wide_deep"
        if mesh is not None:
            cfg.parallel.data_shards = mesh.shape["data"]
            cfg.parallel.kv_shards = mesh.shape["kv"]
        self.trainer = tr = PodTrainer(cfg, mesh=mesh, reporter=reporter)
        self.mesh = mesh
        self.num_keys = cfg.data.num_keys
        self.emb_dim, self.hidden = cfg.wd.emb_dim, list(cfg.wd.hidden)
        self.steps_per_call = cfg.solver.steps_per_call
        self.push_mode = cfg.parallel.push_mode
        self.max_delay = tr.clock.max_delay
        self.wide_up = tr.app.table("wide").updater
        self.emb_up = tr.app.table("emb").updater
        self.opt = tr.app.dense.opt
        self.reporter = tr.reporter

    # -- the state's pieces, out of and into the trainer's flat state ------
    @property
    def examples_seen(self) -> int:
        return self.trainer.examples_seen

    @property
    def wide_state(self) -> State:
        return self.trainer.table_state("wide")

    @wide_state.setter
    def wide_state(self, slots: State) -> None:
        self.trainer.set_table("wide", slots)

    @property
    def emb_state(self) -> State:
        return self.trainer.table_state("emb")

    @emb_state.setter
    def emb_state(self, slots: State) -> None:
        self.trainer.set_table("emb", slots)

    @property
    def mlp_params(self):
        return self.trainer.dense()[0]

    @mlp_params.setter
    def mlp_params(self, params) -> None:
        self.trainer.set_dense(params, self.opt_state)

    @property
    def opt_state(self):
        return self.trainer.dense()[1]

    @opt_state.setter
    def opt_state(self, opt_state) -> None:
        self.trainer.set_dense(self.mlp_params, opt_state)

    # -- training and scoring: the trainer's ----------------------------------
    def train(self, batches: Iterable[CSRBatch], report_every: int = 100) -> dict:
        """One pass over a CSRBatch stream through ``PodTrainer``: with a
        mesh each microstep consumes D batches (one per data shard, in
        stream order), ``steps_per_call`` microsteps a device call,
        ``max_delay`` + 1 calls in flight. report_every counts device
        calls."""
        return self.trainer.train_batches(batches, report_every=report_every)

    def predict(self, batches: Iterable[CSRBatch]) -> tuple[np.ndarray, np.ndarray]:
        return self.trainer.predict_batches(batches)

    def evaluate(self, batches: Iterable[CSRBatch]) -> dict:
        return self.trainer.evaluate_batches(batches)

    def dump_model(self, path: str) -> str:
        return dump_model(self.trainer, path)


def dump_model(trainer, path: str) -> str:
    """Dump inference weights (npz): derived wide weights, embedding
    table, MLP layers (ref: the text model dump each server range
    writes; one npz here since the deep half isn't a flat vector)."""
    host = {
        "wide_w": trainer.full_weights("wide"),
        "emb_w": trainer.full_weights("emb"),
    }
    for i, layer in enumerate(trainer.dense()[0]):
        host[f"mlp_W{i}"] = np.asarray(layer["W"])
        host[f"mlp_b{i}"] = np.asarray(layer["b"])
    np.savez(path, **host)
    return path


def evaluate_dump(cfg, model_path: str, files: list[str]) -> dict:
    """Evaluate a ``dump_model`` npz over files (the CLI ``evaluate`` path
    for app wide_deep; ref: the offline model evaluator reading each
    server range's dump): the dump's weights become the tables of a
    ``PodTrainer`` (weight-only tables, ``Sgd``'s one slot) and its
    ``evaluate_files`` scores them."""
    from parameter_server_tpu.kv.updaters import Sgd
    from parameter_server_tpu.parallel.trainer import PodTrainer

    d = np.load(model_path)
    mlp = []
    while f"mlp_W{len(mlp)}" in d:
        i = len(mlp)
        mlp.append(
            {"W": jnp.asarray(d[f"mlp_W{i}"]), "b": jnp.asarray(d[f"mlp_b{i}"])}
        )
    app = wide_deep_app(
        Sgd(), Sgd(), optax.identity(), d["emb_w"].shape[1], mlp_init=lambda: mlp
    )
    trainer = PodTrainer(cfg, app=app)
    trainer.set_table("wide", {"w": d["wide_w"]})
    trainer.set_table("emb", {"w": d["emb_w"]})
    return trainer.evaluate_files(files)
