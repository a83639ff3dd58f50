"""linear_method: sparse logistic regression over the KV store.

Reference analog: src/app/linear_method/async_sgd.h — the flagship app.
The worker loop (stream minibatch -> localize -> Pull weights -> CSR
gradient -> Push) and the server updater (FTRL/AdaGrad/SGD entries) fuse
into ONE jitted step per minibatch: pull (row gather), logit loss, grad
segment-sum, push (updater + row scatter). On a pod the same step runs
under shard_map with the state sharded over the ``kv`` axis
(parameter_server_tpu.parallel); here is the single-chip path.
"""

from __future__ import annotations

import functools
import time
from collections.abc import Iterable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.data.batch import BatchBuilder, CSRBatch
from parameter_server_tpu.data.reader import MinibatchReader
from parameter_server_tpu.kv.store import KVStore, State
from parameter_server_tpu.kv.updaters import Updater, make_updater
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter


def updater_from_config(cfg: PSConfig) -> Updater:
    algo = cfg.solver.algo
    if algo == "ftrl":
        return make_updater(
            "ftrl",
            alpha=cfg.lr.alpha,
            beta=cfg.lr.beta,
            lambda_l1=cfg.penalty.lambda_l1,
            lambda_l2=cfg.penalty.lambda_l2,
        )
    if algo == "adagrad":
        return make_updater("adagrad", eta=cfg.lr.eta, lambda_l2=cfg.penalty.lambda_l2)
    if algo == "sgd":
        return make_updater("sgd", eta=cfg.lr.eta, lambda_l2=cfg.penalty.lambda_l2)
    raise ValueError(f"linear_method solver '{algo}' is not a streaming updater")


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def train_step(
    updater: Updater, state: State, batch: dict[str, jax.Array]
) -> tuple[State, dict[str, jax.Array]]:
    """One fused pull -> grad -> push step. ``batch`` holds device arrays of
    a CSRBatch (``batch_to_device``)."""
    idx = batch["unique_keys"]
    rows = {k: jnp.take(v, idx, axis=0) for k, v in state.items()}
    w_u = updater.weights(rows)  # pull
    logits = csr_logits(
        w_u, batch["values"], batch["local_ids"], batch["row_ids"],
        batch["row_splits"],
    )
    loss, err = logistic_loss(logits, batch["labels"], batch["example_mask"])
    g = csr_grad(
        err, batch["values"], batch["local_ids"], batch["row_ids"],
        batch["row_splits"], num_unique=idx.shape[0],
    )
    deltas = updater.delta(rows, g)  # push: server-side updater ...
    new_state = {k: state[k].at[idx].add(deltas[k]) for k in state}  # ... scatter-add
    out = {
        "loss_sum": loss,
        "probs": jax.nn.sigmoid(logits),
        "logits": logits,
    }
    return new_state, out


@functools.partial(jax.jit, static_argnums=0)
def predict_step(
    updater: Updater, state: State, batch: dict[str, jax.Array]
) -> jax.Array:
    idx = batch["unique_keys"]
    rows = {k: jnp.take(v, idx, axis=0) for k, v in state.items()}
    w_u = updater.weights(rows)
    logits = csr_logits(
        w_u, batch["values"], batch["local_ids"], batch["row_ids"],
        batch["row_splits"],
    )
    return jax.nn.sigmoid(logits)


def batch_to_device(b: CSRBatch) -> dict[str, jax.Array]:
    return {
        "unique_keys": jnp.asarray(b.unique_keys),
        "local_ids": jnp.asarray(b.local_ids),
        "row_ids": jnp.asarray(b.row_ids),
        "row_splits": jnp.asarray(b.row_splits),
        "values": jnp.asarray(b.values),
        "labels": jnp.asarray(b.labels),
        "example_mask": jnp.asarray(b.example_mask),
    }


class LinearMethod:
    """The app object (reference analog: the linear_method App subclasses).

    Single-host driver: owns the KVStore, streams batches, reports progress
    the way the reference scheduler prints merged worker Progress."""

    def __init__(self, cfg: PSConfig, reporter: ProgressReporter | None = None):
        self.cfg = cfg
        self.updater = updater_from_config(cfg)
        self.store = KVStore(self.updater, cfg.data.num_keys)
        self.reporter = reporter or ProgressReporter()
        self.examples_seen = 0

    def make_builder(self, key_mode: str = "hash") -> BatchBuilder:
        from parameter_server_tpu.data.batch import training_builder

        return training_builder(self.cfg, key_mode)

    def train(
        self,
        batches: Iterable[CSRBatch],
        report_every: int = 50,
    ) -> dict[str, Any]:
        """Run the streaming solver over ``batches``; returns final metrics."""
        t0 = time.perf_counter()
        # device arrays accumulate un-synced so host work overlaps device
        # compute (JAX async dispatch); we only materialize at report time
        window_loss: list[jax.Array] = []
        window_probs: list[tuple[jax.Array, int]] = []
        window_labels: list[np.ndarray] = []
        n_since = 0
        last: dict[str, Any] = {}

        def _flush() -> dict[str, Any]:
            nonlocal window_loss, window_probs, window_labels, n_since, t0
            loss_sum = float(sum(float(x) for x in jax.device_get(window_loss)))
            p = np.concatenate(
                [np.asarray(pr)[:n] for pr, n in window_probs]
            )
            y = np.concatenate(window_labels)
            rec = self.reporter.report(
                examples=self.examples_seen,
                objv=loss_sum / max(n_since, 1),
                auc=M.auc(y, p),
                ex_per_sec=n_since / max(time.perf_counter() - t0, 1e-9),
            )
            window_loss, window_probs, window_labels = [], [], []
            n_since = 0
            t0 = time.perf_counter()
            return rec

        for step_i, b in enumerate(batches):
            dev = batch_to_device(b)
            self.store.state, out = train_step(self.updater, self.store.state, dev)
            self.examples_seen += b.num_examples
            n_since += b.num_examples
            window_loss.append(out["loss_sum"])
            window_probs.append((out["probs"], b.num_examples))
            window_labels.append(b.labels[: b.num_examples])
            if (step_i + 1) % report_every == 0:
                last = _flush()
        if n_since:
            last = _flush()
        return last

    def train_files(
        self, files: list[str], key_mode: str = "hash", report_every: int = 50
    ) -> dict[str, Any]:
        reader = MinibatchReader(
            files,
            self.cfg.data.format,
            self.make_builder(key_mode),
            epochs=self.cfg.solver.epochs,
        )
        return self.train(reader, report_every=report_every)

    def predict(self, batches: Iterable[CSRBatch]) -> tuple[np.ndarray, np.ndarray]:
        """Returns (labels, probs) over the stream."""
        ys, ps = [], []
        for b in batches:
            probs = predict_step(self.updater, self.store.state, batch_to_device(b))
            ps.append(np.asarray(probs)[: b.num_examples])
            ys.append(b.labels[: b.num_examples])
        return np.concatenate(ys), np.concatenate(ps)

    def evaluate(self, batches: Iterable[CSRBatch]) -> dict[str, float]:
        """Batch evaluation (reference analog: model_evaluation app)."""
        y, p = self.predict(batches)
        return {"auc": M.auc(y, p), "logloss": M.logloss(y, p), "examples": len(y)}

    def save(self, ckpt_dir: str) -> None:
        """Sharded checkpoint of the KV state + training cursor (reference:
        per-server SaveModel of its key range + recovery metadata)."""
        from parameter_server_tpu.utils.checkpoint import save_checkpoint

        save_checkpoint(
            ckpt_dir,
            {"kv": {k: np.asarray(v) for k, v in self.store.state.items()}},
            meta={
                "examples_seen": self.examples_seen,
                "algo": self.cfg.solver.algo,
                "num_keys": self.cfg.data.num_keys,
            },
        )

    def load(self, ckpt_dir: str) -> None:
        from parameter_server_tpu.utils.checkpoint import load_checkpoint

        state, meta = load_checkpoint(ckpt_dir)
        if meta.get("num_keys") != self.cfg.data.num_keys:
            raise ValueError(
                f"checkpoint num_keys {meta.get('num_keys')} != config "
                f"{self.cfg.data.num_keys}"
            )
        if meta.get("algo") != self.cfg.solver.algo:
            raise ValueError(
                f"checkpoint algo {meta.get('algo')!r} != config "
                f"{self.cfg.solver.algo!r}: updater state is not transferable"
            )
        self.store.state = {k: jnp.asarray(v) for k, v in state["kv"].items()}
        self.examples_seen = int(meta.get("examples_seen", 0))

    def dump_model(self, path: str) -> int:
        """Reference-style text dump of nonzero weights (key\\tweight)."""
        from parameter_server_tpu.utils.checkpoint import dump_weights_text

        return dump_weights_text(np.asarray(self.store.weights())[:, 0], path)
