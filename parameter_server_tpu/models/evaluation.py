"""Batch model evaluation app.

Reference analog: src/app/linear_method/model_evaluation.h — load a saved
model dump (text key\\tweight) plus validation files, compute AUC/logloss.
No online serving system exists in the reference; batch evaluation is the
parity surface."""

from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.reader import MinibatchReader
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.ops.sparse import csr_logits
from parameter_server_tpu.utils.checkpoint import load_weights_text


def evaluate_model(
    weights: np.ndarray | str | Path,
    files: list[str],
    fmt: str,
    num_keys: int,
    batch_size: int = 8192,
    max_nnz_per_example: int = 256,
    key_mode: str = "hash",
) -> dict:
    """AUC / logloss of a weight vector over validation files."""
    if isinstance(weights, (str, Path)):
        weights = load_weights_text(weights, num_keys)
    w = jnp.asarray(np.asarray(weights, dtype=np.float32).reshape(-1, 1))
    builder = BatchBuilder(
        num_keys=num_keys,
        batch_size=batch_size,
        max_nnz_per_example=max_nnz_per_example,
        key_mode=key_mode,
    )
    ys, ps = [], []
    n = 0
    for b in MinibatchReader(files, fmt, builder):
        w_u = jnp.take(w, jnp.asarray(b.unique_keys), axis=0)
        logits = csr_logits(
            w_u,
            jnp.asarray(b.values),
            jnp.asarray(b.local_ids),
            jnp.asarray(b.row_ids),
            jnp.asarray(b.row_splits),
        )
        ps.append(np.asarray(jax.nn.sigmoid(logits))[: b.num_examples])
        ys.append(b.labels[: b.num_examples])
        n += b.num_examples
    y = np.concatenate(ys)
    p = np.concatenate(ps)
    return {
        "auc": M.auc(y, p),
        "logloss": M.logloss(y, p),
        "examples": n,
        "nnz_w": int((np.asarray(weights) != 0).sum()),
    }
