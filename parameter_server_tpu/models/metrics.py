"""Evaluation metrics (reference analog: the AUC/logloss computed by
src/app/linear_method/model_evaluation.h and the online Progress AUC)."""

from __future__ import annotations

import numpy as np


def auc(labels: np.ndarray, scores: np.ndarray) -> float:
    """Exact ROC AUC via the rank statistic (ties averaged)."""
    y = np.asarray(labels).astype(bool)
    s = np.asarray(scores, dtype=np.float64)
    n_pos = int(y.sum())
    n_neg = len(y) - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(s, kind="mergesort")
    ranks = np.empty(len(s), dtype=np.float64)
    # average ranks over ties
    s_sorted = s[order]
    uniq, inv, counts = np.unique(s_sorted, return_inverse=True, return_counts=True)
    cum = np.cumsum(counts)
    avg_rank = (cum - (counts - 1) / 2.0).astype(np.float64)
    ranks[order] = avg_rank[inv]
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def logloss(labels: np.ndarray, probs: np.ndarray, eps: float = 1e-12) -> float:
    y = np.asarray(labels, dtype=np.float64)
    p = np.clip(np.asarray(probs, dtype=np.float64), eps, 1 - eps)
    return float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean())


def rmse(labels: np.ndarray, predictions: np.ndarray) -> float:
    """Root mean squared error of real-valued predictions."""
    d = np.asarray(predictions, dtype=np.float64) - np.asarray(labels, dtype=np.float64)
    return float(np.sqrt(np.mean(d * d)))


def sgns_loss(labels: np.ndarray, predictions: np.ndarray) -> float:
    """Mean negative-sampling loss an example: the skip-gram app predicts
    an example's log-likelihood, the negated loss."""
    return float(-np.mean(np.asarray(predictions, dtype=np.float64)))


# What an app's description (``parallel.spmd.StepApp.score``) names as its
# evaluator's scores, each a function of (labels, predictions); the first is
# also the progress table's column.
BINARY_SCORES = (("auc", auc), ("logloss", logloss))
REGRESSION_SCORES = (("rmse", rmse),)
SGNS_SCORES = (("sgns_loss", sgns_loss),)
