"""The plain reference for skip-gram with negative sampling, for the tests:
NumPy float32, dense ``syn0`` and ``syn1neg`` over the whole small
vocabulary, no localisation, no buckets, no scan, nothing imported from the
program.

Semantics (Mikolov et al., arXiv:1310.4546, eq. 4, negated; BASELINE.json
config 4): an example is a centre c, a context o_0 and k negatives
o_1..o_k; s_j = <syn0[c], syn1neg[o_j]>; loss = sum_j softplus(s_j) - s_0;
err_j = sigmoid(s_j) - [j == 0]; the gradient of syn0[c] is sum_j err_j
syn1neg[o_j], of syn1neg[o_j] err_j syn0[c]. A minibatch's loss is the SUM
over its examples; a row's gradient is summed over its repeats in the
batch and applied once, w -= eta * g (word2vec.c applies them pair by
pair). With several workers a step, each worker's gradient is taken at the
step's starting vectors and the pushes land in worker order.
"""

from __future__ import annotations

import numpy as np


def _softplus(x):
    return np.maximum(x, 0) + np.log1p(np.exp(-np.abs(x)))


def _sigmoid(x):
    return (1.0 / (1.0 + np.exp(-x.astype(np.float64)))).astype(np.float32)


class DenseSGNS:
    def __init__(self, in_vectors: np.ndarray, out_vectors: np.ndarray, eta: float):
        self.syn0 = np.array(in_vectors, np.float32)  # (V, d), by word id
        self.syn1 = np.array(out_vectors, np.float32)  # (V, d), by word id
        self.eta = np.float32(eta)

    def example_loss(self, centres, contexts, negatives) -> np.ndarray:
        """(n,) negative-sampling loss of every example."""
        out = np.column_stack([contexts, negatives])
        s = np.sum(self.syn0[centres][:, None, :] * self.syn1[out], axis=2, dtype=np.float32)
        return (np.sum(_softplus(s.astype(np.float64)), axis=1) - s[:, 0]).astype(np.float64)

    def mean_loss(self, centres, contexts, negatives) -> float:
        return float(np.mean(self.example_loss(centres, contexts, negatives)))

    def _grads(self, centres, contexts, negatives):
        out = np.column_stack([contexts, negatives])  # (n, 1+k)
        u, v = self.syn0[centres], self.syn1[out]
        s = np.sum(u[:, None, :] * v, axis=2, dtype=np.float32)
        err = _sigmoid(s)
        err[:, 0] -= np.float32(1.0)
        g0, g1 = np.zeros_like(self.syn0), np.zeros_like(self.syn1)
        np.add.at(g0, centres, np.sum(err[:, :, None] * v, axis=1, dtype=np.float32))
        np.add.at(g1, out.ravel(), (err[:, :, None] * u[:, None, :]).reshape(-1, u.shape[1]))
        t0, t1 = np.unique(centres), np.unique(out)
        loss = float(np.sum(_softplus(s.astype(np.float64))) - np.sum(s[:, 0].astype(np.float64)))
        return loss, (t0, g0[t0]), (t1, g1[t1])

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (centres, contexts,
        negatives (n, k)) minibatches. Returns the summed loss."""
        loss, pushes = 0.0, []
        for centres, contexts, negatives in workers:
            l, p0, p1 = self._grads(np.asarray(centres), np.asarray(contexts), np.asarray(negatives))
            loss += l
            pushes.append((p0, p1))
        for (t0, g0), (t1, g1) in pushes:
            self.syn0[t0] -= self.eta * g0
            self.syn1[t1] -= self.eta * g1
        return loss
