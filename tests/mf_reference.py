"""The plain reference for matrix factorization, for the tests: NumPy
float32, dense ``U`` and ``V``, no localisation, no buckets, no scan, nothing
imported from the program. Per minibatch: predict, squared error, the
gradient summed over a row's repeats, L2 on the touched rows, one SGD step.

Semantics (BASELINE.json config 3, "rank-64 SGD, async push/pull"; the
program's ``_mf_loss_and_grads`` of PRs 1-31): loss = sum err^2 with err =
<u, v> - r; the pushed gradient is that of HALF the loss (err * v, err * u)
plus l2 * row on every row the batch touches, once a batch however often
the row repeats; w -= eta * g. Departures from the words of the config:
"async" is bounded staleness, which changes no arithmetic (a step sees every
earlier step's push); with several workers a step, each worker's gradient is
taken at the step's starting factors and the pushes land in worker order.
"""

from __future__ import annotations

import numpy as np


class DenseMF:
    def __init__(self, user_factors: np.ndarray, item_factors: np.ndarray, eta: float, l2: float):
        self.U = np.array(user_factors, np.float32)  # (num_users, rank), by user id
        self.V = np.array(item_factors, np.float32)  # (num_items, rank), by item id
        self.eta, self.l2 = np.float32(eta), np.float32(l2)

    def predict(self, users, items) -> np.ndarray:
        return np.sum(self.U[users] * self.V[items], axis=1, dtype=np.float32)

    def rmse(self, users, items, ratings) -> float:
        d = self.predict(users, items).astype(np.float64) - ratings
        return float(np.sqrt(np.mean(d * d)))

    def _grads(self, users, items, ratings):
        u, v = self.U[users], self.V[items]
        err = np.sum(u * v, axis=1, dtype=np.float32) - np.asarray(ratings, np.float32)
        g_u, g_v = np.zeros_like(self.U), np.zeros_like(self.V)
        np.add.at(g_u, users, err[:, None] * v)
        np.add.at(g_v, items, err[:, None] * u)
        tu, tv = np.unique(users), np.unique(items)
        g_u[tu] += self.l2 * self.U[tu]
        g_v[tv] += self.l2 * self.V[tv]
        return float(np.sum(err.astype(np.float64) ** 2)), (tu, g_u[tu]), (tv, g_v[tv])

    def step(self, workers: list) -> float:
        """One parameter-server step over the workers' (users, items,
        ratings) minibatches. Returns the summed squared error."""
        loss, pushes = 0.0, []
        for users, items, ratings in workers:
            l, pu, pv = self._grads(np.asarray(users), np.asarray(items), ratings)
            loss += l
            pushes.append((pu, pv))
        for (tu, g_u), (tv, g_v) in pushes:
            self.U[tu] -= self.eta * g_u
            self.V[tv] -= self.eta * g_v
        return loss
