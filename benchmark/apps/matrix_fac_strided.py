"""The ``matrix_fac_strided`` app: ``apps/matrix_fac.py`` at a rank the
program stores wider than itself (100 lanes in 128: ``spmd.row_stride``).
Everything is that app's; what differs is the read-back: whole stored rows
are read off each shard, the first ``rank`` lanes are the row the reference
is compared with, and the lanes past them are counted where they are not
exactly zero (``prefix.pad_lanes_nonzero``, limit 0: a pad lane that moved
is a push that wrote where no row is). And, for the collectives' readers,
the keys a microstep's minibatches really hold are counted by the worker
that holds them and the kv shard that owns their rows (``keys_owned``).
"""

from __future__ import annotations

import numpy as np

from benchmark.apps import matrix_fac as base
from benchmark.apps.sgns import read_rows  # whole stored rows, shard by shard, in place at whole tiles
from benchmark.harness.checks import Check

StopWindow = base.StopWindow
prepare = base.prepare
control = base.control
heldout_scores = base.heldout_scores


class Problem(base.Problem):
    shard_rows = None  # rows a kv shard of the program's table holds: the session reads it off the table

    def real_keys(self) -> float:
        """As the base's; besides, where the table's sharding is known, the
        same minibatches' distinct keys by (worker, owning kv shard), the
        mean over the microsteps of the training files' calls, left in
        ``config["observed"]["keys_owned"]`` for ``coll.ici_share``."""
        if self.shard_rows:
            d = self.data_shards
            shards = -(-self.num_keys // self.shard_rows)
            counts = []
            for call in range(self.n_train_files // d):
                for k in range(self.steps_per_call):
                    per_worker = []
                    for w in range(d):
                        at = (call * d + w) * self.file_examples + k * self.minibatch
                        rows = np.unique(np.concatenate(self.rows_of(slice(at, at + self.minibatch))))
                        per_worker.append(np.bincount(rows // self.shard_rows, minlength=shards))
                    counts.append(per_worker)
            self.ctx.config.setdefault("observed", {})["keys_owned"] = np.mean(counts, axis=0).tolist()
        return super().real_keys()


class Session(base.Session):
    problem_type = Problem

    def __init__(self, ctx):
        super().__init__(ctx)
        self.problem.shard_rows = self.trainer.state[base.TABLE].shape[0] // self.kv_shards

    def read_state(self, rows) -> np.ndarray:
        """Rows ``rows`` of the table off the device(s): (len(rows), rank),
        the stored rows' lanes past ``rank`` counted and cut off."""
        table = self.trainer.state[base.TABLE]
        rank = int(self.problem.hyper["rank"])
        got = read_rows(table, rows, table.shape[1], base.SAMPLE_ROWS)
        self.pad_lanes_nonzero = int(np.count_nonzero(got[:, rank:]))
        return got[:, :rank]

    def prefix_checks(self, ref, ref_losses) -> list:
        name = "prefix.pad_lanes_nonzero"
        return super().prefix_checks(ref, ref_losses) + [
            Check(name, self.pad_lanes_nonzero, self.ctx.traffic["limits"][name],
                  note="elements past the rank's lanes, of the rows read back, that are not exactly zero")
        ]
