"""The ``matrix_fac`` app: rank-64 matrix factorization under plain SGD over
one key-addressed table (items' rows, then users'), built and stepped
through the program's own entry, ``PodTrainer`` with ``cfg.app =
"matrix_fac"`` and ``user item rating`` files.

Everything the traffic kinds ask of a session is ``apps/linear_ftrl.py``'s
(the files' cycling, the stamp on ``clock.finish``, the record around
``step_fn`` / ``predict_fn``, the prefix's bookkeeping); what differs is
here: the data (``harness/ratings.py``), the configuration handed to the
trainer, the reference (``harness/ref_mf.py``), the read-back of 64-wide
rows, the numbers compared, and RMSE where the CTR apps have AUC.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmark.apps import linear_ftrl as base
from benchmark.apps.wide_deep import _seed32, l2_gap
from benchmark.harness import ratings
from benchmark.harness.checks import Check, element_gaps
from benchmark.harness.ref_mf import RefMf, parse_ratings, rmse

StopWindow = base.StopWindow
SAMPLE_ROWS = base.SAMPLE_ROWS
TABLE = "mf.w"  # the trainer's state entry read back
PARTS = ("item", "user")  # compared apart: an item's row takes thousands of gradients a batch, a user's one


try:  # a program whose PodTrainer has no matrix-factorization app cannot run the cell:
    # say so as the app is loaded, before any data is made or a chip is looked for
    from parameter_server_tpu.models.matrix_fac import pod_config
except ImportError:
    raise SystemExit(
        "this program's PodTrainer knows no app matrix_fac (models.matrix_fac.pod_config "
        "is missing): it cannot run the cell"
    ) from None


def prepare(ctx, write: bool = True) -> dict:
    """Make the cell's ratings from the seed and write its files: NumPy and
    the file system only, so ``run.py`` does it while the TPU runtime
    starts. One file holds ``steps_per_call x minibatch`` ratings, one
    device call's worth for one worker. ``write=False`` (the control, which
    runs no program) makes the arrays alone."""
    st, t = ctx.config["settings"], ctx.traffic
    per_file = int(st["minibatch"]) * int(st["steps_per_call"])
    data_dir = os.path.join(ctx.workdir, "data", "base")
    if write:
        shutil.rmtree(os.path.join(ctx.workdir, "data"), ignore_errors=True)
        os.makedirs(data_dir)
    paths, parts = [], []
    for i in range(int(t["train_files"]) + int(t["heldout_files"])):
        parts.append(ratings.make_ratings(
            ctx.seed, per_file, ctx.config["data"], int(st["num_users"]), int(st["num_items"]), part=i
        ))
        paths.append(os.path.join(data_dir, f"part-{i:03d}.txt"))
        if write:
            ratings.write_text(paths[-1], *parts[-1])
    users, items, labels = (np.concatenate(x) for x in zip(*parts))
    ctx.stage(f"{len(paths)} files of {per_file} ratings made" + (" and written" if write else ""))
    return {"paths": paths, "users": users, "items": items, "labels": labels}


class Problem(base.Problem):
    """The data of one run and the plain reference over it: no program."""

    def __init__(self, ctx, data: dict):
        st = ctx.config["settings"]
        self.ctx = ctx
        self.data_shards = int(ctx.config["mesh"]["data"])
        self.minibatch = int(st["minibatch"])
        self.steps_per_call = int(st["steps_per_call"])
        self.file_examples = self.minibatch * self.steps_per_call
        self.num_users, self.num_items = int(st["num_users"]), int(st["num_items"])
        self.num_keys = 1 + self.num_items + self.num_users  # the pad row, the items, the users
        self.hyper = {k: st[k] for k in ("rank", "eta", "l2")}
        self.seed = _seed32(ctx.seed)
        self.users, self.items, self.labels = data["users"], data["items"], data["labels"]
        self.n_train_files = int(ctx.traffic["train_files"])
        self.prefix_files = int(ctx.traffic["prefix_calls"]) * self.data_shards

    def load_files(self, paths: list) -> None:
        """Take the ratings from the reference's own parse of the files the
        program read, in place of the arrays they were written from."""
        self.users, self.items, self.labels = (np.concatenate(x) for x in zip(*map(parse_ratings, paths)))

    def rows_of(self, span: slice):
        """(item rows, user rows) of the span's ratings, in the table."""
        return ratings.table_rows(self.users[span], self.items[span], self.num_items)

    def real_keys(self) -> float:
        """Keys a minibatch of the training files holds, on average: its
        distinct items plus its distinct users (the pad slot is none)."""
        counts = []
        for at in range(0, self.n_train_files * self.file_examples, self.minibatch):
            item_rows, user_rows = self.rows_of(slice(at, at + self.minibatch))
            counts.append(len(np.unique(item_rows)) + len(np.unique(user_rows)))
        return float(np.mean(counts))

    def sample_rows(self) -> np.ndarray:
        """Table rows read back after the prefix, the items' first: every
        item row the prefix touched, and a seeded sample of its user rows;
        at most SAMPLE_ROWS."""
        item_rows, user_rows = self.rows_of(slice(0, self.prefix_files * self.file_examples))
        hot, rest = np.unique(item_rows)[: SAMPLE_ROWS // 2], np.unique(user_rows)
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        return np.concatenate([hot, rng.choice(rest, min(len(rest), SAMPLE_ROWS - len(hot)), replace=False)])

    def new_reference(self, rows_universe: np.ndarray, precision: str):
        return RefMf(rows_universe, self.hyper, self.seed, self.num_keys, precision)

    def reference(self, assignment: list, precision: str = "float32", score: tuple = ("heldout",)):
        """The plain reference after the prefix's steps, its per-step
        losses, and {name: (item positions, user positions, ratings)} of
        the spans named in ``score``, which its row universe then holds."""
        named = self.score_spans()
        spans = [slice(0, self.prefix_files * self.file_examples)] + [named[k] for k in score]
        rows = [self.rows_of(s) for s in spans]
        ref = self.new_reference(np.concatenate([np.concatenate(r) for r in rows]), precision)
        item_at, user_at = (ref.index(r) for r in rows[0])
        losses = []
        for per_worker in assignment:
            for k in range(self.steps_per_call):
                batches = []
                for f in per_worker:
                    lo = f * self.file_examples + k * self.minibatch
                    sl = slice(lo, lo + self.minibatch)
                    batches.append((item_at[sl], user_at[sl], self.labels[sl]))
                losses.append(ref.step(batches))
        scored = {
            k: (ref.index(r[0]), ref.index(r[1]), self.labels[s]) for k, r, s in zip(score, rows[1:], spans[1:])
        }
        return ref, np.asarray(losses), scored

    def prefix_numbers(self, got_losses, got: np.ndarray, rows: np.ndarray, ref: RefMf, ref_losses) -> dict:
        """The prefix's compared numbers: the worst relative gap of the 8
        losses; of the item rows and of the user rows read back, each over
        all their lanes, the gap that half and 99% of the elements stay
        under and the worst one (plain SGD has no step that jumps: the worst
        element is as steady as the median), and the distance between the
        two sides' CHANGE since the start over the size of the reference's:
        a user's row moves by a thousandth of itself in the prefix, so a
        table left as it was reads 1 there where an element's gap reads 1e-3."""
        at = ref.index(rows)
        want, start = ref.w[at], ref.w0[at]
        out = {"prefix.loss_gap": float(np.max(np.abs(got_losses - ref_losses) / np.abs(ref_losses)))}
        is_item = rows <= self.num_items
        for part, mine in zip(PARTS, (is_item, ~is_item)):
            gaps = element_gaps(got[mine], want[mine])
            for q in (50, 99, 100):
                out[f"prefix.{part}_w_gap_{'max' if q == 100 else f'q{q}'}"] = float(np.percentile(gaps, q))
            out[f"prefix.{part}_step_gap"] = l2_gap(got[mine] - start[mine], want[mine] - start[mine])
        return out


def read_rows(arr, rows: np.ndarray, block: int) -> np.ndarray:
    """Rows ``rows`` of a (num_rows, rank) jax array, range-sharded over
    ``kv`` or not, as float32 (len(rows), rank): each shard is asked for its
    own rows on its own device at one fixed shape, element by element. A
    gather of whole 64-lane rows would have XLA copy the table into padded
    row-major tiles first, twice the table's bytes (PERF.md section 6, PR 32)."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda v, i: v[i[:, None], jnp.arange(v.shape[1], dtype=jnp.int32)[None, :]])
    rows = np.asarray(rows, np.int64)
    out = np.zeros((len(rows), arr.shape[1]), np.float32)
    seen = set()
    for shard in arr.addressable_shards:
        sl = shard.index[0]
        lo = sl.start or 0
        hi = sl.stop if sl.stop is not None else arr.shape[0]
        if (lo, hi) in seen:  # a replica over the data axis
            continue
        seen.add((lo, hi))
        mine = np.flatnonzero((rows >= lo) & (rows < hi))
        for at in range(0, len(mine), block):
            part = mine[at : at + block]
            idx = np.zeros(block, np.int32)
            idx[: len(part)] = rows[part] - lo
            out[part] = np.asarray(take(shard.data, idx))[: len(part)]
    return out


def heldout_scores(ref: RefMf, held) -> tuple:
    """(RMSE, predictions) of the reference over a scored span."""
    item_at, user_at, y = held
    p = ref.predict(item_at, user_at)
    return rmse(p, y), p


def rmse_above_reference(ref, other, scored: dict) -> dict:
    """``<span>.rmse_above_reference`` of every scored span: the RMSE of
    ``other``, a reference over the same universe, minus the reference's."""
    return {
        f"{k}.rmse_above_reference": heldout_scores(other, s)[0] - heldout_scores(ref, s)[0]
        for k, s in scored.items()
    }


def gap_lines(got_losses, ref_losses, numbers: dict) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit."""
    rel = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
    return ["[gaps] losses: " + " ".join(f"{g:.3g}" for g in rel)] + [
        f"[gaps] {name}: {value:.4g}" for name, value in numbers.items()
    ]


def control(ctx, precision: str = "bfloat16") -> dict:
    """The control: the reference in ``precision`` put in the program's
    place, at the cell's own size. Needs no chip: the program is not in it."""
    prob = Problem(ctx, prepare(ctx, write=False))
    plan = prob.nominal_assignment()
    ref, ref_losses, scored = prob.reference(plan, "float32", score=("heldout", "trained"))
    low, low_losses, _ = prob.reference(plan, precision, score=())
    rows = prob.sample_rows()
    out = prob.prefix_numbers(low_losses, low.w[low.index(rows)], rows, ref, ref_losses)
    print("\n".join(gap_lines(low_losses, ref_losses, out)), flush=True)
    # the lower precision's scores: its state carried over the float32
    # reference's universe row by row
    wide = prob.new_reference(ref.rows, precision)
    wide.w[ref.index(low.rows)] = low.w
    out.update(rmse_above_reference(ref, wide, scored))
    return out


class Session(base.Session):
    problem_type = Problem

    def __init__(self, ctx):
        if ctx.prepared is None:  # a run that ``run.py`` did not start
            ctx.prepared = prepare(ctx)
        super().__init__(ctx)

    def _config(self):
        from parameter_server_tpu.utils.config import PSConfig

        st, p = self.settings, self.problem
        cfg = PSConfig()
        cfg.seed = p.seed
        cfg.mf.num_users, cfg.mf.num_items = p.num_users, p.num_items
        cfg.mf.rank, cfg.mf.algo = int(st["rank"]), st["algo"]
        cfg.mf.eta, cfg.mf.l2 = st["eta"], st["l2"]
        cfg.mf.batch_size = self.minibatch
        cfg.data.pipeline_depth = int(st["pipeline_depth"])
        cfg.data.bucket_nnz = bool(st["bucket_nnz"])
        cfg.solver.steps_per_call = self.steps_per_call
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.epochs = 1
        cfg.parallel.data_shards = self.data_shards
        cfg.parallel.kv_shards = self.kv_shards
        cfg.parallel.push_mode = st["push_mode"]
        return pod_config(cfg)  # rating files, the key space's size, two entries an example

    def measure_build_rate(self) -> float:
        """Parse + BatchBuilder on one stream, one file, ratings/s. Also
        builds ``libpsdata.so`` in a fresh checkout and reads the file once."""
        from parameter_server_tpu.data.batch import training_builder
        from parameter_server_tpu.data.reader import MinibatchReader, ingest_of

        fmt, key_mode = ingest_of(self.cfg)
        t0 = time.perf_counter()
        n = sum(
            b.num_examples
            for b in MinibatchReader([self.train_paths[0]], fmt, training_builder(self.cfg, key_mode))
        )
        return n / (time.perf_counter() - t0)

    def read_state(self, rows) -> np.ndarray:
        """Rows ``rows`` of the table off the device(s): (len(rows), rank)."""
        return read_rows(self.trainer.state[TABLE], rows, SAMPLE_ROWS)

    def prefix(self, score_heldout: bool = False) -> None:
        """The linear app's prefix; the held-out files are scored by RMSE,
        at the state the reference will have had the training of (those
        seconds are the harness's own checking: ``ctx.excluded_s``)."""
        super().prefix(score_heldout=False)
        if score_heldout:
            t = time.perf_counter()
            self.heldout_rmse = float(self.evaluate(self.heldout_paths)["rmse"])
            self.ctx.excluded_s += time.perf_counter() - t
            self.ctx.stage("held-out files scored at the prefix's state (not set-up)")
            self.prefix_epoch_done()

    def prefix_epoch_done(self) -> None:
        """As Wide&Deep's: the epoch ended in inert calls of the smallest
        bucket's shape, a second program of the step's module name whose
        names ``op_scopes`` would merge with the window's."""
        from parameter_server_tpu.parallel import spmd

        spmd.forget_programs()

    def reference(self, precision: str = "float32", score: tuple = ("heldout",)):
        self.problem.load_files(self.train_paths + self.heldout_paths)
        return self.problem.reference(self.worker_files(), precision, score)

    def prefix_checks(self, ref: RefMf, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        got = self.problem.prefix_numbers(self.prefix_losses, self.sample_state, self.sample_rows, ref, ref_losses)
        print("\n".join(gap_lines(self.prefix_losses, ref_losses, got)), flush=True)
        return [Check(name, value, lim[name]) for name, value in got.items()]
