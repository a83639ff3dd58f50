"""The ``linear_ftrl`` app: sparse logistic regression under FTRL over one
key-addressed table, built and stepped through the program's own entry,
``parallel.trainer.PodTrainer``.

What the benchmark takes from the program: the trainer (``train_files``,
``evaluate_files``, ``state``, ``examples_seen``, ``max_inflight``), the
named timers of ``utils.metrics`` and the place ``hostenv`` puts the compile
cache. What it adds around the program's calls, from outside: a stamp when
a device call retires (``trainer.clock.finish`` is what ``_retire`` calls
right after its blocking read), and a record of each dispatched call's
outputs (``trainer.step_fn``), so that every loss of the window can be read
once the window has closed.

Sessions of other apps (Wide&Deep, MF, SGNS) offer the same few methods to
the traffic kinds: ``prefix``, ``train``, ``evaluate``, ``reference``,
``prefix_checks``.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import sys
import time

import numpy as np

from benchmark.harness import criteo
from benchmark.harness.checks import Check, element_gaps, norm_gap, worst_gap
from benchmark.harness.readback import read_rows
from benchmark.harness.ref_ftrl import RefFtrl, auc, logloss

NEVER = 10**9  # report_every beyond any window: no drain-and-AUC inside it
SAMPLE_ROWS = 1 << 18  # table rows read back after the prefix, a fixed shape


class StopWindow(Exception):
    """Raised from the retire hook by a traffic kind to end an epoch."""


def prepare(ctx, write: bool = True) -> dict:
    """Make the cell's data from the seed and write its files: NumPy and
    the file system only, so ``run.py`` does it while the TPU runtime
    starts. One file holds ``steps_per_call x minibatch`` examples, one
    device call's worth for one worker. ``write=False`` (the control, which
    runs no program) makes the arrays alone."""
    st, t = ctx.config["settings"], ctx.traffic
    per_file = int(st["minibatch"]) * int(st["steps_per_call"])
    base = os.path.join(ctx.workdir, "data", "base")
    if write:
        shutil.rmtree(os.path.join(ctx.workdir, "data"), ignore_errors=True)
        os.makedirs(base)
    paths, parts = [], []
    for i in range(int(t["train_files"]) + int(t["heldout_files"])):
        parts.append(criteo.make_examples(ctx.seed, per_file, ctx.config["data"], part=i))
        paths.append(os.path.join(base, f"part-{i:03d}.tsv"))
        if write:
            criteo.write_tsv(paths[-1], *parts[-1])
    labels, ints, cats = (np.concatenate(x) for x in zip(*parts))
    ctx.stage(f"{len(paths)} files of {per_file} examples made" + (" and written" if write else ""))
    return {"paths": paths, "labels": labels, "ints": ints, "cats": cats}


class Problem:
    """The data of one run and the plain reference over it: no program."""

    def __init__(self, ctx, data: dict):
        st = ctx.config["settings"]
        self.ctx = ctx
        self.data_shards = int(ctx.config["mesh"]["data"])
        self.minibatch = int(st["minibatch"])
        self.steps_per_call = int(st["steps_per_call"])
        self.file_examples = self.minibatch * self.steps_per_call
        self.num_keys = int(st["num_keys"])
        self.hyper = {k: st[k] for k in ("alpha", "beta", "lambda_l1", "lambda_l2")}
        self.labels, self.ints, self.cats = data["labels"], data["ints"], data["cats"]
        self.n_train_files = int(ctx.traffic["train_files"])
        self.prefix_files = int(ctx.traffic["prefix_calls"]) * self.data_shards

    def file_slice(self, i: int) -> slice:
        return slice(i * self.file_examples, (i + 1) * self.file_examples)

    def nominal_assignment(self) -> list:
        """[[file of worker 0, file of worker 1, ...] per prefix call] when
        the streams take the files in list order."""
        d = self.data_shards
        return [list(range(c * d, (c + 1) * d)) for c in range(self.prefix_files // d)]

    def sample_rows(self) -> np.ndarray:
        """Table rows read back after the prefix: the 13 integer columns'
        rows (every example touches them) and a seeded sample of the rest;
        at most SAMPLE_ROWS."""
        ex = slice(0, self.prefix_files * self.file_examples)
        rows, _ = criteo.features(self.ints[ex], self.cats[ex], self.num_keys)
        hot = np.unique(rows[:, : criteo.N_INT])
        rest = np.setdiff1d(np.unique(rows[:, criteo.N_INT :]), hot)
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        take = min(len(rest), SAMPLE_ROWS - len(hot))
        return np.concatenate([hot, rng.choice(rest, take, replace=False)])

    def score_spans(self) -> dict:
        """The examples a run scores beside the prefix it trains: the
        held-out files, and as many of the training files from the first
        on (the guard of the window scores the table on what it trained)."""
        fe, n_files = self.file_examples, len(self.labels) // self.file_examples
        n_trained = min(n_files - self.n_train_files, self.n_train_files)
        return {
            "heldout": slice(self.n_train_files * fe, n_files * fe),
            "trained": slice(0, n_trained * fe),
        }

    def new_reference(self, rows_universe: np.ndarray, precision: str):
        return RefFtrl(rows_universe, self.hyper, precision)

    def reference(self, assignment: list, precision: str = "float32", score: tuple = ("heldout",)):
        """The plain reference after the prefix's steps, its per-step
        losses, and {name: (idx, vals, labels)} of the spans named in
        ``score`` (``score_spans``), which its row universe then holds."""
        named = self.score_spans()
        spans = [slice(0, self.prefix_files * self.file_examples)] + [named[k] for k in score]
        feats = [criteo.features(self.ints[s], self.cats[s], self.num_keys) for s in spans]
        ref = self.new_reference(np.concatenate([f[0].ravel() for f in feats]), precision)
        idx, vals = ref.index(feats[0][0]), feats[0][1]
        losses = []
        for per_worker in assignment:
            for k in range(self.steps_per_call):
                batches = []
                for f in per_worker:
                    lo = f * self.file_examples + k * self.minibatch
                    sl = slice(lo, lo + self.minibatch)
                    batches.append((idx[sl], vals[sl], self.labels[sl]))
                losses.append(ref.step(batches))
        scored = {
            k: (ref.index(f[0]), f[1], self.labels[s]) for k, f, s in zip(score, feats[1:], spans[1:])
        }
        return ref, np.asarray(losses), scored

    @staticmethod
    def prefix_numbers(got_losses, got_state: dict, rows, ref: RefFtrl, ref_losses) -> dict:
        """The prefix's compared numbers: ``got_*`` against ``ref``. A row's
        ``z`` is what a sum of gradients left over, and ``n`` is the sum of
        their squares: ``z``'s gap is measured against sqrt(n) where that is
        the larger. An integer column's row takes every example's gradient
        (n of 4e7 after one call) and may end inside the L1 dead zone,
        |z| < lambda_l1, where both sides' weight is 0: float32 leaves 0.04
        there, which is 6e-6 of sqrt(n) and was 0.109 of the median row
        (``PERF.md`` section 2)."""
        at = ref.index(rows)
        return {
            "prefix.loss_gap": float(np.max(np.abs(got_losses - ref_losses) / np.abs(ref_losses))),
            "prefix.z_gap": worst_gap(got_state["z"], ref.z[at], scale=np.sqrt(ref.n[at])),
            "prefix.n_gap": worst_gap(got_state["n"], ref.n[at]),
            "prefix.z_norm_gap": norm_gap(got_state["z"], ref.z[at]),
            "prefix.n_norm_gap": norm_gap(got_state["n"], ref.n[at]),
        }

    @staticmethod
    def eval_numbers(got_probs, got_logloss: float, got_auc: float, ref_scores) -> dict:
        ref_auc, ref_ll, ref_p = ref_scores
        return {
            "eval.prob_gap": float(np.max(np.abs(got_probs - ref_p[: len(got_probs)]))),
            "eval.logloss_gap": abs(got_logloss - ref_ll) / ref_ll,
            "eval.auc_gap": abs(got_auc - ref_auc),
        }


def control(ctx, precision: str = "bfloat16") -> dict:
    """The control: the reference in ``precision`` put in the program's
    place, at the cell's own size. Every number the cell compares, as the
    lower precision reads it. Needs no chip: the program is not in it."""
    prob = Problem(ctx, prepare(ctx, write=False))
    plan = prob.nominal_assignment()
    ref, ref_losses, scored = prob.reference(plan, "float32", score=("heldout", "trained"))
    low, low_losses, _ = prob.reference(plan, precision, score=())
    rows = prob.sample_rows()
    at = low.index(rows)
    out = Problem.prefix_numbers(low_losses, {"z": low.z[at], "n": low.n[at]}, rows, ref, ref_losses)
    # the lower precision's scores over the float32 reference's universe:
    # copy its state across by row
    pos = ref.index(low.rows)
    wide = RefFtrl(ref.rows, prob.hyper, precision)
    wide.z[pos], wide.n[pos] = low.z, low.n
    held = scored["heldout"]
    auc_l, ll_l, p_l = heldout_scores(wide, held)
    out.update(Problem.eval_numbers(p_l[: prob.minibatch], ll_l, auc_l, heldout_scores(ref, held)))
    out.update(auc_below_reference(ref, wide, scored))
    return out


class Session:
    problem_type = Problem

    def __init__(self, ctx):
        self.ctx = ctx
        data = ctx.prepared or prepare(ctx)
        self.problem = p = self.problem_type(ctx, data)
        self.settings = ctx.config["settings"]
        self.data_shards, self.minibatch = p.data_shards, p.minibatch
        self.kv_shards = int(ctx.config["mesh"]["kv"])
        self.steps_per_call, self.file_examples = p.steps_per_call, p.file_examples
        self.call_examples = self.file_examples * self.data_shards
        self.num_keys, self.hyper = p.num_keys, p.hyper
        n_train = p.n_train_files
        paths = data["paths"]
        self.train_paths, self.heldout_paths = paths[:n_train], paths[n_train:]
        # the training files the window's guard scores
        self.trained_paths = self.train_paths[: p.score_spans()["trained"].stop // p.file_examples]
        self.n_train_files, self.prefix_files = n_train, p.prefix_files
        self.retired = 0  # device calls retired in the current epoch
        self.calls: list = []  # one dict per dispatched call of the current epoch
        self.on_retire = None
        self.keep_labels = False
        self.eval_first = None
        self.eval_slots: list = []  # unique-key slots of each predict call of the last pass
        self.heldout_auc = None  # the program's, right after the prefix
        self._build()
        import jax

        jax.block_until_ready(self.trainer.state)
        ctx.stage("trainer built, table on the device")

    def file_list(self, n_files: int, start: int = 0) -> list:
        """``n_files`` distinct paths that cycle over the training files:
        repetition r reads them through the symlink ``data/r<r>``, because
        the program's WorkloadPool is keyed by path."""
        out = []
        for j in range(start, start + n_files):
            rep, i = divmod(j, self.n_train_files)
            d = os.path.join(self.ctx.workdir, "data", f"r{rep:04d}")
            if not os.path.islink(d):
                os.symlink("base", d)
            out.append(os.path.join(d, os.path.basename(self.train_paths[i])))
        return out

    # -- the program ------------------------------------------------------
    def _config(self):
        """The program's configuration for this cell's settings."""
        from parameter_server_tpu.utils.config import PSConfig

        st = self.settings
        cfg = PSConfig()
        cfg.data.format = "criteo"
        cfg.data.num_keys = self.num_keys
        cfg.data.pipeline_depth = int(st["pipeline_depth"])
        cfg.data.bucket_nnz = bool(st["bucket_nnz"])
        cfg.data.max_nnz_per_example = int(st["max_nnz_per_example"])
        cfg.solver.algo = st["algo"]
        cfg.solver.minibatch = self.minibatch
        cfg.solver.steps_per_call = self.steps_per_call
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.epochs = 1
        cfg.lr.alpha, cfg.lr.beta = st["alpha"], st["beta"]
        cfg.penalty.lambda_l1, cfg.penalty.lambda_l2 = st["lambda_l1"], st["lambda_l2"]
        cfg.parallel.data_shards = self.data_shards
        cfg.parallel.kv_shards = self.kv_shards
        cfg.parallel.push_mode = st["push_mode"]
        return cfg

    def _build(self) -> None:
        from parameter_server_tpu.parallel.trainer import PodTrainer

        self.cfg = self._config()
        self.trainer = tr = PodTrainer(self.cfg)

        finish = tr.clock.finish

        def finish_stamped(worker, step):
            t = time.perf_counter()  # the retire's blocking read just returned
            out = finish(worker, step)
            self.retired += 1
            if self.on_retire is not None:
                self.on_retire(t, self.retired - 1)
            return out

        tr.clock.finish = finish_stamped
        step_fn = tr.step_fn

        def step_recorded(state, batch, seed):
            new_state, out = step_fn(state, batch, seed)
            self.calls.append({
                "seen_before": tr.examples_seen,
                "loss": out["loss_sum"],
                "examples": out["examples"],
                "slots": batch["unique_keys"].shape[-1],
                "labels": batch["labels"] if self.keep_labels else None,
            })
            return new_state, out

        tr.step_fn = step_recorded
        predict_fn = tr.predict_fn

        def predict_recorded(state, batch):
            probs = predict_fn(state, batch)
            self.eval_slots.append(batch["unique_keys"].shape[-1])
            if self.eval_first is None:
                self.eval_first = probs
            return probs

        tr.predict_fn = predict_recorded

    def measure_build_rate(self) -> float:
        """Parse + BatchBuilder on one stream, one file, examples/s. Also
        builds ``libpsdata.so`` in a fresh checkout and reads the file once."""
        from parameter_server_tpu.data.batch import training_builder
        from parameter_server_tpu.data.reader import MinibatchReader

        t0 = time.perf_counter()
        n = sum(
            b.num_examples
            for b in MinibatchReader(
                [self.train_paths[0]], "criteo", training_builder(self.cfg)
            )
        )
        return n / (time.perf_counter() - t0)

    def train(self, files: list) -> bool:
        """One epoch over ``files`` through ``train_files``; True if it ran
        to its own end, False if the retire hook stopped it."""
        self.retired, self.calls = 0, []
        try:
            # the reporter's progress table goes to stderr: stdout is ours
            with contextlib.redirect_stdout(sys.stderr):
                self.trainer.train_files(files, report_every=NEVER)
            return True
        except StopWindow:
            return False

    def call_work(self) -> list:
        """Examples each dispatched call of the epoch carried (host count)."""
        seen = [c["seen_before"] for c in self.calls] + [self.trainer.examples_seen]
        return [b - a for a, b in zip(seen, seen[1:])]

    def call_slots(self) -> list:
        """Unique-key slots a worker's microstep of each dispatched call
        carried: the last axis of the ``unique_keys`` the step was handed."""
        return [c["slots"] for c in self.calls]

    def call_outputs(self):
        """Per dispatched call: (K,) losses and (K,) device example counts.
        Blocks until every one of them is done."""
        return (
            [np.atleast_1d(np.asarray(c["loss"])) for c in self.calls],
            [np.atleast_1d(np.asarray(c["examples"])) for c in self.calls],
        )

    def evaluate(self, files: list) -> dict:
        self.eval_first, self.eval_slots = None, []
        return self.trainer.evaluate_files(files)

    # -- the correctness prefix --------------------------------------------
    def prefix(self, score_heldout: bool = False) -> None:
        """From the fresh table, the first ``prefix_calls`` device calls
        through the window's own call and feed; keeps what the reference is
        compared with once the window has closed. ``score_heldout``: also
        the AUC of the held-out files at this state, which has had the
        training the reference will have had; those seconds are the
        harness's own checking and not set-up (``ctx.excluded_s``)."""
        n_calls = int(self.ctx.traffic["prefix_calls"])
        self.keep_labels = True
        self.ctx.stage("prefix starts")
        ran_out = self.train(self.file_list(self.prefix_files))
        self.ctx.stage("prefix epoch done")
        self.keep_labels = False
        if not ran_out:
            raise RuntimeError("the prefix epoch was stopped")
        if score_heldout:
            t = time.perf_counter()
            self.heldout_auc = float(self.evaluate(self.heldout_paths)["auc"])
            self.ctx.excluded_s += time.perf_counter() - t
            self.ctx.stage("held-out files scored at the prefix's state (not set-up)")
        self.prefix_epoch_done()
        work = self.call_work()
        real = [i for i, w in enumerate(work) if w > 0]
        if len(real) != n_calls or any(work[i] != self.call_examples for i in real):
            raise RuntimeError(f"prefix calls carried {work}, want {n_calls} x {self.call_examples}")
        losses, _ = self.call_outputs()
        self.prefix_losses = np.concatenate([losses[i] for i in real])
        self.prefix_labels = [np.asarray(self.calls[i]["labels"]) for i in real]
        self.sample_rows = self.problem.sample_rows()
        self.sample_state = self.read_state(self.sample_rows)
        self.ctx.stage("prefix trained and read back")

    def prefix_epoch_done(self) -> None:
        """For an app whose prefix leaves something behind that the window
        must not see."""

    def read_state(self, rows) -> dict:
        return read_rows(self.trainer.state, rows, SAMPLE_ROWS)

    # -- the reference, run once the window has closed ----------------------
    def worker_files(self) -> list:
        """Which file each worker's stream handed to each prefix call, read
        off the labels the step was given: [[file of worker 0, ...], ...]."""
        p, out = self.problem, []
        for labels in self.prefix_labels:  # (D, K, B)
            per_worker = []
            for d in range(self.data_shards):
                first = labels[d, 0]
                hits = [
                    f for f in range(self.prefix_files)
                    if np.array_equal(first, p.labels[p.file_slice(f)][: self.minibatch])
                ]
                if len(hits) != 1:
                    raise RuntimeError(f"worker {d}'s batch matches files {hits}")
                per_worker.append(hits[0])
            out.append(per_worker)
        return out

    def reference(self, precision: str = "float32", score: tuple = ("heldout",)):
        return self.problem.reference(self.worker_files(), precision, score)

    def prefix_checks(self, ref: RefFtrl, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        print("\n".join(gap_lines(self.sample_state, self.sample_rows, ref)), flush=True)
        got = Problem.prefix_numbers(self.prefix_losses, self.sample_state, self.sample_rows, ref, ref_losses)
        return [Check(name, value, lim[name]) for name, value in got.items()]

    def close(self) -> None:
        shutil.rmtree(os.path.join(self.ctx.workdir, "data"), ignore_errors=True)


def gap_lines(got_state: dict, rows: np.ndarray, ref: RefFtrl) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit: where the
    sampled rows' gaps lie, and the worst row of each array with both
    sides' ``z`` and ``n`` there. ``Problem.sample_rows`` puts the rows of
    the 13 integer columns, which every example touches, first."""
    at = ref.index(rows)
    want = {"z": ref.z[at], "n": ref.n[at]}
    scale = {"z": np.sqrt(want["n"]), "n": None}  # as ``Problem.prefix_numbers`` measures them
    out = []
    for name in ("z", "n"):
        g = element_gaps(got_state[name], want[name], scale[name])
        w = int(np.argmax(g))
        qs = " ".join(f"p{q:g}={np.percentile(g, q):.3g}" for q in (50, 90, 99, 99.9, 99.99, 100))
        out.append(
            f"[gaps] {name}: {qs}; worst at table row {int(rows[w])} "
            f"({'an integer column' if w < criteo.N_INT else 'a categorical value'}): "
            f"z {got_state['z'][w]:.9g} for {want['z'][w]:.9g}, n {got_state['n'][w]:.9g} for {want['n'][w]:.9g}"
        )
    return out


def heldout_scores(ref: RefFtrl, held) -> tuple[float, float, np.ndarray]:
    idx, vals, y = held
    p = ref.predict(idx, vals)
    return auc(y, p), logloss(y, p), p


def auc_below_reference(ref, other, scored: dict) -> dict:
    """``<span>.auc_below_reference`` of every scored span: the reference's
    AUC there minus that of ``other``, a reference over the same universe."""
    return {
        f"{k}.auc_below_reference": heldout_scores(ref, s)[0] - heldout_scores(other, s)[0]
        for k, s in scored.items()
    }
