"""The ``sgns`` app: skip-gram with negative sampling at 300 dimensions under
plain SGD over one key-addressed table (the input vectors' rows, then the
output vectors'), built and stepped through the program's own entry,
``PodTrainer`` with ``cfg.app = "word2vec"`` and ``centre context
negatives...`` files.

Everything the traffic kinds ask of a session is ``apps/linear_ftrl.py``'s
(the files' cycling, the stamp on ``clock.finish``, the record around
``step_fn`` / ``predict_fn``, the prefix's bookkeeping); what differs is
here: the data (``harness/sgns_pairs.py``), the configuration handed to the
trainer, the reference (``harness/ref_sgns.py``), the read-back of 300-wide
rows, the numbers compared, and the mean negative-sampling loss a pair
where the CTR apps have AUC and matrix factorization RMSE.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from benchmark.apps import linear_ftrl as base
from benchmark.apps.wide_deep import _seed32, l2_gap
from benchmark.harness import sgns_pairs
from benchmark.harness.checks import Check, element_gaps
from benchmark.harness.ref_sgns import RefSgns, parse_examples

StopWindow = base.StopWindow
SAMPLE_ROWS = base.SAMPLE_ROWS
TABLE = "sgns.w"  # the trainer's state entry read back
PARTS = ("in", "out")  # compared apart: a hot output row takes hundreds of gradients a batch, an input row a handful


try:  # a program whose PodTrainer has no skip-gram app cannot run the cell:
    # say so as the app is loaded, before any data is made or a chip is looked for
    from parameter_server_tpu.models.word2vec import pod_config
except ImportError:
    raise SystemExit(
        "this program's PodTrainer knows no app word2vec (models.word2vec.pod_config "
        "is missing): it cannot run the cell"
    ) from None


def prepare(ctx, write: bool = True) -> dict:
    """Make the cell's examples from the seed and write its files: NumPy
    and the file system only, so ``run.py`` does it while the TPU runtime
    starts. One file holds ``steps_per_call x minibatch`` pairs with their
    negatives drawn, one device call's worth for one worker. ``write=False``
    (the control, which runs no program) makes the arrays alone."""
    st, t = ctx.config["settings"], ctx.traffic
    per_file = int(st["minibatch"]) * int(st["steps_per_call"])
    data_dir = os.path.join(ctx.workdir, "data", "base")
    if write:
        shutil.rmtree(os.path.join(ctx.workdir, "data"), ignore_errors=True)
        os.makedirs(data_dir)
    paths, parts = [], []
    for i in range(int(t["train_files"]) + int(t["heldout_files"])):
        parts.append(sgns_pairs.make_pairs(
            ctx.seed, per_file, ctx.config["data"], int(st["vocab_size"]), int(st["window"]),
            int(st["negatives"]), part=i,
        ))
        paths.append(os.path.join(data_dir, f"part-{i:03d}.txt"))
        if write:
            sgns_pairs.write_text(paths[-1], *parts[-1])
    centres, contexts, negatives = (np.concatenate(x) for x in zip(*parts))
    ctx.stage(f"{len(paths)} files of {per_file} pairs made" + (" and written" if write else ""))
    return {"paths": paths, "centres": centres, "outputs": np.column_stack([contexts, negatives])}


class Problem(base.Problem):
    """The data of one run and the plain reference over it: no program."""

    def __init__(self, ctx, data: dict):
        st = ctx.config["settings"]
        self.ctx = ctx
        self.data_shards = int(ctx.config["mesh"]["data"])
        self.minibatch = int(st["minibatch"])
        self.steps_per_call = int(st["steps_per_call"])
        self.file_examples = self.minibatch * self.steps_per_call
        self.vocab_size, self.dim = int(st["vocab_size"]), int(st["dim"])
        self.num_keys = 1 + 2 * self.vocab_size  # the pad row, the input vectors, the output vectors
        self.hyper = {k: st[k] for k in ("dim", "eta")}
        self.seed = _seed32(ctx.seed)
        self.centres, self.outputs = data["centres"], data["outputs"]
        self.n_train_files = int(ctx.traffic["train_files"])
        self.prefix_files = int(ctx.traffic["prefix_calls"]) * self.data_shards

    @property
    def labels(self) -> np.ndarray:
        """An example has no label of its own: the format reads 1.0."""
        return np.ones(len(self.centres), np.float32)

    def load_files(self, paths: list) -> None:
        """Take the examples from the reference's own parse of the files the
        program read, in place of the arrays they were written from."""
        self.centres, self.outputs = (np.concatenate(x) for x in zip(*map(parse_examples, paths)))

    def rows_of(self, span: slice):
        """(input rows (n,), output rows (n, 1 + k)) of the span's examples."""
        return sgns_pairs.table_rows(self.centres[span], self.outputs[span], self.vocab_size)

    def real_keys(self) -> float:
        """Keys a minibatch of the training files holds, on average: its
        distinct centres plus its distinct contexts and negatives (the pad
        slot is none)."""
        counts = []
        for at in range(0, self.n_train_files * self.file_examples, self.minibatch):
            in_rows, out_rows = self.rows_of(slice(at, at + self.minibatch))
            counts.append(len(np.unique(in_rows)) + len(np.unique(out_rows)))
        return float(np.mean(counts))

    def sample_rows(self) -> np.ndarray:
        """Table rows read back after the prefix, the input vectors' first:
        every input row the prefix touched, and a seeded sample of the
        output rows it touched; at most SAMPLE_ROWS."""
        in_rows, out_rows = self.rows_of(slice(0, self.prefix_files * self.file_examples))
        hot, rest = np.unique(in_rows)[: SAMPLE_ROWS // 2], np.unique(out_rows)
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        return np.concatenate([hot, rng.choice(rest, min(len(rest), SAMPLE_ROWS - len(hot)), replace=False)])

    def new_reference(self, rows_universe: np.ndarray, precision: str):
        return RefSgns(rows_universe, self.hyper, self.seed, self.vocab_size, precision)

    def reference(self, assignment: list, precision: str = "float32", score: tuple = ("heldout",)):
        """The plain reference after the prefix's steps, its per-step
        losses, and {name: (input positions, output positions)} of the
        spans named in ``score``, which its row universe then holds."""
        named = self.score_spans()
        spans = [slice(0, self.prefix_files * self.file_examples)] + [named[k] for k in score]
        rows = [self.rows_of(s) for s in spans]
        ref = self.new_reference(np.concatenate([np.concatenate([r[0], r[1].ravel()]) for r in rows]), precision)
        in_at, out_at = (ref.index(r) for r in rows[0])
        losses = []
        for per_worker in assignment:
            for k in range(self.steps_per_call):
                batches = []
                for f in per_worker:
                    lo = f * self.file_examples + k * self.minibatch
                    sl = slice(lo, lo + self.minibatch)
                    batches.append((in_at[sl], out_at[sl]))
                losses.append(ref.step(batches))
        scored = {k: (ref.index(r[0]), ref.index(r[1])) for k, r in zip(score, rows[1:])}
        return ref, np.asarray(losses), scored

    def prefix_numbers(self, got_losses, got: np.ndarray, rows: np.ndarray, ref: RefSgns, ref_losses) -> dict:
        """The prefix's compared numbers: the worst relative gap of the 8
        losses; of the input rows and of the output rows read back, each
        over all their lanes, the gap that half and 99% of the elements
        stay under and the worst one (plain SGD has no step that jumps),
        and the distance between the two sides' CHANGE since the start over
        the size of the reference's: a table left as it was reads 1 there
        (an output row starts at zero, so its change is the row)."""
        at = ref.index(rows)
        want, start = ref.w[at], ref.w0[at]
        out = {"prefix.loss_gap": float(np.max(np.abs(got_losses - ref_losses) / np.abs(ref_losses)))}
        is_in = rows <= self.vocab_size
        for part, mine in zip(PARTS, (is_in, ~is_in)):
            gaps = element_gaps(got[mine], want[mine])
            for q in (50, 99, 100):
                out[f"prefix.{part}_w_gap_{'max' if q == 100 else f'q{q}'}"] = float(np.percentile(gaps, q))
            out[f"prefix.{part}_step_gap"] = l2_gap(got[mine] - start[mine], want[mine] - start[mine])
        return out


def read_rows(arr, rows: np.ndarray, dim: int, block: int) -> np.ndarray:
    """Rows ``rows`` of a (num_rows, stride) jax array, range-sharded over
    ``kv`` or not, as float32 (len(rows), dim): each shard is asked for its
    own rows on its own device at one fixed shape, whole stored rows (the
    program keeps 300-lane rows in whole 128-lane tiles, which the chip
    gathers where they lie), cut to the ``dim`` lanes that are the row."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda v, i: jnp.take(v, i, axis=0)[:, :dim])
    rows = np.asarray(rows, np.int64)
    out = np.zeros((len(rows), dim), np.float32)
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


def gap_lines(got_losses, ref_losses, numbers: dict) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit."""
    rel = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
    return ["[gaps] losses: " + " ".join(f"{g:.3g}" for g in rel)] + [
        f"[gaps] {name}: {value:.4g}" for name, value in numbers.items()
    ]


def mean_loss(ref: RefSgns, held) -> float:
    """Mean negative-sampling loss a pair of the reference over a scored span."""
    return float(np.mean(ref.example_loss(*held)))


def loss_above_reference(ref, other, scored: dict) -> dict:
    """``<span>.loss_above_reference`` of every scored span: the mean loss
    of ``other``, a reference over the same universe, minus the reference's."""
    return {f"{k}.loss_above_reference": mean_loss(other, s) - mean_loss(ref, s) for k, s in scored.items()}


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
    out.update(loss_above_reference(ref, wide, scored))
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
        cfg.w2v.vocab_size, cfg.w2v.dim = p.vocab_size, p.dim
        cfg.w2v.window, cfg.w2v.negatives = int(st["window"]), int(st["negatives"])
        cfg.w2v.eta = st["eta"]
        cfg.w2v.batch_size = self.minibatch
        cfg.data.pipeline_depth = int(st["pipeline_depth"])
        cfg.data.bucket_nnz = bool(st["bucket_nnz"])
        cfg.solver.steps_per_call = self.steps_per_call
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.epochs = 1
        cfg.parallel.data_shards = self.data_shards
        cfg.parallel.kv_shards = self.kv_shards
        cfg.parallel.push_mode = st["push_mode"]
        return pod_config(cfg)  # sgns files, the key space's size, 2 + k entries an example

    def measure_build_rate(self) -> float:
        """Parse + BatchBuilder on one stream, one file, pairs/s. Also
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
        """Rows ``rows`` of the table off the device(s): (len(rows), dim)."""
        return read_rows(self.trainer.state[TABLE], rows, self.problem.dim, SAMPLE_ROWS)

    def prefix(self, score_heldout: bool = False) -> None:
        """The linear app's prefix; the held-out files are scored by their
        mean loss a pair, at the state the reference will have had the
        training of (those seconds are the harness's own checking:
        ``ctx.excluded_s``)."""
        super().prefix(score_heldout=False)
        if score_heldout:
            t = time.perf_counter()
            self.heldout_loss = float(self.evaluate(self.heldout_paths)["sgns_loss"])
            self.ctx.excluded_s += time.perf_counter() - t
            self.ctx.stage("held-out files scored at the prefix's state (not set-up)")
            self.prefix_epoch_done()

    def prefix_epoch_done(self) -> None:
        """As Wide&Deep's: the epoch ended in inert calls of the smallest
        bucket's shape, a second program of the step's module name whose
        names ``op_scopes`` would merge with the window's."""
        from parameter_server_tpu.parallel import spmd

        spmd.forget_programs()

    def worker_files(self) -> list:
        """The files carry no labels to tell them by: the streams take them
        in list order, one worker."""
        if self.data_shards != 1:
            raise RuntimeError("the sgns app tells a worker's files by list order: one data shard")
        return self.problem.nominal_assignment()

    def reference(self, precision: str = "float32", score: tuple = ("heldout",)):
        self.problem.load_files(self.train_paths + self.heldout_paths)
        return self.problem.reference(self.worker_files(), precision, score)

    def prefix_checks(self, ref: RefSgns, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        got = self.problem.prefix_numbers(self.prefix_losses, self.sample_state, self.sample_rows, ref, ref_losses)
        print("\n".join(gap_lines(self.prefix_losses, ref_losses, got)), flush=True)
        return [Check(name, value, lim[name]) for name, value in got.items()]
