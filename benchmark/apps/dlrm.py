"""The ``dlrm`` app: DLRM (26 per-field tables of 128-wide rows in one key
space under SGD, a bottom MLP over the 13 dense columns, the pairwise-dot
interaction, a top MLP) built and stepped through the program's own entry,
``PodTrainer`` with ``cfg.app = "dlrm"``.

Everything the traffic kinds ask of a session is ``apps/linear_ftrl.py``'s
(the generated click logs, the files, the stamp on ``clock.finish``, the
record around ``step_fn`` / ``predict_fn``, the prefix's bookkeeping); what
differs is here: the configuration handed to the trainer, the per-field row
layout, the reference (``harness/ref_dlrm.py``), the read-back of 128-wide
rows and of the two MLPs, and the numbers compared.

The tables' sizes are the configuration's ``field_rows`` = min(cardinality,
``max_ind_range``), 14 + their sum its ``num_keys``. A copy of the
configuration with a smaller ``num_keys`` (the CPU rehearsals make one) is
a smaller budget of rows: each table is capped at an equal share of it
(``field_rows_of``).
"""

from __future__ import annotations

import importlib.util
import time

import numpy as np

from benchmark.apps import linear_ftrl as base
from benchmark.apps.wide_deep import _seed32, l2_gap, read_rows
from benchmark.harness import ref_dlrm
from benchmark.harness.checks import Check, element_gaps
from benchmark.harness.ref_dlrm import RefDlrm
from benchmark.harness.ref_ftrl import auc, logloss  # noqa: F401  (the kinds' scores)

StopWindow = base.StopWindow
heldout_scores = base.heldout_scores
auc_below_reference = base.auc_below_reference
SAMPLE_ROWS = base.SAMPLE_ROWS
TABLE = "emb.w"  # the trainer's state entry read back
HOT_TABLE_ROWS = 1024  # a table under this many rows: every row takes hundreds of gradients a batch
RESERVED = ref_dlrm.FIRST_FIELD_ROW  # rows 0..13: the pad's and the dense columns'


def field_rows_of(config: dict) -> list:
    """The 26 tables' sizes: the configuration's ``field_rows`` =
    min(cardinality, ``max_ind_range``), checked. A copy whose ``num_keys``
    holds fewer rows than that (``tests/tiny.py`` cuts every configuration
    by that one setting, which the accepted readers take the table's size
    from) caps each table at an equal share of it instead."""
    st = config["settings"]
    vocab = [int(v) for v in config["data"]["cat_vocab"]]
    budget = int(st["num_keys"]) - RESERVED
    rows = [min(v, int(st["max_ind_range"])) for v in vocab]
    if sum(rows) > budget:
        return [min(v, budget // len(vocab)) for v in vocab]
    if rows != [int(r) for r in st["field_rows"]]:
        raise ValueError("settings.field_rows is not min(data.cat_vocab, settings.max_ind_range)")
    return rows


def prepare(ctx, write: bool = True) -> dict:
    """``linear_ftrl.prepare`` (the generated click logs and their files),
    behind one look for the program's app: a program without
    ``models.dlrm`` cannot run the cell, and says so before any data or
    table is made."""
    if importlib.util.find_spec("parameter_server_tpu.models.dlrm") is None:
        raise SystemExit("this program has no parameter_server_tpu.models.dlrm: it cannot run DLRM")
    return base.prepare(ctx, write)


class Problem(base.Problem):
    """The data of one run and the plain DLRM reference over it."""

    def __init__(self, ctx, data: dict):
        st = ctx.config["settings"]
        self.ctx = ctx
        self.data_shards = int(ctx.config["mesh"]["data"])
        self.minibatch = int(st["minibatch"])
        self.steps_per_call = int(st["steps_per_call"])
        self.file_examples = self.minibatch * self.steps_per_call
        self.field_rows = field_rows_of(ctx.config)
        self.num_keys = ref_dlrm.num_rows(self.field_rows)
        self.hyper = {k: st[k] for k in ("emb_dim", "bot", "top", "eta")}
        self.seed = _seed32(ctx.seed)
        self.labels, self.ints, self.cats = data["labels"], data["ints"], data["cats"]
        self.n_train_files = int(ctx.traffic["train_files"])
        self.prefix_files = int(ctx.traffic["prefix_calls"]) * self.data_shards

    def features(self, span: slice):
        """(table rows (n, 26), dense input (n, 13)) of the span's examples."""
        return ref_dlrm.features(self.ints[span], self.cats[span], self.field_rows)

    def real_keys(self) -> float:
        """Rows a minibatch of the training files really touches, on
        average: its distinct categorical rows (the 13 dense columns' rows
        and the pad are pulled and pushed too, but hold nothing)."""
        counts = []
        for at in range(0, self.n_train_files * self.file_examples, self.minibatch):
            counts.append(len(np.unique(self.features(slice(at, at + self.minibatch))[0])))
        return float(np.mean(counts))

    def sample_rows(self) -> np.ndarray:
        """Table rows read back after the prefix: rows 0..13, every row of
        the tables under ``HOT_TABLE_ROWS`` rows, and a seeded sample of
        the other rows the prefix touched; at most SAMPLE_ROWS."""
        rows, _ = self.features(slice(0, self.prefix_files * self.file_examples))
        first = ref_dlrm.field_first_rows(self.field_rows)
        hot = np.concatenate([np.arange(RESERVED)] + [
            np.arange(f, f + r) for f, r in zip(first, self.field_rows) if r < HOT_TABLE_ROWS
        ])
        rest = np.setdiff1d(np.unique(rows), hot)
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        take = min(len(rest), SAMPLE_ROWS - len(hot))
        return np.concatenate([hot, np.sort(rng.choice(rest, take, replace=False))])

    def early_rows(self) -> np.ndarray:
        """Table rows that the prefix's FIRST microstep touched and no
        later one: their change since the start is ``eta`` times one
        gradient taken at the starting state, which both sides hold to the
        bit, so the two sides' arithmetic is all that can part them. The
        rest of the prefix's rows and the MLPs carry eight microsteps of a
        training that amplifies a last-bit difference several times a
        microstep (``PERF.md`` section 2)."""
        fe, mb = self.file_examples, self.minibatch
        rows, _ = self.features(slice(0, self.prefix_files * fe))
        first = np.zeros(len(rows), bool)
        for f in range(self.data_shards):  # the first call's files: one a worker
            first[f * fe : f * fe + mb] = True
        return np.setdiff1d(np.unique(rows[first]), np.unique(rows[~first]))

    def is_hot(self, rows: np.ndarray) -> np.ndarray:
        """Which of table rows ``rows`` lie in a table under ``HOT_TABLE_ROWS`` rows."""
        first = ref_dlrm.field_first_rows(self.field_rows)
        field = np.searchsorted(first, rows, side="right") - 1
        return (field >= 0) & (np.asarray(self.field_rows)[np.maximum(field, 0)] < HOT_TABLE_ROWS)

    def new_reference(self, rows_universe: np.ndarray, precision: str):
        return RefDlrm(rows_universe, self.hyper, self.seed, self.field_rows, precision)

    def reference(self, assignment: list, precision: str = "float32", score: tuple = ("heldout",)):
        """The plain reference after the prefix's steps, its per-step
        losses, and {name: (row positions (n, 26), dense input, labels)} of
        the spans named in ``score``, which its row universe then holds
        beside the rows ``sample_rows`` reads back."""
        named = self.score_spans()
        spans = [slice(0, self.prefix_files * self.file_examples)] + [named[k] for k in score]
        feats = [self.features(s) for s in spans]
        ref = self.new_reference(
            np.concatenate([self.sample_rows()] + [f[0].ravel() for f in feats]), precision
        )
        idx, x = ref.index(feats[0][0]), feats[0][1]
        losses = []
        for per_worker in assignment:
            for k in range(self.steps_per_call):
                batches = []
                for f in per_worker:
                    lo = f * self.file_examples + k * self.minibatch
                    sl = slice(lo, lo + self.minibatch)
                    batches.append((idx[sl], x[sl], self.labels[sl]))
                losses.append(ref.step(batches))
        scored = {
            k: (ref.index(f[0]), f[1], self.labels[s]) for k, f, s in zip(score, feats[1:], spans[1:])
        }
        return ref, np.asarray(losses), scored

    def state_of(self, ref: RefDlrm, rows) -> dict:
        """The reference's state at table rows ``rows`` and where those rows
        started, its MLPs as one vector and where they started."""
        at = ref.index(rows)
        return {"emb.w": ref.w[at], "emb.w0": ref.w0[at], "mlp": ref.mlp_flat(), "mlp0": ref.mlp_flat_start()}

    def prefix_numbers(self, got_losses, got: dict, want: dict, ref_losses, rows) -> dict:
        """The prefix's compared numbers, ``got`` (the program's read-back,
        or a control's state) against ``want`` (``state_of`` the float32
        reference), at table rows ``rows``. The worst relative gap of the
        losses; of the rows of the small tables (``hot``: hundreds to
        thousands of gradients a row and batch) and of the other sampled
        rows apart, the gap that half and 99% of their elements stay under
        and the worst, and the distance between the two sides' CHANGE since
        the start over the size of the reference's (a table left as it
        started reads 1), and that distance over the rows the first
        microstep alone touched (``early_rows``), where the two sides start
        from the same bits; the same of the MLPs' parameters (not the gap
        between their norms, what 2.4M differences that cancel leave over: a
        sound run read it anywhere from 2.8e-9 to 3.4e-6 at the first rate, 1e-4, the control 2.9e-6); and the elements of rows 0..13 that are not
        zero, which no push may move."""
        loss_gaps = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
        out = {"prefix.loss_gap": float(np.max(loss_gaps))}
        hot = self.is_hot(rows)
        live = rows >= RESERVED
        for name, mask in (("emb_hot", hot), ("emb", live & ~hot)):
            g, w, w0 = got["emb.w"][mask], want["emb.w"][mask], want["emb.w0"][mask]
            gaps = element_gaps(g, w)
            out[f"prefix.{name}_w_gap_q50"] = float(np.percentile(gaps, 50))
            out[f"prefix.{name}_w_gap_q99"] = float(np.percentile(gaps, 99))
            out[f"prefix.{name}_w_gap_max"] = float(gaps.max())
            out[f"prefix.{name}_step_gap"] = step_gap(g, w, w0)
        early = np.isin(rows, self.early_rows())
        out["prefix.emb_early_step_gap"] = step_gap(
            got["emb.w"][early], want["emb.w"][early], want["emb.w0"][early]
        )
        gaps = element_gaps(got["mlp"], want["mlp"])
        out["prefix.mlp_gap_q50"] = float(np.percentile(gaps, 50))
        out["prefix.mlp_gap_q90"] = float(np.percentile(gaps, 90))
        out["prefix.mlp_step_gap"] = step_gap(got["mlp"], want["mlp"], want["mlp0"])
        out["prefix.reserved_rows_moved"] = float(np.count_nonzero(got["emb.w"][~live]))
        return out


def step_gap(got, want, start) -> float:
    """||(got - start) - (want - start)|| / ||want - start||: how far the
    two sides' change since the start differs, over the size of the
    reference's change, beyond what float32 keeps of a value. Each side
    holds its own rounding of start + change, and the change is small
    beside the value (``eta`` 2.5e-5 a summed gradient against rows of 0.01
    to 0.5: about a thousand ulps): two sums that differ in their last bits
    land one ulp apart, 1e-3 of the change, which is the state's spacing
    and no arithmetic's fault. So an element's gap counts as far as it
    passes two ulps of the value. State left as it started reads 1."""
    got, want, start = (np.asarray(a, np.float32) for a in (got, want, start))
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    ulp = np.spacing(np.maximum(np.abs(got), np.abs(want)))
    excess = np.maximum(np.abs(got.astype(np.float64) - want) - 2.0 * ulp, 0.0)
    moved = float(np.linalg.norm((want - start).astype(np.float64)))
    return float(np.linalg.norm(excess)) / moved if moved > 0 else float(np.linalg.norm(excess))


def gap_lines(prob: Problem, got_losses, ref_losses, got: dict, want: dict, rows) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit: the losses'
    gaps in order, and where each compared array's elements' gaps lie."""
    rel = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
    out = ["[gaps] losses: " + " ".join(f"{g:.3g}" for g in rel)]
    hot = prob.is_hot(rows)
    parts = (("emb_hot", got["emb.w"][hot], want["emb.w"][hot]),
             ("emb", got["emb.w"][~hot & (rows >= RESERVED)], want["emb.w"][~hot & (rows >= RESERVED)]),
             ("mlp", got["mlp"], want["mlp"]))
    for name, g, w in parts:
        gaps = element_gaps(g, w)
        qs = " ".join(f"p{q:g}={np.percentile(gaps, q):.3g}" for q in (50, 90, 99, 99.9, 99.99, 100))
        out.append(f"[gaps] {name}: {qs} diff={l2_gap(g, w):.3g} over={np.mean(gaps > 1e-3):.3g} of {gaps.size}")
    return out


def control(ctx, precision: str = "bfloat16") -> dict:
    """The control: the reference in ``precision`` ("bfloat16", or
    "bfloat16_products" for the products alone: ``tests/control_dlrm.py``)
    put in the program's place, at the cell's own size. Needs no chip: the
    program is not in it."""
    prob = Problem(ctx, base.prepare(ctx, write=False))
    plan = prob.nominal_assignment()
    ref, ref_losses, scored = prob.reference(plan, "float32", score=("heldout", "trained"))
    low, low_losses, _ = prob.reference(plan, precision, score=("heldout", "trained"))
    rows = prob.sample_rows()
    got, want = prob.state_of(low, rows), prob.state_of(ref, rows)
    out = prob.prefix_numbers(low_losses, got, want, ref_losses, rows)
    print("\n".join(gap_lines(prob, low_losses, ref_losses, got, want, rows)), flush=True)
    out.update(auc_below_reference(ref, low, scored))  # one universe: both hold the scored spans
    return out


class Session(base.Session):
    problem_type = Problem

    def _config(self):
        """The program's configuration for this cell's settings."""
        from parameter_server_tpu.models import dlrm
        from parameter_server_tpu.utils.config import PSConfig

        st = self.settings
        cfg = PSConfig()
        cfg.seed = _seed32(self.ctx.seed)
        cfg.dlrm.emb_dim, cfg.dlrm.eta = int(st["emb_dim"]), float(st["eta"])
        cfg.dlrm.bot, cfg.dlrm.top = list(st["bot"]), list(st["top"])
        cfg.dlrm.field_rows = list(self.problem.field_rows)
        cfg.data.pipeline_depth = int(st["pipeline_depth"])
        cfg.data.bucket_nnz = bool(st["bucket_nnz"])
        cfg.data.max_nnz_per_example = int(st["max_nnz_per_example"])
        cfg.solver.minibatch = self.minibatch
        cfg.solver.steps_per_call = self.steps_per_call
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.epochs = 1
        cfg.parallel.data_shards = self.data_shards
        cfg.parallel.kv_shards = self.kv_shards
        cfg.parallel.push_mode = st["push_mode"]
        return dlrm.pod_config(cfg)

    def _build(self) -> None:
        super()._build()
        # the ``train`` kind's facts carry the bucket's key slots, not the
        # keys: the reader of ``store.dlrm_hbm_share`` finds the count here,
        # on the configuration the traced run's record carries
        self.ctx.config["counted"] = {"real_keys": self.problem.real_keys()}

    def measure_build_rate(self) -> float:
        """Parse + BatchBuilder on one stream, one file, examples/s, in the
        per-field layout the trainer reads. Also builds ``libpsdata.so`` in
        a fresh checkout and reads the file once."""
        from parameter_server_tpu.data.batch import training_builder
        from parameter_server_tpu.data.reader import MinibatchReader, ingest_of

        fmt, key_mode = ingest_of(self.cfg)
        t0 = time.perf_counter()
        n = sum(
            b.num_examples
            for b in MinibatchReader([self.train_paths[0]], fmt, training_builder(self.cfg, key_mode))
        )
        return n / (time.perf_counter() - t0)

    def read_state(self, rows) -> dict:
        """Rows ``rows`` of the table off the device(s), and the MLPs'
        parameters as one vector in the reference's order."""
        state = self.trainer.state
        got = read_rows({TABLE: state[TABLE]}, rows, SAMPLE_ROWS)
        st = self.settings
        got["mlp"] = np.concatenate([
            np.asarray(state[f"mlp.{name}.{i}.{p}"]).ravel()
            for name in ("bot", "top") for i in range(len(st[name])) for p in ("W", "b")
        ])
        return got

    def prefix_epoch_done(self) -> None:
        """As Wide&Deep's: the epoch's inert calls and the predict program
        of the held-out scoring are forgotten, so that ``op_scopes`` reads
        the window's program alone."""
        from parameter_server_tpu.parallel import spmd

        spmd.forget_programs()

    def prefix_checks(self, ref: RefDlrm, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        prob, rows = self.problem, self.sample_rows
        want = prob.state_of(ref, rows)
        print("\n".join(gap_lines(prob, self.prefix_losses, ref_losses, self.sample_state, want, rows)), flush=True)
        got = prob.prefix_numbers(self.prefix_losses, self.sample_state, want, ref_losses, rows)
        return [Check(name, value, lim[name]) for name, value in got.items()]
