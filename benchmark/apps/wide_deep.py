"""The ``wide_deep`` app: Wide&Deep (a wide FTRL part and a 16-wide AdaGrad
embedding table over one hashed key space, a ReLU tower under Adam) built
and stepped through the program's own entry, ``PodTrainer`` with
``cfg.app = "wide_deep"``.

Everything the traffic kinds ask of a session is ``apps/linear_ftrl.py``'s
(the data, the files, the stamp on ``clock.finish``, the record around
``step_fn`` / ``predict_fn``, the prefix's bookkeeping); what differs is
here: the configuration handed to the trainer, the reference
(``harness/ref_wd.py``), the read-back of 16-wide rows and of the tower,
and the numbers compared.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.apps import linear_ftrl as base
from benchmark.harness import criteo
from benchmark.harness.checks import Check, norm_gap
from benchmark.harness.ref_ftrl import auc, logloss  # noqa: F401  (the kinds' scores)
from benchmark.harness.ref_wd import RefWd

StopWindow = base.StopWindow
prepare = base.prepare
heldout_scores = base.heldout_scores
SAMPLE_ROWS = base.SAMPLE_ROWS
TABLES = ("wide.z", "wide.n", "emb.w", "emb.n")  # the trainer's state entries read back


def _seed32(seed: int) -> int:
    """The benchmark's seed as the program's ``cfg.seed`` and the
    reference's: any whole number, folded to 31 bits."""
    return int(seed) % (2**31 - 1)


class Problem(base.Problem):
    """The data of one run and the plain Wide&Deep reference over it."""

    def __init__(self, ctx, data: dict):
        super().__init__(ctx, data)
        st = ctx.config["settings"]
        self.hyper = {**self.hyper, **{k: st[k] for k in ("emb_dim", "hidden", "emb_eta", "mlp_lr")}}
        self.seed = _seed32(ctx.seed)

    def reference(self, assignment: list, precision: str = "float32", heldout: bool = True):
        """The reference after the prefix's steps, its per-step losses, and
        (idx, vals, labels) of the held-out examples."""
        spans = [slice(0, self.prefix_files * self.file_examples)]
        if heldout:
            spans.append(slice(self.n_train_files * self.file_examples, len(self.labels)))
        feats = [criteo.features(self.ints[s], self.cats[s], self.num_keys) for s in spans]
        ref = RefWd(
            np.concatenate([f[0].ravel() for f in feats]), self.hyper, self.seed, self.num_keys, precision
        )
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
        held = (ref.index(feats[1][0]), feats[1][1], self.labels[spans[1]]) if heldout else None
        return ref, np.asarray(losses), held

    @staticmethod
    def state_of(ref: RefWd, rows) -> dict:
        """The reference's state at table rows ``rows``, under the names of
        the trainer's state entries, and its tower as one vector."""
        at = ref.index(rows)
        return {
            "wide.z": ref.z[at], "wide.n": ref.n[at], "emb.w": ref.emb_w[at], "emb.n": ref.emb_n[at],
            "mlp": ref.tower_flat(),
        }

    @staticmethod
    def prefix_numbers(got_losses, got: dict, want: dict, ref_losses) -> dict:
        """The prefix's compared numbers: the worst relative gap of the
        first three losses and of all eight; of each table's sampled rows
        and of the tower, the gap that half, 90% and (``wide``) 99% of the
        elements stay under; ``emb.n``'s relative L2 distance, which the
        rows touched most weigh most in; the gap between the tower's norms.
        Every limit lies between the largest a sound run on the chip read
        and the smallest the bfloat16 control reads (``PERF.md`` section 2).

        Not compared, because a sound run and the control read the same
        there: ``emb.w`` beyond its 90th percentile and every array's worst
        element. AdaGrad's first step on a row (and Adam's on the tower) is
        eta x sign(g) whatever g's size; where g is a sum that all but
        cancels, two float32 evaluations land 2 eta = 0.1 apart at a
        starting scale of 0.05, and 1% of ``emb.w``'s elements read 0.05
        to 1.5 off on a sound run, 1.9 under the control."""
        loss_gaps = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
        out = {"prefix.early_loss_gap": float(np.max(loss_gaps[:3])), "prefix.loss_gap": float(np.max(loss_gaps))}
        for name, quantiles in (("wide.z", (50, 90, 99)), ("wide.n", (50, 90, 99)), ("emb.w", (50, 90)),
                                ("emb.n", (50,)), ("mlp", (50, 90))):
            gaps = element_gaps(got[name], want[name])
            for q in quantiles:
                out[f"prefix.{name.replace('.', '_')}_gap_q{q}"] = float(np.percentile(gaps, q))
        out["prefix.emb_n_l2_gap"] = l2_gap(got["emb.n"], want["emb.n"])
        out["prefix.mlp_norm_gap"] = norm_gap(got["mlp"], want["mlp"])
        return out


def l2_gap(got, want) -> float:
    """||got - want|| / ||want|| over all elements."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def element_gaps(got, want) -> np.ndarray:
    """|got - want| of every element against max(|want|, median |want|),
    as ``checks.worst_gap`` measures its worst."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape or not np.isfinite(got).all():
        return np.full(max(want.size, 1), np.inf)
    mag = np.abs(want)
    nz = mag[mag > 0]
    floor = float(np.median(nz)) if nz.size else 1.0
    return np.abs(got - want) / np.maximum(mag, floor)


def gap_lines(got_losses, ref_losses, got: dict, want: dict) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit: the 8 losses'
    gaps in order, and where each compared array's elements' gaps lie."""
    rel = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
    out = ["[gaps] losses: " + " ".join(f"{g:.3g}" for g in rel)]
    for name in (*TABLES, "mlp"):
        g = element_gaps(got[name], want[name])
        qs = " ".join(f"p{q:g}={np.percentile(g, q):.3g}" for q in (50, 90, 99, 99.9, 99.99, 100))
        out.append(f"[gaps] {name}: {qs} diff={l2_gap(got[name], want[name]):.3g} over={np.mean(g > 1e-2):.3g}")
    return out


def control(ctx, precision: str = "bfloat16") -> dict:
    """The control: the reference in ``precision`` put in the program's
    place, at the cell's own size. Needs no chip: the program is not in it."""
    prob = Problem(ctx, prepare(ctx, write=False))
    plan = prob.nominal_assignment()
    ref, ref_losses, held = prob.reference(plan, "float32")
    low, low_losses, _ = prob.reference(plan, precision, heldout=False)
    rows = prob.sample_rows()
    got, want = Problem.state_of(low, rows), Problem.state_of(ref, rows)
    out = Problem.prefix_numbers(low_losses, got, want, ref_losses)
    print("\n".join(gap_lines(low_losses, ref_losses, got, want)), flush=True)
    # the lower precision's held-out AUC, its state carried over the
    # float32 reference's universe row by row
    pos = ref.index(low.rows)
    wide = RefWd(ref.rows, prob.hyper, prob.seed, prob.num_keys, precision)
    wide.z[pos], wide.n[pos], wide.emb_w[pos], wide.emb_n[pos] = low.z, low.n, low.emb_w, low.emb_n
    wide.tower = low.tower
    out["heldout.auc_below_reference"] = heldout_scores(ref, held)[0] - heldout_scores(wide, held)[0]
    return out


def read_rows(state: dict, rows: np.ndarray, block: int) -> dict:
    """``state``: name -> (num_rows, vdim) jax array, range-sharded over
    ``kv`` or not. Returns name -> float32 (len(rows), vdim), each shard
    asked for its own rows on its own device at one fixed shape
    (``harness/readback.py``, for rows of any width)."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda v, i: jnp.take(v, i, axis=0))
    rows = np.asarray(rows, np.int64)
    out = {}
    for name, arr in state.items():
        out[name] = np.zeros((len(rows), arr.shape[1]), np.float32)
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
                out[name][part] = np.asarray(take(shard.data, idx))[: len(part)]
    return out


class Session(base.Session):
    def __init__(self, ctx):
        ctx.prepared = ctx.prepared or prepare(ctx)
        super().__init__(ctx)
        self.problem = Problem(ctx, ctx.prepared)

    def _build(self) -> None:
        try:
            from parameter_server_tpu.parallel.trainer import PodTrainer, app_from_config  # noqa: F401
        except ImportError:
            raise SystemExit(
                "this program's PodTrainer takes no app from cfg.app: it cannot run Wide&Deep"
            ) from None
        from parameter_server_tpu.utils.config import PSConfig

        st = self.settings
        cfg = PSConfig()
        cfg.app = "wide_deep"
        cfg.seed = _seed32(self.ctx.seed)
        cfg.data.format = "criteo"
        cfg.data.num_keys = self.num_keys
        cfg.data.pipeline_depth = int(st["pipeline_depth"])
        cfg.data.bucket_nnz = bool(st["bucket_nnz"])
        cfg.data.compact_wire = bool(st["compact_wire"])
        cfg.data.max_nnz_per_example = int(st["max_nnz_per_example"])
        cfg.solver.minibatch = self.minibatch
        cfg.solver.steps_per_call = self.steps_per_call
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.epochs = 1
        cfg.lr.alpha, cfg.lr.beta = st["alpha"], st["beta"]
        cfg.penalty.lambda_l1, cfg.penalty.lambda_l2 = st["lambda_l1"], st["lambda_l2"]
        cfg.wd.emb_dim, cfg.wd.hidden = int(st["emb_dim"]), list(st["hidden"])
        cfg.wd.emb_eta, cfg.wd.mlp_lr = st["emb_eta"], st["mlp_lr"]
        cfg.parallel.data_shards = self.data_shards
        cfg.parallel.kv_shards = self.kv_shards
        cfg.parallel.push_mode = st["push_mode"]
        self.cfg = cfg
        self.trainer = tr = PodTrainer(cfg)

        # the stamp and the records, from outside, as apps/linear_ftrl.py sets them
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
                "labels": batch["labels"] if self.keep_labels else None,
            })
            return new_state, out

        tr.step_fn = step_recorded
        predict_fn = tr.predict_fn

        def predict_recorded(state, batch):
            probs = predict_fn(state, batch)
            if self.eval_first is None:
                self.eval_first = probs
            return probs

        tr.predict_fn = predict_recorded

    def read_state(self, rows) -> dict:
        """Rows ``rows`` of the four tables off the device(s), and the
        tower's parameters as one vector in the reference's order."""
        state = self.trainer.state
        got = read_rows({k: state[k] for k in TABLES}, rows, SAMPLE_ROWS)
        got["wide.z"], got["wide.n"] = got["wide.z"][:, 0], got["wide.n"][:, 0]
        layers = len(self.settings["hidden"]) + 1
        got["mlp"] = np.concatenate(
            [np.asarray(state[f"mlp.{i}.{p}"]).ravel() for i in range(layers) for p in ("W", "b")]
        )
        return got

    def prefix(self) -> None:
        """From the fresh tables, the first ``prefix_calls`` device calls
        through the window's own call and feed; keeps what the reference is
        compared with once the window has closed."""
        n_calls = int(self.ctx.traffic["prefix_calls"])
        self.keep_labels = True
        self.ctx.stage("prefix starts")
        ran_out = self.train(self.file_list(self.prefix_files))
        self.ctx.stage("prefix epoch done")
        self.keep_labels = False
        if not ran_out:
            raise RuntimeError("the prefix epoch was stopped")
        # The epoch ended in inert calls of the smallest bucket's shape: a
        # second program of the step's module name, which numbers its
        # fusions otherwise. ``op_scopes`` merges by module name and would
        # blank most of the window's names, so the program forgets what
        # ran up to here; the warm call registers the window's program.
        from parameter_server_tpu.parallel import spmd

        spmd.forget_programs()
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

    def prefix_checks(self, ref: RefWd, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        want = Problem.state_of(ref, self.sample_rows)
        print("\n".join(gap_lines(self.prefix_losses, ref_losses, self.sample_state, want)), flush=True)
        got = Problem.prefix_numbers(self.prefix_losses, self.sample_state, want, ref_losses)
        return [Check(name, value, lim[name]) for name, value in got.items()]
