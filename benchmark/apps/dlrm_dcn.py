"""The ``dlrm_dcn`` app: DLRM-DCNv2 on multi-hot click logs (26 per-field
tables of 128-wide rows in one key space under AdaGrad, every id a fixed
bag of 1 to 100 rows summed a field, a bottom MLP over the 13 dense
columns, the low-rank cross network, a top MLP) built and stepped through
the program's own entry, ``PodTrainer`` with ``cfg.app = "dlrm"`` and the
multi-hot ``[dlrm]`` settings.

Everything the traffic kinds ask of a session is ``apps/linear_ftrl.py``'s,
and the configuration, the table sizes and the step gap are
``apps/dlrm.py``'s; what differs is here: the bags (the reference's own,
``harness/ref_dlrm_dcn.py``), the reference's dense table with its
accumulators, the read-back of ``w`` and ``n``, and the numbers compared.

**Which numbers hold the program to float32: the first microstep's.**
AdaGrad's first step on an element is ``eta g / (|g| + eps)``: ``eta`` times
the SIGN of the gradient, whatever its size; what keeps the size is ``n =
g^2``. Sixteen million dense parameters and every touched row move by
``eta`` a microstep, so an element whose gradient is a sum that all but
cancels lands ``2 eta`` apart in two float32 evaluations, the next
microstep's gradients differ for it, and by the prefix's fourth microstep a
sound run's state has parted from the reference's by tenths of its change
(at 1,024 examples a microstep on the CPU: ``emb_step_gap`` 0.03 to 0.24,
``loss_gap`` up to 5e-3), as far as the bfloat16 controls part. So the
precision checks are the numbers of the FIRST microstep, where both sides
start from the same bits: its loss (``prefix.early_loss_gap``) and, of the
rows it alone touched, ``n``'s relative distance and ``w``'s step gap
(``prefix.emb_early_n_gap``, ``prefix.emb_early_step_gap``). The numbers
after the whole prefix keep limits over what a sound run reads: they catch a
push that is wrong (a sign, a rule, a row: 1 and more), not a precision.
``w``'s worst element is not compared at all.
"""

from __future__ import annotations

import numpy as np

from benchmark.apps import dlrm as one_hot
from benchmark.apps import linear_ftrl as base
from benchmark.apps.wide_deep import _seed32, l2_gap, read_rows
from benchmark.harness import ref_dlrm_dcn as ref_dcn
from benchmark.harness.checks import Check, element_gaps
from benchmark.harness.ref_dlrm_dcn import RefDcn
from benchmark.harness.ref_ftrl import auc, logloss  # noqa: F401  (the kinds' scores)

StopWindow = base.StopWindow
heldout_scores = base.heldout_scores
auc_below_reference = base.auc_below_reference
step_gap = one_hot.step_gap
field_rows_of = one_hot.field_rows_of
SAMPLE_ROWS = base.SAMPLE_ROWS
HOT_TABLE_ROWS = one_hot.HOT_TABLE_ROWS
RESERVED = one_hot.RESERVED
UNTOUCHED_ROWS = 1 << 14  # of the sample: rows no bag of the prefix names, whose n must stay zero
# The largest ``n`` a row can hold that only saturated examples touched. A label-1 example whose
# logit passed about 15 has a float32 gradient of exactly 0 in the program (``softplus(x)`` rounds
# to ``x``) and of 6e-8 x the chain, or 0, in the reference (``sigmoid`` rounds to 1 - 6e-8 up to
# 17.3), and a gradient under 1e-19 squares to a denormal that NumPy keeps and the chip flushes:
# below this, whether ``n`` is zero is rounding's to say. A row any live example trained holds 1e-8
# and more.
SOLID_N = 1e-12
SETTINGS = ("hot", "cross_layers", "cross_rank", "updater", "eps")  # what [dlrm] must know
HYPER = ("emb_dim", "bot", "top", "cross_layers", "cross_rank", "eta", "eps")


def prepare(ctx, write: bool = True) -> dict:
    """``linear_ftrl.prepare`` (the generated one-hot click logs and their
    files), behind one look at the program's ``[dlrm]`` settings: a program
    that does not know the multi-hot form cannot run the cell, and says so
    before any data or table is made."""
    try:
        from parameter_server_tpu.utils.config import DLRMConfig
    except ImportError:
        raise SystemExit("this program has no [dlrm] settings: it cannot run DLRM-DCNv2") from None
    missing = [k for k in SETTINGS if not hasattr(DLRMConfig(), k)]
    if missing:
        raise SystemExit(
            f"this program's [dlrm] settings have no {', '.join(missing)}: it cannot run the "
            "multi-hot DLRM-DCNv2 (bags of ids a field, the cross network, AdaGrad)"
        )
    return base.prepare(ctx, write)


class Problem(one_hot.Problem):
    """The data of one run and the plain DLRM-DCNv2 reference over it."""

    def __init__(self, ctx, data: dict):
        super().__init__(ctx, data)
        st = ctx.config["settings"]
        self.hyper = {k: st[k] for k in HYPER}
        self.hot = [int(h) for h in st["hot"]]
        self.bag_seed = int(st["bag_seed"])
        self._prefix = None  # (bags, dense input) of the prefix's examples, made once

    def features(self, span: slice):
        """(bags: 26 arrays (n, h_f) of table rows, dense input (n, 13))."""
        return ref_dcn.features(self.ints[span], self.cats[span], self.field_rows, self.hot, self.bag_seed)

    def real_keys(self) -> float:
        """Rows a minibatch of the training files really touches, on
        average: the distinct rows of its 26 x 8,192 bags."""
        counts = []
        for at in range(0, self.n_train_files * self.file_examples, self.minibatch):
            counts.append(len(ref_dcn.rows_of(self.features(slice(at, at + self.minibatch))[0])))
        return float(np.mean(counts))

    def prefix_features(self):
        """``features`` of the prefix's examples, and every table row their
        bags name, ascending."""
        if self._prefix is None:
            bags, x = self.features(slice(0, self.prefix_files * self.file_examples))
            self._prefix = (bags, x, ref_dcn.rows_of(bags))
        return self._prefix

    def prefix_rows(self) -> np.ndarray:
        return self.prefix_features()[2]

    def sample_rows(self) -> np.ndarray:
        """Table rows read back after the prefix: rows 0..13, every row of
        the tables under ``HOT_TABLE_ROWS`` rows, ``UNTOUCHED_ROWS`` seeded
        rows that no bag of the prefix names, and a seeded sample of the
        rows they do name; at most SAMPLE_ROWS."""
        first = ref_dcn.field_first_rows(self.field_rows)
        hot = np.concatenate([np.arange(RESERVED)] + [
            np.arange(f, f + r) for f, r in zip(first, self.field_rows) if r < HOT_TABLE_ROWS
        ])
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        named = np.setdiff1d(self.prefix_rows(), hot)
        rest = np.setdiff1d(np.arange(RESERVED, self.num_keys), np.concatenate([hot, named]))
        idle = np.sort(rng.choice(rest, min(len(rest), UNTOUCHED_ROWS), replace=False))
        take = min(len(named), SAMPLE_ROWS - len(hot) - len(idle))
        return np.concatenate([hot, idle, np.sort(rng.choice(named, take, replace=False))])

    def early_rows(self) -> np.ndarray:
        """Table rows that the prefix's FIRST microstep touched and no
        later one (``apps/dlrm.py``): one gradient from a start both sides
        hold to the bit."""
        fe, mb = self.file_examples, self.minibatch
        bags = self.prefix_features()[0]
        first = np.zeros(len(bags[0]), bool)
        for f in range(self.data_shards):
            first[f * fe : f * fe + mb] = True
        return np.setdiff1d(
            ref_dcn.rows_of([b[first] for b in bags]), ref_dcn.rows_of([b[~first] for b in bags])
        )

    def new_reference(self, precision: str):
        return RefDcn(self.hyper, self.seed, self.field_rows, precision)

    def reference(self, assignment: list, precision: str = "float32", score: tuple = ("heldout",)):
        """The plain reference after the prefix's steps, its per-step
        losses, and {name: (bags, dense input, labels)} of the spans named
        in ``score``."""
        named = self.score_spans()
        bags, x, _ = self.prefix_features()
        ref = self.new_reference(precision)
        losses = []
        for per_worker in assignment:
            for k in range(self.steps_per_call):
                batches = []
                for f in per_worker:
                    lo = f * self.file_examples + k * self.minibatch
                    sl = slice(lo, lo + self.minibatch)
                    batches.append((ref_dcn.cut(bags, sl), x[sl], self.labels[sl]))
                losses.append(ref.step(batches))
        scored = {k: (*self.features(named[k]), self.labels[named[k]]) for k in score}
        return ref, np.asarray(losses), scored

    def state_of(self, ref: RefDcn, rows) -> dict:
        """The reference's state at table rows ``rows`` and where those rows
        started, its dense parameters as one vector and where they started."""
        return {
            "emb.w": ref.w[rows], "emb.n": ref.n[rows], "emb.w0": ref.start_rows(rows),
            "mlp": ref.dense_flat(), "mlp0": ref.dense_flat_start(),
        }

    def prefix_numbers(self, got_losses, got: dict, want: dict, ref_losses, rows) -> dict:
        """The prefix's compared numbers, ``got`` (the program's read-back,
        or a control's state) against ``want`` (``state_of`` the float32
        reference) at table rows ``rows``. The first microstep's loss;
        ``dlrm1tb.train``'s list (the worst loss; of the small tables' rows and of the other rows the
        prefix's bags name apart, the gap that half and 99% of ``w``'s
        elements stay under and the distance between the two sides' CHANGE
        since the start over the size of the reference's; the same of the
        dense parameters; rows 0..13 never moved), less ``w``'s worst
        element (the module's docstring), and beside it: ``n``'s gaps (half,
        99%, the worst) over every named row sampled; ``w``'s step gap and
        ``n``'s relative distance over the rows the first microstep alone
        touched; and the bags, exactly: a sampled row that a bag of the
        prefix names and whose ``n`` is still zero (where the reference's
        passes ``SOLID_N``) is missed, a sampled row that none names
        and whose ``n`` left zero is extra."""
        loss_gaps = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
        # the first microstep's loss is taken at the start both sides share
        out = {"prefix.early_loss_gap": float(loss_gaps[0]), "prefix.loss_gap": float(np.max(loss_gaps))}
        hot = self.is_hot(rows)
        live = rows >= RESERVED
        named = np.isin(rows, self.prefix_rows())
        for name, mask in (("emb_hot", hot & named), ("emb", named & ~hot)):
            g, w, w0 = got["emb.w"][mask], want["emb.w"][mask], want["emb.w0"][mask]
            gaps = element_gaps(g, w)
            out[f"prefix.{name}_w_gap_q50"] = float(np.percentile(gaps, 50))
            out[f"prefix.{name}_w_gap_q99"] = float(np.percentile(gaps, 99))
            out[f"prefix.{name}_step_gap"] = step_gap(g, w, w0)
        gaps = element_gaps(got["emb.n"][named], want["emb.n"][named])
        out["prefix.emb_n_gap_q50"] = float(np.percentile(gaps, 50))
        out["prefix.emb_n_gap_q99"] = float(np.percentile(gaps, 99))
        out["prefix.emb_n_gap_max"] = float(gaps.max())
        early = np.isin(rows, self.early_rows())
        out["prefix.emb_early_step_gap"] = step_gap(
            got["emb.w"][early], want["emb.w"][early], want["emb.w0"][early]
        )
        out["prefix.emb_early_n_gap"] = l2_gap(got["emb.n"][early], want["emb.n"][early])
        gaps = element_gaps(got["mlp"], want["mlp"])
        out["prefix.mlp_gap_q50"] = float(np.percentile(gaps, 50))
        out["prefix.mlp_gap_q90"] = float(np.percentile(gaps, 90))
        out["prefix.mlp_step_gap"] = step_gap(got["mlp"], want["mlp"], want["mlp0"])
        out["prefix.reserved_rows_moved"] = float(
            np.count_nonzero(got["emb.w"][~live]) + np.count_nonzero(got["emb.n"][~live])
        )
        touched = np.asarray(got["emb.n"]).any(axis=1)
        solid = np.asarray(want["emb.n"]).max(axis=1) > SOLID_N
        out["prefix.bag_rows_missed"] = float(np.count_nonzero(named & solid & ~touched))
        out["prefix.bag_rows_extra"] = float(np.count_nonzero(live & ~named & touched))
        return out


def gap_lines(prob: Problem, got_losses, ref_losses, got: dict, want: dict, rows) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit."""
    rel = np.abs(got_losses - ref_losses) / np.abs(ref_losses)
    out = ["[gaps] losses: " + " ".join(f"{g:.3g}" for g in rel)]
    named = np.isin(rows, prob.prefix_rows())
    parts = (("emb.w", got["emb.w"][named], want["emb.w"][named]),
             ("emb.n", got["emb.n"][named], want["emb.n"][named]),
             ("mlp", got["mlp"], want["mlp"]))
    for name, g, w in parts:
        gaps = element_gaps(g, w)
        qs = " ".join(f"p{q:g}={np.percentile(gaps, q):.3g}" for q in (50, 90, 99, 99.9, 99.99, 100))
        out.append(f"[gaps] {name}: {qs} diff={l2_gap(g, w):.3g} over={np.mean(gaps > 1e-3):.3g} of {gaps.size}")
    return out


def control(ctx, precision: str = "bfloat16") -> dict:
    """The control: the reference in ``precision`` ("bfloat16", or
    "bfloat16_products" for the products alone) put in the program's
    place, at the cell's own size. Needs no chip: the program is not in it.
    One reference at a time: each holds the whole table twice."""
    prob = Problem(ctx, base.prepare(ctx, write=False))
    plan = prob.nominal_assignment()
    rows = prob.sample_rows()
    ref, ref_losses, scored = prob.reference(plan, "float32", score=("heldout", "trained"))
    want = prob.state_of(ref, rows)
    ref_auc = {k: heldout_scores(ref, s)[0] for k, s in scored.items()}
    del ref
    low, low_losses, _ = prob.reference(plan, precision, score=())
    got = prob.state_of(low, rows)
    out = prob.prefix_numbers(low_losses, got, want, ref_losses, rows)
    print("\n".join(gap_lines(prob, low_losses, ref_losses, got, want, rows)), flush=True)
    out.update({f"{k}.auc_below_reference": ref_auc[k] - heldout_scores(low, s)[0] for k, s in scored.items()})
    return out


class Session(one_hot.Session):
    problem_type = Problem

    def _config(self):
        """``apps/dlrm.py``'s configuration with the multi-hot settings."""
        from parameter_server_tpu.models import dlrm

        st = self.settings
        if int(st["bag_seed"]) != dlrm.BAG_SEED:
            raise ValueError(
                f"the configuration's bag_seed {st['bag_seed']} is not the program's "
                f"models.dlrm.BAG_SEED {dlrm.BAG_SEED}: the reference's bags would not be the parser's"
            )
        cfg = super()._config()
        d = cfg.dlrm
        d.hot, d.updater, d.eps = list(st["hot"]), st["updater"], float(st["eps"])
        d.cross_layers, d.cross_rank = int(st["cross_layers"]), int(st["cross_rank"])
        return dlrm.pod_config(cfg)

    def read_state(self, rows) -> dict:
        """Rows ``rows`` of the table's ``w`` and ``n`` off the device, and
        the dense parameters as one vector in the reference's order."""
        state = self.trainer.state
        got = read_rows({"emb.w": state["emb.w"], "emb.n": state["emb.n"]}, rows, SAMPLE_ROWS)
        st = self.settings
        groups = (("bot", len(st["bot"]), "Wb"), ("cross", int(st["cross_layers"]), "VWb"), ("top", len(st["top"]), "Wb"))
        got["mlp"] = np.concatenate([
            np.asarray(state[f"mlp.{name}.{i}.{p}"]).ravel() for name, layers, leaves in groups for i in range(layers) for p in leaves
        ])
        return got

    def prefix_checks(self, ref: RefDcn, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        prob, rows = self.problem, self.sample_rows
        want = prob.state_of(ref, rows)
        print("\n".join(gap_lines(prob, self.prefix_losses, ref_losses, self.sample_state, want, rows)), flush=True)
        got = prob.prefix_numbers(self.prefix_losses, self.sample_state, want, ref_losses, rows)
        return [Check(name, value, lim[name]) for name, value in got.items()]
