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

import numpy as np

from benchmark.apps import linear_ftrl as base
from benchmark.harness.checks import Check, element_gaps, norm_gap
from benchmark.harness.ref_ftrl import auc, logloss  # noqa: F401  (the kinds' scores)
from benchmark.harness.ref_wd import RefWd

StopWindow = base.StopWindow
prepare = base.prepare
heldout_scores = base.heldout_scores
auc_below_reference = base.auc_below_reference
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

    def new_reference(self, rows_universe: np.ndarray, precision: str):
        return RefWd(rows_universe, self.hyper, self.seed, self.num_keys, precision)

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
    ref, ref_losses, scored = prob.reference(plan, "float32", score=("heldout", "trained"))
    low, low_losses, _ = prob.reference(plan, precision, score=())
    rows = prob.sample_rows()
    got, want = Problem.state_of(low, rows), Problem.state_of(ref, rows)
    out = Problem.prefix_numbers(low_losses, got, want, ref_losses)
    print("\n".join(gap_lines(low_losses, ref_losses, got, want)), flush=True)
    # the lower precision's AUCs, its state carried over the float32
    # reference's universe row by row
    pos = ref.index(low.rows)
    wide = prob.new_reference(ref.rows, precision)
    wide.z[pos], wide.n[pos], wide.emb_w[pos], wide.emb_n[pos] = low.z, low.n, low.emb_w, low.emb_n
    wide.tower = low.tower
    out.update(auc_below_reference(ref, wide, scored))
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
    problem_type = Problem

    def _config(self):
        st = self.settings
        cfg = super()._config()
        cfg.app = "wide_deep"
        cfg.seed = _seed32(self.ctx.seed)
        cfg.wd.emb_dim, cfg.wd.hidden = int(st["emb_dim"]), list(st["hidden"])
        cfg.wd.emb_eta, cfg.wd.mlp_lr = st["emb_eta"], st["mlp_lr"]
        return cfg

    def _build(self) -> None:
        try:
            from parameter_server_tpu.parallel.trainer import app_from_config  # noqa: F401
        except ImportError:
            raise SystemExit(
                "this program's PodTrainer takes no app from cfg.app: it cannot run Wide&Deep"
            ) from None
        super()._build()

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

    def prefix_epoch_done(self) -> None:
        """The epoch ended in inert calls of the smallest bucket's shape: a
        second program of the step's module name, which numbers its
        fusions otherwise. ``op_scopes`` merges by module name and would
        blank most of the window's names, so the program forgets what
        ran up to here (the predict program of the held-out scoring too);
        the warm call registers the window's program."""
        from parameter_server_tpu.parallel import spmd

        spmd.forget_programs()

    def prefix_checks(self, ref: RefWd, ref_losses: np.ndarray) -> list:
        lim = self.ctx.traffic["limits"]
        want = Problem.state_of(ref, self.sample_rows)
        print("\n".join(gap_lines(self.prefix_losses, ref_losses, self.sample_state, want)), flush=True)
        got = Problem.prefix_numbers(self.prefix_losses, self.sample_state, want, ref_losses)
        return [Check(name, value, lim[name]) for name, value in got.items()]
