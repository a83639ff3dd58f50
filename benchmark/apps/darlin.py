"""The ``darlin`` app: the batch solver (delayed block proximal gradient with
the KKT filter, L1 logistic regression) over one worker's share of
Criteo-shaped click logs held on the chip as column blocks, built and
stepped through the program's own entries: Criteo TSV shards through
``data.blockcache.cached_column_blocks`` (the native parser, the block
cache, as ``cli convert`` goes) and ``models.darlin.Darlin`` (``begin``,
``run_calls``, ``solve``) on a 1x1 mesh.

What the benchmark takes from the program: the solver (its ``state``,
``pred``, ``max_inflight``, the hook ``on_retire`` it calls right after the
blocking read of a call's scalars, a step's and a refresh's alike), the
cache's per-block entry counts (what a call claims to have swept) and the
named timers. The reference (``harness/ref_darlin.py``) takes the raw
columns and ``criteo.features``, nothing the program made.

Size. The configuration's ``num_examples`` is the worker's share. The
rehearsals' tiny settings (``benchmark/tests/tiny.py``, ``test_controls.py``)
cut ``minibatch``, and a batch solver's minibatch is its whole share: where
``minibatch`` is given the app runs ``min(num_examples, minibatch)`` examples.
The configuration's file gives none.
"""

from __future__ import annotations

import json
import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.apps.wide_deep import _seed32
from benchmark.harness import criteo, ref_darlin
from benchmark.harness.checks import Check, element_gaps

# Examples a TSV shard, and a piece of the reference's scan. 2^16 keeps every
# temporary of the generator under glibc's 32 MB mmap ceiling, so the heap is
# reused: at 2^18 each of a few hundred 54 MB temporaries a second was mapped
# and unmapped, which the chip machine's sandboxed kernel gives back late
# (40 GiB held at 48 s, the run killed) and slowly (a third of the rate)
PART = 1 << 16
SAMPLE = 1 << 16  # examples whose pred is held to Xw at the window's close
THREADS = 8
# A block step streams each of its block's entries three times (for g, for h,
# for X_b d: a gather and a segment sum or scatter each), the filter's refresh
# once (for g alone): a refresh call's work is a third of a step call's over
# the same blocks
REFRESH_STREAMS, STEP_STREAMS = 1, 3


class StopWindow(Exception):
    """Raised from the retire hook by the traffic kind to end the solve."""


def is_refresh(record: dict) -> bool:
    """A retired call that refreshed its blocks' active set (it reports
    their active coordinates) and did not step them."""
    return "n_active" in record


try:  # a program whose batch solver does not go through the store's range pull
    # and push cannot run the cell: say so as the app is loaded, before any
    # data is made or a chip is looked for
    from parameter_server_tpu.models.darlin import Darlin
    from parameter_server_tpu.parallel.spmd import pull_range, push_range  # noqa: F401

    if not hasattr(Darlin, "run_calls"):
        raise ImportError
except ImportError:
    raise SystemExit(
        "this program's batch solver has no store-backed block calls "
        "(parallel.spmd.pull_range / models.darlin.Darlin.run_calls are missing): "
        "it cannot run the cell"
    ) from None


def size_of(ctx) -> tuple:
    """(examples, keys, blocks, blocks a call) of this run."""
    st = ctx.config["settings"]
    n = min(int(st["num_examples"]), int(st.get("minibatch", st["num_examples"])))
    return n, int(st["num_keys"]), int(st["feature_blocks"]), int(st["steps_per_call"])


def prepare(ctx, write: bool = True) -> dict:
    """The worker's examples from the seed, shard by shard on a pool of
    threads, and (``write``) their TSV files: NumPy and the file system
    only, so ``run.py`` does it while the TPU runtime starts. A directory
    of this seed and size that an earlier run on this machine finished is
    kept: its files are as they were (so the block cache beside them is
    mapped, not rebuilt) and the raw columns, which the reference works
    from, are mapped from where that run saved them. ``write=False`` (the
    control) makes the arrays alone."""
    n, num_keys, n_blocks, _ = size_of(ctx)
    root = os.path.join(ctx.workdir, "data")
    data_dir = os.path.join(root, f"s{ctx.seed}-n{n}-k{num_keys}-b{n_blocks}")
    ready = os.path.join(data_dir, "ready.json")
    bounds = [(i, lo, min(lo + PART, n)) for i, lo in enumerate(range(0, n, PART))]
    cached = False
    if write and os.path.isfile(ready):
        with open(ready) as f:
            cached = json.load(f) == {"seed": ctx.seed, "examples": n, "files": len(bounds)}
    if write and not cached:
        shutil.rmtree(root, ignore_errors=True)  # one seed's files at a time: 10 GB a seed
        os.makedirs(data_dir)
    paths = [os.path.join(data_dir, f"part-{i:03d}.tsv") for i, _, _ in bounds]
    columns = [os.path.join(data_dir, f"{k}.npy") for k in ("labels", "ints", "cats")]
    if cached:  # the raw columns as the first run left them, for the reference
        labels, ints, cats = (np.load(f, mmap_mode="r") for f in columns)
        ctx.stage(f"{n} examples in {len(paths)} shards: files of an earlier run of this seed kept")
    else:
        # each shard lands in its slice of the whole columns: no second copy
        labels = np.empty(n, np.float32)
        ints = np.empty((n, criteo.N_INT), np.int32)
        cats = np.empty((n, criteo.N_CAT), np.uint32)

        def make(part):
            i, lo, hi = part
            ex = criteo.make_examples(ctx.seed, hi - lo, ctx.config["data"], part=i)
            if write:
                criteo.write_tsv(paths[i], *ex)
            labels[lo:hi], ints[lo:hi], cats[lo:hi] = ex

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(make, bounds))
        if write:
            for f, a in zip(columns, (labels, ints, cats)):
                np.save(f, a)
            with open(ready, "w") as f:
                json.dump({"seed": ctx.seed, "examples": n, "files": len(bounds)}, f)
        ctx.stage(f"{n} examples in {len(paths)} shards made" + (" and written" if write else ""))
    return {
        "paths": paths, "labels": labels, "ints": ints, "cats": cats,
        "cache_dir": os.path.join(data_dir, "blocks"), "cached": cached,
    }


class Problem:
    """The data of one run and the plain reference over it: no program."""

    def __init__(self, ctx, data: dict):
        st = ctx.config["settings"]
        self.ctx = ctx
        self.n, self.num_keys, self.n_blocks, self.call_blocks = size_of(ctx)
        self.block_size = self.num_keys // self.n_blocks
        self.hyper = {k: st[k] for k in ("lambda_l1", "lambda_l2", "eta")}
        self.kkt = float(st["kkt_filter_threshold"])
        self.seed = _seed32(ctx.seed)
        self.labels, self.ints, self.cats = data["labels"], data["ints"], data["cats"]
        self._entries: dict = {}
        self.block_entries = None  # (n_blocks,) entries a block, by the reference's own hash

    def order(self, it: int) -> np.ndarray:
        return ref_darlin.block_order(self.seed, it, self.n_blocks)

    def prefix_blocks(self) -> np.ndarray:
        return self.order(0)[: self.call_blocks * int(self.ctx.traffic["prefix_calls"])]

    def scan(self, blocks) -> None:
        """One sweep of the raw columns through ``criteo.features``: the
        entries of ``blocks`` (local feature, example, value) and every
        block's entry count."""
        want = np.zeros(self.n_blocks, bool)
        want[np.asarray(blocks, np.int64)] = True

        def part(lo: int):
            sl = slice(lo, min(lo + PART, self.n))
            rows, vals = criteo.features(self.ints[sl], self.cats[sl], self.num_keys)
            blk = rows // self.block_size
            counts = np.bincount(blk.ravel(), minlength=self.n_blocks)
            ex = np.broadcast_to(np.arange(sl.start, sl.stop)[:, None], rows.shape)
            keep = want[blk]
            return counts, blk[keep], (rows[keep] - blk[keep] * self.block_size), ex[keep], vals[keep]

        with ThreadPoolExecutor(THREADS) as pool:
            got = list(pool.map(part, range(0, self.n, PART)))
        self.block_entries = np.sum([g[0] for g in got], axis=0)
        blk, feat, ex, vals = (np.concatenate([g[i] for g in got]) for i in (1, 2, 3, 4))
        for b in np.flatnonzero(want):
            m = blk == b
            self._entries[int(b)] = (feat[m], ex[m], vals[m])

    def entries_of(self, b: int) -> tuple:
        return self._entries[int(b)]

    def reference(self, precision: str = "float32", alphas=None, cls=ref_darlin.RefDarlin):
        """The plain reference after the prefix's block steps, and what each
        step reported. ``alphas`` (the program's) are followed and priced."""
        blocks = self.prefix_blocks()
        if not all(int(b) in self._entries for b in blocks):
            self.scan(blocks)
        ref = cls(self.labels, self.block_size, self.hyper, precision)
        steps = [
            ref.block_step(int(b), *self.entries_of(b), alpha=None if alphas is None else float(alphas[i]))
            for i, b in enumerate(blocks)
        ]
        # the filter's refresh of the prefix's blocks at the state the steps
        # left, with the threshold from the violations those steps saw
        for b in blocks:
            ref.refresh(int(b), *self.entries_of(b), self.kkt * max(ref.viol_max, 1e-12))
        return ref, steps

    def prefix_numbers(self, got_w: dict, got_pred, got_obj: float, got_active: dict, ref, steps: list) -> dict:
        """The prefix's compared numbers: of the weights of the call's
        blocks that either side moved, and of ``pred`` over all N, the gap
        99% of the elements stay under and the worst one; what the
        program's step scales cost by the reference's objective; the
        objective's gap; the share of the blocks' coordinates that the
        filter's refresh right after leaves active on one side and not on
        the other."""
        want_w = np.concatenate([ref.weights(int(b)) for b in self.prefix_blocks()])
        have_w = np.concatenate([got_w[int(b)] for b in self.prefix_blocks()])
        moved = (want_w != 0) | (have_w != 0)
        w_gaps = element_gaps(have_w[moved], want_w[moved]) if moved.any() else np.zeros(1)
        p_gaps = element_gaps(got_pred, ref.pred)
        want_obj = ref.objective()
        want_active = np.concatenate([ref.active[int(b)] for b in self.prefix_blocks()])
        have_active = np.concatenate([got_active[int(b)] for b in self.prefix_blocks()])
        return {
            "prefix.w_gap_q99": float(np.percentile(w_gaps, 99)),
            "prefix.w_gap_max": float(np.max(w_gaps)),
            "prefix.pred_gap_q99": float(np.percentile(p_gaps, 99)),
            "prefix.pred_gap_max": float(np.max(p_gaps)),
            "prefix.alpha_regret": float(max(s["regret"] for s in steps)),
            "prefix.objective_gap": abs(got_obj - want_obj) / abs(want_obj),
            "prefix.active_mismatch": float(np.mean(want_active != have_active)),
        }

    def sample_examples(self) -> np.ndarray:
        rng = np.random.default_rng([self.ctx.seed, 0x5A])
        return np.sort(rng.choice(self.n, min(SAMPLE, self.n), replace=False))

    def xw_of(self, w_table: np.ndarray, ex: np.ndarray) -> np.ndarray:
        rows, vals = criteo.features(self.ints[ex], self.cats[ex], self.num_keys)
        return ref_darlin.xw(w_table, rows, vals)


def gap_lines(numbers: dict, steps: list) -> list:
    """``[gaps]`` lines, for whoever sets or doubts a limit."""
    return [
        "[gaps] alphas: " + " ".join(f"{s['alpha']:g}/{s['own_alpha']:g}" for s in steps)
    ] + [f"[gaps] {name}: {value:.4g}" for name, value in numbers.items()]


def control(ctx, precision: str = "bfloat16", cls=None) -> dict:
    """The control: the reference in ``precision`` (or a broken one,
    ``cls``) put in the program's place, at the cell's own size. Needs no
    chip: the program is not in it."""
    prob = Problem(ctx, prepare(ctx, write=False))
    low, low_steps = prob.reference(precision, cls=cls or ref_darlin.RefDarlin)
    ref, steps = prob.reference("float32", alphas=[s["alpha"] for s in low_steps])
    got_w = {int(b): low.weights(int(b)) for b in prob.prefix_blocks()}
    got_active = {int(b): low.active.get(int(b), np.ones(prob.block_size, bool)) for b in prob.prefix_blocks()}
    out = prob.prefix_numbers(got_w, low.pred, low.objective(), got_active, ref, steps)
    # pred against Xw of the control's own table, as the window's close holds the program's
    table = np.zeros(prob.num_keys, np.float32)
    for b, w in low.w.items():
        table[b * prob.block_size : (b + 1) * prob.block_size] = w
    ex = prob.sample_examples()
    gaps = element_gaps(low.pred[ex], prob.xw_of(table, ex))
    out.update({"window.pred_gap_q99": float(np.percentile(gaps, 99)), "window.pred_gap_max": float(np.max(gaps))})
    print("\n".join(gap_lines(out, steps)), flush=True)
    return out


class Session:
    """One run's solver, from the files to the table."""

    def __init__(self, ctx):
        if ctx.prepared is None:  # a run that ``run.py`` did not start
            ctx.prepared = prepare(ctx)
        self.ctx = ctx
        self.problem = Problem(ctx, ctx.prepared)
        self.data_shards, self.kv_shards = (int(ctx.config["mesh"][k]) for k in ("data", "kv"))
        self.cfg = self._config()
        from parameter_server_tpu.data.blockcache import cached_column_blocks
        from parameter_server_tpu.parallel import make_mesh

        self.cb = cached_column_blocks(self.cfg)
        ctx.stage(
            f"column blocks {'mapped from the cache' if ctx.prepared['cached'] else 'parsed and cached'}: "
            f"{int(self.cb.entries.sum())} entries in {self.cb.feat_local.shape[0]} chunks of {self.cb.chunk_len}"
        )
        from parameter_server_tpu.utils.metrics import ProgressReporter

        self.solver = Darlin(
            self.cfg, mesh=make_mesh(self.data_shards, self.kv_shards),
            reporter=ProgressReporter(print_fn=lambda *_: None),  # no table row a pass
        )
        self.solver.begin(self.cb)
        ctx.stage("blocks on the device")
        self.on_retire = None
        self.records: list = []  # every retired call's record, the prefix's first
        self.stamps: list = []  # and the host clock right after its blocking read
        self.solver.on_retire = self._retired

    def _config(self):
        from parameter_server_tpu.utils.config import PSConfig

        st, p = self.ctx.config["settings"], self.problem
        cfg = PSConfig()
        cfg.seed = p.seed
        cfg.data.files = list(self.ctx.prepared["paths"])
        cfg.data.format = self.ctx.config["data"]["format"]
        cfg.data.num_keys = p.num_keys
        cfg.data.max_nnz_per_example = int(st["max_nnz_per_example"])
        cfg.data.cache_dir = self.ctx.prepared["cache_dir"]
        cfg.solver.algo = st["algo"]
        cfg.solver.feature_blocks = p.n_blocks
        cfg.solver.steps_per_call = p.call_blocks
        cfg.solver.max_delay = int(st["max_delay"])
        cfg.solver.kkt_filter_threshold = float(st["kkt_filter_threshold"])
        cfg.solver.epsilon = float(st["epsilon"])
        cfg.solver.block_iters = 10**6  # the window ends the solve, not a pass count
        cfg.penalty.lambda_l1, cfg.penalty.lambda_l2 = float(st["lambda_l1"]), float(st["lambda_l2"])
        cfg.lr.eta = float(st["eta"])
        cfg.parallel.data_shards, cfg.parallel.kv_shards = self.data_shards, self.kv_shards
        return cfg

    def _retired(self, rec: dict) -> None:
        self.stamps.append(time.perf_counter())
        self.records.append(rec)
        if self.on_retire is not None:
            self.on_retire(self.stamps[-1], len(self.records) - 1)

    def call_work(self) -> list:
        """Examples each retired call swept: N x (its blocks' real entries
        / all real entries), by the cache's own counts, for a call that
        steps its blocks; a third of that for one that refreshes them (one
        stream over the entries for a step's three)."""
        entries = np.asarray(self.cb.entries, np.float64)
        return [
            self.problem.n * float(entries[r["blocks"]].sum()) / float(entries.sum())
            * (REFRESH_STREAMS / STEP_STREAMS if is_refresh(r) else 1.0)
            for r in self.records
        ]

    def prefix(self) -> None:
        """The first call(s) from the fresh table, and the state right
        after: the call's blocks' weights, pred, the scales, the objective;
        then the filter's refresh of those same blocks (the calls that will
        refresh them again at the pass's end, so nothing later reads what
        this one leaves) and their active set (reading them back is the
        harness's own checking: ``excluded_s``)."""
        import jax

        p, s = self.problem, self.solver
        order = s.block_order(0)
        if not np.array_equal(order, p.order(0)):
            raise RuntimeError("the program's block order is not the seed's")
        self.prefix_calls = int(self.ctx.traffic["prefix_calls"])
        self.calls_a_pass = -(-p.n_blocks // p.call_blocks)
        recs = s.run_calls(order, 0, self.prefix_calls)
        self.ctx.stage("prefix trained")
        t = time.perf_counter()
        w = s.state["w"]
        self.prefix_w = {
            int(b): np.asarray(jax.device_get(w[int(b) * p.block_size : (int(b) + 1) * p.block_size, 0]))
            for b in p.prefix_blocks()
        }
        self.prefix_pred = np.asarray(s.pred)[: p.n]
        self.prefix_alphas = np.concatenate([r["alphas"] for r in recs])
        self.prefix_obj = recs[-1]["obj"]
        self.ctx.excluded_s += time.perf_counter() - t
        thr = p.kkt * max(max(r["viol_max"] for r in recs), 1e-12)
        s.run_calls(order, 0, self.prefix_calls, refresh_at=thr)
        self.ctx.stage("prefix's blocks refreshed")
        t = time.perf_counter()
        active = s.state["active"]
        self.prefix_active = {
            int(b): np.asarray(jax.device_get(active[int(b) * p.block_size : (int(b) + 1) * p.block_size, 0])) > 0
            for b in p.prefix_blocks()
        }
        self.ctx.excluded_s += time.perf_counter() - t
        self.ctx.stage("state after the prefix read back (not set-up)")

    def solve(self) -> bool:
        """The solve from the call after the prefix on; True if it ended
        (converged) before the hook stopped it."""
        try:
            self.solver.solve(first_call=self.prefix_calls)
        except StopWindow:
            return False
        return True

    def prefix_checks(self) -> list:
        lim = self.ctx.traffic["limits"]
        ref, steps = self.problem.reference("float32", alphas=self.prefix_alphas)
        got = self.problem.prefix_numbers(
            self.prefix_w, self.prefix_pred, self.prefix_obj, self.prefix_active, ref, steps
        )
        print("\n".join(gap_lines(got, steps)), flush=True)
        return [Check(name, value, lim[name]) for name, value in got.items()]

    def close_checks(self, open_at: int, close_at: int) -> list:
        """At the window's close: the objective no higher than after the
        prefix and never rising from one call to the next; pred against Xw
        recomputed by the reference from the table read back, on a seeded
        sample of examples; the entries the window's calls claim to have
        swept against the reference's own count of those blocks' entries."""
        lim, p, s = self.ctx.traffic["limits"], self.problem, self.solver
        objs = np.array([r["obj"] for r in self.records if not is_refresh(r)])
        rises = (objs[1:] - objs[:-1]) / np.abs(objs[:-1])
        table = np.asarray(s.state["w"])[: p.num_keys, 0]
        pred = np.asarray(s.pred)[: p.n]
        ex = p.sample_examples()
        gaps = element_gaps(pred[ex], p.xw_of(table, ex))
        inside = [b for r in self.records[open_at + 1 : close_at + 1] for b in r["blocks"]]
        claimed = float(np.asarray(self.cb.entries)[inside].sum())
        counted = float(p.block_entries[inside].sum())
        return [
            Check("window.nonfinite_objectives", float((~np.isfinite(objs)).sum()), 0),
            Check(
                "window.objective_rise", (objs[-1] - self.prefix_obj) / abs(self.prefix_obj),
                lim["window.objective_rise"],
                note=f"after the prefix {self.prefix_obj:.6g}, after {len(objs)} calls {objs[-1]:.6g}",
            ),
            Check("window.call_objective_rise", float(rises.max()) if len(rises) else 0.0, lim["window.call_objective_rise"]),
            Check("window.pred_gap_q99", float(np.percentile(gaps, 99)), lim["window.pred_gap_q99"]),
            Check("window.pred_gap_max", float(np.max(gaps)), lim["window.pred_gap_max"]),
            Check(
                "window.swept_entries_gap", abs(claimed - counted) / counted, lim["window.swept_entries_gap"],
                note=f"{len(inside)} blocks stepped or refreshed inside: the calls claim {claimed:.0f} entries, the reference counts {counted:.0f}",
            ),
        ]

    def close(self) -> None:
        self.solver.on_retire = None
