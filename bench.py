"""Benchmark suite: flagship sparse-LR FTRL throughput + sub-benches.

Prints ONE COMPACT JSON line (< 1500 chars — the driver records only a
2000-char stdout tail, so the contract fields must fit it) and writes the
FULL nested result to BENCH_full_latest.json next to this file
(override with PS_BENCH_FULL_OUT). Contract fields on the stdout line:
  {"metric": ..., "value": N, "unit": "examples/sec", "vs_baseline": N,
   "platform": ..., "suite_wall_s": N, "full_results": <filename>}

value       — steady-state training examples/sec of the fused device step
              (pull -> CSR grad -> FTRL push), median of 3 timed passes.
vs_baseline — speedup over a single-core numpy implementation of the exact
              same algorithm (median of 3 passes over 8 batches; raw
              numbers for both sides are in "raw" so the ratio's noise is
              auditable). BASELINE.md records why the true reference
              cannot be executed in this environment.

Orchestration: the parent process never initializes JAX (a chip belongs
to one process at a time). It probes the backend in a subprocess and
exits non-zero before any child starts unless that says "tpu". Each
sub-bench then runs in its OWN child process under a hard deadline, one
after another. Children share a persistent XLA compilation cache
(utils.hostenv.init_compile_cache) so the split costs compile time once
per program. The headline child runs FIRST. A device-bound child that
fails makes the suite exit non-zero after the compact line is printed.

Sub-benches ("sub"):
  pallas_ftrl  — fused Pallas FTRL delta vs the jnp composite on the same
                 rows. If the kernel wins the headline step re-runs with
                 use_pallas=True and the better number is the headline
                 (raw.headline_use_pallas).
  pipeline_e2e — end-to-end files -> trained AUC through the parallel
                 host pipeline, as an in-process A/B matrix over the wire
                 format {compact, full} x {f32, f16} (one process: the
                 ratios are attribution-safe; AUC per cell guards
                 quantization).
  ladder       — in-process feature ladder on the same e2e workload:
                 serial -> pipelined -> steps_per_call K in {1, 4, 8} ->
                 bucketing off, isolating each flag's contribution.
  hbm_scale    — the fused FTRL step and a full-table dense update at
                 num_keys = 2^27 (1 GiB of z+n state on TPU): rows/sec,
                 effective HBM GB/s, and no-OOM at reference-shaped key
                 counts (SURVEY §7.4 huge key spaces).
  scale        — sustained e2e: 10^7 examples (2.3 GB of libsvm text)
                 streamed through parse -> frequency filter -> bucketing
                 -> pipeline -> K=8 multistep vs a 2^24-key table, with
                 held-out AUC (the Criteo-TB-shaped north star on a
                 synthetic stand-in).
  word2vec     — fused-SGNS pairs/sec (BASELINE's second parity config),
                 K in {1, 8}, now with a single-core numpy SGNS baseline
                 on identical batch semantics (vs_baseline).
  matrix_fac   — MF rating-triple throughput (BASELINE's MovieLens-shaped
                 config) with a single-core numpy baseline (vs_baseline).
  darlin       — DARLIN batch-solver block passes/sec + objective/nnz
                 (the reference's second flagship; RCV1-shaped L1-LR).
  spmd_push    — per_worker vs aggregate push wall-clock on a (data=8)
                 virtual CPU mesh (multi-device modes can't run on one
                 real chip; recorded as platform "cpu-sim").
  wd_push      — Wide&Deep push-mode matrix (per_worker / aggregate /
                 int8-quantized) on a (data=4, kv=2) cpu-sim mesh: the
                 embedding push is W&D's dominant traffic, and this
                 measures every claimed mode on the app that needs the
                 quantized wire most.
  ingest       — host-side native parse MB/s + parse+localize ex/s per
                 stream (bounds e2e on co-located hardware).
  wire_rpc     — loopback RPC tier microbench: (1) ShardServer +
                 ServerHandle over real TCP (one handle reused across
                 repeats): pull/push round-trips/sec and p50/p99
                 client-observed latency from the telemetry plane's
                 log-bucketed histograms; (2) pipelined-vs-lockstep push
                 round trips at window W=8 against a separate-process ack
                 server (the async engine's headline ratio); (3) a
                 4 KiB -> 4 MiB payload sweep reporting MB/s for lockstep
                 vs pipelined through the zero-copy frame path plus a
                 compressible cell exercising the adaptive-zip probe;
                 (4) observability overhead guards: flightrec_ratio
                 (ISSUE 9, armed recorder within 5%) and
                 observability_ratio (ISSUE 13: flightrec + time-series
                 rolling + the sampling profiler ALL armed vs all off,
                 also within 5%). Its process telemetry snapshot is
                 embedded in the full results as "telemetry", so
                 BENCH_* rounds track RPC latency alongside throughput.
  server_apply — shard-server batched apply engine A/B on loopback: push
                 throughput at 8 concurrent pipelined clients with the
                 apply engine ON (coalesced, single-dispatch batches)
                 vs OFF (the serial per-push lock), plus small-frame
                 (4 KiB) pipelined push rps with binary vs JSON headers
                 against a separate-process ack server.
  quant_wire   — quantized push/pull wire A/B (ISSUE 6 acceptance): the
                 linear-method e2e workload trained over the real wire
                 tier at f32 / int8+error-feedback / int16 with identical
                 seeds; measured push payload ratio (>= 3x at int8) and
                 AUC parity (|dAUC| <= 0.002) per arm, plus the
                 residual-norm peak gauge.
  backend      — transport-neutral KV backend A/B (ISSUE 11 acceptance):
                 the SAME canonical train_linear client loop on the
                 socket tier (2 loopback ShardServers) and the in-mesh
                 GSPMD tier (8-device cpu-sim kv mesh), plus a push-
                 throughput sweep over keys-per-push that places the
                 socket/mesh crossover as a number, and the int8
                 quantized-collective arm (payload bytes ratio + AUC
                 parity vs the mesh f32 arm at equal seeds).
  serve        — online serving plane A/B (ISSUE 7 acceptance): 256
                 simulated Zipf(1.1) read-mostly clients multiplexed
                 over 16 threads against one shard server; cached
                 (client versioned key cache + server single-flight
                 encode coalescing) vs uncached pull QPS (>= 5x), cache
                 hit rate, coalesce ratio, an int8 quant_pull arm, and
                 a push-flood shed arm proving p99 stays bounded under
                 admission control.
"""

from __future__ import annotations

import json
import os
import signal
import statistics
import subprocess
import sys
import tempfile
import time

import numpy as np

BATCH = 8192
NNZ_PER = 32
NUM_KEYS = 1 << 20
N_BATCHES = 12
BASELINE_BATCHES = 8
REPEATS = 3
ALPHA, BETA, L1, L2 = 0.1, 1.0, 1.0, 0.0

# hard per-child deadlines (seconds). Generous vs expected runtime but
# small enough that a wedged child can't eat the driver's whole window.
CHILD_BUDGET_S = {
    "headline": 360,
    "pipeline_e2e": 480,
    "ladder": 480,
    "hbm_scale": 300,
    "scale": 720,
    "word2vec": 360,
    "matrix_fac": 300,
    "spmd_push": 300,
    "wd_push": 420,
    "darlin": 300,
    "ingest": 240,
    "wire_rpc": 300,
    "server_apply": 360,
    "quant_wire": 420,
    "backend": 420,
    "serve": 300,
}
# run order = value order: the contract fields land first, platform-bound
# numbers next, platform-independent ones last
CHILD_ORDER = (
    "headline", "pipeline_e2e", "hbm_scale", "ladder", "scale", "word2vec",
    "matrix_fac", "darlin", "spmd_push", "wd_push", "ingest", "wire_rpc",
    "server_apply", "quant_wire", "backend", "serve",
)


# ---------------------------------------------------------------------------
# shared helpers (children only — the parent never imports jax)
# ---------------------------------------------------------------------------


def _make_batches(n_batches: int = N_BATCHES, num_keys: int = NUM_KEYS,
                  feature_space: int = 1 << 18, seed: int = 7):
    from parameter_server_tpu.data.batch import BatchBuilder
    from parameter_server_tpu.data.synthetic import make_sparse_logistic

    labels, keys, vals, _ = make_sparse_logistic(
        BATCH * n_batches, feature_space, nnz_per_example=NNZ_PER,
        noise=0.4, seed=seed,
    )
    builder = BatchBuilder(
        num_keys=num_keys, batch_size=BATCH, max_nnz_per_example=4 * NNZ_PER
    )
    return [
        builder.build(
            labels[i : i + BATCH], keys[i : i + BATCH], vals[i : i + BATCH]
        )
        for i in range(0, BATCH * n_batches, BATCH)
    ]


def bench_device(batches, use_pallas: bool = False,
                 num_keys: int = NUM_KEYS) -> tuple[float, list[float]]:
    """Median-of-REPEATS steady-state device throughput (examples/sec)."""
    import jax

    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.models.linear import batch_to_device, train_step

    up = Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2,
              use_pallas=use_pallas)
    dev_batches = [batch_to_device(b) for b in batches]

    def one_run(state, cycles: int) -> tuple[float, int]:
        t0 = time.perf_counter()
        steps = 0
        for _ in range(cycles):
            for b in dev_batches[1:]:
                state, out = train_step(up, state, b)
                steps += 1
        jax.block_until_ready(out["loss_sum"])
        return time.perf_counter() - t0, steps

    def warm_state():
        state = up.init(num_keys, 1)
        state, out = train_step(up, state, dev_batches[0])  # warmup/compile
        jax.block_until_ready(out["loss_sum"])
        return state

    # size the timed window toward ~0.5s of device work: an 11-step run
    # finishes in ~1ms on a fast chip and would time only dispatch/sync
    # noise. Capped, so a slow device cannot stretch the window without
    # bound
    probe_dt, _ = one_run(warm_state(), 1)
    cycles = min(max(2, int(0.5 / max(probe_dt, 1e-4))), 60)
    runs = []
    for _ in range(REPEATS):
        dt, steps = one_run(warm_state(), cycles)
        runs.append(BATCH * steps / dt)
    return statistics.median(runs), [round(r, 1) for r in runs]


def bench_numpy_baseline(batches) -> tuple[float, list[float]]:
    """Single-core numpy FTRL on identical batches, median of REPEATS
    passes over BASELINE_BATCHES batches (state reset per pass)."""
    runs = []
    for _ in range(REPEATS):
        z = np.zeros(NUM_KEYS, dtype=np.float32)
        n = np.zeros(NUM_KEYS, dtype=np.float32)
        sub = batches[:BASELINE_BATCHES]
        t0 = time.perf_counter()
        for b in sub:
            U = len(b.unique_keys)
            idx = b.unique_keys
            # pull
            shrunk = np.sign(z[idx]) * np.maximum(np.abs(z[idx]) - L1, 0.0)
            w_u = -shrunk / ((BETA + np.sqrt(n[idx])) / ALPHA + L2)
            # forward
            contrib = b.values * w_u[b.local_ids]
            logits = np.bincount(b.row_ids, weights=contrib, minlength=BATCH)
            p = 1.0 / (1.0 + np.exp(-logits))
            err = (p - b.labels) * b.example_mask
            # grad per unique key
            g = np.bincount(
                b.local_ids, weights=b.values * err[b.row_ids], minlength=U
            ).astype(np.float32)
            # FTRL push
            n_new = n[idx] + g * g
            sigma = (np.sqrt(n_new) - np.sqrt(n[idx])) / ALPHA
            z[idx] += g - sigma * w_u
            n[idx] = n_new
        dt = time.perf_counter() - t0
        runs.append(BATCH * len(sub) / dt)
    return statistics.median(runs), [round(r, 1) for r in runs]


def bench_pallas_ftrl() -> dict:
    """Fused Pallas FTRL delta vs the jnp composite over 2^20 rows."""
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Ftrl

    rows_n = 1 << 20
    rng = np.random.default_rng(3)
    rows = {
        "z": jnp.asarray(rng.normal(size=(rows_n, 1)).astype(np.float32)),
        "n": jnp.asarray(np.abs(rng.normal(size=(rows_n, 1))).astype(np.float32)),
    }
    g = jnp.asarray(rng.normal(size=(rows_n, 1)).astype(np.float32))
    kw = dict(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2)

    def _time(up) -> float:
        import jax

        f = jax.jit(lambda r, gg: up.delta(r, gg))
        jax.block_until_ready(f(rows, g))  # compile
        # adaptive window (~0.5s): a 30-iter loop finishes in ~1ms on a
        # fast chip and times only dispatch/sync noise
        t0 = time.perf_counter()
        jax.block_until_ready(f(rows, g))
        probe = max(time.perf_counter() - t0, 1e-5)
        iters = min(max(10, int(0.5 / probe)), 300)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(rows, g)
        jax.block_until_ready(out)
        return rows_n * iters / (time.perf_counter() - t0)

    jnp_rows = _time(Ftrl(**kw))
    pallas_rows = _time(Ftrl(**kw, use_pallas=True))
    return {
        "mode": "real",
        "jnp_rows_per_sec": round(jnp_rows, 1),
        "pallas_rows_per_sec": round(pallas_rows, 1),
        "pallas_speedup": round(pallas_rows / jnp_rows, 3),
    }


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def child_headline() -> dict:
    """Driver-contract numbers: device FTRL step vs numpy baseline, plus
    the Pallas-vs-XLA comparison (which may promote the headline)."""
    batches = _make_batches()
    baseline, baseline_runs = bench_numpy_baseline(batches)
    value, device_runs = bench_device(batches)
    headline_use_pallas = False
    pallas = bench_pallas_ftrl()
    if pallas.get("mode") == "real" and pallas.get("pallas_speedup", 0) > 1.0:
        v2, runs2 = bench_device(batches, use_pallas=True)
        pallas["headline_step_ex_per_sec_pallas"] = round(v2, 1)
        if v2 > value:
            value, device_runs = v2, runs2
            headline_use_pallas = True
    return {
        "platform": _platform(),
        "value": round(value, 1),
        "vs_baseline": round(value / baseline, 2),
        "raw": {
            "device_ex_per_sec_runs": device_runs,
            "baseline_ex_per_sec": round(baseline, 1),
            "baseline_ex_per_sec_runs": baseline_runs,
            "baseline_batches": BASELINE_BATCHES,
            "headline_use_pallas": headline_use_pallas,
        },
        "pallas_ftrl": pallas,
    }


def _write_e2e_files(d: str, n: int, files: int) -> list[str]:
    from parameter_server_tpu.data.synthetic import (
        make_sparse_logistic,
        write_libsvm,
    )

    labels, keys, vals, _ = make_sparse_logistic(
        n, 1 << 16, nnz_per_example=NNZ_PER, noise=0.4, seed=23
    )
    paths = []
    per = n // files
    for i in range(files):
        p = os.path.join(d, f"part-{i}.svm")
        s = slice(i * per, (i + 1) * per)
        write_libsvm(p, labels[s], keys[s], vals[s])
        paths.append(p)
    return paths


def _e2e_run(paths: list[str], n: int, *, depth: int, k: int, delay: int,
             bucket: bool = True, compact: bool = True,
             wire_values: str = "f32") -> tuple[float, float]:
    """One end-to-end files->AUC training run; returns (ex/s, auc)."""
    from parameter_server_tpu.parallel.trainer import PodTrainer
    from parameter_server_tpu.utils.config import PSConfig
    from parameter_server_tpu.utils.metrics import ProgressReporter

    cfg = PSConfig()
    cfg.data.num_keys = NUM_KEYS
    cfg.data.pipeline_depth = depth
    cfg.data.bucket_nnz = bucket
    cfg.data.compact_wire = compact
    cfg.data.wire_values = wire_values
    cfg.data.max_nnz_per_example = 4 * NNZ_PER
    cfg.solver.minibatch = 4096
    cfg.solver.steps_per_call = k
    cfg.solver.max_delay = delay
    cfg.penalty.lambda_l1 = L1
    t = PodTrainer(cfg, reporter=ProgressReporter(print_fn=lambda *_: None))
    t.train_files(paths[:1], report_every=1000)  # compile warmup
    t0 = time.perf_counter()
    last = t.train_files(paths, report_every=1000)
    dt = time.perf_counter() - t0
    return round(n / dt, 1), round(last.get("auc", float("nan")), 4)


def child_pipeline_e2e() -> dict:
    """Wire-format A/B matrix {compact, full} x {f32, f16} inside ONE
    process, all at the production fast path (K=8,
    depth=2, delay=2, bucketed). AUC per cell: the f16 wire is only a
    win if it holds AUC."""
    n, files = 1 << 16, 4
    out: dict = {"platform": _platform(), "config": "K=8 depth=2 delay=2 bucketed"}
    with tempfile.TemporaryDirectory() as d:
        paths = _write_e2e_files(d, n, files)
        for compact, wv in (
            (True, "f32"), (True, "f16"), (False, "f32"), (False, "f16"),
        ):
            label = f"{'compact' if compact else 'full'}_{wv}"
            ex, auc = _e2e_run(
                paths, n, depth=2, k=8, delay=2, compact=compact,
                wire_values=wv,
            )
            out[f"{label}_ex_per_sec"] = ex
            out[f"{label}_auc"] = auc
    best = max(
        (k[: -len("_ex_per_sec")] for k in out if k.endswith("_ex_per_sec")),
        key=lambda k: out[f"{k}_ex_per_sec"],
    )
    out["fastest"] = best
    # continuity with r1-r3 captures: the default-config cell under the
    # old key names
    out["pipelined_k8_ex_per_sec"] = out["compact_f32_ex_per_sec"]
    out["auc_k8"] = out["compact_f32_auc"]
    return out


def child_ladder() -> dict:
    """In-process feature ladder on the e2e workload: each rung toggles
    one flag off the production config, so per-feature attribution never
    spans processes."""
    n, files = 1 << 16, 4
    out: dict = {"platform": _platform()}
    with tempfile.TemporaryDirectory() as d:
        paths = _write_e2e_files(d, n, files)
        # one flag per rung: serial->pipelined toggles the thread pipeline
        # alone (delay stays 0), async adds SSP run-ahead, k4/k8 add the
        # scanned multistep, bucket_off removes nnz bucketing
        rungs = {
            "serial": dict(depth=0, k=1, delay=0),
            "pipelined_k1": dict(depth=2, k=1, delay=0),
            "async_k1": dict(depth=2, k=1, delay=2),
            "k4": dict(depth=2, k=4, delay=2),
            "k8": dict(depth=2, k=8, delay=2),
            "k8_bucket_off": dict(depth=2, k=8, delay=2, bucket=False),
        }
        aucs = {}
        for label, kw in rungs.items():
            ex, aucs[label] = _e2e_run(paths, n, **kw)
            out[f"{label}_ex_per_sec"] = ex
        out["auc"] = aucs["k8"]
    out["pipeline_speedup"] = round(
        out["pipelined_k1_ex_per_sec"] / out["serial_ex_per_sec"], 3
    )
    out["runahead_speedup"] = round(
        out["async_k1_ex_per_sec"] / out["pipelined_k1_ex_per_sec"], 3
    )
    out["k8_over_k1"] = round(
        out["k8_ex_per_sec"] / out["async_k1_ex_per_sec"], 3
    )
    out["bucketing_speedup"] = round(
        out["k8_ex_per_sec"] / out["k8_bucket_off_ex_per_sec"], 3
    )
    return out


def child_hbm_scale() -> dict:
    """The HBM-resident-state demonstration (SURVEY §7.4 huge key spaces):
    fused FTRL step + full-table dense update at num_keys = 2^27 on TPU
    (1 GiB of z+n state; ~2^27 is what one chip's HBM comfortably holds
    next to batches). CPU fallback runs 2^24 so the capture stays honest
    about what ran where."""
    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Ftrl

    plat = _platform()
    log2 = 27 if plat == "tpu" else 24
    num_keys = 1 << log2
    out: dict = {
        "platform": plat,
        "num_keys_log2": log2,
        "state_bytes": 2 * num_keys * 4,  # z + n, f32
    }
    if plat != "tpu":
        # VERDICT r4 weak #4: CPU numbers here smoke-test the sub-bench,
        # nothing more — say so in the artifact itself (cpu_smoke is the
        # compact-line marker; the note rides the full-results file)
        out["cpu_smoke"] = True
        out["note"] = (
            "CPU smoke run of the sub-bench; NOT an HBM measurement — "
            "the 2^27 HBM-resident claim needs the TPU capture"
        )
    # sparse path: the real train step over a huge table — gather/scatter
    # bandwidth at reference-shaped key counts (keys Zipf-hashed into the
    # full 2^27 space)
    batches = _make_batches(
        n_batches=8, num_keys=num_keys, feature_space=1 << 24, seed=7
    )
    touched = int(np.mean([b.num_unique for b in batches]))
    ex_s, runs = bench_device(batches, num_keys=num_keys)
    out["sparse_step_ex_per_sec"] = round(ex_s, 1)
    out["sparse_step_runs"] = runs
    out["touched_rows_per_step"] = touched
    # ~5 arrays of touched rows move per step (z, n read + z, n write + g)
    out["sparse_step_touched_mb"] = round(touched * 5 * 4 / 1e6, 2)

    # dense path: FTRL updates over EVERY row — 5 f32 streams over the
    # whole table per pass; rows/sec * 20 B = effective HBM bandwidth.
    # The passes chain inside ONE jitted fori_loop (a real z/n dependency
    # chain, so nothing is DCE'd): one dispatch, in-place buffer reuse —
    # a host loop of async calls would stack un-retired 1 GiB outputs in
    # HBM (the unbounded-dispatch failure eval had to bound)
    from jax import lax

    up = Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2)
    rng = np.random.default_rng(5)
    z = jnp.asarray(rng.normal(size=(num_keys, 1)).astype(np.float32))
    nacc = jnp.asarray(
        np.abs(rng.normal(size=(num_keys, 1))).astype(np.float32)
    )
    g = jnp.asarray(rng.normal(size=(num_keys, 1)).astype(np.float32))

    @jax.jit
    def passes(z, n, g, iters):
        def body(_, c):
            d = up.delta({"z": c[0], "n": c[1]}, g)
            return (c[0] + d["z"], c[1] + d["n"])

        return lax.fori_loop(0, iters, body, (z, n))

    jax.block_until_ready(passes(z, nacc, g, 1))  # compile
    t0 = time.perf_counter()
    jax.block_until_ready(passes(z, nacc, g, 2))
    probe = max((time.perf_counter() - t0) / 2, 1e-4)
    iters = min(max(3, int(1.0 / probe)), 200)
    t0 = time.perf_counter()
    jax.block_until_ready(passes(z, nacc, g, iters))
    dt = time.perf_counter() - t0
    rows_s = num_keys * iters / dt
    out["dense_passes"] = iters
    out["dense_rows_per_sec"] = round(rows_s, 1)
    out["dense_hbm_gb_per_sec"] = round(rows_s * 20 / 1e9, 1)
    return out


def child_scale() -> dict:
    """Sustained-scale streaming e2e (the BASELINE north star is
    Criteo-TB-shaped; zero egress => synthetic stand-in): 10^7 examples
    through the FULL path — native parse -> count-min frequency
    admission -> pow-2 nnz bucketing -> prefetch pipeline -> scanned K=8
    multistep with SSP run-ahead — against a 2^24-key table, with
    held-out AUC. One 57 MB shard is written once and streamed 40x
    (page-cache resident: this measures the framework, not the disk)."""
    from parameter_server_tpu.data.synthetic import (
        make_sparse_logistic,
        write_libsvm,
    )
    from parameter_server_tpu.parallel.trainer import PodTrainer
    from parameter_server_tpu.utils.config import PSConfig
    from parameter_server_tpu.utils.metrics import ProgressReporter

    shard_n, repeats, test_n = 250_000, 40, 50_000
    out: dict = {
        "platform": _platform(),
        "num_keys_log2": 24,
        "examples_streamed": shard_n * repeats,
    }
    with tempfile.TemporaryDirectory() as d:
        # ONE generation call: train shard and held-out slice share the
        # same ground-truth weights (different seeds would mean a test
        # set from a different true model — AUC 0.5 by construction)
        labels, keys, vals, _ = make_sparse_logistic(
            shard_n + test_n, 1 << 22, nnz_per_example=NNZ_PER, noise=0.4,
            seed=31,
        )
        train_p = os.path.join(d, "shard.svm")
        write_libsvm(
            train_p, labels[:shard_n], keys[:shard_n], vals[:shard_n]
        )
        test_p = os.path.join(d, "test.svm")
        write_libsvm(
            test_p, labels[shard_n:], keys[shard_n:], vals[shard_n:]
        )
        out["shard_mb"] = round(os.path.getsize(train_p) / 1e6, 1)
        out["gb_streamed"] = round(out["shard_mb"] * repeats / 1000, 2)
        cfg = PSConfig()
        cfg.data.num_keys = 1 << 24
        cfg.data.pipeline_depth = 2
        cfg.data.bucket_nnz = True
        cfg.data.compact_wire = True
        cfg.data.max_nnz_per_example = 4 * NNZ_PER
        cfg.data.freq_min_count = 2
        cfg.solver.minibatch = 8192
        cfg.solver.steps_per_call = 8
        cfg.solver.max_delay = 2
        cfg.solver.epochs = 1
        cfg.penalty.lambda_l1 = L1
        t = PodTrainer(
            cfg, reporter=ProgressReporter(print_fn=lambda *_: None)
        )
        t.train_files([train_p], report_every=200)  # compile warmup pass
        t0 = time.perf_counter()
        last = t.train_files([train_p] * repeats, report_every=200)
        dt = time.perf_counter() - t0
        out["ex_per_sec"] = round(shard_n * repeats / dt, 1)
        out["wall_s_stream"] = round(dt, 1)
        out["train_auc_tail"] = last.get("auc")
        ev = t.evaluate_files([test_p])
        out["holdout_auc"] = round(ev["auc"], 4)
    return out


def child_word2vec() -> dict:
    """word2vec SGNS throughput (BASELINE's second parity config) at
    steps_per_call 1 and 8, plus a single-core numpy SGNS baseline with
    identical batch semantics (adagrad tables, scatter-add of deltas)."""
    from parameter_server_tpu.models.word2vec import Word2Vec
    from parameter_server_tpu.utils.metrics import ProgressReporter

    vocab, dim, n_tokens, neg = 1 << 16, 64, 1 << 20, 5
    rng = np.random.default_rng(11)
    corpus = rng.integers(0, vocab, n_tokens)
    bs = 8192
    total = 2 * (2 * n_tokens - 3)  # window=2 skip-gram pair count
    pairs = total // bs * bs  # only full batches are dispatched
    out: dict = {
        "platform": _platform(), "vocab": vocab, "dim": dim, "negatives": neg,
    }
    for k in (1, 8):
        w2v = Word2Vec(
            vocab_size=vocab, dim=dim, eta=0.1, num_negatives=neg, window=2,
            # SSP run-ahead: without it every call pays a full
            # host<->device round trip on loss retirement
            max_delay=8,
            steps_per_call=k,
            reporter=ProgressReporter(print_fn=lambda *_: None),
        )
        w2v.train_epoch(corpus[: 1 << 17], batch_size=bs, seed=0)  # warmup
        t0 = time.perf_counter()
        w2v.train_epoch(corpus, batch_size=bs, seed=1)
        dt = time.perf_counter() - t0
        key = "pairs_per_sec" if k == 1 else f"pairs_per_sec_k{k}"
        out[key] = round(pairs / dt, 1)
    out["multistep_speedup"] = round(
        out["pairs_per_sec_k8"] / out["pairs_per_sec"], 3
    )

    # single-core numpy baseline: the same SGNS math (einsum logits,
    # softplus loss, adagrad deltas, np.add.at scatter — the duplicate-id
    # semantics of the device step) on identical batch shapes
    n_base = 8  # batches per timed pass
    centers = rng.integers(0, vocab, n_base * bs).astype(np.int32)
    contexts = rng.integers(0, vocab, n_base * bs).astype(np.int32)
    negs = rng.integers(0, vocab, (n_base * bs, neg)).astype(np.int32)
    eta, eps = 0.1, 1e-8
    runs = []
    for _ in range(REPEATS):
        w_in = rng.uniform(-0.5 / dim, 0.5 / dim, (vocab, dim)).astype(np.float32)
        n_in = np.zeros((vocab, dim), np.float32)
        w_out = np.zeros((vocab, dim), np.float32)
        n_out = np.zeros((vocab, dim), np.float32)
        labels = np.concatenate(
            [np.ones((bs, 1), np.float32), np.zeros((bs, neg), np.float32)],
            axis=1,
        )
        t0 = time.perf_counter()
        for i in range(n_base):
            s = slice(i * bs, (i + 1) * bs)
            c = centers[s]
            out_ids = np.concatenate(
                [contexts[s][:, None], negs[s]], axis=1
            ).reshape(-1)
            u = w_in[c]  # (B, d)
            v = w_out[out_ids].reshape(bs, 1 + neg, dim)
            logits = np.einsum("bd,bkd->bk", u, v)
            err = 1.0 / (1.0 + np.exp(-logits)) - labels
            g_u = np.einsum("bk,bkd->bd", err, v)
            g_v = (err[:, :, None] * u[:, None, :]).reshape(-1, dim)
            # adagrad deltas from the PULLED rows, then scatter-add
            nu = n_in[c] + g_u * g_u
            np.add.at(n_in, c, g_u * g_u)
            np.add.at(w_in, c, -eta * g_u / (np.sqrt(nu) + eps))
            nv = n_out[out_ids] + g_v * g_v
            np.add.at(n_out, out_ids, g_v * g_v)
            np.add.at(w_out, out_ids, -eta * g_v / (np.sqrt(nv) + eps))
        runs.append(n_base * bs / (time.perf_counter() - t0))
    base = statistics.median(runs)
    out["baseline_pairs_per_sec"] = round(base, 1)
    out["baseline_runs"] = [round(r, 1) for r in runs]
    out["vs_baseline"] = round(out["pairs_per_sec_k8"] / base, 2)
    # the device number includes host-side skip-gram pair generation that
    # the numpy baseline is not charged for (it times only the SGNS math
    # on pre-generated arrays) — the ratio understates the device side
    out["vs_baseline_note"] = "conservative: device side includes pairgen"
    return out


def child_matrix_fac() -> dict:
    """Matrix-factorization rating-triple throughput (BASELINE's MovieLens
    parity config shape: rank-64 adagrad) plus a single-core numpy
    baseline running the same per-batch algorithm (unique + segment-sum
    grads + adagrad scatter)."""
    from parameter_server_tpu.models.matrix_fac import (
        MatrixFactorization,
        MFBatchBuilder,
    )
    from parameter_server_tpu.utils.metrics import ProgressReporter

    users_n = items_n = (1 << 16) - 1
    rank, bs, n = 64, 8192, 1 << 19
    rng = np.random.default_rng(17)
    users = rng.integers(0, users_n, n)
    items = rng.integers(0, items_n, n)
    ratings = (rng.normal(size=n) + 3.5).astype(np.float32)
    out: dict = {
        "platform": _platform(), "rank": rank, "ratings": n,
    }
    app = MatrixFactorization(
        users_n, items_n, rank=rank, eta=0.05, l2=0.01, algo="adagrad",
        seed=0, max_delay=4, steps_per_call=8,
        reporter=ProgressReporter(print_fn=lambda *_: None),
    )
    app.train_epoch(
        users[: bs * 8], items[: bs * 8], ratings[: bs * 8], batch_size=bs
    )
    t0 = time.perf_counter()
    app.train_epoch(users, items, ratings, batch_size=bs, seed=1)
    dt = time.perf_counter() - t0
    out["pairs_per_sec_k8"] = round(n / dt, 1)

    # numpy baseline: same math per batch over the same triples
    l2, eta, eps = 0.01, 0.05, 1e-8
    builder = MFBatchBuilder(bs)
    n_base = 8
    runs = []
    for _ in range(REPEATS):
        U = rng.normal(scale=0.1, size=(users_n + 1, rank)).astype(np.float32)
        V = rng.normal(scale=0.1, size=(items_n + 1, rank)).astype(np.float32)
        U[0] = V[0] = 0.0  # pad row, as in the device tables
        Un = np.zeros_like(U)
        Vn = np.zeros_like(V)
        t0 = time.perf_counter()
        for i in range(n_base):
            s = slice(i * bs, (i + 1) * bs)
            b = builder.build(users[s], items[s], ratings[s])
            u = U[b.user_keys][b.user_ids]
            v = V[b.item_keys][b.item_ids]
            err = (np.sum(u * v, axis=1) - b.ratings) * b.mask
            g_u = np.zeros((len(b.user_keys), rank), np.float32)
            np.add.at(g_u, b.user_ids, err[:, None] * v)
            g_u += l2 * U[b.user_keys] * (np.arange(len(b.user_keys)) > 0)[:, None]
            g_v = np.zeros((len(b.item_keys), rank), np.float32)
            np.add.at(g_v, b.item_ids, err[:, None] * u)
            g_v += l2 * V[b.item_keys] * (np.arange(len(b.item_keys)) > 0)[:, None]
            for W, N, keys, g in (
                (U, Un, b.user_keys, g_u), (V, Vn, b.item_keys, g_v),
            ):
                nn = N[keys] + g * g
                np.add.at(N, keys, g * g)
                np.add.at(W, keys, -eta * g / (np.sqrt(nn) + eps))
        runs.append(n_base * bs / (time.perf_counter() - t0))
    base = statistics.median(runs)
    out["baseline_pairs_per_sec"] = round(base, 1)
    out["baseline_runs"] = [round(r, 1) for r in runs]
    out["vs_baseline"] = round(out["pairs_per_sec_k8"] / base, 2)
    return out


def child_spmd_push() -> dict:
    """per_worker vs aggregate push wall-clock on a (data=8, kv=1) virtual
    CPU mesh (the parent forces the CPU-sim env for this child)."""
    import jax

    from parameter_server_tpu.data.batch import BatchBuilder
    from parameter_server_tpu.data.synthetic import make_sparse_logistic
    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.parallel.mesh import make_mesh
    from parameter_server_tpu.parallel.spmd import (
        make_spmd_train_step,
        shard_state,
        stack_batches,
    )

    D, num_keys, bs, nnz = 8, 1 << 18, 2048, 32
    labels, keys, vals, _ = make_sparse_logistic(
        bs * D * 4, 1 << 16, nnz_per_example=nnz, noise=0.4, seed=11
    )
    builder = BatchBuilder(
        num_keys=num_keys, batch_size=bs, max_nnz_per_example=4 * nnz
    )
    batches = [
        builder.build(labels[i : i + bs], keys[i : i + bs], vals[i : i + bs])
        for i in range(0, bs * D * 4, bs)
    ]
    mesh = make_mesh(D, 1)
    up = Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2)
    out: dict = {"data_shards": D, "platform": "cpu-sim"}
    for mode in ("per_worker", "aggregate"):
        step = make_spmd_train_step(up, mesh, num_keys, push_mode=mode)
        state = shard_state(up.init(num_keys, 1), mesh)
        stacked = [
            stack_batches(batches[i : i + D], mesh)
            for i in range(0, len(batches), D)
        ]
        state, o = step(state, stacked[0])  # compile
        jax.block_until_ready(o["loss_sum"])
        t0 = time.perf_counter()
        for s in stacked[1:]:
            state, o = step(state, s)
        jax.block_until_ready(o["loss_sum"])
        dt = time.perf_counter() - t0
        out[f"{mode}_ex_per_sec"] = round(bs * D * (len(stacked) - 1) / dt, 1)
    out["aggregate_speedup"] = round(
        out["aggregate_ex_per_sec"] / out["per_worker_ex_per_sec"], 3
    )
    return out


def child_darlin() -> dict:
    """DARLIN batch-solver throughput (the reference's second flagship;
    BASELINE's RCV1-shaped L1-LR parity config): block passes/sec of the
    resident single-device solve on the e2e synthetic family, plus the
    objective it reaches and the sparsity the KKT filter keeps."""
    from parameter_server_tpu.data.blockcache import ColumnBlocks
    from parameter_server_tpu.models.darlin import Darlin
    from parameter_server_tpu.utils.config import PSConfig
    from parameter_server_tpu.utils.metrics import ProgressReporter

    n, blocks = 1 << 16, 32
    batches = _make_batches(n_batches=n // BATCH, num_keys=1 << 18,
                            feature_space=1 << 16, seed=29)
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 18
    cfg.solver.algo = "darlin"
    cfg.solver.feature_blocks = blocks
    cfg.solver.block_iters = 4
    cfg.solver.kkt_filter_threshold = 0.1  # exercise the KKT active set
    cfg.penalty.lambda_l1 = 1.0
    out: dict = {"platform": _platform(), "examples": n, "blocks": blocks}
    quiet = ProgressReporter(print_fn=lambda *_: None)
    # pack the column blocks ONCE outside the timed region (fit() would
    # rebuild them per call — host packing is not solver throughput)
    cb = ColumnBlocks.from_batches(batches, cfg.data.num_keys, blocks)
    Darlin(cfg, reporter=quiet).fit_blocks(cb)  # compile warmup
    t0 = time.perf_counter()
    res = Darlin(cfg, reporter=quiet).fit_blocks(cb)
    dt = time.perf_counter() - t0
    # the solver may early-stop on its relative-objective epsilon: rate
    # uses the pass count it actually ran, not the configured ceiling
    iters_ran = max(int(res.get("iters", cfg.solver.block_iters)), 1)
    out["block_passes"] = iters_ran
    out["block_passes_per_sec"] = round(blocks * iters_ran / dt, 2)
    out["example_blocks_per_sec"] = round(n * blocks * iters_ran / dt, 1)
    out["objv"] = round(res["objv"], 4)
    out["nnz_w"] = res.get("nnz_w")
    return out


def child_wd_push() -> dict:
    """Wide&Deep push-mode matrix on the (data=4, kv=2) virtual CPU mesh:
    per_worker vs aggregate vs int8-quantized wall-clock on identical
    batches (the embedding push is W&D's dominant traffic, so the mode
    choice is this app's biggest wire knob; BASELINE.json lists W&D as a
    parity config and the quantized mode is new this round). Multi-device
    modes can't run on one real chip — recorded as platform cpu-sim."""
    import jax

    from parameter_server_tpu.data.batch import BatchBuilder
    from parameter_server_tpu.data.synthetic import make_sparse_logistic
    from parameter_server_tpu.models.wide_deep import WideDeep
    from parameter_server_tpu.parallel.mesh import make_mesh
    from parameter_server_tpu.utils.metrics import ProgressReporter

    D, K = 4, 2
    num_keys, bs, nnz = 1 << 18, 2048, 16
    n = bs * D * 8  # 8 full D-shard groups per mode
    labels, keys, vals, _ = make_sparse_logistic(
        n, 1 << 16, nnz_per_example=nnz, noise=0.4, seed=13
    )
    builder = BatchBuilder(
        num_keys=num_keys, batch_size=bs, max_nnz_per_example=4 * nnz
    )
    batches = [
        builder.build(labels[i : i + bs], keys[i : i + bs], vals[i : i + bs])
        for i in range(0, n, bs)
    ]
    mesh = make_mesh(D, K)
    out: dict = {"platform": "cpu-sim", "mesh": f"data={D} kv={K}",
                 "emb_dim": 16}
    spc = 2  # scanned microsteps per device call
    for mode in ("per_worker", "aggregate", "quantized"):
        app = WideDeep(
            num_keys=num_keys, emb_dim=16, hidden=[64, 32], mesh=mesh,
            push_mode=mode, steps_per_call=spc, max_delay=2,
            reporter=ProgressReporter(print_fn=lambda *_: None),
        )
        app.train(batches[: D * spc], report_every=10**6)  # compile warmup
        jax.block_until_ready(app.emb_state["w"])
        t0 = time.perf_counter()
        app.train(batches, report_every=10**6)
        jax.block_until_ready(app.emb_state["w"])
        out[f"{mode}_ex_per_sec"] = round(n / (time.perf_counter() - t0), 1)
    out["aggregate_speedup"] = round(
        out["aggregate_ex_per_sec"] / out["per_worker_ex_per_sec"], 3
    )
    out["quantized_vs_per_worker"] = round(
        out["quantized_ex_per_sec"] / out["per_worker_ex_per_sec"], 3
    )
    return out


def child_ingest() -> dict:
    """Host ingest throughput (platform-independent): native parse-only
    MB/s and parse+build (localize) examples/sec per stream — the numbers
    that bound e2e on co-located hardware (SURVEY §7.4: the parser must be
    fast enough to keep chips busy)."""
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.batch import BatchBuilder
    from parameter_server_tpu.data.reader import MinibatchReader
    from parameter_server_tpu.data.synthetic import (
        make_sparse_logistic,
        write_libsvm,
    )

    n = 1 << 17
    labels, keys, vals, _ = make_sparse_logistic(
        n, 1 << 16, nnz_per_example=NNZ_PER, noise=0.4, seed=23
    )
    out: dict = {"native": native.native_available()}
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "part.svm")
        write_libsvm(p, labels, keys, vals)
        sz = os.path.getsize(p)
        if native.native_available():
            runs = []
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                rows = sum(len(fl[0]) for fl in native.iter_chunks(p, "libsvm"))
                runs.append(time.perf_counter() - t0)
            dt = statistics.median(runs)
            out["parse_mb_per_sec"] = round(sz / dt / 1e6, 1)
            out["parse_ex_per_sec"] = round(rows / dt, 1)
        builder = BatchBuilder(
            num_keys=NUM_KEYS, batch_size=4096, max_nnz_per_example=4 * NNZ_PER
        )
        r = MinibatchReader([p], "libsvm", builder)
        t0 = time.perf_counter()
        cnt = sum(b.num_examples for b in r)
        dt = time.perf_counter() - t0
        out["parse_build_ex_per_sec"] = round(cnt / dt, 1)

        # parse-once columnar cache (ref: text2proto + the SlotReader
        # block cache): first call parses and populates, repeat runs
        # fingerprint-hit and mmap-load — the payoff the cache exists
        # for. The load is lazy (mmap pages in on first access), so
        # cache_load_s is the re-parse cost AVOIDED at open time, not a
        # data-throughput claim
        from parameter_server_tpu.data import blockcache
        from parameter_server_tpu.utils.config import PSConfig

        cfg = PSConfig()
        cfg.data.files = [p]
        cfg.data.format = "libsvm"
        cfg.data.num_keys = NUM_KEYS
        cfg.data.cache_dir = os.path.join(d, "cache")
        cfg.data.max_nnz_per_example = 4 * NNZ_PER
        cfg.solver.minibatch = 4096
        cfg.solver.feature_blocks = 16
        t0 = time.perf_counter()
        blockcache.cached_column_blocks(cfg)  # parse + populate
        build_s = time.perf_counter() - t0
        loads = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            blockcache.cached_column_blocks(cfg)  # fingerprint hit
            loads.append(time.perf_counter() - t0)
        load_s = statistics.median(loads)
        out["cache_build_s"] = round(build_s, 2)
        out["cache_load_s"] = round(load_s, 3)
        out["cache_load_speedup"] = round(build_s / max(load_s, 1e-9), 1)
    return out


_ACK_SERVER_CODE = """
import sys, time
sys.path.insert(0, {repo!r})
from parameter_server_tpu.parallel.control import RpcServer
PTS = int(time.time() * 1e6)  # the "publish" this bench process serves
FRESH = [False]  # toggled server-side, like the real serving tier
def _ack(h, a):
    if h.get("cmd") == "fresh":
        FRESH[0] = bool(h.get("on"))
        return ({{"ok": True}}, {{}})
    if FRESH[0]:
        # freshness-armed rounds (ISSUE 17): the reply carries the
        # publish stamp + measured age through the v3 binary slots,
        # the exact decoration a serving-tier pull reply pays. The
        # toggle is a control command, not a per-request field: the
        # armed rounds measure the decoration, not a JSON-tail tax
        # production requests never carry.
        now = int(time.time() * 1e6)
        return ({{"ok": True, "pts": PTS, "_age_us": now - PTS}}, {{}})
    return ({{"ok": True}}, {{}})
srv = RpcServer(_ack).start()
print("ADDR", srv.address, flush=True)
while not srv._stop.wait(0.5):
    pass
"""


def child_wire_rpc() -> dict:
    """Loopback RPC tier microbench, three blocks:

    1. A real ShardServer + ServerHandle over TCP in one process —
       pull/push round-trips/sec plus the p50/p99 client-observed
       latencies the telemetry plane records per command. ONE handle is
       reused for every repeat, so connection setup never pollutes p50.
    2. Pipelined-vs-lockstep push round trips at W=8 against an ack
       RpcServer in a SEPARATE process (same-process client+server share
       a GIL and mask the overlap the async engine exists for).
    3. A payload-size sweep (4 KiB -> 4 MiB) reporting MB/s for lockstep
       vs W=8 pipelined pushes through the zero-copy frame path, plus a
       compressible 1 MiB cell showing the adaptive-zip savings counter.

    The process's merged telemetry snapshot rides along so the full
    results file tracks RPC latency next to throughput."""
    import statistics as stats
    import subprocess
    import sys as sys_mod

    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.parallel.control import RpcClient
    from parameter_server_tpu.parallel.multislice import ServerHandle, ShardServer
    from parameter_server_tpu.utils.config import PSConfig
    from parameter_server_tpu.utils.keyrange import KeyRange
    from parameter_server_tpu.utils.metrics import (
        hist_percentile,
        latency_histograms,
        telemetry_snapshot,
        wire_counters,
    )

    # -- block 1: real ShardServer round trips (handle reused throughout)
    n_keys, iters = 1 << 18, 300
    srv = ShardServer(
        Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2),
        KeyRange(0, n_keys),
    ).start()
    handle = ServerHandle(srv.address, 0, 0, PSConfig(), range_size=n_keys)
    rng = np.random.default_rng(7)
    keys = np.unique(rng.integers(1, n_keys, 1024)).astype(np.int64)
    g = rng.normal(size=len(keys)).astype(np.float32)
    for _ in range(20):  # warmup: jit the updater, settle TCP
        handle.pull(keys)
        handle.push(keys, g)
    latency_histograms.reset()
    t0 = time.perf_counter()
    for _ in range(iters):
        handle.pull(keys)
        handle.push(keys, g)
    dt = time.perf_counter() - t0
    snap = latency_histograms.snapshot()
    out: dict = {
        "platform": "cpu-loopback",
        "roundtrips_per_sec": round(2 * iters / dt, 1),
        "touched_keys": int(len(keys)),
    }
    for cmd in ("pull", "push"):
        s = snap.get(f"client.{cmd}")
        if s:
            out[f"{cmd}_p50_ms"] = round(hist_percentile(s, 0.5) * 1e3, 3)
            out[f"{cmd}_p99_ms"] = round(hist_percentile(s, 0.99) * 1e3, 3)
    # W=8 pipelined pushes against the SAME ShardServer (updater applies
    # serialize server-side; the win is the removed per-call lockstep)
    t0 = time.perf_counter()
    for _ in range(iters):
        handle.push(keys, g)
    out["push_rps_shard_lockstep"] = round(
        iters / (time.perf_counter() - t0), 1
    )
    t0 = time.perf_counter()
    futs = [handle.push_async(keys, g) for _ in range(iters)]
    for f in futs:
        f.result()
    out["push_rps_shard_pipelined_w8"] = round(
        iters / (time.perf_counter() - t0), 1
    )
    handle.shutdown()
    handle.close()

    # -- blocks 2+3: ack server in its own process (no shared GIL)
    repo = os.path.dirname(os.path.abspath(__file__))
    ack = subprocess.Popen(
        [sys_mod.executable, "-c", _ACK_SERVER_CODE.format(repo=repo)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        line = ack.stdout.readline()
        if not line.startswith("ADDR "):
            # died before binding: surface ITS error, not an IndexError
            err = (ack.stderr.read() or "no stderr").strip()[-400:]
            raise RuntimeError(f"ack server failed to start: {err}")
        addr = line.split()[1]
        payload = {  # a per-shard push segment's shape (matches block 1)
            "keys": np.arange(1024, dtype=np.uint32),
            "g": rng.normal(size=1024).astype(np.float32),
        }
        lockstep = RpcClient(addr, window=1)
        pipelined = RpcClient(addr, window=8)
        for cli in (lockstep, pipelined):  # settle TCP + warm both paths
            fs = [cli.call_async("push", arrays=payload) for _ in range(100)]
            for f in fs:
                f.result()

        def _rps_lockstep(n: int) -> float:
            t0 = time.perf_counter()
            for _ in range(n):
                lockstep.call("push", arrays=payload)
            return n / (time.perf_counter() - t0)

        def _rps_pipelined(n: int) -> float:
            t0 = time.perf_counter()
            fs = [pipelined.call_async("push", arrays=payload) for _ in range(n)]
            for f in fs:
                f.result()
            return n / (time.perf_counter() - t0)

        def _freshness(on: bool) -> None:
            pipelined.call("fresh", on=int(on))
            lockstep.call("fresh", on=int(on))

        # INTERLEAVED rounds, median per-round ratio: shared-host noise
        # (this is a loopback bench on whatever machine the driver uses)
        # hits both modes of a round alike instead of biasing one side
        rounds = [
            (_rps_lockstep(500), _rps_pipelined(500)) for _ in range(5)
        ]
        ls = stats.median(r[0] for r in rounds)
        pp = stats.median(r[1] for r in rounds)
        out["push_rps_lockstep"] = round(ls, 1)
        out["push_rps_pipelined_w8"] = round(pp, 1)
        out["pipelined_speedup_w8"] = round(
            stats.median(p / l for l, p in rounds), 2
        )

        # payload sweep: incompressible float32 with zip=True — the
        # adaptive probe must DECLINE every one of these (zlib on random
        # grads is pure CPU loss), so the sweep rides the probe-and-skip
        # path production compressed runs take. Same interleaved-rounds
        # discipline as the headline ratio.
        skipped0 = wire_counters.get("wire_comp_skipped")
        sweep: dict = {}
        for kib in (4, 64, 1024, 4096):
            nb = kib << 10
            arr = {"g": rng.normal(size=nb // 4).astype(np.float32)}
            reps = max(8, min(200, (16 << 20) // nb))
            cells = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    lockstep.call("push", arrays=arr, zip=True)
                mb_ls = nb * reps / (time.perf_counter() - t0) / 1e6
                t0 = time.perf_counter()
                fs = [
                    pipelined.call_async("push", arrays=arr, zip=True)
                    for _ in range(reps)
                ]
                for f in fs:
                    f.result()
                mb_pp = nb * reps / (time.perf_counter() - t0) / 1e6
                cells.append((mb_ls, mb_pp))
            sweep[f"{kib}KiB"] = {
                "lockstep_mb_s": round(stats.median(c[0] for c in cells), 1),
                "pipelined_mb_s": round(stats.median(c[1] for c in cells), 1),
                "speedup": round(
                    stats.median(c[1] / c[0] for c in cells), 2
                ),
            }
        out["sweep"] = sweep
        out["mb_s_1mib_pipelined"] = sweep["1024KiB"]["pipelined_mb_s"]

        # compressible cell: zeros under zip=True — the probe accepts,
        # and the savings land in the wire_bytes_saved counter
        saved0 = wire_counters.get("wire_bytes_saved")
        z = {"g": np.zeros(1 << 18, np.float32)}
        t0 = time.perf_counter()
        fs = [
            pipelined.call_async("push", arrays=z, zip=True)
            for _ in range(40)
        ]
        for f in fs:
            f.result()
        out["comp_mb_s_1mib_zip"] = round(
            40 * (1 << 20) / (time.perf_counter() - t0) / 1e6, 1
        )
        out["wire_bytes_saved"] = wire_counters.get("wire_bytes_saved") - saved0
        # delta over this child's sweep (same semantics as bytes_saved):
        # every incompressible sweep array must have been probe-declined
        out["wire_comp_skipped"] = (
            wire_counters.get("wire_comp_skipped") - skipped0
        )

        # flight-recorder overhead guard (ISSUE 9 acceptance: armed push
        # throughput within 5% of disarmed). Interleaved off/on rounds so
        # shared-host noise hits both sides of a round alike; configure()
        # rebinds the module-level record between the identity-pinned
        # no-op and the live ring append, which is exactly what the
        # always-on instrumentation pays in production.
        import tempfile as tmp_mod

        from parameter_server_tpu.utils import flightrec

        bb_dir = tmp_mod.mkdtemp(prefix="psbb_bench_")
        fr_rounds = []
        for _ in range(5):
            flightrec.configure(None)
            off = _rps_pipelined(400)
            flightrec.configure(
                bb_dir, process_name="bench-wire_rpc",
                flush_interval_s=0, watchdog_interval_s=60,
            )
            on = _rps_pipelined(400)
            fr_rounds.append((off, on))
        flightrec.configure(None)
        out["push_rps_flightrec_off"] = round(
            stats.median(r[0] for r in fr_rounds), 1
        )
        out["push_rps_flightrec_on"] = round(
            stats.median(r[1] for r in fr_rounds), 1
        )
        out["flightrec_ratio"] = round(
            stats.median(on / off for off, on in fr_rounds), 3
        )

        # FULL observability overhead guard (ISSUE 13 acceptance: push
        # throughput with flightrec + time-series rolling + the sampling
        # profiler ALL armed within 5% of all-off; ISSUE 14 extends the
        # armed side with the audit event spool — every push's
        # issue/reply now also passes the spool's admission filter, the
        # exact cost a live-audited production node pays; ISSUE 15 adds
        # head-sampled tracing at sample=16 WITH tail capture, so the
        # always-on slow-trace retention — pending buffers, promotion
        # checks, limbo ring — is inside the same ratio; ISSUE 17 arms
        # the freshness plane: every armed-round reply carries the
        # publish stamp + measured age through the v3 binary header
        # slots, the serving tier's per-reply decoration). The roller
        # runs far above its production cadence (0.1 s vs one roll per
        # heartbeat) and the profiler at its default Hz, so this is a
        # conservative ceiling on what a fully-instrumented node pays.
        from parameter_server_tpu.utils import profiler as prof_mod
        from parameter_server_tpu.utils import timeseries as ts_mod
        from parameter_server_tpu.utils import trace as trace_mod

        tr_dir = tmp_mod.mkdtemp(prefix="pstrace_bench_")
        obs_rounds = []
        for _ in range(5):
            flightrec.configure(None)
            flightrec.configure_spool(None)
            prof_mod.configure(0)
            trace_mod.configure(None)
            off = _rps_pipelined(400)
            flightrec.configure(
                bb_dir, process_name="bench-wire_rpc",
                flush_interval_s=0, watchdog_interval_s=60,
            )
            flightrec.configure_spool(4096)
            prof_mod.configure(prof_mod.DEFAULT_HZ)
            trace_mod.configure(
                tr_dir, process_name="bench-wire_rpc",
                sample=16, tail=True,
            )
            roller = ts_mod.Roller(0.1)
            _freshness(True)
            try:
                on = _rps_pipelined(400)
            finally:
                _freshness(False)
                roller.close()
                prof_mod.configure(0)
                flightrec.configure(None)
                flightrec.configure_spool(None)
                trace_mod.configure(None)
            obs_rounds.append((off, on))
        out["push_rps_observability_off"] = round(
            stats.median(r[0] for r in obs_rounds), 1
        )
        out["push_rps_observability_on"] = round(
            stats.median(r[1] for r in obs_rounds), 1
        )
        out["observability_ratio"] = round(
            stats.median(on / off for off, on in obs_rounds), 3
        )
        # proof the tail-capture layer ENGAGED during the armed rounds
        # (a ratio measured with promotion never firing proves nothing)
        out["trace_tail_promoted"] = wire_counters.get(
            "trace_tail_promoted"
        )
        # ... and proof the freshness decoration engaged: one echoed
        # age, measured by the server against its own publish stamp
        _freshness(True)
        rep, _ = pipelined.call("push", arrays=payload)
        _freshness(False)
        out["freshness_echo_age_us"] = int(rep.get("_age_us", -1))

        # ISSUE 15's MARGINAL cost, isolated: tracing armed (sample=16)
        # on BOTH sides, tail capture toggled — what the retention layer
        # itself adds on top of the tracing plane. The full-stack
        # observability_ratio above now includes armed tracing, whose
        # own per-span cost (span + wire-context header) dominates on
        # this pure-RPC loop; this ratio answers "does TAIL CAPTURE
        # blow the budget" without conflating the two.
        tail_rounds = []
        for _ in range(5):
            trace_mod.configure(
                tr_dir, process_name="bench-wire_rpc", sample=16,
                tail=False,
            )
            off = _rps_pipelined(400)
            trace_mod.configure(
                tr_dir, process_name="bench-wire_rpc", sample=16,
                tail=True,
            )
            on = _rps_pipelined(400)
            tail_rounds.append((off, on))
        trace_mod.configure(None)
        out["trace_tail_ratio"] = round(
            stats.median(on / off for off, on in tail_rounds), 3
        )
        lockstep.close()
        pipelined.close()
    finally:
        ack.kill()
        try:
            ack.wait(timeout=10)  # reap: no zombie for the suite's life
        except subprocess.TimeoutExpired:
            pass
        ack.stdout.close()
        ack.stderr.close()
    out["telemetry"] = telemetry_snapshot()
    return out


def child_server_apply() -> dict:
    """Shard-server batched apply engine A/B, two blocks:

    1. Push throughput at W=8 concurrent pipelined clients against a real
       ShardServer on loopback, apply engine ON (pushes coalesce into
       segment-summed single-dispatch batches; pulls serve from the RCU
       snapshot) vs OFF ([server] apply_queue=0 — every push applies
       inline under the global lock, the pre-engine discipline).
       Interleaved rounds, median per-round ratio.
    2. Small-frame rps: 4 KiB pipelined pushes against a separate-process
       ack server with binary vs JSON headers (same interleaved-rounds
       discipline), plus the hdr_bytes_saved the codec banked."""
    import statistics as stats
    import subprocess
    import sys as sys_mod
    import threading

    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.parallel.control import RpcClient
    from parameter_server_tpu.parallel.multislice import ServerHandle, ShardServer
    from parameter_server_tpu.utils.config import PSConfig, ServerConfig
    from parameter_server_tpu.utils.keyrange import KeyRange
    from parameter_server_tpu.utils.metrics import (
        hist_percentile,
        latency_histograms,
        telemetry_snapshot,
        wire_counters,
    )

    n_keys = 1 << 18
    W, per_client = 8, 120
    rng = np.random.default_rng(7)
    keysets = [
        np.unique(rng.integers(1, n_keys, 1024)).astype(np.int64)
        for _ in range(W)
    ]
    gradsets = [
        rng.normal(size=len(k)).astype(np.float32) for k in keysets
    ]

    def _push_rate(batched: bool) -> float:
        scfg = ServerConfig() if batched else ServerConfig(apply_queue=0)
        srv = ShardServer(
            Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2),
            KeyRange(0, n_keys), server_cfg=scfg,
        ).start()
        handles = [
            ServerHandle(srv.address, 0, w, PSConfig(), range_size=n_keys)
            for w in range(W)
        ]
        try:
            for h, k, g in zip(handles, keysets, gradsets):  # warmup + sigs
                h.push(k, g)
            # concurrent warmup burst: compiles the engine's pow-2 union
            # buckets before the timed window
            futs = [
                h.push_async(k, g)
                for h, k, g in zip(handles, keysets, gradsets)
            ]
            for f in futs:
                f.result(timeout=120)
            barrier = threading.Barrier(W)
            errs: list = []

            def run(i: int) -> None:
                try:
                    barrier.wait()
                    futs = [
                        handles[i].push_async(keysets[i], gradsets[i])
                        for _ in range(per_client)
                    ]
                    for f in futs:
                        f.result(timeout=120)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errs.append(e)
            ts = [
                threading.Thread(target=run, args=(i,)) for i in range(W)
            ]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=180)
            dt = time.perf_counter() - t0
            if errs:
                raise errs[0]
            return W * per_client / dt
        finally:
            handles[0].shutdown()
            for h in handles:
                h.close()

    coalesced0 = wire_counters.get("push_coalesced")
    # same ABBA symmetry as the header cell below: serial, batched,
    # batched, serial per round, harmonic-combined — monotonic host-load
    # drift cancels inside each round's ratio instead of flattering
    # whichever mode runs later
    n_round = W * per_client
    rounds = []
    for _ in range(2):
        s1 = _push_rate(False)
        b1 = _push_rate(True)
        b2 = _push_rate(True)
        s2 = _push_rate(False)
        rounds.append((
            2 * n_round / (n_round / s1 + n_round / s2),
            2 * n_round / (n_round / b1 + n_round / b2),
        ))
    out: dict = {
        "platform": "cpu-loopback",
        "clients": W,
        "push_rps_serial_w8": round(stats.median(r[0] for r in rounds), 1),
        "push_rps_batched_w8": round(stats.median(r[1] for r in rounds), 1),
        "batched_speedup_w8": round(
            stats.median(b / s for s, b in rounds), 2
        ),
        "push_coalesced": wire_counters.get("push_coalesced") - coalesced0,
    }
    bsnap = latency_histograms.snapshot().get("server.apply_batch.n")
    if bsnap:
        # observe_scalar convention: value percentiles recover via * 1e6
        out["batch_p50"] = round(hist_percentile(bsnap, 0.5) * 1e6, 1)
        out["batch_p99"] = round(hist_percentile(bsnap, 0.99) * 1e6, 1)

    # -- block 2: binary vs JSON headers at 4 KiB frames (ack server in
    # its own process so the codec cost isn't masked by a shared GIL)
    repo = os.path.dirname(os.path.abspath(__file__))
    ack = subprocess.Popen(
        [sys_mod.executable, "-c", _ACK_SERVER_CODE.format(repo=repo)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        line = ack.stdout.readline()
        if not line.startswith("ADDR "):
            err = (ack.stderr.read() or "no stderr").strip()[-400:]
            raise RuntimeError(f"ack server failed to start: {err}")
        addr = line.split()[1]
        payload = {"g": rng.normal(size=1024).astype(np.float32)}  # 4 KiB
        saved0 = wire_counters.get("hdr_bytes_saved")
        clients = {
            c: RpcClient(addr, window=8, hdr_codec=c) for c in ("json", "bin")
        }
        for cli in clients.values():  # settle TCP, negotiate codecs
            fs = [cli.call_async("push", arrays=payload) for _ in range(100)]
            for f in fs:
                f.result()

        def _elapsed(cli, n: int = 250) -> float:
            t0 = time.perf_counter()
            fs = [cli.call_async("push", arrays=payload) for _ in range(n)]
            for f in fs:
                f.result()
            return time.perf_counter() - t0

        # symmetric ABBA rounds (json, bin, bin, json): linear load drift
        # on a shared host cancels exactly inside each round's ratio,
        # instead of biasing whichever codec ran later
        hdr_rounds = []
        for _ in range(6):
            tj1 = _elapsed(clients["json"])
            tb1 = _elapsed(clients["bin"])
            tb2 = _elapsed(clients["bin"])
            tj2 = _elapsed(clients["json"])
            hdr_rounds.append((500 / (tj1 + tj2), 500 / (tb1 + tb2)))
        out["push_rps_4k_json"] = round(
            stats.median(r[0] for r in hdr_rounds), 1
        )
        out["push_rps_4k_bin"] = round(
            stats.median(r[1] for r in hdr_rounds), 1
        )
        out["hdr_speedup_4k"] = round(
            stats.median(b / j for j, b in hdr_rounds), 3
        )
        out["hdr_bytes_saved"] = (
            wire_counters.get("hdr_bytes_saved") - saved0
        )
        for cli in clients.values():
            cli.close()
    finally:
        ack.kill()
        try:
            ack.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        ack.stdout.close()
        ack.stderr.close()
    out["telemetry"] = telemetry_snapshot()
    return out


def child_quant_wire() -> dict:
    """Quantized push/pull wire A/B (ISSUE 6 acceptance cell): the
    linear-method e2e workload (synthetic sparse logistic regression)
    trained over the REAL wire tier (ShardServer + ServerHandle on
    loopback) three times — float32, int8+error-feedback, int16 — with
    identical seeds. Reports the measured push payload ratio (the
    ``wire_push_payload_bytes`` counter, f32 / quantized; acceptance:
    >= 3x at int8) and AUC per arm (progressive validation over the
    stream's second half + a held-out slice scored against the final
    pulled weights; acceptance: |dAUC| <= 0.002 at equal seeds)."""
    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.models import metrics as M
    from parameter_server_tpu.parallel.multislice import ServerHandle, ShardServer
    from parameter_server_tpu.utils.config import PSConfig
    from parameter_server_tpu.utils.keyrange import KeyRange
    from parameter_server_tpu.utils.metrics import wire_counters

    n_keys = 1 << 14
    nnz = NNZ_PER
    bsz, n_batches, n_holdout = 2048, 20, 4096
    rng = np.random.default_rng(23)
    w_true = rng.normal(size=n_keys) * 1.2
    n_total = bsz * n_batches + n_holdout
    kb_all = rng.integers(0, n_keys, size=(n_total, nnz))
    logits = w_true[kb_all].sum(axis=1) / np.sqrt(nnz)
    y_all = (rng.random(n_total) < 1 / (1 + np.exp(-logits))).astype(
        np.float64
    )

    def _arm(quant: str) -> dict:
        srv = ShardServer(
            # alpha/l1 sized for per-example-MEAN gradients on this
            # workload (the localizer-normalized form): the default l1=1
            # would pin every weight at zero and flatline the AUC both
            # arms are compared on
            Ftrl(alpha=1.0, beta=BETA, lambda_l1=1e-4, lambda_l2=L2),
            KeyRange(0, n_keys + 1),
        ).start()
        cfg = PSConfig()
        cfg.wire.quant = quant
        h = ServerHandle(srv.address, 0, 0, cfg, range_size=n_keys + 1)
        try:
            # warmup: negotiation round trip AND one full-size push/pull
            # so the server's pow-2 apply bucket compiles outside the
            # timed window (arms would otherwise be ordering-biased)
            warm = np.arange(1, n_keys + 1, dtype=np.int64)
            h.push(warm, np.zeros(n_keys, np.float32))
            h.pull(warm)
            pay0 = wire_counters.get("wire_push_payload_bytes")
            ys, ps = [], []
            t0 = time.perf_counter()
            for b in range(n_batches):
                s = slice(b * bsz, (b + 1) * bsz)
                kb, y = kb_all[s], y_all[s]
                uniq, inv = np.unique(kb, return_inverse=True)
                keys = (uniq + 1).astype(np.int64)  # row 0 = pad row
                w = h.pull(keys).astype(np.float64)
                logit_hat = w[inv.reshape(bsz, nnz)].sum(axis=1)
                p = 1 / (1 + np.exp(-logit_hat))
                err = p - y
                g = np.zeros(len(uniq))
                np.add.at(
                    g, inv.reshape(bsz, nnz).ravel(), np.repeat(err, nnz)
                )
                h.push(keys, (g / bsz).astype(np.float32))
                if b >= n_batches // 2:
                    ys.append(y)
                    ps.append(p)
            dt = time.perf_counter() - t0
            payload = wire_counters.get("wire_push_payload_bytes") - pay0
            w_full = h.pull(
                np.arange(1, n_keys + 1, dtype=np.int64)
            ).astype(np.float64)
            kb_h = kb_all[bsz * n_batches:]
            p_h = 1 / (1 + np.exp(-w_full[kb_h].sum(axis=1)))
            return {
                "auc": round(
                    float(M.auc(np.concatenate(ys), np.concatenate(ps))), 4
                ),
                "holdout_auc": round(
                    float(M.auc(y_all[bsz * n_batches:], p_h)), 4
                ),
                "push_payload_mb": round(payload / 1e6, 3),
                "ex_per_sec": round(bsz * n_batches / dt, 1),
                "residual_peak_x1e6": wire_counters.get(
                    "wire_quant_residual_peak"
                ),
            }
        finally:
            h.shutdown()
            h.close()

    out: dict = {"platform": "cpu-loopback", "config":
                 f"keys=2^14 nnz={nnz} batches={n_batches}x{bsz} ftrl"}
    # throwaway warmup arm: the seeds pin every batch's unique-key count,
    # so one full pass compiles every eager gather/updater shape the
    # measured arms will hit — without it the first arm eats them all and
    # the ex_per_sec comparison is ordering, not codec
    _arm("off")
    arms = {}
    for quant in ("off", "int8", "int16"):
        wire_counters.reset()
        arms[quant] = _arm(quant)
    out["auc_f32"] = arms["off"]["auc"]
    out["holdout_auc_f32"] = arms["off"]["holdout_auc"]
    out["push_payload_mb_f32"] = arms["off"]["push_payload_mb"]
    out["ex_per_sec_f32"] = arms["off"]["ex_per_sec"]
    for quant in ("int8", "int16"):
        a = arms[quant]
        out[f"auc_{quant}"] = a["auc"]
        out[f"holdout_auc_{quant}"] = a["holdout_auc"]
        out[f"push_payload_mb_{quant}"] = a["push_payload_mb"]
        out[f"ex_per_sec_{quant}"] = a["ex_per_sec"]
        out[f"residual_peak_x1e6_{quant}"] = a["residual_peak_x1e6"]
        out[f"push_bytes_ratio_{quant}"] = round(
            arms["off"]["push_payload_mb"] / max(a["push_payload_mb"], 1e-9),
            2,
        )
        out[f"auc_delta_{quant}"] = round(
            abs(a["holdout_auc"] - arms["off"]["holdout_auc"]), 4
        )
    return out


def child_backend() -> dict:
    """Transport-neutral KV backend A/B (ISSUE 11 acceptance cell).

    Both backends are driven by the IDENTICAL client code — the
    canonical ``parallel.backend.train_linear`` loop the parity tests
    pin — so every ratio below is transport, not client drift:

    - trainer arm: FTRL linear run on socket (2 loopback ShardServers)
      vs mesh (8-device cpu-sim kv mesh), AUC + ex/s per arm, plus the
      int8 quantized-collective mesh arm (error feedback preserved):
      measured payload bytes ratio and |dAUC| vs the mesh f32 arm.
    - push sweep: keys-per-push U in {2^8..2^16}, pipelined socket
      pushes vs mesh sharded-update dispatches, rows/sec per side. The
      compact line carries the large-batch speedup and the CROSSOVER
      (smallest U where in-mesh wins) — the number that says when to
      leave the socket tier for ICI."""
    import jax

    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.parallel.backend import (
        local_socket_backend,
        train_linear,
    )
    from parameter_server_tpu.parallel.meshbackend import MeshBackend
    from parameter_server_tpu.utils.metrics import wire_counters

    num_keys = 1 << 18
    kv = min(8, len(jax.devices()))

    def _updater() -> Ftrl:
        # sized for per-example-mean gradients (see child_quant_wire)
        return Ftrl(alpha=1.0, beta=BETA, lambda_l1=1e-4, lambda_l2=L2)

    def _socket():
        return local_socket_backend(_updater, num_keys, num_servers=2)

    out: dict = {
        "platform": "cpu-sim",
        "config": f"keys=2^18 mesh_kv={kv} socket_servers=2",
    }

    # -- trainer arm: one loop, three transports ---------------------------
    rng = np.random.default_rng(23)
    nnz, bsz, nb = 32, 2048, 12
    w_true = rng.normal(size=num_keys - 1) * 1.2
    kb = rng.integers(0, num_keys - 1, size=(bsz * nb, nnz))
    logits = w_true[kb].sum(axis=1) / np.sqrt(nnz)
    y = (rng.random(bsz * nb) < 1 / (1 + np.exp(-logits))).astype(
        np.float64
    )

    sb = _socket()
    try:
        train_linear(sb, kb[: bsz * 2], y[: bsz * 2], bsz)  # warm jits/TCP
        t0 = time.perf_counter()
        res_s = train_linear(sb, kb, y, bsz)
        out["train_ex_per_sec_socket"] = round(
            res_s["examples"] / (time.perf_counter() - t0), 1
        )
        out["train_auc_socket"] = round(res_s["auc"], 4)
    finally:
        sb.close()

    payloads: dict[str, int] = {}
    for quant in ("off", "int8"):
        mb = MeshBackend(_updater(), num_keys, kv_shards=kv, quant=quant)
        train_linear(mb, kb[: bsz * 2], y[: bsz * 2], bsz)  # compile
        pay0 = wire_counters.get("mesh_push_payload_bytes")
        t0 = time.perf_counter()
        res_m = train_linear(mb, kb, y, bsz)
        dt = time.perf_counter() - t0
        payloads[quant] = (
            wire_counters.get("mesh_push_payload_bytes") - pay0
        )
        tag = "mesh" if quant == "off" else "mesh_int8"
        out[f"train_ex_per_sec_{tag}"] = round(res_m["examples"] / dt, 1)
        out[f"train_auc_{tag}"] = round(res_m["auc"], 4)
    out["auc_delta_int8"] = round(
        abs(out["train_auc_mesh_int8"] - out["train_auc_mesh"]), 4
    )
    out["quant_bytes_ratio_int8"] = round(
        payloads["off"] / max(payloads["int8"], 1), 2
    )
    out["push_payload_mb_f32"] = round(payloads["off"] / 1e6, 3)
    out["push_payload_mb_int8"] = round(payloads["int8"] / 1e6, 3)

    # -- push-throughput sweep: where does in-mesh win? --------------------
    mb = MeshBackend(_updater(), num_keys, kv_shards=kv)
    sb = _socket()
    sweep: dict = {}
    try:
        for u_log2 in (8, 10, 12, 14, 16):
            u = 1 << u_log2
            keys = np.sort(
                rng.choice(
                    np.arange(1, num_keys, dtype=np.int64), size=u,
                    replace=False,
                )
            )
            g = (rng.normal(size=(u, 1)) * 0.01).astype(np.float32)
            reps = max(4, min(48, (1 << 21) // u))
            mb.push(keys, g)
            mb.flush()  # compile this bucket outside the timed window
            t0 = time.perf_counter()
            for _ in range(reps):
                mb.push(keys, g)
            mb.flush()
            mesh_rate = reps * u / (time.perf_counter() - t0)
            sb.push(keys, g)  # warm the server's apply bucket
            sb.flush()
            t0 = time.perf_counter()
            futs = [sb.push_async(keys, g) for _ in range(reps)]
            for f in futs:
                f.result()
            sock_rate = reps * u / (time.perf_counter() - t0)
            sweep[f"u{u}"] = {
                "mesh_rows_per_sec": round(mesh_rate, 1),
                "socket_rows_per_sec": round(sock_rate, 1),
                "speedup": round(mesh_rate / sock_rate, 2),
            }
    finally:
        sb.close()
    out["push_sweep"] = sweep
    out["mesh_vs_socket_push_speedup"] = sweep["u65536"]["speedup"]
    # the crossover: smallest keys-per-push where the in-mesh path wins
    # (0 = socket won everywhere in the sweep)
    out["crossover_keys_per_push"] = next(
        (
            1 << lg
            for lg in (8, 10, 12, 14, 16)
            if sweep[f"u{1 << lg}"]["speedup"] >= 1.0
        ),
        0,
    )
    return out


#: the serve cell's shard server, run in its OWN process (real serving
#: topology — a same-process server shares the client GIL and bottlenecks
#: both arms on each other). Prints ADDR on bind; on shutdown prints one
#: STATS line with its counters (incl. the server-side wire gauges the
#: cell reports: withheld peak, quantized-pull bytes saved).
_SERVE_SERVER_CODE = """
import sys
sys.path.insert(0, {repo!r})
import json
from parameter_server_tpu.kv.updaters import Sgd
from parameter_server_tpu.parallel.multislice import ShardServer
from parameter_server_tpu.utils.config import ServeConfig
from parameter_server_tpu.utils.keyrange import KeyRange
from parameter_server_tpu.utils.metrics import wire_counters

scfg = ServeConfig(
    cache=True, ttl_ms=1000, max_stale_ms=4000, hot_min_pulls=2,
    encode_cache_entries={enc}, snapshot_keys_max={snap},
    shed_queue_depth={shedq}, retry_after_ms=20,
)
srv = ShardServer(Sgd(eta=0.1), KeyRange(0, {nkeys}), serve_cfg=scfg)
print("ADDR " + srv.address, flush=True)
srv.serve_forever()
stats = dict(srv.counters)
stats["withheld_peak"] = wire_counters.get("wire_withheld_bytes_peak")
stats["quant_bytes_saved"] = wire_counters.get("wire_quant_bytes_saved")
print("STATS " + json.dumps(stats), flush=True)
"""


def child_serve() -> dict:
    """Online serving plane A/B (ISSUE 7 acceptance cell): 256 simulated
    read-mostly clients (32 per thread, each with its own Zipf(1.1)
    stream over 512 hot key sets, multiplexed over 8 handle connections
    per stack — the serving-frontend model: one shared cache per
    frontend process, many users behind it) against shard servers in
    their OWN processes, while a background writer churns versions
    (~50 pushes/s, read-mostly). Blocks:

      A/B     — INTERLEAVED rounds (median of per-round ratios, the
                wire_rpc discipline: shared-host noise hits adjacent
                rounds equally): baseline = the pre-serving-plane path
                (no client cache, no server encode cache/snapshot) vs
                cached = the full plane (client versioned key cache
                with TTL 1s + if_newer revalidation + single-flight
                refresh, server single-flight encode coalescing,
                hot-key detection, per-version host weights snapshot).
                hit_rate counts rows served from the local cache
                (fresh TTL hits + bounded-stale rows served while
                another thread's refresh was in flight).
      int8    — cached + [wire] quant_pull: wire refreshes ride the
                per-segment int8 codec (PR-6 carry-over: the codec now
                has a serving workload exercising it).
      shed    — cached under a push FLOOD with [serve] shed thresholds
                armed: revalidations carrying a cached fallback get
                retry-after instead of queueing behind the apply
                engine; p99 and the withheld-bytes peak stay bounded.

    Acceptance: cached pull QPS >= 5x baseline (median over rounds),
    hit rate and coalesce ratio on the compact line, bounded shed p99."""
    import statistics as stats_mod
    import subprocess
    import threading

    from parameter_server_tpu.filters.keycache import ClientKeyCache
    from parameter_server_tpu.parallel.multislice import ServerHandle
    from parameter_server_tpu.utils.config import PSConfig, ServeConfig
    from parameter_server_tpu.utils.metrics import wire_counters

    n_keys = 1 << 15
    n_sets, set_keys = 512, 32
    n_threads, clients_per = 8, 32  # 256 simulated clients per stack
    # a serving frontend is latency-bound on thread handoffs: the default
    # 5ms GIL switch interval turns every future-wait wakeup into a
    # convoy at p50 scale — tighten it for every arm alike
    sys.setswitchinterval(0.001)
    rng = np.random.default_rng(7)
    keysets = [
        np.sort(
            rng.choice(np.arange(1, n_keys), size=set_keys, replace=False)
        ).astype(np.int64)
        for _ in range(n_sets)
    ]
    ranks = np.arange(1, n_sets + 1, dtype=np.float64)
    pz = ranks ** -1.1  # Zipf(1.1) key-set popularity
    pz /= pz.sum()
    repo = os.path.dirname(os.path.abspath(__file__))

    class _Stack:
        """One serving stack: a shard server process + a frontend (8
        handles sharing one cache when serving) + its churn writer."""

        def __init__(
            self, plane: bool, serving: bool, quant: str = "off",
            shed: bool = False,
        ):
            self.scfg = ServeConfig(
                cache=plane, ttl_ms=1000, max_stale_ms=4000, hot_min_pulls=2,
                encode_cache_entries=256 if plane else 0,
                snapshot_keys_max=(1 << 22) if plane else 0,
                shed_queue_depth=4 if shed else 0, retry_after_ms=20,
            )
            self.shed = shed
            self.proc = subprocess.Popen(
                [sys.executable, "-c", _SERVE_SERVER_CODE.format(
                    repo=repo, nkeys=n_keys,
                    enc=self.scfg.encode_cache_entries,
                    snap=self.scfg.snapshot_keys_max,
                    shedq=self.scfg.shed_queue_depth,
                )],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            )
            line = self.proc.stdout.readline()
            if not line.startswith("ADDR "):
                err = (self.proc.stderr.read() or "no stderr").strip()[-400:]
                raise RuntimeError(f"serve shard server: {err}")
            addr = line.split()[1]
            cfg = PSConfig()
            cfg.serve = self.scfg
            cfg.wire.quant = quant
            cfg.wire.quant_pull = quant != "off"
            shared = ClientKeyCache(
                cap=self.scfg.cache_entries, ttl_s=self.scfg.ttl_ms / 1e3,
                max_stale_s=self.scfg.max_stale_ms / 1e3,
            )
            self.handles = [
                ServerHandle(
                    addr, 0, t, cfg, range_size=n_keys, serving=serving,
                    key_cache=shared,
                )
                for t in range(n_threads)
            ]
            self.writers = [
                ServerHandle(addr, 0, 99 + i, PSConfig(), range_size=n_keys)
                for i in range(2 if shed else 1)
            ]
            self.stop = threading.Event()
            self.wthreads = [
                threading.Thread(target=self._write_loop, args=(i,))
                for i in range(len(self.writers))
            ]
            for th in self.wthreads:
                th.start()

        def _write_loop(self, wi: int) -> None:
            wr = np.random.default_rng(11 + wi)
            futs: list = []
            while not self.stop.is_set():
                ks = keysets[int(wr.integers(0, n_sets))]
                g = (wr.normal(size=set_keys) * 0.01).astype(np.float32)
                if self.shed:
                    # flood: a window of async pushes keeps the apply
                    # queue deep so the shed thresholds actually trip
                    futs.append(self.writers[wi].push_async(ks, g))
                    if len(futs) >= 32:
                        for f in futs:
                            f.result()
                        futs.clear()
                else:
                    self.writers[wi].push(ks, g)  # read-mostly (~10/s)
                    self.stop.wait(0.1)
            for f in futs:
                try:
                    f.result()
                except Exception:  # noqa: BLE001 — teardown race
                    pass

        def run_round(self, dur_s: float, seed: int) -> tuple[int, list]:
            """Drive the frontend for one timed round; returns (pulls,
            latencies). Each thread multiplexes its 32 clients round-
            robin, every client on its own Zipf stream."""
            lats: list[list[float]] = [[] for _ in range(n_threads)]
            counts = [0] * n_threads

            def loop(t: int) -> None:
                crngs = [
                    np.random.default_rng(seed + t * clients_per + c)
                    for c in range(clients_per)
                ]
                picks = [
                    crngs[c].choice(n_sets, size=64, p=pz)
                    for c in range(clients_per)
                ]
                idx = [0] * clients_per
                h = self.handles[t]
                my = lats[t]
                end = time.perf_counter() + dur_s
                n = c = 0
                while True:
                    now = time.perf_counter()
                    if now >= end:
                        break
                    c = (c + 1) % clients_per
                    if idx[c] >= 64:
                        picks[c] = crngs[c].choice(n_sets, size=64, p=pz)
                        idx[c] = 0
                    ks = keysets[int(picks[c][idx[c]])]
                    idx[c] += 1
                    h.pull(ks)
                    my.append(time.perf_counter() - now)
                    n += 1
                counts[t] = n

            ths = [
                threading.Thread(target=loop, args=(t,))
                for t in range(n_threads)
            ]
            for th in ths:
                th.start()
            for th in ths:
                th.join()
            return sum(counts), [x for sub in lats for x in sub]

        def server_stats(self) -> dict:
            return self.writers[0].stats()

        def teardown(self) -> dict:
            """Stop writers, shut the server down, return its final
            counters (the STATS line it prints on exit)."""
            self.stop.set()
            for th in self.wthreads:
                th.join()
            for h in self.handles:
                h.close()
            try:
                self.writers[0].shutdown()
            except Exception:  # noqa: BLE001 — already gone
                pass
            for w in self.writers:
                w.close()
            try:
                sout, _ = self.proc.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                sout, _ = self.proc.communicate()
            st = {"pull_encodes": 0, "encode_reuse": 0, "not_modified": 0,
                  "shed": 0, "withheld_peak": 0, "quant_bytes_saved": 0}
            for ln in sout.splitlines():
                if ln.startswith("STATS "):
                    st.update(json.loads(ln[6:]))
            return st

    def _pct(lat: list, p: float) -> float:
        a = np.sort(np.asarray(lat))
        return float(a[int(p * (len(a) - 1))]) * 1e3 if len(a) else 0.0

    out: dict = {
        "platform": "cpu-loopback",
        "config": (
            f"keys=2^15 sets={n_sets}x{set_keys} zipf=1.1 "
            f"clients={n_threads * clients_per}/{n_threads}thr "
            f"rounds=5x0.8s interleaved"
        ),
    }

    # -- A/B: interleaved rounds over two live stacks ----------------------
    base = _Stack(plane=False, serving=False)
    cached = _Stack(plane=True, serving=True)
    base.run_round(1.2, seed=1)  # warm: jit, negotiation, steady caches
    cached.run_round(1.2, seed=1)
    wire_counters.reset()
    st0 = cached.server_stats()
    qps_b, qps_c, lat_b, lat_c = [], [], [], []
    total_c = 0
    for r in range(5):
        nb, lb = base.run_round(0.8, seed=10 + r)
        nc, lc = cached.run_round(0.8, seed=10 + r)
        qps_b.append(nb / 0.8)
        qps_c.append(nc / 0.8)
        lat_b += lb
        lat_c += lc
        total_c += nc
    snap = wire_counters.snapshot()
    base.teardown()
    st1 = cached.teardown()
    hits = (
        snap.get("serve_cache_hits", 0)
        + snap.get("serve_cache_stale_hits", 0)
    )
    enc = st1["pull_encodes"] - int(st0.get("pull_encodes", 0))
    reuse = st1["encode_reuse"] - int(st0.get("encode_reuse", 0))
    out["pull_qps_uncached"] = round(stats_mod.median(qps_b), 1)
    out["pull_qps_cached"] = round(stats_mod.median(qps_c), 1)
    out["qps_speedup_cached"] = round(stats_mod.median(
        [c / max(b, 1e-9) for b, c in zip(qps_b, qps_c)]
    ), 2)
    out["p50_ms_uncached"] = round(_pct(lat_b, 0.50), 3)
    out["p99_ms_uncached"] = round(_pct(lat_b, 0.99), 3)
    out["p50_ms_cached"] = round(_pct(lat_c, 0.50), 3)
    out["p99_ms_cached"] = round(_pct(lat_c, 0.99), 3)
    out["hit_rate"] = round(hits / max(total_c, 1), 4)
    out["fresh_hit_rate"] = round(
        snap.get("serve_cache_hits", 0) / max(total_c, 1), 4
    )
    out["coalesce_ratio"] = round(reuse / max(reuse + enc, 1), 4)
    out["not_modified"] = st1["not_modified"] - int(
        st0.get("not_modified", 0)
    )

    # -- int8 quant_pull arm (PR-6 carry-over exercised) -------------------
    wire_counters.reset()
    q = _Stack(plane=True, serving=True, quant="int8")
    q.run_round(1.0, seed=2)
    n_q, lat_q = q.run_round(2.0, seed=20)
    st_q = q.teardown()
    out["pull_qps_int8"] = round(n_q / 2.0, 1)
    out["p99_ms_int8"] = round(_pct(lat_q, 0.99), 3)
    out["int8_wire_bytes_saved"] = st_q["quant_bytes_saved"]

    # -- shed arm: push flood + admission control --------------------------
    wire_counters.reset()
    s = _Stack(plane=True, serving=True, shed=True)
    s.run_round(1.0, seed=3)
    n_s, lat_s = s.run_round(2.0, seed=30)
    st_s = s.teardown()
    out["pull_qps_shed"] = round(n_s / 2.0, 1)
    out["p99_ms_shed"] = round(_pct(lat_s, 0.99), 3)
    out["shed_count"] = st_s["shed"]
    out["shed_served"] = wire_counters.get("serve_shed_served")
    out["withheld_peak_shed"] = st_s["withheld_peak"]
    return out


_CHILDREN = {
    "headline": child_headline,
    "pipeline_e2e": child_pipeline_e2e,
    "ladder": child_ladder,
    "hbm_scale": child_hbm_scale,
    "scale": child_scale,
    "word2vec": child_word2vec,
    "matrix_fac": child_matrix_fac,
    "darlin": child_darlin,
    "spmd_push": child_spmd_push,
    "wd_push": child_wd_push,
    "ingest": child_ingest,
    "wire_rpc": child_wire_rpc,
    "server_apply": child_server_apply,
    "quant_wire": child_quant_wire,
    "backend": child_backend,
    "serve": child_serve,
}


# ---------------------------------------------------------------------------
# parent orchestration (never imports jax)
# ---------------------------------------------------------------------------


def _cpu_sim_env(n_devices: int = 8) -> dict:
    from parameter_server_tpu.utils.hostenv import force_cpu

    env = force_cpu(dict(os.environ))
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def _probe_backend(env: dict, timeout_s: float) -> str | None:
    """Ask a subprocess what platform jax.devices() resolves to; None on
    timeout/failure. A subprocess, because the parent must never hold the
    chip its children need."""
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        if r.returncode == 0 and r.stdout.strip():
            return r.stdout.strip().splitlines()[-1]
    except subprocess.TimeoutExpired:
        pass
    return None


def _run_child(name: str, env: dict, timeout_s: float) -> dict:
    """Run one sub-bench child under a hard deadline. Children are started
    in their own session so a stuck child can be killed as a group; if
    SIGKILL doesn't take, the child is abandoned and the suite moves on."""
    t0 = time.perf_counter()
    with tempfile.TemporaryFile(mode="w+") as fout, \
            tempfile.TemporaryFile(mode="w+") as ferr:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child", name],
            stdout=fout, stderr=ferr, env=env, start_new_session=True,
        )
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass  # abandoned: unkillable
            return {"error": f"timeout after {timeout_s:.0f}s"}
        fout.seek(0)
        lines = fout.read().strip().splitlines()
        if proc.returncode == 0 and lines:
            try:
                out = json.loads(lines[-1])
                out["wall_s"] = round(time.perf_counter() - t0, 1)
                return out
            except json.JSONDecodeError:
                pass
        ferr.seek(0)
        return {"error": (ferr.read() or "no output").strip()[-500:]}


def main() -> int:
    t_start = time.perf_counter()
    env = dict(os.environ)
    platform = _probe_backend(env, timeout_s=240.0)
    if platform != "tpu":
        print(
            f"bench: needs a TPU backend, found {platform!r}; nothing ran",
            file=sys.stderr,
        )
        return 1

    results: dict = {}
    failed_device_children: list[str] = []
    for name in CHILD_ORDER:
        # wire_rpc/server_apply/quant_wire measure host TCP + updater
        # latency, never the accelerator: pin them to CPU like the
        # cpu-sim meshes
        pinned = name in (
            "spmd_push", "wd_push", "wire_rpc", "server_apply",
            "quant_wire", "backend", "serve",
        )
        r = _run_child(
            name, _cpu_sim_env() if pinned else env, CHILD_BUDGET_S[name]
        )
        results[name] = r
        if "error" in r and not pinned:
            failed_device_children.append(name)

    head = results["headline"]
    # the wire_rpc child carries its process's telemetry snapshot out; it
    # rides the full results top-level so BENCH rounds track RPC latency
    # histograms alongside throughput (popped: the sub entry stays scalar)
    wire_rpc = results.get("wire_rpc", {})
    telemetry = (
        wire_rpc.pop("telemetry", None) if isinstance(wire_rpc, dict) else None
    )
    extra = {}
    if telemetry:
        extra["telemetry"] = telemetry
    if "error" in head:
        extra["error"] = head["error"]

    full = {
        "metric": "sparse_lr_ftrl_train_throughput",
        "value": head.get("value"),
        "unit": "examples/sec",
        "vs_baseline": head.get("vs_baseline"),
        "platform": head.get("platform", platform),
        "raw": head.get("raw", {}),
        "sub": {
            "pallas_ftrl": head.get("pallas_ftrl", {}),
            "pipeline_e2e": results.get("pipeline_e2e", {}),
            "ladder": results.get("ladder", {}),
            "hbm_scale": results.get("hbm_scale", {}),
            "scale": results.get("scale", {}),
            "word2vec": results.get("word2vec", {}),
            "matrix_fac": results.get("matrix_fac", {}),
            "darlin": results.get("darlin", {}),
            "spmd_push": results.get("spmd_push", {}),
            "wd_push": results.get("wd_push", {}),
            "ingest": results.get("ingest", {}),
            "wire_rpc": wire_rpc,
            "server_apply": results.get("server_apply", {}),
            "quant_wire": results.get("quant_wire", {}),
            "backend": results.get("backend", {}),
            "serve": results.get("serve", {}),
        },
        "suite_wall_s": round(time.perf_counter() - t_start, 1),
        **extra,
    }
    # FULL nested result goes to a file (committable as the round's
    # capture); stdout gets ONE compact line. The driver records only a
    # 2000-char stdout tail — round 4's full-result line overflowed it and
    # truncated the contract fields away (VERDICT r4 missing #1).
    out_path = os.environ.get(
        "PS_BENCH_FULL_OUT",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_full_latest.json"),
    )
    try:
        with open(out_path, "w") as f:
            json.dump(full, f, indent=1)
        full_ref = os.path.basename(out_path)
    except OSError:
        full_ref = "unwritable"
    print(json.dumps(_compact_contract(full, full_ref)))
    if failed_device_children:
        print(
            "bench: device-bound children failed: "
            + ", ".join(failed_device_children),
            file=sys.stderr,
        )
        return 1
    return 0


def _compact_contract(full: dict, full_ref: str) -> dict:
    """One-scalar-per-sub-bench summary of the full result, guaranteed to
    serialize < 1500 chars so the driver's stdout-tail buffer keeps the
    contract fields intact whatever else the suite printed."""

    def _pick(sub: str, *keys: str) -> dict:
        d = full["sub"].get(sub) or {}
        if "error" in d:
            return {"error": str(d["error"])[-80:]}
        return {k: d[k] for k in keys if k in d}

    compact = {
        "metric": full["metric"],
        "value": full["value"],
        "unit": full["unit"],
        "vs_baseline": full["vs_baseline"],
        "platform": full["platform"],
        "suite_wall_s": full["suite_wall_s"],
        "full_results": full_ref,
        "sub": {
            "pallas_ftrl": _pick("pallas_ftrl", "pallas_speedup", "mode"),
            "e2e": _pick(
                "pipeline_e2e", "pipelined_k8_ex_per_sec", "auc_k8",
                "fastest"),
            "ladder": _pick("ladder", "bucketing_speedup", "k8_over_k1"),
            "hbm": _pick(
                "hbm_scale", "num_keys_log2", "sparse_step_ex_per_sec",
                "dense_hbm_gb_per_sec", "cpu_smoke"),
            "scale": _pick(
                "scale", "ex_per_sec", "holdout_auc", "gb_streamed"),
            "w2v": _pick("word2vec", "pairs_per_sec_k8", "vs_baseline"),
            "mf": _pick("matrix_fac", "pairs_per_sec_k8", "vs_baseline"),
            "darlin": _pick("darlin", "block_passes_per_sec", "objv"),
            "spmd": _pick("spmd_push", "aggregate_speedup"),
            "wd": _pick(
                "wd_push", "per_worker_ex_per_sec",
                "quantized_vs_per_worker"),
            "ingest": _pick(
                "ingest", "parse_mb_per_sec", "parse_build_ex_per_sec"),
            # the telemetry block: RPC latency + the pipelined wire's
            # headline ratios reach the driver-recorded line, not just
            # the full results file
            # observability_ratio (ISSUE 13 acceptance): push rps with
            # flightrec + timeseries + profiler all armed vs all off
            "rpc": _pick(
                "wire_rpc", "roundtrips_per_sec", "pull_p50_ms",
                "push_p99_ms", "pipelined_speedup_w8",
                "mb_s_1mib_pipelined", "observability_ratio"),
            # the batched apply engine's acceptance ratios (ISSUE 4):
            # batched-vs-serial push throughput at 8 pipelined clients
            # and binary-vs-JSON header rps at 4 KiB frames
            "srv": _pick(
                "server_apply", "batched_speedup_w8",
                "push_rps_batched_w8", "hdr_speedup_4k"),
            # the quantized wire's acceptance numbers (ISSUE 6): push
            # wire-bytes ratio at int8 and AUC parity vs the float arm
            "quant": _pick(
                "quant_wire", "push_bytes_ratio_int8", "auc_delta_int8",
                "holdout_auc_f32", "holdout_auc_int8"),
            # the transport-neutral backend's acceptance numbers (ISSUE
            # 11): in-mesh vs socket push throughput at the large-batch
            # end, the crossover point where in-mesh starts winning, the
            # quantized-collective payload ratio and its AUC parity
            "backend": _pick(
                "backend", "mesh_vs_socket_push_speedup",
                "crossover_keys_per_push", "quant_bytes_ratio_int8",
                "auc_delta_int8"),
            # the serving plane's acceptance numbers (ISSUE 7): cached
            # pull QPS vs the uncached baseline at 256 Zipf clients,
            # cache hit rate, encode-coalesce ratio, p99 under shedding
            "serve": _pick(
                "serve", "pull_qps_cached", "qps_speedup_cached",
                "hit_rate", "coalesce_ratio", "p99_ms_shed"),
        },
    }
    if "error" in full:
        compact["error"] = str(full["error"])[-120:]
    # belt and braces: the contract fields must survive the tail buffer.
    # Degrade by shedding whole sub-blocks oldest-acceptance-first (the
    # newest cells' acceptance numbers are what a fresh capture is FOR;
    # everything always lands in the full results file regardless), and
    # only pop the whole sub dict if even that isn't enough.
    drop_order = (
        "hbm", "ingest", "darlin", "mf", "w2v", "ladder", "scale", "wd",
        "spmd", "e2e", "pallas_ftrl", "rpc", "srv",
        "quant", "serve", "backend",
    )
    for name in drop_order:
        if len(json.dumps(compact)) <= 1400:
            break
        compact["sub"].pop(name, None)
    if len(json.dumps(compact)) > 1400:
        compact.pop("sub", None)
    return compact


if __name__ == "__main__":
    if "--child" in sys.argv:
        from parameter_server_tpu.utils.hostenv import init_compile_cache

        init_compile_cache()
        name = sys.argv[sys.argv.index("--child") + 1]
        print(json.dumps(_CHILDREN[name]()))
    else:
        sys.exit(main())
