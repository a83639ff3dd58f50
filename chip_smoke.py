#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # from the repo root, JAX_PLATFORMS unset

One process owns the chip for every phase (a chip belongs to one process
at a time); the only child it starts is pinned to the CPU. Phases, in
order, each timed, the first failure raising:

  1. train    the flagship at full width: sparse LR + FTRL over a
              2^27-key table (z+n f32 = 1 GiB), synthetic libsvm files ->
              native parse -> BatchBuilder -> PrefetchPipeline ->
              PodTrainer scanned multistep -> retire -> AUC, then
              evaluate_files on a held-out file
  2. server   one ShardServer holding its table on the chip answers
              push/pull over real TCP from a CPU-pinned ServerHandle
              child; pulled rows must equal a NumPy FTRL of the same pushes
  3. mesh     (only with >= 4 chips) the phase-1 run through
              cli.main(["train", ...]) on (data, kv) = (1, 4) and (2, 2),
              push_mode per_worker and aggregate, and
              cli.main(["backend", ...]) on MeshBackend kv = 4: shards on
              four distinct devices, memory balanced, (1, 4) per_worker
              losses equal to phase 1's

It refuses to run unless jax.devices()[0].platform == "tpu". Any
examples/s it prints is a smoke figure, not a measurement. The last line
of stdout is {"ok": true, "device": {...}} with the device as JAX
reports it.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

NUM_KEYS = 1 << 27  # the flagship's width: z + n f32 = 1 GiB of HBM
MINIBATCH = 8192
NNZ_PER = 32
STEPS_PER_CALL = 8
TRAIN_FILES = 8
BATCHES_PER_FILE = 5  # 8 files x 5 batches = 40 microsteps = 5 device calls
TEST_BATCHES = 4
FEATURE_SPACE = 1 << 18
ALPHA, BETA, L1, L2 = 0.1, 1.0, 1.0, 0.0
SERVER_KEYS = 1 << 20


class _Phases:
    """Wall time per phase, and what each left on device 0 once its
    locals are gone; a failed phase raises out of ``run``."""

    def __init__(self, dev) -> None:
        self.dev = dev
        self.walls: dict[str, float] = {}

    def run(self, name: str, fn, *args):
        print(f"[smoke] phase {name} ...", flush=True)
        t0 = time.perf_counter()
        out = fn(*args)
        self.walls[name] = time.perf_counter() - t0
        gc.collect()
        print(
            f"[smoke] phase {name} ok in {self.walls[name]:.1f}s; device 0 "
            f"holds {_bytes_in_use(self.dev) / 2**20:.0f} MiB after it",
            flush=True,
        )
        return out


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _bytes_in_use(dev) -> int:
    return int(dev.memory_stats()["bytes_in_use"])


# ---------------------------------------------------------------------------
# phase 1: the flagship through PodTrainer
# ---------------------------------------------------------------------------


def write_files(workdir: str) -> tuple[list[str], str]:
    """Synthetic libsvm shards + one held-out file, from ONE generation
    call so both share the ground-truth weights."""
    from parameter_server_tpu.data.synthetic import (
        make_sparse_logistic,
        write_libsvm,
    )

    per_file = BATCHES_PER_FILE * MINIBATCH
    n_train = TRAIN_FILES * per_file
    labels, keys, vals, _ = make_sparse_logistic(
        n_train + TEST_BATCHES * MINIBATCH, FEATURE_SPACE,
        nnz_per_example=NNZ_PER, noise=0.4, seed=31,
    )
    files = []
    for i in range(TRAIN_FILES):
        s = slice(i * per_file, (i + 1) * per_file)
        files.append(os.path.join(workdir, f"part-{i}.svm"))
        write_libsvm(files[-1], labels[s], keys[s], vals[s])
    test = os.path.join(workdir, "test.svm")
    write_libsvm(test, labels[n_train:], keys[n_train:], vals[n_train:])
    return files, test


def flagship_cfg(files: list[str], test: str, data_shards: int = 1,
                 kv_shards: int = 1, push_mode: str = "per_worker"):
    """The flagship's configuration at NUM_KEYS."""
    from parameter_server_tpu.utils.config import PSConfig

    cfg = PSConfig()
    cfg.data.files = list(files)
    cfg.data.val_files = [test]
    cfg.data.num_keys = NUM_KEYS
    cfg.data.pipeline_depth = 2
    cfg.data.bucket_nnz = True
    cfg.data.max_nnz_per_example = 4 * NNZ_PER
    cfg.solver.minibatch = MINIBATCH
    cfg.solver.steps_per_call = STEPS_PER_CALL
    cfg.solver.max_delay = 2
    cfg.solver.epochs = 1
    cfg.lr.alpha, cfg.lr.beta = ALPHA, BETA
    cfg.penalty.lambda_l1, cfg.penalty.lambda_l2 = L1, L2
    cfg.parallel.data_shards = data_shards
    cfg.parallel.kv_shards = kv_shards
    cfg.parallel.push_mode = push_mode
    return cfg


def check_learned(trainer, label: str) -> list[float]:
    """The pass condition of every training run; returns its loss
    sequence (one windowed mean per device call)."""
    import jax.numpy as jnp

    windows = trainer.reporter.history
    losses = [float(w["objv"]) for w in windows]
    per_call = trainer.data_shards * STEPS_PER_CALL
    want_calls = -(-TRAIN_FILES * BATCHES_PER_FILE // per_call)
    if len(windows) < want_calls:
        raise AssertionError(
            f"{label}: {len(windows)} device calls reported, want >= {want_calls}"
        )
    if not np.isfinite(losses).all():
        raise AssertionError(f"{label}: non-finite loss in {losses}")
    auc = float(windows[-1]["auc"])
    if not auc > 0.55:
        raise AssertionError(f"{label}: last windowed AUC {auc:.4f} <= 0.55")
    touched = int(jnp.count_nonzero(trainer.state["n"]))  # counted on device
    if touched <= 0:
        raise AssertionError(f"{label}: state['n'] is all zero after training")
    print(
        f"[smoke] {label}: {len(windows)} device calls, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, last windowed AUC {auc:.4f}, "
        f"nonzero n rows {touched}, smoke figure "
        f"{windows[-1]['ex_per_sec']:.0f} ex/s (not a measurement)",
        flush=True,
    )
    return losses


def phase_train(files: list[str], test: str) -> list[float]:
    import jax

    from parameter_server_tpu.data import native
    from parameter_server_tpu.parallel.trainer import PodTrainer

    so = native._NATIVE_DIR / "libpsdata.so"
    had_so = so.exists()
    if not native.native_available():
        raise AssertionError(
            "native parser unavailable (build output above): the flagship "
            "path must not run on the Python parser"
        )
    print(
        f"[smoke] parser backend: native ({so.name} "
        f"{'already built' if had_so else 'built from parser.cpp now'})",
        flush=True,
    )
    dev = jax.devices()[0]
    before = _bytes_in_use(dev)
    trainer = PodTrainer(flagship_cfg(files, test))
    jax.block_until_ready(trainer.state)
    nominal = 2 * NUM_KEYS * 4
    in_use = _bytes_in_use(dev) - before
    print(
        f"[smoke] state after init: {in_use / 2**20:.0f} MiB in use for "
        f"{nominal / 2**20:.0f} MiB nominal (z+n f32 x {NUM_KEYS} rows)",
        flush=True,
    )
    if in_use > 1.5 * nominal:
        raise AssertionError(
            f"({NUM_KEYS}, 1) f32 tables take {in_use} bytes, more than 1.5x "
            f"the nominal {nominal}: the unit minor dimension was padded"
        )
    # progress tables go to stderr: stdout carries the smoke's own lines
    # and ends in the result object
    with contextlib.redirect_stdout(sys.stderr):
        trainer.train_files(files, report_every=1)
    losses = check_learned(trainer, "1x1 PodTrainer")
    ev = trainer.evaluate_files([test])
    if not np.isfinite([ev["auc"], ev["logloss"]]).all():
        raise AssertionError(f"held-out metrics not finite: {ev}")
    print(
        f"[smoke] held-out: AUC {ev['auc']:.4f}, logloss {ev['logloss']:.4f} "
        f"over {ev['examples']} examples",
        flush=True,
    )
    return losses


# ---------------------------------------------------------------------------
# phase 2: a ShardServer on the chip, a client on the CPU
# ---------------------------------------------------------------------------

_CLIENT_CODE = """
import sys
import numpy as np
from parameter_server_tpu.parallel.multislice import ServerHandle
from parameter_server_tpu.utils.config import PSConfig

addr, req_path, out_path, n_keys = sys.argv[1:5]
req = np.load(req_path)
h = ServerHandle(addr, 0, 0, PSConfig(), range_size=int(n_keys))
pulled = {}
for r in range(int(req["rounds"])):
    h.push(req[f"keys{r}"], req[f"grad{r}"])
    pulled[f"pull{r}"] = h.pull(req[f"keys{r}"])
pulled["final"] = h.pull(req["union"])
np.savez(out_path, **pulled)
h.close()
"""


def numpy_ftrl(n_keys: int, pushes: list[tuple[np.ndarray, np.ndarray]]):
    """Plain float32 FTRL over the same pushes; yields weights of each
    push's keys."""
    z = np.zeros(n_keys, np.float32)
    n = np.zeros(n_keys, np.float32)

    def weights(idx):
        shrunk = np.sign(z[idx]) * np.maximum(np.abs(z[idx]) - L1, 0.0)
        return -shrunk / ((BETA + np.sqrt(n[idx])) / ALPHA + L2)

    per_push = []
    for idx, g in pushes:
        n_new = n[idx] + g * g
        sigma = (np.sqrt(n_new) - np.sqrt(n[idx])) / ALPHA
        z[idx] += g - sigma * weights(idx)
        n[idx] = n_new
        per_push.append(weights(idx))
    return per_push, weights


def phase_server(workdir: str) -> None:
    import jax

    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.parallel.multislice import ShardServer
    from parameter_server_tpu.utils.hostenv import force_cpu
    from parameter_server_tpu.utils.keyrange import KeyRange

    rng = np.random.default_rng(5)
    hot = np.unique(rng.integers(1, SERVER_KEYS, 512))
    pushes = []
    for _ in range(4):  # overlapping key sets: rows updated more than once
        keys = np.unique(
            np.concatenate([hot[::2], rng.integers(1, SERVER_KEYS, 1024)])
        ).astype(np.int64)
        pushes.append((keys, (3.0 * rng.normal(size=len(keys))).astype(np.float32)))
    union = np.unique(np.concatenate([k for k, _ in pushes]))
    req_path = os.path.join(workdir, "wire_req.npz")
    out_path = os.path.join(workdir, "wire_out.npz")
    np.savez(
        req_path, rounds=len(pushes), union=union,
        **{f"keys{r}": k for r, (k, _) in enumerate(pushes)},
        **{f"grad{r}": g for r, (_, g) in enumerate(pushes)},
    )

    srv = ShardServer(
        Ftrl(alpha=ALPHA, beta=BETA, lambda_l1=L1, lambda_l2=L2),
        KeyRange(0, SERVER_KEYS),
    ).start()
    try:
        table_devices = {d for v in srv.state.values() for d in v.devices()}
        if table_devices != {jax.devices()[0]}:
            raise AssertionError(f"server table is on {table_devices}, not the chip")
        env = force_cpu(dict(os.environ))
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.abspath(__file__)), env.get("PYTHONPATH", "")]
        )
        client = subprocess.Popen(
            [sys.executable, "-c", _CLIENT_CODE, srv.address, req_path,
             out_path, str(SERVER_KEYS)],
            env=env,
        )
        try:
            rc = client.wait(timeout=300)
        finally:
            if client.poll() is None:
                client.kill()
                client.wait()
        if rc != 0:
            raise AssertionError(f"wire client exited {rc}")
        counters = dict(srv.counters)
    finally:
        srv.server.stop()

    got = np.load(out_path)
    per_push, weights = numpy_ftrl(SERVER_KEYS, pushes)
    for r, want in enumerate(per_push):
        np.testing.assert_allclose(
            got[f"pull{r}"].ravel(), want, rtol=1e-5, atol=1e-6,
            err_msg=f"pull after push {r} differs from the NumPy FTRL",
        )
    final = weights(union)
    np.testing.assert_allclose(got["final"].ravel(), final, rtol=1e-5, atol=1e-6)
    nonzero = int(np.count_nonzero(final))
    if nonzero == 0:
        raise AssertionError("every reference weight is zero: the check is vacuous")
    print(
        f"[smoke] ShardServer on {jax.devices()[0].device_kind}: "
        f"{counters['pushes']} pushes, {counters['pulls']} pulls over TCP, "
        f"{len(union)} rows ({nonzero} nonzero) equal the NumPy FTRL",
        flush=True,
    )


# ---------------------------------------------------------------------------
# phase 3: four chips, through the CLI
# ---------------------------------------------------------------------------


def _record_instances(module, name: str):
    """Swap ``module.name`` for a subclass that remembers what the CLI
    constructs, so the smoke can inspect the live tables afterwards.
    Returns (instances, restore)."""
    cls = getattr(module, name)
    made: list = []

    class Recorded(cls):  # type: ignore[misc, valid-type]
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    Recorded.__name__ = cls.__name__
    setattr(module, name, Recorded)
    return made, lambda: setattr(module, name, cls)


def _write_app(cfg, workdir: str, tag: str) -> str:
    from parameter_server_tpu.utils.config import config_to_dict

    path = os.path.join(workdir, f"{tag}.json")
    with open(path, "w") as f:
        json.dump(config_to_dict(cfg), f)
    return path


def check_sharded(state: dict, rows: int, kv: int, label: str) -> None:
    """Each table's shards sit on four distinct devices, rows/kv rows
    each, and no device holds more than twice another's bytes."""
    import jax

    for name, arr in state.items():
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        if len(shards) != 4 or len(devices) != 4:
            raise AssertionError(
                f"{label}: table {name!r} has {len(shards)} shards on "
                f"{len(devices)} devices, want 4 on 4"
            )
        for s in shards:
            if s.data.shape[0] != rows // kv:
                raise AssertionError(
                    f"{label}: table {name!r} shard on {s.device} holds "
                    f"{s.data.shape[0]} rows, want {rows // kv}"
                )
    used = {str(d): _bytes_in_use(d) for d in jax.devices()[:4]}
    print(
        f"[smoke] {label}: bytes in use per device "
        + ", ".join(f"{k}={v / 2**20:.0f} MiB" for k, v in used.items()),
        flush=True,
    )
    if max(used.values()) > 2 * min(used.values()):
        raise AssertionError(f"{label}: device memory is unbalanced: {used}")


def phase_mesh(workdir: str, files: list[str], test: str,
               losses_1x1: list[float]) -> None:
    from parameter_server_tpu import cli
    from parameter_server_tpu.parallel import meshbackend, trainer as trainer_mod

    for data, kv in ((1, 4), (2, 2)):
        for mode in ("per_worker", "aggregate"):
            label = f"cli train {data}x{kv} {mode}"
            app = _write_app(
                flagship_cfg(files, test, data, kv, mode), workdir,
                f"train_{data}x{kv}_{mode}",
            )
            made, restore = _record_instances(trainer_mod, "PodTrainer")
            try:
                with contextlib.redirect_stdout(sys.stderr):
                    rc = cli.main(
                        ["train", "--app_file", app, "--report_interval", "1"]
                    )
            finally:
                restore()
            if rc != 0 or len(made) != 1:
                raise AssertionError(f"{label}: rc {rc}, {len(made)} trainers")
            trainer = made.pop()
            losses = check_learned(trainer, label)
            check_sharded(trainer.state, NUM_KEYS, kv, label)
            if (data, kv, mode) == (1, 4, "per_worker"):
                # D = 1 per-worker push is the 1x1 arithmetic, only sharded
                np.testing.assert_allclose(
                    losses, losses_1x1, rtol=1e-5,
                    err_msg="(1,4) per_worker losses differ from the 1x1 run",
                )
                print("[smoke] (1,4) per_worker losses equal 1x1 to 1e-5", flush=True)
            del trainer
            gc.collect()

    from parameter_server_tpu.utils.config import PSConfig

    cfg = PSConfig()
    cfg.data.num_keys = NUM_KEYS
    cfg.lr.alpha, cfg.lr.beta = ALPHA, BETA
    cfg.penalty.lambda_l1 = 0.01
    cfg.mesh.backend = "mesh"
    cfg.mesh.kv_shards = 4
    app = _write_app(cfg, workdir, "backend_mesh_kv4")
    made, restore = _record_instances(meshbackend, "MeshBackend")
    try:
        with contextlib.redirect_stdout(sys.stderr):
            rc = cli.main(["backend", "--app_file", app])
    finally:
        restore()
    if rc != 0 or len(made) != 1:
        raise AssertionError(f"cli backend: rc {rc}, {len(made)} backends")
    check_sharded(made.pop().state, NUM_KEYS, 4, "cli backend mesh kv=4")


# ---------------------------------------------------------------------------


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({len(devices)} x {dev.device_kind}). Nothing ran.",
            file=sys.stderr,
        )
        return 1
    from parameter_server_tpu.utils.hostenv import init_compile_cache

    cache_dir = init_compile_cache()
    cache_before = _cache_entries(cache_dir)
    print(
        f"[smoke] platform {dev.platform}, device_kind {dev.device_kind}, "
        f"{len(devices)} device(s), jax {jax.__version__}\n"
        f"[smoke] compile cache {cache_dir}: {cache_before} entries before",
        flush=True,
    )
    phases = _Phases(dev)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        files, test = phases.run("write_files", write_files, workdir)
        losses = phases.run("train", phase_train, files, test)
        phases.run("server", phase_server, workdir)
        if len(devices) >= 4:
            phases.run("mesh", phase_mesh, workdir, files, test, losses)
        else:
            print(f"[smoke] phase mesh skipped: {len(devices)} device(s) < 4", flush=True)
    print(
        f"[smoke] compile cache {cache_dir}: {cache_before} entries before, "
        f"{_cache_entries(cache_dir)} after\n[smoke] wall per phase: "
        + ", ".join(f"{k} {v:.1f}s" for k, v in phases.walls.items()),
        flush=True,
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
