"""PodTrainer: the multi-worker training driver.

Reference analog: the whole runtime stack working together — scheduler
assigns file shards (WorkloadPool), M workers stream minibatches and
Push/Pull against N servers (the SPMD step over the data x kv mesh),
bounded-delay consistency (SSPClock), merged Progress at the scheduler
(ProgressReporter), heartbeats.

SSP on a pod, concretely: collectives make each *global* step synchronous
across the mesh, so per-worker staleness lives in two places —
  1. within a step, every worker's gradient is computed against step-start
     weights and pushes land sequentially (parallel.spmd), and
  2. across steps, the host DISPATCHES up to ``max_delay + 1`` steps before
     blocking on completed results (JAX async dispatch gives the overlap,
     the SSPClock bounds the run-ahead — the Executor wait_time analog).
max_delay = 0 is BSP-with-pipelining-of-one; larger values overlap more
host batch-prep with device compute."""

from __future__ import annotations

import contextlib
import itertools
import os
import time
from collections.abc import Iterator
from typing import Any

import jax
import numpy as np

from parameter_server_tpu.data.batch import BatchBuilder, CSRBatch, inert_like
from parameter_server_tpu.data.pipeline import PrefetchPipeline
from parameter_server_tpu.data.reader import MinibatchReader, ingest_of
from parameter_server_tpu.kv.store import push_walk_share
from parameter_server_tpu.models.linear import updater_from_config
from parameter_server_tpu.ops.sparse import walked_entries
from parameter_server_tpu.parallel.mesh import make_mesh
from parameter_server_tpu.parallel.runtime import Runtime
from parameter_server_tpu.parallel.spmd import (
    StepApp,
    linear_app,
    make_spmd_predict_step,
    make_spmd_train_multistep,
    make_spmd_train_step,
    padded_num_keys,
    stack_batches,
    stack_step_groups,
)
from parameter_server_tpu.parallel.ssp import DispatchWindow, SSPClock
from parameter_server_tpu.parallel.workload import WorkloadPool
from parameter_server_tpu.utils import flightrec, trace
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter


# process-wide trainer sequence for control-plane KV namespacing (see
# PodTrainer._bucket_ns)
_TRAINER_SEQ = itertools.count()

# lower bound on the bucket-agreement probe window: real pods need room
# for ordinary startup skew whatever fault.startup_grace_s says; tests
# shrink it to exercise the timeout diagnostic without a 2-minute wait
_PROBE_GRACE_FLOOR_S = 120.0

# eval's bounded async-dispatch depth (see PodTrainer.evaluate_files):
# enough to overlap host batch-build with device predict, small enough
# that queued input/result buffers stay a constant HBM footprint
_EVAL_INFLIGHT = 2

# the dense group's leaves in a checkpoint directory, beside the tables'
# per-host shards
_DENSE_FILE = "dense.npz"


class _WorkerStream:
    """One logical worker's batch source: drains workloads (files) from the
    pool, reading each through a MinibatchReader (ref: SGD workers asking
    the scheduler for the next file shard). The reader's thread parses and
    ``next_batch`` builds, on its caller's thread (the pipeline's producer
    thread, which would only wait otherwise): a stream's parse and its
    ``BatchBuilder`` run side by side (``ctr1.train``: 9.9 and 5.8 ms a
    batch beside a 17.8 ms step; on one thread their sum paced the cell)."""

    def __init__(
        self, worker_id: int, pool: WorkloadPool, fmt: str, builder: BatchBuilder,
        backend: str = "auto",
    ):
        self.worker_id = worker_id
        self.pool = pool
        self.fmt = fmt
        self.builder = builder
        self.backend = backend
        self._reader: MinibatchReader | None = None
        self._iter: Iterator[tuple] | None = None  # the reader's parsed batches
        self._current: str | None = None
        self._last: CSRBatch | None = None  # the newest real batch: the inert pad's shape

    def next_batch(self) -> CSRBatch | None:
        while True:
            if self._iter is not None:
                piece = next(self._iter, None)
                if piece is not None:
                    self._last = self._reader.build(piece)
                    return self._last
                if self._current is not None:
                    self.pool.finish(self._current)
                self._iter = None
                self._current = None
            w = self.pool.fetch(self.worker_id)
            if w is None:
                return None
            self._current = w
            self._reader = MinibatchReader(
                [self._reader_path(w)], self.fmt, self.builder,
                backend=self.backend,
            )
            self._iter = self._reader.parsed()

    def _reader_path(self, workload: str) -> str:
        """Map a pool item to the file it names (identity here; the
        dynamic-pool stream carries an epoch prefix)."""
        return workload

    def _empty(self) -> CSRBatch:
        """Inert batch (all padding) for a drained worker: contributes no
        loss, no gradient. In the shapes of the stream's newest real batch,
        so that the all-inert call that ends an epoch runs the program the
        epoch's calls ran: a call of the smallest bucket would be a second
        program of the step's module name, compiled for one call, whose
        instruction names ``spmd.op_scopes`` merges with the step's (and,
        numbered otherwise, blanks). A stream that never held a batch pads
        with the builder's empty one."""
        if self._last is not None:
            return inert_like(self._last)
        return self.builder.build(np.zeros(0, dtype=np.float32), [], [])


class _ListStream:
    """One logical worker's share of an in-memory batch list, offered the
    way ``_WorkerStream`` offers a file's batches (``train_batches``)."""

    def __init__(self, batches: list[CSRBatch], like: CSRBatch):
        self._iter = iter(batches)
        self._like = like

    def next_batch(self) -> CSRBatch | None:
        return next(self._iter, None)

    def _empty(self) -> CSRBatch:
        return inert_like(self._like)


def app_from_config(cfg: PSConfig) -> StepApp:
    """The description of ``cfg.app`` for the shared step: its tables, its
    dense group, its model (``parallel.spmd.StepApp``)."""
    if cfg.app == "wide_deep":
        from parameter_server_tpu.models import wide_deep

        return wide_deep.app_from_config(cfg)
    if cfg.app == "matrix_fac":
        from parameter_server_tpu.models import matrix_fac

        return matrix_fac.app_from_config(cfg)
    if cfg.app == "word2vec":
        from parameter_server_tpu.models import word2vec

        return word2vec.app_from_config(cfg)
    if cfg.app == "dlrm":
        from parameter_server_tpu.models import dlrm

        return dlrm.app_from_config(cfg)
    return linear_app(updater_from_config(cfg))


class _RemotePool:
    """WorkloadPool facade over the TCP Coordinator: the wire tier's
    scheduler assigns shards across SPMD hosts (tier composition)."""

    def __init__(self, ctl):
        self._ctl = ctl

    def fetch(self, worker: int) -> str | None:
        return self._ctl.workload_fetch(worker)

    def finish(self, workload: str) -> None:
        self._ctl.workload_finish(workload)


class _EpochStream(_WorkerStream):
    """_WorkerStream whose pool items are ``"<epoch>:<path>"`` (epochs ride
    the dynamic pool as distinct workloads)."""

    def _reader_path(self, workload: str) -> str:
        return workload.split(":", 1)[1]


class PodTrainer:
    """Train ``cfg.app`` (the flagship sparse-LR app, Wide&Deep, matrix
    factorization, skip-gram or DLRM) across a data x kv device mesh: state,
    step, predict, scores and checkpoint all come from the app's description
    (``app_from_config``; ``app`` overrides it), the files' format and key
    mode from ``cfg.data.format`` (``data.reader.ingest_of``)."""

    def __init__(
        self,
        cfg: PSConfig,
        mesh=None,
        reporter: ProgressReporter | None = None,
        runtime: Runtime | None = None,
        profile_dir: str = "",
        app: StepApp | None = None,
    ):
        self.cfg = cfg
        if cfg.trace.trace_dir and not trace.tracer.enabled:
            # config-armed tracing for the in-process pod path (spawned
            # nodes arm via run_node / PS_TRACE_DIR instead)
            trace.configure(
                cfg.trace.trace_dir, capacity=cfg.trace.capacity,
                process_name="pod-trainer",
            )
        if runtime is not None:
            self.runtime = runtime
        else:
            m = mesh or make_mesh(cfg.parallel.data_shards, cfg.parallel.kv_shards)
            self.runtime = Runtime(
                mesh=m,
                process_index=0,
                process_count=1,
                data_shards=m.shape["data"],
                kv_shards=m.shape["kv"],
                local_data_shards=m.shape["data"],
            )
        self.mesh = self.runtime.mesh
        # one source of truth (ref: the scheduler validating -num_servers /
        # -num_workers against the registered cluster): a cfg whose
        # parallel section disagrees with the mesh it runs on must fail
        # loudly, not train silently under different sharding
        got = (self.mesh.shape["data"], self.mesh.shape["kv"])
        want = (cfg.parallel.data_shards, cfg.parallel.kv_shards)
        if (mesh is not None or runtime is not None) and got != want:
            raise ValueError(
                f"cfg.parallel says (data_shards, kv_shards)={want} but the "
                f"provided {'runtime' if runtime is not None else 'mesh'} is "
                f"{got}; update cfg.parallel (or build the runtime with "
                "runtime.init(..., cfg=cfg)) so both agree"
            )
        # multi-host bucketing: shapes are sized per host, but SPMD demands
        # identical shapes (and programs) on every process per step — a
        # tiny per-step cross-host max-agreement re-pads every host to the
        # pod max bucket (see _agree_bucket). The agreement rides the
        # coordination-service KV (control plane) when available, which
        # keeps SSP run-ahead alive; the device-allgather fallback caps
        # run-ahead at 1 because it syncs the dispatch thread to the
        # device stream.
        self._bucket_sync = (
            cfg.data.bucket_nnz and self.runtime.process_count > 1
        )
        # KV-key namespacing: trainers are constructed in the same order
        # on every process (the SPMD same-program contract), so a
        # process-wide counter yields pod-agreed, collision-free
        # namespaces; epochs within a trainer get their own sub-counter
        self._bucket_ns = f"t{next(_TRAINER_SEQ)}"
        self._epoch_seq = itertools.count()
        if self._bucket_sync:
            # the probe doubles as a fail-fast check of the namespacing
            # contract: _TRAINER_SEQ only yields pod-agreed namespaces when
            # every process constructs its PodTrainers in the same order.
            # An asymmetric construction makes the probe tags disagree, so
            # the blocking get would time out — surface that as a clear
            # contract error within the startup-grace window, not a
            # 10-minute silent hang on the first training step. The window
            # is bounded below (_PROBE_GRACE_FLOOR_S) so ordinary
            # cross-process startup skew (slow checkpoint load on one
            # host) isn't misdiagnosed, and the wait is 2x that window in
            # ONE cp_allmax call: a transiently slow host then simply
            # arrives mid-wait and the blocking get completes — a true
            # rendezvous, where a retry under a fresh tag could never
            # meet a peer still posting under the first tag (and a
            # re-post under the SAME tag errors: set-once KV keys).
            grace_ms = int(
                max(_PROBE_GRACE_FLOOR_S, cfg.fault.startup_grace_s * 2)
                * 1000
            )
            try:
                probe = self.runtime.cp_allmax(
                    f"{self._bucket_ns}probe/0", (0,),
                    timeout_ms=2 * grace_ms,
                )
            except Exception as e:
                raise RuntimeError(
                    f"pod bucket-agreement probe for trainer namespace "
                    f"{self._bucket_ns!r} failed ({e!r}). If the other "
                    "processes are alive, the likely cause is processes "
                    "constructing PodTrainers in different orders (the KV "
                    "namespacing contract) — make every process build the "
                    "same trainers in the same sequence. A process that "
                    f"is merely >{2 * grace_ms // 1000}s slower to "
                    "construct its trainer also trips this; raise "
                    "fault.startup_grace_s if that is legitimate in your "
                    "deployment"
                ) from e
            if probe is None and cfg.solver.max_delay > 0:
                print(
                    "[pod] note: no control-plane KV — multi-host "
                    "bucket_nnz agreement falls back to a device "
                    "allgather, capping dispatch run-ahead at 1; "
                    f"max_delay {cfg.solver.max_delay} will not add "
                    "overlap",
                    flush=True,
                )
        self.data_shards = self.mesh.shape["data"]
        # this process feeds only its own data rows (multi-host contract)
        self.local_data_shards = self.runtime.local_data_shards
        self.app = app if app is not None else app_from_config(cfg)
        # how the files are read and keyed: ``data.format`` says both
        self._format, self._key_mode = ingest_of(cfg)
        self.updater = self.app.tables[0].updater
        # K microsteps scanned per device call (see SolverConfig.steps_per
        # _call): amortizes the per-call host->device round-trip floor
        if cfg.solver.steps_per_call < 1:
            raise ValueError(
                f"solver.steps_per_call must be >= 1, got "
                f"{cfg.solver.steps_per_call}"
            )
        self.steps_per_call = cfg.solver.steps_per_call
        if cfg.data.wire_values not in ("f32", "f16"):
            raise ValueError(
                f"data.wire_values must be 'f32' or 'f16', got "
                f"{cfg.data.wire_values!r}"
            )
        maker = (
            make_spmd_train_multistep
            if self.steps_per_call > 1
            else make_spmd_train_step
        )
        self.step_fn = maker(
            self.app, self.mesh, cfg.data.num_keys,
            push_mode=cfg.parallel.push_mode,
        )
        self.predict_fn = make_spmd_predict_step(
            self.app, self.mesh, cfg.data.num_keys
        )
        # table rows are num_keys rounded up to the kv-axis multiple (pad
        # rows stay exactly zero — no batch key ever reaches them), so
        # arbitrary num_keys run on any mesh shape
        self._table_rows = padded_num_keys(
            cfg.data.num_keys, self.mesh.shape["kv"]
        )
        # one flat {name: array} dict: every table's slots made on the
        # devices, slice by slice; the dense group's leaves replicated
        self.state = self.runtime.init_state(
            lambda: self.app.init_tables(self._table_rows)
        )
        if self.app.dense is not None:
            self.state.update(self._replicated(self.app.dense.init_state()))
        self.reporter = reporter or ProgressReporter()
        self.clock = SSPClock(
            num_workers=1, max_delay=max(cfg.solver.max_delay, 0)
        )
        self.examples_seen = 0
        # device calls dispatched with real examples, over the trainer's
        # life: the base of each call's push_seed, so that quantized
        # rounding never reuses a key, in a later epoch either
        self.calls_trained = 0
        # observability: peak dispatch run-ahead (the SSP/async-overlap
        # depth actually reached; == max_delay + 1 when the gate binds)
        self.max_inflight = 0
        # observability (SURVEY §5.1): a jax.profiler trace of every
        # train_files / evaluate_files call on demand; the host phases
        # below (trace.phase) land on its timeline by name
        self.profile_dir = profile_dir
        # (program, nnz, unique) bucket shapes this trainer has dispatched:
        # with bucket_nnz a new one is a new device program (the phases
        # trainer.new_shapes / eval.new_shapes)
        self._dispatched_shapes: set[tuple[str, int, int]] = set()

    def _builder(self, key_mode: str | None) -> BatchBuilder:
        from parameter_server_tpu.data.batch import training_builder

        return training_builder(self.cfg, key_mode or self._key_mode)

    def train_files(
        self,
        files: list[str],
        key_mode: str | None = None,
        report_every: int = 20,
    ) -> dict:
        """Run all epochs over ``files`` sharded across workers.
        ``key_mode``: "hash" or "identity"; without it, what the files'
        format says (``ingest_of``)."""
        with self._trace_cm():
            return self._run_epochs(files, key_mode, report_every)

    def train_batches(self, batches, report_every: int = 20) -> dict:
        """One pass over an in-memory CSRBatch stream, through the loop
        ``train_files`` runs: worker d of the D data shards takes batches
        d, d + D, ... so that a microstep consumes D consecutive batches,
        ``steps_per_call`` microsteps a device call."""
        batches = list(batches)
        if not batches:
            return {}
        d = self.local_data_shards
        streams = [_ListStream(batches[w::d], batches[0]) for w in range(d)]
        with self._trace_cm():
            return self._train_epoch(streams, report_every)

    def _trace_cm(self):
        return (
            jax.profiler.trace(self.profile_dir)
            if self.profile_dir
            else contextlib.nullcontext()
        )

    def _new_shape_phase(self, name: str, stacked: dict, **args):
        """The timed phase ``name`` (``trainer.new_shapes`` for the step,
        ``eval.new_shapes`` for predict) around the first device call of
        each bucket shape — the call that compiles, or fetches, a program:
        its count says how many, its span which bucket (and step). A null
        context for a shape this trainer has dispatched before."""
        bucket = (
            name, stacked["values"].shape[-1],
            stacked["unique_keys"].shape[-1],
        )
        if bucket in self._dispatched_shapes:
            return contextlib.nullcontext()
        self._dispatched_shapes.add(bucket)
        return trace.phase(name, nnz=bucket[1], unique=bucket[2], **args)

    def train_files_dynamic(
        self,
        files: list[str],
        coordinator: str,
        key_mode: str | None = None,
        report_every: int = 20,
    ) -> dict:
        """Compose the two multi-process tiers (SURVEY §2.8/§5.8): the TCP
        tier's Coordinator hands file shards to SPMD hosts DYNAMICALLY
        (the reference scheduler's WorkloadPool, instead of this module's
        static per-host split), while the data plane stays XLA collectives
        over the (data, kv) mesh. A fast host simply fetches more shards;
        a host that drains early keeps issuing inert steps until the
        pod-wide example count hits zero (the existing termination
        contract — dynamic assignment needs no new synchronization).

        Process 0 must be running the Coordinator (or anything hosting
        its protocol) at ``coordinator``; EVERY process calls this with
        the same file list. Epochs ride the pool as distinct items."""
        from parameter_server_tpu.parallel.control import ControlClient
        from parameter_server_tpu.utils.metrics import wire_counters

        cfg = self.cfg
        # self-healing client: a coordinator restart or injected control-
        # plane fault mid-run is absorbed by reconnect + resend (the
        # server-side reply cache keeps workload_fetch exactly-once)
        ctl = ControlClient(
            coordinator, reconnect_timeout_s=cfg.fault.reconnect_timeout_s
        )
        try:
            items = [
                f"{e}:{f}"
                for e in range(max(1, cfg.solver.epochs))
                for f in sorted(files)
            ]
            if self.runtime.process_index == 0:
                ctl.workload_init(items)
                # workload_init is first-wins on the Coordinator: a pool
                # someone else already initialized (a second dynamic run,
                # or the wire tier's scheduler) would be silently reused
                # and this pod would train on nothing — fail loudly
                st = ctl.workload_stats()
                total = st["pending"] + st["active"] + st["done"]
                if total != len(items) or st["done"] or st["active"]:
                    raise RuntimeError(
                        f"coordinator at {coordinator} already holds a "
                        f"workload pool ({st}); train_files_dynamic needs "
                        "a fresh Coordinator per run"
                    )
                ctl.kv_set("pod_pool_ready")
            else:
                ctl.kv_get("pod_pool_ready", block=True, timeout=120)
            pool = _RemotePool(ctl)
            streams = [
                _EpochStream(
                    self.runtime.process_index * self.local_data_shards + w,
                    pool, self._format, self._builder(key_mode),
                )
                for w in range(self.local_data_shards)
            ]
            with self._trace_cm():
                out = dict(self._train_epoch(streams, report_every) or {})
            # recovery observability for the pod path (cumulative for this
            # process; mostly zero on a healthy wire)
            out["rpc_retries"] = wire_counters.get("rpc_retries")
            out["rpc_reconnects"] = wire_counters.get("rpc_reconnects")
            return out
        finally:
            ctl.close()

    def _run_epochs(self, files, key_mode, report_every) -> dict:
        cfg = self.cfg
        last: dict = {}
        for _ in range(max(1, cfg.solver.epochs)):
            # per-host pool over this host's local data rows. Contract:
            # callers pass the FULL file list on every host; the trainer
            # applies runtime.shard_files exactly once here (pre-sharding
            # upstream would double-shard and silently drop files)
            pool = WorkloadPool(self.runtime.shard_files(files))
            streams = [
                _WorkerStream(w, pool, self._format, self._builder(key_mode))
                for w in range(self.local_data_shards)
            ]
            last = self._train_epoch(streams, report_every) or last
        return last

    @staticmethod
    def _assemble_group(items: list[tuple]) -> tuple:
        """Combine K prepared step items into one multistep dispatch item
        (runs on the pipeline's stacker thread, never the dispatch loop):
        (stacked (D, K, ...), total examples, per-microstep metas)."""
        stacked = stack_step_groups([it[0] for it in items])
        n = sum(it[1] for it in items)
        metas = [(it[2], it[3]) for it in items]
        return stacked, n, metas

    def _prepare(self, batches: list[CSRBatch]) -> tuple:
        """Per-step host work: stack D per-worker batches + bookkeeping.
        Runs on the pipeline's stacker thread (or inline when serial).
        Bucketed batches are first re-padded to the group max (buckets are
        powers of two, so group shapes stay a small compiled set)."""
        from parameter_server_tpu.data.batch import pad_group

        # the step's push tells XLA its rows ascend (spmd._local_push): on
        # the chip a batch out of order is undefined behaviour, not an error
        assert all(b.keys_in_order() for b in batches), "unique_keys out of order"
        self._check(batches)
        padded = pad_group(batches)
        if trace.enabled():
            # beside pad_group's feed.unique_fill: what a sweep by key slot
            # of ps.grad visits of the entry slots it is handed (ops.sparse's
            # walk), and the push's scatters of their key slots (kv.store.add_rows')
            entries, slots = len(padded[0].values), len(padded[0].unique_keys)
            kv = self.mesh.shape["kv"]
            for b in batches:
                trace.counter(
                    "grad.walk_share", walked_entries(b.num_entries, entries) / entries
                )
                if self.cfg.parallel.push_mode != "aggregate":
                    trace.counter("push.walk_share", push_walk_share(
                        self.app.tables, b.unique_keys[: b.num_unique],
                        self._table_rows // kv, kv, slots,
                    ))
        stacked = stack_batches(
            padded, None, values_f16=self.cfg.data.wire_values == "f16",
        )
        n = sum(b.num_examples for b in batches)
        labels = np.concatenate([b.labels[: b.num_examples] for b in batches])
        counts = [b.num_examples for b in batches]
        return stacked, n, labels, counts

    def _check(self, batches: list[CSRBatch]) -> None:
        """The app's own check of host batches it is about to be handed
        (``StepApp.check_batch``), on the thread that stacks them."""
        if self.app.check_batch is not None:
            for b in batches:
                self.app.check_batch(b)

    def _agree_bucket(self, stacked: dict, tag: str) -> dict:
        """Pod-wide bucket agreement for bucketed batches: max-reduce
        every host's local (nnz, unique) shape and zero-pad up to the pod
        max. Buckets are powers of two, so the agreed set of shapes (and
        compiled programs) stays small pod-wide.

        The reduce rides the coordination-service KV (Runtime.cp_allmax)
        — pure control plane, so the dispatch thread keeps its SSP
        run-ahead. Fallback (no distributed client): a device allgather,
        which blocks this thread on the device stream and caps run-ahead
        at 1 regardless of max_delay (warned at init)."""
        from parameter_server_tpu.data.batch import zero_extend

        # trailing axis is the variable one for both single-step (D, NNZ)
        # and multistep-group (D, K, NNZ) stacks
        local = (
            stacked["values"].shape[-1], stacked["unique_keys"].shape[-1],
        )
        agreed = self.runtime.cp_allmax(tag, local)
        if agreed is None:
            from jax.experimental import multihost_utils

            agreed = (
                np.asarray(
                    multihost_utils.process_allgather(
                        np.array(local, dtype=np.int32)
                    )
                )
                .reshape(-1, 2)
                .max(axis=0)
            )
        nnz_t, u_t = agreed
        return {
            **stacked,
            "unique_keys": zero_extend(stacked["unique_keys"], int(u_t), axis=-1),
            "local_ids": zero_extend(stacked["local_ids"], int(nnz_t), axis=-1),
            "values": zero_extend(stacked["values"], int(nnz_t), axis=-1),
        }

    def _train_epoch(self, streams: list[_WorkerStream], report_every: int) -> dict:
        window: list = []
        n_since = 0
        t0 = time.perf_counter()
        step_idx = 0
        last: dict = {}
        drained = False  # a retired step reported 0 pod-wide examples
        # per-epoch control-plane KV namespace (pod-agreed; see _bucket_ns)
        bkt_gen = f"{self._bucket_ns}e{next(self._epoch_seq)}"

        def _retire(step: int, entry) -> None:
            nonlocal drained
            loss_arr, examples_arr, probs, metas, n = entry
            # np.asarray blocks until the device call is done (the SSP
            # bound taking effect); single-step outputs are scalars,
            # multistep outputs carry a (K,) microstep axis
            with trace.phase("trainer.retire", step=step):
                losses = np.atleast_1d(np.asarray(loss_arr))
                exs = np.atleast_1d(np.asarray(examples_arr))
            # flight recorder: the trainer's dispatch/retire cadence —
            # "which step was in flight when the pod wedged"
            flightrec.record("step.retire", step=step, examples=int(n))
            self.clock.finish(0, step)
            # empties only ever trail real batches within a group, so the
            # LAST microstep's pod-wide count is the drained signal
            if float(exs[-1]) == 0.0:
                drained = True
            probs_l = self.runtime.localize_data(probs)  # (Dl, [K,] B)
            if probs_l.ndim == 2:
                probs_l = probs_l[:, None, :]
            for k, meta in enumerate(metas):
                window.append((float(losses[k]), probs_l[:, k, :], meta))

        gate = DispatchWindow(self.clock.max_delay, _retire)
        K = self.steps_per_call

        # Host input pipeline (ref: learner/sgd.h parser threads): batch
        # builds run on background threads — with K > 1 the K-way group
        # stacking too (pipeline group_size/assemble) — so the loop below
        # only pops ready dispatch items and issues the device call.
        depth = self.cfg.data.pipeline_depth
        pipeline = (
            PrefetchPipeline(
                streams, self._prepare, depth=depth,
                group_size=K,
                assemble=self._assemble_group if K > 1 else None,
            )
            if depth > 0
            else None
        )
        empty_item = None  # lazily-built inert step item for drained hosts
        empty_group = None  # its assembled K-group form

        def _serial_item():
            batches = [s.next_batch() for s in streams]
            if not any(b is not None for b in batches):
                return None
            return self._prepare(
                [
                    b if b is not None else streams[i]._empty()
                    for i, b in enumerate(batches)
                ]
            )

        def _empty_single():
            nonlocal empty_item
            if empty_item is None:
                empty_item = self._prepare([s._empty() for s in streams])
            return empty_item

        def _empty_dispatch():
            nonlocal empty_group
            if K == 1:
                return _empty_single()
            if empty_group is None:
                empty_group = self._assemble_group([_empty_single()] * K)
            return empty_group

        def _next_item():
            """Next dispatch item: a prepared step (K == 1) or an
            assembled K-group. Never None — drained hosts keep issuing
            inert items so every host runs the same collectives until the
            pod-wide count hits 0."""
            if pipeline is not None:
                item = pipeline.get()
                return item if item is not None else _empty_dispatch()
            # serial (pipeline_depth=0) debug path: build inline
            if K == 1:
                return _serial_item() or _empty_single()
            singles = [_serial_item() for _ in range(K)]
            if all(s is None for s in singles):
                return _empty_dispatch()
            singles = [s if s is not None else _empty_single() for s in singles]
            return self._assemble_group(singles)

        # Termination contract (multi-host safe): a host whose local
        # streams dry up keeps issuing steps with all-empty batches — every
        # process must issue the same collectives — and ALL hosts stop
        # after retiring the first step whose pod-wide example count
        # (psum'd inside the step) is zero. The SSP gate's retirement
        # schedule is deterministic, so every host stops at the same step
        # index with no blocking host-side barrier on the dispatch path.
        try:
            while True:
                # SSP gate: block until call (t - tau - 1) fully completed
                # (with K > 1 the gate counts device CALLS, each K
                # microsteps deep — the documented steps_per_call contract)
                gate.gate(step_idx)
                if drained:
                    break
                # step anatomy: fetch (host pipeline pop) vs dispatch
                # (bucket agreement + H2D + device-call issue) vs retire
                # (blocked on the chip): one name each in the named
                # timers, the tracer's timeline and the profiler's
                with trace.phase("trainer.fetch", step=step_idx):
                    if K == 1:
                        stacked_np, n, labels, mask_counts = _next_item()
                        metas = [(labels, mask_counts)]
                    else:
                        stacked_np, n, metas = _next_item()
                with trace.phase("trainer.dispatch", step=step_idx):
                    if self._bucket_sync:
                        stacked_np = self._agree_bucket(
                            stacked_np, f"{bkt_gen}/{step_idx}"
                        )
                    stacked = self.runtime.globalize_batch(stacked_np)
                    # push_seed varies per microstep so quantized-push
                    # stochastic rounding never reuses a key (traced
                    # scalar: no recompile); calls_trained * K is this
                    # call's first microstep index
                    with self._new_shape_phase(
                        "trainer.new_shapes", stacked_np, step=step_idx
                    ):
                        self.state, out = self.step_fn(
                            self.state, stacked, self.calls_trained * K
                        )
                flightrec.record("step.dispatch", step=step_idx, examples=int(n))
                self.calls_trained += int(n > 0)
                self.examples_seen += n
                n_since += n
                gate.add(
                    step_idx,
                    (
                        out["loss_sum"], out["examples"], out["probs"],
                        metas, n,
                    ),
                )
                self.max_inflight = max(self.max_inflight, gate.max_inflight)
                step_idx += 1
                if step_idx % report_every == 0:
                    gate.drain()
                    if n_since:  # a drained host's inert calls report nothing
                        last = self._flush(window, n_since, t0)
                    window, n_since, t0 = [], 0, time.perf_counter()
            gate.wait_all()  # epoch sync point: every dispatched step retired
        finally:
            if pipeline is not None:
                pipeline.close()
        if n_since:
            last = self._flush(window, n_since, t0)
        return last

    def _flush(self, window, n_since: int, t0: float) -> dict:
        losses = sum(w[0] for w in window)
        ys, ps = [], []
        for _, probs, (labels, counts) in window:
            off = 0
            for d, c in enumerate(counts):
                ps.append(probs[d, :c])
            ys.append(labels)
        y = np.concatenate(ys) if ys else np.zeros(0)
        p = np.concatenate(ps) if ps else np.zeros(0)
        name, score = self.app.score[0]  # "auc" for the logistic apps
        return self.reporter.report(
            examples=self.examples_seen,
            objv=losses / max(n_since, 1),
            **{name: score(y, p) if len(y) else float("nan")},
            ex_per_sec=n_since / max(time.perf_counter() - t0, 1e-9),
            ssp=self.clock.progress(),
        )

    def _replicated(self, tree):
        """Place host values whole on every device of the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def table_state(self, name: str = "") -> dict:
        """Table ``name``'s slots ({slot: (rows, vdim) array}) out of the
        flat state."""
        return self.app.table(name).of(self.state)

    def _place_tables(self, host: dict) -> dict:
        """Host arrays of at least ``num_keys`` rows, keyed as the state is,
        onto the mesh: cut to the real rows, zero-padded to THIS mesh's
        table rows (another mesh shape carries another pad tail)."""
        from parameter_server_tpu.data.batch import zero_extend

        return self.runtime.state_from_host(
            {
                k: zero_extend(
                    np.asarray(v)[: self.cfg.data.num_keys], self._table_rows
                )
                for k, v in host.items()
            }
        )

    def set_table(self, name: str, slots: dict) -> None:
        """Put host or device arrays of ``num_keys`` rows (or some mesh's
        padded rows) in place of table ``name``'s slots."""
        t = self.app.table(name)
        self.state = {
            **self.state,
            **self._place_tables({t.key(k): v for k, v in slots.items()}),
        }

    def dense(self) -> tuple:
        """(parameters, optimizer state) of the app's dense group."""
        return self.app.dense.unpack(self.state)

    def set_dense(self, params, opt_state) -> None:
        self.state = {
            **self.state,
            **self._replicated(self.app.dense.pack(params, opt_state)),
        }

    def full_weights(self, table: str | None = None) -> np.ndarray:
        """Materialize a table's (num_keys, vdim) weights on this host from
        its local replica of the kv-sharded state (the app's first table
        unless named)."""
        import jax.numpy as jnp

        t = self.app.tables[0] if table is None else self.app.table(table)
        host = self.runtime.state_to_host(t.of(self.state))
        return np.asarray(
            t.updater.weights({k: jnp.asarray(v) for k, v in host.items()})
        )[: self.cfg.data.num_keys, : t.vdim]

    def save(self, ckpt_dir, meta: dict | None = None) -> None:
        """Per-host sharded checkpoint (each host writes its key-range
        slice of every table; ref: each server dumps its own range); the
        dense group and its optimizer state, which every host holds whole,
        go into ``dense.npz`` from host 0.

        Multi-host contract: ``save`` ends in a cross-host barrier, so
        EVERY process must call it with the same decision to save — run
        the identical CLI flags (--ckpt_dir in particular) on all hosts,
        or a saving host deadlocks waiting on one that skipped it."""
        self.runtime.save_checkpoint(
            ckpt_dir,
            {k: self.state[k] for k in self.app.table_keys()},
            meta={"examples_seen": self.examples_seen, **(meta or {})},
        )
        if self.app.dense is not None and self.runtime.process_index == 0:
            np.savez(
                os.path.join(ckpt_dir, _DENSE_FILE),
                **{k: np.asarray(self.state[k]) for k in self.app.dense.keys()},
            )
        self.runtime.barrier("ckpt_saved")

    def load(self, ckpt_dir) -> dict:
        from parameter_server_tpu.utils.checkpoint import load_checkpoint

        host, meta = load_checkpoint(ckpt_dir)
        tables = self._place_tables({k: host[k] for k in self.app.table_keys()})
        self.state = dict(tables)
        if self.app.dense is not None:
            with np.load(os.path.join(ckpt_dir, _DENSE_FILE)) as d:
                self.state.update(
                    self._replicated({k: d[k] for k in self.app.dense.keys()})
                )
        self.examples_seen = int(meta.get("examples_seen", 0))
        return meta

    def evaluate_files(self, files: list[str], key_mode: str | None = None) -> dict:
        """Pod-wide batch evaluation using the predict step on shard 0's
        stream layout (eval is read-only; one worker suffices).

        Three threads: the ``MinibatchReader``'s parse thread and its build
        thread run side by side, each up to four batches ahead of the next
        (``reader.parse`` / ``reader.build`` / ``reader.parsed_wait`` /
        ``reader.put_wait``, see ``data/reader.py``); the caller's thread
        takes finished batches off the build's queue, pads, stacks, ships
        and enqueues them, retires results and scores.

        Host phases of the caller's thread (``trace.phase``). ``eval.pass``
        is the whole pass, and six leaves add up to it but for loop
        overhead: ``eval.open_reader`` (builder and reader), ``eval.read``
        (one group of ``data_shards`` batches taken off the reader's queue:
        the wait for the reader's two threads, which the pass's first read
        starts; count: groups), ``eval.stack`` (``pad_group`` +
        ``stack_batches``: host stack + H2D), ``eval.enqueue`` (the predict
        call), ``eval.retire`` (the blocking read of the oldest call's
        result), ``eval.score`` (the app's scores over the pass: AUC and
        logloss, or RMSE). Around them,
        since PR 24: ``eval.open`` from entry to the return of the first
        predict call (the first open_reader, read, stack and enqueue),
        ``eval.dispatch`` each later call's stack + enqueue; the device
        idles in ``eval.open`` and ``eval.score``. ``eval.new_shapes`` times
        the first predict call of a bucket shape (the compile), inside
        ``eval.enqueue``."""
        if self.runtime.process_count > 1:
            # multi-host: evaluate host-locally against the full weight
            # vector (every host holds a complete replica) — no cross-host
            # collectives, so hosts may evaluate different file sets
            from parameter_server_tpu.models.evaluation import evaluate_model

            if self.app.dense is not None:
                raise NotImplementedError(
                    "multi-host evaluation scores a linear model's weight "
                    "vector host-locally; an app with a dense group is "
                    "evaluated on one host"
                )

            return evaluate_model(
                self.full_weights().ravel(),
                files,
                self.cfg.data.format,
                self.cfg.data.num_keys,
                batch_size=self.cfg.solver.minibatch,
                max_nnz_per_example=self.cfg.data.max_nnz_per_example,
                key_mode=key_mode or self._key_mode,
            )
        from parameter_server_tpu.data.batch import eval_builder

        def open_reader():
            builder = eval_builder(self.cfg, key_mode or self._key_mode)
            reader = MinibatchReader(files, self._format, builder)
            return iter(reader), lambda: _pad_like(builder)

        with self._trace_cm():
            return self._scored_pass(open_reader)

    def predict_batches(self, batches) -> tuple[np.ndarray, np.ndarray]:
        """(labels, probabilities) of an in-memory CSRBatch stream through
        the predict step, D batches a call."""
        ys, ps = self._predict_pass(_opener(batches))
        return np.concatenate(ys), np.concatenate(ps)

    def evaluate_batches(self, batches) -> dict:
        return self._scored_pass(_opener(batches))

    def _scored_pass(self, open_batches) -> dict:
        with trace.phase("eval.pass"):
            return self._score(*self._predict_pass(open_batches))

    def _score(self, ys: list, ps: list) -> dict:
        with trace.phase("eval.score"):
            y = np.concatenate(ys)
            p = np.concatenate(ps)
            return {
                **{name: score(y, p) for name, score in self.app.score},
                "examples": len(y),
            }

    def _predict_pass(self, open_batches) -> tuple[list, list]:
        """One pass of the predict step over the batches ``open_batches()``
        yields ((iterator, maker of an inert batch of their shape), opened
        inside ``eval.open``): per-group labels and probabilities."""
        from parameter_server_tpu.data.batch import pad_group

        # bounded async dispatch (the train loop's DispatchWindow pattern):
        # up to EVAL_INFLIGHT predicts ride JAX async dispatch — no
        # host<->device sync per D-group — while retirement of the oldest
        # keeps queued input/result buffers from accumulating in HBM
        # without bound on large eval sets
        pending: list[tuple[Any, list[np.ndarray]]] = []
        ys: list[np.ndarray] = []
        ps: list[np.ndarray] = []

        def _retire_oldest() -> None:
            probs_dev, labels_list = pending.pop(0)
            probs = np.asarray(probs_dev)  # sync point, bounded by depth
            for d, labels in enumerate(labels_list):
                ps.append(probs[d, : len(labels)])
                ys.append(labels)

        def _dispatch(group: list[CSRBatch]) -> None:
            # fill every data shard with real batches (D at a time); only
            # the tail group pads with inert batches
            with trace.phase("eval.stack"):
                self._check(group)
                batches = pad_group(
                    group + [pad() for _ in range(self.data_shards - len(group))]
                )
                stacked = stack_batches(
                    batches, self.mesh,
                    values_f16=self.cfg.data.wire_values == "f16",
                )
            with trace.phase("eval.enqueue"), self._new_shape_phase("eval.new_shapes", stacked):
                probs_dev = self.predict_fn(self.state, stacked)
            pending.append(
                (probs_dev, [b.labels[: b.num_examples] for b in group])
            )

        def _read() -> list[CSRBatch]:
            # a pure wait for whoever makes the batches: under iter(reader) the
            # reader's parse thread and, ahead of this one, its build thread
            with trace.phase("eval.read") as read:
                group = list(itertools.islice(reader, self.data_shards))
                if not group:
                    read.count = 0  # the probe that finds the stream at its end
            return group

        with trace.phase("eval.open"):
            with trace.phase("eval.open_reader"):
                reader, pad = open_batches()
            groups = iter(_read, [])
            first = next(groups, None)
            if first is not None:
                _dispatch(first)
        for group in groups:
            with trace.phase("eval.dispatch"):
                _dispatch(group)
            if len(pending) >= _EVAL_INFLIGHT:
                with trace.phase("eval.retire"):
                    _retire_oldest()
        while pending:
            with trace.phase("eval.retire"):
                _retire_oldest()
        return ys, ps


def _opener(batches):
    """``_predict_pass``'s source over an in-memory batch stream."""
    batches = list(batches)
    return lambda: (iter(batches), lambda: inert_like(batches[0]))


def _pad_like(builder: BatchBuilder) -> CSRBatch:
    return builder.build(np.zeros(0, dtype=np.float32), [], [])
