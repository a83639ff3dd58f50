"""Multi-host runtime bootstrap.

Reference analog: process startup — main.cc flags -> Postoffice::Run ->
scheduler assigns node ids + key ranges, nodes connect (src/system/
postoffice.*, van.*) — plus the mpirun/hostfile launchers (script/). On a
TPU pod the cluster manager starts one identical process per host; this
module is what those processes call first:

    rt = runtime.init(coordinator_addr, num_processes, process_id)
    trainer = PodTrainer(cfg, runtime=rt)
    trainer.train_files(all_files)  # trainer shards the list per host

``init`` wires ``jax.distributed.initialize`` (the control plane the
reference's scheduler registry collapses into), builds the global
(data, kv) mesh from per-process devices, and hands out the host-local
views of it. Mesh layout contract: the **kv axis lives within each
process** and the **data axis spans processes** — so every host feeds
only its own data shards from local files (the reference's
worker-owns-its-shard design) and every host holds a full replica of the
range-sharded server state across its local devices (which makes
checkpoint writes shardable by host and evaluation host-local).

Simulated hosts for tests (SURVEY §4(b)): run N processes with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=K``
and gloo CPU collectives — exercised by tests/test_multihost.py.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np


@dataclass(frozen=True)
class Runtime:
    """Handle on the initialized multi-host run (or a single-host run when
    ``process_count == 1`` — every helper degrades to the local path)."""

    mesh: Any  # jax.sharding.Mesh over (data, kv)
    process_index: int
    process_count: int
    data_shards: int  # global data axis size
    kv_shards: int
    local_data_shards: int  # data rows owned by this process
    # cp_allmax's deferred-deletion slot (mutable on the frozen handle):
    # holds the one previous tag whose published max is deleted on the
    # next call — see cp_allmax's cleanup note
    _cp_state: dict = field(default_factory=dict, repr=False, compare=False)

    # -- input sharding ---------------------------------------------------

    def shard_files(self, files: list[str]) -> list[str]:
        """This host's input file shard (ref: the scheduler's WorkloadPool
        hands file shards to workers; across hosts the split is static)."""
        return list(files)[self.process_index :: self.process_count]

    # -- host-local <-> global arrays ------------------------------------

    def globalize_batch(self, arrays: dict[str, np.ndarray]) -> dict:
        """Lift this host's stacked (local_data_shards, ...) batch arrays
        into global arrays sharded over the full data axis."""
        if self.process_count == 1:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(self.mesh, P("data", None))
            return {k: jax.device_put(v, sh) for k, v in arrays.items()}
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        return {
            k: multihost_utils.host_local_array_to_global_array(
                np.asarray(v), self.mesh, P("data", None)
            )
            for k, v in arrays.items()
        }

    def localize_data(self, arr) -> np.ndarray:
        """This host's (local_data_shards, ...) slice of a P("data", ...)
        output (e.g. per-shard probabilities)."""
        if self.process_count == 1:
            return np.asarray(arr)
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        return np.asarray(
            multihost_utils.global_array_to_host_local_array(
                arr, self.mesh, P("data", None)
            )
        )

    # -- state ------------------------------------------------------------

    def init_state(self, init_fn) -> dict:
        """Build the kv-sharded global state: each device materializes its
        slice (no host-side full copy, no cross-host transfer)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P("kv", None))
        return jax.jit(init_fn, out_shardings=sh)()

    def state_to_host(self, state: dict) -> dict[str, np.ndarray]:
        """Assemble the FULL state on this host from its addressable
        shards. Valid under the layout contract (kv within process): every
        host holds a complete replica across its devices."""
        out = {}
        for name, arr in state.items():
            pieces: dict[int, np.ndarray] = {}
            for s in arr.addressable_shards:
                start = s.index[0].start or 0
                pieces[start] = np.asarray(s.data)
            out[name] = np.concatenate(
                [pieces[k] for k in sorted(pieces)], axis=0
            )
        return out

    def state_from_host(self, host_state: dict[str, np.ndarray]) -> dict:
        """Inverse of ``state_to_host``: place a full host-local state dict
        back onto the mesh (each device takes its kv slice)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P("kv", None))
        if self.process_count == 1:
            return {k: jax.device_put(v, sh) for k, v in host_state.items()}
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        # kv is within-process, so the host-local array already has global
        # shape; multihost placement just needs the global-array wrapper
        return {
            k: multihost_utils.host_local_array_to_global_array(
                v, self.mesh, P("kv", None)
            )
            for k, v in host_state.items()
        }

    # -- checkpoint -------------------------------------------------------

    def save_checkpoint(
        self, ckpt_dir, state: dict, meta: dict | None = None
    ) -> None:
        """Per-host sharded write (ref: each server dumps its own key
        range): host p writes key rows [p, p+P) / P of every table from its
        local replica; the manifest comes from host 0."""
        from parameter_server_tpu.utils.checkpoint import save_checkpoint

        host = self.state_to_host(state)
        rows = next(iter(host.values())).shape[0]
        if rows % self.process_count:
            raise ValueError(
                f"num_keys {rows} not divisible by {self.process_count} hosts"
            )
        per = rows // self.process_count
        lo = self.process_index * per
        save_checkpoint(
            ckpt_dir,
            {k: v[lo : lo + per] for k, v in host.items()},
            meta=meta,
            shard_id=self.process_index,
            num_shards=self.process_count,
        )

    def cp_allmax(
        self, tag: str, values: tuple[int, ...], timeout_ms: int = 600_000
    ) -> tuple[int, ...] | None:
        """Control-plane elementwise max across processes via the
        coordination-service KV store — NO device collective, so the
        dispatch thread never syncs to the device stream and async
        run-ahead (SSP max_delay) survives. This is the bucket-agreement
        fast path. Single-process runtimes short-circuit to the local
        values; ``None`` means a MULTI-process runtime has no distributed
        client wired (built without jax.distributed) and the caller
        should fall back to a device allgather.

        ``tag`` must be unique per reduction pod-wide and issued in the
        same order on every process (the trainer uses "<epoch-gen>/<step>").
        Designated-reducer shape: every process posts its values; process
        0 reads all P posts and publishes the max; followers do ONE
        blocking get on the published key — O(1) RPCs per follower per
        step, so the control-plane cost does not grow with the pod on the
        dispatch critical path (process 0 pays O(P), off-device).

        Cleanup (bounded across arbitrarily many calls/epochs/trainers):
        a follower deletes its own post right after its get succeeds —
        the published max existing proves process 0 already read every
        post of this tag. Process 0 deletes the PREVIOUS call's max after
        publishing the current one: its posts being all in proves every
        process completed the previous call's get (calls are issued in
        identical order per process). Steady-state KV footprint is
        therefore exactly one `max` key; only the final call's max of a
        Runtime's lifetime leaks (O(1), reclaimed when the coordinator
        exits)."""
        if self.process_count == 1:
            return tuple(int(v) for v in values)
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            return None
        me = self.process_index
        enc = ",".join(str(int(v)) for v in values)
        if me == 0:
            out = [int(v) for v in values]
            for p in range(1, self.process_count):
                got = client.blocking_key_value_get(
                    f"psbkt/{tag}/{p}", timeout_ms
                )
                for i, v in enumerate(got.split(",")):
                    out[i] = max(out[i], int(v))
            client.key_value_set(
                f"psbkt/{tag}/max", ",".join(str(v) for v in out)
            )
            prev = self._cp_state.get("prev_tag")
            if prev is not None:
                client.key_value_delete(f"psbkt/{prev}/max")
            self._cp_state["prev_tag"] = tag
            return tuple(out)
        client.key_value_set(f"psbkt/{tag}/{me}", enc)
        got = client.blocking_key_value_get(f"psbkt/{tag}/max", timeout_ms)
        client.key_value_delete(f"psbkt/{tag}/{me}")
        return tuple(int(v) for v in got.split(","))

    def barrier(self, name: str = "") -> None:
        if self.process_count == 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name or "ps_runtime_barrier")


def init(
    coordinator_addr: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    kv_shards: int = 1,
    data_shards: int | None = None,
    cfg=None,
) -> Runtime:
    """Bootstrap this process into the pod and build the global mesh.

    Single-host: call with no coordinator (or num_processes=1). Multi-host:
    every process calls with the same coordinator address and its own
    process_id — the TPU analog of `-scheduler ip:port -my_node ...`.

    cfg: a PSConfig — when given, the mesh shape comes from
    ``cfg.parallel`` and the explicit kv_shards/data_shards kwargs must
    not be used (ONE source of truth; PodTrainer re-checks its cfg
    against the runtime mesh and fails loudly on mismatch).
    """
    import jax

    if cfg is not None:
        if kv_shards != 1 or data_shards is not None:
            raise ValueError(
                "pass EITHER cfg (mesh shape from cfg.parallel) OR explicit "
                "kv_shards/data_shards — not both"
            )
        kv_shards = cfg.parallel.kv_shards
        data_shards = cfg.parallel.data_shards

    if coordinator_addr is None and (num_processes or 1) > 1:
        # the mirror of the guard below: N processes launched without a
        # coordinator would each run the FULL workload independently
        raise ValueError(
            f"num_processes={num_processes} requires a coordinator address"
        )
    if coordinator_addr is not None:
        if num_processes is None or num_processes < 2:
            # a forgotten --num_processes would otherwise yield N silent
            # INDEPENDENT runs clobbering each other's outputs
            raise ValueError(
                "a coordinator address requires num_processes >= 2 "
                f"(got {num_processes!r})"
            )
        # env check only — probing jax.default_backend() here would
        # initialize the backend BEFORE distributed init, hiding the pod
        if _cpu_platform_requested():
            # simulated hosts: CPU collectives ride gloo
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_addr,
            num_processes=num_processes,
            process_id=process_id,
        )
    procs = jax.process_count()
    local = jax.local_device_count()
    if local % kv_shards:
        raise ValueError(
            f"kv_shards {kv_shards} must divide local device count {local}: "
            "the kv axis must live within each process (layout contract)"
        )
    rows_per_proc = local // kv_shards
    max_data = procs * rows_per_proc
    data = data_shards if data_shards is not None else max_data
    if data > max_data or data % procs:
        raise ValueError(
            f"data_shards {data} must be a multiple of {procs} processes "
            f"and at most {max_data}"
        )
    # process-major device order keeps each data row on exactly one
    # process; when using fewer rows than available, take the same number
    # of rows from EVERY process (never starve a process of mesh devices)
    rows_used = data // procs
    blocks = np.array(jax.devices()).reshape(procs, rows_per_proc, kv_shards)
    for p in range(procs):
        owners = {d.process_index for d in blocks[p].flatten()}
        if owners != {p}:
            # the whole module's layout contract (kv within process, data
            # across processes) leans on process-contiguous device order;
            # violating it would silently truncate state_to_host replicas
            raise RuntimeError(
                "jax.devices() is not process-contiguous: block for "
                f"process {p} spans processes {sorted(owners)}; cannot "
                "honor the mesh layout contract"
            )
    grid = blocks[:, :rows_used, :].reshape(data, kv_shards)
    from jax.sharding import Mesh

    mesh = Mesh(grid, axis_names=("data", "kv"))
    return Runtime(
        mesh=mesh,
        process_index=jax.process_index(),
        process_count=procs,
        data_shards=data,
        kv_shards=kv_shards,
        local_data_shards=data // procs,
    )


def _cpu_platform_requested() -> bool:
    import os

    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
