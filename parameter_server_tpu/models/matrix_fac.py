"""Matrix factorization over the KV store.

Reference analog: the reference's matrix-factorization app (rank-r factors
on a bipartite rating graph; workers hold rating blocks and Push/Pull the
row/column factor vectors they touch — named in BASELINE.json's north star
alongside linear_method).

TPU re-expression: user and item factor tables are KV tables with
``vdim = rank`` (the "value segments per key" of the reference's KVVector).
A rating minibatch is localized exactly like sparse-LR batches: unique
touched users/items are pulled, per-pair gradients are segment-summed onto
the unique sets, and one fused step pushes both tables' updates."""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.kv.store import State
from parameter_server_tpu.kv.updaters import Adagrad, Sgd, Updater
from parameter_server_tpu.parallel.spmd import place_stacked
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.hashing import PAD_KEY
from parameter_server_tpu.utils.metrics import ProgressReporter


@dataclass
class MFBatch:
    """Localized rating minibatch (static shapes). The key lists obey the
    batch contract of ``data.batch``: slot 0 ``PAD_KEY``, then
    ``np.unique``'s strictly ascending ids, then ``PAD_KEY`` to the end
    (what the mesh step promises ``_local_push``)."""

    user_keys: np.ndarray  # (Uu,) unique user ids (slot 0 = pad)
    item_keys: np.ndarray  # (Ui,) unique item ids (slot 0 = pad)
    user_ids: np.ndarray  # (B,) pair -> unique user slot
    item_ids: np.ndarray  # (B,) pair -> unique item slot
    ratings: np.ndarray  # (B,)
    mask: np.ndarray  # (B,)
    num_pairs: int


class MFBatchBuilder:
    """The MF localizer: unique users/items per batch, padded."""

    def __init__(self, batch_size: int, user_capacity: int | None = None,
                 item_capacity: int | None = None):
        self.batch_size = batch_size
        self.user_capacity = user_capacity or batch_size + 1
        self.item_capacity = item_capacity or batch_size + 1

    def build(
        self, users: np.ndarray, items: np.ndarray, ratings: np.ndarray
    ) -> MFBatch:
        b = len(ratings)
        if b > self.batch_size:
            raise ValueError(f"{b} pairs > batch_size {self.batch_size}")
        uu, uinv = np.unique(users, return_inverse=True)
        ii, iinv = np.unique(items, return_inverse=True)
        if len(uu) + 1 > self.user_capacity or len(ii) + 1 > self.item_capacity:
            raise ValueError("unique capacity exceeded")
        out = MFBatch(
            user_keys=np.zeros(self.user_capacity, dtype=np.int64),
            item_keys=np.zeros(self.item_capacity, dtype=np.int64),
            user_ids=np.zeros(self.batch_size, dtype=np.int32),
            item_ids=np.zeros(self.batch_size, dtype=np.int32),
            ratings=np.zeros(self.batch_size, dtype=np.float32),
            mask=np.zeros(self.batch_size, dtype=np.float32),
            num_pairs=b,
        )
        out.user_keys[1 : len(uu) + 1] = uu + 1  # +1: key 0 is the pad row
        out.item_keys[1 : len(ii) + 1] = ii + 1
        out.user_ids[:b] = uinv + 1
        out.item_ids[:b] = iinv + 1
        out.ratings[:b] = ratings
        out.mask[:b] = 1.0
        assert PAD_KEY == 0
        return out


def batch_to_device(b: MFBatch) -> dict[str, jax.Array]:
    return {k: jnp.asarray(v) for k, v in _mf_host_dict(b).items()}


def _mf_loss_and_grads(
    U: jax.Array, V: jax.Array, batch: dict[str, jax.Array], l2: float
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Shared SSE loss + per-unique-key factor gradients (single-device and
    SPMD paths both use this; pad slot 0 is excluded from L2)."""
    u = jnp.take(U, batch["user_ids"], axis=0)  # (B, r)
    v = jnp.take(V, batch["item_ids"], axis=0)
    pred = jnp.sum(u * v, axis=1)
    err = (pred - batch["ratings"]) * batch["mask"]
    loss = jnp.sum(err * err)
    uu, ui = U.shape[0], V.shape[0]
    # d/du = err * v (+ l2 u), aggregated over duplicate users in the batch
    g_u = jax.ops.segment_sum(
        err[:, None] * v, batch["user_ids"], num_segments=uu
    ) + l2 * U * (jnp.arange(uu) > 0)[:, None]
    g_v = jax.ops.segment_sum(
        err[:, None] * u, batch["item_ids"], num_segments=ui
    ) + l2 * V * (jnp.arange(ui) > 0)[:, None]
    return loss, g_u, g_v


def _mf_micro(
    user_up: Updater,
    item_up: Updater,
    user_state: State,
    item_state: State,
    batch: dict[str, jax.Array],
    l2: float,
) -> tuple[State, State, jax.Array]:
    """One fused MF step: pull touched factors, SSE gradient, push both —
    shared verbatim by the per-step jit and the scanned multistep."""
    uk, ik = batch["user_keys"], batch["item_keys"]
    u_rows = {k: jnp.take(v, uk, axis=0) for k, v in user_state.items()}
    i_rows = {k: jnp.take(v, ik, axis=0) for k, v in item_state.items()}
    U = user_up.weights(u_rows)  # (Uu, r)
    V = item_up.weights(i_rows)  # (Ui, r)

    loss, g_u, g_v = _mf_loss_and_grads(U, V, batch, l2)

    du = user_up.delta(u_rows, g_u)
    dv = item_up.delta(i_rows, g_v)
    new_user = {k: user_state[k].at[uk].add(du[k]) for k in user_state}
    new_item = {k: item_state[k].at[ik].add(dv[k]) for k in item_state}
    return new_user, new_item, loss


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def mf_train_step(
    user_up: Updater,
    item_up: Updater,
    user_state: State,
    item_state: State,
    batch: dict[str, jax.Array],
    l2: float,
) -> tuple[State, State, jax.Array]:
    return _mf_micro(user_up, item_up, user_state, item_state, batch, l2)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def mf_train_multistep(
    user_up: Updater,
    item_up: Updater,
    user_state: State,
    item_state: State,
    batch: dict[str, jax.Array],  # fields carry a leading (K_steps, ...) axis
    l2: float,
) -> tuple[State, State, jax.Array]:
    """K sequential MF steps scanned on-device in one dispatch (the
    steps_per_call idiom; see parallel.spmd.make_spmd_train_multistep).
    Returns the summed loss over microsteps."""

    def body(carry, mb):
        new_u, new_i, loss = _mf_micro(user_up, item_up, carry[0], carry[1], mb, l2)
        return (new_u, new_i), loss

    (us, its), losses = jax.lax.scan(body, (user_state, item_state), batch)
    return us, its, jnp.sum(losses)


def _make_mf_spmd(
    user_up: Updater,
    item_up: Updater,
    mesh,
    num_user_rows: int,
    num_item_rows: int,
    l2: float,
    push_mode: str,
    multistep: bool,
):
    """Shared builder for the K=1 and scanned-K MF mesh programs (one home
    for validation, specs, and the jit contract)."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel.spmd import (
        _local_pull,
        _local_push,
        _local_push_aggregate,
        _shard_size,
        batch_spec,
        state_spec,
    )

    if push_mode not in ("per_worker", "aggregate"):
        raise ValueError(f"unknown push_mode {push_mode!r}")
    u_shard = _shard_size(num_user_rows, mesh.shape["kv"])
    i_shard = _shard_size(num_item_rows, mesh.shape["kv"])

    def micro(user_l, item_l, b):
        uk, ik = b["user_keys"], b["item_keys"]
        U = lax.psum(_local_pull(user_up, user_l, uk, u_shard), "kv")
        V = lax.psum(_local_pull(item_up, item_l, ik, i_shard), "kv")
        loss, g_u, g_v = _mf_loss_and_grads(U, V, b, l2)
        if push_mode == "aggregate":
            new_user = _local_push_aggregate(user_up, user_l, uk, g_u, u_shard)
            new_item = _local_push_aggregate(item_up, item_l, ik, g_v, i_shard)
        else:
            # MFBatch's key lists ascend behind slot 0: the push may say so
            new_user = _local_push(
                user_up, user_l, lax.all_gather(uk, "data"),
                lax.all_gather(g_u, "data"), u_shard, ascending=True,
            )
            new_item = _local_push(
                item_up, item_l, lax.all_gather(ik, "data"),
                lax.all_gather(g_v, "data"), i_shard, ascending=True,
            )
        return new_user, new_item, loss

    def local_step(user_l, item_l, batch):
        b = {k: v[0] for k, v in batch.items()}
        if not multistep:
            new_user, new_item, loss = micro(user_l, item_l, b)
            return new_user, new_item, lax.psum(loss, "data")

        def body(carry, mb):  # b fields carry a leading (K_steps, ...) axis
            new_u, new_i, loss = micro(carry[0], carry[1], mb)
            return (new_u, new_i), loss

        (us, its), losses = lax.scan(body, (user_l, item_l), b)
        return us, its, lax.psum(jnp.sum(losses), "data")

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec(), state_spec(), batch_spec()),
        out_specs=(state_spec(), state_spec(), P()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def jitted(user_state, item_state, batch):
        return step(user_state, item_state, batch)

    return jitted


def make_mf_spmd_train_step(
    user_up: Updater,
    item_up: Updater,
    mesh,
    num_user_rows: int,
    num_item_rows: int,
    l2: float,
    push_mode: str = "per_worker",
):
    """Multi-device MF step: user and item factor tables range-sharded over
    the ``kv`` mesh axis, rating batches over ``data`` (the reference's MF
    app topology: rating blocks on workers, factors on servers).

    push_mode "aggregate": pre-sum per-key factor grads across data shards
    with one psum per table and apply ONE updater step (see
    parallel/spmd._local_push_aggregate — exactly equal to per_worker for
    plain SGD, standard sync aggregation for AdaGrad)."""
    return _make_mf_spmd(
        user_up, item_up, mesh, num_user_rows, num_item_rows, l2,
        push_mode, multistep=False,
    )


def make_mf_spmd_train_multistep(
    user_up: Updater,
    item_up: Updater,
    mesh,
    num_user_rows: int,
    num_item_rows: int,
    l2: float,
    push_mode: str = "per_worker",
):
    """K sequential MF steps per device call over the (data, kv) mesh:
    batch fields stacked (D, K_steps, ...) — data shard leading (sharded),
    microstep second (lax.scan'd). Returns the summed loss."""
    return _make_mf_spmd(
        user_up, item_up, mesh, num_user_rows, num_item_rows, l2,
        push_mode, multistep=True,
    )


_MF_FIELDS = ("user_keys", "item_keys", "user_ids", "item_ids", "ratings", "mask")


def stack_mf_batches(batches: list[MFBatch], mesh=None) -> dict[str, jax.Array]:
    """Stack per-worker MFBatches on a leading axis, sharded over data."""
    from parameter_server_tpu.parallel.spmd import stack_fields

    return stack_fields(batches, _MF_FIELDS, mesh)


def _mf_host_dict(b: MFBatch) -> dict[str, np.ndarray]:
    return {f: getattr(b, f) for f in _MF_FIELDS}


def _group_mf(items: list[dict], k_steps: int, axis: int, empty: dict) -> dict:
    """Stack up to K per-microstep host dicts on a NEW microstep axis for
    the scanned multistep programs; a partial final group is padded with
    the inert ``empty`` dict (mask 0 => zero loss and zero gradient)."""
    if len(items) < k_steps:
        items = items + [empty] * (k_steps - len(items))
    return {k: np.stack([b[k] for b in items], axis=axis) for k in items[0]}


def iter_rating_blocks(
    files: list[str], block_lines: int = 1 << 20
):
    """Stream ``user item rating`` text files (the MovieLens-style triple
    format the reference's MF app consumes) in bounded blocks of
    (users, items, ratings) int64/int64/float32 arrays."""
    for path in sorted(map(str, files)):
        us: list[int] = []
        it: list[int] = []
        rt: list[float] = []
        with open(path) as f:
            for line in f:
                parts = line.split()
                if len(parts) < 3:
                    continue
                try:
                    u, v, x = int(parts[0]), int(parts[1]), float(parts[2])
                except ValueError:
                    continue  # header / malformed line: skip, don't crash
                us.append(u)
                it.append(v)
                rt.append(x)
                if len(us) >= block_lines:
                    yield (
                        np.asarray(us, dtype=np.int64),
                        np.asarray(it, dtype=np.int64),
                        np.asarray(rt, dtype=np.float32),
                    )
                    us, it, rt = [], [], []
        if us:
            yield (
                np.asarray(us, dtype=np.int64),
                np.asarray(it, dtype=np.int64),
                np.asarray(rt, dtype=np.float32),
            )


class MatrixFactorization:
    """The MF app. num_users/num_items rows + 1 pad row each.

    With ``mesh`` the factor tables are range-sharded over "kv" and
    rating batches over "data" (the reference MF topology); the kv axis
    size must divide num_users+1 and num_items+1 (each shard owns an
    equal contiguous row range)."""

    def __init__(
        self,
        num_users: int,
        num_items: int,
        rank: int = 64,
        eta: float = 0.05,
        l2: float = 0.01,
        algo: str = "adagrad",
        init_scale: float = 0.1,
        seed: int = 0,
        reporter: ProgressReporter | None = None,
        mesh=None,
        push_mode: str = "per_worker",
        max_delay: int = 0,
        steps_per_call: int = 1,
    ):
        self.rank = rank
        self.l2 = l2
        # K sequential MF steps scanned per device call (the
        # solver.steps_per_call idiom): amortizes the per-call
        # host<->device round-trip floor; max_delay then counts device
        # CALLS in flight (each K steps deep)
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        self.steps_per_call = steps_per_call
        self.reporter = reporter or ProgressReporter()
        make = {"adagrad": lambda: Adagrad(eta=eta), "sgd": lambda: Sgd(eta=eta)}
        if algo not in make:
            raise ValueError(f"mf algo must be one of {sorted(make)}")
        self.user_up = make[algo]()
        self.item_up = make[algo]()
        rng = np.random.default_rng(seed)
        self.user_state = self.user_up.init(num_users + 1, rank)
        self.item_state = self.item_up.init(num_items + 1, rank)
        # factors start small-random (a zero product has zero gradient);
        # pad row 0 stays zero
        u0 = rng.normal(scale=init_scale, size=(num_users + 1, rank))
        i0 = rng.normal(scale=init_scale, size=(num_items + 1, rank))
        u0[0] = 0.0
        i0[0] = 0.0
        self.user_state["w"] = jnp.asarray(u0, dtype=jnp.float32)
        self.item_state["w"] = jnp.asarray(i0, dtype=jnp.float32)
        self.mesh = mesh
        self.max_delay = max_delay  # SSP dispatch bound (ref: wait_time)
        if mesh is not None:
            kv = mesh.shape["kv"]
            for what, rows in (("num_users", num_users), ("num_items", num_items)):
                if (rows + 1) % kv:
                    # surface the hidden +1 pad row — a round user-chosen
                    # size always fails the raw _shard_size check with a
                    # message naming neither knob
                    raise ValueError(
                        f"{what}+1 = {rows + 1} (the table has a pad row 0) "
                        f"must be divisible by kv_shards={kv}; pick "
                        f"{what} = k*{kv} - 1"
                    )
            from parameter_server_tpu.parallel.spmd import shard_state

            maker = (
                make_mf_spmd_train_multistep
                if steps_per_call > 1
                else make_mf_spmd_train_step
            )
            self._spmd_step = maker(
                self.user_up, self.item_up, mesh,
                num_users + 1, num_items + 1, l2=l2, push_mode=push_mode,
            )
            self.user_state = shard_state(self.user_state, mesh)
            self.item_state = shard_state(self.item_state, mesh)

    def _run_pairs(
        self, users, items, ratings, batch_size: int, builder: MFBatchBuilder
    ) -> tuple[float, int]:
        """Dispatch (already shuffled) rating triples as minibatches on the
        single-device or SPMD step, SSP-gated: losses are read back only
        on retirement, never a per-batch device sync (the DispatchWindow
        pattern every trainer here shares); returns (sse, pairs)."""
        from parameter_server_tpu.parallel.ssp import DispatchWindow

        sse, n = 0.0, 0

        def _retire(step: int, loss_arr) -> None:
            nonlocal sse
            sse += float(loss_arr)

        gate = DispatchWindow(self.max_delay, _retire)
        K = self.steps_per_call
        call_i = 0
        if self.mesh is not None:
            D = self.mesh.shape["data"]
            global_bs = batch_size * D
            empty = builder.build(
                np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(0, np.float32),
            )
            empty_stacked = None  # lazily built pad for partial K-groups
            starts = list(range(0, len(ratings), global_bs))
            for c in range(0, len(starts), K):
                gate.gate(call_i)
                micro = []  # per-microstep (D, ...) host stacks
                for s in starts[c : c + K]:
                    subs = []
                    for d in range(D):
                        sel = slice(s + d * batch_size, s + (d + 1) * batch_size)
                        if len(ratings[sel]):
                            subs.append(
                                builder.build(users[sel], items[sel], ratings[sel])
                            )
                        else:
                            subs.append(empty)
                    micro.append(stack_mf_batches(subs, None))
                    n += sum(b.num_pairs for b in subs)
                if K == 1:
                    batch = place_stacked(micro[0], self.mesh)
                else:
                    if len(micro) < K and empty_stacked is None:
                        empty_stacked = stack_mf_batches([empty] * D, None)
                    batch = place_stacked(
                        _group_mf(micro, K, axis=1, empty=empty_stacked),
                        self.mesh,
                    )
                self.user_state, self.item_state, loss = self._spmd_step(
                    self.user_state, self.item_state, batch
                )
                gate.add(call_i, loss)
                call_i += 1
            gate.drain()
            return sse, n
        empty_host = None
        starts = list(range(0, len(ratings), batch_size))
        for c in range(0, len(starts), K):
            gate.gate(call_i)
            hosts = []
            for s in starts[c : c + K]:
                sel = slice(s, s + batch_size)
                b = builder.build(users[sel], items[sel], ratings[sel])
                hosts.append(_mf_host_dict(b))
                n += b.num_pairs
            if K == 1:
                dev = {k: jnp.asarray(v) for k, v in hosts[0].items()}
                self.user_state, self.item_state, loss = mf_train_step(
                    self.user_up, self.item_up,
                    self.user_state, self.item_state, dev, self.l2,
                )
            else:
                if len(hosts) < K and empty_host is None:
                    empty_host = _mf_host_dict(
                        builder.build(
                            np.zeros(0, np.int64), np.zeros(0, np.int64),
                            np.zeros(0, np.float32),
                        )
                    )
                grouped = _group_mf(hosts, K, axis=0, empty=empty_host)
                dev = {k: jnp.asarray(v) for k, v in grouped.items()}
                self.user_state, self.item_state, loss = mf_train_multistep(
                    self.user_up, self.item_up,
                    self.user_state, self.item_state, dev, self.l2,
                )
            gate.add(call_i, loss)
            call_i += 1
        gate.drain()
        return sse, n

    def train_epoch(
        self, users, items, ratings, batch_size: int = 4096, seed: int = 0
    ) -> float:
        """One shuffled pass; returns train RMSE."""
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(ratings))
        builder = MFBatchBuilder(batch_size)
        t0 = time.perf_counter()
        sse, n = self._run_pairs(
            np.asarray(users)[order], np.asarray(items)[order],
            np.asarray(ratings)[order], batch_size, builder,
        )
        rmse = float(np.sqrt(sse / max(n, 1)))
        self.reporter.report(
            examples=n, objv=rmse, ex_per_sec=n / max(time.perf_counter() - t0, 1e-9)
        )
        return rmse

    def train_files(
        self,
        files: list[str],
        batch_size: int = 4096,
        epochs: int = 1,
        block_lines: int = 1 << 20,
        seed: int = 0,
    ) -> float:
        """Stream ``user item rating`` text files (ref: the reference MF
        app's file-driven workers; BASELINE's MovieLens config): blocks of
        block_lines triples are shuffled in bounded memory and dispatched
        — ratings are never materialized file-set-wide. Returns the final
        epoch's train RMSE."""
        builder = MFBatchBuilder(batch_size)
        rmse = float("nan")
        for ep in range(max(1, epochs)):
            rng = np.random.default_rng(seed + 1009 * ep)
            sse, n = 0.0, 0
            t0 = time.perf_counter()
            for us, it, rt in iter_rating_blocks(files, block_lines):
                perm = rng.permutation(len(rt))
                s, c = self._run_pairs(
                    us[perm], it[perm], rt[perm], batch_size, builder
                )
                sse += s
                n += c
            if n == 0:
                # silently reporting a perfect 0.0 RMSE on an unparseable
                # file set (e.g. comma-separated input) would pass any
                # downstream quality check with zero examples trained
                raise ValueError(
                    f"no rating triples parsed from {files}: expected "
                    "whitespace-separated 'user item rating' lines"
                )
            rmse = float(np.sqrt(sse / n))
            self.reporter.report(
                examples=n, objv=rmse,
                ex_per_sec=n / max(time.perf_counter() - t0, 1e-9),
            )
        return rmse

    def predict(self, users, items) -> np.ndarray:
        U = np.asarray(self.user_up.weights(self.user_state))
        V = np.asarray(self.item_up.weights(self.item_state))
        return np.sum(U[np.asarray(users) + 1] * V[np.asarray(items) + 1], axis=1)

    def rmse(self, users, items, ratings) -> float:
        p = self.predict(users, items)
        return float(np.sqrt(np.mean((p - ratings) ** 2)))
