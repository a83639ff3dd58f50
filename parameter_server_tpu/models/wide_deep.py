"""Wide & Deep CTR model with a server-sharded embedding table.

Reference analog: BASELINE.json parity config "Wide-&-Deep CTR with
100M-row embedding table (server-sharded embeddings)". The wide half IS the
reference's sparse linear model (FTRL over the hashed key space); the deep
half is an embedding table living in the same KV store (vdim = embedding
dim) feeding a small MLP.

Design note vs the reference: the reference hand-writes worker gradients;
here the whole forward is one differentiable function and ``jax.grad``
produces the pulled-row gradients, which are then pushed through the same
server updaters (FTRL for wide, AdaGrad for embeddings, Adam for the dense
MLP). Pull/push stay the only interface to model state."""

from __future__ import annotations

import functools
import time
from collections.abc import Iterable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.kv.store import State
from parameter_server_tpu.kv.updaters import Adagrad, Ftrl, Updater
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.models.linear import batch_to_device
from parameter_server_tpu.ops.sparse import csr_logits
from parameter_server_tpu.utils.metrics import ProgressReporter


def init_mlp(dim: int, hidden: list[int], seed: int = 0) -> list[dict[str, Any]]:
    rng = np.random.default_rng(seed)
    sizes = [dim, *hidden, 1]
    params = []
    for fan_in, fan_out in zip(sizes, sizes[1:]):
        params.append(
            {
                "W": jnp.asarray(
                    rng.normal(scale=np.sqrt(2.0 / fan_in), size=(fan_in, fan_out)),
                    dtype=jnp.float32,
                ),
                "b": jnp.zeros(fan_out, dtype=jnp.float32),
            }
        )
    return params


def _mlp_apply(params, x):
    for layer in params[:-1]:
        x = jax.nn.relu(x @ layer["W"] + layer["b"])
    last = params[-1]
    return (x @ last["W"] + last["b"])[:, 0]


def _forward(w_u, emb_rows_w, mlp_params, b):
    """Differentiable forward: wide logits + deep logits -> masked loss."""
    wide = csr_logits(
        w_u, b["values"], b["local_ids"], b["row_ids"],
        num_rows=b["labels"].shape[0],
    )
    # mean-pool the batch's unique-key embeddings per example
    ent_emb = jnp.take(emb_rows_w, b["local_ids"], axis=0)  # (NNZ, d)
    ones = (b["values"] != 0).astype(jnp.float32)
    num = jax.ops.segment_sum(
        ent_emb * ones[:, None], b["row_ids"], num_segments=b["labels"].shape[0]
    )
    cnt = jax.ops.segment_sum(
        ones, b["row_ids"], num_segments=b["labels"].shape[0]
    )
    pooled = num / jnp.maximum(cnt, 1.0)[:, None]
    deep = _mlp_apply(mlp_params, pooled)
    logits = wide + deep
    m = b["example_mask"].astype(jnp.float32)
    loss = jnp.sum(m * (jax.nn.softplus(logits) - b["labels"] * logits))
    return loss, logits


def _wd_grads(w_u, e_w, mlp_params, b):
    """Shared loss + grads wrt (pulled wide rows, pulled emb rows, MLP)."""
    (loss, logits), grads = jax.value_and_grad(
        lambda w, e, p: _forward(w, e, p, b), argnums=(0, 1, 2), has_aux=True
    )(w_u, e_w, mlp_params)
    return loss, logits, grads


def _mlp_update(opt, g_mlp, opt_state, mlp_params):
    updates, new_opt_state = opt.update(g_mlp, opt_state, mlp_params)
    return optax.apply_updates(mlp_params, updates), new_opt_state


def _gated_mlp_update(opt, g_mlp, opt_state, mlp_params, act):
    """MLP/optimizer step applied only when ``act`` (bool scalar) is true;
    an inert step returns params and optimizer state unchanged."""
    new_mlp, new_opt = _mlp_update(opt, g_mlp, opt_state, mlp_params)
    gate = lambda new, old: jax.tree.map(  # noqa: E731
        lambda n, o: jnp.where(act, n, o), new, old
    )
    return gate(new_mlp, mlp_params), gate(new_opt, opt_state)


def _wd_micro(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    wide_state: State,
    emb_state: State,
    mlp_params: Any,
    opt_state: Any,
    batch: dict[str, jax.Array],
):
    """One single-device Wide&Deep step — shared verbatim by the per-step
    jit and the scanned multistep program."""
    idx = batch["unique_keys"]
    wide_rows = {k: jnp.take(v, idx, axis=0) for k, v in wide_state.items()}
    emb_rows = {k: jnp.take(v, idx, axis=0) for k, v in emb_state.items()}
    w_u = wide_up.weights(wide_rows)
    e_w = emb_up.weights(emb_rows)

    loss, logits, (g_wide, g_emb, g_mlp) = _wd_grads(w_u, e_w, mlp_params, batch)

    d_wide = wide_up.delta(wide_rows, g_wide)
    new_wide = {k: wide_state[k].at[idx].add(d_wide[k]) for k in wide_state}
    d_emb = emb_up.delta(emb_rows, g_emb)
    new_emb = {k: emb_state[k].at[idx].add(d_emb[k]) for k in emb_state}

    # an all-masked (inert) batch must be a true no-op: unlike the KV
    # updaters (zero grad => zero delta), Adam still advances its moment
    # decay on a zero gradient, so the MLP update is gated on activity
    # (multistep pads partial groups with inert microsteps)
    act = jnp.any(batch["example_mask"])
    new_mlp, new_opt_state = _gated_mlp_update(
        opt, g_mlp, opt_state, mlp_params, act
    )
    probs = jax.nn.sigmoid(logits)
    return new_wide, new_emb, new_mlp, new_opt_state, loss, probs


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def wd_train_step(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,  # optax optimizer (static: hashable namedtuple of fns? no — see make)
    wide_state: State,
    emb_state: State,
    mlp_params: Any,
    opt_state: Any,
    batch: dict[str, jax.Array],
):
    return _wd_micro(
        wide_up, emb_up, opt, wide_state, emb_state, mlp_params, opt_state,
        batch,
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4))
def wd_train_multistep(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    wide_state: State,
    emb_state: State,
    mlp_params: Any,
    opt_state: Any,
    batch: dict[str, jax.Array],  # fields carry a leading (K_steps, ...) axis
):
    """K sequential Wide&Deep steps scanned on-device in one dispatch (the
    steps_per_call idiom; see parallel.spmd.make_spmd_train_multistep).
    Returns per-microstep losses (K,) and probs (K, B)."""

    def body(carry, mb):
        new = _wd_micro(wide_up, emb_up, opt, *carry, mb)
        return tuple(new[:4]), (new[4], new[5])

    carry = (wide_state, emb_state, mlp_params, opt_state)
    (w, e, m, o), (losses, probs) = jax.lax.scan(body, carry, batch)
    return w, e, m, o, losses, probs


def _make_wd_spmd(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    mesh,
    num_keys: int,
    push_mode: str,
    multistep: bool,
):
    """Shared builder for the K=1 and scanned-K Wide&Deep mesh programs
    (one home for validation, specs, and the jit contract)."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel.spmd import (
        PUSH_MODES,
        _local_pull,
        _local_push,
        _local_push_aggregate,
        _local_push_quantized,
        _shard_size,
        batch_spec,
        state_spec,
    )

    if push_mode not in PUSH_MODES:
        raise ValueError(
            f"unknown push_mode {push_mode!r}; known: {PUSH_MODES}"
        )
    shard_size = _shard_size(num_keys, mesh.shape["kv"])

    def micro(wide_l, emb_l, mlp_params, opt_state, b, seed):
        idx = b["unique_keys"]
        w_u = lax.psum(_local_pull(wide_up, wide_l, idx, shard_size), "kv")
        e_u = lax.psum(_local_pull(emb_up, emb_l, idx, shard_size), "kv")

        loss, logits, (g_wide, g_emb, g_mlp) = _wd_grads(w_u, e_u, mlp_params, b)

        if push_mode == "aggregate":
            new_wide = _local_push_aggregate(
                wide_up, wide_l, idx, g_wide, shard_size
            )
            new_emb = _local_push_aggregate(
                emb_up, emb_l, idx, g_emb, shard_size
            )
        elif push_mode == "quantized":
            # int8 stochastic-rounding push on BOTH tables — the embedding
            # push is this app's dominant traffic (see make_wd_spmd_train_
            # step), so it's the table where the 4x wire shrink pays most.
            # Distinct streams decorrelate the two tables' rounding noise
            # under the shared per-microstep seed.
            new_wide = _local_push_quantized(
                wide_up, wide_l, idx, g_wide, shard_size, seed, stream=1
            )
            new_emb = _local_push_quantized(
                emb_up, emb_l, idx, g_emb, shard_size, seed, stream=2
            )
        else:
            all_idx = lax.all_gather(idx, "data")
            new_wide = _local_push(
                wide_up, wide_l, all_idx, lax.all_gather(g_wide, "data"),
                shard_size,
            )
            new_emb = _local_push(
                emb_up, emb_l, all_idx, lax.all_gather(g_emb, "data"),
                shard_size,
            )
        g_mlp = jax.tree.map(lambda g: lax.psum(g, "data"), g_mlp)
        # gate on POD-WIDE activity (any shard's real examples): a fully
        # inert microstep must not advance Adam's moment decay
        act = lax.psum(jnp.sum(b["example_mask"]), "data") > 0
        new_mlp, new_opt_state = _gated_mlp_update(
            opt, g_mlp, opt_state, mlp_params, act
        )
        loss_sum = lax.psum(loss, "data")
        probs = jax.nn.sigmoid(logits)
        return new_wide, new_emb, new_mlp, new_opt_state, loss_sum, probs

    def local_step(wide_l, emb_l, mlp_params, opt_state, batch, push_seed):
        b = {k: v[0] for k, v in batch.items()}
        if not multistep:
            out = micro(wide_l, emb_l, mlp_params, opt_state, b, push_seed)
            return (*out[:5], out[5][None, :])  # probs -> (D, B)

        def body(carry, xs):  # b fields carry a leading (K_steps, ...) axis
            mb, i = xs
            # quantized mode: a distinct PRNG key per microstep (same
            # contract as parallel.spmd.make_spmd_train_multistep)
            out = micro(*carry, mb, push_seed + i)
            return tuple(out[:4]), (out[4], out[5])

        n_micro = b["labels"].shape[0]
        carry = (wide_l, emb_l, mlp_params, opt_state)
        (w, e, m, o), (losses, probs) = lax.scan(
            body, carry, (b, jnp.arange(n_micro, dtype=jnp.int32))
        )
        return w, e, m, o, losses, probs[None]  # probs -> (D, K, B)

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec(), state_spec(), P(), P(), batch_spec(), P()),
        out_specs=(state_spec(), state_spec(), P(), P(), P(), batch_spec()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def _jitted(wide_state, emb_state, mlp_params, opt_state, batch,
                push_seed):
        return step(wide_state, emb_state, mlp_params, opt_state, batch,
                    jnp.int32(push_seed))

    def stepper(wide_state, emb_state, mlp_params, opt_state, batch,
                push_seed=None):
        if push_seed is None:
            if push_mode == "quantized":
                # same contract as parallel.spmd._wrap_stepper: a silently
                # defaulted seed would reuse one PRNG key every step,
                # correlating the rounding noise instead of averaging it
                raise ValueError(
                    "quantized push mode requires a per-call push_seed: "
                    "call step(wide, emb, mlp, opt, batch, seed)"
                )
            push_seed = 0
        return _jitted(wide_state, emb_state, mlp_params, opt_state, batch,
                       push_seed)

    return stepper


def make_wd_spmd_train_step(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    mesh,
    num_keys: int,
    push_mode: str = "per_worker",
):
    """Multi-device Wide&Deep step: both KV tables range-sharded over the
    ``kv`` mesh axis (BASELINE.json: "server-sharded embeddings"), batches
    over ``data``; MLP params replicated with psum'd gradients.

    Same wire pattern as the linear SPMD step (parallel/spmd.py): pull =
    masked gather + psum over kv; push = all_gather over data + sequential
    per-worker updates on each kv shard — or, with push_mode "aggregate",
    one psum per table pre-sums the per-key grads and ONE updater step
    applies them (parallel/spmd._local_push_aggregate), or, with
    "quantized", per_worker semantics with int8 stochastically-rounded
    gradients on the wire for BOTH tables (the embedding-table push is
    this app's dominant traffic, so it benefits most from the 4x shrink;
    quantized mode requires a per-call push_seed — the WideDeep app
    threads one automatically)."""
    return _make_wd_spmd(
        wide_up, emb_up, opt, mesh, num_keys, push_mode, multistep=False
    )


def make_wd_spmd_train_multistep(
    wide_up: Updater,
    emb_up: Updater,
    opt: Any,
    mesh,
    num_keys: int,
    push_mode: str = "per_worker",
):
    """K sequential Wide&Deep steps per device call over the (data, kv)
    mesh: batch fields stacked (D, K_steps, ...). Returns per-microstep
    losses (K,) and probs (D, K, B)."""
    return _make_wd_spmd(
        wide_up, emb_up, opt, mesh, num_keys, push_mode, multistep=True
    )


def _inert_like(b: CSRBatch) -> CSRBatch:
    """All-zero batch with b's static shapes (mask False, value 0): the
    pad for a partial multistep group — zero loss, zero gradient."""
    return CSRBatch(
        unique_keys=np.zeros_like(b.unique_keys),
        local_ids=np.zeros_like(b.local_ids),
        row_ids=np.zeros_like(b.row_ids),
        values=np.zeros_like(b.values),
        labels=np.zeros_like(b.labels),
        example_mask=np.zeros_like(b.example_mask),
        row_splits=np.zeros_like(b.row_splits),
        num_examples=0,
        num_unique=1,
        num_entries=0,
    )


class WideDeep:
    """The Wide&Deep app: shared hashed key space for wide + embedding."""

    def __init__(
        self,
        num_keys: int,
        emb_dim: int = 16,
        hidden: list[int] | None = None,
        ftrl_kw: dict | None = None,
        emb_eta: float = 0.1,
        mlp_lr: float = 1e-3,
        seed: int = 0,
        reporter: ProgressReporter | None = None,
        steps_per_call: int = 1,
        mesh=None,
        push_mode: str = "per_worker",
        max_delay: int = 0,
    ):
        self.num_keys = num_keys
        self.reporter = reporter or ProgressReporter()
        # K sequential W&D steps scanned per device call (the
        # solver.steps_per_call idiom; see parallel.spmd): amortizes the
        # per-call host<->device round-trip floor. report_every then
        # counts device calls.
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        self.steps_per_call = steps_per_call
        self.hidden = list(hidden or [32, 16])
        self.emb_dim = emb_dim
        self.wide_up = Ftrl(**(ftrl_kw or {"alpha": 0.1, "lambda_l1": 0.5}))
        self.emb_up = Adagrad(eta=emb_eta)
        self.wide_state = self.wide_up.init(num_keys, 1)
        self.emb_state = self.emb_up.init(num_keys, emb_dim)
        rng = np.random.default_rng(seed)
        init = rng.normal(scale=0.05, size=(num_keys, emb_dim)).astype(np.float32)
        init[0] = 0.0
        self.emb_state["w"] = jnp.asarray(init)
        self.mlp_params = init_mlp(emb_dim, self.hidden, seed=seed)
        self.opt = optax.adam(mlp_lr)
        self.opt_state = self.opt.init(self.mlp_params)
        self.examples_seen = 0
        self.mesh = mesh
        self.max_delay = max_delay  # SSP dispatch bound (ref: wait_time)
        if mesh is not None:
            from parameter_server_tpu.parallel.spmd import shard_state

            maker = (
                make_wd_spmd_train_multistep
                if steps_per_call > 1
                else make_wd_spmd_train_step
            )
            self._spmd_step = maker(
                self.wide_up, self.emb_up, self.opt, mesh, num_keys,
                push_mode=push_mode,
            )
            self.wide_state = shard_state(self.wide_state, mesh)
            self.emb_state = shard_state(self.emb_state, mesh)
        self.push_mode = push_mode
        # quantized push: each device call gets a fresh base seed (the
        # scan folds +i per microstep), so rounding noise never repeats
        self._push_calls = 0

    @classmethod
    def from_config(cls, cfg, mesh=None, reporter=None) -> "WideDeep":
        """Build the app from a PSConfig (ref: App::Create on the W&D
        config): wide half from [lr]/[penalty] FTRL fields, deep half from
        the [wd] section, dispatch shape from [solver]/[parallel]."""
        return cls(
            num_keys=cfg.data.num_keys,
            emb_dim=cfg.wd.emb_dim,
            hidden=list(cfg.wd.hidden),
            ftrl_kw=dict(
                alpha=cfg.lr.alpha, beta=cfg.lr.beta,
                lambda_l1=cfg.penalty.lambda_l1,
                lambda_l2=cfg.penalty.lambda_l2,
            ),
            emb_eta=cfg.wd.emb_eta,
            mlp_lr=cfg.wd.mlp_lr,
            seed=cfg.seed,
            reporter=reporter,
            steps_per_call=cfg.solver.steps_per_call,
            mesh=mesh,
            push_mode=cfg.parallel.push_mode,
            max_delay=max(cfg.solver.max_delay, 0),
        )

    def _dispatch(self, chunk: list[CSRBatch]):
        """Issue ONE device call on up to D*K batches (padded with inert
        batches to the static shape); returns (loss_dev, probs_dev,
        metas) where metas aligns (k, d) -> (num_examples, labels)."""
        from parameter_server_tpu.data.batch import pad_group

        K = self.steps_per_call
        D = self.mesh.shape["data"] if self.mesh is not None else 1
        full = chunk + [_inert_like(chunk[0]) for _ in range(D * K - len(chunk))]
        metas = [
            [
                (full[k * D + d].num_examples,
                 full[k * D + d].labels[: full[k * D + d].num_examples])
                for d in range(D)
            ]
            for k in range(K)
        ]
        if self.mesh is not None:
            from parameter_server_tpu.parallel.spmd import (
                place_stacked,
                stack_batches,
                stack_step_groups,
            )

            # W&D consumes the full wire format (row_ids)
            stacks = [
                stack_batches(pad_group(full[k * D : (k + 1) * D]), None)
                for k in range(K)
            ]
            dev = place_stacked(
                stacks[0] if K == 1 else stack_step_groups(stacks), self.mesh
            )
            (
                self.wide_state, self.emb_state, self.mlp_params,
                self.opt_state, loss, probs,
            ) = self._spmd_step(
                self.wide_state, self.emb_state, self.mlp_params,
                self.opt_state, dev, self._push_calls * K,
            )
            self._push_calls += 1
            return loss, probs, metas
        if K == 1:
            (
                self.wide_state, self.emb_state, self.mlp_params,
                self.opt_state, loss, probs,
            ) = wd_train_step(
                self.wide_up, self.emb_up, self.opt,
                self.wide_state, self.emb_state, self.mlp_params,
                self.opt_state, batch_to_device(chunk[0]),
            )
            return loss, probs, metas
        from parameter_server_tpu.parallel.spmd import (
            CSR_FULL_FIELDS,
            stack_fields,
        )

        stacked = stack_fields(pad_group(full), CSR_FULL_FIELDS, None)
        dev = {k: jnp.asarray(v) for k, v in stacked.items()}
        (
            self.wide_state, self.emb_state, self.mlp_params,
            self.opt_state, loss, probs,
        ) = wd_train_multistep(
            self.wide_up, self.emb_up, self.opt,
            self.wide_state, self.emb_state, self.mlp_params,
            self.opt_state, dev,
        )
        return loss, probs, metas

    def train(self, batches: Iterable[CSRBatch], report_every: int = 100) -> dict:
        """Train over a CSRBatch stream. With steps_per_call = K > 1,
        groups of K batches are scanned in a single device call; with a
        mesh, each microstep consumes D batches (one per data shard).
        Dispatch is SSP-gated (max_delay device calls in flight; losses
        and probs are read back only on retirement — the DispatchWindow
        pattern every trainer here shares). report_every counts device
        calls."""
        import itertools

        from parameter_server_tpu.parallel.ssp import DispatchWindow

        window_p, window_y, losses = [], [], []
        n_since = 0
        t0 = time.perf_counter()
        last: dict = {}
        K = self.steps_per_call
        D = self.mesh.shape["data"] if self.mesh is not None else 1

        def _retire(step: int, entry) -> None:
            loss_arr, probs_dev, metas = entry
            losses.append(float(np.sum(np.asarray(loss_arr))))
            p = np.asarray(probs_dev)
            # normalize (B,) | (K,B) | (D,B) | (D,K,B) -> (D, K, B)
            if self.mesh is None:
                p = p.reshape(K, 1, -1).swapaxes(0, 1) if K > 1 else p[None, None]
            elif K == 1:
                p = p[:, None]
            for k in range(K):
                for d in range(D):
                    n_ex, lab = metas[k][d]
                    if n_ex:
                        window_p.append(p[d, k, :n_ex])
                        window_y.append(lab)

        gate = DispatchWindow(self.max_delay, _retire)
        it = iter(batches)
        call_i = 0
        while True:
            chunk = list(itertools.islice(it, D * K))
            if not chunk:
                break
            gate.gate(call_i)
            loss, probs, metas = self._dispatch(chunk)
            gate.add(call_i, (loss, probs, metas))
            n_group = sum(b.num_examples for b in chunk)
            self.examples_seen += n_group
            n_since += n_group
            call_i += 1
            if call_i % report_every == 0:
                gate.drain()
                last = self._flush(losses, window_p, window_y, n_since, t0)
                losses, window_p, window_y = [], [], []
                n_since, t0 = 0, time.perf_counter()
        gate.drain()
        if n_since:
            last = self._flush(losses, window_p, window_y, n_since, t0)
        return last

    def train_files(
        self,
        files: list[str],
        fmt: str,
        builder,
        epochs: int = 1,
        report_every: int = 100,
    ) -> dict:
        """Streaming file-driven training (ref: the SGD worker's
        MinibatchReader loop): parse -> localize -> W&D step per epoch."""
        from parameter_server_tpu.data.reader import MinibatchReader

        last: dict = {}
        for _ in range(max(1, epochs)):
            last = (
                self.train(
                    MinibatchReader(files, fmt, builder),
                    report_every=report_every,
                )
                or last
            )
        return last

    def evaluate_files(self, files: list[str], fmt: str, builder) -> dict:
        from parameter_server_tpu.data.reader import MinibatchReader

        return self.evaluate(MinibatchReader(files, fmt, builder))

    def dump_model(self, path: str) -> str:
        """Dump inference weights (npz): derived wide weights, embedding
        table, MLP layers (ref: the text model dump each server range
        writes; one npz here since the deep half isn't a flat vector)."""
        host = {
            k: np.asarray(v)
            for k, v in (("wide_w", self.wide_up.weights(self.wide_state)),
                         ("emb_w", self.emb_up.weights(self.emb_state)))
        }
        for i, layer in enumerate(self.mlp_params):
            host[f"mlp_W{i}"] = np.asarray(layer["W"])
            host[f"mlp_b{i}"] = np.asarray(layer["b"])
        np.savez(path, **host)
        return path

    def _flush(self, losses, window_p, window_y, n_since, t0):
        loss_sum = float(sum(losses))
        p = np.concatenate(window_p) if window_p else np.zeros(0)
        y = np.concatenate(window_y) if window_y else np.zeros(0)
        return self.reporter.report(
            examples=self.examples_seen,
            objv=loss_sum / max(n_since, 1),
            auc=M.auc(y, p) if len(y) else float("nan"),
            ex_per_sec=n_since / max(time.perf_counter() - t0, 1e-9),
        )

    def predict(self, batches: Iterable[CSRBatch]) -> tuple[np.ndarray, np.ndarray]:
        ys, ps = [], []
        for b in batches:
            dev = batch_to_device(b)
            idx = dev["unique_keys"]
            wide_rows = {k: jnp.take(v, idx, axis=0) for k, v in self.wide_state.items()}
            emb_rows = {k: jnp.take(v, idx, axis=0) for k, v in self.emb_state.items()}
            _, logits = _forward(
                self.wide_up.weights(wide_rows),
                self.emb_up.weights(emb_rows),
                self.mlp_params,
                dev,
            )
            ps.append(np.asarray(jax.nn.sigmoid(logits))[: b.num_examples])
            ys.append(b.labels[: b.num_examples])
        return np.concatenate(ys), np.concatenate(ps)

    def evaluate(self, batches: Iterable[CSRBatch]) -> dict:
        y, p = self.predict(batches)
        return {"auc": M.auc(y, p), "logloss": M.logloss(y, p), "examples": len(y)}


def evaluate_dump(
    model_path: str,
    files: list[str],
    fmt: str,
    builder,
) -> dict:
    """Evaluate a ``WideDeep.dump_model`` npz over files (the CLI
    ``evaluate`` path for app wide_deep; ref: the offline model evaluator
    reading each server range's dump)."""
    from parameter_server_tpu.data.reader import MinibatchReader

    d = np.load(model_path)
    wide_w = jnp.asarray(d["wide_w"])
    emb_w = jnp.asarray(d["emb_w"])
    mlp = []
    i = 0
    while f"mlp_W{i}" in d:
        mlp.append(
            {"W": jnp.asarray(d[f"mlp_W{i}"]), "b": jnp.asarray(d[f"mlp_b{i}"])}
        )
        i += 1
    ys, ps = [], []
    for b in MinibatchReader(files, fmt, builder):
        dev = batch_to_device(b)
        idx = dev["unique_keys"]
        _, logits = _forward(
            jnp.take(wide_w, idx, axis=0),
            jnp.take(emb_w, idx, axis=0),
            mlp,
            dev,
        )
        ps.append(np.asarray(jax.nn.sigmoid(logits))[: b.num_examples])
        ys.append(b.labels[: b.num_examples])
    y = np.concatenate(ys)
    p = np.concatenate(ps)
    return {"auc": M.auc(y, p), "logloss": M.logloss(y, p), "examples": len(y)}
