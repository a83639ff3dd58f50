"""word2vec skip-gram with negative sampling (SGNS) over the KV store.

Reference analog: BASELINE.json's parity config "word2vec skip-gram
negative-sampling (1B-word corpus, bounded-staleness SSP)" — the classic
parameter-server workload: two huge embedding tables (input/output), each
step touching only the batch's words, pushed with bounded staleness.

TPU re-expression: in/out embedding tables are KV tables with vdim = dim;
a step batch is (center, context, K negatives) id arrays; negatives are
pre-sampled host-side from the unigram^0.75 distribution (the data-layer
job, like the reference's worker-side samplers); the fused step pulls the
touched rows, computes the SGNS loss, and pushes exact deltas."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.kv.store import State
from parameter_server_tpu.kv.updaters import Adagrad, Updater
from parameter_server_tpu.utils.metrics import ProgressReporter


def _sgns_micro(
    in_up: Updater,
    out_up: Updater,
    in_state: State,
    out_state: State,
    batch: dict[str, jax.Array],  # center (B,), context (B,), negatives (B, K)
) -> tuple[State, State, jax.Array]:
    """One single-device SGNS step — shared verbatim by the per-step jit
    and the scanned multistep program so the math cannot diverge."""
    center, context, negatives = batch["center"], batch["context"], batch["negatives"]
    B, K = negatives.shape

    in_rows = {k: jnp.take(v, center, axis=0) for k, v in in_state.items()}
    # output rows for context + negatives, flattened: (B*(1+K),)
    out_ids = jnp.concatenate([context[:, None], negatives], axis=1).reshape(-1)
    out_rows = {k: jnp.take(v, out_ids, axis=0) for k, v in out_state.items()}

    loss, g_u, g_v = _sgns_weights_math(
        in_up.weights(in_rows), out_up.weights(out_rows), B, K,
        mask=batch.get("mask"),
    )

    d_in = in_up.delta(in_rows, g_u)
    new_in = {k: in_state[k].at[center].add(d_in[k]) for k in in_state}
    # NOTE: duplicate ids inside one batch are handled by scatter-add of
    # deltas; each occurrence computed its delta from the same pulled row —
    # the same within-step staleness semantics as the SPMD push path.
    d_out = out_up.delta(out_rows, g_v)
    new_out = {k: out_state[k].at[out_ids].add(d_out[k]) for k in out_state}
    return new_in, new_out, loss


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def sgns_train_step(
    in_up: Updater,
    out_up: Updater,
    in_state: State,
    out_state: State,
    batch: dict[str, jax.Array],
) -> tuple[State, State, jax.Array]:
    return _sgns_micro(in_up, out_up, in_state, out_state, batch)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def sgns_train_multistep(
    in_up: Updater,
    out_up: Updater,
    in_state: State,
    out_state: State,
    batch: dict[str, jax.Array],  # fields carry a leading (K_steps, ...) axis
) -> tuple[State, State, jax.Array]:
    """K sequential SGNS steps scanned on-device in one dispatch (the
    steps_per_call idiom of parallel.spmd.make_spmd_train_multistep:
    amortize the per-call host<->device round-trip floor). Returns the
    summed loss over microsteps."""

    def body(carry, mb):
        in_s, out_s = carry
        new_in, new_out, loss = _sgns_micro(in_up, out_up, in_s, out_s, mb)
        return (new_in, new_out), loss

    (in_s, out_s), losses = jax.lax.scan(body, (in_state, out_state), batch)
    return in_s, out_s, jnp.sum(losses)


def _sgns_weights_math(u, v_flat, B, K, mask=None):
    """SGNS loss/grads from materialized weights, shared verbatim by the
    single-device and SPMD steps.

    loss: -log sig(pos) - sum log sig(-neg), in softplus form.
    mask: optional (B,) float — padded pairs (the streaming tail) get zero
    loss AND zero gradient, so their (id 0) rows are never touched."""
    v_all = v_flat.reshape(B, 1 + K, -1)  # (B, 1+K, d)
    logits = jnp.einsum("bd,bkd->bk", u, v_all)  # (B, 1+K)
    labels = jnp.concatenate([jnp.ones((B, 1)), jnp.zeros((B, K))], axis=1)
    terms = jax.nn.softplus(logits) - labels * logits
    err = jax.nn.sigmoid(logits) - labels  # (B, 1+K)
    if mask is not None:
        terms = terms * mask[:, None]
        err = err * mask[:, None]
    loss = jnp.sum(terms)
    g_u = jnp.einsum("bk,bkd->bd", err, v_all)  # (B, d)
    g_v = (err[:, :, None] * u[:, None, :]).reshape(B * (1 + K), -1)
    return loss, g_u, g_v


def _make_w2v_local_micro(in_up, out_up, shard: int, push_mode: str):
    """Per-device SGNS microstep over the (data, kv) mesh — shared by the
    single-step and scanned multistep shard_map programs. Returns the
    LOCAL (un-psummed) loss."""
    from jax import lax

    from parameter_server_tpu.parallel.spmd import (
        _local_pull,
        _local_push,
        _local_push_aggregate,
    )

    def micro(in_l, out_l, b):
        center, context, negatives = b["center"], b["context"], b["negatives"]
        B, K = negatives.shape
        out_ids = jnp.concatenate(
            [context[:, None], negatives], axis=1
        ).reshape(-1)
        u_w = lax.psum(_local_pull(in_up, in_l, center, shard), "kv")
        v_w = lax.psum(_local_pull(out_up, out_l, out_ids, shard), "kv")
        loss, g_u, g_v = _sgns_weights_math(u_w, v_w, B, K, mask=b.get("mask"))
        if push_mode == "aggregate":
            new_in = _local_push_aggregate(in_up, in_l, center, g_u, shard)
            new_out = _local_push_aggregate(out_up, out_l, out_ids, g_v, shard)
        else:
            # word ids as the pairs come, repeats among them: no ascending
            # promise to the scatter
            new_in = _local_push(
                in_up, in_l, lax.all_gather(center, "data"),
                lax.all_gather(g_u, "data"), shard,
            )
            new_out = _local_push(
                out_up, out_l, lax.all_gather(out_ids, "data"),
                lax.all_gather(g_v, "data"), shard,
            )
        return new_in, new_out, loss

    return micro


def _make_w2v_spmd(
    in_up: Updater, out_up: Updater, mesh, vocab_size: int,
    push_mode: str, multistep: bool,
):
    """Shared builder for the K=1 and scanned-K w2v mesh programs (one
    home for validation, specs, and the jit contract, so the single/multi
    pair cannot silently diverge — the _wrap_stepper pattern of
    parallel.spmd)."""
    import functools

    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel.spmd import _shard_size, state_spec

    if push_mode not in ("per_worker", "aggregate"):
        raise ValueError(f"unknown push_mode {push_mode!r}")
    micro = _make_w2v_local_micro(
        in_up, out_up, _shard_size(vocab_size, mesh.shape["kv"]), push_mode
    )

    def local_step(in_l, out_l, batch):
        b = {k: v[0] for k, v in batch.items()}
        if not multistep:
            new_in, new_out, loss = micro(in_l, out_l, b)
            return new_in, new_out, lax.psum(loss, "data")

        def body(carry, mb):  # b fields carry a leading (K_steps, ...) axis
            new_in, new_out, loss = micro(carry[0], carry[1], mb)
            return (new_in, new_out), loss

        (in_s, out_s), losses = lax.scan(body, (in_l, out_l), b)
        return in_s, out_s, lax.psum(jnp.sum(losses), "data")

    step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec(), state_spec(), P("data")),
        out_specs=(state_spec(), state_spec(), P()),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def jitted(in_state, out_state, batch):
        return step(in_state, out_state, batch)

    return jitted


def make_w2v_spmd_train_step(
    in_up: Updater, out_up: Updater, mesh, vocab_size: int,
    push_mode: str = "per_worker",
):
    """SGNS step over the (data, kv) mesh: BOTH embedding tables are
    range-sharded over "kv" (the server tables), pair batches over "data"
    (the workers) — same layout as the MF app (BASELINE word2vec config:
    the classic two-huge-tables parameter-server workload).

    push_mode "aggregate" pre-sums per-key grads across data shards with
    one psum per table and applies ONE updater step (the north star's
    "push ≡ reduce-scatter") — the win matters most here, where the
    (B·(1+K), dim) output-table push makes the all-gather the most
    expensive part of the per_worker path. Standard sync aggregation for
    AdaGrad (same fixed point, different trajectory)."""
    return _make_w2v_spmd(
        in_up, out_up, mesh, vocab_size, push_mode, multistep=False
    )


def make_w2v_spmd_train_multistep(
    in_up: Updater, out_up: Updater, mesh, vocab_size: int,
    push_mode: str = "per_worker",
):
    """K sequential SGNS steps per device call over the (data, kv) mesh:
    batch fields are stacked (D, K_steps, ...) — data shard leading
    (sharded), microstep second (lax.scan'd). One transfer + one dispatch
    per K steps (the steps_per_call idiom; see
    parallel.spmd.make_spmd_train_multistep). Returns the summed loss."""
    return _make_w2v_spmd(
        in_up, out_up, mesh, vocab_size, push_mode, multistep=True
    )


def _group_microbatches(items: list[dict], k_steps: int, axis: int) -> dict:
    """Stack up to K per-microstep host batch dicts on a NEW microstep
    axis (axis 0 for single-device (B, ...) items, axis 1 for mesh-stacked
    (D, ...) items) for the scanned multistep programs. A ones mask is
    added where absent, and a partial final group is padded with all-zero
    microsteps — mask 0 makes them inert (zero loss, zero gradient)."""
    items = [
        dict(b, mask=b.get("mask", np.ones_like(b["center"], dtype=np.float32)))
        for b in items
    ]
    if len(items) < k_steps:
        pad = {k: np.zeros_like(v) for k, v in items[0].items()}
        items = items + [pad] * (k_steps - len(items))
    return {k: np.stack([b[k] for b in items], axis=axis) for k in items[0]}


class NegativeSampler:
    """unigram^0.75 sampler (word2vec's standard trick): inverse-CDF via
    searchsorted — O(log V) per draw, no per-call table rebuild (rng.choice
    with p re-normalizes the whole distribution every call)."""

    def __init__(self, counts: np.ndarray, power: float = 0.75, seed: int = 0):
        p = np.asarray(counts, dtype=np.float64) ** power
        self.p = p / p.sum()
        self._cdf = np.cumsum(self.p)
        self._cdf[-1] = 1.0
        self.rng = np.random.default_rng(seed)

    def sample(self, shape) -> np.ndarray:
        u = self.rng.random(size=shape)
        return np.searchsorted(self._cdf, u, side="right")


# ---------------------------------------------------------------------------
# Streaming corpus path (BASELINE's "1B-word corpus" spec): skip-gram pairs
# are NEVER materialized for the whole corpus. Token files flow through a
# WorkloadPool (the reference's file-shard assignment), each worker stream
# reads blocks of tokens, windows them into pairs, block-shuffles, and
# emits fixed-size batches — host memory is bounded by one block's pairs
# (~ 2 * window * block_tokens), independent of corpus size.
# ---------------------------------------------------------------------------


def _window_pairs(
    tokens: np.ndarray, window: int, skip_prefix: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """(center, context) pairs within ``window``; with skip_prefix = W,
    pairs whose LATER token falls inside the first W tokens are dropped —
    the cross-block carry trick: prepend the previous block's last W
    tokens, and boundary-crossing pairs appear exactly once."""
    cs, xs = [], []
    for off in range(1, window + 1):
        a, b = tokens[:-off], tokens[off:]  # pair i: (i, i + off)
        lo = max(0, skip_prefix - off)  # keep i + off >= skip_prefix
        cs.append(a[lo:])
        xs.append(b[lo:])
        cs.append(b[lo:])
        xs.append(a[lo:])
    if not cs:
        z = np.zeros(0, dtype=tokens.dtype)
        return z, z
    return np.concatenate(cs), np.concatenate(xs)


def iter_token_blocks(path: str, block_tokens: int = 1 << 20):
    """Stream int token-id blocks from a corpus file: ``.npy`` arrays are
    mmap'd and sliced; anything else is whitespace-separated integer text
    read in bounded chunks (partial tokens carried across chunk reads)."""
    if str(path).endswith(".npy"):
        arr = np.load(path, mmap_mode="r")
        for lo in range(0, len(arr), block_tokens):
            yield np.asarray(arr[lo : lo + block_tokens], dtype=np.int64)
        return
    carry = b""
    pending: list[np.ndarray] = []
    n_pending = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 22)
            if not chunk:
                break
            chunk = carry + chunk
            cut = max(chunk.rfind(b" "), chunk.rfind(b"\n"), chunk.rfind(b"\t"))
            if cut < 0:
                carry = chunk
                continue
            carry = chunk[cut + 1 :]
            toks = chunk[:cut].split()
            if toks:
                pending.append(np.array(toks, dtype=np.int64))
                n_pending += len(pending[-1])
            if n_pending >= block_tokens:
                # concatenate ONCE per read chunk and yield fixed-offset
                # slices (re-concatenating the tail per block would memcpy
                # the remainder O(blocks) times)
                flat = np.concatenate(pending)
                usable = len(flat) // block_tokens * block_tokens
                for off in range(0, usable, block_tokens):
                    yield flat[off : off + block_tokens]
                rest = flat[usable:]
                pending, n_pending = ([rest], len(rest)) if len(rest) else ([], 0)
    if carry.strip():
        pending.append(np.array([int(carry)], dtype=np.int64))
        n_pending += 1
    if n_pending:
        yield np.concatenate(pending)


def count_vocab(
    files: list[str], vocab_size: int, block_tokens: int = 1 << 20
) -> np.ndarray:
    """Streaming unigram counts over corpus files (the sampler's input)."""
    counts = np.zeros(vocab_size, dtype=np.int64)
    for f in files:
        for block in iter_token_blocks(str(f), block_tokens):
            if len(block) and (block.min() < 0 or block.max() >= vocab_size):
                bad = block[(block < 0) | (block >= vocab_size)][0]
                raise ValueError(
                    f"corpus file {f!r} has token id {int(bad)} outside "
                    f"[0, vocab_size={vocab_size})"
                )
            counts += np.bincount(block, minlength=vocab_size)
    return counts


class PairStream:
    """One worker's streaming pair source: drains corpus files from the
    pool, windows token blocks into block-shuffled (center, context) pair
    batches with negatives. Compatible with data.pipeline.PrefetchPipeline
    (``next_batch`` / ``_empty``)."""

    def __init__(
        self,
        worker_id: int,
        pool,  # WorkloadPool of corpus file paths
        *,
        window: int,
        batch_size: int,
        num_negatives: int,
        sampler: NegativeSampler,
        block_tokens: int = 1 << 20,
        seed: int = 0,
    ):
        self.worker_id = worker_id
        self.pool = pool
        self.window = window
        self.batch_size = batch_size
        self.K = num_negatives
        self.sampler = sampler
        self.block_tokens = block_tokens
        self.rng = np.random.default_rng(seed * 100003 + worker_id * 7919)
        self._blocks = None  # token-block iterator of the current file
        self._current: str | None = None
        self._tail: np.ndarray | None = None  # last W tokens of prev block
        self._buf_c = np.zeros(0, dtype=np.int64)
        self._buf_x = np.zeros(0, dtype=np.int64)
        self.max_buffered = 0  # observability: peak pairs held

    def _next_block(self) -> np.ndarray | None:
        while True:
            if self._blocks is not None:
                block = next(self._blocks, None)
                if block is not None:
                    return block
                if self._current is not None:
                    self.pool.finish(self._current)
                self._blocks = None
                self._current = None
                self._tail = None  # windows never span files
            w = self.pool.fetch(self.worker_id)
            if w is None:
                return None
            self._current = w
            self._blocks = iter_token_blocks(str(w), self.block_tokens)

    def _fill(self) -> None:
        if len(self._buf_c) >= self.batch_size:
            return
        new_c, new_x = [], []
        n_new = 0
        while len(self._buf_c) + n_new < self.batch_size:
            block = self._next_block()
            if block is None:
                break
            if self._tail is not None and len(self._tail):
                t = np.concatenate([self._tail, block])
                c, x = _window_pairs(t, self.window, skip_prefix=len(self._tail))
            else:
                t = block
                c, x = _window_pairs(block, self.window)
            # carry the last W tokens of the CONCATENATED stream (a block
            # shorter than W must not truncate the window)
            self._tail = t[-self.window :].copy()
            if len(c):
                new_c.append(c)
                new_x.append(x)
                n_new += len(c)
        if n_new:
            # block shuffle: ONE permutation over (buffer + new pairs) per
            # fill — same uniform shuffle as permuting per appended block,
            # without re-copying the growing buffer k times
            c = np.concatenate([self._buf_c, *new_c])
            x = np.concatenate([self._buf_x, *new_x])
            perm = self.rng.permutation(len(c))
            self._buf_c, self._buf_x = c[perm], x[perm]
            self.max_buffered = max(self.max_buffered, len(self._buf_c))

    def next_batch(self) -> dict | None:
        self._fill()
        n = min(len(self._buf_c), self.batch_size)
        if n == 0:
            return None
        b = self._make(self._buf_c[:n], self._buf_x[:n])
        self._buf_c = self._buf_c[n:]
        self._buf_x = self._buf_x[n:]
        return b

    def _make(self, c: np.ndarray, x: np.ndarray) -> dict:
        bs = self.batch_size
        out = {
            "center": np.zeros(bs, dtype=np.int32),
            "context": np.zeros(bs, dtype=np.int32),
            "negatives": self.sampler.sample((bs, self.K)).astype(np.int32),
            "mask": np.zeros(bs, dtype=np.float32),
        }
        out["center"][: len(c)] = c
        out["context"][: len(c)] = x
        out["mask"][: len(c)] = 1.0
        return out

    def _empty(self) -> dict:
        return {
            "center": np.zeros(self.batch_size, dtype=np.int32),
            "context": np.zeros(self.batch_size, dtype=np.int32),
            "negatives": np.zeros((self.batch_size, self.K), dtype=np.int32),
            "mask": np.zeros(self.batch_size, dtype=np.float32),
        }


class Word2Vec:
    """SGNS app over vocab_size words, dim-dimensional embeddings."""

    def __init__(
        self,
        vocab_size: int,
        dim: int = 64,
        eta: float = 0.3,
        num_negatives: int = 5,
        window: int = 2,
        seed: int = 0,
        reporter: ProgressReporter | None = None,
        mesh=None,
        max_delay: int = 0,
        push_mode: str = "per_worker",
        steps_per_call: int = 1,
    ):
        self.vocab_size = vocab_size
        self.dim = dim
        self.K = num_negatives
        self.window = window
        self.reporter = reporter or ProgressReporter()
        self.in_up = Adagrad(eta=eta)
        self.out_up = Adagrad(eta=eta)
        self.mesh = mesh
        self.max_delay = max_delay  # SSP dispatch bound (ref: BASELINE's
        # "bounded-staleness SSP" word2vec config)
        # K sequential SGNS steps scanned per device call (the
        # solver.steps_per_call idiom): amortizes the per-call
        # host<->device round-trip floor; max_delay then counts device
        # CALLS in flight (each K steps deep)
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        self.steps_per_call = steps_per_call
        rng = np.random.default_rng(seed)
        self.in_state = self.in_up.init(vocab_size, dim)
        self.out_state = self.out_up.init(vocab_size, dim)
        self.in_state["w"] = jnp.asarray(
            rng.uniform(-0.5 / dim, 0.5 / dim, size=(vocab_size, dim)),
            dtype=jnp.float32,
        )
        # output table starts at zero (standard word2vec init)
        if mesh is not None:
            from parameter_server_tpu.parallel.spmd import shard_state

            maker = (
                make_w2v_spmd_train_multistep
                if steps_per_call > 1
                else make_w2v_spmd_train_step
            )
            self._spmd_step = maker(
                self.in_up, self.out_up, mesh, vocab_size, push_mode=push_mode
            )
            self.in_state = shard_state(self.in_state, mesh)
            self.out_state = shard_state(self.out_state, mesh)

    def make_pairs(self, corpus: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(center, context) skip-gram pairs within the window."""
        centers, contexts = [], []
        n = len(corpus)
        for off in range(1, self.window + 1):
            centers.append(corpus[:-off])
            contexts.append(corpus[off:])
            centers.append(corpus[off:])
            contexts.append(corpus[:-off])
        return np.concatenate(centers), np.concatenate(contexts)

    def _make_batch(self, centers, contexts, sampler, sel) -> dict:
        return {
            "center": centers[sel].astype(np.int32),
            "context": contexts[sel].astype(np.int32),
            "negatives": sampler.sample((len(sel), self.K)).astype(np.int32),
        }

    def _dispatch_prepared(self, batch_np: dict, k_steps: int):
        """Issue ONE device call on ready host arrays (already
        microstep-grouped when ``k_steps > 1``); returns the device loss
        (sum over the call's microsteps, unretired)."""
        if self.mesh is not None:
            from parameter_server_tpu.parallel.spmd import place_stacked

            batch = place_stacked(batch_np, self.mesh)
            self.in_state, self.out_state, loss = self._spmd_step(
                self.in_state, self.out_state, batch
            )
            return loss
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        fn = sgns_train_multistep if k_steps > 1 else sgns_train_step
        self.in_state, self.out_state, loss = fn(
            self.in_up, self.out_up, self.in_state, self.out_state, batch
        )
        return loss

    def _dispatch(self, micro: list[dict], k_steps: int):
        """Group up to ``k_steps`` microstep batches (mesh-stacked
        (D, ...) dicts when a mesh is set, plain (B, ...) dicts otherwise)
        inline and issue one device call — the in-memory and serial/debug
        paths; the streaming pipeline assembles groups on its stacker
        thread instead (see _train_stream)."""
        if k_steps == 1:
            return self._dispatch_prepared(micro[0], 1)
        axis = 1 if self.mesh is not None else 0
        return self._dispatch_prepared(
            _group_microbatches(micro, k_steps, axis), k_steps
        )

    def train_epoch(
        self,
        corpus: np.ndarray,
        batch_size: int = 8192,
        seed: int = 0,
    ) -> float:
        """One shuffled pass. Dispatch is SSP-gated: up to ``max_delay + 1``
        steps stay in flight and losses are read back only on retirement —
        never a per-batch device sync (the async windowed pattern of
        models/linear.py, ref: the worker Executor's wait_time bound)."""
        from parameter_server_tpu.parallel.ssp import DispatchWindow

        counts = np.bincount(corpus, minlength=self.vocab_size)
        sampler = NegativeSampler(counts, seed=seed)
        centers, contexts = self.make_pairs(corpus)
        rng = np.random.default_rng(seed)
        order = rng.permutation(len(centers))
        D = self.mesh.shape["data"] if self.mesh is not None else 1
        global_bs = batch_size * D

        total_loss, n = 0.0, 0
        t0 = time.perf_counter()

        def _retire(step: int, loss_arr) -> None:
            nonlocal total_loss
            total_loss += float(loss_arr)  # sync point, bounded by the gate

        gate = DispatchWindow(self.max_delay, _retire)
        K_steps = self.steps_per_call
        starts = list(range(0, len(order) - global_bs + 1, global_bs))
        call_i = 0
        for c in range(0, len(starts), K_steps):
            chunk = starts[c : c + K_steps]
            # SSP gate: retire calls <= t - tau - 1 before dispatching t
            gate.gate(call_i)
            micro = []  # host batch dict per microstep in this call
            for s in chunk:
                sel = order[s : s + global_bs]
                if self.mesh is not None:
                    subs = [
                        self._make_batch(
                            centers, contexts, sampler,
                            sel[d * batch_size : (d + 1) * batch_size],
                        )
                        for d in range(D)
                    ]
                    micro.append(
                        {k: np.stack([b[k] for b in subs]) for k in subs[0]}
                    )
                else:
                    micro.append(
                        self._make_batch(centers, contexts, sampler, sel)
                    )
                n += len(sel)
            loss = self._dispatch(micro, K_steps)
            gate.add(call_i, loss)
            call_i += 1
        gate.drain()
        mean = total_loss / max(n, 1)
        self.reporter.report(
            examples=n, objv=mean, ex_per_sec=n / max(time.perf_counter() - t0, 1e-9)
        )
        return mean

    def train_files(
        self,
        files: list[str],
        batch_size: int = 8192,
        epochs: int = 1,
        block_tokens: int = 1 << 20,
        seed: int = 0,
        counts: np.ndarray | None = None,
        pipeline_depth: int = 2,
    ) -> float:
        """Streaming corpus training (BASELINE's 1B-word operating point):
        corpus file shards flow through a WorkloadPool to one PairStream
        per data shard; pair batches are built on PrefetchPipeline threads
        and dispatched SSP-gated — pairs are never materialized corpus-wide
        and host memory is bounded by blocks, not the corpus.

        counts: pre-computed unigram counts (else one cheap streaming
        counting pass feeds the negative sampler)."""
        from parameter_server_tpu.parallel.workload import WorkloadPool

        if counts is None:
            counts = count_vocab(files, self.vocab_size, block_tokens)
        D = self.mesh.shape["data"] if self.mesh is not None else 1
        total_loss, n_pairs = 0.0, 0
        t0 = time.perf_counter()
        for ep in range(epochs):
            pool = WorkloadPool([str(f) for f in files])
            streams = [
                PairStream(
                    w, pool,
                    window=self.window, batch_size=batch_size,
                    num_negatives=self.K,
                    sampler=NegativeSampler(counts, seed=seed + 31 * ep + w),
                    block_tokens=block_tokens, seed=seed + 997 * ep,
                )
                for w in range(D)
            ]
            loss, n = self._train_stream(streams, pipeline_depth)
            total_loss += loss
            n_pairs += n
        mean = total_loss / max(n_pairs, 1)
        self.reporter.report(
            examples=n_pairs, objv=mean,
            ex_per_sec=n_pairs / max(time.perf_counter() - t0, 1e-9),
        )
        return mean

    def _train_stream(self, streams, pipeline_depth: int) -> tuple[float, int]:
        """SSP-gated dispatch of streamed pair batches; returns
        (sum loss, real pairs). pipeline_depth=0 builds batches serially
        inline (deterministic stream->file assignment, no threads) — same
        contract as cfg.data.pipeline_depth in PodTrainer."""
        import contextlib

        from parameter_server_tpu.data.pipeline import PrefetchPipeline
        from parameter_server_tpu.parallel.ssp import DispatchWindow

        def prepare(batches: list[dict]) -> tuple[dict, int]:
            stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
            return stacked, int(sum(b["mask"].sum() for b in batches))

        total_loss, n_pairs = 0.0, 0

        def _retire(step: int, loss_arr) -> None:
            nonlocal total_loss
            total_loss += float(loss_arr)

        gate = DispatchWindow(self.max_delay, _retire)
        K_steps = self.steps_per_call

        def _strip(stacked: dict) -> dict:
            # mesh batches stay (D, ...)-stacked; single-device takes its
            # lone shard's (B, ...) view
            return (
                stacked
                if self.mesh is not None
                else {k: v[0] for k, v in stacked.items()}
            )

        def assemble(items: list[tuple]) -> tuple[dict, int]:
            # K-way group stacking ON the pipeline's stacker thread (the
            # trainer's group_size/assemble pattern): the dispatch loop
            # below only pops ready device-call payloads
            grouped = _group_microbatches(
                [_strip(it[0]) for it in items], K_steps,
                axis=1 if self.mesh is not None else 0,
            )
            return grouped, sum(it[1] for it in items)

        piped = pipeline_depth > 0
        if piped:
            pipeline = PrefetchPipeline(
                streams, prepare, depth=pipeline_depth,
                group_size=K_steps,
                assemble=assemble if K_steps > 1 else None,
            )
            next_item = pipeline.get
        else:
            pipeline = contextlib.nullcontext()

            def next_item():
                batches = [s.next_batch() for s in streams]
                if all(b is None for b in batches):
                    return None
                return prepare(
                    [
                        b if b is not None else streams[i]._empty()
                        for i, b in enumerate(batches)
                    ]
                )

        call_i = 0
        with pipeline:
            while True:
                gate.gate(call_i)
                if piped and K_steps > 1:
                    item = next_item()  # pre-assembled (grouped, n)
                    if item is None:
                        break
                    grouped, n = item
                    n_pairs += n
                    loss = self._dispatch_prepared(grouped, K_steps)
                elif K_steps == 1:
                    item = next_item()
                    if item is None:
                        break
                    stacked, n = item
                    n_pairs += n
                    loss = self._dispatch([_strip(stacked)], 1)
                else:  # serial/debug path: group inline
                    micro = []
                    for _ in range(K_steps):
                        item = next_item()
                        if item is None:
                            break
                        stacked, n = item
                        micro.append(_strip(stacked))
                        n_pairs += n
                    if not micro:
                        break
                    loss = self._dispatch(micro, K_steps)
                gate.add(call_i, loss)
                call_i += 1
            gate.drain()
        return total_loss, n_pairs

    def embeddings(self) -> np.ndarray:
        return np.asarray(self.in_up.weights(self.in_state))

    def similarity(self, a: int, b: int) -> float:
        E = self.embeddings()
        x, y = E[a], E[b]
        den = np.linalg.norm(x) * np.linalg.norm(y)
        return float(x @ y / den) if den > 0 else 0.0
