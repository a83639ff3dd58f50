"""Cross-process parameter-server tier: range-sharded servers + slice workers.

Reference analog: the whole N-servers x M-workers topology of the reference
(scheduler assigns ranges, workers Push/Pull against servers over the wire,
src/system/ + src/parameter/shared_parameter.h). On a TPU pod that topology
collapses into one SPMD program (parallel/spmd.py) — THIS module is for the
tier where a single program can't reach: separate processes/slices joined
only by host networking (DCN), and the multi-process integration harness
(the analog of script/local.sh, the reference's de-facto integration test).

Each *server* process owns a contiguous key range of the model (ref:
Range::EvenDivide over servers) and applies the shared updaters
(kv/updaters.py) on push. Each *worker* process streams its assigned file
shards (coordinator workload pool), localizes batches, pulls touched
weights per range, computes the CSR gradient on its local device with the
same jitted math as the single-program path (ops/sparse.py), and pushes
per-range gradients back. Consistency is the coordinator's SSP clock
(`max_delay`), exactly the reference's wait_time dependency.

The reference's message filters come back to life on this wire
(src/filter/): key caching (send a signature instead of the key list when
the server has seen it), zlib compression of payload blocks, and
fixed-point float truncation with stochastic rounding (filters/fixed_point).

Quantized transport (``[wire] quant = int8|int16``, filters/quant.py): a
push's gradient rides as a per-segment-scale integer payload (~3.8x fewer
bytes at int8) with CLIENT-SIDE ERROR FEEDBACK — the residual each
quantized push loses to rounding is folded into the next push of the same
keys, so the server's (stochastically rounded, unbiased) applies converge
to the float trajectory. The feature negotiates per connection (the
``_feat``/"qwire" advert): against a server that never acks, the handle
transparently stays on the float path — and flushes any accumulated
residual into its next float push, so no gradient mass is ever stranded
by a mid-run downgrade. Residual folding happens exactly once per LOGICAL
push, at encode time: transport-level resends and the ``"k<n>"``
keyed-seq recovery path reuse the already-encoded payload, so chaos
(drop/disconnect/duplicate) can never double-fold an accumulator.
``[wire] quant_pull`` extends the codec to pull replies (read-mostly
serving traffic; no feedback loop, so it is opt-in).

Serving plane (``[serve]``, ISSUE 7): production traffic is dominated by
read-mostly pulls from inference, and the OSDI'14 key-cache filter
generalizes to VALUES for it. Every RCU publish stamps the shard with a
monotonic per-life snapshot version; pull replies carry it, and a
serving :class:`ServerHandle` (``serving=True`` + ``[serve] cache``)
caches the decoded rows per key-set signature — serving them locally
within ``ttl_ms``, revalidating with ``if_newer=<ver>`` past it (an
unchanged shard answers ``not_modified`` with zero payload), and
invalidating its own entries exactly on push. Server-side, concurrent
and repeated pulls of a HOT key set against one snapshot share a single
encoded reply (single-flight coalescing), and admission control sheds
revalidations that advertised a cached fallback (``shed_ok``) once the
apply queue or the withheld reply bytes cross the ``[serve] shed_*``
thresholds — bounded staleness for readers instead of unbounded queue
growth for everyone. The training tier never arms the cache: its
staleness contract is the SSP clock, not a TTL.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue as queue_mod
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

import numpy as np

from parameter_server_tpu.kv import store as kv_store
from parameter_server_tpu.kv.updaters import Updater
from parameter_server_tpu.parallel.chaos import PLAN_ENV, SEED_ENV, FaultPlan
from parameter_server_tpu.parallel.control import (
    Arrays,
    ControlClient,
    Coordinator,
    DeferredReply,
    RpcClient,
    RpcServer,
)
from parameter_server_tpu.utils import flightrec, trace
from parameter_server_tpu.utils.clock import now_wall_us, skew_clamped_age_s
from parameter_server_tpu.utils.config import PSConfig, ServeConfig, ServerConfig
from parameter_server_tpu.utils.flightrec import watchdog
from parameter_server_tpu.utils.heartbeat import HeartbeatReporter, host_stats
from parameter_server_tpu.utils.keyrange import KeyRange
from parameter_server_tpu.utils.metrics import (
    RangeScope,
    key_heat,
    latency_histograms,
    observe_scalar,
    race_track,
    telemetry_snapshot,
    wire_counters,
)


def _plan_from_cfg(cfg: PSConfig) -> FaultPlan | None:
    """FaultPlan from [fault] fault_plan/fault_seed ("" = rely on the
    PS_FAULT_PLAN env fallback inside RpcServer)."""
    if not cfg.fault.fault_plan:
        return None
    return FaultPlan.parse(cfg.fault.fault_plan, seed=cfg.fault.fault_seed)


def _sig(keys: np.ndarray) -> str:
    """Key-list signature (ref: key_caching.h signatures)."""
    return hashlib.blake2b(keys.tobytes(), digest_size=8).hexdigest()


# Bound on cached key lists per endpoint. Streamed minibatches mostly have
# distinct key sets (hits come from pull->push pairs and epoch repeats), so
# an unbounded cache would grow linearly with steps; the need_keys retry
# makes eviction always safe.
_KEY_CACHE_CAP = 512


class _LruSigs:
    """Tiny thread-safe LRU over signature -> value (value may be None for a
    set). Locked: server connection threads and the worker's in-flight push
    threads touch these caches concurrently."""

    def __init__(self, cap: int = _KEY_CACHE_CAP):
        from collections import OrderedDict

        self._d: OrderedDict = OrderedDict()
        self._cap = cap
        self._lock = threading.Lock()

    def get(self, k):
        with self._lock:
            if k in self._d:
                self._d.move_to_end(k)
                return self._d[k]
            return None

    def __contains__(self, k) -> bool:
        with self._lock:
            return k in self._d

    def put(self, k, v=None) -> None:
        with self._lock:
            self._d[k] = v
            self._d.move_to_end(k)
            while len(self._d) > self._cap:
                self._d.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


class _EncodeEntry:
    """One single-flight encoded pull reply: the first puller of a hot
    key set against a given snapshot computes the encode; concurrent and
    later pulls of the same (signature, version, codec) wait on ``event``
    and reuse the SAME reply header + arrays (``rep is None`` after the
    event fires means the owner's encode failed — followers encode for
    themselves). ``nbytes`` is the payload size counted against the
    cache's byte budget: 0 until filled, and 0 forever if the entry was
    evicted before its owner filled it."""

    __slots__ = ("event", "rep", "arrays", "nbytes")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.rep: dict[str, Any] | None = None
        self.arrays: Arrays | None = None
        self.nbytes = 0


class _QueuedPush:
    """One decoded push waiting in the apply queue: keys + decoded grad,
    its durable dedup identity, the caller's trace context (so the apply
    still joins the client's trace across the thread hop), and the Future
    the deferred RPC reply resolves from."""

    __slots__ = ("keys", "grad", "cid", "seq", "tctx", "future", "t_enq")

    def __init__(
        self, keys: np.ndarray, grad: np.ndarray,
        cid: str | None, seq: str | None,
        tctx: dict | None = None,
    ):
        self.keys = keys
        self.grad = grad
        self.cid = cid
        self.seq = seq
        self.tctx = tctx
        self.future: Future = Future()
        # enqueue mark: the apply thread reports queue-wait vs jitted-
        # apply time back through the deferred reply (_apw_us/_apl_us),
        # the latency-forensics planes' apply-segment split
        self.t_enq = time.perf_counter()


class ShardServer:
    """One server process: updater state over its key range, served via RPC.

    Commands: pull / push / dump / stats / shutdown. State lives on the
    process's default JAX device (CPU in the simulated harness, the local
    chip in a real multi-slice run) and updates run eagerly — this tier is
    wire-bound, not compute-bound.

    Batched apply engine (ref: the paper's servers applying *aggregated*
    updates over touched keys only): pushes don't apply on their serving
    connection threads anymore. Each decoded push lands in a bounded
    queue; ONE dedicated apply thread drains whatever has concurrently
    arrived (up to ``[server] max_batch``), pre-aggregates duplicate keys
    across clients (``kv.store.coalesce_pushes`` — the store's
    exactly-once invariant for nonlinear updaters), applies the updater
    ONCE over the union of touched rows, records the whole batch in the
    durable push ledger atomically with the state it produced, and
    publishes the new state as a single reference swap. Pulls and dumps
    serve from that published snapshot WITHOUT the write lock (RCU: the
    state dict is never mutated after publish, so a reader sees the
    pre- or post-batch table, never a torn mix); SSP bounded-delay
    semantics are unchanged — staleness was always bounded by the clock,
    not by this lock. ``[server] apply_queue = 0`` disables the engine
    (pushes apply inline under the lock — the serial pre-engine
    discipline, kept as the baseline the engine is compared with).
    """

    def __init__(
        self,
        updater: Updater,
        key_range: KeyRange,
        vdim: int = 1,
        host: str = "127.0.0.1",
        port: int = 0,
        advertise_host: str = "",
        fault_plan: FaultPlan | None = None,
        server_cfg: ServerConfig | None = None,
        serve_cfg: ServeConfig | None = None,
    ):
        import jax.numpy as jnp

        scfg = server_cfg or ServerConfig()
        svcfg = serve_cfg or ServeConfig()
        self.updater = updater
        self.range = key_range
        # versioned RCU publish: (state dict, version) swap as ONE tuple,
        # so a lock-free reader can never see rows stamped with a version
        # they don't belong to. The version is an opaque snapshot id —
        # monotonic within this server life, namespaced by a per-life
        # nonce in the high bits so a cached version from a PREVIOUS life
        # (whose tail pushes a checkpoint restart may have rolled back)
        # can never falsely validate against this one. 23 nonce bits +
        # 40 counter bits stays under 2^63, so ver / if_newer always fit
        # the binary header's fixed unsigned slots (an unmasked nonce
        # overflowed them half the time, silently demoting the serving
        # fields to the JSON tail for that server life).
        self._ver_base = (
            int.from_bytes(os.urandom(3), "big") & ((1 << 23) - 1)
        ) << 40
        # freshness plane (ISSUE 17): the publish timestamp (µs epoch)
        # rides the tuple so the lock-free reader captures (state,
        # version, publish-ts) in ONE reference swap — a pull reply's
        # age is measured against exactly the publish its rows came
        # from, never a neighbour publish.
        self._pub: tuple[dict[str, Any], int, int] = (
            updater.init(key_range.size, vdim), self._ver_base + 1,
            now_wall_us(),
        )
        self._serve_cfg = svcfg
        # freshness plane: this range's traffic/age matrix (per-range
        # counters+hists riding the ordinary telemetry namespaces)
        self._range_scope = RangeScope(key_range.begin, key_range.end)
        # single-flight encoded-pull cache: (sig, version, codec) -> entry
        self._enc_lock = threading.Lock()
        self._enc_cache: OrderedDict[tuple, _EncodeEntry] = OrderedDict()
        self._enc_cap = max(0, int(svcfg.encode_cache_entries))
        self._enc_bytes = 0  # filled entries' payload bytes (LRU-bounded)
        self._enc_bytes_max = max(0, int(svcfg.encode_cache_mb)) << 20
        # hot-key detection: pull counts per key-set signature (advisory
        # — a lost increment under a race only delays hotness by a pull)
        self._hot_counts = _LruSigs(cap=4096)
        # host weights snapshot: (version, full weights table as numpy),
        # materialized lazily on the first HOT pull of a snapshot and
        # shared by every encode at that version — a hot pull is then a
        # numpy fancy-index (~us) instead of an eager jax gather +
        # weights dispatch (~ms). Swapped as one tuple (atomic read);
        # racing materializations of a fresh version duplicate bounded
        # work instead of serializing behind a lock.
        self._host_w: tuple[int, np.ndarray] | None = None
        self._jnp = jnp
        self._key_cache = _LruSigs()  # (worker, sig) -> key array
        self._lock = threading.Lock()
        self._max_batch = max(1, int(scfg.max_batch))
        # adaptive batch ceiling (scfg.adaptive_batch): ramp the drain
        # bound to the observed arrival rate — double while batches fill
        # and the queue stays hot, halve when arrivals go sparse;
        # max_batch stays the hard ceiling
        self._adaptive_batch = bool(scfg.adaptive_batch)
        self._eff_batch = (
            min(4, self._max_batch) if self._adaptive_batch
            else self._max_batch
        )
        self._apply_q: queue_mod.Queue[_QueuedPush] | None = (
            queue_mod.Queue(maxsize=int(scfg.apply_queue))
            if scfg.apply_queue > 0
            else None
        )
        self._apply_open = self._apply_q is not None
        self._apply_thread: threading.Thread | None = None
        self._ctr_lock = threading.Lock()  # counters bumped by conn threads
        self._ckpt_write_lock = threading.Lock()  # one dump writer at a time
        self._ckpt_thread: threading.Thread | None = None
        # durable push dedup: cid -> recently applied push seqs (str-keyed;
        # seqs normalize through str() so the ledger survives the npz
        # round-trip). Mutated ONLY under self._lock, in the same critical
        # section as the state mutation it describes, and checkpointed
        # with the state — the RpcServer reply cache dies with the
        # process, so without this a push applied-and-dumped whose reply
        # was lost to a kill would be re-applied by the restarted server.
        self._applied_push: OrderedDict[str, OrderedDict[str, None]] = OrderedDict()
        self.counters = {
            "pulls": 0, "pushes": 0, "cache_hits": 0, "need_keys": 0,
            "push_replays": 0, "apply_batches": 0, "push_coalesced": 0,
            # serving plane (ISSUE 7): conditional pulls answered without
            # a payload, pulls shed under overload, real row encodes, and
            # encodes shared across pulls by the single-flight cache
            "not_modified": 0, "shed": 0, "pull_encodes": 0,
            "encode_reuse": 0,
        }
        if host in ("0.0.0.0", "::", "") and not advertise_host:
            raise ValueError(
                "binding a wildcard address requires advertise_host: "
                "publishing 0.0.0.0 to the coordinator would point remote "
                "workers at their own loopback"
            )
        self.server = RpcServer(
            self._handle, host, port, fault_plan=fault_plan,
            # pull/dump/stats re-apply harmlessly — bypassing the reply
            # cache keeps their row-payload replies from being pinned
            idempotent_cmds=frozenset({"pull", "dump", "stats"}),
            expose_identity=True,  # push branch keeps the durable ledger
            lane_hi=scfg.lane_hi,
            lane_lo=scfg.lane_lo,
            withheld_max_bytes=scfg.withheld_max_mb << 20,
            # this server decodes the per-segment quantized codec: acking
            # "qwire" is what lets a quantized client leave the float path
            features=frozenset({"qwire"}),
        )
        # bind and advertise may differ: bind 0.0.0.0 to accept remote
        # workers, advertise a routable hostname via the coordinator KV
        _, bound_port = self.server.address.rsplit(":", 1)
        self.address = f"{advertise_host or host}:{bound_port}"
        # lockset race witness (PS_RACE_WITNESS=1): the encode-cache
        # byte budget mutates under _enc_lock and the durable ledger
        # reference only inside _lock's apply/checkpoint critical
        # sections — the two pieces of serving/apply state a refactor
        # is most likely to touch lock-free by accident
        race_track(
            self, ("_enc_bytes", "_applied_push"),
            f"ShardServer:{self.address}",
        )

    # push-ledger bounds: wider than the reply cache's — entries are tiny
    # (short strings) and must cover a restart window, not just the last
    # in-flight call per client
    _LEDGER_SEQS = 64
    _LEDGER_CLIENTS = 1024

    def _record_push(self, cid: str, seq: str) -> None:
        """Record an applied push in the durable dedup ledger. Caller holds
        ``self._lock``: the record and the state mutation it witnesses must
        be one atomic unit with respect to ``save_state``'s snapshot."""
        per = self._applied_push.get(cid)
        if per is None:
            per = self._applied_push[cid] = OrderedDict()
            while len(self._applied_push) > self._LEDGER_CLIENTS:
                self._applied_push.popitem(last=False)
        else:
            self._applied_push.move_to_end(cid)
        per[seq] = None
        while len(per) > self._LEDGER_SEQS:
            per.popitem(last=False)

    def _bump(self, name: str) -> None:
        with self._ctr_lock:
            self.counters[name] += 1

    # -- versioned RCU state ----------------------------------------------

    @property
    def state(self) -> dict[str, Any]:
        """The published state table (RCU: immutable after publish)."""
        return self._pub[0]

    @state.setter
    def state(self, new_state: dict[str, Any]) -> None:
        """Publish a new state table AND bump the snapshot version in one
        reference swap — every writer (batched apply, serial push,
        checkpoint load) goes through here, so a pull reply's ``ver``
        always identifies exactly the table its rows came from."""
        ver = self._pub[1] + 1
        self._pub = (new_state, ver, now_wall_us())
        # flight recorder: every publish, whatever the writer — the
        # postmortem's version-regression detector reads this stream
        flightrec.record("rcu.publish", ver=ver)

    @property
    def version(self) -> int:
        """Current published snapshot version (opaque; see __init__)."""
        return self._pub[1]

    # -- serving plane: overload signal + single-flight encode cache ------

    def overloaded(self) -> bool:
        """Admission-control signal (``[serve] shed_*``): the apply queue
        is backing up or this server's withheld coalesced replies are
        pinning too many bytes — time to shed cache-backed pulls."""
        svcfg = self._serve_cfg
        if (
            svcfg.shed_queue_depth > 0
            and self._apply_q is not None
            and self._apply_q.qsize() >= svcfg.shed_queue_depth
        ):
            return True
        mb = svcfg.shed_withheld_mb
        return mb > 0 and self.server.withheld_bytes() >= (mb << 20)

    def _note_pull(self, sig: str) -> bool:
        """Count one pull of this key-set signature; True once the sig
        is HOT (its encoded reply is worth caching). The threshold keeps
        one-off training sweeps out of the encode cache."""
        c = (self._hot_counts.get(sig) or 0) + 1
        self._hot_counts.put(sig, c)
        if c == self._serve_cfg.hot_min_pulls:
            wire_counters.inc("serve_hot_keys")
        return c >= self._serve_cfg.hot_min_pulls

    def _enc_claim(self, ck: tuple) -> tuple[_EncodeEntry, bool]:
        """(entry, owner): owner=True means this pull computes the
        encode; False means another pull (possibly already finished)
        owns it and the entry's event/result are to be shared."""
        with self._enc_lock:
            ent = self._enc_cache.get(ck)
            if ent is not None:
                self._enc_cache.move_to_end(ck)
                return ent, False
            ent = self._enc_cache[ck] = _EncodeEntry()
            self._enc_evict_over_budget()
            return ent, True

    def _enc_evict_over_budget(self) -> None:
        """LRU-evict past the entry AND byte budgets (caller holds
        ``_enc_lock``). Each filled entry pins its reply payload, so the
        byte bound — not just the entry count — is what stops a server
        with multi-MB pulls pinning entries x payload of memory.
        Unfilled entries count 0; an owner filling an already-evicted
        entry notices and skips the byte accounting."""
        while self._enc_cache and (
            len(self._enc_cache) > self._enc_cap
            or self._enc_bytes > self._enc_bytes_max
        ):
            _, old = self._enc_cache.popitem(last=False)
            self._enc_bytes -= old.nbytes

    def _enc_fill(
        self, ck: tuple, ent: _EncodeEntry, rep: dict[str, Any],
        arrays: Arrays,
    ) -> None:
        """Publish the owner's finished encode to its followers and
        count its payload against the byte budget (only while the entry
        is still cached — a concurrent eviction wins)."""
        nb = sum(int(a.nbytes) for a in arrays.values())
        with self._enc_lock:
            ent.rep, ent.arrays = rep, arrays
            if self._enc_cache.get(ck) is ent:
                ent.nbytes = nb
                self._enc_bytes += nb
                self._enc_evict_over_budget()
        ent.event.set()

    def _enc_fail(self, ck: tuple, ent: _EncodeEntry) -> None:
        """The owner's encode raised: drop the entry and release any
        followers (they see ``rep is None`` and encode for themselves) —
        a poisoned entry must never park the reply lane."""
        with self._enc_lock:
            if self._enc_cache.get(ck) is ent:
                del self._enc_cache[ck]
        ent.event.set()

    def start(self) -> "ShardServer":
        self._start_apply_thread()
        self.server.start()
        return self

    def serve_forever(self) -> None:
        self._start_apply_thread()
        self.server.start()
        while not self.server._stop.wait(0.2):
            pass

    # -- batched apply engine ---------------------------------------------

    def _start_apply_thread(self) -> None:
        if self._apply_q is None or self._apply_thread is not None:
            return
        # watchdog: a non-advancing apply engine is THE server stall the
        # flight recorder exists to catch — busy means work queued or a
        # batch mid-apply; progress is the completed-batch counter.
        # The id suffix keeps the name unique per server INSTANCE: two
        # servers over the same range (tests, a restart in-process)
        # must never alias one registry entry, or one engine's exit
        # would unregister the other's probe.
        self._applying = False
        self._wd_name = (
            f"apply:{self.range.begin}-{self.range.end}:{id(self):x}"
        )

        def probe() -> tuple[bool, int]:
            q = self._apply_q
            busy = (q is not None and not q.empty()) or self._applying
            return busy, self.counters["apply_batches"]

        watchdog.register(self._wd_name, probe, thread_name="ps-apply")
        self._apply_thread = threading.Thread(
            target=self._apply_loop, daemon=True, name="ps-apply"
        )
        self._apply_thread.start()

    @staticmethod
    def _fail_stopping(item: _QueuedPush) -> None:
        """Fail a push stranded by engine shutdown with ConnectionError —
        the RPC layer severs the connection instead of sending a clean
        error reply, so the client's transport heal RESENDS the push
        (against the relaunched server, deduped by the durable ledger)
        rather than hard-failing the worker on a transient condition."""
        if not item.future.done():
            try:
                item.future.set_exception(ConnectionError(
                    "shard server stopping; push not applied"
                ))
            except Exception:  # noqa: BLE001 — the drain beat us to it
                pass

    def _enqueue_push(self, item: _QueuedPush) -> None:
        """Admit one decoded push into the apply queue (backpressure: a
        full queue parks this serving thread until the engine drains —
        which also withholds this connection's coalesced replies for the
        drain's duration, bounded by apply_queue/max_batch batch applies;
        settling deferred acks before every push instead would serialize
        the very pipeline the engine exists to batch). Never raises — a
        shutdown race resolves the item's future with ConnectionError
        instead (see _fail_stopping)."""
        q = self._apply_q
        assert q is not None
        observe_scalar("server.apply_queue.n", q.qsize() + 1)
        trace.counter("server.apply_queue_depth", q.qsize() + 1)
        while True:
            if not self._apply_open:
                self._fail_stopping(item)
                return
            try:
                q.put(item, timeout=0.05)
            except queue_mod.Full:
                continue
            if not self._apply_open:
                # raced with engine shutdown: the grace drain may already
                # have finished, leaving this item parked in a queue
                # nobody drains — fail it here (drain may also have)
                self._fail_stopping(item)
            return

    def _apply_loop(self) -> None:
        """The apply thread: drain whatever pushes have concurrently
        arrived (bounded by max_batch) and apply them as ONE coalesced
        update. Exits once the server stops, failing stragglers so no
        serving thread parks on an unresolvable deferred reply (their
        clients resend to the relaunched server; the ledger dedups)."""
        q = self._apply_q
        assert q is not None
        stop = self.server._stop
        try:
            while not stop.is_set():
                try:
                    first = q.get(timeout=0.2)
                except queue_mod.Empty:
                    continue
                batch = [first]
                limit = (
                    self._eff_batch if self._adaptive_batch
                    else self._max_batch
                )
                while len(batch) < limit:
                    try:
                        batch.append(q.get_nowait())
                    except queue_mod.Empty:
                        break
                if self._adaptive_batch:
                    self._adapt_batch(len(batch), q.qsize())
                self._applying = True
                try:
                    self._apply_batch(batch)
                except Exception:  # noqa: BLE001 — isolate the offender
                    # one malformed push (bad grad shape, poison payload)
                    # must not fail the innocent pushes it happened to
                    # coalesce with — the serial path confined the error
                    # to its own request, so does the retry: each item
                    # re-runs as its own batch and only the offender's
                    # future fails
                    for p in batch:
                        if p.future.done():
                            continue
                        try:
                            self._apply_batch([p])
                        except Exception as e1:  # noqa: BLE001
                            if not p.future.done():
                                p.future.set_exception(e1)
                finally:
                    self._applying = False
        finally:
            # the watchdog must stop probing a dead engine (and a
            # re-start() after stop re-registers a fresh probe)
            watchdog.unregister(self._wd_name)
        self._apply_open = False
        deadline = time.monotonic() + 0.5  # grace: racing enqueuers land
        while time.monotonic() < deadline:
            try:
                p = q.get_nowait()
            except queue_mod.Empty:
                time.sleep(0.05)
                continue
            self._fail_stopping(p)

    def _adapt_batch(self, got: int, backlog: int) -> None:
        """Adaptive batch-ceiling policy (``[server] adaptive_batch``),
        called by the apply thread after each drain with the batch it
        actually collected and the queue depth left behind. A FULL batch
        with more still queued means arrivals outpace the ceiling —
        double it (the drain is leaving coalescing wins on the table); a
        batch far below the ceiling means arrivals are sparse — halve it,
        so one slow client's trickle is applied at low latency instead of
        waiting to fill a ceiling sized for a burst. Every change bumps
        ``server_batch_adapts``; ``max_batch`` stays the hard ceiling."""
        eff = self._eff_batch
        if got >= eff and backlog > 0 and eff < self._max_batch:
            self._eff_batch = min(eff * 2, self._max_batch)
        elif got <= max(1, eff // 4) and eff > 1:
            self._eff_batch = max(1, eff // 2)
        if self._eff_batch != eff:
            wire_counters.inc("server_batch_adapts")

    def _apply_batch(self, batch: list[_QueuedPush]) -> None:
        """Coalesce and apply one batch: segment-sum duplicate keys across
        the batch's pushes, ONE updater delta over the union of touched
        rows, the whole batch recorded in the durable ledger atomically
        with the state publish (save_state can never snapshot a state
        that disagrees with its ledger)."""
        flightrec.record("apply.begin", pushes=len(batch))
        todo: list[_QueuedPush] = []
        dups: list[_QueuedPush] = []
        commit_ver = 0
        t_apply0 = t_apply1 = 0.0
        with self._lock:
            seen: set[tuple[str | None, str | None]] = set()
            for p in batch:
                if p.cid is not None:
                    per = self._applied_push.get(p.cid)
                    if per is not None and p.seq in per:
                        # already applied (and ledgered) in a previous
                        # server life: durably done — ack immediately
                        self._bump("push_replays")
                        wire_counters.inc("rpc_dedup_hits")
                        flightrec.record(
                            "apply.replay", cid=p.cid, seq=p.seq,
                        )
                        if not p.future.done():
                            p.future.set_result(({"ok": True}, {}))
                        continue
                    if (p.cid, p.seq) in seen:
                        # duplicate within THIS batch: its first instance
                        # has not applied yet, so the ack must WAIT for
                        # the publish — acking now would break 'acked =>
                        # durably applied' if the apply then fails
                        self._bump("push_replays")
                        wire_counters.inc("rpc_dedup_hits")
                        dups.append(p)
                        continue
                    seen.add((p.cid, p.seq))
                todo.append(p)
            if todo:
                t_apply0 = time.perf_counter()
                # pad_to_pow2: a coalesced union has a different length
                # every batch, and each fresh shape re-dispatches the
                # whole eager updater chain — the pow-2 bucket pins
                # batches to a handful of compiled shapes (pad rows are
                # PAD_KEY 0 + zero grad, which every updater maps to a
                # zero delta per the store invariant)
                # psl: ignore[blocking-under-lock]: the apply lock must span the ledger check, the coalesce+jitted apply and the publish — the serial raw-frame path mutates state under this same lock, so an unlocked compute window would lose any raw push that interleaved
                idx, grad = kv_store.coalesce_pushes(
                    [p.keys for p in todo], [p.grad for p in todo],
                    pad_to_pow2=True,
                )
                with trace.span(
                    "server.apply_batch", cat="ps",
                    pushes=len(todo), keys=len(idx),
                ):
                    # ONE jitted dispatch for the whole batch (the
                    # bucketed shapes keep the compile count at ~one per
                    # pow-2 union size). Deliberately NOT donated: the
                    # old buffers must stay valid for concurrent RCU
                    # snapshot readers (pull/dump) until they drop them.
                    new_state = kv_store.push(
                        self.updater, self.state,
                        self._jnp.asarray(idx), self._jnp.asarray(grad),  # psl: ignore[blocking-under-lock]: same unit as the coalesce above — ledger check, jitted apply and RCU publish are one atomic section vs the serial raw-frame path
                    )
                    for p in todo:
                        if p.cid is not None:
                            self._record_push(p.cid, p.seq)
                    # RCU publish: ONE reference swap — pull/dump capture
                    # self.state without the lock and see the pre- or
                    # post-batch table, never a torn mix
                    self.state = new_state
                    commit_ver = self.version
        t_apply1 = time.perf_counter()
        #: jitted-apply duration for this batch (the latency-forensics
        #: apply segment, echoed on replies and the updater markers)
        apl_us = int(max(t_apply1 - t_apply0, 0.0) * 1e6) if todo else 0
        if todo:
            # the postmortem's AND the live auditor's acked-vs-applied
            # ledger: every (cid, seq) this commit made durable, against
            # the version it produced. The full batch, never a slice —
            # a truncated ledger makes the streaming ack⇒applied monitor
            # read the tail pushes as acked-but-unapplied on a healthy
            # cluster whenever [server] max_batch exceeds the cap (the
            # event stays bounded by max_batch, an operator knob)
            flightrec.record(
                "apply.commit", ver=commit_ver, pushes=len(todo),
                pairs=[
                    [p.cid, p.seq] for p in todo if p.cid is not None
                ],
            )
        if todo:
            # per-range matrix: applied pushes, their payload bytes and
            # the jitted-apply cost (the batch's, once — the coalesced
            # apply IS this range's cost, not per-push)
            self._range_scope.push(
                len(todo), sum(int(p.grad.nbytes) for p in todo)
            )
            self._range_scope.apply(max(t_apply1 - t_apply0, 0.0))
        with self._ctr_lock:
            self.counters["pushes"] += len(todo)
            self.counters["apply_batches"] += 1
            # only genuinely APPLIED pushes count as coalesced — counting
            # ledger replays/duplicates would inflate the batching win by
            # exactly the dedup traffic
            self.counters["push_coalesced"] += max(len(todo) - 1, 0)
        if len(todo) > 1:
            wire_counters.inc("push_coalesced", len(todo) - 1)
        observe_scalar("server.apply_batch.n", len(batch))
        trace.counter("server.apply_batch_size", len(batch))
        if trace.enabled():
            # per-push updater spans re-join each caller's trace across
            # the thread hop (the PR-2 contract: one logical push is one
            # trace id, client span -> dispatch span -> updater span).
            # The marker fires AFTER the batch applied, so it carries
            # the measured queue-wait/apply split as args — the
            # critical-path engine reads them to split the post-dispatch
            # gap into apply_wait vs apply (jit compiles land in the
            # right column)
            for p in todo:
                with trace.activate(p.tctx), trace.span(
                    "server.updater", cat="ps",
                    keys=len(p.keys), batched=len(todo),
                    apw_us=int(max(t_apply0 - p.t_enq, 0.0) * 1e6),
                    apl_us=apl_us,
                ):
                    pass
        # dups resolve here too: the publish they waited on has happened
        # (on an exception above, neither list resolves — the apply loop's
        # per-item retry re-runs them, and a dup then replays off the
        # ledger its first instance just wrote). The reply carries the
        # apply-segment timings (_apw_us queue wait, _apl_us jitted
        # apply) the RPC layer's _svc_us echo can't see from outside —
        # the latency-forensics split of "server" into its real phases.
        for p in todo + dups:
            if not p.future.done():  # the shutdown race may fail one first
                try:
                    p.future.set_result((
                        {
                            "ok": True,
                            "_apw_us": int(
                                max(t_apply0 - p.t_enq, 0.0) * 1e6
                            ),
                            "_apl_us": apl_us,
                        },
                        {},
                    ))
                except Exception:  # noqa: BLE001 — lost the race benignly
                    pass

    # -- checkpoint/restart (ref: each server dumps its own key range;
    # resume = reload the range before continuing) ------------------------

    def _ckpt_path(self, ckpt_dir: str) -> str:
        import os

        r = self.range
        return os.path.join(ckpt_dir, f"server-{r.begin}-{r.end}.npz")

    def save_state(self, ckpt_dir: str) -> None:
        """Atomic dump of this range's updater state (tmp + rename: a
        crash mid-write never leaves a torn checkpoint at the final path;
        writers serialize so the final shutdown dump can't interleave with
        an in-flight periodic dump on the shared tmp file)."""
        import os

        with trace.span(
            "server.checkpoint.save", cat="ckpt",
            range=f"{self.range.begin}-{self.range.end}",
        ):
            with self._lock:
                # same critical section for the state REFERENCE and the
                # ledger: the ledger in a checkpoint must witness exactly
                # the pushes that checkpoint contains — never one more,
                # never one fewer. Only the reference capture needs the
                # lock (the published dict is immutable after the RCU
                # swap); the device->host transfer below runs OUTSIDE it
                # (pslint blocking-under-lock true positive: the full-
                # state D2H sync used to stall every push for the
                # checkpoint's duration).
                state = self.state
                ledger = json.dumps(
                    {cid: list(per) for cid, per in self._applied_push.items()}
                )
            host = {k: np.asarray(v) for k, v in state.items()}
            with self._ckpt_write_lock:
                os.makedirs(ckpt_dir, exist_ok=True)
                path = self._ckpt_path(ckpt_dir)
                tmp = path + ".tmp.npz"  # .npz: savez must not append one
                np.savez(
                    tmp,
                    __push_ledger__=np.frombuffer(
                        ledger.encode(), dtype=np.uint8
                    ),
                    **host,
                )
                os.replace(tmp, path)

    def load_state(self, ckpt_dir: str) -> bool:
        """Load this range's dump if one exists; False when absent."""
        import os

        path = self._ckpt_path(ckpt_dir)
        if not os.path.exists(path):
            return False
        with trace.span("server.checkpoint.load", cat="ckpt"), np.load(
            path
        ) as z:
            host = {k: z[k] for k in z.files}
        ledger_raw = host.pop("__push_ledger__", None)
        if set(host) != set(self.state) or any(
            host[k].shape != tuple(self.state[k].shape) for k in host
        ):
            raise ValueError(
                f"checkpoint {path} does not match this server's state "
                "layout (different updater or key range?)"
            )
        applied: OrderedDict[str, OrderedDict[str, None]] = OrderedDict()
        if ledger_raw is not None:  # absent in pre-ledger checkpoints
            for cid, seqs in json.loads(ledger_raw.tobytes().decode()).items():
                applied[cid] = OrderedDict((str(s), None) for s in seqs)
        # host->device transfer OUTSIDE the lock (pslint
        # blocking-under-lock): only the two reference swaps need the
        # critical section — they form the same atomic state+ledger unit
        # save_state snapshots
        new_state = {k: self._jnp.asarray(v) for k, v in host.items()}
        with self._lock:
            self.state = new_state
            self._applied_push = applied
        return True

    def start_checkpointing(self, ckpt_dir: str, interval_s: float) -> None:
        """Background periodic dumps until the server stops (pushes since
        the last dump are lost on a crash — the bounded-staleness price the
        reference's recovery design also pays)."""

        def loop() -> None:
            while not self.server._stop.wait(interval_s):
                self.save_state(ckpt_dir)

        self._ckpt_thread = threading.Thread(target=loop, daemon=True)
        self._ckpt_thread.start()

    def stop_checkpointing(self) -> None:
        """Join the periodic dump thread (the stop event must already be
        set — serve_forever has returned)."""
        if self._ckpt_thread is not None:
            self._ckpt_thread.join(timeout=30)
            self._ckpt_thread = None

    def _resolve_keys(
        self, h: dict[str, Any], arrays: Arrays
    ) -> np.ndarray | None:
        """Key-caching filter, server side: prefer the cached list for this
        (worker, signature); fall back to the sent keys and cache them."""
        ck = (int(h["worker"]), h["sig"])
        if "keys" in arrays:
            keys = arrays["keys"].astype(np.int64)
            self._key_cache.put(ck, keys)
            return keys
        keys = self._key_cache.get(ck)
        if keys is None:
            self._bump("need_keys")
            return None
        self._bump("cache_hits")
        return keys

    def _handle(self, h: dict[str, Any], arrays: Arrays):
        cmd = h["cmd"]
        if cmd == "pull":
            return self._handle_pull(h, arrays)
        if cmd == "push":
            cid = h.get("_cid")
            seq = None if cid is None else str(h.get("_seq"))
            if cid is not None:
                with self._lock:
                    per = self._applied_push.get(cid)
                    if per is not None and seq in per:
                        # this exact push already mutated state in a
                        # previous server life; its reply died with the
                        # kill, and the resend must not re-apply
                        self._bump("push_replays")
                        wire_counters.inc("rpc_dedup_hits")
                        flightrec.record("apply.replay", cid=cid, seq=seq)
                        return {"ok": True}, {}
            keys = self._resolve_keys(h, arrays)
            if keys is None:
                # _transient: nothing committed — the reply cache must NOT
                # pin this bounce, so the keyed follow-up (same seq) re-runs
                return {"ok": True, "need_keys": True, "_transient": True}, {}
            g = self._decode_grad(h, arrays).reshape(len(keys), -1)
            # per-key heat (ISSUE 9): pushed GLOBAL keys feed the
            # count-min the replication/tier-promotion planes will read
            key_heat.add(np.asarray(keys, np.int64) + self.range.begin)
            if (
                self._apply_q is not None
                and self._apply_thread is not None
                and cid is not None
            ):
                # engine path only once start() armed the apply thread: a
                # handler driven directly (tests, tools) keeps the inline
                # path instead of deferring onto a thread nobody runs
                # batched apply engine: enqueue the DECODED push and defer
                # the reply — the serving thread keeps draining buffered
                # requests (pulls flow past queued pushes) and the RPC
                # layer settles this reply once the batch applied, so an
                # acked push is still a durably recorded one. Raw no-cid
                # frames keep the inline path: their reply ordering
                # contract has no seq echo to survive deferral.
                item = _QueuedPush(
                    np.asarray(keys), np.asarray(g), cid, seq,
                    # the dispatch span's identity: the apply thread's
                    # server.updater span re-joins this push's trace
                    tctx=trace.wire_context() if trace.enabled() else None,
                )
                self._enqueue_push(item)
                return DeferredReply(item.future), {}
            # serial path ([server] apply_queue = 0): apply inline under
            # the write lock — the pre-engine discipline, kept as the
            # engine's baseline and the raw-frame fallback
            with trace.span("server.updater", cat="ps", keys=len(keys)):
                with self._lock:
                    rows = {k: v[keys] for k, v in self.state.items()}
                    # psl: ignore[blocking-under-lock]: the serial path ([server] apply_queue = 0) applies INLINE under the write lock by definition — that serialization is the pre-engine baseline discipline the engine is benchmarked against
                    deltas = self.updater.delta(rows, self._jnp.asarray(g))
                    self.state = {
                        k: self.state[k].at[keys].add(deltas[k])
                        for k in self.state
                    }
                    if cid is not None:
                        self._record_push(cid, seq)
                serial_ver = self.version
            self._bump("pushes")
            self._range_scope.push(1, int(np.asarray(g).nbytes))
            flightrec.record(
                "apply.commit", ver=serial_ver, pushes=1,
                pairs=[[cid, seq]] if cid is not None else [],
            )
            return {"ok": True}, {}
        if cmd == "dump":
            state = self.state  # RCU snapshot (see pull)
            w = np.asarray(self.updater.weights(state))
            return {"ok": True, "begin": self.range.begin, "end": self.range.end}, {
                "w": w
            }
        if cmd == "stats":
            rep = {
                "ok": True,
                **self.counters,
                # current RCU publish version. NOT the key "ver": that
                # is a binary-header-v2 slot, and stats replies must
                # stay v1-decodable to old binary peers
                "state_ver": self.version,
                "bytes_out": self.server.bytes_out,
                "bytes_in": self.server.bytes_in,
                "frames_in": self.server.frames_in,
                "cached_sigs": len(self._key_cache),
                # recovery observability: resent/duplicated frames this
                # server answered from the reply cache instead of
                # re-applying (process-wide counter; one server per
                # process in the spawned tier)
                "rpc_dedup_hits": wire_counters.get("rpc_dedup_hits"),
                # serving observability: quantized-pull payload savings
                # (process-wide, like rpc_dedup_hits above)
                "wire_quant_bytes_saved": wire_counters.get(
                    "wire_quant_bytes_saved"
                ),
            }
            faults = self.server.fault_stats()
            if faults is not None:
                rep["faults"] = faults
            return rep, {}
        if cmd == "shutdown":
            raise RpcServer.Shutdown
        raise ValueError(f"unknown server command {cmd!r}")

    def _handle_pull(
        self, h: dict[str, Any], arrays: Arrays
    ) -> tuple[dict[str, Any], Arrays]:
        """The read path (ISSUE 7 serving plane). In order:

        1. conditional pull: ``if_newer=<ver>`` against an unchanged
           snapshot answers ``not_modified`` — no gather, no encode, no
           payload (the client re-arms its TTL on its cached rows);
        2. admission control: under overload, a revalidation the client
           flagged ``shed_ok`` (it holds a within-bounds cached
           fallback) is shed with a retry-after hint instead of
           queueing an encode behind the backlog;
        3. single-flight encode: concurrent/repeated pulls of a HOT key
           set against the same snapshot share ONE encoded reply — the
           buffers are reused across the reply lane, not re-gathered
           per client.

        Replies to VERSION-AWARE pulls (``sv: 1``, sent by serving
        handles; implied by ``if_newer``) carry ``ver``, the RCU publish
        version of exactly the table the rows came from. Pulls without
        the signal get the PR-6 reply shape byte for byte: ``ver`` is a
        binary-header-v2 slot, and stamping it into every reply would
        livelock a v1-binary peer in a mixed cluster (the ``sv`` signal
        itself rides the request's JSON tail, so first-contact requests
        stay v1-decodable everywhere)."""
        keys = self._resolve_keys(h, arrays)
        if keys is None:
            return {"ok": True, "need_keys": True}, {}
        # RCU snapshot read: ONE reference capture of the published
        # (state, version) pair (the apply thread swaps a complete new
        # tuple per batch, never mutates one in place), so this pull
        # sees the pre- or post-batch table — never a torn mix, never a
        # version that disagrees with its rows — without the write lock
        state, ver, pts = self._pub  # psl: ignore[rcu]: THE sanctioned lock-free read — one atomic capture of the whole (state, version, publish-ts) tuple; the state/version properties would be two captures and could pair rows with a foreign version
        ifn = h.get("if_newer")
        sv = bool(h.get("sv")) or ifn is not None
        if ifn is not None and int(ifn) == ver:
            # the client's cached rows ARE this snapshot (equality, not
            # ordering: versions are opaque per-life snapshot ids)
            self._bump("pulls")
            self._bump("not_modified")
            wire_counters.inc("serve_not_modified")
            self._range_scope.pull(0)
            # pts: the publish ts of the snapshot the client's cached
            # rows ARE — the wire layer turns it into a per-serve
            # ``_age_us`` (see control.decorated), and the client
            # re-anchors its cache entry's age off this revalidation
            return {
                "ok": True, "not_modified": True, "ver": ver, "pts": pts,
            }, {}
        if ifn is not None and h.get("shed_ok") and self.overloaded():
            # shed: the client promised a cached fallback within its
            # staleness ceiling — tell it to keep serving that and come
            # back, instead of queueing rows behind a saturated engine.
            # No ``ver``: nothing was validated, so the client must not
            # re-arm version trust off this reply.
            self._bump("pulls")
            self._bump("shed")
            wire_counters.inc("serve_shed")
            flightrec.record("serve.shed", sig=h.get("sig"))
            return {"ok": True, "not_modified": True, "shed": True,
                    "retry_after_ms": self._serve_cfg.retry_after_ms}, {}
        qn = int(h.get("quant", 0))
        ent = None
        hot = self._enc_cap > 0 and self._note_pull(h["sig"])
        # sv is part of the cache key: a version-stamped reply cached
        # for a serving client must never be replayed to a client that
        # can't decode the v2 header slot (and vice versa)
        ck = (
            h["sig"], ver, qn, int(h.get("qseg", 256)),
            bool(h.get("zip")), sv,
        )
        if hot:
            ent, owner = self._enc_claim(ck)
            if not owner:
                # single-flight: another pull of the same keys against
                # the same snapshot owns the encode — share its buffers
                # (the wait parks only on a concurrent first encode; a
                # finished entry's event is already set)
                if ent.event.wait(timeout=5.0) and ent.rep is not None:
                    self._bump("pulls")
                    self._bump("encode_reuse")
                    wire_counters.inc("serve_encode_reuse")
                    self._range_scope.pull(
                        sum(a.nbytes for a in ent.arrays.values())
                    )
                    self._range_scope.age(skew_clamped_age_s(pts))
                    return ent.rep, ent.arrays
                ent = None  # owner failed or timed out: encode ourselves
        try:
            # snapshot materialization is gated on hot AND a conditional
            # pull (`if_newer` proves a caching serving client): a
            # training tier with epoch-repeated key sets and per-step
            # version churn must never pay a full-table weights()
            # materialization per step just because its sigs went hot
            rep, out = self._encode_pull(
                state, ver, keys, h, qn, hot and ifn is not None,
                with_ver=sv, pts=pts,
            )
        except BaseException:
            if ent is not None:
                self._enc_fail(ck, ent)
            raise
        self._bump("pulls")
        self._bump("pull_encodes")
        # per-range matrix: rows left this range at this snapshot's age
        # (publish and serve clocks are usually this process's own, but
        # a replicated pts can be a peer's — the clamp absorbs the skew)
        self._range_scope.pull(sum(a.nbytes for a in out.values()))
        self._range_scope.age(skew_clamped_age_s(pts))
        if ent is not None:
            self._enc_fill(ck, ent, rep, out)
        return rep, out

    def _host_weights(self, state: dict[str, Any], ver: int) -> np.ndarray:
        """Full weights table for snapshot ``ver``, materialized on the
        host ONCE per version that receives a hot pull and shared by
        every encode at that version: a hot pull becomes a numpy
        fancy-index (~us) instead of an eager jax gather + weights
        dispatch per request (~ms). Bounded by ``[serve]
        snapshot_keys_max`` — the caller gates on the range size, so a
        10^9-key training shard never pays a full-table device->host
        sync for one read. Benign race: two threads materializing a
        fresh version duplicate the work; the tuple swap is atomic and
        last-writer-wins, never torn."""
        cur = self._host_w
        if cur is not None and cur[0] == ver:
            return cur[1]
        w = np.asarray(self.updater.weights(state)).reshape(
            self.range.size, -1
        )
        self._host_w = (ver, w)
        return w

    def _encode_pull(
        self, state: dict[str, Any], ver: int, keys: np.ndarray,
        h: dict[str, Any], qn: int, snap: bool = False,
        with_ver: bool = False, pts: int = 0,
    ) -> tuple[dict[str, Any], Arrays]:
        """Gather + encode one pull reply from an RCU snapshot (shared
        verbatim across clients by the single-flight cache — nothing
        here may depend on the requesting connection). ``snap`` allows
        MATERIALIZING the per-version host weights snapshot (hot +
        revalidation traffic, ranges within ``snapshot_keys_max``); an
        already-current snapshot serves every pull either way, and
        everything else keeps the per-row jax path."""
        # per-key heat, read side: only REAL row encodes count (a
        # not_modified / shed / single-flight-reused reply moves no
        # rows, so it adds no promotion-relevant heat — and the serving
        # fast paths stay sketch-free)
        key_heat.add(np.asarray(keys, np.int64) + self.range.begin)
        cur = self._host_w
        if cur is not None and cur[0] == ver:
            # a snapshot for THIS version is already materialized (some
            # hot pull paid for it): every pull may ride it for free
            w = cur[1][keys]
        elif snap and 0 < self.range.size <= self._serve_cfg.snapshot_keys_max:
            w = self._host_weights(state, ver)[keys]
        else:
            rows = {k: v[keys] for k, v in state.items()}
            w = np.asarray(self.updater.weights(rows)).reshape(len(keys), -1)
        if qn:
            # quantized pull (read-mostly/serving traffic): the rows
            # ride as per-segment-scale integers at the width the
            # client asked for. Only quant-negotiated clients send
            # the field, so an old client can never receive a
            # payload it can't decode. Round-to-NEAREST, not
            # stochastic: reads have no error-feedback loop, so
            # nearest halves the worst-case error and keeps repeated
            # reads of one unchanged snapshot bit-identical.
            from parameter_server_tpu.filters.quant import SegmentQuantizer

            qz = SegmentQuantizer(qn, int(h.get("qseg", 256)))
            q, qs = qz.encode_nearest(w.ravel())
            wire_counters.inc(
                "wire_quant_bytes_saved",
                max(w.nbytes - q.nbytes - qs.nbytes, 0),
            )
            rep = {"ok": True, "codec": qn, "qseg": qz.seg}
            if with_ver:  # see _handle_pull: only version-aware clients
                rep["ver"] = ver
                if pts:
                    rep["pts"] = pts  # freshness: version-constant, so
                    # safe on single-flight-shared replies; the wire
                    # layer derives each serve's _age_us from it
            return rep, {"q": q, "qs": qs}
        rep = {"ok": True, "zip": h.get("zip", False)}
        if with_ver:
            rep["ver"] = ver
            if pts:
                rep["pts"] = pts
        return rep, {"w": w.ravel()}

    def _decode_grad(self, h: dict[str, Any], arrays: Arrays) -> np.ndarray:
        codec_bytes = int(h.get("codec", 0))
        if not codec_bytes:
            return arrays["g"]
        if "qs" in arrays:
            # per-segment-scale codec (filters/quant.py, the negotiated
            # "qwire" path): dequantize here on the serving thread — the
            # decoded float grad then enters the apply queue, where
            # coalesce_pushes segment-sums it into the engine's single
            # jitted dispatch like any other push
            from parameter_server_tpu.filters.quant import SegmentQuantizer

            qz = SegmentQuantizer(codec_bytes, int(h.get("qseg", 256)))
            return qz.decode(arrays["q"], arrays["qs"])
        # legacy whole-array affine codec (filters/fixed_point, the
        # un-negotiated [filter] fixing_float_bytes knob)
        from parameter_server_tpu.filters.fixed_point import Encoded, FixedPointCodec

        codec = FixedPointCodec(num_bytes=codec_bytes)
        e = Encoded(
            self._jnp.asarray(arrays["q"]),
            self._jnp.asarray(arrays["lo"][0]),
            self._jnp.asarray(arrays["scale"][0]),
        )
        return np.asarray(codec.decode(e))


class ServerHandle:
    """Worker-side proxy to one shard server, applying the send filters
    (ref: SharedParameter's per-call FilterConfigs)."""

    def __init__(
        self,
        address: str,
        rank: int,
        worker: int,
        cfg: PSConfig,
        range_size: int = 0,
        resolve_addr=None,  # () -> current address, for server-restart recovery
        reconnect_timeout_s: float | None = None,
        serving: bool = False,
        key_cache=None,
        key_range: KeyRange | None = None,
    ):
        """``serving=True`` marks this handle as part of the read-mostly
        serving tier: with ``[serve] cache`` on, it arms the client-side
        versioned key cache (filters/keycache.py) — pulls are served
        locally within the TTL, revalidated by version past it, and
        invalidated exactly by this handle's own pushes. ``key_cache``
        lets a serving FRONTEND share ONE cache across ALL its handles —
        same shard or a whole multi-shard cluster (many connections, one
        process-wide working set): entries and the inverted invalidation
        index are namespaced by this handle's ``rank``, so two shards'
        range-relative keys can never collide or cross-invalidate, and
        invalidation stays exact because every handle's pushes
        invalidate the shared instance under its own rank. The
        training tier NEVER passes serving=True: a trainer's staleness
        contract is the SSP clock, not a TTL (see ``_connect_servers``).

        ``key_range`` (optional) names the server range this handle
        proxies: with it, every serve this CLIENT answers — cached,
        bounded-stale, shed-fallback or fresh off the wire — books its
        realized data age into that range's matrix alongside the
        server's own bookings (freshness plane, ISSUE 17)."""
        import itertools

        self.rank = rank
        self.worker = worker
        self._range_scope = (
            RangeScope(key_range.begin, key_range.end)
            if key_range is not None else None
        )
        self._kcache = None
        if serving and cfg.serve.cache:
            from parameter_server_tpu.filters.keycache import ClientKeyCache

            # `is not None`, NOT `or`: the cache defines __len__, so a
            # shared instance that happens to be empty is falsy — `or`
            # would silently hand every handle a private cache
            self._kcache = key_cache if key_cache is not None else (
                ClientKeyCache(
                    cap=cfg.serve.cache_entries,
                    ttl_s=cfg.serve.ttl_ms / 1e3,
                    max_stale_s=cfg.serve.max_stale_ms / 1e3,
                )
            )
        self._resolve_addr = resolve_addr
        self._reconnect_timeout_s = (
            reconnect_timeout_s
            if reconnect_timeout_s is not None
            else cfg.fault.reconnect_timeout_s
        )
        # client-internal same-address retry window: short, so transient
        # connection loss (injected faults, restarts on the same port)
        # heals in-place with the SAME sequence numbers (dedup-safe), while
        # a genuinely moved server falls through to the resolver loop in
        # _keyed_call quickly instead of burning the whole handle window
        self._client_window_s = min(3.0, self._reconnect_timeout_s)
        self._pipeline_window = max(1, cfg.wire.window)
        self._hdr_codec = cfg.wire.hdr_codec
        self._adaptive_window = cfg.wire.adaptive_window
        # quantized push transport ([wire] quant, filters/quant.py):
        # negotiated per connection via the "qwire" feature advert —
        # until (unless) the peer acks, pushes stay on the float path
        qmode = cfg.wire.quant
        if qmode not in ("off", "int8", "int16"):
            raise ValueError(
                f"[wire] quant must be off|int8|int16, got {qmode!r}"
            )
        self._quant_bytes = {"off": 0, "int8": 1, "int16": 2}[qmode]
        self._quant_pull = bool(cfg.wire.quant_pull) and self._quant_bytes > 0
        self._features = (
            frozenset({"qwire"}) if self._quant_bytes else frozenset()
        )
        if self._quant_bytes:
            from parameter_server_tpu.filters.quant import SegmentQuantizer

            self._quantizer = SegmentQuantizer(
                self._quant_bytes, max(1, int(cfg.wire.quant_seg))
            )
        # error-feedback accumulator: the residual each quantized push
        # loses to rounding, folded into the NEXT push of the same keys.
        # Folded exactly once per logical push at encode time (resends
        # reuse the encoded payload), guarded by its own lock so a
        # recovery-thread re-encode can never race the worker loop.
        self._res_lock = threading.Lock()
        self._residual: np.ndarray | None = None
        self._res_vdim = 0
        self._res_range = int(range_size)
        self._res_map: dict[int, int] | None = None
        self.client = RpcClient(
            address, reconnect_timeout_s=self._client_window_s,
            window=self._pipeline_window,
            hdr_codec=self._hdr_codec,
            adaptive_window=self._adaptive_window,
            features=self._features,
        )
        # a worker's pull and in-flight push threads share this handle;
        # concurrent failures must rebuild the connection once — the
        # generation counter lets a late-arriving failing thread see that
        # another thread already replaced the client and just retry
        self._reconnect_lock = threading.Lock()
        # the recovery-executor singleton gets its OWN lock (pslint
        # blocking-under-lock true positive): _recovery() used to share
        # _reconnect_lock, so the client's READER thread — which calls
        # _recovery() from a completion callback — could park for a full
        # reconnect window behind a thread sleeping inside _reconnect,
        # stalling every other in-flight completion on that connection
        self._pool_lock = threading.Lock()
        self._conn_gen = 0
        self._sent_sigs = _LruSigs()
        self._key_caching = cfg.filter.key_caching
        self._zip = cfg.filter.compressing
        self._codec_bytes = cfg.filter.fixing_float_bytes
        # local (range-relative) keys ride the wire as u32 when the range
        # fits, u64 otherwise — a silent u32 truncation at 10^9+ feature
        # scale would corrupt the model
        self._key_dtype = (
            np.uint64 if range_size > (1 << 32) else np.uint32
        )
        # atomic: concurrent in-flight push threads must not reuse a
        # stochastic-rounding seed
        self._quant_seed = itertools.count()
        # logical-call sequence numbers ("k<n>" — a namespace disjoint from
        # RpcClient's internal integer counter): one per _keyed_call, held
        # constant across client rebuilds so every delivery of a logical
        # push is one dedup identity on the server
        self._kseq = itertools.count()
        # lazy single-thread executor for the RESOLVER retry path of async
        # calls: a reader thread completing a failed future must never run
        # the blocking reconnect loop itself
        self._recovery_pool: ThreadPoolExecutor | None = None
        # watchdog: this handle's client carries only pull/push/dump/stats
        # (nothing that legitimately parks), so in-flight requests whose
        # completions stop moving mean a reader parked past every
        # deadline — one of the stalls the flight recorder dumps on.
        # ``self.client`` is re-read per poll, so the probe follows
        # recovery rebuilds.
        self._wd_name = f"handle:{rank}:w{worker}:{id(self):x}"
        watchdog.register(
            self._wd_name, lambda: self.client.stall_probe(),
            thread_name="ps-rpc-reader",
        )
        if self._codec_bytes:
            from parameter_server_tpu.filters.fixed_point import FixedPointCodec

            self._codec = FixedPointCodec(num_bytes=self._codec_bytes)
        # lockset race witness (PS_RACE_WITNESS=1): the error-feedback
        # residual state is shared between the worker loop and the
        # recovery/reader threads — every access must hold _res_lock or
        # the exactly-once folding guarantee is a race away from double
        # counting
        race_track(
            self, ("_residual", "_res_map", "_res_vdim"),
            f"ServerHandle:{rank}:w{worker}",
        )

    def _keyed_call(
        self, cmd: str, keys: np.ndarray, arrays: Arrays,
        lseq: str | None = None, **fields,
    ):
        """Issue a keyed request, sending the key list only when the server
        doesn't hold it (key-caching filter, worker side). A lost
        connection triggers reconnect-and-retry against the (possibly
        relaunched) server when a resolver was provided. ``lseq`` re-enters
        a logical call that already holds a dedup identity (the async
        recovery path); fresh calls allocate their own."""
        if lseq is None:
            lseq = f"k{next(self._kseq)}"
        gen = self._conn_gen
        try:
            return self._keyed_call_once(cmd, keys, arrays, lseq, **fields)
        except (ConnectionError, BrokenPipeError, OSError):
            if self._resolve_addr is None:
                raise
        # retry until the reconnect window closes: one retry is not enough
        # around a server death — a connect can land in the dying listen
        # socket's backlog (or reach a not-yet-serving replacement) and
        # then reset on first use
        t0 = time.monotonic()
        deadline = t0 + self._reconnect_timeout_s
        while True:
            self._reconnect(gen, deadline)
            gen = self._conn_gen
            try:
                return self._keyed_call_once(cmd, keys, arrays, lseq, **fields)
            except (ConnectionError, BrokenPipeError, OSError) as e:
                if time.monotonic() > deadline:
                    raise ConnectionError(
                        f"server rank {self.rank} kept resetting for "
                        f"{time.monotonic() - t0:.1f}s across reconnects: {e}"
                    ) from e
                # backoff: a connect that succeeds into a dying backlog and
                # resets on first use would otherwise hot-loop at full speed
                time.sleep(0.3)

    def _reconnect(self, failed_gen: int, deadline: float | None = None) -> None:
        """Rebuild the connection to wherever this rank's server now lives
        (ref: re-resolving the node registry after recovery). The relaunch
        starts with an empty key cache, so our sent-signature memory is
        dropped; the need_keys protocol would also recover, at one extra
        round-trip per cached set.

        failed_gen: the connection generation the caller's failure was
        observed on — if another thread already replaced that connection,
        this call must NOT tear the fresh one down, just retry on it.
        deadline: caller's overall monotonic deadline (the retry loop's);
        defaults to a fresh reconnect window."""
        if deadline is None:
            deadline = time.monotonic() + self._reconnect_timeout_s
        with self._reconnect_lock:
            if self._conn_gen != failed_gen:
                return  # a concurrent failure already rebuilt the client
            self.client.close()
            # the rebuilt client must BE the old one to the server's dedup
            # machinery: same cid so retried "k<n>" seqs are recognized,
            # start_seq past the old internal counter so fresh un-keyed
            # calls (dump/stats) can't collide with cached old replies
            cid, next_seq = self.client.identity
            last: Exception | None = None
            while time.monotonic() < deadline:
                try:
                    addr = self._resolve_addr()
                    # psl: ignore[blocking-under-lock]: _reconnect_lock IS the serialization of connection rebuilds — concurrent failing threads must park until exactly one rebuild completes; no completion/reader thread takes it (the recovery pool moved to _pool_lock)
                    self.client = RpcClient(
                        addr, retries=1,
                        reconnect_timeout_s=self._client_window_s,
                        cid=cid, start_seq=next_seq,
                        window=self._pipeline_window,
                        hdr_codec=self._hdr_codec,
                        adaptive_window=self._adaptive_window,
                        # feature negotiation restarts with the rebuilt
                        # connection: a downgraded replacement server
                        # simply never acks, and pushes drop to floats
                        features=self._features,
                    )
                    self._sent_sigs = _LruSigs()
                    self._conn_gen += 1
                    return
                except (ConnectionError, OSError) as e:
                    last = e
                    # psl: ignore[blocking-under-lock]: rebuild-retry backoff under the rebuild serialization lock — waiters WANT to park until the one rebuild lands (see the pragma above)
                    time.sleep(0.3)
        raise ConnectionError(
            f"server rank {self.rank} unreachable for "
            f"{self._reconnect_timeout_s}s: {last}"
        )

    def _keyed_call_once(
        self, cmd: str, keys: np.ndarray, arrays: Arrays, lseq: str, **fields
    ):
        sig = _sig(keys)
        send_keys = not (self._key_caching and sig in self._sent_sigs)
        payload = dict(arrays)
        if send_keys:
            payload["keys"] = keys.astype(self._key_dtype)
        rep, out = self.client.call(
            cmd, arrays=payload, worker=self.worker, sig=sig,
            zip=self._zip, _seq=lseq, **fields,
        )
        if rep.get("need_keys"):  # cache miss on a sig we believed was cached
            # SAME lseq: a need_keys bounce is marked non-committing server
            # side, so this follow-up re-runs the handler while the logical
            # mutation keeps a single dedup identity end to end
            payload["keys"] = keys.astype(self._key_dtype)
            rep, out = self.client.call(
                cmd, arrays=payload, worker=self.worker, sig=sig,
                zip=self._zip, _seq=lseq, **fields,
            )
        self._sent_sigs.put(sig)
        return rep, out

    # -- async (pipelined) issue path -------------------------------------

    def _keyed_call_async(
        self, cmd: str, keys: np.ndarray, arrays: Arrays, **fields
    ):
        """Async twin of ``_keyed_call``: issues the request onto the
        client's pipelined window and returns a Future of (rep, arrays).
        The need_keys bounce re-issues with the SAME "k<n>" seq from the
        completion callback (``_urgent``: a reader thread must not block
        on window space it is responsible for freeing), and a connection
        that outlives the client's own heal window falls back to the
        blocking resolver retry loop on the handle's recovery thread."""
        outer: Future = Future()
        lseq = f"k{next(self._kseq)}"
        sig = _sig(keys)
        send_keys = not (self._key_caching and sig in self._sent_sigs)
        payload = dict(arrays)
        if send_keys:
            payload["keys"] = keys.astype(self._key_dtype)

        def on_reply(f, bounced: bool = False) -> None:
            # NOTHING may escape this callback: concurrent.futures logs
            # and swallows done-callback exceptions, which would leave
            # ``outer`` unresolved and its waiter parked forever — every
            # failure (including a shut-down recovery pool or a closed
            # client on the bounce re-issue) must land in ``outer``
            try:
                try:
                    rep, out = f.result()
                except (ConnectionError, BrokenPipeError, OSError):
                    if self._resolve_addr is None:
                        raise
                    # server moved or kept resetting past the client's
                    # heal: run the blocking resolver loop OFF this
                    # (reader) thread, same lseq so every delivery stays
                    # one dedup identity
                    self._recovery().submit(
                        self._recover_async, cmd, keys, arrays, lseq,
                        fields, outer,
                    )
                    return
                if rep.get("need_keys"):
                    if bounced:  # keys were in the frame: a repeat is a bug
                        raise RuntimeError(
                            f"server rank {self.rank} bounced a keyed {cmd}"
                        )
                    p2 = dict(arrays)
                    p2["keys"] = keys.astype(self._key_dtype)
                    f2 = self.client.call_async(
                        cmd, arrays=p2, worker=self.worker, sig=sig,
                        zip=self._zip, _seq=lseq, _urgent=True, **fields,
                    )
                    f2.add_done_callback(lambda g: on_reply(g, bounced=True))
                    return
                self._sent_sigs.put(sig)
                outer.set_result((rep, out))
            except BaseException as e:  # noqa: BLE001 — future boundary
                if not outer.done():
                    outer.set_exception(e)

        try:
            f1 = self.client.call_async(
                cmd, arrays=payload, worker=self.worker, sig=sig,
                zip=self._zip, _seq=lseq, **fields,
            )
        except (ConnectionError, BrokenPipeError, OSError) as e:
            if self._resolve_addr is None:
                raise
            self._recovery().submit(
                self._recover_async, cmd, keys, arrays, lseq, fields, outer
            )
            return outer
        f1.add_done_callback(on_reply)
        return outer

    def _recover_async(
        self, cmd, keys, arrays, lseq, fields, outer
    ) -> None:
        """Recovery-thread tail of a failed async call: the synchronous
        resolver retry loop, completing the caller's outer future."""
        try:
            outer.set_result(
                self._keyed_call(cmd, keys, arrays, lseq=lseq, **fields)
            )
        except BaseException as e:  # noqa: BLE001 — future boundary
            outer.set_exception(e)

    def _recovery(self) -> ThreadPoolExecutor:
        with self._pool_lock:  # NOT _reconnect_lock: see __init__
            if self._recovery_pool is None:
                self._recovery_pool = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"ps-recover-{self.rank}",
                )
            return self._recovery_pool

    def pull_async(self, local_keys: np.ndarray):
        """Issue a pull without blocking; Future of the float32 rows. Flow
        events link the issue span to the completion across the window.
        Serving handles consult the key cache first — a fresh entry
        resolves the future immediately with zero wire traffic."""
        out_f: Future = Future()
        if len(local_keys) == 0:
            out_f.set_result(np.zeros(0, dtype=np.float32))
            return out_f
        extra: dict[str, Any] = {}
        sig = ent = None
        own = False
        gen = None
        if self._kcache is not None:
            vals, extra, sig, ent, own, gen = self._cache_try(local_keys)
            if vals is not None:
                out_f.set_result(vals)
                return out_f
        try:
            with trace.span(
                "ps.pull", cat="ps", rank=self.rank, keys=len(local_keys)
            ):
                flow = trace.flow_start("ps.pull.inflight", cat="ps")
                ctx = trace.wire_context()
                inner = self._keyed_call_async(
                    "pull", local_keys, {}, **self._pull_fields(), **extra
                )
        except BaseException:
            if own:
                self._kcache.end_refresh(sig)
            raise

        def done(f) -> None:
            # nothing may escape (see _keyed_call_async.on_reply): a
            # swallowed callback error would leave out_f unresolved and
            # its waiter parked forever
            try:
                with trace.activate(ctx):
                    trace.flow_end(
                        "ps.pull.inflight", cat="ps", flow_id=flow
                    )
                rep, out = f.result()
                if self._kcache is not None:
                    out_f.set_result(
                        self._cache_settle(
                            rep, out, local_keys, sig, ent, own, gen
                        )
                    )
                else:
                    out_f.set_result(self._decode_pull(out))
            except BaseException as e:  # noqa: BLE001 — future boundary
                if own:
                    self._kcache.end_refresh(sig)  # idempotent release
                if not out_f.done():
                    out_f.set_exception(e)

        inner.add_done_callback(done)
        return out_f

    def push_async(self, local_keys: np.ndarray, grads: np.ndarray):
        """Issue a push without blocking; the Future resolves (to None)
        once the server acked the apply — the worker's PushWindow hangs
        ssp_finish off that. A flow event pair links the issue span to
        the completion event so Perfetto draws the in-flight arrow."""
        done_f: Future = Future()
        if len(local_keys) == 0:
            done_f.set_result(None)
            return done_f
        fields, arrays = self._encode_push(local_keys, grads)
        with trace.span(
            "ps.push", cat="ps", rank=self.rank, keys=len(local_keys),
            bytes=int(sum(a.nbytes for a in arrays.values())),
        ):
            flow = trace.flow_start("ps.push.inflight", cat="ps")
            ctx = trace.wire_context()
            inner = self._keyed_call_async(
                "push", local_keys, arrays, **fields
            )

        def done(f) -> None:
            # nothing may escape (see _keyed_call_async.on_reply)
            try:
                with trace.activate(ctx):
                    trace.flow_end(
                        "ps.push.inflight", cat="ps", flow_id=flow
                    )
                f.result()
                if self._kcache is not None:
                    # second, ACK-time invalidation: the server defers
                    # the ack until the batched apply published, so a
                    # pull raced between the encode-time invalidation
                    # and this ack may have re-cached the PRE-apply
                    # snapshot — drop it now, and read-your-writes holds
                    # from the moment this future resolves
                    self._kcache.invalidate_keys(local_keys, rank=self.rank)
                done_f.set_result(None)
            except BaseException as e:  # noqa: BLE001 — future boundary
                if not done_f.done():
                    done_f.set_exception(e)

        inner.add_done_callback(done)
        return done_f

    # -- error-feedback accumulator (quantized transport) ------------------

    #: above this many rows the accumulator switches from a dense
    #: range-indexed array to a compact touched-keys-only map — a sparse
    #: workload on a 10^9-key shard must not allocate the whole range
    #: client-side just because one high key was pushed
    _DENSE_RESIDUAL_ROWS = 1 << 22

    def _res_rows(self, keys: np.ndarray, vdim: int) -> np.ndarray:
        """Row indices into the residual buffer for ``keys``, allocating
        as needed (caller holds ``_res_lock``). Small known ranges index
        the buffer by the range-relative key directly (vectorized);
        large or unknown ranges go through a compact key->row map, so
        memory is bounded by TOUCHED keys, never the range."""
        if self._residual is None or self._res_vdim != vdim:
            self._residual = np.zeros((0, vdim), np.float32)
            self._res_vdim = vdim
            self._res_map = (
                None
                if 0 < self._res_range <= self._DENSE_RESIDUAL_ROWS
                else {}
            )
        if self._res_map is None:
            rows = keys
            hi = int(keys.max()) + 1 if len(keys) else 0
        else:
            m = self._res_map
            rows = np.empty(len(keys), np.int64)
            for i, k in enumerate(keys.tolist()):
                j = m.get(k)
                if j is None:
                    j = m[k] = len(m)
                rows[i] = j
            hi = len(m)
        if hi > len(self._residual):
            grown = np.zeros(
                (max(hi, 2 * len(self._residual)), vdim), np.float32
            )
            grown[: len(self._residual)] = self._residual
            self._residual = grown
        return rows

    def residual_rows(self, keys: np.ndarray) -> np.ndarray:
        """Current residual rows for ``keys``, zeros where nothing
        accumulated (observability + the tests' telescoping identity).
        Strictly READ-ONLY: unlike ``_res_rows`` it never allocates map
        entries or grows the buffer — a metrics loop sweeping the key
        space must not inflate the accumulator it is observing."""
        with self._res_lock:
            if self._residual is None:
                return np.zeros((len(keys), 1), np.float32)
            out = np.zeros((len(keys), self._res_vdim), np.float32)
            if self._res_map is None:
                known = keys < len(self._residual)
                out[known] = self._residual[keys[known]]
            else:
                m = self._res_map
                for i, k in enumerate(keys.tolist()):
                    j = m.get(k)
                    if j is not None:
                        out[i] = self._residual[j]
            return out

    def residual_norm(self) -> float:
        """Mean |residual| over allocated rows (observability + tests)."""
        with self._res_lock:
            if self._residual is None:
                return 0.0
            n = (
                len(self._res_map)
                if self._res_map is not None
                else len(self._residual)
            )
            if n == 0:
                return 0.0
            return float(np.abs(self._residual[:n]).mean())

    def _encode_push(
        self, local_keys: np.ndarray, grads: np.ndarray
    ) -> tuple[dict[str, Any], Arrays]:
        """Apply the send filters to one push payload (shared by the sync
        and async paths): the negotiated per-segment quantized codec with
        error feedback, the legacy fixed-point filter, else f32.

        Called exactly once per LOGICAL push — transport resends, the
        need_keys bounce and the keyed-seq recovery path all reuse the
        returned arrays — so the residual fold below happens exactly once
        however chaotic the wire gets."""
        if self._kcache is not None:
            # exact self-invalidation (serving handles): this handle must
            # never read its own write stale out of its own cache. Done
            # at encode time — once per logical push — though dropping a
            # cache entry twice would be harmless anyway.
            self._kcache.invalidate_keys(local_keys, rank=self.rank)
        fields: dict[str, Any] = {"codec": 0}
        g = grads.astype(np.float32, copy=False).reshape(len(local_keys), -1)
        if self._quant_bytes and "qwire" in self.client.peer_features:
            with self._res_lock:
                rows = self._res_rows(local_keys, g.shape[1])
                g_tot = g + self._residual[rows]
                q, qs = self._quantizer.encode(next(self._quant_seed), g_tot)
                res = g_tot - self._quantizer.decode(q, qs).reshape(
                    g_tot.shape
                )
                self._residual[rows] = res
            arrays: Arrays = {"q": q, "qs": qs}
            fields["codec"] = self._quant_bytes
            fields["qseg"] = self._quantizer.seg
            wire_counters.inc(
                "wire_quant_bytes_saved",
                max(int(g_tot.nbytes) - q.nbytes - qs.nbytes, 0),
            )
            # residual-norm gauge (micro-units, cluster-merged as a max):
            # a growing peak means quantization error is accumulating
            # faster than error feedback drains it
            wire_counters.observe_max(
                "wire_quant_residual_peak",
                int(np.abs(res).mean() * 1e6),
            )
        elif self._quant_bytes:
            # quant configured but the peer never acked "qwire" (old or
            # downgraded server, or the pre-negotiation first frames):
            # float path — flushing any residual accumulated before a
            # downgrade so no gradient mass is ever stranded
            with self._res_lock:
                if self._residual is not None and len(self._residual):
                    rows = self._res_rows(local_keys, g.shape[1])
                    g = g + self._residual[rows]  # fresh buffer
                    self._residual[rows] = 0.0
                else:
                    g = np.array(g, dtype=np.float32)  # own the buffer
            arrays = {"g": g}
        elif self._codec_bytes:
            import jax

            e = self._codec.encode(
                jax.random.key(next(self._quant_seed)),
                grads.astype(np.float32),
            )
            arrays = {
                "q": np.asarray(e.q),
                "lo": np.asarray(e.lo)[None],
                "scale": np.asarray(e.scale)[None],
            }
            fields["codec"] = self._codec_bytes
        else:
            # own the buffer (np.array always copies): the async pipeline
            # serializes at send — and heal RESEND — time, so aliasing
            # the caller's gradient array would let a reused buffer
            # silently corrupt an in-flight push
            arrays = {"g": np.array(g, dtype=np.float32)}
        # push payload accounting (pre-compression, keys excluded): a
        # wire-bytes ratio divides the float-path total by the
        # quantized-path total on identical workloads
        wire_counters.inc(
            "wire_push_payload_bytes",
            sum(int(a.nbytes) for a in arrays.values()),
        )
        return fields, arrays

    # -- quantized pull (read-mostly traffic) ------------------------------

    def _pull_fields(self) -> dict[str, Any]:
        """Extra pull request fields: ask for quantized rows only once
        the peer negotiated the codec ([wire] quant_pull)."""
        if self._quant_pull and "qwire" in self.client.peer_features:
            return {"quant": self._quant_bytes, "qseg": self._quantizer.seg}
        return {}

    def _decode_pull(self, out: Arrays) -> np.ndarray:
        """Decode one pull reply: quantized rows when the server sent
        them, the float fallback otherwise (a non-quant server ignores
        the ``quant`` field and replies floats — degrade, not corrupt)."""
        if "q" in out:
            return self._quantizer.decode(out["q"], out["qs"])
        return out["w"].astype(np.float32)

    # -- client-side versioned key cache (serving handles only) -----------

    def _book_serve_age(self, age_us: float, src: str) -> None:
        """Book the realized data age ONE serve handed its consumer
        (freshness plane, ISSUE 17): the global ``serve.age_s``
        histogram (what `cli top`'s age column and the ``pull_age_ms``
        SLO read; the pre-rename name ``serve.age`` stays a read-side
        alias for beats from older nodes — utils/timeseries.py),
        this handle's per-range matrix when it knows its range, and the
        flight recorder (a shed-stale serve near the staleness ceiling
        is exactly the context a postmortem wants on the timeline)."""
        age_s = max(float(age_us), 0.0) / 1e6
        latency_histograms.observe("serve.age_s", age_s)
        if self._range_scope is not None:
            self._range_scope.age(age_s)
        flightrec.record(
            "freshness.serve", rank=self.rank, src=src,
            age_us=int(age_us),
        )

    def _cache_try(
        self, local_keys: np.ndarray
    ) -> tuple[np.ndarray | None, dict[str, Any], str, Any, bool, int]:
        """Consult the key cache for one pull: (locally served rows or
        None, extra wire fields for the revalidation, sig, entry, owns-
        refresh). A fresh entry short-circuits the wire entirely; a
        stale one turns the pull into an ``if_newer`` revalidation —
        claimed single-flight, so while one caller refreshes, concurrent
        pulls of the same keys serve the bounded-stale rows instead of
        duplicating the wire refresh. ``shed_ok`` is advertised only
        while the entry is within the hard staleness ceiling (an
        overloaded server can never stretch us past it); a caller that
        got the refresh claim MUST settle it via ``_cache_settle`` or
        ``end_refresh`` on the error path. The final element is the
        cache's invalidation generation AT ISSUE: ``_cache_settle``
        hands it to ``put`` so rows that crossed a concurrent push on
        the wire are never installed over that push's invalidation."""
        # (rank, digest) composite: keys are range-relative, so a shared
        # multi-shard frontend cache must namespace entries by shard —
        # two shards produce the same digest for different rows
        sig = (self.rank, _sig(local_keys))
        gen = self._kcache.gen
        ent = self._kcache.lookup(sig)
        if ent is None:
            wire_counters.inc("serve_cache_misses")
            # sv: ask for the reply's version stamp (rides the JSON
            # tail; if_newer implies it on the revalidation paths below)
            return None, {"sv": 1}, sig, None, False, gen
        if self._kcache.fresh(ent):
            wire_counters.inc("serve_cache_hits")
            self._book_serve_age(ent.age_us(), "cache")
            # a copy, not the cached buffer: callers own their rows and
            # may scribble on them; the cache must stay pristine
            return ent.values.copy(), {}, sig, ent, False, gen
        if not self._kcache.begin_refresh(sig):
            if self._kcache.can_shed(ent):
                # another thread's refresh is in flight: serve the
                # bounded-stale rows rather than duplicate its RTT
                wire_counters.inc("serve_cache_stale_hits")
                self._book_serve_age(ent.age_us(), "stale")
                return ent.values.copy(), {}, sig, ent, False, gen
            # past the staleness ceiling: correctness wins — do our own
            # wire pull alongside the in-flight refresh
            fields: dict[str, Any] = {"if_newer": ent.version}
            return None, fields, sig, ent, False, gen
        fields = {"if_newer": ent.version}
        if self._kcache.can_shed(ent):
            fields["shed_ok"] = 1
        return None, fields, sig, ent, True, gen

    def _cache_settle(
        self, rep: dict[str, Any], out: Arrays,
        local_keys: np.ndarray, sig: str, ent, own: bool = False,
        gen: int | None = None,
    ) -> np.ndarray:
        """Interpret one pull reply against the cache and return the
        rows. ``ent`` is the entry reference captured at issue time: a
        concurrent invalidation doesn't invalidate THIS read (the read
        was validated against a snapshot that preceded the push), it
        only stops the entry from being revalidated in place. ``own``
        releases this pull's single-flight refresh claim."""
        try:
            age = rep.get("_age_us")  # server-measured realized age
            if rep.get("not_modified") and ent is not None:
                if rep.get("shed"):
                    # the server shed our revalidation: keep serving the
                    # cached rows (we only advertised shed_ok while
                    # inside max_stale) and back off for retry_after
                    wire_counters.inc("serve_shed_served")
                    self._kcache.shed_backoff(
                        sig, float(rep.get("retry_after_ms", 20)) / 1e3
                    )
                    # no age echo on a shed reply (nothing validated):
                    # the realized age is the entry's own, still growing
                    self._book_serve_age(ent.age_us(), "shed")
                else:
                    self._kcache.revalidated(
                        sig, int(rep["ver"]), age_us=age,
                    )
                    self._book_serve_age(
                        age if age is not None else ent.age_us(),
                        "revalidate",
                    )
                return ent.values.copy()
            vals = self._decode_pull(out)
            ver = rep.get("ver")
            if ver is not None:
                # as_of: an invalidation (a concurrent push) since this
                # pull was issued wins — the install is skipped rather
                # than resurrect possibly pre-push rows
                self._kcache.put(
                    sig, local_keys, vals, int(ver), as_of=gen,
                    rank=self.rank, age_us=age,
                )
                if age is not None:
                    self._book_serve_age(age, "pull")
            return vals
        finally:
            if own:
                self._kcache.end_refresh(sig)

    def pull(self, local_keys: np.ndarray) -> np.ndarray:
        if len(local_keys) == 0:
            return np.zeros(0, dtype=np.float32)
        extra: dict[str, Any] = {}
        sig = ent = None
        own = False
        gen = None
        if self._kcache is not None:
            vals, extra, sig, ent, own, gen = self._cache_try(local_keys)
            if vals is not None:
                return vals  # served locally: zero wire traffic
        try:
            with trace.span(
                "ps.pull", cat="ps", rank=self.rank, keys=len(local_keys)
            ) as sp:
                rep, out = self._keyed_call(
                    "pull", local_keys, {}, **self._pull_fields(), **extra
                )
                sp.set(bytes=int(sum(a.nbytes for a in out.values())))
        except BaseException:
            if own:
                self._kcache.end_refresh(sig)
            raise
        if self._kcache is not None:
            return self._cache_settle(
                rep, out, local_keys, sig, ent, own, gen
            )
        return self._decode_pull(out)

    def push(self, local_keys: np.ndarray, grads: np.ndarray) -> None:
        if len(local_keys) == 0:
            return
        fields, arrays = self._encode_push(local_keys, grads)
        with trace.span(
            "ps.push", cat="ps", rank=self.rank, keys=len(local_keys),
            bytes=int(sum(a.nbytes for a in arrays.values())),
        ):
            self._keyed_call("push", local_keys, arrays, **fields)
        if self._kcache is not None:
            # ack-time invalidation (see push_async.done): a pull that
            # raced the deferred apply may have re-cached pre-push rows
            self._kcache.invalidate_keys(local_keys, rank=self.rank)

    def dump(self) -> tuple[int, np.ndarray]:
        rep, out = self.client.call("dump")
        return int(rep["begin"]), out["w"]

    def stats(self) -> dict[str, Any]:
        rep, _ = self.client.call("stats")
        return {k: v for k, v in rep.items() if k != "ok"}

    def shutdown(self) -> None:
        self.client.call("shutdown")

    def close(self) -> None:
        watchdog.unregister(self._wd_name)
        self.client.close()
        if self._recovery_pool is not None:
            self._recovery_pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# node entry points (ref: main.cc role dispatch; spawned by launch_local or
# the `cli node` subcommand — one process per node, like script/local.sh)
# ---------------------------------------------------------------------------


def _export_witness_env(child_env: dict) -> None:
    """Arm the runtime lock-order witness in spawned children whenever
    THIS process runs under it — whether it was armed by the
    ``PS_LOCK_WITNESS`` env var (already inherited via the env copy) or
    by an explicit ``witness.install()`` (the tier-1 conftest), which an
    env copy alone would silently fail to propagate. Children arm at
    package import (parallel/__init__), so every lock a spawned node
    constructs is order-checked too. The lockset race witness rides the
    same rule: an armed parent spawns armed children, so the
    registered shared objects of every node in a launch_local cluster
    are lockset-checked."""
    from parameter_server_tpu.analysis import racewitness, witness

    if witness.installed():
        child_env[witness.ENV_VAR] = "1"
    if racewitness.installed():
        child_env[racewitness.ENV_VAR] = "1"


class _RemoteBeatSink:
    """Adapter giving ``HeartbeatReporter`` a coordinator RPC sink.

    Opens its OWN connection: the node's main ControlClient serializes
    calls under a lock and legitimately parks for long stretches
    (blocking kv_get, ssp_wait) — beats riding that lock would stall and
    read as a dead node exactly when the node is merely waiting."""

    def __init__(self, scheduler: str):
        self._scheduler = scheduler
        # short retry window: a beat is periodic — retrying one for longer
        # than the beat interval just delays the NEXT (fresher) beat
        self._ctl: ControlClient | None = ControlClient(
            scheduler, reconnect_timeout_s=1.0
        )

    def beat(self, node_id: int, stats: dict | None = None) -> bool:
        # a single transient socket failure must not silence beats forever
        # (a healthy node would read as dead): drop the connection and
        # rebuild it on the next beat. Returns delivery success so the
        # reporter knows whether to ack the audit-spool batches the beat
        # carried (False = they stay in flight for the next beat).
        try:
            if self._ctl is None:
                self._ctl = ControlClient(
                    self._scheduler, retries=1, retry_delay=0.0,
                    reconnect_timeout_s=1.0,
                )
            self._ctl.beat(node_id, stats)
            return True
        except Exception:
            if self._ctl is not None:
                self._ctl.close()
            self._ctl = None
            return False

    def close(self) -> None:
        if self._ctl is not None:
            self._ctl.close()


class _Beats:
    """A node's liveness heartbeat: HeartbeatReporter over a dedicated
    coordinator connection (ref: the reference's heartbeat thread —
    liveness must not depend on training cadence). Each beat piggybacks
    this process's telemetry snapshot (counters + latency histograms +
    named timers), which is what the coordinator's ``telemetry`` command
    merges into the cluster view — no second collection path."""

    def __init__(
        self,
        scheduler: str,
        node_id: int,
        interval_s: float,
        audit_cfg: "AuditConfig | None" = None,
    ):
        self._sink = _RemoteBeatSink(scheduler)
        # audit plane (ISSUE 14): heartbeating nodes arm the flightrec
        # event spool so their protocol-invariant events (push acks,
        # apply commits, RCU publishes, heals, sheds) ride every beat to
        # the coordinator's streaming auditor; the reporter drains/acks
        self._armed_spool = False
        if audit_cfg is not None and audit_cfg.enabled:
            flightrec.configure_spool(
                audit_cfg.spool_capacity, audit_cfg.batch_events
            )
            self._armed_spool = True

        def beat_stats() -> dict:
            # ONE snapshot serves three planes (ISSUE 13): the beat
            # piggyback, this node's local time-series ring roll, and
            # the heartbeat payload guard's saturation caps
            from parameter_server_tpu.utils.timeseries import beat_telemetry

            return {**host_stats(), "telemetry": beat_telemetry()}

        self._rep = HeartbeatReporter(
            self._sink, node_id, interval_s, stats_fn=beat_stats
        )
        self._rep.start()
        # watchdog: heartbeat silence, seen from INSIDE the silent node —
        # the beat thread is always "busy" (liveness is its whole job),
        # so a beats counter that stops advancing is a wedged reporter
        self._wd_name = f"heartbeat:{node_id}"
        watchdog.register(
            self._wd_name, lambda: (True, self._rep.beats),
            thread_name="ps-heartbeat",
        )

    def stop(self) -> None:
        watchdog.unregister(self._wd_name)
        self._rep.stop()
        self._sink.close()
        if self._armed_spool:
            flightrec.configure_spool(None)


def run_server(
    cfg: PSConfig,
    scheduler: str,
    rank: int,
    num_servers: int,
    bind_host: str = "127.0.0.1",
    advertise_host: str = "",
    ckpt_dir: str = "",
) -> None:
    """One server process. ``bind_host="0.0.0.0"`` + a routable
    ``advertise_host`` lets workers on other hosts connect (the default
    loopback pair only serves the single-host multi-process harness).

    ``ckpt_dir`` enables recovery (ref: each server dumps its own range;
    resume = reload it): an existing dump for this range is loaded on
    startup (a relaunched server resumes where its last dump left off),
    and with fault.server_ckpt_interval_s > 0 the state is re-dumped
    periodically while serving."""
    from parameter_server_tpu.models.linear import updater_from_config

    ranges = KeyRange(0, cfg.data.num_keys).even_divide(num_servers)
    srv = ShardServer(
        updater_from_config(cfg),
        ranges[rank],
        host=bind_host,
        advertise_host=advertise_host,
        fault_plan=_plan_from_cfg(cfg),
        server_cfg=cfg.server,
        serve_cfg=cfg.serve,
    )
    if ckpt_dir:
        if srv.load_state(ckpt_dir):
            print(f"[server {rank}] resumed from {ckpt_dir}", flush=True)
        if cfg.fault.server_ckpt_interval_s > 0:
            srv.start_checkpointing(ckpt_dir, cfg.fault.server_ckpt_interval_s)
    ctl = ControlClient(
        scheduler, reconnect_timeout_s=cfg.fault.reconnect_timeout_s
    )
    node_id = ctl.register("server", rank=rank)
    # set AFTER any resume: workers re-resolving this key must never beat
    # the state load and pull pre-resume zeros
    ctl.kv_set(f"server_addr/{rank}", addr=srv.address)
    beats = _Beats(
        scheduler, node_id, cfg.fault.heartbeat_interval_s,
        audit_cfg=cfg.audit,
    )
    srv.serve_forever()  # until the scheduler's shutdown
    if ckpt_dir:
        srv.stop_checkpointing()  # no periodic writer behind the final dump
        srv.save_state(ckpt_dir)
    beats.stop()
    ctl.close()
    trace.tracer.flush()  # export this process's spans (no-op if disabled)


def _connect_servers(
    ctl: ControlClient, worker_rank: int, num_servers: int, cfg: PSConfig
) -> list[ServerHandle]:
    ranges = KeyRange(0, cfg.data.num_keys).even_divide(num_servers)
    handles = []
    for s in range(num_servers):
        fields, _ = ctl.kv_get(f"server_addr/{s}", block=True, timeout=60)

        def resolve(s=s) -> str:
            # re-read the registry: a relaunched server re-publishes its
            # (new) address under the same rank key
            f, _ = ctl.kv_get(f"server_addr/{s}", block=True, timeout=10)
            return f["addr"]

        handles.append(
            ServerHandle(
                fields["addr"], s, worker_rank, cfg,
                range_size=ranges[s].size, key_range=ranges[s],
                resolve_addr=resolve,
                # the TRAINING tier: never a serving handle. A trainer's
                # staleness contract is the SSP clock (bounded delay in
                # steps), and a TTL cache would stack a second, time-based
                # staleness on top of it — so training pulls always hit
                # the wire even when [serve] cache is on for this config.
                serving=False,
            )
        )
    return handles


def run_worker(
    cfg: PSConfig,
    scheduler: str,
    rank: int,
    num_servers: int,
    report_interval: int = 20,
) -> None:
    """The async-SGD worker loop over the wire (ref: AsyncSGDWorker)."""
    import jax

    from parameter_server_tpu.data.reader import MinibatchReader
    from parameter_server_tpu.models import metrics as M
    from parameter_server_tpu.ops.sparse import csr_grad, csr_logits, logistic_loss

    ctl = ControlClient(
        scheduler, reconnect_timeout_s=cfg.fault.reconnect_timeout_s
    )
    node_id = ctl.register("worker", rank=rank)
    beats = _Beats(
        scheduler, node_id, cfg.fault.heartbeat_interval_s,
        audit_cfg=cfg.audit,
    )
    # the scheduler's ssp_init/workload_init must land before our first
    # fetch; registration order doesn't guarantee it, this kv flag does
    ctl.kv_get("scheduler_init_done", block=True, timeout=120)
    servers = _connect_servers(ctl, rank, num_servers, cfg)
    ranges = KeyRange(0, cfg.data.num_keys).even_divide(num_servers)
    # the transport-neutral data plane (parallel/backend.py): this loop
    # only ever sees global keys; the backend owns the range fan-out
    # (slice against server ranges, concurrent per-shard wire calls,
    # merge) that used to be hand-rolled here
    from parameter_server_tpu.parallel.backend import SocketBackend

    backend = SocketBackend(
        servers, ranges, cfg.data.num_keys, own_handles=False
    )
    from parameter_server_tpu.data.batch import training_builder

    builder = training_builder(cfg)

    @jax.jit
    def grad_step(w_u, values, local_ids, row_ids, row_splits, labels, mask):
        logits = csr_logits(w_u, values, local_ids, row_ids, row_splits)
        loss, err = logistic_loss(logits, labels, mask)
        g = csr_grad(
            err, values, local_ids, row_ids, row_splits, num_unique=w_u.shape[0]
        )
        return loss, jax.nn.sigmoid(logits), g

    from parameter_server_tpu.parallel.ssp import PushWindow

    # in-flight push bound, in whole steps: the SSP delay shapes it (a step
    # only ssp_finishes when its pushes applied, so more than tau+1 steps
    # in flight could never clear the gate anyway), and the explicit
    # wire.max_inflight_pushes knob tightens it when wire memory — not
    # staleness — is the binding constraint
    max_delay = cfg.solver.max_delay
    ssp_limit = max_delay if max_delay >= 0 else (1 << 30)
    cap = cfg.wire.max_inflight_pushes
    inflight_limit = ssp_limit if cap <= 0 else min(ssp_limit, cap)
    pushes = PushWindow(
        inflight_limit, retire=lambda step_i: ctl.ssp_finish(rank, step_i)
    )

    step = 0
    window: list[tuple[float, np.ndarray, np.ndarray]] = []
    t0 = time.perf_counter()
    ex_seen = 0

    def flush_window() -> None:
        """Send the window's merged Progress (ref: per-report_interval
        Progress protos merged at the scheduler)."""
        nonlocal window, t0
        if not window:
            return
        n = sum(len(y) for _, _, y in window)
        y = np.concatenate([y for _, _, y in window])
        p = np.concatenate([pr for _, pr, _ in window])
        ctl.progress(
            rank,
            {
                "examples": n,
                "examples_total": ex_seen,
                "objv": sum(l for l, _, _ in window) / n,
                "auc": M.auc(y, p),
                "ex_per_sec": n / max(time.perf_counter() - t0, 1e-9),
                # MEASURED wire traffic, cumulative for this worker (ref:
                # the Postoffice per-message byte counters) — merged at the
                # scheduler as a sum over workers. Counted at the FRAME
                # layer (send_frame/recv_frame), so control, heartbeat and
                # data-plane traffic are all in
                "wire_bytes_out": wire_counters.get("wire_bytes_out"),
                "wire_bytes_in": wire_counters.get("wire_bytes_in"),
                # adaptive-compression accounting (the per-filter byte
                # counters the reference's Postoffice kept): bytes the
                # codec won, and probes that declined incompressible data
                "wire_bytes_saved": wire_counters.get("wire_bytes_saved"),
                "wire_comp_skipped": wire_counters.get("wire_comp_skipped"),
                # self-healing counters, cumulative for this worker process
                # (merged at the scheduler as cluster totals)
                "rpc_retries": wire_counters.get("rpc_retries"),
                "rpc_reconnects": wire_counters.get("rpc_reconnects"),
            },
        )
        window = []
        t0 = time.perf_counter()

    while True:
        with trace.span("step.workload_fetch", cat="step"):
            workload = ctl.workload_fetch(rank)
        if workload is None:
            if ctl.workload_all_done():
                break
            # nothing pending, but another worker still holds active
            # shards — if it dies the scheduler requeues them, so keep
            # polling instead of exiting (ref: the pool is drained only
            # when every shard is FINISHED, not merely assigned)
            time.sleep(0.2)
            continue
        _epoch, path = workload.split(":", 1)
        for b in MinibatchReader([path], cfg.data.format, builder):
            # retire our own in-flight pushes first: the clock's gate for
            # step t includes this worker's finished counter (wait_time
            # semantics), so draining after the gate would self-deadlock
            pushes.gate()
            # step anatomy (the "where did this step's 40 ms go" spans):
            # one enclosing step span; ssp_wait / pull / compute are its
            # children. Pull and push fan out over every shard server
            # CONCURRENTLY on the pipelined async wire — no thread pool;
            # flow events tie each push's issue span to its completion.
            with trace.span("step", cat="step", step=step):
                with trace.span("step.ssp_wait", cat="step"):
                    ctl.ssp_wait(rank, step)
                # the batch's (sorted) unique GLOBAL keys; the backend
                # does the range slicing + concurrent per-shard wire
                real = b.unique_keys[1 : b.num_unique]
                with trace.span("step.pull", cat="step"):
                    pulled = backend.pull(real)
                with trace.span("step.compute", cat="step"):
                    w_u = np.zeros(len(b.unique_keys), dtype=np.float32)
                    w_u[1 : b.num_unique] = pulled.ravel()
                    loss, probs, g = grad_step(
                        w_u, b.values, b.local_ids, b.row_ids, b.row_splits,
                        b.labels, b.example_mask,
                    )
                    g_real = np.asarray(g).ravel()[1 : b.num_unique]
                # pushes stay in flight past this span's exit; the flow
                # links (ps.push.inflight) bridge issue to completion
                futs = [backend.push_async(real, g_real)]
            pushes.add(step, futs)
            ex_seen += b.num_examples
            window.append(
                (
                    float(loss),
                    np.asarray(probs)[: b.num_examples],
                    b.labels[: b.num_examples],
                )
            )
            if len(window) >= report_interval:
                flush_window()
            step += 1
        ctl.workload_finish(workload)
    pushes.wait_all()  # the sync point: every in-flight push acked
    flush_window()
    ctl.ssp_retire(rank)  # out of data: stop gating the still-running workers
    # completion signal (replaces a fixed barrier: a barrier over
    # num_workers+1 can never release once a worker dies — the scheduler's
    # monitor loop instead waits for every rank to be done-or-dead)
    ctl.kv_set(f"worker_done/{rank}")
    beats.stop()
    for sh in servers:
        sh.close()
    ctl.close()
    trace.tracer.flush()  # export this process's spans (no-op if disabled)


def run_scheduler(
    cfg: PSConfig,
    coordinator: Coordinator,
    num_servers: int,
    num_workers: int,
    model_out: str = "",
) -> dict[str, Any]:
    """Drive a run: init pools/clock, wait for completion, assemble the
    model from server dumps (ref: SaveModel, each server writes its range),
    evaluate, shut everything down."""
    ctl = ControlClient(coordinator.address)
    ctl.register("scheduler")
    ctl.ssp_init(num_workers, cfg.solver.max_delay)
    items = [
        f"{e}:{f}" for e in range(max(cfg.solver.epochs, 1)) for f in cfg.data.files
    ]
    ctl.workload_init(items)
    ctl.kv_set("scheduler_init_done")  # workers block on this before fetching
    if cfg.fault.recovery_sweep_interval_s > 0:
        # dead-WORKER recovery (requeue + clock release) runs inside the
        # coordinator's sweep thread; this loop just records its verdicts.
        # Dead-SERVER policy (grace window / fail fast) stays here — it
        # needs run-level knowledge (checkpointing on? abort or wait?)
        coordinator.start_recovery(cfg.fault.recovery_sweep_interval_s)

    # Monitor loop (ref: the scheduler's dead-node handling): wait until
    # every worker rank is done or dead. A plain barrier cannot do this —
    # it would park forever on the dead worker's missing arrival.
    dead_ranks: set[int] = set()
    server_dead_since: dict[int, float] = {}  # rank -> first seen dead
    t_start = time.monotonic()

    def declare_dead(r: int, why: str) -> None:
        requeued = ctl.workload_reassign(worker=r)
        ctl.ssp_retire(r)
        dead_ranks.add(r)
        print(
            f"[scheduler] worker {r} {why}; requeued {len(requeued)} "
            f"shard(s), retired its clock",
            flush=True,
        )

    while True:
        done = {
            r
            for r in range(num_workers)
            if ctl.kv_get(f"worker_done/{r}") is not None
        }
        if done | dead_ranks >= set(range(num_workers)):
            break
        for r, info in ctl.recovered_workers().items():
            if r not in dead_ranks:
                dead_ranks.add(r)
                print(
                    f"[scheduler] worker {r} dead (missed heartbeats); "
                    f"sweep requeued {len(info['requeued'])} shard(s) and "
                    "retired its clock",
                    flush=True,
                )
        registry = ctl.nodes()
        dead_ids, _alive = ctl.dead_nodes()
        dead_set = {int(x) for x in dead_ids}
        alive_server_ranks = {
            int(n["rank"])
            for nid2, n in registry.items()
            if n.get("role") == "server"
            and "rank" in n
            and int(nid2) not in dead_set
        }
        for nid in dead_ids:
            info = registry.get(str(nid), {})
            role = info.get("role")
            if role == "server":
                r = int(info.get("rank", -1))
                grace = cfg.fault.server_restart_grace_s
                if r in alive_server_ranks:
                    # a replacement re-registered under this rank (resume
                    # from its checkpoint); the old corpse can be ignored
                    server_dead_since.pop(r, None)
                    continue
                now = time.monotonic()
                since = server_dead_since.setdefault(r, now)
                if grace <= 0 or now - since > grace:
                    # without checkpoint-backed restart a dead server is
                    # unrecoverable (its key range is gone): fail fast with
                    # the cause instead of letting workers hang on its
                    # socket until the launcher timeout
                    raise RuntimeError(
                        f"shard server rank {r} died (missed heartbeats) "
                        + (
                            f"and no replacement registered within {grace}s; "
                            if grace > 0
                            else "; "
                        )
                        + "aborting the run"
                    )
                continue
            if role != "worker":
                continue
            r = int(info.get("rank", -1))
            if r not in dead_ranks and r not in done:
                # sweep disabled (recovery_sweep_interval_s == 0): fall
                # back to scheduler-driven recovery over the wire
                declare_dead(r, "dead (missed heartbeats)")
        if time.monotonic() - t_start > cfg.fault.startup_grace_s:
            # a rank that NEVER registered is in neither the dead list
            # (no beats recorded) nor done — without this it would park
            # the monitor forever (e.g. the process crashed on startup)
            registered = {
                int(n["rank"])
                for n in registry.values()
                if n.get("role") == "worker" and "rank" in n
            }
            for r in set(range(num_workers)) - registered - dead_ranks - done:
                declare_dead(r, "never registered (startup failure?)")
        if cfg.fault.straggler_reassign_s > 0:
            ctl.workload_reassign(older_than=cfg.fault.straggler_reassign_s)
        time.sleep(0.5)

    servers = _connect_servers(ctl, worker_rank=-1, num_servers=num_servers, cfg=cfg)
    from parameter_server_tpu.parallel.backend import SocketBackend

    w = SocketBackend(
        servers,
        KeyRange(0, cfg.data.num_keys).even_divide(num_servers),
        cfg.data.num_keys,
        own_handles=False,
    ).weights().ravel()
    out: dict[str, Any] = {
        "merged": ctl.progress_merged(),
        "server_stats": [sh.stats() for sh in servers],
        "nnz_w": int(np.count_nonzero(w)),
        "workloads": ctl.workload_stats(),
        "dead_workers": sorted(dead_ranks),
        # scheduler-process wire/recovery counters; the coordinator runs
        # in-process, so rpc_dedup_hits here covers every control frame
        # the cluster resent or duplicated
        "wire": wire_counters.snapshot(),
        # cluster telemetry merged from every node's heartbeat snapshot
        # (+ this process): counters, per-command latency histograms,
        # named timers — the `cli stats` view, embedded in the run result
        "telemetry": ctl.telemetry()["merged"],
    }
    chaos_stats = coordinator.server.fault_stats()
    if chaos_stats is not None:
        out["chaos"] = chaos_stats
        out["control_frames"] = coordinator.server.frames_in
    if model_out:
        from parameter_server_tpu.utils.checkpoint import dump_weights_text

        dump_weights_text(w, model_out)
        out["model_out"] = model_out
    if cfg.data.val_files:
        from parameter_server_tpu.models.evaluation import evaluate_model

        ev = evaluate_model(
            w, cfg.data.val_files, cfg.data.format, cfg.data.num_keys,
            batch_size=cfg.solver.minibatch,
            max_nnz_per_example=cfg.data.max_nnz_per_example,
        )
        out["val_auc"] = ev["auc"]
        out["val_logloss"] = ev["logloss"]
    for sh in servers:
        sh.shutdown()
        sh.close()
    ctl.close()
    coordinator.stop()
    trace.tracer.flush()  # export this process's spans (no-op if disabled)
    return out


def launch_local(
    app_file: str,
    num_servers: int,
    num_workers: int,
    model_out: str = "",
    timeout: float = 600.0,
    devices: str = "cpu",
    fault_kill: str = "",
    fault_restart_after: float = -1.0,
    ckpt_dir: str = "",
    fault_plan: str = "",
    fault_seed: int = 0,
    trace_dir: str = "",
    trace_sample: int = 1,
    blackbox_dir: str = "",
) -> dict[str, Any]:
    """Spawn scheduler + servers + workers as real processes on this host
    (ref: script/local.sh — the de-facto integration test harness).

    ``devices="cpu"`` (default) pins every spawned node to the CPU backend:
    the harness simulates a multi-host cluster on one machine, and N
    processes must not fight over this host's accelerator (real multi-host
    runs get one process per host from the cluster manager, not from here).
    ``devices="inherit"`` leaves the environment alone — and is refused
    with a ValueError unless that environment already says
    ``JAX_PLATFORMS=cpu``: every node imports JAX and initialises a
    backend, an accelerator belongs to one process at a time, and the
    ``1 + num_servers + num_workers`` nodes started here would hang
    waiting for it rather than fail.

    ``fault_kill="worker:1@2.0"`` is the fault-injection hook (SURVEY §5.3:
    "fault injection = kill a host process in the simulated integration
    test"): SIGKILL the named node 2.0s after it registers with the
    coordinator, exercising dead-node detection + workload requeue.

    ``fault_restart_after >= 0`` respawns the killed node that many seconds
    after the kill — with ``ckpt_dir`` set (server checkpointing, see
    run_server) this exercises the checkpoint-backed server recovery path.

    ``fault_plan`` (parallel/chaos.py spec) arms a seeded FaultPlan on
    EVERY spawned node's RpcServers via the PS_FAULT_PLAN env var —
    frame-level drop/delay/disconnect/duplicate chaos on top of (or
    instead of) the process-kill fault.

    ``blackbox_dir`` arms the flight recorder + watchdog on every
    spawned node via the PS_BLACKBOX_DIR env var (the PS_TRACE_DIR
    pattern): each process leaves a ``blackbox-<role>-<rank>-<pid>.json``
    dump behind — periodically flushed, so even a SIGKILL'd node's box
    survives for ``cli postmortem`` to merge.
    """
    import os
    import socket as socket_mod
    import subprocess
    import sys

    with socket_mod.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    addr = f"127.0.0.1:{port}"

    child_env = dict(os.environ)
    if devices == "cpu":
        from parameter_server_tpu.utils.hostenv import force_cpu

        force_cpu(child_env)
    elif devices != "inherit":
        raise ValueError(f"devices must be 'cpu' or 'inherit', got {devices!r}")
    elif child_env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise ValueError(
            f"launch_local(devices='inherit') would start "
            f"{1 + num_servers + num_workers} processes that each "
            "initialise a JAX backend, and an accelerator belongs to one "
            "process at a time; use devices='cpu', or set JAX_PLATFORMS=cpu"
        )
    if fault_plan:
        FaultPlan.parse(fault_plan, seed=fault_seed)  # fail fast on a typo
        child_env[PLAN_ENV] = fault_plan
        child_env[SEED_ENV] = str(fault_seed)
    if trace_dir:
        # arm tracing on EVERY spawned node (the PS_FAULT_PLAN pattern):
        # each process exports trace-<role>-<rank>-<pid>.json into this dir
        os.makedirs(trace_dir, exist_ok=True)
        child_env[trace.TRACE_DIR_ENV] = trace_dir
        if trace_sample > 1:
            # head sampling rides along: children keep whole traces or
            # drop them, consistently with every other node (the
            # decision is keyed off the trace id, not the process)
            child_env[trace.TRACE_SAMPLE_ENV] = str(int(trace_sample))
    if blackbox_dir:
        # arm the flight recorder on EVERY spawned node (same pattern):
        # any soak failure then leaves a postmortem behind
        os.makedirs(blackbox_dir, exist_ok=True)
        child_env[flightrec.BLACKBOX_DIR_ENV] = blackbox_dir
    _export_witness_env(child_env)

    import tempfile

    logdir = tempfile.mkdtemp(prefix="pslaunch_")

    def spawn(role: str, rank: int, attempt: int = 0) -> subprocess.Popen:
        cmd = [
            sys.executable, "-m", "parameter_server_tpu.cli", "node",
            "--role", role, "--rank", str(rank), "--scheduler", addr,
            "--num_servers", str(num_servers), "--num_workers", str(num_workers),
            "--app_file", app_file,
        ]
        if role == "scheduler" and model_out:
            cmd += ["--model_out", model_out]
        if role == "server" and ckpt_dir:
            cmd += ["--ckpt_dir", ckpt_dir]
        # child output goes to files, not PIPEs: nobody drains N pipes while
        # training runs, and a chatty child must never block on a full pipe
        tag = f"{role}-{rank}" + (f"-r{attempt}" if attempt else "")
        out_f = open(f"{logdir}/{tag}.out", "w+")
        err_f = open(f"{logdir}/{tag}.err", "w+")
        p = subprocess.Popen(cmd, stdout=out_f, stderr=err_f, text=True, env=child_env)
        p._ps_logs = (out_f, err_f)  # type: ignore[attr-defined]
        p._ps_tag = f"{role}:{rank}"  # type: ignore[attr-defined]
        return p

    def logs_of(p: subprocess.Popen) -> tuple[str, str]:
        out_f, err_f = p._ps_logs  # type: ignore[attr-defined]
        out_f.seek(0)
        err_f.seek(0)
        return out_f.read(), err_f.read()

    procs = [spawn("scheduler", 0)]
    procs += [spawn("server", r) for r in range(num_servers)]
    procs += [spawn("worker", r) for r in range(num_workers)]
    victims: list[subprocess.Popen] = []  # processes whose death is the test
    replacement_box: list[subprocess.Popen] = []  # assassin -> main handoff
    respawn_lock = threading.Lock()
    harness_done = threading.Event()
    if fault_kill:
        role_rank, delay_s = fault_kill.split("@")
        kill_role, kill_rank = role_rank.split(":")
        killed_tag = f"{kill_role}:{int(kill_rank)}"
        victim = next(p for p in procs if p._ps_tag == killed_tag)  # type: ignore[attr-defined]
        victims.append(victim)

        def assassin() -> None:
            # wait for the victim to REGISTER first: killing a process that
            # never reached the coordinator would leave the scheduler unable
            # to tell "dead" from "still starting up"
            ctl = ControlClient(addr, retries=600)
            try:
                while True:
                    if any(
                        n.get("role") == kill_role
                        and int(n.get("rank", -1)) == int(kill_rank)
                        for n in ctl.nodes().values()
                    ):
                        break
                    time.sleep(0.2)
            finally:
                ctl.close()
            time.sleep(float(delay_s))
            victim.kill()
            if fault_restart_after >= 0:
                time.sleep(fault_restart_after)
                # checkpoint-backed recovery: the replacement re-registers
                # under the same rank and reloads its range dump. Spawned
                # into its own box (NOT procs — the main wait loop is
                # iterating that) and only while the scheduler is alive:
                # respawning after the run ended would leave a server
                # nobody ever shuts down.
                with respawn_lock:
                    if not harness_done.is_set() and procs[0].poll() is None:
                        replacement_box.append(
                            spawn(kill_role, int(kill_rank), attempt=1)
                        )

        threading.Thread(target=assassin, daemon=True).start()
    deadline = time.monotonic() + timeout
    timed_out = False
    try:
        for p in procs:
            try:
                p.wait(timeout=max(deadline - time.monotonic(), 1))
            except subprocess.TimeoutExpired:
                timed_out = True
                break
        # the replacement (if any) exits when the scheduler shuts it down;
        # a replacement spawned too close to run end may have nobody left
        # to do that — reap it leniently rather than hang or fail the run
        with respawn_lock:
            harness_done.set()  # no further respawns
        for p in replacement_box:
            if not timed_out:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
    finally:
        with respawn_lock:
            harness_done.set()
        for p in replacement_box:
            victims.append(p)  # its rc never decides the run's outcome
            procs.append(p)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    outs = [(p, *logs_of(p)) for p in procs]
    for p, _, _ in outs:
        p._ps_logs[0].close()  # type: ignore[attr-defined]
        p._ps_logs[1].close()  # type: ignore[attr-defined]
    if timed_out:
        tails = "\n".join(
            f"--- {p._ps_tag} rc={p.returncode} ---\n{err[-1500:]}"  # type: ignore[attr-defined]
            for p, _, err in outs
        )
        raise RuntimeError(f"multi-process run timed out after {timeout}s:\n{tails}")
    for p, stdout, stderr in outs:
        if p.returncode != 0 and not any(p is v for v in victims):
            raise RuntimeError(
                f"node {p._ps_tag} failed rc={p.returncode}:\n{stderr[-2000:]}"  # type: ignore[attr-defined]
            )
    # scheduler prints the result JSON on its last stdout line
    return json.loads(outs[0][1].strip().splitlines()[-1])


def run_node(
    cfg: PSConfig,
    role: str,
    rank: int,
    scheduler: str,
    num_servers: int,
    num_workers: int,
    model_out: str = "",
    bind_host: str = "127.0.0.1",
    advertise_host: str = "",
    ckpt_dir: str = "",
) -> dict[str, Any] | None:
    """Role dispatch for one spawned process (ref: App::Create + main.cc)."""
    import os

    from parameter_server_tpu.utils.hostenv import init_compile_cache

    # the ONE unknown-role gate, before ANY arming side effects (an
    # armed tracer/recorder/profiler named after a typo'd role, or a
    # KeyError out of the metrics-port table, are worse diagnostics);
    # the table doubles as the metrics-endpoint port layout below
    metrics_offset = {
        "scheduler": 0,
        "server": 1 + rank,
        "worker": 1 + num_servers + rank,
    }.get(role)
    if metrics_offset is None:
        raise ValueError(f"unknown role {role!r}")

    init_compile_cache()

    # arm tracing for this node: config [trace] trace_dir wins, then the
    # inherited PS_TRACE_DIR env (launch_local's arming path); the process
    # name makes each node's export file self-describing
    tdir = cfg.trace.trace_dir or os.environ.get(trace.TRACE_DIR_ENV, "")
    if tdir:
        # head-sampling rate: an explicit [trace] sample wins, else the
        # inherited PS_TRACE_SAMPLE (launch_local's arming path)
        sample = cfg.trace.sample
        if sample <= 1:
            sample = trace._env_sample()
        trace.configure(
            tdir, capacity=cfg.trace.capacity,
            process_name=f"{role}-{rank}",
            sample=sample,
            # tail-biased capture (ISSUE 15): on by default — promotion
            # rescues the slow traces head sampling would drop
            tail=cfg.trace.tail,
            tail_k=cfg.trace.tail_k,
            tail_limbo=cfg.trace.tail_limbo,
        )
    # arm the black box: config [blackbox] dir wins, then the inherited
    # PS_BLACKBOX_DIR (launch_local's arming path) — re-configured even
    # when env-armed at import so the dump carries a role-rank name
    bdir = cfg.blackbox.dir or os.environ.get(flightrec.BLACKBOX_DIR_ENV, "")
    if bdir:
        flightrec.configure(
            bdir, capacity=cfg.blackbox.capacity,
            process_name=f"{role}-{rank}",
            flush_interval_s=cfg.blackbox.flush_interval_s,
            watchdog_interval_s=cfg.blackbox.watchdog_interval_s,
            stall_timeout_s=cfg.blackbox.stall_timeout_s,
        )
    # arm the continuous profiler: config [profile] hz wins, then the
    # inherited PS_PROFILE (env-armed at import; re-configured here so
    # the dump carries a role-rank name) — ISSUE 13
    from parameter_server_tpu.utils import profiler, timeseries

    prof_hz = cfg.profile.hz if cfg.profile.hz > 0 else profiler.env_hz()
    if prof_hz > 0:
        profiler.configure(
            prof_hz, top_n=cfg.profile.top_n,
            max_depth=cfg.profile.max_depth,
            dump_dir=cfg.profile.dump_dir
            or os.environ.get(profiler.PROFILE_DIR_ENV, ""),
            process_name=f"{role}-{rank}",
        )
    # OpenMetrics scrape endpoint: [timeseries] metrics_port (or the
    # inherited PS_METRICS_PORT) is the BASE port; each role-rank binds
    # a deterministic offset so one host's processes never collide
    mbase = cfg.timeseries.metrics_port or int(
        os.environ.get(timeseries.METRICS_PORT_ENV, "0") or 0
    )
    # size this node's local delta ring (fed by each beat's
    # beat_telemetry roll; served windowed by /healthz)
    timeseries.reset_local_ring(cfg.timeseries.capacity)
    msrv = roller = None
    if mbase > 0:
        msrv = timeseries.start_metrics_server(
            mbase + metrics_offset, process_name=f"{role}-{rank}",
            host=cfg.timeseries.metrics_host,
            window_s=cfg.timeseries.window_s,
        )
        if role == "scheduler":
            # servers/workers roll the local ring on every beat; the
            # scheduler never beats, so without this its /healthz
            # window would stay empty forever and read as a wedged node
            roller = timeseries.Roller(cfg.fault.heartbeat_interval_s)
    # audit plane (ISSUE 14): the scheduler has no heartbeat reporter,
    # so its own spool (SSP clock movements, control rpc.reply acks) is
    # drained inline by the coordinator's audit pass — arm it here, with
    # the same role gate the _Beats path applies on servers/workers
    armed_spool = False
    if role == "scheduler" and cfg.audit.enabled:
        flightrec.configure_spool(
            cfg.audit.spool_capacity, cfg.audit.batch_events
        )
        armed_spool = True
    try:
        if role == "scheduler":
            host, port = scheduler.rsplit(":", 1)
            coord = Coordinator(
                host, int(port),
                heartbeat_timeout_s=cfg.fault.heartbeat_timeout_s,
                fault_plan=_plan_from_cfg(cfg),
                slo_cfg=cfg.slo,
                series_capacity=cfg.timeseries.capacity,
                series_window_s=cfg.timeseries.window_s,
                audit_cfg=cfg.audit,
            )
            return run_scheduler(cfg, coord, num_servers, num_workers, model_out)
        if role == "server":
            run_server(
                cfg, scheduler, rank, num_servers,
                bind_host=bind_host, advertise_host=advertise_host,
                ckpt_dir=ckpt_dir,
            )
            return None
        run_worker(cfg, scheduler, rank, num_servers)
        return None
    finally:
        if roller is not None:
            roller.close()
        if msrv is not None:
            msrv.close()
        if armed_spool:
            flightrec.configure_spool(None)
