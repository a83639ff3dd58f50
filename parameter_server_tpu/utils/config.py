"""Typed configuration (reference analog: gflags + protobuf text-format configs).

The reference splits config in two tiers (ref: src/main.cc gflags for
topology; src/app/linear_method/proto/linear_method.proto for the app).
Here the same inventory of fields lives in dataclasses, loadable from
JSON or TOML. Field names are kept close to the reference's proto fields
(``minibatch``, ``max_delay``, ``lambda_l1`` ...) so parity is auditable.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any


@dataclass
class DataConfig:
    """Ref: linear_method.proto DataConfig {format, file, ignore_feature_group}."""

    files: list[str] = field(default_factory=list)
    format: str = "libsvm"  # libsvm | criteo | adfea | rating | sgns | cache
    num_keys: int = 1 << 22  # dense hashed key-space size (power of two + pad row)
    val_files: list[str] = field(default_factory=list)
    max_nnz_per_example: int = 512
    cache_dir: str = ""  # columnar block cache (ref: SlotReader cache)
    # frequency-filter admission (ref: parameter/frequency_filter.h): only
    # keys seen >= this many times enter batches; 0 disables. Sketch
    # geometry comes from the [sketch] section. Applies to the streaming
    # (SGD/FTRL) ingest; eval always sees all keys (unadmitted ones simply
    # carry zero weight).
    freq_min_count: int = 0
    # host input pipeline depth (ref: learner/sgd.h parser threads +
    # threadsafe queues): bound of the prefetch queues feeding the SPMD
    # dispatch loop; 0 builds batches serially inline (debugging)
    pipeline_depth: int = 2
    # bucketed static shapes (TPU idiom): pad batch entry/unique arrays to
    # the next power of two above the real count instead of the
    # max_nnz_per_example worst case — host->device bytes track actual
    # density; jit compiles once per bucket (a handful of shapes).
    # Default ON; its gain on the chip is not measured (ROADMAP S1)
    bucket_nnz: bool = True
    # feature-value dtype on the host->device wire: "f32" (exact, default)
    # or "f16" — half the value bytes; IEEE round-to-nearest quantization,
    # cast back to f32 on-device before compute (the reference's
    # fixing_float filter applied to the H2D feed instead of the
    # server wire). Binary/one-hot features (criteo cats, adfea) are
    # exactly representable; log1p-scaled ints lose <0.1% relative.
    wire_values: str = "f32"


@dataclass
class LearningRateConfig:
    """Ref: learning_rate.h — alpha/beta as in the FTRL paper."""

    alpha: float = 0.1
    beta: float = 1.0
    eta: float = 0.1  # plain SGD step size
    decay: float = 0.0


@dataclass
class PenaltyConfig:
    """Ref: penalty.h — elastic net."""

    lambda_l1: float = 1.0
    lambda_l2: float = 0.0


@dataclass
class SolverConfig:
    """Ref: linear_method.proto solver settings (sgd/ftrl/darlin)."""

    algo: str = "ftrl"  # ftrl | adagrad | sgd | darlin
    minibatch: int = 4096
    max_delay: int = 0  # SSP bounded delay tau; 0 => BSP, <0 => fully async
    # microsteps scanned per device call (TPU idiom for the reference's
    # bounded-delay pipelining of many small Push/Pull tasks): K > 1 runs K
    # SEQUENTIAL parameter-server steps inside one jitted program — one
    # host->device transfer, one dispatch, one retirement per K steps —
    # amortizing the per-call round-trip floor that dominates on
    # dispatch-bound hosts. Same trajectory as K single-step calls;
    # max_delay then counts device CALLS in flight (each K steps deep).
    # Honored by the linear_method path (PodTrainer) and the word2vec and
    # matrix_fac apps (steps_per_call=..., wired from this field by the CLI).
    steps_per_call: int = 1
    epochs: int = 1
    # darlin-only:
    block_iters: int = 20
    feature_blocks: int = 16
    # distributed darlin data residency: 0 keeps all packed blocks in HBM
    # (device_put once); C > 0 streams C blocks at a time from the block
    # cache (bounded memory; ref: SlotReader streams per block)
    block_chunk: int = 0
    kkt_filter_threshold: float = 0.0  # 0 disables the KKT filter
    epsilon: float = 1e-4  # relative-objective stopping rule


@dataclass
class GraphConfig:
    """graph_partition app settings (ref: the graph_partition App config)."""

    num_partitions: int = 8
    balance_penalty: float = 1.0


@dataclass
class MFConfig:
    """matrix_fac app settings (ref: the MF app's config; BASELINE's
    MovieLens parity config). data.files = 'user item rating' text
    (data.format "rating": models.matrix_fac.pod_config sets it, and
    data.num_keys = 1 + num_items + num_users, for PodTrainer)."""

    num_users: int = 1000
    num_items: int = 1000
    rank: int = 64
    eta: float = 0.05
    l2: float = 0.01
    algo: str = "adagrad"  # adagrad | sgd
    batch_size: int = 4096


@dataclass
class W2VConfig:
    """word2vec app settings (ref: BASELINE's SGNS parity config; Mikolov
    et al., arXiv:1310.4546). data.files = whitespace-separated token-id
    text (or .npy), turned into ``sgns`` example files
    (models.word2vec.examples_from_corpus; data.format "sgns":
    models.word2vec.pod_config sets it, and data.num_keys = 1 + 2 x
    vocab_size) and trained by plain SGD, the summed gradient of a
    minibatch applied once a row."""

    vocab_size: int = 1 << 16
    dim: int = 64
    window: int = 2
    negatives: int = 5
    eta: float = 0.025  # word2vec.c's starting rate for skip-gram
    batch_size: int = 8192
    block_tokens: int = 1 << 20


@dataclass
class WDConfig:
    """wide_deep app settings (ref: BASELINE's "Wide-&-Deep CTR with
    100M-row embedding table" parity config). The wide half reuses the
    [lr]/[penalty] FTRL hyperparameters; fields here shape the deep half.
    data.files = criteo/libsvm/adfea text like the linear app."""

    emb_dim: int = 16
    hidden: list[int] = field(default_factory=lambda: [32, 16])
    emb_eta: float = 0.05  # AdaGrad step for the embedding table
    mlp_lr: float = 1e-3  # Adam step for the dense MLP


@dataclass
class DLRMConfig:
    """dlrm app settings (Naumov et al., arXiv:1906.00091; the MLPerf
    recommendation benchmark's flags where they have one:
    --arch-sparse-feature-size, --arch-mlp-bot, --arch-mlp-top,
    --learning-rate a summed example). data.files = criteo text, read in the
    per-field layout (``models.dlrm.pod_config`` sets data.format and
    data.num_keys = 1 + 13 + sum(field_rows) for PodTrainer)."""

    emb_dim: int = 128
    # the bottom MLP's layers behind the 13 dense columns; its last is emb_dim
    bot: list[int] = field(default_factory=lambda: [512, 256, 128])
    # the top MLP's layers behind the interaction's output; its last is 1
    top: list[int] = field(default_factory=lambda: [1024, 1024, 512, 256, 1])
    # plain SGD on the summed gradient, both halves, constant. MLPerf's 24.0
    # on the mean of 55,296 is 4.34e-4 a summed example BEHIND a warm-up:
    # held constant from the first step it diverges at these widths inside
    # the first call, and 1e-4 wrecked one seed of thirty-one within 1,200
    # steps; 5e-5 held there, and the default is half of that
    eta: float = 2.5e-5
    # rows of each categorical column's table: min(cardinality, max_ind_range)
    field_rows: list[int] = field(default_factory=list)
    # What the multi-hot form differs by (MLPerf Training's DLRM-DCNv2 since
    # v3.0: --multi_hot_sizes, --dcn_num_layers, --dcn_low_rank_dim, --adagrad).
    # ids an example carries in each column: an id stands for a fixed bag of
    # that many rows of its table, summed (data.libsvm.CriteoBags); all 1 is
    # the one-hot form
    hot: list[int] = field(default_factory=lambda: [1] * 26)
    # layers of the low-rank cross network (Wang et al., arXiv:2008.13535) in
    # the pairwise dots' place; 0 keeps the dots
    cross_layers: int = 0
    cross_rank: int = 512
    # both halves' rule, at ``eta``: "sgd" | "adagrad" (n from 0, eps outside
    # the root: kv.updaters.Adagrad)
    updater: str = "sgd"
    eps: float = 1e-8


@dataclass
class SketchConfig:
    """sketch app settings (ref: the sketch App — distributed count-min)."""

    width: int = 1 << 20
    depth: int = 4
    min_count: int = 2  # heavy-hitter admission threshold


@dataclass
class FilterConfig:
    """Ref: the per-task FilterConfig protos (src/filter/). On-pod traffic
    needs none of these (static layouts over ICI); they apply to the
    cross-process wire tier (parallel/control, parallel/multislice)."""

    key_caching: bool = True  # ref: filter/key_caching.h signatures
    compressing: bool = False  # ref: filter/compressing.h (zlib here)
    fixing_float_bytes: int = 0  # ref: filter/fixing_float.h; 0 off, 1|2 bytes


@dataclass
class WireConfig:
    """Async pipelined RPC data plane (parallel/control.py): the wire-tier
    analog of the reference's bounded per-connection send window."""

    # in-flight seq-numbered requests per RpcClient connection; 1 restores
    # the old lockstep request-reply discipline
    window: int = 8
    # bound on whole STEPS of in-flight pushes a wire-tier worker may hold
    # before blocking (run_worker's PushWindow); 0 derives the bound purely
    # from solver.max_delay, so SSP semantics alone shape the window
    max_inflight_pushes: int = 0
    # derive the EFFECTIVE in-flight window from the client latency
    # histograms at runtime (shrink on p99 blowup, grow back while healthy
    # and saturated); ``window`` stays the hard ceiling. Off by default:
    # a fixed window is deterministic and the adaptation is a tail-latency
    # guard, not a throughput feature.
    adaptive_window: bool = False
    # RPC header codec: "bin" (versioned fixed-layout binary header,
    # negotiated per connection — a peer that never confirms binary
    # support keeps receiving JSON) or "json" (wire format of PRs 0-3,
    # always understood)
    hdr_codec: str = "bin"
    # quantized push transport (filters/quant.py): "off" sends float32
    # gradients; "int8"/"int16" sends per-segment-scale quantized
    # payloads with client-side error-feedback accumulators folding each
    # push's quantization residual into the next. Negotiated per
    # connection (the _feat advert, like the binary-header _bh): against
    # a server that never acks quant support the client transparently
    # stays on the float path — mixed clusters degrade, never corrupt.
    quant: str = "off"
    # quantizer segment length: one float32 scale rides the wire per this
    # many gradient coordinates (256 => ~1.6% scale overhead on int8)
    quant_seg: int = 256
    # also quantize PULL replies (read-mostly/serving traffic): the
    # server encodes the requested rows at the negotiated width. Off by
    # default — pulls have no error-feedback loop, so this trades exact
    # weight reads for wire bytes and belongs to serving tiers, not
    # training convergence paths.
    quant_pull: bool = False


@dataclass
class ServerConfig:
    """Shard-server batched apply engine (parallel/multislice.py): a
    dedicated apply thread drains a bounded queue of decoded pushes and
    coalesces everything concurrently arrived into ONE segment-summed
    updater apply, while pulls serve from an RCU-published snapshot."""

    # bound of the decoded-push apply queue; 0 disables the engine
    # entirely (pushes apply inline under the write lock — the serial
    # pre-engine discipline, kept as the baseline it is compared with)
    apply_queue: int = 256
    # max pushes coalesced into one updater apply
    max_batch: int = 64
    # scale the EFFECTIVE batch ceiling to the observed arrival rate
    # instead of always draining up to max_batch: the ceiling doubles
    # while batches fill and the queue stays hot, halves when arrivals
    # go sparse (adaptations counted in ``server_batch_adapts``).
    # ``max_batch`` stays the hard ceiling.
    adaptive_batch: bool = False
    # reply-coalescing lane bounds, in withheld frames per connection:
    # control replies (the hi lane) flush at lane_hi, bulk pull/push
    # replies (the lo lane) at lane_lo
    lane_hi: int = 4
    lane_lo: int = 16
    # byte bound on withheld coalesced replies per connection: pull
    # replies pin their row arrays while withheld, so the lo lane also
    # flushes once this many MiB accumulate
    withheld_max_mb: int = 8


@dataclass
class ServeConfig:
    """Online serving plane (read-mostly pull traffic): client-side
    versioned key caching inside ``ServerHandle`` (generalizing the
    reference's key-cache filter to VALUES), server-side single-flight
    pull-encode coalescing, and admission control that sheds cache-backed
    pulls before the apply engine starves. Servers always speak the
    protocol (versions + not-modified replies cost nothing); the CLIENT
    cache arms only on handles constructed with ``serving=True`` AND
    ``cache = true`` — the training tier always bypasses it, because a
    trainer's staleness is bounded by the SSP clock, not a TTL."""

    # arm the client-side versioned key cache on serving handles
    cache: bool = False
    # serve a cached entry locally (no wire traffic at all) while younger
    # than this; past it the entry revalidates with an if_newer pull
    # (a not-modified reply re-arms the TTL without moving row bytes)
    ttl_ms: int = 50
    # HARD staleness ceiling: a shed revalidation may keep serving the
    # cached entry only while it is younger than this — past it the
    # client withholds shed_ok and the server must serve real rows, so
    # no client ever observes staleness beyond max(ttl, max_stale)
    max_stale_ms: int = 500
    # cached key-set entries per handle (LRU; invalidation is exact, so
    # eviction is a perf knob, never a correctness one)
    cache_entries: int = 1024
    # server: a key-set signature becomes HOT (its encoded pull reply is
    # cached and shared single-flight across clients at one version)
    # after this many pulls; higher keeps one-off training sweeps out of
    # the encode cache
    hot_min_pulls: int = 2
    # server: encoded-reply cache entries (per (sig, version, codec));
    # 0 disables pull coalescing entirely
    encode_cache_entries: int = 256
    # byte bound on the encoded-reply cache (each entry pins its reply
    # payload arrays): LRU-evicts past this many MiB, so a training
    # server with multi-MB pulls can't pin entries x payload of memory
    # for encodes that version churn will never let it reuse
    encode_cache_mb: int = 64
    # server: materialize a full host weights snapshot per version (the
    # serving read path: hot pulls become numpy fancy-indexing instead
    # of per-request jax dispatch) only while the shard's key range is
    # within this bound — a huge training shard must never pay a
    # full-table device->host sync for one read. 0 disables snapshots.
    snapshot_keys_max: int = 1 << 22
    # admission control: shed cache-backed pulls (the client advertised a
    # fallback via shed_ok) once the apply queue is this deep; 0 off
    shed_queue_depth: int = 0
    # ... or once this server's withheld coalesced-reply bytes (the lo
    # lane pinning pull payloads) cross this many MiB; 0 off
    shed_withheld_mb: int = 0
    # rides shed replies: how long the client should serve its cached
    # entry before revalidating again
    retry_after_ms: int = 20


@dataclass
class ParallelConfig:
    """Mesh topology: the TPU analog of -num_servers / -num_workers."""

    kv_shards: int = 1  # 'kv' mesh axis: range-sharded state (servers)
    data_shards: int = 1  # 'data' mesh axis: example shards (workers)
    # "per_worker": each worker's push is its own server updater step
    # (reference semantics); "aggregate": pre-sum grads across workers with
    # one psum and update once (exact for linear SGD); "quantized":
    # per_worker semantics with int8 grads on the wire (stochastic
    # rounding; the fixing_float filter as a quantized collective for
    # DCN-limited pods). See parallel/spmd.py.
    push_mode: str = "per_worker"


@dataclass
class MeshConfig:
    """Transport-neutral client data plane (parallel/backend.py): which
    KV backend apps written against ``PSBackend`` bind to. "socket" is
    the cross-process wire tier (ShardServer + ServerHandle, every
    filter/recovery feature of PRs 1-7); "mesh" is the in-mesh GSPMD
    tier (parallel/meshbackend.py) — the KV table is one NamedSharding-
    sharded array over the kv axis and push/pull lower to collectives
    over ICI instead of loopback sockets. Rule of thumb: co-located
    workers+servers in ONE JAX process mesh want "mesh"; anything
    crossing a process/DCN boundary stays "socket"."""

    backend: str = "socket"  # socket | mesh
    # kv-axis width of the mesh backend's table sharding; 0 = every
    # local device (the whole-host mesh)
    kv_shards: int = 0
    # quantized push collective (filters/quant.py fused into the sharded
    # update, EQuARX-style): "off" moves f32 gradients onto the mesh;
    # "int8"/"int16" move per-segment-scale integer payloads with the
    # client error-feedback residual preserved (the PR-6 win surviving
    # the transport change)
    quant: str = "off"
    # quantizer segment length (one f32 scale per this many coordinates)
    quant_seg: int = 256


@dataclass
class FaultConfig:
    """Failure detection / recovery knobs for the multi-process tier
    (ref: heartbeat_info + the scheduler's dead-node handling)."""

    heartbeat_interval_s: float = 2.0  # node -> scheduler beat cadence
    heartbeat_timeout_s: float = 10.0  # overdue beats mark a node dead
    straggler_reassign_s: float = 0.0  # age-based workload requeue; 0 off
    startup_grace_s: float = 60.0  # rank never registered by then => dead
    # server recovery (ref: checkpoint-based hot recovery; SURVEY §5.3/§5.4):
    server_ckpt_interval_s: float = 0.0  # periodic range dumps; 0 off
    # dead server: 0 = fail fast (unrecoverable); > 0 = tolerate this many
    # seconds for a relaunched server to re-register from its checkpoint
    server_restart_grace_s: float = 0.0
    reconnect_timeout_s: float = 60.0  # worker retry window per lost server
    # coordinator recovery sweep: dead workers' shards requeued + SSP clock
    # retired every this many seconds (0 disables the sweep thread)
    recovery_sweep_interval_s: float = 0.5
    # fault injection (parallel/chaos.py): a FaultPlan spec armed on every
    # RpcServer this config spawns (coordinator + shard servers); "" = off.
    # The PS_FAULT_PLAN / PS_FAULT_SEED env vars arm the same plans on
    # processes this config never reaches (spawned children).
    fault_plan: str = ""
    fault_seed: int = 0


@dataclass
class TraceConfig:
    """Distributed tracing (utils/trace.py). ``trace_dir`` arms span
    capture + Chrome trace-event export (open in Perfetto) on every
    process this config reaches; the ``PS_TRACE_DIR`` env var arms
    processes the config never touches (spawned children — the
    PS_FAULT_PLAN inheritance pattern)."""

    trace_dir: str = ""  # "" = tracing disabled (the free no-op path)
    capacity: int = 65536  # span ring-buffer bound per process
    # head-based trace sampling: record 1/N of TRACES (not spans), keyed
    # off the trace id so the decision is consistent for every span of
    # one logical operation across every process it touches — always-on
    # tracing at production step rates keeps whole traces, never
    # fragments. 1 (default) records everything.
    sample: int = 1
    # tail-biased capture (ISSUE 15): head-dropped traces buffer until
    # completion and PROMOTE past the sampler when they land in the
    # slowest-K per cmd, carry anomaly events, or breach the live
    # windowed p99 — so `sample = N` keeps exactly the traces a tail-
    # latency investigation needs. On by default wherever tracing is
    # armed (run_node / the train path); disable to get the pure
    # head-sampled stream back.
    tail: bool = True
    tail_k: int = 4  # slowest-K retained per root-span name per window
    tail_limbo: int = 8192  # limbo ring bound (events) for the sidecar


@dataclass
class BlackboxConfig:
    """Black-box flight recorder + stall watchdog + postmortem dumps
    (utils/flightrec.py). ``dir`` arms the always-on ring recorder and
    the per-process watchdog on every process this config reaches; the
    ``PS_BLACKBOX_DIR`` env var arms processes the config never touches
    (spawned children — the PS_FAULT_PLAN / PS_TRACE_DIR pattern).
    Dumps land as ``blackbox-<role>-<rank>-<pid>.json`` for
    ``cli postmortem <dir>`` to merge."""

    dir: str = ""  # "" = disabled (the identity-pinned no-op path)
    capacity: int = 4096  # event ring bound per process
    # periodic re-dump cadence: what a SIGKILL'd process leaves behind
    # is at most this stale; 0 disables the flusher (trigger dumps only)
    flush_interval_s: float = 1.0
    # watchdog sampling cadence and the no-progress-while-busy window
    # after which a registered source (apply engine, SSP clock, pipeline
    # reader, heartbeat thread) is declared stalled and dumped
    watchdog_interval_s: float = 1.0
    stall_timeout_s: float = 30.0


@dataclass
class TimeseriesConfig:
    """Live cluster time series (utils/timeseries.py): every node keeps a
    bounded ring of timestamped telemetry DELTAS (counter rates + exact
    bucket-wise histogram deltas -> windowed p50/p99), fed from the same
    ``telemetry_snapshot()`` roll the heartbeats piggyback; the
    coordinator retains each node's beat stream in its own ring, which is
    what ``cli top`` and the ``[slo]`` burn-rate engine read."""

    # ring entries retained per node (~30 min of history at the default
    # 5 s heartbeat cadence)
    capacity: int = 360
    # default dashboard window (cli top / the telemetry command's
    # windowed rates + percentiles)
    window_s: float = 60.0
    # OpenMetrics scrape endpoint (/metrics + /healthz, stdlib HTTP):
    # 0 disables; > 0 is the BASE port — the scheduler binds it exactly,
    # server rank r binds base+1+r, worker rank r binds
    # base+1+num_servers+r, so one host's processes never collide. The
    # PS_METRICS_PORT env var arms processes the config never reaches.
    metrics_port: int = 0
    # scrape bind address: the loopback default only serves same-host
    # scrapers; set "0.0.0.0" for an off-host Prometheus (the endpoint
    # is unauthenticated read-only telemetry — bind wide deliberately)
    metrics_host: str = "127.0.0.1"


@dataclass
class ProfileConfig:
    """Continuous sampling profiler (utils/profiler.py): a daemon thread
    samples ``sys._current_frames()`` at ``hz``, folds stacks, and the
    top-N hot stacks ride the heartbeat telemetry piggyback. Disarmed
    (hz=0) it follows the flightrec discipline: the module-level
    ``top_stacks`` is an identity-pinned no-op and no thread exists.
    The ``PS_PROFILE`` env var (a rate in Hz, or 1/true/on for the
    default rate) arms processes the config never reaches."""

    hz: float = 0.0  # sampling rate; 0 = profiler off
    top_n: int = 5  # hot stacks piggybacked per heartbeat
    max_depth: int = 24  # frames kept per folded stack
    # write prof-<name>-<pid>.collapsed (flamegraph/speedscope input) and
    # a Perfetto-loadable .trace.json here at process exit / dump()
    dump_dir: str = ""


@dataclass
class AuditConfig:
    """Live audit plane (ISSUE 14): nodes spool audit-relevant
    flight-recorder events (utils/flightrec.py ``EventSpool``) and ship
    them as sequence-numbered batches on the heartbeat piggyback; the
    coordinator streams them through the shared protocol monitors
    (analysis/monitors.py via utils/auditor.py) — the LIVE incarnation
    of the invariants psmc proves offline (exactly-once pushes, RCU
    version monotonicity, SSP staleness, heal convergence, shed storms).
    Violations fire ``audit.violation`` flight-recorder events, bump
    ``audit_violations`` (the dormant-until-violated ``[slo]`` hook),
    and surface in ``cli top`` and ``cli audit``."""

    enabled: bool = True
    # node-side event spool bound; a full spool drops NEW events and
    # counts them (``audit_spool_dropped``) — the auditor reads the
    # drop watermark and suppresses verdicts over holed windows
    spool_capacity: int = 4096
    # events per drained batch (a beat carries up to 4 batches)
    batch_events: int = 512
    # pairing window: an acked push whose apply.commit has not been
    # seen this many seconds after the ack arrived is a violation
    # (must comfortably exceed the heartbeat interval — the commit
    # rides the SERVER's next beat)
    watermark_s: float = 15.0
    # a heal.begin with no rpc.healed after this long is a violation
    heal_timeout_s: float = 30.0
    # shed-storm detector: >= n sheds within window_s
    shed_storm_n: int = 10
    shed_storm_window_s: float = 1.0
    # recent violations retained for cli audit / cli top panels
    recent: int = 256


@dataclass
class SloConfig:
    """Declarative SLO rules (utils/slo.py), evaluated as multi-window
    burn rates over each node's time-series ring at the coordinator.

    Rule grammar, one string per rule::

        <name> <kind>:<series> <= <threshold> [target <frac>] [burn <x>]

    ``kind`` is ``rate`` (counter delta per second), ``p50`` or ``p99``
    (windowed histogram percentile — milliseconds for latency series,
    raw values for ``.n`` count series). A window's error budget is
    ``1 - target`` (default 0.99); an alert fires when the budget burns
    at >= ``burn``x (default 10) over BOTH the short and the long
    window, once per episode (it re-arms only after both windows
    recover). ``replication_lag_s`` is declared but has no emitter yet —
    it is the reserved health signal for chain replication (ROADMAP
    direction #1); a series with no data never burns."""

    rules: list[str] = field(default_factory=lambda: [
        "push_p99_ms p99:server.push <= 250",
        "shed_rate rate:serve_shed <= 10",
        "stall_count rate:watchdog_stalls <= 0",
        "ssp_blocked_ms rate:ssp_blocked_ms <= 500",
        "apply_queue_depth p99:server.apply_queue.n <= 192",
        "replication_lag_s p99:replication_lag_s <= 1",
        # freshness plane (ISSUE 17): realized data age of client
        # serves (server-measured _age_us echo + local cache dwell) and
        # realized SSP staleness at the gate. Both are dormant until a
        # freshness-armed serve/gate emits the series — the shipped
        # thresholds are the paper's serving-tier defaults (age under a
        # second; lag within the configured bound's usual allowance)
        "pull_age_ms p99:serve.age_s <= 1000",
        "ssp_lag_clocks p99:ssp.lag_clocks.n <= 8",
        # the audit plane's alert hook (ISSUE 14): the coordinator bumps
        # audit_violations in its own ring, so a sustained violation
        # stream pages through the same burn-rate machinery; a clean
        # cluster's rate is exactly 0 and the rule never burns
        "audit_violations rate:audit_violations <= 0 target 0.9 burn 1",
    ])
    short_window_s: float = 60.0
    long_window_s: float = 300.0


@dataclass
class PSConfig:
    """Top-level app config (ref: linear_method.proto LinearMethodConfig)."""

    app: str = "linear_method"
    data: DataConfig = field(default_factory=DataConfig)
    lr: LearningRateConfig = field(default_factory=LearningRateConfig)
    penalty: PenaltyConfig = field(default_factory=PenaltyConfig)
    solver: SolverConfig = field(default_factory=SolverConfig)
    filter: FilterConfig = field(default_factory=FilterConfig)
    graph: GraphConfig = field(default_factory=GraphConfig)
    sketch: SketchConfig = field(default_factory=SketchConfig)
    mf: MFConfig = field(default_factory=MFConfig)
    w2v: W2VConfig = field(default_factory=W2VConfig)
    wd: WDConfig = field(default_factory=WDConfig)
    dlrm: DLRMConfig = field(default_factory=DLRMConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    wire: WireConfig = field(default_factory=WireConfig)
    server: ServerConfig = field(default_factory=ServerConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    fault: FaultConfig = field(default_factory=FaultConfig)
    trace: TraceConfig = field(default_factory=TraceConfig)
    blackbox: BlackboxConfig = field(default_factory=BlackboxConfig)
    timeseries: TimeseriesConfig = field(default_factory=TimeseriesConfig)
    profile: ProfileConfig = field(default_factory=ProfileConfig)
    slo: SloConfig = field(default_factory=SloConfig)
    audit: AuditConfig = field(default_factory=AuditConfig)
    model_output: str = ""
    report_interval: int = 1  # progress print cadence, in reports (ref gflag)
    seed: int = 0


def _from_dict(cls: type, d: dict[str, Any]) -> Any:
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(d) - known
    if unknown:
        raise ValueError(
            f"unknown {cls.__name__} field(s) {sorted(unknown)}; known: {sorted(known)}"
        )
    kwargs = {}
    for f in dataclasses.fields(cls):
        if f.name not in d:
            continue
        v = d[f.name]
        if f.name in _NESTED:
            if not isinstance(v, dict):
                raise TypeError(
                    f"config section '{f.name}' must be a table/object, got {type(v).__name__}"
                )
            kwargs[f.name] = _from_dict(_NESTED[f.name], v)
        else:
            kwargs[f.name] = v
    return cls(**kwargs)


_NESTED = {
    "data": DataConfig,
    "lr": LearningRateConfig,
    "penalty": PenaltyConfig,
    "solver": SolverConfig,
    "filter": FilterConfig,
    "graph": GraphConfig,
    "sketch": SketchConfig,
    "mf": MFConfig,
    "w2v": W2VConfig,
    "wd": WDConfig,
    "dlrm": DLRMConfig,
    "parallel": ParallelConfig,
    "mesh": MeshConfig,
    "wire": WireConfig,
    "server": ServerConfig,
    "serve": ServeConfig,
    "fault": FaultConfig,
    "trace": TraceConfig,
    "blackbox": BlackboxConfig,
    "timeseries": TimeseriesConfig,
    "profile": ProfileConfig,
    "slo": SloConfig,
    "audit": AuditConfig,
}


def toml_module():
    """The tomllib import ladder, shared with pslint's ``[tool.pslint]``
    loader (analysis/core.py): stdlib tomllib (python >= 3.11), the
    tomli upstream, then — last resort on dep-frozen 3.10 images — pip's
    vendored copy; prefer a fragile import to losing .toml support."""
    try:
        import tomllib  # stdlib, python >= 3.11
    except ModuleNotFoundError:
        try:
            import tomli as tomllib  # the stdlib module's upstream
        except ModuleNotFoundError:
            from pip._vendor import tomli as tomllib
    return tomllib


def load_config(path: str | Path) -> PSConfig:
    """Load a PSConfig from a .json or .toml file."""
    p = Path(path)
    if p.suffix == ".toml":
        d = toml_module().loads(p.read_text())
    else:
        d = json.loads(p.read_text())
    return _from_dict(PSConfig, d)


def config_to_dict(cfg: PSConfig) -> dict[str, Any]:
    return dataclasses.asdict(cfg)
