"""Static-shape CSR minibatches + the localizer.

Reference analog: src/app/linear_method/localizer.h — per block/minibatch,
``unique`` the touched global keys and remap entries to dense local ids so
the compute kernel works on a small dense index space; the unique key list
is what Pull/Push are issued against.

TPU twist: every batch is padded to static (B, NNZ, U) so one compiled
program serves the whole stream. Padding contract (see kv.store):
  - ``unique_keys[0] == PAD_KEY (0)`` always; ``unique_keys[1:num_unique]``
    are the real keys, strictly ascending (``np.unique``'s order; a real
    key is never 0); the tail ``unique_keys[num_unique:]`` repeats
    PAD_KEY. Every grow path (``zero_extend``) and ``inert_like`` keep
    this, and the step's push rests on it: it tells XLA that the rows it
    scatters to ascend (parallel.spmd ``_local_push``), which a key list in
    any other order would make undefined behaviour on the chip.
  - padded CSR entries have ``value == 0`` and point at unique slot 0, row 0.
  - padded example rows have ``label == 0`` and ``example_mask == False``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.hashing import PAD_KEY, hash_keys


@dataclass
class CSRBatch:
    """One device-ready minibatch. All arrays have static shapes.

    ``unique_keys`` is int32 whenever num_keys fits (practically always)
    and ``row_splits`` carries the same row structure as ``row_ids`` in
    B+1 ints instead of NNZ — together the batch wire of the pod path
    (parallel.spmd CSR_FIELDS): ``row_ids`` stays on the host, for the
    host-side consumers, and the device rebuilds it by marking the
    splits and summing along the entries. The reference ships raw int64
    keys + per-entry row ids over ZeroMQ and leans on its filter pipeline
    instead (src/filter/); here the transfer layout itself is the
    filter."""

    unique_keys: np.ndarray  # (U,) int32/int64 — hashed global ids, slot 0 = pad
    local_ids: np.ndarray  # (NNZ,) int32 — entry -> unique slot
    row_ids: np.ndarray  # (NNZ,) int32 — entry -> example row
    values: np.ndarray  # (NNZ,) float32
    labels: np.ndarray  # (B,) float32 in {0, 1}
    example_mask: np.ndarray  # (B,) bool
    row_splits: np.ndarray  # (B+1,) int32 — cumulative real entries per row
    num_examples: int
    num_unique: int  # real unique keys (including pad slot 0)
    num_entries: int

    @property
    def shape(self) -> tuple[int, int, int]:
        return (len(self.labels), len(self.values), len(self.unique_keys))

    def keys_in_order(self) -> bool:
        """The key half of the padding contract above (``PAD_KEY``,
        strictly ascending real keys, ``PAD_KEY`` to the end): what the
        step's push promises XLA. For an ``assert`` where batches from any
        builder enter the trainer; about 0.1 ms at 2^19 slots."""
        keys, n = self.unique_keys, self.num_unique
        return bool(
            keys[0] == PAD_KEY
            and (keys[1:n] != PAD_KEY).all()
            and (keys[2:n] > keys[1 : n - 1]).all()
            and not keys[n:].any()
        )


def training_builder(cfg, key_mode: str = "hash") -> "BatchBuilder":
    """The training-ingest builder for a PSConfig: wires the frequency
    filter (cfg.data.freq_min_count + [sketch] geometry) into admission.
    Eval paths build plain BatchBuilders — unadmitted keys carry zero
    weight, so filtering there would be pointless work."""
    freq_filter = None
    if cfg.data.freq_min_count > 0:
        from parameter_server_tpu.filters.frequency import CountMinSketch

        freq_filter = CountMinSketch(cfg.sketch.width, cfg.sketch.depth)
    return BatchBuilder(
        num_keys=cfg.data.num_keys,
        batch_size=cfg.solver.minibatch,
        max_nnz_per_example=cfg.data.max_nnz_per_example,
        key_mode=key_mode,
        freq_filter=freq_filter,
        freq_min_count=cfg.data.freq_min_count,
        bucket_nnz=cfg.data.bucket_nnz,
    )


def eval_builder(cfg, key_mode: str = "hash") -> "BatchBuilder":
    """The evaluation-ingest builder: NO frequency admission. A fresh
    filter would restart every key at count 0 and silently drop entries
    for keys the model actually trained on, skewing val metrics; and
    unadmitted keys carry zero weight anyway, so filtering eval input is
    pointless work either way."""
    return BatchBuilder(
        num_keys=cfg.data.num_keys,
        batch_size=cfg.solver.minibatch,
        max_nnz_per_example=cfg.data.max_nnz_per_example,
        key_mode=key_mode,
        bucket_nnz=cfg.data.bucket_nnz,
    )


# bucketed batches never shrink below this many entries: tiny buckets buy
# nothing and each distinct shape costs one jit compile
BUCKET_FLOOR = 2048


def _nnz_bucket(n: int, cap: int, floor: int = BUCKET_FLOOR) -> int:
    """Smallest power-of-two >= n (>= floor), capped at the static max."""
    b = max(floor, 1 << max(n - 1, 0).bit_length())
    return min(b, cap)


def pad_group(batches: list["CSRBatch"]) -> list["CSRBatch"]:
    """Bring a group of (possibly bucketed) batches to one static shape —
    the group max per dimension (buckets are powers of two, so the set of
    group shapes stays small). Used before stacking D shards."""
    nnz_t = max(len(b.values) for b in batches)
    u_t = max(len(b.unique_keys) for b in batches)
    for b in batches:  # real keys over the slots the device will work on
        trace.counter("feed.unique_fill", b.num_unique / u_t)
    return [pad_batch(b, nnz_t, u_t) for b in batches]


def zero_extend(a: np.ndarray, n: int, axis: int = 0) -> np.ndarray:
    """Zero-pad ``a`` to length ``n`` along ``axis`` — THE inert-padding
    primitive (zeros are inert everywhere by the PAD_KEY == slot 0
    convention); every grow path must come through here so the pad
    sentinel lives in one place."""
    if a.shape[axis] == n:
        return a
    if a.shape[axis] > n:
        raise ValueError(f"cannot shrink axis {axis}: {a.shape[axis]} > {n}")
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, n - a.shape[axis])
    return np.pad(a, pad)


def inert_like(b: CSRBatch) -> CSRBatch:
    """All-zero batch with b's static shapes (mask False, value 0): the
    pad for a partial multistep group or a worker whose stream has dried
    up — zero loss, zero gradient."""
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


def pad_batch(b: CSRBatch, nnz_cap: int, u_cap: int) -> CSRBatch:
    """Re-pad a (possibly bucketed) batch to the given capacities — used
    to bring a group of differently-bucketed batches to one static shape
    before stacking."""
    if len(b.values) == nnz_cap and len(b.unique_keys) == u_cap:
        return b
    if len(b.values) > nnz_cap or len(b.unique_keys) > u_cap:
        raise ValueError(
            f"cannot shrink batch ({len(b.values)}, {len(b.unique_keys)}) "
            f"to ({nnz_cap}, {u_cap})"
        )
    return CSRBatch(
        unique_keys=zero_extend(b.unique_keys, u_cap),
        local_ids=zero_extend(b.local_ids, nnz_cap),
        row_ids=zero_extend(b.row_ids, nnz_cap),
        values=zero_extend(b.values, nnz_cap),
        labels=b.labels,
        example_mask=b.example_mask,
        row_splits=b.row_splits,  # fixed (B+1,): counts real entries only
        num_examples=b.num_examples,
        num_unique=b.num_unique,
        num_entries=b.num_entries,
    )


class BatchBuilder:
    """Turns parsed (label, keys, values) rows into CSRBatches.

    key_mode:
      "hash"     — splitmix64 into [1, num_keys) (production path; slots salt)
      "identity" — key+1 used directly (exact parity runs vs sklearn; requires
                   raw keys < num_keys - 1)
    """

    def __init__(
        self,
        num_keys: int,
        batch_size: int,
        max_nnz_per_example: int = 256,
        unique_capacity: int | None = None,
        key_mode: str = "hash",
        freq_filter=None,
        freq_min_count: int = 0,
        bucket_nnz: bool = False,
    ):
        if key_mode not in ("hash", "identity"):
            raise ValueError(f"bad key_mode {key_mode!r}")
        self.num_keys = num_keys
        self.batch_size = batch_size
        self.nnz_capacity = batch_size * max_nnz_per_example
        # +1 for the pad slot; capped at nnz (can't see more uniques than entries)
        self.unique_capacity = unique_capacity or min(
            self.nnz_capacity + 1, num_keys
        )
        self.key_mode = key_mode
        # bucketed static shapes (TPU idiom): pad entry/unique arrays to
        # the next power of two above the REAL count instead of the worst
        # case — host->device bytes track actual density, and jit compiles
        # once per bucket (a handful of shapes), not per batch
        self.bucket_nnz = bucket_nnz
        # streaming admission (ref: parameter/frequency_filter.h — only
        # admit keys seen >= k times; at 10^9-key CTR scale the tail is
        # noise). The sketch counts RAW pre-hash keys as they stream by;
        # entries below the threshold are dropped before localization.
        self.freq_filter = freq_filter
        self.freq_min_count = freq_min_count
        if freq_min_count > 0 and freq_filter is None:
            from parameter_server_tpu.filters.frequency import CountMinSketch

            self.freq_filter = CountMinSketch()

    def build(
        self,
        labels: np.ndarray,
        keys: list[np.ndarray],
        values: list[np.ndarray],
        slot_ids: list[np.ndarray] | None = None,
    ) -> CSRBatch:
        """labels: (b,); keys[i]/values[i]: per-example sparse features."""
        counts = np.array([len(k) for k in keys], dtype=np.int64)
        row_splits = np.zeros(len(labels) + 1, dtype=np.int64)
        np.cumsum(counts, out=row_splits[1:])
        nnz = int(row_splits[-1])
        return self.build_flat(
            np.asarray(labels),
            row_splits,
            np.concatenate(keys) if nnz else np.zeros(0, dtype=np.uint64),
            (
                np.concatenate(values).astype(np.float32)
                if nnz
                else np.zeros(0, dtype=np.float32)
            ),
            np.concatenate(slot_ids) if slot_ids is not None else None,
        )

    def build_flat(
        self,
        labels: np.ndarray,
        row_splits: np.ndarray,
        flat_keys: np.ndarray,
        flat_vals: np.ndarray,
        flat_slots: np.ndarray | None = None,
    ) -> CSRBatch:
        """Vectorized build from flat CSR arrays (the native-parser path)."""
        b = len(labels)
        if b > self.batch_size:
            raise ValueError(f"{b} examples > batch_size {self.batch_size}")
        nnz = int(row_splits[-1])
        if nnz > self.nnz_capacity:
            raise ValueError(f"{nnz} entries > nnz capacity {self.nnz_capacity}")
        flat_vals = np.asarray(flat_vals, dtype=np.float32)
        row_ids = np.repeat(
            np.arange(b, dtype=np.int32), np.diff(row_splits).astype(np.int64)
        )

        splits_src = row_splits  # reusable unless the filter drops entries
        if self.freq_min_count > 0 and nnz:
            # count first (whole batch), then admit: a key is admitted —
            # including all its occurrences WITHIN this batch — once its
            # running count crosses the threshold. Admission is
            # batch-granular, not per-occurrence; occurrences in batches
            # before the crossing are sacrificed (the tail-filtering the
            # reference's frequency filter exists for)
            raw = np.asarray(flat_keys, dtype=np.uint64)
            self.freq_filter.add(raw)
            keep = self.freq_filter.admit(raw, self.freq_min_count)
            flat_keys = raw[keep]
            flat_vals = flat_vals[keep]
            row_ids = row_ids[keep]
            if flat_slots is not None:
                flat_slots = np.asarray(flat_slots)[keep]
            nnz = int(keep.sum())
            splits_src = None  # row structure changed; rederive below

        # Localizer: unique + inverse, with the pad key forced into slot 0
        # (ref: localizer.h). The native kernel fuses hash + sort-unique
        # with the GIL released (builder threads scale across cores); the
        # numpy path below is the exact-parity fallback.
        from parameter_server_tpu.data import native as _native

        nat = (
            _native.hash_localize(
                flat_keys, flat_slots, self.num_keys,
                identity=self.key_mode != "hash",
            )
            if nnz
            else None
        )
        if nat is not None:
            uniq, inverse = nat
        else:
            if self.key_mode == "hash":
                salts = flat_slots if flat_slots is not None else 0
                gids = hash_keys(flat_keys, self.num_keys, slot_ids=salts)
            else:
                gids = np.asarray(flat_keys, dtype=np.int64) + 1
                if nnz and gids.max() >= self.num_keys:
                    raise ValueError(
                        f"identity key {gids.max() - 1} >= num_keys-1; "
                        "grow num_keys or use key_mode='hash'"
                    )
            uniq, inverse = np.unique(gids, return_inverse=True)

        # Keys ride the wire as int32 whenever the key space fits (always,
        # short of a >2^31 dense space) — half the per-unique bytes.
        key_dtype = (
            np.int32 if self.num_keys <= np.iinfo(np.int32).max else np.int64
        )
        n_uniq = len(uniq) + 1  # + the forced PAD row at slot 0
        if n_uniq > self.unique_capacity:
            raise ValueError(
                f"{n_uniq} unique keys > capacity {self.unique_capacity}"
            )

        if self.bucket_nnz:
            nnz_cap = _nnz_bucket(nnz, self.nnz_capacity)
            # the key axis gets a bucket of its own, by the keys the batch
            # holds: repeated keys (Zipf traffic) leave far fewer keys than
            # entries, and every slot is gathered, updated and scattered
            u_cap = _nnz_bucket(
                n_uniq, min(nnz_cap + 1, self.unique_capacity, self.num_keys)
            )
        else:
            nnz_cap = self.nnz_capacity
            u_cap = self.unique_capacity
        # np.empty + explicit pad-tail zeroing, writing each entry ONCE:
        # np.zeros-then-overwrite double-writes the big per-entry arrays
        # (~1.5 MB/batch of pure zeroing at CTR densities), and the +1 /
        # PAD-prepend intermediates each cost another full copy — this
        # assembly glue, not the C localizer, bounds ingest (measured)
        out = CSRBatch(
            unique_keys=np.empty(u_cap, dtype=key_dtype),
            local_ids=np.empty(nnz_cap, dtype=np.int32),
            row_ids=np.empty(nnz_cap, dtype=np.int32),
            values=np.empty(nnz_cap, dtype=np.float32),
            labels=np.zeros(self.batch_size, dtype=np.float32),
            example_mask=np.zeros(self.batch_size, dtype=bool),
            row_splits=np.zeros(self.batch_size + 1, dtype=np.int32),
            num_examples=b,
            num_unique=n_uniq,
            num_entries=nnz,
        )
        out.unique_keys[0] = PAD_KEY
        out.unique_keys[1:n_uniq] = uniq  # downcast copy, no intermediate
        out.unique_keys[n_uniq:] = PAD_KEY
        # local ids shift by one for the PAD row, written straight into
        # the output (int64 numpy-fallback inverses narrow safely: ids
        # are bounded by unique_capacity)
        np.add(inverse, 1, out=out.local_ids[:nnz], casting="unsafe")
        out.local_ids[nnz:] = 0
        out.row_ids[:nnz] = row_ids
        out.row_ids[nnz:] = 0
        out.values[:nnz] = flat_vals
        out.values[nnz:] = 0.0
        out.labels[:b] = np.asarray(labels, dtype=np.float32)
        out.example_mask[:b] = True
        # compact row structure: same information as row_ids in B+1 ints
        # (row_ids over REAL entries is non-decreasing by construction)
        if splits_src is not None:
            out.row_splits[: b + 1] = splits_src  # unfiltered: caller's splits
        elif nnz:
            np.cumsum(
                np.bincount(row_ids, minlength=b), out=out.row_splits[1 : b + 1]
            )
        out.row_splits[b + 1 :] = out.row_splits[b]
        return out
