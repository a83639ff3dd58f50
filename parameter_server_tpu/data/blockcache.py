"""Columnar feature-block layout + disk cache for the batch solver.

Reference analog: src/data/slot_reader.h/.cc — the reference's SlotReader
parses the training text once and caches per-slot column blocks as binary
files in a local cache dir; later passes (and re-runs) read the cache
instead of re-parsing. Same contract here:

  - ``ColumnBlocks`` is the feature-major (CSC-ish) layout the DARLIN
    solver sweeps: entries grouped by contiguous dense-key block, within a
    block ascending by feature, cut into chunks of one fixed length so that
    every shape is static and only a block's last chunk is padded.
  - ``ColumnBlocksBuilder`` builds it shard by shard (one ``add`` a file):
    no array of all entries is made on the host, and with a directory to
    write to the result lies in ``.npy`` files, never in memory whole.
  - ``save_column_blocks`` / ``load_column_blocks`` persist the arrays as
    ``.npy`` files plus a ``meta.json`` stats sidecar carrying a source
    fingerprint (file paths, sizes, mtimes, parse parameters). Loads are
    ``mmap_mode="r"`` so a reload never re-parses text and only pages in
    what a pass touches.
  - ``cached_column_blocks`` orchestrates: fingerprint-hit -> mmap load;
    miss (or no cache dir) -> parse + build (+ save).
"""

from __future__ import annotations

import hashlib
import json
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from parameter_server_tpu.data.batch import CSRBatch

CACHE_VERSION = 2  # 2: fixed-length chunks (1 padded every block to the longest)
_ARRAYS = ("feat_local", "rows", "values", "labels", "chunk_begin", "entries")
ENTRY_ARRAYS = ("feat_local", "rows", "values")
_BUILD_THREADS = 8


def default_chunk_len(total_entries: int, n_blocks: int) -> int:
    """Entries a chunk holds unless the caller says: the power of two that
    keeps the padding (half a chunk a block on average, a whole one at
    worst) under about 1% of the entries, between 8 and 2^17."""
    want = max(total_entries // (64 * max(n_blocks, 1)), 1)
    return int(min(max(1 << (want.bit_length() - 1), 8), 1 << 17))


@dataclass
class ColumnBlocks:
    """Feature-major (CSC-ish) layout of the full training set.

    Entries are grouped by feature block (contiguous ranges of the dense
    key space — the reference picks blocks from slots/feature groups and
    splits hot slots; dense hashed ranges cut into chunks are the TPU
    analog). Block ``b`` owns chunks ``chunk_begin[b] .. chunk_begin[b+1]``
    of the ``(n_chunks, chunk_len)`` entry arrays and ``entries[b]`` real
    entries in them, **ascending by feature** (ties in example order): a
    feature's entries are one run of the entry axis, so a block's sums by
    feature are running sums along it, chunk by chunk (``models.darlin``).
    Only a block's last chunk is padded, with entries of the block's last
    local feature, row 0 and value 0 (inert, and the order stays sorted),
    so the arrays hold the entries within a few percent of their own bytes
    whatever the key skew."""

    feat_local: np.ndarray  # (n_chunks, chunk_len) int32 — gid - block_begin
    rows: np.ndarray  # (n_chunks, chunk_len) int32
    values: np.ndarray  # (n_chunks, chunk_len) float32
    labels: np.ndarray  # (N,) float32
    chunk_begin: np.ndarray  # (n_blocks + 1,) int64
    entries: np.ndarray  # (n_blocks,) int64 — real entries a block
    num_keys: int
    block_size: int
    num_examples: int

    @property
    def n_blocks(self) -> int:
        return len(self.entries)

    @property
    def chunk_len(self) -> int:
        return self.feat_local.shape[1]

    def block(self, b: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(feat_local, rows, values) of block ``b``'s real entries."""
        lo = int(self.chunk_begin[b]) * self.chunk_len
        sl = slice(lo, lo + int(self.entries[b]))
        return tuple(np.asarray(getattr(self, k)).reshape(-1)[sl] for k in ENTRY_ARRAYS)

    @classmethod
    def from_batches(
        cls, batches: list[CSRBatch], num_keys: int, n_blocks: int,
        chunk_len: int | None = None,
    ) -> "ColumnBlocks":
        """Build from CSRBatches (uses their global hashed unique_keys)."""
        builder = ColumnBlocksBuilder(num_keys, n_blocks, chunk_len)
        for b in batches:
            n, e = b.num_examples, b.num_entries
            builder.add(
                b.unique_keys[b.local_ids[:e]], b.row_ids[:e], b.values[:e], b.labels[:n]
            )
        return builder.finish()


class ColumnBlocksBuilder:
    """``ColumnBlocks`` from shards of examples, one ``add`` a shard (a
    file, a batch), in example order. A shard's entries are partitioned by
    block as they come (a radix sort on the block id); ``finish`` then
    assembles block by block, sorting one block's entries by feature at a
    time, into arrays that lie in ``out_dir`` as ``.npy`` files where one is
    given. Nothing here is ever the size of all entries but the result."""

    def __init__(self, num_keys: int, n_blocks: int, chunk_len: int | None = None):
        if num_keys % n_blocks:
            raise ValueError(f"num_keys {num_keys} % n_blocks {n_blocks} != 0")
        self.num_keys, self.n_blocks = num_keys, n_blocks
        self.block_size = num_keys // n_blocks
        self.chunk_len = chunk_len
        self._pieces: list[tuple] = []  # (feat_local, rows, values, block offsets)
        self._labels: list[np.ndarray] = []
        self._n = 0

    def add(self, gids, rows_in_shard, values, labels) -> None:
        """One shard: entry ``i`` is feature ``gids[i]`` of the shard's
        example ``rows_in_shard[i]`` with ``values[i]``."""
        self.add_partitioned(
            partition_by_block(gids, rows_in_shard, values, self.block_size, self.n_blocks),
            labels,
        )

    def add_partitioned(self, piece: tuple, labels) -> None:
        """``add`` of what ``partition_by_block`` made of the shard (on
        another thread, say): only the row offset is applied here."""
        feat, rows, vals, offsets = piece
        if self._n + len(labels) > np.iinfo(np.int32).max:
            raise ValueError("more examples than an int32 row id holds")
        self._pieces.append((feat, rows + np.int32(self._n), vals, offsets))
        self._labels.append(np.asarray(labels, np.float32))
        self._n += len(labels)

    def finish(self, out_dir: str | Path | None = None) -> ColumnBlocks:
        counts = np.zeros(self.n_blocks, np.int64)
        for *_, offsets in self._pieces:
            counts += np.diff(offsets)
        c = self.chunk_len or default_chunk_len(int(counts.sum()), self.n_blocks)
        chunk_begin = np.zeros(self.n_blocks + 1, np.int64)
        np.cumsum(-(-counts // c), out=chunk_begin[1:])
        n_chunks = max(int(chunk_begin[-1]), 1)
        out = {
            k: _new_array(out_dir, k, (n_chunks, c), np.float32 if k == "values" else np.int32)
            for k in ENTRY_ARRAYS
        }
        flat = {k: v.reshape(-1) for k, v in out.items()}

        def assemble(b: int) -> None:
            parts = [
                tuple(a[offsets[b] : offsets[b + 1]] for a in (feat, rows, vals))
                for feat, rows, vals, offsets in self._pieces
            ]
            feat, rows, vals = (np.concatenate(x) for x in zip(*parts))
            order = np.argsort(feat, kind="stable")  # ties stay in example order
            lo, n = int(chunk_begin[b]) * c, int(counts[b])
            end = int(chunk_begin[b + 1]) * c
            flat["feat_local"][lo : lo + n] = feat[order]
            flat["rows"][lo : lo + n] = rows[order]
            flat["values"][lo : lo + n] = vals[order]
            if n:  # the pad keeps the block's order ascending
                flat["feat_local"][lo + n : end] = flat["feat_local"][lo + n - 1]
            flat["rows"][lo + n : end] = 0
            flat["values"][lo + n : end] = 0.0

        if not chunk_begin[-1]:
            for v in flat.values():
                v[:] = 0
        # a block's sort holds about 40 B an entry while it runs: the few
        # blocks with the hot keys one at a time, the many small ones side by side
        big = [b for b in range(self.n_blocks) if counts[b] > 4 * max(counts.mean(), 1)]
        for b in big:
            assemble(b)
        with ThreadPoolExecutor(_BUILD_THREADS) as pool:
            list(pool.map(assemble, [b for b in range(self.n_blocks) if b not in big]))
        self._pieces = []
        labels = np.concatenate(self._labels) if self._labels else np.zeros(0, np.float32)
        return ColumnBlocks(
            **out,
            labels=labels,
            chunk_begin=chunk_begin,
            entries=counts,
            num_keys=self.num_keys,
            block_size=self.block_size,
            num_examples=self._n,
        )


def partition_by_block(gids, rows, values, block_size: int, n_blocks: int) -> tuple:
    """A shard's entries grouped by block, in the order they came within a
    block: (feat_local int32, rows int32, values float32, offsets
    (n_blocks + 1,) of each block's run). The sort key is the block id
    alone, 16 bits where the blocks allow it: NumPy's stable sort of those
    is a radix sort."""
    gids = np.asarray(gids, np.int64)
    blk = gids // block_size
    if len(blk) and (blk.min() < 0 or blk.max() >= n_blocks):
        raise ValueError(f"a key outside [0, {block_size * n_blocks})")
    blk = blk.astype(np.uint16 if n_blocks <= 1 << 16 else np.int64)
    order = np.argsort(blk, kind="stable")
    offsets = np.zeros(n_blocks + 1, np.int64)
    np.cumsum(np.bincount(blk, minlength=n_blocks), out=offsets[1:])
    blk = blk[order].astype(np.int64)
    return (
        (gids[order] - blk * block_size).astype(np.int32),
        np.asarray(rows)[order].astype(np.int32),
        np.asarray(values, np.float32)[order],
        offsets,
    )


def _new_array(out_dir, name: str, shape: tuple, dtype) -> np.ndarray:
    if out_dir is None:
        return np.empty(shape, dtype)
    return np.lib.format.open_memmap(
        Path(out_dir) / f"{name}.npy", mode="w+", dtype=dtype, shape=shape
    )


def source_fingerprint(
    files: list[str],
    fmt: str,
    num_keys: int,
    n_blocks: int,
    max_nnz_per_example: int,
) -> str:
    """Hash of everything that determines the cache contents: source file
    identities (path, size, mtime) + the parse/layout parameters."""
    ident = {
        "version": CACHE_VERSION,
        "fmt": fmt,
        "num_keys": num_keys,
        "n_blocks": n_blocks,
        "max_nnz": max_nnz_per_example,
        "files": [],
    }
    for f in sorted(map(str, files)):
        st = Path(f).stat()  # missing source files are a hard error
        ident["files"].append([f, st.st_size, st.st_mtime_ns])
    return hashlib.sha256(json.dumps(ident).encode()).hexdigest()


def _invalidate(cache_dir: Path) -> None:
    """Before the arrays are touched: a crash mid-write can then never
    leave a valid-looking sidecar over mixed contents."""
    cache_dir.mkdir(parents=True, exist_ok=True)
    (cache_dir / "meta.json").unlink(missing_ok=True)


def _write_meta(cache_dir: Path, cb: ColumnBlocks, fingerprint: str) -> None:
    meta = {
        "version": CACHE_VERSION,
        "fingerprint": fingerprint,
        "num_keys": cb.num_keys,
        "block_size": cb.block_size,
        "num_examples": cb.num_examples,
        "n_blocks": cb.n_blocks,
        "chunk_len": cb.chunk_len,
        "nnz": int(cb.entries.sum()),
    }
    # sidecar written last and atomically: its presence marks a complete
    # cache, so a partial write must never be observable at the final path
    tmp = cache_dir / "meta.json.tmp"
    tmp.write_text(json.dumps(meta, indent=1))
    os.replace(tmp, cache_dir / "meta.json")


def save_column_blocks(cache_dir: str | Path, cb: ColumnBlocks, fingerprint: str) -> None:
    d = Path(cache_dir)
    _invalidate(d)
    for name in _ARRAYS:
        np.save(d / f"{name}.npy", getattr(cb, name))
    _write_meta(d, cb, fingerprint)


def load_column_blocks(
    cache_dir: str | Path, fingerprint: str | None = None
) -> ColumnBlocks | None:
    """mmap-load a cache; None when absent, incomplete, or stale."""
    d = Path(cache_dir)
    meta_path = d / "meta.json"
    if not meta_path.exists():
        return None
    try:
        meta = json.loads(meta_path.read_text())
        if meta.get("version") != CACHE_VERSION:
            return None
        if fingerprint is not None and meta.get("fingerprint") != fingerprint:
            return None
        arrays = {}
        for name in _ARRAYS:
            p = d / f"{name}.npy"
            if not p.exists():
                return None
            arrays[name] = np.load(p, mmap_mode="r" if name in ENTRY_ARRAYS else None)
        return ColumnBlocks(
            **arrays,
            num_keys=meta["num_keys"],
            block_size=meta["block_size"],
            num_examples=meta["num_examples"],
        )
    except (json.JSONDecodeError, KeyError, ValueError, OSError):
        return None  # corrupt/truncated cache == cache miss, rebuild it


def _shard_pieces(path: str, fmt: str, num_keys: int, max_nnz: int, block_size: int, n_blocks: int) -> list:
    """One file through the parser (the native one where it is built) and
    the key hash: [(a parsed chunk's entries partitioned by block, its
    labels)], in the file's order; an example is cut to its first
    ``max_nnz`` entries, the builders' cap."""
    from parameter_server_tpu.data.reader import iter_flat_rows
    from parameter_server_tpu.utils.hashing import hash_keys

    out = []
    for y, splits, keys, v, slots in iter_flat_rows([path], fmt):
        lens = np.diff(splits)
        row = np.repeat(np.arange(len(y), dtype=np.int64), lens)
        if len(lens) and lens.max() > max_nnz:
            keep = np.arange(len(keys)) - np.repeat(splits[:-1], lens) < max_nnz
            keys, v, row = keys[keep], v[keep], row[keep]
            slots = None if slots is None else slots[keep]
        gids = hash_keys(keys, num_keys, 0 if slots is None else slots)
        out.append((partition_by_block(gids, row, v, block_size, n_blocks), y))
    return out


def cached_column_blocks(cfg) -> ColumnBlocks:
    """SlotReader behavior for a PSConfig: reuse ``data.cache_dir`` when its
    fingerprint matches the sources, else parse once, file by file, and
    populate it (the arrays are written where they will be mapped from)."""
    n_blocks = cfg.solver.feature_blocks
    fp = source_fingerprint(
        cfg.data.files,
        cfg.data.format,
        cfg.data.num_keys,
        n_blocks,
        cfg.data.max_nnz_per_example,
    )
    cache_dir = Path(cfg.data.cache_dir) if cfg.data.cache_dir else None
    if cache_dir is not None:
        cb = load_column_blocks(cache_dir, fp)
        if cb is not None:
            return cb
        _invalidate(cache_dir)
    builder = ColumnBlocksBuilder(cfg.data.num_keys, n_blocks)
    files = sorted(map(str, cfg.data.files))
    with ThreadPoolExecutor(_BUILD_THREADS) as pool:
        shards = pool.map(
            lambda f: _shard_pieces(
                f, cfg.data.format, cfg.data.num_keys, cfg.data.max_nnz_per_example,
                builder.block_size, n_blocks,
            ),
            files,
        )
        for pieces in shards:  # in file order: rows number the examples
            for piece, labels in pieces:
                builder.add_partitioned(piece, labels)
    cb = builder.finish(cache_dir)
    if cache_dir is not None:
        for name in ("labels", "chunk_begin", "entries"):
            np.save(cache_dir / f"{name}.npy", getattr(cb, name))
        _write_meta(cache_dir, cb, fp)
    return cb
