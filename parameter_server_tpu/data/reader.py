"""Streaming minibatch reader with prefetch.

Reference analog: learner/sgd.h MinibatchReader (parser thread feeding a
threadsafe queue) + data/stream_reader.h (multi-file, gz-aware streaming).

Every iteration of a ``MinibatchReader`` starts one producer thread, the
reader's thread, and the parse and the ``BatchBuilder`` run there, in
training and in evaluation alike: whoever iterates only takes finished
batches off a queue. Named phases of that thread (``trace.phase``; one a
chunk or a batch, never one a row): ``reader.parse`` one step of
``iter_chunks`` (read + native parse of a 2 MiB chunk, and its merge with
the rows the chunk before left over; count: chunks), ``reader.build`` one
``BatchBuilder.build_flat`` / ``build`` (hash, unique, localize, bucket;
count: batches), ``reader.put_wait`` the thread blocked on its full queue
(the reader's slack: near 0 means this thread sets the pace). The Python
parsers yield a row at a time, so that path has no ``reader.parse``.
"""

from __future__ import annotations

import queue
import threading
from collections.abc import Iterator
from pathlib import Path

import numpy as np

from parameter_server_tpu.data.batch import BatchBuilder, CSRBatch
from parameter_server_tpu.data.libsvm import iter_format
from parameter_server_tpu.utils import trace


class MinibatchReader:
    """Streams CSRBatches from text files through a prefetch thread: the
    reader's thread parses and builds up to ``prefetch`` batches ahead of
    whoever iterates (a ``PrefetchPipeline`` producer thread in training,
    the caller's thread in ``evaluate_files``), and carries the
    ``reader.*`` phases of the module's docstring.

    ``epochs`` and ``drop_remainder`` control the stream; a worker id /
    num_workers pair shards *files* across workers the way the reference's
    WorkloadPool hands file shards to workers (ref: learner/workload_pool.h).
    """

    def __init__(
        self,
        files: list[str | Path],
        fmt: str,
        builder: BatchBuilder,
        epochs: int = 1,
        prefetch: int = 4,
        worker_id: int = 0,
        num_workers: int = 1,
        drop_remainder: bool = False,
        backend: str = "auto",  # auto | native | python
    ):
        if not files:
            raise ValueError("no input files")
        if backend not in ("auto", "native", "python"):
            raise ValueError(f"bad backend {backend!r}")
        self.files = [f for i, f in enumerate(sorted(map(str, files))) if i % num_workers == worker_id]
        self.fmt = fmt
        self.builder = builder
        self.epochs = epochs
        self.prefetch = prefetch
        self.drop_remainder = drop_remainder
        from parameter_server_tpu.data import native as _native

        self.use_native = backend == "native" or (
            backend == "auto"
            and _native.has_native(fmt)
            and _native.native_available()
        )
        if backend == "native" and not _native.native_available():
            raise RuntimeError("native parser requested but not available")

    def _build(self, build, labels, *entries) -> CSRBatch:
        """One batch through ``build`` (the builder's ``build_flat`` or
        ``build``), timed where it happens."""
        with trace.phase("reader.build", examples=len(labels)):
            return build(labels, *entries)

    def _epoch_rows(self) -> Iterator:
        for f in self.files:
            yield from iter_format(self.fmt, f)

    def _flat_batches(self) -> Iterator[CSRBatch]:
        """Native path: C++ chunk parse -> vectorized batch slicing."""
        from parameter_server_tpu.data.native import iter_chunks

        bs, nnz_cap = self.builder.batch_size, self.builder.nnz_capacity

        def take(slots, sl):
            # slots is None for slotless formats (native.SLOTLESS_FORMATS)
            return None if slots is None else slots[sl]

        def slices(flat):
            """Yield CSRBatches of full size from ``flat``; return leftover."""
            labels, splits, keys, vals, slots = flat
            i = 0
            n = len(labels)
            while i < n:
                # largest j with rows<=bs and entries<=nnz_cap
                j_row = min(n, i + bs)
                base = splits[i]
                j = int(
                    np.searchsorted(splits, base + nnz_cap, side="right") - 1
                )
                j = max(i + 1, min(j_row, j))
                if j < n or (n - i) >= bs:
                    yield self._build(
                        self.builder.build_flat,
                        labels[i:j],
                        (splits[i : j + 1] - base),
                        keys[base : splits[j]],
                        vals[base : splits[j]],
                        take(slots, slice(base, splits[j])),
                    )
                    i = j
                else:
                    break  # tail smaller than a batch: keep pending
            base = splits[i]
            return (
                labels[i:],
                splits[i:] - base,
                keys[base:],
                vals[base:],
                take(slots, slice(base, None)),
            )

        def cat(a, b):
            la, sa, ka, va, oa = a
            lb, sb, kb, vb, ob = b
            return (
                np.concatenate([la, lb]),
                np.concatenate([sa, sb[1:] + sa[-1]]),
                np.concatenate([ka, kb]),
                np.concatenate([va, vb]),
                # slots-ness is per-format, fixed per reader: both sides
                # always agree
                None if oa is None else np.concatenate([oa, ob]),
            )

        for _ in range(self.epochs):
            leftover = None
            for f in self.files:
                chunks = iter_chunks(f, self.fmt)
                while True:
                    with trace.phase("reader.parse") as parse:
                        flat = next(chunks, None)
                        if flat is None:
                            parse.count = 0  # the step that finds the file at its end
                            break
                        parse.set(examples=len(flat[0]))
                        merged = cat(leftover, flat) if leftover is not None else flat
                    gen = slices(merged)
                    while True:
                        try:
                            yield next(gen)
                        except StopIteration as s:
                            leftover = s.value
                            break
            # epoch boundary flushes (epochs=N == N runs of epochs=1)
            if leftover is not None and len(leftover[0]) and not self.drop_remainder:
                yield self._build(self.builder.build_flat, *leftover)

    def _batches(self) -> Iterator[CSRBatch]:
        if self.use_native:
            yield from self._flat_batches()
            return
        for _ in range(self.epochs):
            labels: list[float] = []
            keys: list[np.ndarray] = []
            vals: list[np.ndarray] = []
            slots: list[np.ndarray] = []
            nnz = 0
            for label, k, v, s in self._epoch_rows():
                # flush if the next row would overflow either capacity
                if labels and (
                    len(labels) == self.builder.batch_size
                    or nnz + len(k) > self.builder.nnz_capacity
                ):
                    yield self._build(self.builder.build, np.array(labels), keys, vals, slots)
                    labels, keys, vals, slots, nnz = [], [], [], [], 0
                labels.append(label)
                keys.append(k)
                vals.append(v)
                slots.append(s)
                nnz += len(k)
            if labels and not self.drop_remainder:
                yield self._build(self.builder.build, np.array(labels), keys, vals, slots)

    def __iter__(self) -> Iterator[CSRBatch]:
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        _END = object()
        err: list[BaseException] = []
        stop = threading.Event()

        def _put(item) -> bool:
            try:
                q.put_nowait(item)
                return True
            except queue.Full:
                pass
            # the reader's slack: this thread is ahead of whoever iterates
            with trace.phase("reader.put_wait"):
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return True
                    except queue.Full:
                        continue
            return False

        def produce() -> None:
            try:
                for b in self._batches():
                    if not _put(b):
                        return  # consumer abandoned iteration
            except BaseException as e:  # surfaced on the consumer side
                err.append(e)
            finally:
                _put(_END)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            # unstick the producer if the consumer broke out early
            stop.set()


def ingest_of(cfg) -> tuple[str, str]:
    """(format, key mode) that the readers and ``BatchBuilder`` take for a
    PSConfig's files. ``data.format`` decides both: ``rating`` and ``sgns``
    lines carry ids of one dense space in two ranges (items then users,
    ``[mf].num_items`` saying where the users' begin; input then output
    vectors, ``[w2v].vocab_size`` apart), and are keyed by identity; every
    other format carries features, hashed into ``data.num_keys``."""
    from parameter_server_tpu.data.libsvm import (
        RATING, SGNS, rating_format, sgns_format,
    )

    if cfg.data.format == RATING:
        return rating_format(cfg.mf.num_items), "identity"
    if cfg.data.format == SGNS:
        return sgns_format(cfg.w2v.vocab_size), "identity"
    return cfg.data.format, "hash"


def iter_flat_rows(files: list[str | Path], fmt: str):
    """Yield flat CSR chunks ``(labels, row_splits, keys, vals, slots)`` from
    text files — the raw-key stream consumed by ingest-side components that
    don't need batches (frequency filter warmup, the sketch app). Native
    chunk parser when available, else the Python row parsers. ``slots`` is
    None for slotless formats (native.SLOTLESS_FORMATS — all slot ids are
    0 there) on BOTH backends, so consumers see one contract."""
    from parameter_server_tpu.data import native as _native

    paths = sorted(map(str, files))
    if _native.has_native(fmt) and _native.native_available():
        for f in paths:
            yield from _native.iter_chunks(f, fmt)
        return
    from parameter_server_tpu.data.libsvm import iter_format

    for f in paths:
        labels, splits, keys, vals, slots = [], [0], [], [], []
        for label, k, v, s in iter_format(fmt, f):
            labels.append(label)
            splits.append(splits[-1] + len(k))
            keys.append(k)
            vals.append(v)
            slots.append(s)
        if labels:
            yield (
                np.asarray(labels, dtype=np.float32),
                np.asarray(splits, dtype=np.int64),
                np.concatenate(keys) if keys else np.zeros(0, np.uint64),
                np.concatenate(vals) if vals else np.zeros(0, np.float32),
                (
                    None
                    if fmt.partition(":")[0] in _native.SLOTLESS_FORMATS
                    else np.concatenate(slots)
                    if slots
                    else np.zeros(0, np.uint64)
                ),
            )
