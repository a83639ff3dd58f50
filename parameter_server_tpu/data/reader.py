"""Streaming minibatch reader with prefetch.

Reference analog: learner/sgd.h MinibatchReader (parser thread feeding a
threadsafe queue) + data/stream_reader.h (multi-file, gz-aware streaming).

A batch has two stages, the parse and the ``BatchBuilder`` call, and every
iteration of a ``MinibatchReader`` runs them side by side on two threads.
Its own parse thread (``ps-reader-parse``) parses, up to ``prefetch``
batches ahead. Under ``iter(reader)`` a second thread of the reader's own
(``ps-reader-build``) takes the parsed pieces in the order the parse made
them, builds them and runs up to ``prefetch`` batches ahead in its turn;
whoever iterates only takes finished batches off a queue (evaluation,
validation, the apps' reference batches). Under ``reader.parsed()`` the
caller is the build stage: it takes each piece as parsed and finishes it
with ``reader.build(piece)`` on its own thread (training:
``_WorkerStream.next_batch`` on the pipeline's producer thread). Both
threads end with the iteration, exhausted or abandoned.

Named phases (``trace.phase``; one a chunk or a batch, never one a row), each
on the thread that does the work: ``reader.parse`` one step of
``iter_chunks`` (read + native parse of a 2 MiB chunk, and its merge with
the rows the chunk before left over; count: chunks), ``reader.build`` one
``BatchBuilder.build_flat`` / ``build`` (hash, unique, localize, bucket;
count: batches), ``reader.parsed_wait`` the build thread's wait for a
parsed piece (count: pieces), ``reader.put_wait`` a stage blocked on its
full queue (its slack). Which stage paces an ``iter(reader)``: a build
thread in ``reader.parsed_wait`` says the parse, a parse thread in
``reader.put_wait`` the build, a build thread in ``reader.put_wait`` the
caller. The Python parsers yield a row at a time, so that path has no
``reader.parse``. While a tracing plane is on, the two working phases also
feed a twin ``<name>.cpu``, the CPU seconds of their own thread inside
them: ``reader.parse`` less ``reader.parse.cpu`` is what the parse thread
waited (for the interpreter lock between its native calls, the machine's
run queue, the disk) and did not work. The two waits keep to the wall.
"""

from __future__ import annotations

import queue
import sys
import threading
from collections.abc import Generator, Iterator
from pathlib import Path

import numpy as np

from parameter_server_tpu.data.batch import BatchBuilder, CSRBatch
from parameter_server_tpu.data.libsvm import iter_format
from parameter_server_tpu.utils import trace


class MinibatchReader:
    """Streams CSRBatches from text files through two prefetch stages: the
    parse thread runs up to ``prefetch`` parsed batches ahead of the build,
    which under ``iter(reader)`` is a second thread of the reader's, itself
    up to ``prefetch`` finished batches ahead of whoever iterates (the
    caller's thread in ``evaluate_files`` only waits), and under
    ``reader.parsed()`` the caller (a training stream's ``next_batch``).
    Both carry the ``reader.*`` phases of the module's docstring.

    The two depths are the same ``prefetch``, one queue each: the parse can
    run a file to its last batch while the build holds a piece and the
    caller a group of ``data_shards`` batches. At most ``2 * prefetch + 2``
    batches are alive between the stages, a few MB each at 8192 x 39 entries.

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

    def build(self, piece: tuple) -> CSRBatch:
        """One of ``_pieces``' parsed batches through its ``BatchBuilder``
        call, timed where it happens: the reader's build thread under
        ``__iter__``, the caller's under ``parsed``."""
        build, rows = piece
        with trace.phase("reader.build", examples=len(rows[0])):
            return build(*rows)

    def _epoch_rows(self) -> Iterator:
        for f in self.files:
            yield from iter_format(self.fmt, f)

    def _flat_pieces(self) -> Iterator[tuple]:
        """Native path: C++ chunk parse -> vectorized batch slicing."""
        from parameter_server_tpu.data.native import iter_chunks

        bs, nnz_cap = self.builder.batch_size, self.builder.nnz_capacity

        def rows(flat, i, j):
            """Rows ``[i, j)`` of a flat chunk, its splits counted from row
            ``i``: views of the chunk's arrays but for the splits."""
            labels, splits, keys, vals, slots = flat
            lo, hi = splits[i], splits[j]
            return (
                labels[i:j],
                splits[i : j + 1] - lo,
                keys[lo:hi],
                vals[lo:hi],
                # None for slotless formats (native.SLOTLESS_FORMATS)
                None if slots is None else slots[lo:hi],
            )

        def end_of_batch(splits, i, n):
            """The row behind the batch that starts at row ``i`` of ``n``:
            the largest with rows <= bs and entries <= nnz_cap, and at least
            one row. ``splits`` may end before row ``n`` if it reaches
            ``i + bs``."""
            j = int(np.searchsorted(splits, splits[i] + nnz_cap, side="right") - 1)
            return max(i + 1, min(n, i + bs, j))

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

        build = self.builder.build_flat
        for _ in range(self.epochs):
            # the rows a chunk left over: fewer than a batch, and they fit one
            leftover = None
            for f in self.files:
                chunks = iter_chunks(f, self.fmt)
                while True:
                    with trace.phase("reader.parse") as parse:
                        flat = next(chunks, None)
                        if flat is None:
                            parse.count = 0  # the step that finds the file at its end
                            break
                        n = len(flat[0])
                        parse.set(examples=n)
                        if leftover is not None:
                            # the batch that begins in the leftover rows ends
                            # within bs rows: copy those alone, never the
                            # chunk, whose other batches are views of it
                            held = len(leftover[0])
                            straddle = cat(leftover, rows(flat, 0, min(n, bs - held)))
                    i = 0
                    if leftover is not None:
                        j = end_of_batch(straddle[1], 0, held + n)
                        if j == held + n < bs:
                            leftover = straddle  # the whole chunk, and still no batch
                            continue
                        yield build, rows(straddle, 0, j)
                        i = j - held
                    splits = flat[1]
                    while i < n:
                        j = end_of_batch(splits, i, n)
                        if j == n and n - i < bs:
                            break  # tail smaller than a batch: keep pending
                        yield build, rows(flat, i, j)
                        i = j
                    leftover = rows(flat, i, n) if i < n else None
            # epoch boundary flushes (epochs=N == N runs of epochs=1)
            if leftover is not None and not self.drop_remainder:
                yield build, leftover

    def _pieces(self) -> Iterator[tuple]:
        """The stream's batches before the ``BatchBuilder``, in order: each
        a ``(build, rows)`` for ``build``. The pieces of a native chunk are
        views of the chunk's own arrays, fresh every chunk."""
        if self.use_native:
            yield from self._flat_pieces()
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
                    yield self.builder.build, (np.array(labels), keys, vals, slots)
                    labels, keys, vals, slots, nnz = [], [], [], [], 0
                labels.append(label)
                keys.append(k)
                vals.append(v)
                slots.append(s)
                nnz += len(k)
            if labels and not self.drop_remainder:
                yield self.builder.build, (np.array(labels), keys, vals, slots)

    def __iter__(self) -> Iterator[CSRBatch]:
        """Finished batches in file order: the pieces of ``parsed()`` built
        one after the other on the reader's build thread."""
        return self._ahead(self._built(), "ps-reader-build")

    def _built(self) -> Iterator[CSRBatch]:
        pieces = self.parsed()
        try:
            while True:
                with trace.phase("reader.parsed_wait") as wait:
                    piece = next(pieces, None)
                    if piece is None:
                        wait.count = 0  # the probe that finds the stream at its end
                        return
                yield self.build(piece)
        finally:
            pieces.close()  # a build that leaves, or fails, takes the parse with it

    def parsed(self) -> Iterator[tuple]:
        """The batches as the parse thread parsed them, for the caller to
        ``build`` on its own thread, in the order it takes them (a
        ``BatchBuilder`` with a frequency filter counts in that order): a
        stream's parse and build then run side by side."""
        return self._ahead(self._pieces(), "ps-reader-parse")

    def _ahead(self, source: Generator, name: str) -> Iterator:
        """``source`` run on a new thread ``name``, up to ``prefetch`` items
        ahead of whoever iterates. An exception of ``source`` is raised
        here, behind the items made before it. The thread ends with the
        iteration: closing this generator (or dropping it) lets a blocked
        thread go, has it close ``source`` and joins it."""
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        _END = object()
        err: list[BaseException] = []
        stop = threading.Event()

        def _put(item) -> bool:
            """False once whoever iterates has left."""
            try:
                q.put_nowait(item)
            except queue.Full:
                if stop.is_set():
                    return False
                # this stage's slack: it is ahead of whoever takes its items
                with trace.phase("reader.put_wait"):
                    q.put(item)  # whoever sets ``stop`` empties the queue behind it
            return not stop.is_set()

        def produce() -> None:
            try:
                try:
                    for item in source:
                        if not _put(item):
                            return
                finally:
                    source.close()  # on this thread, the one that ran it
            except BaseException as e:  # surfaced on the consumer side
                err.append(e)
            finally:
                _put(_END)

        t = threading.Thread(target=produce, name=name, daemon=True)
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
            stop.set()
            while not q.empty():  # room for a put that is blocked
                q.get_nowait()
            # a generator dropped at interpreter exit finds its daemon
            # thread frozen: nothing to wait for
            if not sys.is_finalizing():
                t.join()


def ingest_of(cfg) -> tuple[str, str]:
    """(format, key mode) that the readers and ``BatchBuilder`` take for a
    PSConfig's files. ``data.format`` decides both: ``rating`` and ``sgns``
    lines carry ids of one dense space in two ranges (items then users,
    ``[mf].num_items`` saying where the users' begin; input then output
    vectors, ``[w2v].vocab_size`` apart), and are keyed by identity; so are
    the lines of "criteo:<26 sizes>" (``criteo_format``), whose 26
    categorical columns are 26 tables of those sizes in one id space; every
    other format, the bare "criteo" among them, carries features, hashed
    into ``data.num_keys``."""
    from parameter_server_tpu.data.libsvm import (
        CRITEO, RATING, SGNS, rating_format, sgns_format, split_format,
    )

    if cfg.data.format == RATING:
        return rating_format(cfg.mf.num_items), "identity"
    if cfg.data.format == SGNS:
        return sgns_format(cfg.w2v.vocab_size), "identity"
    name, sizes = split_format(cfg.data.format)
    if name == CRITEO and sizes is not None:
        return cfg.data.format, "identity"
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
