"""Parallel prefetching host input pipeline.

Reference analog: learner/sgd.h — each SGD worker runs a parser thread
feeding a threadsafe minibatch queue so gradient compute never waits on
text parsing (SURVEY §2.2 threading/queues, §7.4 "the C++ parser must
sustain ≥ GB/s/host"). That feed structure is what keeps reference
workers busy; this module is its pod analog.

Topology: D producer threads (one per worker stream, each owning its own
stateful BatchBuilder so admission filters stay single-threaded) push
per-worker batches into per-stream bounded queues; one stacker thread
assembles them into ready global step items — stacked arrays plus the
host-side bookkeeping (example counts, labels) — in a bounded output
queue. The dispatch loop then only pops + dispatches the device step,
overlapping host parse/build with device compute instead of serializing
D batch builds inline before every step.

A training stream over files is two threads deep before the stacker, and
both work: ``next_batch()`` takes the next batch as the stream's
``MinibatchReader`` thread parsed it (``reader.parse`` /
``reader.put_wait``, up to its queue of four batches ahead) and runs the
``BatchBuilder`` on it here, in the producer thread (``reader.build``, see
``data/reader.py``), so a stream's parse and build overlap. So: reader
thread (parse) -> producer thread (build) -> stacker thread -> dispatch
loop.

Named phases (``trace.phase``: a named timer each, and a span in the
profiler's and the tracer's timelines when those run): ``feed.build`` one
``next_batch()`` in a producer thread (count: batches): over a
``MinibatchReader`` that is the build itself (the ``reader.build`` inside
it) plus the producer's wait for the reader's thread to have parsed the
batch (plus, once a file, the start of the next reader and its thread);
``feed.put_wait`` a producer blocked on its full queue (the feed's slack);
``feed.stack`` ``prepare`` + ``assemble`` in the stacker thread (count:
emitted items; one thread serves every stream, so its busy share has a
wall at 100%). While a tracing plane is on, ``feed.stack`` also feeds a
twin ``feed.stack.cpu``, the CPU seconds of the stacker inside the phase:
wall less CPU is what it stood waiting for the interpreter lock, the
runtime or the machine. The other two, a phase around the reader's own and
a wait, keep to the wall.

Draining contract: ``get()`` returns ``None`` once every stream is
exhausted (and forever after). Callers that must keep issuing collectives
(multi-host SPMD: every process runs the same program) substitute their
own inert batches after that.
"""

from __future__ import annotations

import queue
import threading
from collections.abc import Callable, Sequence
from typing import Any

from parameter_server_tpu.utils import trace

_END = object()


class PrefetchPipeline:
    """Bounded parallel producer of ready-to-dispatch global step items.

    streams: objects exposing ``next_batch() -> batch | None`` (None =
        drained) and ``_empty() -> batch`` (inert all-padding batch).
    prepare: ``prepare(batches: list) -> item`` run on the stacker thread —
        the per-step host work (stacking, label bookkeeping) moved off the
        dispatch loop.
    depth: bound of every internal queue (per-stream and output).
    group_size / assemble: multistep grouping ON the stacker thread —
        every ``group_size`` prepared items are combined by
        ``assemble(items) -> group_item`` before emission, so the K-way
        group stacking (one device call's worth of microsteps) never runs
        on the dispatch loop. A partial final group is padded with
        prepared inert items (empties only ever trail real batches —
        the termination contract's invariant).
    """

    def __init__(
        self,
        streams: Sequence[Any],
        prepare: Callable[[list], Any],
        depth: int = 2,
        group_size: int = 1,
        assemble: Callable[[list], Any] | None = None,
    ):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        if group_size > 1 and assemble is None:
            raise ValueError("group_size > 1 requires an assemble callable")
        self.streams = list(streams)
        self.prepare = prepare
        self.group_size = group_size
        self.assemble = assemble
        self._qs = [queue.Queue(maxsize=depth) for _ in self.streams]
        self._out: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._errs: list[BaseException] = []
        self._drained = False
        self._threads = [
            threading.Thread(target=self._produce, args=(i,), daemon=True)
            for i in range(len(self.streams))
        ]
        self._threads.append(
            threading.Thread(target=self._stack_loop, daemon=True)
        )
        for t in self._threads:
            t.start()

    # -- queue helpers that respect shutdown ------------------------------
    def _put(self, q: queue.Queue, item) -> bool:
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _get(self, q: queue.Queue):
        while not self._stop.is_set():
            try:
                return q.get(timeout=0.1)
            except queue.Empty:
                continue
        return _END

    # -- threads -----------------------------------------------------------
    def _produce(self, i: int) -> None:
        try:
            q = self._qs[i]
            while not self._stop.is_set():
                with trace.phase("feed.build") as build:
                    b = self.streams[i].next_batch()
                    if b is None:
                        build.count = 0  # the probe that finds it drained
                        break
                try:
                    q.put_nowait(b)
                except queue.Full:
                    # the feed's slack: this stream is ahead of the stacker
                    with trace.phase("feed.put_wait"):
                        if not self._put(q, b):
                            return
        except BaseException as e:  # re-raised on the consumer side
            self._errs.append(e)
        finally:
            self._put(self._qs[i], _END)

    def _stack_loop(self) -> None:
        done = [False] * len(self.streams)
        pending: list = []  # partially-filled multistep group
        try:
            while not self._stop.is_set():
                batches = []
                for i, q in enumerate(self._qs):
                    if done[i]:
                        batches.append(self.streams[i]._empty())
                        continue
                    item = self._get(q)
                    if item is _END:
                        done[i] = True
                        batches.append(self.streams[i]._empty())
                    else:
                        batches.append(item)
                if all(done):
                    break
                # feed.stack counts emitted items: a group's K prepares and
                # its one assemble add up to one
                with trace.phase("feed.stack") as stack:
                    item = self.prepare(batches)
                    if self.group_size > 1:
                        pending.append(item)
                        if len(pending) < self.group_size:
                            stack.count = 0
                            continue
                        item = self.assemble(pending)
                        pending = []
                if not self._put(self._out, item):
                    return
            if pending and not self._stop.is_set():
                # pad the final partial group with inert prepared items
                with trace.phase("feed.stack"):
                    empty = self.prepare([s._empty() for s in self.streams])
                    pending += [empty] * (self.group_size - len(pending))
                    item = self.assemble(pending)
                self._put(self._out, item)
        except BaseException as e:
            self._errs.append(e)
        finally:
            self._put(self._out, _END)

    # -- consumer API ------------------------------------------------------
    def get(self):
        """Next ready step item; None once (and forever after) every
        stream has drained. Producer-thread exceptions re-raise here."""
        if self._errs:
            self._stop.set()
            raise self._errs[0]
        if self._drained:
            return None
        item = self._out.get()
        if item is _END:
            self._drained = True
            if self._errs:
                raise self._errs[0]
            return None
        return item

    def close(self) -> None:
        """Unstick and retire all threads (safe to call twice)."""
        self._stop.set()
        for q in [*self._qs, self._out]:
            try:
                q.get_nowait()
            except queue.Empty:
                pass
        for t in self._threads:
            t.join(timeout=5)

    def __enter__(self) -> "PrefetchPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
