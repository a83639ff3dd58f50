"""PrefetchPipeline: the parallel host input feed (ref: learner/sgd.h —
parser thread per worker + threadsafe queues keeping compute fed)."""

import time

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.pipeline import PrefetchPipeline
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.parallel.trainer import PodTrainer
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter


class FakeStream:
    """Yields (stream_id, seq) tuples; optional per-batch delay simulates a
    slow parser."""

    def __init__(self, sid: int, n: int, delay: float = 0.0):
        self.sid = sid
        self.n = n
        self.delay = delay
        self.i = 0

    def next_batch(self):
        if self.i >= self.n:
            return None
        if self.delay:
            time.sleep(self.delay)
        b = (self.sid, self.i)
        self.i += 1
        return b

    def _empty(self):
        return (self.sid, -1)


class TestPrefetchPipeline:
    def test_single_stream_order(self):
        with PrefetchPipeline([FakeStream(0, 5)], prepare=list) as p:
            items = []
            while (it := p.get()) is not None:
                items.append(it)
        assert items == [[(0, i)] for i in range(5)]

    def test_multi_stream_slot_association_and_fill(self):
        """Stream i's batches always land in slot i; a drained stream's slot
        is filled with its inert batch while others continue."""
        streams = [FakeStream(0, 2), FakeStream(1, 5), FakeStream(2, 3)]
        with PrefetchPipeline(streams, prepare=list) as p:
            items = []
            while (it := p.get()) is not None:
                items.append(it)
        assert len(items) == 5  # until the longest stream drains
        for step, it in enumerate(items):
            for sid, (got_sid, seq) in enumerate(it):
                assert got_sid == sid
                assert seq == (step if step < streams[sid].n else -1)

    def test_drained_returns_none_forever(self):
        with PrefetchPipeline([FakeStream(0, 1)], prepare=list) as p:
            assert p.get() is not None
            for _ in range(3):
                assert p.get() is None

    def test_producer_error_propagates(self):
        class Boom(FakeStream):
            def next_batch(self):
                if self.i == 2:
                    raise RuntimeError("parse failed")
                return super().next_batch()

        with PrefetchPipeline([Boom(0, 9)], prepare=list) as p:
            with pytest.raises(RuntimeError, match="parse failed"):
                while p.get() is not None:
                    pass

    def test_prepare_error_propagates(self):
        def bad_prepare(batches):
            raise ValueError("stack failed")

        with PrefetchPipeline([FakeStream(0, 3)], prepare=bad_prepare) as p:
            with pytest.raises(ValueError, match="stack failed"):
                while p.get() is not None:
                    pass

    def test_parallel_builds_beat_serial(self):
        """The verdict criterion: with D=4 slow parsers, consuming through
        the pipeline must be >= 2x faster than building serially inline
        (the four builder threads overlap their delays)."""
        D, n, delay = 4, 6, 0.02

        t0 = time.perf_counter()
        serial = [FakeStream(i, n, delay) for i in range(D)]
        while True:
            batches = [s.next_batch() for s in serial]
            if all(b is None for b in batches):
                break
        serial_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        with PrefetchPipeline(
            [FakeStream(i, n, delay) for i in range(D)], prepare=list, depth=2
        ) as p:
            while p.get() is not None:
                pass
        pipe_s = time.perf_counter() - t0
        assert pipe_s * 2 <= serial_s, (pipe_s, serial_s)


def _quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def _cfg(depth: int, data_shards=2, kv_shards=2):
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 12
    cfg.data.pipeline_depth = depth
    cfg.solver.minibatch = 128
    cfg.solver.epochs = 2
    cfg.penalty.lambda_l1 = 0.05
    cfg.parallel.data_shards = data_shards
    cfg.parallel.kv_shards = kv_shards
    return cfg


@pytest.fixture(scope="module")
def svm_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("pipe")
    labels, keys, vals, _ = make_sparse_logistic(
        2000, 600, nnz_per_example=10, noise=0.3, seed=5
    )
    paths = []
    for i in range(2):
        p = d / f"part-{i}.svm"
        s = slice(i * 1000, (i + 1) * 1000)
        write_libsvm(p, labels[s], keys[s], vals[s])
        paths.append(str(p))
    return paths


class TestPodTrainerPipeline:
    def test_single_stream_pipelined_matches_serial_exactly(self, svm_files):
        """D=1: stream order is fully deterministic, so the pipelined and
        serial dispatch sequences are identical batch-for-batch and the
        final FTRL state must match bit-for-bit."""
        ws = []
        for depth in (0, 2):
            t = PodTrainer(
                _cfg(depth, data_shards=1, kv_shards=2), reporter=_quiet()
            )
            t.train_files(svm_files[:1], report_every=5)
            ws.append(t.full_weights())
        np.testing.assert_array_equal(ws[0], ws[1])

    def test_multi_stream_pipelined_converges(self, svm_files):
        """D=2 over 2 file shards: worker->file assignment may race, so
        assert quality, not bitwise equality."""
        aucs = {}
        for depth in (0, 2):
            t = PodTrainer(_cfg(depth), reporter=_quiet())
            last = t.train_files(svm_files, report_every=5)
            aucs[depth] = last["auc"]
            assert t.examples_seen == 2 * 2000
        assert aucs[2] > aucs[0] - 0.02, aucs
        assert aucs[2] > 0.75, aucs


class TestBucketedBatches:
    """bucket_nnz: power-of-two static shapes sized to real density (the
    TPU bucketing idiom) instead of the max_nnz_per_example worst case."""

    def test_builder_buckets_pow2(self):
        from parameter_server_tpu.data.batch import BUCKET_FLOOR, BatchBuilder

        b = BatchBuilder(
            num_keys=1 << 16, batch_size=1024, max_nnz_per_example=256,
            key_mode="identity", bucket_nnz=True,
        )
        small = b.build(
            np.ones(4, dtype=np.float32),
            [np.arange(3, dtype=np.uint64)] * 4,
            [np.ones(3, dtype=np.float32)] * 4,
        )
        assert len(small.values) == BUCKET_FLOOR  # floor bucket
        n = 900
        big = b.build(
            np.ones(n, dtype=np.float32),
            [np.arange(9, dtype=np.uint64)] * n,
            [np.ones(9, dtype=np.float32)] * n,
        )
        sz = len(big.values)
        assert sz >= n * 9 and sz & (sz - 1) == 0
        assert sz < b.nnz_capacity
        # the key axis has a bucket of its own: 10 keys (9 + PAD_KEY) in
        # 8,100 entries take the floor, not entries + 1 slots
        assert big.num_unique == 10 and len(big.unique_keys) == BUCKET_FLOOR

    def test_pad_batch_grows_only(self):
        from parameter_server_tpu.data.batch import BatchBuilder, pad_batch

        b = BatchBuilder(
            num_keys=1 << 12, batch_size=8, key_mode="identity",
            bucket_nnz=True,
        )
        x = b.build(
            np.ones(2, dtype=np.float32),
            [np.array([1, 2], dtype=np.uint64)] * 2,
            [np.ones(2, dtype=np.float32)] * 2,
        )
        big = pad_batch(x, len(x.values) * 2, len(x.unique_keys) * 2)
        assert len(big.values) == len(x.values) * 2
        np.testing.assert_array_equal(big.values[: len(x.values)], x.values)
        assert not big.values[len(x.values):].any()
        with pytest.raises(ValueError, match="shrink"):
            pad_batch(big, 4, 4)

    def test_pod_trainer_bucketed_matches_dense(self, svm_files):
        """Same math, smaller pads: bucketed training must reproduce the
        dense-padded run's quality on the same stream."""
        aucs = {}
        for bucket in (False, True):
            cfg = _cfg(2)
            cfg.data.bucket_nnz = bucket
            t = PodTrainer(cfg, reporter=_quiet())
            last = t.train_files(svm_files, report_every=5)
            ev = t.evaluate_files(svm_files[:1])
            aucs[bucket] = (last["auc"], ev["auc"])
            assert t.examples_seen == 2 * 2000
        assert abs(aucs[True][0] - aucs[False][0]) < 0.03, aucs
        assert abs(aucs[True][1] - aucs[False][1]) < 0.03, aucs
