"""Tests for word2vec's data tools and the Wide&Deep app (matrix
factorization: tests/test_matrix_fac_pod.py; skip-gram through PodTrainer:
tests/test_word2vec_pod.py).

Reference test analog: each parity config in BASELINE.json gets a
small-scale convergence check against task-appropriate baselines."""

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.models import metrics as M
from parameter_server_tpu.models.wide_deep import WideDeep
from parameter_server_tpu.models.word2vec import NegativeSampler
from parameter_server_tpu.utils.metrics import ProgressReporter


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


class TestWord2Vec:
    def test_negative_sampler_distribution(self):
        counts = np.array([100, 10, 1, 0])
        s = NegativeSampler(counts, seed=0)
        draw = s.sample(20000)
        freq = np.bincount(draw, minlength=4) / 20000
        assert freq[0] > freq[1] > freq[2]
        assert freq[3] == 0


class TestWideDeep:
    @staticmethod
    def _interaction_data(n=6000, seed=0):
        """y = XOR of two categorical groups: invisible to a linear model."""
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 2, n)
        b = rng.integers(0, 2, n)
        y = (a ^ b).astype(np.float32)
        # features: cat A value (keys 0/1), cat B value (keys 2/3)
        keys = [np.array([ai, 2 + bi], dtype=np.uint64) for ai, bi in zip(a, b)]
        vals = [np.ones(2, dtype=np.float32) for _ in range(n)]
        return y, keys, vals

    def _batches(self, y, keys, vals, builder, bs=512):
        return [
            builder.build(y[i : i + bs], keys[i : i + bs], vals[i : i + bs])
            for i in range(0, len(y), bs)
        ]

    def test_captures_interactions_linear_cannot(self):
        y, keys, vals = self._interaction_data()
        builder = BatchBuilder(num_keys=64, batch_size=512, key_mode="identity")
        train = self._batches(y[:5000], keys[:5000], vals[:5000], builder)
        test = self._batches(y[5000:], keys[5000:], vals[5000:], builder)

        wd = WideDeep(num_keys=64, emb_dim=8, hidden=[16], mlp_lr=5e-3,
                      reporter=quiet())
        for _ in range(30):
            wd.train(train, report_every=1000)
        ev = wd.evaluate(test)
        assert ev["auc"] > 0.9, ev  # linear AUC on XOR is ~0.5

    def test_linear_fails_on_same_data(self):
        from parameter_server_tpu.models.linear import LinearMethod
        from parameter_server_tpu.utils.config import PSConfig

        y, keys, vals = self._interaction_data()
        builder = BatchBuilder(num_keys=64, batch_size=512, key_mode="identity")
        train = self._batches(y[:5000], keys[:5000], vals[:5000], builder)
        test = self._batches(y[5000:], keys[5000:], vals[5000:], builder)
        cfg = PSConfig()
        cfg.data.num_keys = 64
        app = LinearMethod(cfg, reporter=quiet())
        for _ in range(3):
            app.train(train)
        assert app.evaluate(test)["auc"] < 0.6


class TestWord2VecStreaming:
    """The streaming corpus path: file shards -> WorkloadPool ->
    PairStream blocks; pairs never materialized corpus-wide (BASELINE's
    1B-word operating point)."""

    def test_window_pairs_match_make_pairs(self):
        from parameter_server_tpu.models.word2vec import _window_pairs

        corpus = np.random.default_rng(1).integers(0, 50, 500)
        window = 2
        ref = sorted(
            (int(corpus[i]), int(corpus[j]))
            for i in range(len(corpus))
            for j in range(max(0, i - window), min(len(corpus), i + window + 1))
            if i != j
        )
        c, x = _window_pairs(corpus, window)
        got = sorted(zip(c.tolist(), x.tolist()))
        assert got == ref

    def test_stream_covers_exactly_the_corpus_pairs(self, tmp_path):
        """Every window pair appears exactly once across streamed batches,
        including pairs crossing block boundaries; no duplicates from the
        carry trick."""
        from parameter_server_tpu.models.word2vec import (
            NegativeSampler,
            PairStream,
            _window_pairs,
        )
        from parameter_server_tpu.parallel.workload import WorkloadPool

        rng = np.random.default_rng(3)
        corpus = rng.integers(0, 30, 997)  # deliberately not block-aligned
        f = tmp_path / "corpus.txt"
        f.write_text(" ".join(map(str, corpus)))
        pool = WorkloadPool([str(f)])
        s = PairStream(
            0, pool, window=3, batch_size=64, num_negatives=2,
            sampler=NegativeSampler(np.bincount(corpus, minlength=30), seed=0),
            block_tokens=100,
        )
        got = []
        while (b := s.next_batch()) is not None:
            m = b["mask"] > 0
            got += list(zip(b["center"][m].tolist(), b["context"][m].tolist()))
        ref_c, ref_x = _window_pairs(corpus, 3)
        assert sorted(got) == sorted(zip(ref_c.tolist(), ref_x.tolist()))

    def test_memory_bounded_by_blocks(self, tmp_path):
        """A corpus far larger than the block size streams with the pair
        buffer bounded by ~2*window*block_tokens, not corpus pairs."""
        from parameter_server_tpu.models.word2vec import (
            NegativeSampler,
            PairStream,
        )
        from parameter_server_tpu.parallel.workload import WorkloadPool

        n, block = 200_000, 2_000
        corpus = np.random.default_rng(5).integers(0, 100, n)
        f = tmp_path / "big.npy"
        np.save(f, corpus)
        pool = WorkloadPool([str(f)])
        s = PairStream(
            0, pool, window=2, batch_size=256, num_negatives=2,
            sampler=NegativeSampler(np.bincount(corpus, minlength=100), seed=0),
            block_tokens=block,
        )
        n_pairs = 0
        while (b := s.next_batch()) is not None:
            n_pairs += int((b["mask"] > 0).sum())
        total_pairs = 2 * (2 * n - 3)  # sum over off in {1,2} of 2*(n-off)
        assert n_pairs == total_pairs
        # buffer peak: about one block's pairs (+ carry + an open batch)
        assert s.max_buffered < 2 * 2 * (block + 256 + 4)
        assert s.max_buffered < total_pairs / 20
