"""Disk-backed column-block cache tests (ref: SlotReader's parse-once,
per-slot binary cache — rebuilt here as .npy blocks + meta.json sidecar)."""

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.blockcache import (
    CACHE_VERSION,
    ColumnBlocks,
    cached_column_blocks,
    load_column_blocks,
    save_column_blocks,
    source_fingerprint,
)
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.models.darlin import Darlin
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

NUM_KEYS = 128


def _write_data(tmp_path, n=300, seed=0):
    labels, keys, vals, _ = make_sparse_logistic(
        n, NUM_KEYS - 2, nnz_per_example=8, seed=seed
    )
    p = tmp_path / "train.svm"
    write_libsvm(p, labels, keys, vals)
    return p


def _cfg(files, cache_dir=""):
    cfg = PSConfig()
    cfg.data.files = [str(f) for f in files]
    cfg.data.num_keys = NUM_KEYS
    cfg.data.cache_dir = str(cache_dir)
    cfg.solver.algo = "darlin"
    cfg.solver.feature_blocks = 4
    cfg.solver.block_iters = 10
    cfg.solver.minibatch = 64
    cfg.penalty.lambda_l1 = 0.5
    return cfg


def _blocks_equal(a: ColumnBlocks, b: ColumnBlocks):
    for k in ("feat_local", "rows", "values", "labels", "chunk_begin", "entries"):
        np.testing.assert_array_equal(np.asarray(getattr(a, k)), np.asarray(getattr(b, k)))
    assert (a.num_keys, a.block_size, a.num_examples) == (
        b.num_keys,
        b.block_size,
        b.num_examples,
    )


class TestSaveLoad:
    def test_roundtrip(self, tmp_path):
        p = _write_data(tmp_path)
        cfg = _cfg([p])
        cb = cached_column_blocks(cfg)  # no cache dir: plain build
        save_column_blocks(tmp_path / "cache", cb, "fp0")
        loaded = load_column_blocks(tmp_path / "cache", "fp0")
        assert loaded is not None
        _blocks_equal(cb, loaded)
        # mmap mode: the big arrays come back as memmaps
        assert isinstance(loaded.values, np.memmap)

    def test_missing_and_stale(self, tmp_path):
        assert load_column_blocks(tmp_path / "nope") is None
        p = _write_data(tmp_path)
        cb = cached_column_blocks(_cfg([p]))
        save_column_blocks(tmp_path / "c", cb, "fp0")
        assert load_column_blocks(tmp_path / "c", "other-fp") is None
        (tmp_path / "c" / "values.npy").unlink()  # incomplete cache
        assert load_column_blocks(tmp_path / "c", "fp0") is None

    def test_corrupt_sidecar_is_a_cache_miss(self, tmp_path):
        """A truncated meta.json (crash/disk-full mid-write) must rebuild,
        not wedge every subsequent run with a JSONDecodeError."""
        p = _write_data(tmp_path)
        cb = cached_column_blocks(_cfg([p]))
        save_column_blocks(tmp_path / "c", cb, "fp0")
        meta = tmp_path / "c" / "meta.json"
        meta.write_text(meta.read_text()[: len(meta.read_text()) // 2])
        assert load_column_blocks(tmp_path / "c", "fp0") is None
        meta.write_text('{"version": %d}' % CACHE_VERSION)  # parseable but missing keys
        assert load_column_blocks(tmp_path / "c") is None

    def test_fingerprint_tracks_sources_and_params(self, tmp_path):
        p = _write_data(tmp_path)
        fp1 = source_fingerprint([str(p)], "libsvm", NUM_KEYS, 4, 512)
        assert fp1 == source_fingerprint([str(p)], "libsvm", NUM_KEYS, 4, 512)
        assert fp1 != source_fingerprint([str(p)], "libsvm", NUM_KEYS, 8, 512)
        import os

        os.utime(p, ns=(1, 1))  # touched source -> new fingerprint
        assert fp1 != source_fingerprint([str(p)], "libsvm", NUM_KEYS, 4, 512)
        with pytest.raises(FileNotFoundError):
            source_fingerprint(["/no/such/file"], "libsvm", NUM_KEYS, 4, 512)


class TestCachedColumnBlocks:
    def test_second_call_skips_parsing(self, tmp_path, monkeypatch):
        p = _write_data(tmp_path)
        cfg = _cfg([p], cache_dir=tmp_path / "cache")
        first = cached_column_blocks(cfg)

        def boom(*a, **k):
            raise AssertionError("cache hit must not re-parse")

        import parameter_server_tpu.data.reader as reader_mod

        monkeypatch.setattr(reader_mod, "iter_flat_rows", boom)
        second = cached_column_blocks(cfg)
        _blocks_equal(first, second)

    def test_rewrite_invalidates(self, tmp_path):
        p = _write_data(tmp_path, seed=0)
        cfg = _cfg([p], cache_dir=tmp_path / "cache")
        first = cached_column_blocks(cfg)
        _write_data(tmp_path, seed=1)  # rewrites train.svm
        second = cached_column_blocks(cfg)
        assert not np.array_equal(
            np.asarray(first.labels), np.asarray(second.labels)
        )

    def test_built_file_by_file_equals_the_batches_form(self, tmp_path):
        """Several files through the parser and the key hash, shard by
        shard, written where they are mapped from: the same blocks as
        ``from_batches`` over the builder's batches of the same files."""
        from parameter_server_tpu.data.reader import MinibatchReader

        files = []
        for i in range(3):
            d = tmp_path / f"f{i}"
            d.mkdir()
            files.append(_write_data(d, n=200 + 50 * i, seed=i))
        cfg = _cfg(files, cache_dir=tmp_path / "cache")
        cb = cached_column_blocks(cfg)
        assert isinstance(cb.values, np.memmap) or isinstance(cb.values.base, np.memmap)
        builder = BatchBuilder(num_keys=NUM_KEYS, batch_size=64, max_nnz_per_example=cfg.data.max_nnz_per_example)
        batches = list(MinibatchReader(sorted(map(str, files)), "libsvm", builder))
        _blocks_equal(cb, ColumnBlocks.from_batches(batches, NUM_KEYS, 4, chunk_len=cb.chunk_len))
        _blocks_equal(cb, cached_column_blocks(cfg))  # and mapped back the same

    def test_a_cache_of_the_padded_layout_is_rebuilt(self, tmp_path):
        """CACHE_VERSION 1 padded every block to the longest: such a cache is
        a miss, never read as chunks."""
        import json

        p = _write_data(tmp_path)
        cfg = _cfg([p], cache_dir=tmp_path / "cache")
        first = cached_column_blocks(cfg)
        meta = tmp_path / "cache" / "meta.json"
        old = json.loads(meta.read_text())
        assert old["version"] == CACHE_VERSION == 2 and old["chunk_len"] == first.chunk_len
        meta.write_text(json.dumps({**old, "version": 1}))
        assert load_column_blocks(tmp_path / "cache") is None
        _blocks_equal(first, cached_column_blocks(cfg))
        assert json.loads(meta.read_text())["version"] == CACHE_VERSION

    def test_darlin_same_result_from_cache(self, tmp_path):
        p = _write_data(tmp_path)
        cfg = _cfg([p], cache_dir=tmp_path / "cache")
        quiet = ProgressReporter(print_fn=lambda *_: None)
        r1 = Darlin(cfg, reporter=quiet).fit_blocks(
            cached_column_blocks(cfg), shuffle_blocks=False
        )
        r2 = Darlin(cfg, reporter=quiet).fit_blocks(
            cached_column_blocks(cfg), shuffle_blocks=False
        )
        assert r1["objv"] == pytest.approx(r2["objv"], rel=1e-6)
        assert r1["nnz_w"] == r2["nnz_w"]


def test_a_shard_is_cut_to_the_cap_hashed_and_partitioned_by_block(tmp_path):
    """One file through the cache build's reader: an example longer than
    the cap keeps its first ``max_nnz`` entries, the keys are hashed as the
    online builders hash them, and the entries come out block by block,
    inside a block in the file's order, rows the examples' indices in the
    file."""
    from parameter_server_tpu.data.blockcache import _shard_pieces
    from parameter_server_tpu.utils.hashing import hash_keys

    rng = np.random.default_rng(0)
    lens = rng.integers(1, 12, 300)
    keys = [rng.choice(1 << 20, n, replace=False) + 1 for n in lens]
    path = tmp_path / "shard.libsvm"
    path.write_text("".join(
        f"{i % 2} " + " ".join(f"{k}:{1 + (k % 7)}" for k in ks) + "\n" for i, ks in enumerate(keys)
    ))
    ((feat, rows, vals, offsets), labels), = _shard_pieces(str(path), "libsvm", 1 << 12, 9, 512, 8)
    assert len(labels) == 300  # one parsed chunk: rows count from 0
    kept = [ks[:9] for ks in keys]
    want_rows = np.repeat(np.arange(300), [len(k) for k in kept])
    want_gids = hash_keys(np.concatenate(kept).astype(np.uint64), 1 << 12, 0)
    want_vals = np.concatenate([1 + (k % 7) for k in kept]).astype(np.float32)
    order = np.argsort(want_gids // 512, kind="stable")  # by block, the file's order inside a block
    assert offsets[-1] == len(want_rows) == len(feat)
    blocks = np.repeat(np.arange(8), np.diff(offsets))
    np.testing.assert_array_equal(blocks * 512 + feat, want_gids[order])
    np.testing.assert_array_equal(rows, want_rows[order])
    np.testing.assert_array_equal(vals, want_vals[order])
