"""Unit tests for L0 utilities (ref test analog: range_test,
parallel_ordered_match_test in the reference's src/test/)."""

import json

import numpy as np
import pytest

from parameter_server_tpu.utils.config import PSConfig, load_config
from parameter_server_tpu.utils.hashing import PAD_KEY, hash_keys, splitmix64
from parameter_server_tpu.utils.keyrange import KeyRange
from parameter_server_tpu.utils.metrics import ProgressReporter, Timer, merge_progress


class TestHashing:
    def test_splitmix_bijective_sample(self):
        x = np.arange(100_000, dtype=np.uint64)
        h = splitmix64(x)
        assert len(np.unique(h)) == len(x)  # no collisions on a large sample

    def test_hash_range_and_pad(self):
        keys = np.random.default_rng(0).integers(0, 2**63, 10_000, dtype=np.uint64)
        h = hash_keys(keys, num_keys=1 << 16)
        assert h.min() >= 1 and h.max() < (1 << 16)
        assert PAD_KEY == 0

    def test_hash_deterministic(self):
        keys = np.array([1, 2, 3], dtype=np.uint64)
        np.testing.assert_array_equal(
            hash_keys(keys, 1024, slot_ids=5), hash_keys(keys, 1024, slot_ids=5)
        )

    def test_slot_salt_decorrelates(self):
        keys = np.arange(1000, dtype=np.uint64)
        a = hash_keys(keys, 1 << 20, slot_ids=0)
        b = hash_keys(keys, 1 << 20, slot_ids=1)
        assert (a == b).mean() < 0.01

    def test_hash_spread_uniform(self):
        keys = np.arange(100_000, dtype=np.uint64)
        h = hash_keys(keys, 1 << 10)
        counts = np.bincount(h, minlength=1 << 10)
        assert counts[PAD_KEY] == 0
        # chi-square-ish sanity: max bucet not wildly above the mean
        assert counts[1:].max() < 3 * counts[1:].mean()


class TestKeyRange:
    def test_even_divide_partitions(self):
        r = KeyRange(0, 1000)
        parts = r.even_divide(7)
        assert parts[0].begin == 0 and parts[-1].end == 1000
        assert sum(p.size for p in parts) == 1000
        for a, b in zip(parts, parts[1:]):
            assert a.end == b.begin

    @pytest.mark.parametrize("size,n", [(10, 3), (5, 3), (1024, 8), (1000, 7)])
    def test_shard_of_inverts_even_divide(self, size, n):
        r = KeyRange(0, size)
        parts = r.even_divide(n)
        for k in range(size):
            i = r.shard_of(k, n)
            assert parts[i].contains(k)

    def test_intersect(self):
        assert KeyRange(0, 10).intersect(KeyRange(5, 20)) == KeyRange(5, 10)
        assert KeyRange(0, 5).intersect(KeyRange(7, 9)).size == 0

    def test_invalid(self):
        with pytest.raises(ValueError):
            KeyRange(5, 2)


class TestConfig:
    def test_defaults(self):
        cfg = PSConfig()
        assert cfg.solver.algo == "ftrl"
        assert cfg.data.num_keys == 1 << 22

    def test_load_json(self, tmp_path):
        p = tmp_path / "c.json"
        p.write_text(
            json.dumps(
                {
                    "app": "linear_method",
                    "solver": {"algo": "darlin", "max_delay": 2},
                    "penalty": {"lambda_l1": 4.0},
                }
            )
        )
        cfg = load_config(p)
        assert cfg.solver.algo == "darlin"
        assert cfg.solver.max_delay == 2
        assert cfg.penalty.lambda_l1 == 4.0
        assert cfg.lr.alpha == 0.1  # default preserved

    def test_load_toml(self, tmp_path):
        p = tmp_path / "c.toml"
        p.write_text('app = "linear_method"\n[solver]\nminibatch = 128\n')
        assert load_config(p).solver.minibatch == 128


class TestMetrics:
    def test_reporter_jsonl_and_relobjv(self, tmp_path):
        path = tmp_path / "m.jsonl"
        rep = ProgressReporter(path, print_fn=lambda *_: None)
        rep.report(examples=10, objv=100.0)
        rec = rep.report(examples=20, objv=90.0)
        assert rec["rel_objv"] == pytest.approx(0.1)
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        assert len(lines) == 2 and lines[1]["objv"] == 90.0

    def test_merge_progress_weighted(self):
        m = merge_progress(
            [
                {"examples": 100, "auc": 0.5, "nnz_w": 10},
                {"examples": 300, "auc": 0.9, "nnz_w": 20},
            ]
        )
        assert m["examples"] == 400
        assert m["auc"] == pytest.approx(0.8)
        assert m["nnz_w"] == 30

    def test_timer(self):
        t = Timer()
        with t:
            pass
        assert t.count == 1 and t.total >= 0


class TestCompileCachePlacement:
    """utils.hostenv.init_compile_cache: the one setter of the persistent
    compile cache's directory (jax.config.update is intercepted, so the
    process-wide config stays as conftest left it)."""

    def _updates(self, monkeypatch) -> dict:
        import jax

        seen: dict = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: seen.__setitem__(k, v)
        )
        return seen

    def test_env_placed_cache_is_left_to_jax(self, monkeypatch, tmp_path):
        from parameter_server_tpu.utils.hostenv import init_compile_cache

        seen = self._updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert init_compile_cache() == str(tmp_path)
        assert seen == {}

    def test_unset_env_points_at_the_checkout(self, monkeypatch):
        from pathlib import Path

        from parameter_server_tpu.utils.hostenv import init_compile_cache

        seen = self._updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parent.parent / ".jax_cache")
        assert init_compile_cache() == want
        assert seen["jax_compilation_cache_dir"] == want

    def test_force_cpu_pins_only_the_platform(self):
        from parameter_server_tpu.utils.hostenv import force_cpu

        env = {"JAX_PLATFORMS": "tpu", "OTHER": "kept"}
        assert force_cpu(env) is env
        assert env == {"JAX_PLATFORMS": "cpu", "OTHER": "kept"}
