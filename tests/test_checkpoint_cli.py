"""Checkpoint/resume, model dump/eval, and CLI tests.

Reference test analog: SaveModel/LoadModel round trips + the local.sh
launcher driving a full train->dump->evaluate cycle."""

import json
import subprocess
import sys

import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.models.evaluation import evaluate_model
from parameter_server_tpu.models.linear import LinearMethod
from parameter_server_tpu.utils.checkpoint import (
    dump_weights_text,
    load_checkpoint,
    load_weights_text,
    save_checkpoint,
)
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


@pytest.fixture(scope="module")
def svm_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    labels, keys, vals, _ = make_sparse_logistic(
        2000, 500, nnz_per_example=10, noise=0.3, seed=9
    )
    tr, te = d / "train.svm", d / "test.svm"
    write_libsvm(tr, labels[:1600], keys[:1600], vals[:1600])
    write_libsvm(te, labels[1600:], keys[1600:], vals[1600:])
    return str(tr), str(te)


def make_cfg(train_file):
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 12
    cfg.data.files = [train_file]
    cfg.solver.minibatch = 256
    cfg.penalty.lambda_l1 = 0.05
    return cfg


class TestCheckpoint:
    def test_state_roundtrip_nested(self, tmp_path):
        state = {"kv": {"z": np.arange(6).reshape(3, 2), "n": np.ones((3, 2))}}
        save_checkpoint(tmp_path / "ck", state, meta={"step": 7})
        loaded, meta = load_checkpoint(tmp_path / "ck")
        assert meta["step"] == 7
        np.testing.assert_array_equal(loaded["kv"]["z"], state["kv"]["z"])

    def test_sharded_concat(self, tmp_path):
        d = tmp_path / "ck"
        save_checkpoint(d, {"w": np.arange(4)}, shard_id=0, num_shards=2)
        save_checkpoint(d, {"w": np.arange(4, 8)}, shard_id=1, num_shards=2)
        loaded, _ = load_checkpoint(d)
        np.testing.assert_array_equal(loaded["w"], np.arange(8))
        one, _ = load_checkpoint(d, shard_id=1)
        np.testing.assert_array_equal(one["w"], np.arange(4, 8))

    def test_weights_text_roundtrip(self, tmp_path):
        w = np.zeros(100, dtype=np.float32)
        w[[3, 50, 99]] = [1.5, -2.25, 1e-7]
        p = tmp_path / "m.txt"
        n = dump_weights_text(w, p)
        assert n == 3
        w2 = load_weights_text(p, 100)
        np.testing.assert_allclose(w2, w, rtol=1e-6)

    def test_weights_text_key_overflow(self, tmp_path):
        p = tmp_path / "m.txt"
        p.write_text("150\t1.0\n")
        with pytest.raises(ValueError, match="outside"):
            load_weights_text(p, 100)
        p.write_text("-3\t1.0\n")
        with pytest.raises(ValueError, match="outside"):
            load_weights_text(p, 100)

    def test_train_resume_equals_uninterrupted(self, svm_files):
        """Kill-and-resume must reproduce the uninterrupted trajectory
        (FTRL is deterministic)."""
        tr, _ = svm_files
        import tempfile

        # uninterrupted: 2 epochs
        cfg = make_cfg(tr)
        cfg.solver.epochs = 2
        a = LinearMethod(cfg, reporter=quiet())
        a.train_files([tr])

        # interrupted: 1 epoch, checkpoint, new process-sim, resume 1 epoch
        cfg1 = make_cfg(tr)
        b = LinearMethod(cfg1, reporter=quiet())
        b.train_files([tr])
        with tempfile.TemporaryDirectory() as d:
            b.save(d)
            c = LinearMethod(make_cfg(tr), reporter=quiet())
            c.load(d)
            c.train_files([tr])
        for k in a.store.state:
            np.testing.assert_allclose(
                np.asarray(a.store.state[k]),
                np.asarray(c.store.state[k]),
                atol=1e-6,
                err_msg=k,
            )
        assert c.examples_seen == a.examples_seen

    def test_load_rejects_mismatched_keyspace(self, svm_files, tmp_path):
        tr, _ = svm_files
        app = LinearMethod(make_cfg(tr), reporter=quiet())
        app.save(tmp_path / "ck")
        cfg2 = make_cfg(tr)
        cfg2.data.num_keys = 1 << 10
        other = LinearMethod(cfg2, reporter=quiet())
        with pytest.raises(ValueError, match="num_keys"):
            other.load(tmp_path / "ck")

    def test_load_rejects_mismatched_algo(self, svm_files, tmp_path):
        tr, _ = svm_files
        app = LinearMethod(make_cfg(tr), reporter=quiet())
        app.save(tmp_path / "ck")
        cfg2 = make_cfg(tr)
        cfg2.solver.algo = "sgd"
        other = LinearMethod(cfg2, reporter=quiet())
        with pytest.raises(ValueError, match="algo"):
            other.load(tmp_path / "ck")


class TestModelEvaluation:
    def test_dump_then_evaluate(self, svm_files, tmp_path):
        tr, te = svm_files
        cfg = make_cfg(tr)
        cfg.solver.epochs = 3
        app = LinearMethod(cfg, reporter=quiet())
        app.train_files([tr])
        mp = tmp_path / "model.txt"
        n = app.dump_model(str(mp))
        assert n > 0
        res = evaluate_model(str(mp), [te], "libsvm", cfg.data.num_keys)
        assert res["examples"] == 400
        assert res["auc"] > 0.8
        # evaluating through the app gives the same result
        from parameter_server_tpu.data.reader import MinibatchReader

        direct = app.evaluate(
            MinibatchReader([te], "libsvm", app.make_builder())
        )
        assert res["auc"] == pytest.approx(direct["auc"], abs=1e-6)


def run_cli(*argv):
    """Spawn `python -m parameter_server_tpu.cli ...` on the CPU backend."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "/root/repo:" + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "parameter_server_tpu.cli", *argv],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
    )


class TestCLI:
    def _run(self, *argv):
        return run_cli(*argv)

    def test_train_dump_evaluate_cycle(self, svm_files, tmp_path):
        tr, te = svm_files
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(
            json.dumps(
                {
                    "data": {
                        "files": [tr],
                        "val_files": [te],
                        "num_keys": 4096,
                    },
                    "solver": {"minibatch": 256, "epochs": 2},
                    "penalty": {"lambda_l1": 0.05},
                }
            )
        )
        model = tmp_path / "model.txt"
        r = self._run(
            "train", "--app_file", str(cfg_path), "--model_out", str(model)
        )
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["val_auc"] > 0.8
        assert model.exists()

        r2 = self._run(
            "evaluate", "--app_file", str(cfg_path), "--model", str(model)
        )
        assert r2.returncode == 0, r2.stderr[-2000:]
        out2 = json.loads(r2.stdout.strip().splitlines()[-1])
        assert out2["auc"] == pytest.approx(out["val_auc"], abs=1e-6)

    def test_cli_darlin(self, svm_files, tmp_path):
        tr, _ = svm_files
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(
            json.dumps(
                {
                    "data": {"files": [tr], "num_keys": 4096},
                    "solver": {
                        "algo": "darlin",
                        "minibatch": 512,
                        "feature_blocks": 8,
                        "block_iters": 5,
                    },
                    "penalty": {"lambda_l1": 1.0},
                    "lr": {"eta": 1.0},
                }
            )
        )
        r = self._run("train", "--app_file", str(cfg_path))
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["train_auc"] > 0.7

    def test_cli_darlin_resumes_and_scores_val_files(self, svm_files, tmp_path):
        tr, te = svm_files
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(
            json.dumps(
                {
                    "data": {"files": [tr], "val_files": [te], "num_keys": 4096},
                    "solver": {
                        "algo": "darlin",
                        "minibatch": 512,
                        "feature_blocks": 8,
                        "block_iters": 4,
                    },
                    "penalty": {"lambda_l1": 1.0},
                    "lr": {"eta": 1.0},
                }
            )
        )
        r = self._run("train", "--app_file", str(cfg_path), "--resume")
        assert r.returncode != 0 and "--resume requires --ckpt_dir" in r.stderr
        r2 = self._run(
            "train", "--app_file", str(cfg_path), "--ckpt_dir", str(tmp_path / "ck")
        )
        assert r2.returncode == 0, r2.stderr[-2000:]
        out = json.loads(r2.stdout.strip().splitlines()[-1])
        # held-out files go through PodTrainer.evaluate_files, as every app's
        assert out["val_auc"] > 0.7 and "val_logloss" in out
        manifest = json.loads((tmp_path / "ck" / "manifest.json").read_text())
        assert manifest["meta"]["algo"] == "darlin" and manifest["meta"]["passes_done"] == 4
        assert set(manifest["arrays"]) == {"w", "active"}  # the table's slots, as the store saves them
        # a restart from the last finished pass has none left to run
        r3 = self._run(
            "train", "--app_file", str(cfg_path), "--resume", "--ckpt_dir", str(tmp_path / "ck")
        )
        assert r3.returncode == 0, r3.stderr[-2000:]
        out3 = json.loads(r3.stdout.strip().splitlines()[-1])
        assert out3["iters"] == 4 and out3["objv"] == pytest.approx(out["objv"], rel=1e-6)
        assert out3["val_auc"] == pytest.approx(out["val_auc"], abs=1e-4)

    def test_cli_missing_files_errors(self, tmp_path):
        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text("{}")
        r = self._run("train", "--app_file", str(cfg_path))
        assert r.returncode != 0
        assert "data.files is empty" in r.stderr


class TestCLIDynamicPool:
    def test_pool_serve_single_process(self, svm_files, tmp_path):
        """cli train --pool_coordinator --pool_serve: one process hosts
        the wire tier's Coordinator and trains its pod through the dynamic
        workload pool (the user-facing tier composition)."""
        import socket

        from parameter_server_tpu.utils.config import config_to_dict

        tr, te = svm_files
        cfg = make_cfg(tr)
        cfg.data.val_files = [te]
        cfg.solver.epochs = 2
        cfg.parallel.data_shards = 4
        cfg.parallel.kv_shards = 2
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(config_to_dict(cfg)))
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        r = run_cli(
            "train", "--app_file", str(p),
            "--pool_coordinator", f"127.0.0.1:{port}", "--pool_serve",
        )
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["mesh"] == {"data": 4, "kv": 2}
        assert out["val_auc"] > 0.75, out

    def test_pool_coordinator_rejected_off_pod_path(self, svm_files, tmp_path):
        """The flag must fail loudly on non-pod paths (a silently ignored
        flag would park other pod hosts on a coordinator that never
        starts)."""
        tr, _ = svm_files
        cfg = make_cfg(tr)  # default 1x1 mesh -> single-process path
        from parameter_server_tpu.utils.config import config_to_dict

        p = tmp_path / "cfg1.json"
        p.write_text(json.dumps(config_to_dict(cfg)))
        r = run_cli(
            "train", "--app_file", str(p),
            "--pool_coordinator", "127.0.0.1:1", "--pool_serve",
        )
        assert r.returncode != 0
        assert "pod training path" in r.stderr


class TestCLIConvert:
    def test_convert_populates_cache_then_train_reuses(self, svm_files, tmp_path):
        """cli convert parses once into the columnar block cache (the
        text2proto analog); a darlin train run then hits the cache."""
        tr, _ = svm_files
        from parameter_server_tpu.utils.config import config_to_dict

        cfg = make_cfg(tr)
        cfg.solver.algo = "darlin"
        cfg.solver.feature_blocks = 8
        cfg.solver.block_iters = 3
        cfg.data.cache_dir = str(tmp_path / "cache")
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(config_to_dict(cfg)))
        r = run_cli("convert", "--app_file", str(p))
        assert r.returncode == 0, r.stderr[-1500:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["num_examples"] == 1600 and out["n_blocks"] == 8
        assert (tmp_path / "cache" / "meta.json").exists()
        mtime = (tmp_path / "cache" / "meta.json").stat().st_mtime_ns
        r2 = run_cli("train", "--app_file", str(p))
        assert r2.returncode == 0, r2.stderr[-1500:]
        # the cache was reused, not rebuilt
        assert (tmp_path / "cache" / "meta.json").stat().st_mtime_ns == mtime


class TestCLIAppFactory:
    """cfg.app dispatch for the embedding apps (ref: App::Create covers
    EVERY app from config, not just linear_method)."""

    def test_unknown_app_rejected(self, svm_files, tmp_path):
        tr, _ = svm_files
        from parameter_server_tpu.utils.config import config_to_dict

        cfg = make_cfg(tr)
        cfg.app = "lda"
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(config_to_dict(cfg)))
        r = run_cli("train", "--app_file", str(p))
        assert r.returncode != 0 and "unknown app" in r.stderr

    def test_matrix_fac_app(self, tmp_path):
        rng = np.random.default_rng(0)
        n, n_u, n_i = 4000, 96, 64
        U = rng.normal(size=(n_u, 4)) / 2
        V = rng.normal(size=(n_i, 4)) / 2
        us = rng.integers(0, n_u - 1, n)
        it = rng.integers(0, n_i - 1, n)
        r = (np.sum(U[us] * V[it], 1)).astype(np.float32)
        tr_p, val_p = tmp_path / "tr.txt", tmp_path / "val.txt"
        for p, sl in ((tr_p, slice(0, 3500)), (val_p, slice(3500, None))):
            with open(p, "w") as f:
                for u, v, x in zip(us[sl], it[sl], r[sl]):
                    f.write(f"{u} {v} {x:.5f}\n")
        cfg = {
            "app": "matrix_fac",
            "data": {"files": [str(tr_p)], "val_files": [str(val_p)]},
            "mf": {"num_users": n_u - 1, "num_items": n_i - 1, "rank": 8,
                   "eta": 0.1, "l2": 0.002, "batch_size": 500},
            # through PodTrainer like the linear app and Wide&Deep: the
            # scanned multistep, the (data, kv) mesh, rating files
            "solver": {"epochs": 40, "steps_per_call": 3},
            "parallel": {"data_shards": 2, "kv_shards": 4},
        }
        p = tmp_path / "mf.json"
        p.write_text(json.dumps(cfg))
        r = run_cli("train", "--app_file", str(p),
                    "--model_out", str(tmp_path / "factors.npz"))
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["val_rmse"] < 0.45 and out["val_examples"] == 500, out
        assert out["train_rmse"] < 0.45 and "auc" not in out, out
        z = np.load(tmp_path / "factors.npz")
        assert z["user_factors"].shape == (n_u - 1, 8)
        assert z["item_factors"].shape == (n_i - 1, 8)

    def test_wide_deep_app(self, tmp_path):
        """wide_deep through the factory end-to-end (BASELINE parity
        config): file-driven streaming train on an XOR-interactions
        dataset the wide/linear half cannot express, over a (data, kv)
        mesh, then the npz dump -> CLI evaluate roundtrip."""
        rng = np.random.default_rng(3)
        n = 6000
        a = rng.integers(0, 2, n)
        b = rng.integers(0, 2, n)
        y = (a ^ b).astype(np.float32)
        keys = [np.array([ai, 2 + bi], dtype=np.uint64) for ai, bi in zip(a, b)]
        vals = [np.ones(2, dtype=np.float32) for _ in range(n)]
        from parameter_server_tpu.data.synthetic import write_libsvm

        tr_p, val_p = tmp_path / "tr.svm", tmp_path / "val.svm"
        write_libsvm(tr_p, y[:5000], keys[:5000], vals[:5000])
        write_libsvm(val_p, y[5000:], keys[5000:], vals[5000:])
        cfg = {
            "app": "wide_deep",
            "data": {"files": [str(tr_p)], "val_files": [str(val_p)],
                     "num_keys": 1024, "max_nnz_per_example": 8},
            "wd": {"emb_dim": 8, "hidden": [16], "mlp_lr": 5e-3},
            "penalty": {"lambda_l1": 0.5},
            # steps_per_call: the CLI must wire the scanned multistep into
            # the app; the mesh exercises the server-sharded SPMD path
            "solver": {"epochs": 30, "minibatch": 512, "steps_per_call": 2},
            "parallel": {"data_shards": 2, "kv_shards": 2},
        }
        p = tmp_path / "wd.json"
        p.write_text(json.dumps(cfg))
        model = tmp_path / "wd_model.npz"
        r = run_cli("train", "--app_file", str(p), "--model_out", str(model))
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["val_auc"] > 0.9, out  # linear AUC on XOR is ~0.5
        assert model.exists()

        # the same data through the linear app: interactions invisible
        lin = dict(cfg)
        lin.pop("wd")
        lin["app"] = "linear_method"
        lin["solver"] = {"epochs": 4, "minibatch": 512}
        lp = tmp_path / "lin.json"
        lp.write_text(json.dumps(lin))
        r2 = run_cli("train", "--app_file", str(lp))
        assert r2.returncode == 0, r2.stderr[-2000:]
        out2 = json.loads(r2.stdout.strip().splitlines()[-1])
        assert out2["val_auc"] < 0.65, out2
        assert out["val_auc"] > out2["val_auc"] + 0.25

        # dump -> offline evaluate matches the in-process val metrics
        r3 = run_cli("evaluate", "--app_file", str(p), "--model", str(model))
        assert r3.returncode == 0, r3.stderr[-2000:]
        out3 = json.loads(r3.stdout.strip().splitlines()[-1])
        assert out3["auc"] == pytest.approx(out["val_auc"], abs=1e-5)

    def test_word2vec_app(self, tmp_path):
        rng = np.random.default_rng(0)
        chunks = []
        for _ in range(500):
            topic = rng.integers(0, 2)
            chunks.append(rng.integers(0, 5, 8) + 5 * topic)
        corpus = np.concatenate(chunks)
        cp = tmp_path / "corpus.txt"
        cp.write_text(" ".join(map(str, corpus)))
        cfg = {
            "app": "word2vec",
            "data": {"files": [str(cp)]},
            "w2v": {"vocab_size": 16, "dim": 16, "window": 2,
                    "negatives": 4, "eta": 0.005, "batch_size": 256,
                    "block_tokens": 2048},
            "solver": {"epochs": 20, "max_delay": 1, "steps_per_call": 2},
            "parallel": {"data_shards": 2, "kv_shards": 2},
        }
        p = tmp_path / "w2v.json"
        p.write_text(json.dumps(cfg))
        emb_out = tmp_path / "emb.npz"
        r = run_cli("train", "--app_file", str(p), "--model_out", str(emb_out))
        assert r.returncode == 0, r.stderr[-2000:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        # through PodTrainer: the shared loop's report, scored by the mean loss a pair
        assert np.isfinite(out["mean_loss"]) and out["mean_loss"] < 5 * np.log(2)
        dump = np.load(emb_out)
        E = dump["in_vectors"]
        assert E.shape == dump["out_vectors"].shape == (16, 16)
        # topic structure visible in the dumped embeddings
        def sim(a, b):
            den = np.linalg.norm(E[a]) * np.linalg.norm(E[b])
            return E[a] @ E[b] / den
        within = np.mean([sim(0, i) for i in range(1, 5)])
        across = np.mean([sim(0, i) for i in range(5, 10)])
        assert within > across, (within, across)
