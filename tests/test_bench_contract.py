"""The driver-facing bench output contract: bench's stdout line must stay parseable inside a 2000-char tail buffer
whatever the suite produced. These tests pin the _compact_contract
guarantees without running any benchmark (bench's parent-side code never
imports jax, so this is cheap)."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def _full(sub_overrides=None, **top):
    sub = {
        "pallas_ftrl": {"pallas_speedup": 1.2, "mode": "real"},
        "pipeline_e2e": {"pipelined_k8_ex_per_sec": 1.0, "auc_k8": 0.8,
                         "fastest": "compact_f32"},
        "ladder": {"bucketing_speedup": 3.5, "k8_over_k1": 1.2},
        "hbm_scale": {"num_keys_log2": 27, "sparse_step_ex_per_sec": 1.0,
                      "dense_hbm_gb_per_sec": 600.0},
        "scale": {"ex_per_sec": 5e4, "holdout_auc": 0.95, "gb_streamed": 2.3},
        "word2vec": {"pairs_per_sec_k8": 1.0, "vs_baseline": 2.0},
        "matrix_fac": {"pairs_per_sec_k8": 1.0, "vs_baseline": 2.0},
        "darlin": {"block_passes_per_sec": 150.0, "objv": 0.48},
        "spmd_push": {"aggregate_speedup": 4.5},
        "wd_push": {"per_worker_ex_per_sec": 7500.0,
                    "quantized_vs_per_worker": 0.6},
        "ingest": {"parse_mb_per_sec": 400.0,
                   "parse_build_ex_per_sec": 6e5},
        "wire_rpc": {"roundtrips_per_sec": 1200.0, "pull_p50_ms": 0.512,
                     "pull_p99_ms": 2.048, "push_p50_ms": 0.512,
                     "push_p99_ms": 4.096,
                     "push_rps_lockstep": 900.0,
                     "push_rps_pipelined_w8": 2700.0,
                     "pipelined_speedup_w8": 3.0,
                     "mb_s_1mib_pipelined": 850.0,
                     "sweep": {"4KiB": {"lockstep_mb_s": 3.5,
                                        "pipelined_mb_s": 12.0,
                                        "speedup": 3.4}},
                     "wire_bytes_saved": 41000000},
        "server_apply": {"push_rps_serial_w8": 86.2,
                         "push_rps_batched_w8": 284.0,
                         "batched_speedup_w8": 3.61,
                         "push_coalesced": 2346,
                         "push_rps_4k_json": 2629.1,
                         "push_rps_4k_bin": 3621.1,
                         "hdr_speedup_4k": 1.38,
                         "hdr_bytes_saved": 97410},
        "quant_wire": {"push_bytes_ratio_int8": 3.94,
                       "push_bytes_ratio_int16": 1.99,
                       "auc_delta_int8": 0.0001,
                       "auc_delta_int16": 0.0,
                       "holdout_auc_f32": 0.65,
                       "holdout_auc_int8": 0.6501,
                       "push_payload_mb_f32": 1.287,
                       "push_payload_mb_int8": 0.327,
                       "residual_peak_x1e6_int8": 4},
        "backend": {"mesh_vs_socket_push_speedup": 4.2,
                    "crossover_keys_per_push": 1024,
                    "quant_bytes_ratio_int8": 3.8,
                    "auc_delta_int8": 0.0003,
                    "train_ex_per_sec_socket": 2100.0,
                    "train_ex_per_sec_mesh": 9300.0,
                    "train_auc_socket": 0.651,
                    "train_auc_mesh": 0.651,
                    "push_sweep": {"u256": {"speedup": 0.7}}},
    }
    sub.update(sub_overrides or {})
    return {
        "metric": "sparse_lr_ftrl_train_throughput",
        "value": 1.0,
        "unit": "examples/sec",
        "vs_baseline": 1.0,
        "platform": "tpu",
        "raw": {},
        "sub": sub,
        "suite_wall_s": 1.0,
        **top,
    }


class TestCompactContract:
    def test_normal_line_fits_tail_buffer(self):
        line = json.dumps(bench._compact_contract(_full(), "f.json"))
        assert len(line) < 1500
        c = json.loads(line)
        for k in ("metric", "value", "unit", "vs_baseline", "platform",
                  "suite_wall_s", "full_results"):
            assert k in c, k
        assert set(c["sub"]) >= {"e2e", "ladder", "hbm", "scale", "w2v",
                                 "mf", "darlin", "spmd", "wd", "ingest",
                                 "rpc", "srv", "quant", "backend"}
        assert c["sub"]["srv"]["batched_speedup_w8"] == 3.61
        assert c["sub"]["srv"]["hdr_speedup_4k"] == 1.38

    def test_quant_cell_reaches_the_line(self):
        # the quantized wire's acceptance numbers (ISSUE 6) must ride
        # the driver-recorded stdout line, not just the full file
        c = bench._compact_contract(_full(), "f.json")
        assert c["sub"]["quant"] == {
            "push_bytes_ratio_int8": 3.94,
            "auc_delta_int8": 0.0001,
            "holdout_auc_f32": 0.65,
            "holdout_auc_int8": 0.6501,
        }

    def test_backend_cell_reaches_the_line(self):
        # the transport-neutral backend's acceptance numbers (ISSUE 11):
        # mesh-vs-socket push speedup, the crossover point and the
        # quantized-collective ratios must ride the driver-recorded
        # stdout line, not just the full file
        c = bench._compact_contract(_full(), "f.json")
        assert c["sub"]["backend"] == {
            "mesh_vs_socket_push_speedup": 4.2,
            "crossover_keys_per_push": 1024,
            "quant_bytes_ratio_int8": 3.8,
            "auc_delta_int8": 0.0003,
        }

    def test_telemetry_block_reaches_the_line(self):
        c = bench._compact_contract(_full(), "f.json")
        # the telemetry plane's RPC latency AND the pipelined wire's
        # headline ratios must ride the driver-recorded stdout line, not
        # just the full results file
        assert c["sub"]["rpc"] == {
            "roundtrips_per_sec": 1200.0,
            "pull_p50_ms": 0.512,
            "push_p99_ms": 4.096,
            "pipelined_speedup_w8": 3.0,
            "mb_s_1mib_pipelined": 850.0,
        }

    def test_line_still_fits_with_pipelined_fields(self):
        line = json.dumps(bench._compact_contract(_full(), "f.json"))
        assert len(line) < 1500
        c = json.loads(line)
        assert c["sub"]["rpc"]["pipelined_speedup_w8"] == 3.0

    def test_wire_rpc_error_still_fits_and_is_marked(self):
        full = _full(sub_overrides={"wire_rpc": {"error": "boom " * 100}})
        line = json.dumps(bench._compact_contract(full, "f.json"))
        assert len(line) < 1500
        assert "error" in json.loads(line)["sub"]["rpc"]

    def test_every_child_erroring_still_fits(self):
        sub = {k: {"error": "x" * 600} for k in _full()["sub"]}
        full = _full(sub_overrides=sub, error="boom " * 200)
        line = json.dumps(bench._compact_contract(full, "unwritable"))
        assert len(line) < 1500
        c = json.loads(line)
        assert c["value"] == 1.0 and c["platform"] == "tpu"
        assert c["error"].startswith("boom")

    def test_oversize_sub_is_dropped_not_truncated(self):
        # absurdly long platform string pushes past the guard: the sub
        # dict goes, the contract fields stay, the line stays parseable
        full = _full(platform="tpu " + "pad" * 500)
        line = json.dumps(bench._compact_contract(full, "f.json"))
        c = json.loads(line)
        assert "sub" not in c
        assert c["metric"] == "sparse_lr_ftrl_train_throughput"


class TestNoDeviceNoRun:
    """bench measures the chip or nothing: the parent (JAX-free, so the
    probe and the children are monkeypatched) exits non-zero before any
    child starts when the probe does not say "tpu", and after the
    compact line when a device-bound child failed."""

    @pytest.mark.parametrize("probed", ["cpu", None])
    def test_exits_nonzero_before_any_child(self, monkeypatch, capsys, probed):
        monkeypatch.setattr(bench, "_probe_backend", lambda env, timeout_s: probed)
        ran = []
        monkeypatch.setattr(
            bench, "_run_child", lambda *a, **k: ran.append(a) or {}
        )
        assert bench.main() != 0
        assert ran == []
        assert capsys.readouterr().out == ""

    def _run_main(self, monkeypatch, tmp_path, failing):
        monkeypatch.setattr(bench, "_probe_backend", lambda env, timeout_s: "tpu")
        monkeypatch.setenv("PS_BENCH_FULL_OUT", str(tmp_path / "full.json"))

        def child(name, env, timeout_s):
            if name == failing:
                return {"error": "device said no"}
            if name == "headline":
                return {"platform": "tpu", "value": 2.0, "vs_baseline": 3.0}
            return {}

        monkeypatch.setattr(bench, "_run_child", child)
        return bench.main()

    def test_device_child_error_fails_after_the_line(
        self, monkeypatch, tmp_path, capsys
    ):
        assert self._run_main(monkeypatch, tmp_path, "hbm_scale") != 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["value"] == 2.0 and line["platform"] == "tpu"
        assert "error" in line["sub"]["hbm"]

    def test_headline_error_is_not_a_number(self, monkeypatch, tmp_path, capsys):
        assert self._run_main(monkeypatch, tmp_path, "headline") != 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["value"] is None and line["error"] == "device said no"

    def test_cpu_pinned_child_error_does_not_fail_the_suite(
        self, monkeypatch, tmp_path, capsys
    ):
        assert self._run_main(monkeypatch, tmp_path, "wire_rpc") == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert "error" in line["sub"]["rpc"]

    def test_no_force_cpu_outside_the_cpu_sim_env(self):
        import ast

        tree = ast.parse(Path(bench.__file__).read_text())
        callers = {
            fn.name
            for fn in ast.walk(tree)
            if isinstance(fn, ast.FunctionDef)
            for node in ast.walk(fn)
            if isinstance(node, ast.Call)
            and getattr(node.func, "id", "") == "force_cpu"
        }
        assert callers == {"_cpu_sim_env"}
