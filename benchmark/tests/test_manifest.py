"""Every cell of BENCHMARK.json resolves to files that exist, by name; what
is missing is named with the path looked for."""

import os

import pytest

import tiny
from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()
CELLS = [name for name, _ in tiny.all_cells()]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    found = mf.resolve(tiny.manifest_of(cell), cell)
    assert os.path.isfile(found["kind_path"]) and os.path.isfile(found["app_path"])
    assert found["config"]["chips"] == found["cell"]["chips"]
    for k in found["config_entry"]["reduced"]:
        assert k in found["config"]["reduced"], f"{k} has no reason in the configuration file"
    kind = mf.load_module(found["kind_path"], "kind")
    app = mf.load_module(found["app_path"], "app")
    assert callable(kind.run) and hasattr(app, "Session")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_cell_reports_metrics(cell, group):
    manifest = tiny.manifest_of(cell)
    ms = mf.metrics_of(manifest, group, cell)
    assert ms, f"{cell} reports no {group} metric"
    if group == "end_to_end":
        assert "setup_s" in {m["name"] for m in ms} and len(ms) >= 2
    else:
        e2e = {m["name"] for m in mf.metrics_of(manifest, "end_to_end", cell)}
        for m in ms:
            assert m["moves"] in e2e
            assert callable(mf.load_module(mf.metric_path(m["name"]), "reader").read)


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_missing_pieces_name_their_path():
    with pytest.raises(mf.Missing, match="no_such_cell"):
        mf.resolve(MANIFEST, "no_such_cell")
    with pytest.raises(mf.Missing, match="benchmark/layer_metrics/no.such_metric.py"):
        mf.metric_path("no.such_metric")
    broken = {**MANIFEST, "workloads": [{**MANIFEST["workloads"][0], "traffic": "no_such_mix"}]}
    with pytest.raises(mf.Missing, match="benchmark/traffic/no_such_mix.json"):
        mf.resolve(broken, broken["workloads"][0]["name"])


def test_unknown_device_kind_is_an_error():
    from benchmark.harness import device

    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        device.peaks_for("TPU v9 imaginary")
