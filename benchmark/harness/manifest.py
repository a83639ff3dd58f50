"""Resolve a cell of ``BENCHMARK.json`` to its files, by name and with no
table of names in code. Whatever is missing is an error that names the
path looked for."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class Missing(LookupError):
    pass


def _need(path: str, what: str) -> str:
    if not os.path.isfile(path):
        raise Missing(f"{what}: no file {os.path.relpath(path, ROOT)}")
    return path


PARKED = os.path.join("benchmark", "parked.json")  # cells kept out of BENCHMARK.json, same schema


def load_manifest(root: str = ROOT, path: str = "BENCHMARK.json") -> dict:
    with open(_need(os.path.join(root, path), "the manifest")) as f:
        return json.load(f)


def load_json(path: str, what: str) -> dict:
    with open(_need(path, what)) as f:
        return json.load(f)


def load_module(path: str, what: str):
    """A module by file path: metric names carry dots, so they are never
    module names."""
    _need(path, what)
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise Missing(f"{what} {name!r} is not in BENCHMARK.json (has: {[i['name'] for i in items]})")


def resolve(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """cell -> {cell, config entry, config, traffic, kind module path, app
    module path}. Loads the two JSON files, not the modules."""
    cell = entry(manifest["workloads"], workload, "workload")
    cfg_entry = entry(manifest["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(root, cfg_entry["file"]), f"configuration {cell['config']!r}")
    traffic = load_json(
        os.path.join(root, "benchmark", "traffic", cell["traffic"] + ".json"),
        f"traffic mix {cell['traffic']!r}",
    )
    return {
        "cell": cell,
        "config_entry": cfg_entry,
        "config": config,
        "traffic": traffic,
        "kind_path": _need(
            os.path.join(root, "benchmark", "traffic_kinds", traffic["kind"] + ".py"),
            f"traffic kind {traffic['kind']!r} of mix {cell['traffic']!r}",
        ),
        "app_path": _need(
            os.path.join(root, "benchmark", "apps", config["app"] + ".py"),
            f"app {config['app']!r} of configuration {cell['config']!r}",
        ),
    }


def metric_path(name: str, root: str = ROOT) -> str:
    return _need(
        os.path.join(root, "benchmark", "layer_metrics", name + ".py"),
        f"per-layer metric {name!r}",
    )


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of ``group`` (end_to_end | per_layer) that ``workload``
    reports: those that list it, and those that list no cells at all and
    move an end-to-end metric the cell reports."""
    if group == "end_to_end":
        return [m for m in manifest[group] if workload in m.get("workloads", [workload])]
    e2e = {m["name"] for m in metrics_of(manifest, "end_to_end", workload)}
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out
