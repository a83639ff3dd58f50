"""Put one traced run's record together for the per-layer readers, and
make the ``breakdown`` the ledger keeps: the device operations that took
most time, and the longest idle gaps by what the host was doing."""

from __future__ import annotations

import re
import shutil

from benchmark.harness import xtrace

TOP = 10


def timers_delta(open_snap: dict, close_snap: dict) -> dict:
    out = {}
    for name, after in (close_snap or {}).items():
        before = (open_snap or {}).get(name, {"total_s": 0.0, "count": 0})
        out[name] = {
            "total_s": after["total_s"] - before["total_s"],
            "count": after["count"] - before["count"],
        }
    return out


def assemble(ctx, rec: dict, device: dict, peaks: dict, compiles) -> dict:
    profile = xtrace.load(xtrace.find_xplane(ctx.trace_dir))
    marks = xtrace.collect_marks(profile)
    try:
        t0 = marks["bench.window_open"][0]
        t1 = marks["bench.window_close"][-1]
    except (KeyError, IndexError):
        raise RuntimeError(
            f"the trace holds no window marks (found {sorted(marks)}): "
            "the benchmark's TraceAnnotations did not reach the profile"
        ) from None
    reduced = xtrace.reduce_window(profile, t0, t1)
    if reduced.chips == 0 or reduced.busy_s <= 0:
        raise RuntimeError("the trace shows no operation on any device inside the window")
    if not ctx.keep_trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    win = rec["window"]
    return {
        "cell": ctx.cell,
        "config": ctx.config,
        "traffic": ctx.traffic,
        "window": win,
        "facts": rec.get("facts", {}),
        "timers": timers_delta(rec.get("timers_open"), rec.get("timers_close")),
        "counters": rec.get("counters", {}),
        "trace": reduced,
        "device": device,
        "peaks": peaks,
        "compiles_in_window": compiles.count_between(win["t_open"], win["t_close"]),
    }


def _clean(name: str) -> str:
    """An op name the ledger can hold: letters, digits, '_', '.', '-'."""
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:64]


def label_gap(start_s: float, seconds: float, reduced, mode: str) -> str:
    """What the host was doing in an idle gap of chip 0, from the marks the
    benchmark set around its own calls."""
    t0 = reduced.marks.get("bench.window_open", [0.0])[0]
    at = t0 + start_s * 1e9
    end = at + seconds * 1e9
    def near(name: str, slack_ns: float = 5e5) -> bool:
        return any(at - slack_ns <= m <= end + slack_ns for m in reduced.marks.get(name, []))
    if mode == "eval":
        if near("bench.pass_end") or near("bench.window_open"):
            return "between_passes__drain_AUC_then_reader_builder_first_dispatch"
        return "inside_a_pass__between_predict_calls"
    if mode == "wire":
        return "server_host__rpc_decode_coalesce_encode__or_clients_thinking"
    if near("bench.retire"):
        return "between_device_calls__a_retire_within_0.5_ms"
    return "inside_a_device_call__between_its_ops"


def breakdown(run: dict) -> dict:
    reduced = run["trace"]
    ops = sorted(reduced.ops.items(), key=lambda kv: -kv[1][0])[:TOP]
    mode = run["facts"].get("mode", "train")
    return {
        # seconds a chip: the sums run over every chip's plane
        "device_ops": [[_clean(name), sec / reduced.chips] for name, (sec, _) in ops],
        "idle_gaps": [
            [label_gap(s, d, reduced, mode), d] for s, d in reduced.gaps[:TOP]
        ],
    }
