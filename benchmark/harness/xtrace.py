"""From a JAX profiler trace (``*.xplane.pb``) to the numbers the per-layer
readers and the ``device`` block use. Read with nothing but JAX.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Ops`` (one event per executed HLO
op, named by the HLO) and a line ``XLA Modules`` (one event per executed
program); host threads live in ``/host:CPU``, where the
``jax.profiler.TraceAnnotation`` marks the benchmark sets around its own
calls appear by name. All timestamps share one nanosecond timeline.

The reduction keeps only what falls between two marks (the timed window):
  busy_s      union of the op intervals, per chip and averaged
  ops         per HLO name: summed seconds and count, over the chips
  gaps        the idle intervals of chip 0, longest first
  modules     per program name: summed seconds and count on chip 0
  collectives seconds of collective ops, and the part of them during
              which no other op ran on that chip ("exposed")
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench."
COLLECTIVE_WORDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


@dataclass
class Reduced:
    window_s: float = 0.0
    busy_s: float = 0.0  # mean over chips
    busy_by_chip: list = field(default_factory=list)
    ops: dict = field(default_factory=dict)  # name -> [seconds, count]
    modules: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)  # (start_s, seconds) on chip 0, window-relative
    collective_s: float = 0.0  # mean over chips
    collective_exposed_s: float = 0.0
    marks: dict = field(default_factory=dict)  # name -> [ns, ...]
    chips: int = 0


def find_xplane(profile_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}/plugins/profile")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def collect_marks(profile) -> dict:
    """Every host event whose name starts with ``bench.``: name -> sorted
    start stamps in ns."""
    marks: dict = {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(MARK_PREFIX):
                    marks.setdefault(ev.name, []).append(float(ev.start_ns))
    return {k: sorted(v) for k, v in marks.items()}


def union_seconds(intervals: list) -> tuple[float, list]:
    """Length of the union of [start, end) intervals, and the gaps between
    its pieces as (start, length); everything in the unit it was given."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def _clip(events, t0: float, t1: float):
    for ev in events:
        s, e = float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)
        if e <= t0 or s >= t1:
            continue
        yield ev.name, max(s, t0), min(e, t1)


_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s(]")


def is_container(name: str) -> bool:
    """A control-flow op whose event spans its body's ops: its time is
    theirs, so it counts towards busy time and not towards the op sums."""
    return bool(_CONTAINER.match(name))


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(w in low for w in COLLECTIVE_WORDS)


def reduce_window(profile, t0_ns: float, t1_ns: float) -> Reduced:
    """Reduce the device planes of ``profile`` over [t0_ns, t1_ns)."""
    out = Reduced(window_s=(t1_ns - t0_ns) / 1e9, marks=collect_marks(profile))
    coll, exposed = [], []
    for plane in profile.planes:
        if not is_device_plane(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        first = out.chips == 0
        out.chips += 1
        ivs, c_ivs, other_ivs = [], [], []
        for name, s, e in _clip(lines[OPS_LINE].events, t0_ns, t1_ns):
            ivs.append((s, e))
            if is_container(name):
                continue
            acc = out.ops.setdefault(name, [0.0, 0])
            acc[0] += (e - s) / 1e9
            acc[1] += 1
            (c_ivs if is_collective(name) else other_ivs).append((s, e))
        busy, gaps = union_seconds(ivs)
        # the edges of the window are idle time too
        if ivs:
            lo, hi = min(s for s, _ in ivs), max(e for _, e in ivs)
            gaps = [(t0_ns, lo - t0_ns)] + gaps + [(hi, t1_ns - hi)]
        else:
            gaps = [(t0_ns, t1_ns - t0_ns)]
        out.busy_by_chip.append(busy / 1e9)
        c_total, _ = union_seconds(c_ivs)
        both, _ = union_seconds(c_ivs + other_ivs)
        o_total, _ = union_seconds(other_ivs)
        coll.append(c_total / 1e9)
        exposed.append((both - o_total) / 1e9)
        if first:
            out.gaps = sorted(
                (((s - t0_ns) / 1e9, d / 1e9) for s, d in gaps if d > 0),
                key=lambda g: -g[1],
            )
            if MODULES_LINE in lines:
                for name, s, e in _clip(lines[MODULES_LINE].events, t0_ns, t1_ns):
                    acc = out.modules.setdefault(name, [0.0, 0])
                    acc[0] += (e - s) / 1e9
                    acc[1] += 1
    if out.chips:
        out.busy_s = sum(out.busy_by_chip) / out.chips
        out.collective_s = sum(coll) / out.chips
        out.collective_exposed_s = sum(exposed) / out.chips
    return out


def describe(profile) -> list[str]:
    """Planes, lines and event counts: what to look at by hand first."""
    rows = []
    for plane in profile.planes:
        for line in plane.lines:
            evs = list(line.events)
            head = evs[0].name[:60] if evs else ""
            rows.append(f"{plane.name} | {line.name} | {len(evs)} events | {head}")
    return rows
