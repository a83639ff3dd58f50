#!/usr/bin/env python3
"""What the host was doing while the chip idled, read off a kept trace.

    python3 benchmark/run.py --workload ctr1.eval --seed 7 --seconds 20 --trace 1 --keep-trace
    python3 tools/host_gaps.py .bench_work/ctr1.eval/trace [--gaps 10] [--top 6]

``trace.phase`` puts every host phase of the program (``reader.*``,
``feed.*``, ``trainer.*``, ``eval.*``) into the profiler's ``.xplane.pb`` on
the device's clock, beside the benchmark's ``bench.*`` marks and, where the
profiler traces Python, one event a call (``$file.py:line function``). For
the window between the ``bench.window_open`` and ``bench.window_close`` marks
this prints

(a) seconds by phase name and thread role. A role is the set of layers whose
    phases a thread carries (``bench+eval``: the evaluator's caller;
    ``feed+reader``: a training stream's producer thread, which builds), and
    a ``MinibatchReader``'s own threads, a new pair (evaluation) or one
    (training) every file set or pass, go by their stage, merged into one
    row a stage: ``reader/parse`` and, under ``iter(reader)``,
    ``reader/build``. So the two stages' busy shares of a ``ctr1.eval``
    window are read here, where the ``eval`` kind takes no timer snapshot,
    and which of them paces a pass: ``reader.parsed_wait`` (the build
    thread's alone) says the parse, ``reader.put_wait`` under
    ``reader/parse`` the build, under ``reader/build`` the caller. These
    are wall seconds: how many of them a thread was on a CPU is on a traced
    train run's ``[timers]`` line, ``<phase>.cpu`` beside ``<phase>``;
(b) for the N longest idle gaps of chip 0, every such thread's phases that
    overlap the gap, by seconds of overlap, the share of the gap they cover
    (under 90%: the thread was in no span there), and the events of any kind
    the thread itself spent the gap in (self time: an event's overlap less
    its children's), which names what a thread in no span was doing.

Run by no cell; it reads the trace with the benchmark's own reduction
(``benchmark.harness.xtrace``).
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import xtrace  # noqa: E402

# a ``trace.phase`` or a ``bench.*`` mark: "<layer>.<what>", lower case words;
# XLA's own host events ("PjitFunction(f)", on the CPU backend its numbered
# instructions, "fusion.6") and the Python tracer's ("$...") are spelled otherwise
PHASE = re.compile(r"^[a-z][a-z_]*(\.[a-z][a-z0-9_]*)+$")
IN_A_SPAN = 0.9  # of a gap: under it the thread is reported as in no span
NO_EVENT = "(no event)"


@dataclass
class Thread:
    """One host thread's events inside the window, times in ns."""

    line: str
    names: list
    start: np.ndarray
    end: np.ndarray
    phase: np.ndarray  # which of the events are phases
    role: str = ""


def host_threads(profile, t0: float, t1: float) -> list:
    """The host threads that carry a phase inside [t0, t1), their events
    clipped to it, labelled ``<role>#<k>``: a role is the layers of the
    thread's phases (``bench+eval``, ``feed``, ...), a reader's own thread
    by its stage (``reader/parse``, ``reader/build``; plain ``reader`` where
    one thread carries both or neither)."""
    out: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names, start, end = [], [], []
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if e < t0 or s > t1:
                    continue
                names.append(ev.name)
                start.append(max(s, t0))
                end.append(min(e, t1))
            phase = np.array([bool(PHASE.match(n)) for n in names], dtype=bool)
            layers = sorted({n.split(".")[0] for n, is_phase in zip(names, phase) if is_phase})
            if layers:
                role = "+".join(layers)
                stages = {"reader.parse", "reader.build"}.intersection(names)
                if role == "reader" and len(stages) == 1:
                    role = stages.pop().replace(".", "/")
                out.append(Thread(line.name, names, np.array(start), np.array(end), phase, role))
    seen: dict = {}
    for th in sorted(out, key=lambda th: float(th.start.min())):
        k = seen[th.role] = seen.get(th.role, -1) + 1
        th.line = f"{th.role}#{k}"
    return out


def _overlap(th: Thread, lo: float, hi: float, phases_only: bool = False):
    """(indices, clipped starts, clipped ends) of the events that overlap
    [lo, hi); an instant mark inside it counts, with no length."""
    instant = th.start == th.end
    hit = (th.start < hi) & ((th.end > lo) | (instant & (th.start >= lo)))
    if phases_only:
        hit &= th.phase
    hit = np.nonzero(hit)[0]
    return hit, np.maximum(th.start[hit], lo), np.minimum(th.end[hit], hi)


def phase_seconds(th: Thread, lo: float, hi: float) -> dict:
    """{phase name: [seconds inside [lo, hi), events]}: each phase whole, so
    one that encloses another counts the other's time too."""
    out: dict = {}
    for i, a, b in zip(*_overlap(th, lo, hi, phases_only=True)):
        acc = out.setdefault(th.names[i], [0.0, 0])
        acc[0] += (b - a) / 1e9
        acc[1] += 1
    return out


def covered_seconds(th: Thread, lo: float, hi: float) -> float:
    """Seconds of [lo, hi) during which the thread was inside any phase."""
    _, s, e = _overlap(th, lo, hi, phases_only=True)
    busy, _ = xtrace.union_seconds(list(zip(s, e)))
    return busy / 1e9


def self_seconds(th: Thread, lo: float, hi: float) -> dict:
    """{event name: seconds of [lo, hi) the thread spent in that event and
    in none of the events it encloses}, over events of every kind; the time
    in no event at all is under ``NO_EVENT``. A thread's events nest (they
    are a call tree), which is what the sweep relies on."""
    hit, s, e = _overlap(th, lo, hi)
    order = sorted(range(len(hit)), key=lambda k: (s[k], -e[k]))
    out: dict = {}
    stack: list = []  # (name, end) of the open events, outermost first
    at = lo

    def spend(until: float) -> None:
        nonlocal at
        if until > at:
            name = stack[-1][0] if stack else NO_EVENT
            out[name] = out.get(name, 0.0) + (until - at) / 1e9
            at = until

    for k in order:
        while stack and stack[-1][1] <= s[k]:
            spend(stack[-1][1])
            stack.pop()
        spend(s[k])
        stack.append((th.names[hit[k]], e[k]))
    while stack:
        spend(stack[-1][1])
        stack.pop()
    spend(hi)
    return out


def by_role(threads: list, t0: float, t1: float) -> dict:
    """Reduction (a): {role: {"threads": n, "covered_s": seconds inside any
    phase, "phases": {name: [seconds, events]}}} over the window."""
    out: dict = {}
    for th in threads:
        row = out.setdefault(th.role, {"threads": 0, "covered_s": 0.0, "phases": {}})
        row["threads"] += 1
        row["covered_s"] += covered_seconds(th, t0, t1)
        for name, (sec, n) in phase_seconds(th, t0, t1).items():
            acc = row["phases"].setdefault(name, [0.0, 0])
            acc[0] += sec
            acc[1] += n
    return out


def gap_table(threads: list, gaps: list, t0: float, top: int = 6) -> list:
    """Reduction (b): for each gap ``(start_s, seconds)`` of chip 0 (window
    relative, as ``Reduced.gaps`` has them) the threads alive around it: their
    phases by overlap, the share of the gap those cover, whether that leaves
    the thread in no span, and its events of any kind by self time."""
    rows = []
    for start_s, seconds in gaps:
        lo = t0 + start_s * 1e9
        hi = lo + seconds * 1e9
        per_thread = []
        for th in threads:
            if float(th.start.min()) >= hi or float(th.end.max()) <= lo:
                continue  # a thread that began later or had ended: a reader of another file or pass
            phases = sorted(phase_seconds(th, lo, hi).items(), key=lambda kv: -kv[1][0])
            doing = sorted(self_seconds(th, lo, hi).items(), key=lambda kv: -kv[1])
            share = covered_seconds(th, lo, hi) / seconds
            per_thread.append({
                "thread": th.line,
                "covered_share": share,
                "in_no_span": share < IN_A_SPAN,
                "phases": [(n, sec) for n, (sec, _) in phases[:top]],
                "doing": doing[:top],
            })
        rows.append({"start_s": start_s, "seconds": seconds, "threads": per_thread})
    return rows


def report(profile, n_gaps: int, top: int) -> list:
    marks = xtrace.collect_marks(profile)
    t0 = marks["bench.window_open"][0]
    # a trace cut short of its closing mark (a recording kept for tests) ends at its last mark
    t1 = marks.get("bench.window_close", [max(map(max, marks.values()))])[-1]
    reduced = xtrace.reduce_window(profile, t0, t1)
    threads = host_threads(profile, t0, t1)
    window = reduced.window_s
    lines = [
        f"window {window:.4f} s, {reduced.chips} chip(s), busy {reduced.busy_s:.4f} s a chip "
        f"(idle {100 * (1 - reduced.busy_s / window):.3f}%), {len(reduced.gaps)} gaps on chip 0",
        "", "(a) seconds by phase and thread role (a phase that encloses another counts it too)",
    ]
    for role, row in sorted(by_role(threads, t0, t1).items()):
        lines.append(
            f"  {role}: {row['threads']} thread(s), inside a phase {row['covered_s']:.4f} s "
            f"= {100 * row['covered_s'] / window:.2f}% of the window"
        )
        for name, (sec, n) in sorted(row["phases"].items(), key=lambda kv: -kv[1][0]):
            per = f"{1e3 * sec / n:10.3f} ms each" if n else ""
            lines.append(f"    {name:24s} {sec:10.4f} s {100 * sec / window:7.2f}% {n:8d} x {per}")
    lines += ["", f"(b) the {n_gaps} longest idle gaps of chip 0, by what each host thread was in"]
    for i, row in enumerate(gap_table(threads, reduced.gaps[:n_gaps], t0, top), 1):
        lines.append(f"  gap {i}: {row['seconds']:.6f} s at +{row['start_s']:.6f} s")
        for th in row["threads"]:
            verdict = "IN NO SPAN for %.6f s" % ((1 - th["covered_share"]) * row["seconds"]) if th["in_no_span"] else "in a span"
            lines.append(f"    {th['thread']}: phases cover {100 * th['covered_share']:.1f}% ({verdict})")
            if th["phases"]:
                lines.append("      phases: " + ", ".join(f"{n} {sec:.6f}" for n, sec in th["phases"]))
            lines.append("      itself: " + ", ".join(f"{n[:60]} {sec:.6f}" for n, sec in th["doing"]))
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a *.xplane.pb, or the directory a traced run kept (.bench_work/<cell>/trace)")
    ap.add_argument("--gaps", type=int, default=10, help="how many of chip 0's longest gaps")
    ap.add_argument("--top", type=int, default=6, help="how many names a thread and gap")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) else xtrace.find_xplane(args.trace)
    print(f"{path}: {os.path.getsize(path)} bytes")
    print("\n".join(report(xtrace.load(path), args.gaps, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
