#!/usr/bin/env python3
"""Cut a recorded ``*.xplane.pb`` down to a file small enough to commit:
the device planes' ``XLA Ops`` and ``XLA Modules`` events between the
first ``bench.window_open`` mark and ``--seconds`` later, and every
``bench.*`` host mark. Names and times are kept as recorded.

    python3 benchmark/tests/shrink_trace.py in.xplane.pb out.xplane.pb --seconds 3.5
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import xtrace  # noqa: E402


def _esc(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--seconds", type=float, default=3.5)
    a = ap.parse_args()
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(a.src)
    marks = xtrace.collect_marks(prof)
    t0 = marks["bench.window_open"][0]
    t1 = t0 + a.seconds * 1e9
    out, pid = [], 0
    for plane in prof.planes:
        device = xtrace.is_device_plane(plane.name)
        names: dict = {}
        lines = []
        for lid, line in enumerate(plane.lines):
            if device and line.name not in (xtrace.OPS_LINE, xtrace.MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                keep = (
                    t0 - 1e6 <= ev.start_ns <= t1 + 1e6 if device
                    else ev.name.startswith(xtrace.MARK_PREFIX) and ev.start_ns <= t1 + 1e6
                )
                if keep:
                    mid = names.setdefault(ev.name, len(names) + 1)
                    evs.append(
                        f"events {{ metadata_id: {mid} offset_ps: {int(ev.start_ns * 1000)} "
                        f"duration_ps: {int(ev.duration_ns * 1000)} }}"
                    )
            if evs:
                lines.append(f'lines {{ id: {lid + 1} name: "{_esc(line.name)}" timestamp_ns: 0 ' + " ".join(evs) + " }")
        if lines:
            pid += 1
            meta = " ".join(
                f'event_metadata {{ key: {i} value {{ id: {i} name: "{_esc(n)}" }} }}' for n, i in names.items()
            )
            out.append(f'planes {{ id: {pid} name: "{_esc(plane.name)}" {meta} ' + " ".join(lines) + " }")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(a.dst, "wb") as f:
        f.write(blob)
    print(f"{a.dst}: {len(blob)} bytes, {len(out)} planes, window {a.seconds} s from the first mark")
    return 0


if __name__ == "__main__":
    sys.exit(main())
