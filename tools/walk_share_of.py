"""Median, least and largest of the counters ``push.walk_share``,
``grad.walk_share`` and ``feed.unique_fill`` in a ``PS_TRACE_DIR`` capture
(PERF.md section 6, PRs 44 and 47): one JSON line. No chip.

    PS_TRACE_DIR=chiprun_out/pstrace python3 benchmark/run.py --workload ctr1.train ...
    python3 tools/walk_share_of.py chiprun_out/pstrace
"""
import json, os, statistics, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from parameter_server_tpu.utils import trace

events, _ = trace.read_trace_dir(sys.argv[1])
out = {"trace_dir": sys.argv[1]}
for name in ("push.walk_share", "grad.walk_share", "feed.unique_fill"):
    v = [e["args"]["value"] for e in events if e.get("ph") == "C" and e.get("name") == name]
    out[name] = {"samples": len(v), "median": statistics.median(v), "min": min(v), "max": max(v)} if v else None
print(json.dumps(out))
