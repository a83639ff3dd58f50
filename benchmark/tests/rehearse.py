#!/usr/bin/env python3
"""Rehearse one cell end to end on the CPU at a tiny table:

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py ctr1.train
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 benchmark/tests/rehearse.py ctr2x2.train

Prints the window, the stamps' count and the checks; no device metric."""

import sys

from tiny import tiny_ctx


def main() -> int:
    workload = sys.argv[1]
    seconds = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0
    ctx, kind, app = tiny_ctx(workload, seconds=seconds)
    rec = kind.run(ctx, app)
    print({k: v for k, v in rec["window"].items()})
    print(f"{len(rec['stamps'])} stamps; attempted {rec['attempted']} failed {rec['failed']}")
    for c in rec["checks"]:
        print(c.line())
    return 0 if all(c.ok for c in rec["checks"]) and rec["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
