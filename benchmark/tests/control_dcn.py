#!/usr/bin/env python3
"""``dcn1tb.train``'s two controls at the cell's own size: the reference
all in bfloat16, and the reference with only its matrix products' operands
in bfloat16 (what the chip does to a float32 product that is not asked for
``precision=highest``), each put in the program's place. Each must fail at
least one limit on every seed, or the limits do not hold the program to the
precision the configuration states. Prints every compared number as the
control reads it beside the cell's limit. No chip in it; about 11 GB of
host memory and a few minutes a seed and control.

    python3 benchmark/tests/control_dcn.py bfloat16_products 2520000301 2520000302
"""

import sys

from control import control_of

CELL = "dcn1tb.train"


def main() -> int:
    precision, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]] or [11, 12, 13]
    failed_every_seed = True
    for seed in seeds:
        numbers, limits = control_of(CELL, seed, precision)
        fails = [n for n, v in numbers.items() if n in limits and not v <= limits[n]]
        for n, v in numbers.items():
            if n in limits:
                print(f"[control] {CELL} seed {seed}, {precision}: {n}: {v:.6g} "
                      f"(limit {limits[n]:.6g}) {'fails' if n in fails else 'passes'}", flush=True)
        failed_every_seed &= bool(fails)
    print("control comes out as not correct on every seed" if failed_every_seed else "CONTROL PASSED ON SOME SEED")
    return 0 if failed_every_seed else 1


if __name__ == "__main__":
    sys.exit(main())
