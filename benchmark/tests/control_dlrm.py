#!/usr/bin/env python3
"""``dlrm1tb.train``'s second control at the cell's own size, beside
``control.py``'s (the reference all in bfloat16): the reference with only
its matrix products' operands in bfloat16 and float32 sums, which is what
the chip does to a float32 product that is not asked for
``precision=highest``, put in the program's place. It must fail at least
one limit on every seed, or the limits do not hold the program to the
precision the configuration states. Prints every compared number as the
control reads it beside the cell's limit. No chip in it; about 2.5 min a
seed.

    python3 benchmark/tests/control_dlrm.py 2490000301 2490000302
"""

import sys

from control import control_of

CELL = "dlrm1tb.train"


def main() -> int:
    failed_every_seed = True
    for seed in [int(s) for s in sys.argv[1:]] or [11, 12, 13]:
        numbers, limits = control_of(CELL, seed, "bfloat16_products")
        fails = [n for n, v in numbers.items() if n in limits and not v <= limits[n]]
        for n, v in numbers.items():
            if n in limits:
                print(f"[control] {CELL} seed {seed}, products' operands in bfloat16: {n}: {v:.6g} "
                      f"(limit {limits[n]:.6g}) {'fails' if n in fails else 'passes'}", flush=True)
        failed_every_seed &= bool(fails)
    print("control comes out as not correct on every seed" if failed_every_seed else "CONTROL PASSED ON SOME SEED")
    return 0 if failed_every_seed else 1


if __name__ == "__main__":
    sys.exit(main())
