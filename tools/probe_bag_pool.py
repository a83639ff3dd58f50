"""The multi-hot DLRM's pooled read alone, on the chip (ISSUE 52: how the
bags are summed is chosen on the chip; PERF.md section 6, PR 52): the take
of a minibatch's 8,192 x 214 bag positions out of the pulled rows
f32[2^20,128], the sum of each field's bag into (8192, 26, 128), and the
backward pass (the fields' cotangents scatter-added into the pulled rows'
gradient), at ``dcn1tb.train``'s shapes and bag sizes, host clock, ten
calls back to back, three sets. Forms:

  ``slices``     one take, then a static slice and a sum a field
                 (``models.dlrm.pool_bags``: the step's)
  ``selection``  one take, then one 0/1 selection product (214 x 26) at
                 ``precision=highest``, as ``models.dlrm._pairs`` cuts its pairs
  ``by_field``   a take and a sum a field: no (8192, 214, 128) array is asked for
  ``segment``    one ``segment_sum`` of the gathered rows by (example, field)

Every form's forward is checked against NumPy's on a sample of examples, its
backward against the ``slices`` form's. One JSON line a form, also appended
to chiprun_out/probe_bag_pool.jsonl.

    chiprun --timeout 900 -- python3 tools/probe_bag_pool.py [SEED]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models.dlrm import pool_bags

SEED = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 2520000901
HOT = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)
B, U, REAL, D = 8192, 1 << 20, 627_000, 128
SMALL = "--small" in sys.argv  # a CPU rehearsal of the script
if SMALL:
    B, U, REAL = 64, 1 << 12, 3000
HI = jax.lax.Precision.HIGHEST
STARTS = np.concatenate([[0], np.cumsum(HOT)[:-1]])
FIELD_OF = np.repeat(np.arange(len(HOT)), HOT)


def slices(pulled, slots):
    return pool_bags(jnp.take(pulled, slots, axis=0), HOT)


def selection(pulled, slots):
    sel = np.zeros((sum(HOT), len(HOT)), np.float32)
    sel[np.arange(sum(HOT)), FIELD_OF] = 1.0
    return jnp.einsum("bhd,hf->bfd", jnp.take(pulled, slots, axis=0), sel, precision=HI)


def by_field(pulled, slots):
    out = []
    for at, h in zip(STARTS, HOT):
        rows = jnp.take(pulled, slots[:, at : at + h], axis=0)
        out.append(rows[:, 0] if h == 1 else rows.sum(axis=1))
    return jnp.stack(out, axis=1)


def segment(pulled, slots):
    seg = (np.arange(B)[:, None] * len(HOT) + FIELD_OF[None, :]).reshape(-1)
    rows = jnp.take(pulled, slots.reshape(-1), axis=0)
    return jax.ops.segment_sum(rows, jnp.asarray(seg, jnp.int32), num_segments=B * len(HOT),
                               indices_are_sorted=True).reshape(B, len(HOT), D)


def timed(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    best = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(10):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t) / 10)
    return 1e3 * min(best), out


def main():
    rng = np.random.default_rng(SEED)
    pulled = jnp.asarray(rng.normal(size=(U, D)).astype(np.float32))
    slots_h = rng.integers(1, REAL, size=(B, sum(HOT))).astype(np.int32)
    slots = jnp.asarray(slots_h)
    ct = jnp.asarray(rng.normal(size=(B, len(HOT), D)).astype(np.float32))
    dev = jax.devices()[0]
    want_bwd = None
    sample = np.arange(0, B, max(B // 16, 1))
    host = np.asarray(pulled)
    want_fwd = np.stack([
        np.stack([host[slots_h[i, a : a + h]].sum(axis=0, dtype=np.float64) for a, h in zip(STARTS, HOT)]) for i in sample
    ])
    for name, form in (("slices", slices), ("selection", selection), ("by_field", by_field), ("segment", segment)):
        fwd = jax.jit(form)
        both = jax.jit(lambda p, s, c, form=form: jax.vjp(lambda q: form(q, s), p)[1](c)[0])
        line = {"form": name, "seed": SEED, "device": dev.device_kind, "B": B, "slots": U, "ids": sum(HOT)}
        try:
            line["forward_ms"], out = timed(fwd, pulled, slots)
            line["forward_and_backward_ms"], g = timed(both, pulled, slots, ct)
            line["forward_max_gap"] = float(np.abs(np.asarray(out)[sample] - want_fwd).max())
            if want_bwd is None:
                want_bwd = np.asarray(g)
            line["backward_max_gap_to_slices"] = float(np.abs(np.asarray(g) - want_bwd).max())
            mem = both.lower(pulled, slots, ct).compile().memory_analysis()
            line["temp_gib"] = round(mem.temp_size_in_bytes / 2**30, 3)
        except Exception as e:  # noqa: BLE001 - a form the compiler refuses is a reading too
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_bag_pool.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
