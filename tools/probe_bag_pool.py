"""The multi-hot DLRM's pooled read alone, on the chip (ISSUE 52: how the
bags are summed is chosen on the chip; PERF.md section 6, PR 52): the take
of a minibatch's 8,192 x 214 bag positions out of the pulled rows
f32[2^20,128], the sum of each field's bag into (8192, 26, 128), and the
backward pass (the fields' cotangents scatter-added into the pulled rows'
gradient), at ``dcn1tb.train``'s shapes and bag sizes, host clock, ten
calls back to back, three sets. Forms:

  ``slices``     one take of (B, 214) slots, then a static slice and a sum a
                 field, ``jax.grad`` throughout: the plain form (the step's
                 until PR 53), which the others are checked against
  ``planes``     the step's since PR 53 (``models.dlrm.read_bags``): the take by
                 the TURNED slots, so that the rows lie position-major,
                 (214, B, d), and ``pool_bags`` over them (sums of
                 leading-axis runs, a hand-written backward pass)
  ``selection``  one take, then one 0/1 selection product (214 x 26) at
                 ``precision=highest``, as ``models.dlrm._pairs`` cuts its pairs
  ``by_field``   a take and a sum a field: no (8192, 214, 128) array is asked for
  ``segment``    one ``segment_sum`` of the gathered rows by (example, field)

Every form's forward is checked against NumPy's on a sample of examples, its
backward against the ``slices`` form's. One JSON line a form, also appended
to chiprun_out/probe_bag_pool.jsonl: ``forward_ms``, and ``backward_ms``, the
transpose ALONE (the cotangent's write and the scatter-add: the read is
linear in the pulled rows, so XLA drops the forward pass from its ``vjp``;
until PR 53 this number went by ``forward_and_backward_ms``, which it never
was: ``slices`` read 26.3 and 37.8, and the cell's ``step.pool_ms`` 64.4 is
their SUM). ``temp_gib`` is the backward program's.

``--zipf`` draws the slots from the cell's own data (``benchmark/harness/
criteo.py``'s examples under ``benchmark/configs/dlrm_dcnv2_mh_1chip.json``,
the reference's bags): a field's first id Zipf 1.1 over the source's
cardinality folded by the cap, its bag mates uniform over the field's table
and fixed an id, 623,000-631,000 distinct rows. Without it every slot is
uniform over 627,000 and no row repeats as the hot ids' do; on the chip the
difference is 1.2 ms of ``slices``' backward pass (39.0 for 37.8; PERF.md
section 6, PR 53).

    chiprun --timeout 900 -- python3 tools/probe_bag_pool.py [SEED] [--zipf]
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models.dlrm import read_bags

SEED = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 2520000901
HOT = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1)
B, U, REAL, D = 8192, 1 << 20, 627_000, 128
SMALL = "--small" in sys.argv  # a CPU rehearsal of the script
ZIPF = "--zipf" in sys.argv
if SMALL:
    B, U, REAL = 64, 1 << 12, 3000
HI = jax.lax.Precision.HIGHEST
STARTS = np.concatenate([[0], np.cumsum(HOT)[:-1]])
FIELD_OF = np.repeat(np.arange(len(HOT)), HOT)


def slices(pulled, slots):
    rows = jnp.take(pulled, slots, axis=0)
    return jnp.stack([
        rows[:, at] if h == 1 else rows[:, at : at + h].sum(axis=1) for at, h in zip(STARTS, HOT)
    ], axis=1)


def planes(pulled, slots):
    return read_bags(pulled, slots, HOT)


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


def zipf_slots(seed: int) -> tuple[np.ndarray, int]:
    """((B, 214) slots, distinct rows) of one minibatch of the cell's own
    data: the benchmark's generator and its reference's bags under the
    configuration's settings; a slot is a row's rank among the minibatch's
    distinct rows, behind slot 0 (the pad)."""
    from benchmark.harness import criteo, ref_dlrm_dcn

    with open(os.path.join(ROOT, "benchmark", "configs", "dlrm_dcnv2_mh_1chip.json")) as f:
        config = json.load(f)
    st = config["settings"]
    assert tuple(st["hot"]) == HOT, st["hot"]
    field_rows = [min(r, 40) for r in st["field_rows"]] if SMALL else st["field_rows"]
    _, ints, cats = criteo.make_examples(seed, B, config["data"])
    bags, _ = ref_dlrm_dcn.features(ints, cats, field_rows, HOT, st["bag_seed"])
    distinct, slots = np.unique(np.concatenate(bags, axis=1), return_inverse=True)
    return (1 + slots.reshape(B, sum(HOT))).astype(np.int32), len(distinct)


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
    slots_h, real = rng.integers(1, REAL, size=(B, sum(HOT))).astype(np.int32), REAL
    if ZIPF:
        slots_h, real = zipf_slots(SEED)
        assert real < U, real
    slots = jnp.asarray(slots_h)
    ct = jnp.asarray(rng.normal(size=(B, len(HOT), D)).astype(np.float32))
    dev = jax.devices()[0]
    want_bwd = None
    sample = np.arange(0, B, max(B // 16, 1))
    host = np.asarray(pulled)
    want_fwd = np.stack([
        np.stack([host[slots_h[i, a : a + h]].sum(axis=0, dtype=np.float64) for a, h in zip(STARTS, HOT)]) for i in sample
    ])
    forms = (("slices", slices), ("planes", planes), ("selection", selection), ("by_field", by_field), ("segment", segment))
    for name, form in forms:
        fwd = jax.jit(form)
        # the read is linear in the pulled rows: its transpose runs no forward pass
        bwd = jax.jit(lambda p, s, c, form=form: jax.vjp(lambda q: form(q, s), p)[1](c)[0])
        line = {"form": name, "seed": SEED, "device": dev.device_kind, "B": B, "slots": U, "ids": sum(HOT),
                "draw": "zipf" if ZIPF else "uniform", "distinct": real}
        try:
            line["forward_ms"], out = timed(fwd, pulled, slots)
            line["backward_ms"], g = timed(bwd, pulled, slots, ct)
            line["forward_max_gap"] = float(np.abs(np.asarray(out)[sample] - want_fwd).max())
            if want_bwd is None:
                want_bwd = np.asarray(g)
            line["backward_max_gap_to_slices"] = float(np.abs(np.asarray(g) - want_bwd).max())
            mem = bwd.lower(pulled, slots, ct).compile().memory_analysis()
            line["temp_gib"] = round(mem.temp_size_in_bytes / 2**30, 3)
        except Exception as e:  # noqa: BLE001 - a form the compiler refuses is a reading too
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(line), flush=True)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_bag_pool.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
