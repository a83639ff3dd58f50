"""`correct` has been shown to fail for the skip-gram cell as
``test_controls_mf.py`` shows it for matrix factorization: a sound tiny run
is correct; the bfloat16 control, a push of the wrong sign and a run whose
pushes leave the output vectors untouched each come out as not correct; and
the kind ``train_sgns`` does ``train``'s window arithmetic on the same
stamps. Tiny sizes, CPU; the control at the cell's own size is
``control.py sgns3m.train``."""

import copy
import os
import types

import numpy as np
import pytest

import tiny
from control import control_of

SGNS_CELL = "sgns3m.train"
SGNS_TINY = {"vocab_size": 20_000}  # the width stays 300: rows stored 384 wide


def _tiny_sgns(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update({**SGNS_TINY, "minibatch": 512, "steps_per_call": 4})
    return found


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
def test_sgns_bfloat16_control_fails_the_new_cell(seed):
    numbers, limits = control_of(SGNS_CELL, seed, "bfloat16", _tiny_sgns)
    failing = {n for n, v in numbers.items() if n in limits and not v <= limits[n]}
    # every number of the rows read back; the losses and the two loss numbers
    # need the cell's own size (``control.py sgns3m.train``)
    must = {n for n in limits if n.startswith("prefix.") and "_w_gap" in n or n.endswith("_step_gap")}
    assert len(must) == 8 and must <= failing, {n: numbers[n] for n in must - failing}


def test_sgns_float32_control_passes_the_new_cell():
    numbers, limits = control_of(SGNS_CELL, 21, "float32", _tiny_sgns)
    assert all(v <= limits[n] for n, v in numbers.items() if n in limits), numbers


def _run_sgns(**kw):
    # a directory of this process's own: pytest-xdist runs these side by side
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{SGNS_CELL}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(SGNS_CELL, seed=31, seconds=0.5, workdir=workdir, **SGNS_TINY, **kw)
    rec = kind.run(ctx, app)
    return rec, all(c.ok for c in rec["checks"]) and rec["failed"] == 0


def _failed_sgns(rec) -> set:
    return {c.name for c in rec["checks"] if not c.ok}


def test_sgns_sound_run_is_correct():
    rec, correct = _run_sgns()
    assert correct, [c.line() for c in rec["checks"]]
    assert rec["window"]["units"] >= 1 and rec["attempted"] > 0
    assert rec["facts"]["real_keys"] > 256 and rec["facts"]["mode"] == "train"
    # 256 pairs x 7 entries: the builder's caps, the key axis one more
    assert rec["facts"]["bucket_rows"] == 7 * 256 + 1
    # the loss falls with training: the window's passes gain on the prefix's state
    trained = next(c for c in rec["checks"] if c.name == "trained.loss_above_reference")
    assert trained.value < 0, trained.line()


def test_sgns_push_of_the_wrong_sign_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv.updaters import Sgd

    real = Sgd.delta
    monkeypatch.setattr(Sgd, "delta", lambda self, rows, g: real(self, rows, -g))
    rec, correct = _run_sgns()
    assert not correct
    # the output vectors come out negated; the input vectors' first steps are the sound
    # run's (the two signs cancel in err x v), and from word2vec.c's start the loss takes
    # tens of calls to move either way: how far it rises depends on the window's length
    assert {"prefix.out_step_gap", "prefix.out_w_gap_q50", "prefix.out_w_gap_max"} <= _failed_sgns(rec), _failed_sgns(rec)


def test_sgns_output_rows_left_untouched_is_not_correct(monkeypatch):
    """The input vectors are pushed as they should be, the output vectors
    never: they stay at zero, every score stays 0, and the gap between the
    two sides' change since the start reads 1."""
    import jax.numpy as jnp

    from parameter_server_tpu.parallel import spmd

    add = spmd._add_rows

    def add_inputs_only(table, rows, deltas, ascending):
        keep = (rows <= SGNS_TINY["vocab_size"])[:, None]
        return add(table, rows, jnp.where(keep, deltas, 0.0), ascending)

    monkeypatch.setattr(spmd, "_add_rows", add_inputs_only)
    rec, correct = _run_sgns()
    assert not correct
    failed = _failed_sgns(rec)
    assert "prefix.out_step_gap" in failed, failed
    gap = next(c for c in rec["checks"] if c.name == "prefix.out_step_gap")
    assert gap.value == pytest.approx(1.0, abs=1e-3)


def test_sgns_kind_does_the_train_kinds_window_arithmetic():
    """Both kinds over one made-up session: the same stamps give the same
    window, stamps' lines, counts and end-to-end numbers."""
    from benchmark.harness import manifest as mf

    kinds = {k: mf.load_module(os.path.join(mf.BENCH_DIR, "traffic_kinds", k + ".py"), "kind") for k in ("train", "train_sgns")}

    class Stop(Exception):
        pass

    class FakeSession:
        data_shards, kv_shards, steps_per_call, prefix_files = 1, 1, 8, 1
        heldout_auc, heldout_loss, trained_paths = 0.75, 4.1, ["a", "b"]

        def __init__(self, ctx):
            self.trainer = types.SimpleNamespace(state={}, max_inflight=3)
            self.problem = types.SimpleNamespace(real_keys=lambda: 72_100.0)
            self.on_retire = None
            self.units = 0

        def measure_build_rate(self):
            return 1e6

        def prefix(self, score_heldout=False):
            assert score_heldout

        def file_list(self, n, start=0):
            return list(range(n))

        def train(self, files):
            t = 100.0
            try:
                for i in range(len(files)):
                    t += 0.37 + 0.01 * (i % 3)  # uneven calls: the close lands between stamps
                    self.units += 1
                    self.on_retire(t, i)
            except Stop:
                return False
            return True

        def call_work(self):
            return [131_072] * (self.units + 2)  # two calls still in flight

        def call_slots(self):
            return [114_689] * (self.units + 2)

        def call_outputs(self):
            n = self.units + 2
            return [np.ones(8)] * n, [np.full(8, 16_384.0)] * n

        def evaluate(self, files):
            return {"auc": 0.8, "sgns_loss": 3.0}

        def reference(self, precision, score):
            return None, np.ones(8), {k: None for k in score}

        def prefix_checks(self, ref, ref_losses):
            return []

        def close(self):
            pass

    app = types.SimpleNamespace(Session=FakeSession, StopWindow=Stop, heldout_scores=lambda ref, s: (0.9, None),
                                mean_loss=lambda ref, s: 4.0)
    recs = {}
    for name, kind in kinds.items():
        ctx, _, _ = tiny.tiny_ctx(SGNS_CELL, seconds=5.0, **SGNS_TINY)
        ctx.traffic["limits"] = {f"{a}.{b}": 1.0 for a in ("heldout", "trained")
                                 for b in ("auc_below_reference", "loss_above_reference")}
        ctx.t0 = 0.0
        recs[name] = kind.run(ctx, app)
    a, b = recs["train"], recs["train_sgns"]
    assert a["window"] == b["window"] and a["window"]["units"] == 14
    assert a["stamps"] == b["stamps"] and a["end_to_end"] == b["end_to_end"]
    assert (a["attempted"], a["failed"]) == (b["attempted"], b["failed"]) == (16 * 131_072, 0)
    assert a["end_to_end"]["ex_rate"] == pytest.approx(14 * 131_072 / a["window"]["elapsed_s"])
    assert {k: v for k, v in b["facts"].items() if k != "real_keys"} == a["facts"]
    names = lambda rec: [c.name for c in rec["checks"]]  # noqa: E731
    assert names(b) == [n.replace("auc_below", "loss_above") for n in names(a)]
