"""`correct` has been shown to fail for the matrix-factorization cell as
``test_controls.py`` shows it for the others: a sound tiny run is correct;
the bfloat16 control, a push of the wrong sign and a run whose pushes leave
the user rows untouched each come out as not correct; and the kind
``train_mf`` does ``train``'s window arithmetic on the same stamps. Tiny
sizes, CPU; the control at the cell's own size is ``control.py mfhw.train``."""

import copy
import os
import types

import numpy as np
import pytest

import tiny
from control import control_of

MF_CELL = "mfhw.train"
MF_TINY = {"num_users": 20_000, "num_items": 500}


def _tiny_mf(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update({**MF_TINY, "minibatch": 512, "steps_per_call": 4})
    return found


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
def test_mf_bfloat16_control_fails_the_new_cell(seed):
    numbers, limits = control_of(MF_CELL, seed, "bfloat16", _tiny_mf)
    failing = {n for n, v in numbers.items() if n in limits and not v <= limits[n]}
    # every number of the rows read back; the losses and the two RMSE numbers
    # need the cell's own size (``control.py mfhw.train``)
    must = {n for n in limits if n.startswith("prefix.") and "_w_gap" in n or n.endswith("_step_gap")}
    assert must and must <= failing, {n: numbers[n] for n in must - failing}


def test_mf_float32_control_passes_the_new_cell():
    numbers, limits = control_of(MF_CELL, 21, "float32", _tiny_mf)
    assert all(v <= limits[n] for n, v in numbers.items() if n in limits), numbers


def _run_mf(**kw):
    # a directory of this process's own: pytest-xdist runs these side by side
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{MF_CELL}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(MF_CELL, seed=31, seconds=0.5, workdir=workdir, **MF_TINY, **kw)
    rec = kind.run(ctx, app)
    return rec, all(c.ok for c in rec["checks"]) and rec["failed"] == 0


def _failed_mf(rec) -> set:
    return {c.name for c in rec["checks"] if not c.ok}


def test_mf_sound_run_is_correct():
    rec, correct = _run_mf()
    assert correct, [c.line() for c in rec["checks"]]
    assert rec["window"]["units"] >= 1 and rec["attempted"] > 0
    assert rec["facts"]["real_keys"] > 256 and rec["facts"]["mode"] == "train"
    # RMSE falls with training: the window's passes gain on the prefix's state
    trained = next(c for c in rec["checks"] if c.name == "trained.rmse_above_reference")
    assert trained.value < 0, trained.line()


def test_mf_push_of_the_wrong_sign_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv.updaters import Sgd

    real = Sgd.delta
    monkeypatch.setattr(Sgd, "delta", lambda self, rows, g: real(self, rows, -g))
    rec, correct = _run_mf()
    assert not correct
    assert {"prefix.item_step_gap", "prefix.user_step_gap", "heldout.rmse_above_reference",
            "trained.rmse_above_reference"} <= _failed_mf(rec), _failed_mf(rec)


def test_mf_user_rows_left_untouched_is_not_correct(monkeypatch):
    """The items' rows are pushed as they should be, the users' never: an
    element of a user's row moves by a thousandth of itself in the prefix,
    so the elements' gaps would pass such a run at the cell's size; the gap
    between the two sides' change since the start reads 1."""
    import jax.numpy as jnp

    from parameter_server_tpu.parallel import spmd

    add = spmd._add_rows

    def add_items_only(table, rows, deltas, ascending):
        keep = (rows <= MF_TINY["num_items"])[:, None]
        return add(table, rows, jnp.where(keep, deltas, 0.0), ascending)

    monkeypatch.setattr(spmd, "_add_rows", add_items_only)  # a tiny table's users start at row 501
    rec, correct = _run_mf()
    assert not correct
    failed = _failed_mf(rec)
    assert "prefix.user_step_gap" in failed and "prefix.item_step_gap" not in failed, failed
    gap = next(c for c in rec["checks"] if c.name == "prefix.user_step_gap")
    assert gap.value == pytest.approx(1.0, abs=1e-3)


def test_mf_kind_does_the_train_kinds_window_arithmetic():
    """Both kinds over one made-up session: the same stamps give the same
    window, stamps' lines, counts and end-to-end numbers."""
    from benchmark.harness import manifest as mf

    kinds = {k: mf.load_module(os.path.join(mf.BENCH_DIR, "traffic_kinds", k + ".py"), "kind") for k in ("train", "train_mf")}

    class Stop(Exception):
        pass

    class FakeSession:
        data_shards, kv_shards, steps_per_call, prefix_files = 1, 1, 8, 1
        heldout_auc, heldout_rmse, trained_paths = 0.75, 1.25, ["a", "b"]

        def __init__(self, ctx):
            self.trainer = types.SimpleNamespace(state={}, max_inflight=3)
            self.problem = types.SimpleNamespace(real_keys=lambda: 75_500.0)
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
            return [524_288] * (self.units + 2)  # two calls still in flight

        def call_slots(self):
            return [131_072] * (self.units + 2)

        def call_outputs(self):
            n = self.units + 2
            return [np.ones(8)] * n, [np.full(8, 65_536.0)] * n

        def evaluate(self, files):
            return {"auc": 0.8, "rmse": 1.0}

        def reference(self, precision, score):
            return None, np.ones(8), {k: None for k in score}

        def prefix_checks(self, ref, ref_losses):
            return []

        def close(self):
            pass

    app = types.SimpleNamespace(Session=FakeSession, StopWindow=Stop, heldout_scores=lambda ref, s: (0.9, None))
    recs = {}
    for name, kind in kinds.items():
        ctx, _, _ = tiny.tiny_ctx(MF_CELL, seconds=5.0, **MF_TINY)
        ctx.traffic["limits"] = {f"{a}.{b}": 1.0 for a in ("heldout", "trained")
                                 for b in ("auc_below_reference", "rmse_above_reference")}
        ctx.t0 = 0.0
        recs[name] = kind.run(ctx, app)
    a, b = recs["train"], recs["train_mf"]
    assert a["window"] == b["window"] and a["window"]["units"] == 14
    assert a["stamps"] == b["stamps"] and a["end_to_end"] == b["end_to_end"]
    assert (a["attempted"], a["failed"]) == (b["attempted"], b["failed"]) == (16 * 524_288, 0)
    assert a["end_to_end"]["ex_rate"] == pytest.approx(14 * 524_288 / a["window"]["elapsed_s"])
    assert {k: v for k, v in b["facts"].items() if k != "real_keys"} == a["facts"]
    names = lambda rec: [c.name for c in rec["checks"]]  # noqa: E731
    assert names(b) == [n.replace("auc_below", "rmse_above") for n in names(a)]
