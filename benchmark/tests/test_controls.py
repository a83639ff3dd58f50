"""`correct` has been shown to fail: (1) the reference in bfloat16, put in
the program's place, fails a number of every cell; (2) a run driven past
the harness's look for a chip, with the timed path broken underneath,
comes out as not correct. Tiny sizes, CPU; the same controls at the cells'
own sizes are ``control.py``'s."""

import copy

import numpy as np
import pytest

import tiny
from control import control_of


def _tiny(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update({"num_keys": 1 << 22, "minibatch": 1024, "steps_per_call": 4})
    if found["traffic"]["kind"] == "workers":
        found["traffic"].update(clients=2, push_keys=8192, pull_keys=4096, trips_cap=512)
    return found


CELLS = [name for name, _ in tiny.all_cells()]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [21, 22, 2**31 + 23])
def test_bfloat16_control_fails(cell, seed):
    numbers, limits = control_of(cell, seed, "bfloat16", _tiny)
    failing = [n for n, v in numbers.items() if n in limits and not v <= limits[n]]
    assert failing, f"the bfloat16 control passes every number of {cell}: {numbers}"


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_passes(cell):
    """The same path in the stated precision reads 0: the failure above is
    the precision's and not the harness's."""
    numbers, limits = control_of(cell, 21, "float32", _tiny)
    assert all(v <= limits[n] for n, v in numbers.items() if n in limits and not n.startswith("witness"))


def _run(cell, **kw):
    ctx, kind, app = tiny.tiny_ctx(cell, seed=31, seconds=0.5, **kw)
    rec = kind.run(ctx, app)
    return rec, all(c.ok for c in rec["checks"]) and rec["failed"] == 0


def test_sound_train_run_is_correct():
    rec, correct = _run("ctr1.train")
    assert correct, [c.line() for c in rec["checks"]]
    assert rec["window"]["units"] >= 1 and rec["attempted"] > 0


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Ftrl

    monkeypatch.setattr(Ftrl, "delta", lambda self, rows, g: {k: jnp.zeros_like(v) for k, v in rows.items()})
    rec, correct = _run("ctr1.train")
    assert not correct
    bad = {c.name for c in rec["checks"] if not c.ok}
    # the untrained table scores at chance at the prefix and after the window
    assert {"prefix.z_gap", "prefix.n_gap", "heldout.auc_below_reference", "trained.auc_below_reference"} <= bad


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    from parameter_server_tpu.parallel import spmd

    real = spmd.logistic_loss

    def half(logits, labels, mask):
        return real(logits, labels, mask & (labels.shape[0] // 2 > np.arange(labels.shape[0])))

    monkeypatch.setattr(spmd, "logistic_loss", half)
    rec, correct = _run("ctr1.train")
    assert not correct
    assert "prefix.loss_gap" in {c.name for c in rec["checks"] if not c.ok}


def test_altered_probability_is_not_correct(monkeypatch):
    from parameter_server_tpu.parallel import trainer as tr

    real = tr.make_spmd_predict_step

    def skewed(*a, **k):
        fn = real(*a, **k)
        return lambda state, batch: fn(state, batch) * 0.99

    monkeypatch.setattr(tr, "make_spmd_predict_step", skewed)
    rec, correct = _run("ctr1.eval")
    assert not correct
    assert "eval.prob_gap" in {c.name for c in rec["checks"] if not c.ok}


def test_push_applied_twice_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv import store

    real = store.push

    def twice(updater, state, idx, grad, *a, **k):
        return real(updater, real(updater, state, idx, grad, *a, **k), idx, grad, *a, **k)

    monkeypatch.setattr(store, "push", twice)
    rec, correct = _run("ctrwire.workers")
    assert not correct
    assert "witness.n_gap" in {c.name for c in rec["checks"] if not c.ok}
