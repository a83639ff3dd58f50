"""`correct` has been shown to fail for the multi-hot DLRM cell as the
other ``test_controls*.py`` show it for theirs: both precision controls
fail numbers of ``dcn1tb.train`` (the reference all in bfloat16, and the
reference with only its matrix products' operands in bfloat16, which fails
the rows the first microstep alone touched by their ``n``); a sound tiny run
is correct; a run with one thing broken underneath (a bag read by its
first id alone, the bags drawn from another seed, the cross network
skipped, the push's sign, an accumulator that never grows, the dense group
never stepped) comes out as not correct, each by a limit named here. Tiny
sizes and narrow layers, CPU; the controls at the cell's own size are
``control_dcn.py``. ``bytes_model_dcn.py``'s two counts against a hand
count."""

import copy
import os

import pytest

import tiny
from control import control_of

CELL = "dcn1tb.train"
# the cell's code paths at a size a CPU steps in a second: 26 tables of up to 2,520 rows, the
# cell's own bag sizes (214 ids an example), narrow layers
SMALL = {
    "num_keys": 1 << 16, "minibatch": 512, "steps_per_call": 4,
    "emb_dim": 16, "bot": [32, 16], "top": [64, 32, 1], "cross_rank": 8,
}


def _tiny(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update(SMALL)
    return found


def _failing(numbers, limits) -> set:
    return {n for n, v in numbers.items() if n in limits and not v <= limits[n]}


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
def test_bfloat16_control_fails_the_dcn_cell(seed):
    numbers, limits = control_of(CELL, seed, "bfloat16", _tiny)
    # the first microstep's two numbers and the median element of ``w``: the precision limits
    must = {"prefix.emb_early_n_gap", "prefix.emb_early_step_gap", "prefix.emb_w_gap_q50"}
    assert must <= _failing(numbers, limits), {n: numbers[n] for n in must - _failing(numbers, limits)}


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
def test_products_only_bfloat16_control_fails_the_dcn_cell(seed):
    """State and sums in float32, the products' operands in bfloat16: a
    first AdaGrad step is ``eta`` times the gradient's sign whatever its
    size, so ``w`` hardly tells; ``n``, the gradient's square, does."""
    numbers, limits = control_of(CELL, seed, "bfloat16_products", _tiny)
    assert "prefix.emb_early_n_gap" in _failing(numbers, limits), numbers
    assert numbers["prefix.emb_early_n_gap"] > 3 * limits["prefix.emb_early_n_gap"], numbers


def test_float32_control_passes_the_dcn_cell():
    numbers, limits = control_of(CELL, 21, "float32", _tiny)
    assert not _failing(numbers, limits), {n: numbers[n] for n in _failing(numbers, limits)}


def _run(**kw):
    """(record, correct, ctx) of one tiny run of the cell."""
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{CELL}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(CELL, seed=31, seconds=0.5, workdir=workdir, **{**SMALL, **kw})
    # files of 2,048 examples: a window's few calls move the AUC on them either way
    ctx.traffic["limits"]["trained.auc_below_reference"] = 0.05
    rec = kind.run(ctx, app)
    return rec, all(c.ok for c in rec["checks"]) and rec["failed"] == 0, ctx


def _failed(rec) -> set:
    return {c.name for c in rec["checks"] if not c.ok}


def test_sound_dcn_run_is_correct_and_counts_its_keys():
    rec, correct, ctx = _run()
    assert correct, ([c.line() for c in rec["checks"] if not c.ok], rec["failed"])
    assert rec["window"]["units"] >= 1 and rec["attempted"] > 0
    # what ``store.dcn_hbm_share`` divides by: the distinct rows of a minibatch's bags
    keys = ctx.config["counted"]["real_keys"]
    assert 26 <= keys <= 214 * 512 and keys < rec["facts"]["bucket_rows"]
    names = {c.name for c in rec["checks"]}
    assert {"prefix.emb_n_gap_max", "prefix.bag_rows_missed", "prefix.bag_rows_extra", "prefix.emb_early_n_gap"} <= names


def test_a_bag_read_by_its_first_id_alone_is_not_correct(monkeypatch):
    """The one-hot model's read under the multi-hot feed: every field's
    vector its bag's FIRST row. The other rows of the bags are pulled and
    never read: their ``n`` stays zero."""
    import jax.numpy as jnp

    from parameter_server_tpu.models import dlrm

    def first_only(rows, hot):
        at = [sum(hot[:f]) for f in range(len(hot))]
        return rows[:, jnp.asarray(at)]

    monkeypatch.setattr(dlrm, "pool_bags", first_only)
    rec, correct, _ = _run()
    assert not correct
    assert {"prefix.loss_gap", "prefix.bag_rows_missed"} <= _failed(rec), _failed(rec)


def test_bags_drawn_from_another_seed_are_not_correct(monkeypatch):
    """The parser's bags under a seed that is not the configuration's: rows
    the reference's bags never name take a gradient, rows they name take
    none (the model module names the format for ``pod_config`` and for
    ``app_from_config`` alike, so both take the other seed)."""
    from parameter_server_tpu.data.libsvm import criteo_format
    from parameter_server_tpu.models import dlrm

    monkeypatch.setattr(dlrm, "criteo_format", lambda rows, hot=None, seed=0: criteo_format(rows, hot, seed + 1))
    rec, correct, _ = _run()
    assert not correct
    assert {"prefix.bag_rows_missed", "prefix.bag_rows_extra"} <= _failed(rec), _failed(rec)


def test_cross_network_skipped_is_not_correct(monkeypatch):
    from parameter_server_tpu.models import dlrm

    monkeypatch.setattr(dlrm.mlp, "cross_apply", lambda params, x0: x0)
    rec, correct, _ = _run()
    assert not correct
    assert {"prefix.loss_gap", "prefix.mlp_step_gap"} <= _failed(rec), _failed(rec)


def test_dcn_push_of_the_wrong_sign_is_not_correct(monkeypatch):
    from parameter_server_tpu.kv.updaters import Adagrad

    real = Adagrad.delta

    def flipped(self, rows, grad):
        d = real(self, rows, grad)
        return {"w": -d["w"], "n": d["n"]}

    monkeypatch.setattr(Adagrad, "delta", flipped)
    rec, correct, _ = _run()
    assert not correct
    assert {"prefix.emb_step_gap", "prefix.emb_hot_step_gap", "prefix.emb_early_step_gap"} <= _failed(rec), _failed(rec)


def test_an_accumulator_that_never_grows_is_not_correct(monkeypatch):
    """``n`` written once and never added to: every later step is too
    large, and half of ``w``'s elements show it."""
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Adagrad

    def forgetful(self, rows, grad):
        dn = grad * grad
        return {"w": -self.eta * grad / (jnp.sqrt(dn) + self.eps), "n": jnp.where(rows["n"] > 0, 0.0, dn)}

    monkeypatch.setattr(Adagrad, "delta", forgetful)
    rec, correct, _ = _run()
    assert not correct
    assert {"prefix.emb_w_gap_q50", "prefix.emb_hot_w_gap_q50"} <= _failed(rec), _failed(rec)


def test_dense_group_left_untrained_is_not_correct(monkeypatch):
    from parameter_server_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "_dense_step", lambda group, params, opt_state, grads, active: (params, opt_state))
    rec, correct, _ = _run()
    assert not correct
    assert "prefix.mlp_step_gap" in _failed(rec), _failed(rec)


def test_bytes_model_dcn_against_a_hand_count():
    """A small shape by hand. MLPs 13-4-2 and 54-3-1 (27 vectors of 2
    lanes), two cross layers of rank 5: multiply-adds forward 13*4 + 4*2 =
    60, 54*3 + 3*1 = 165, 2 * (54*5 + 5*54) = 1080; three passes of each
    less the bottom's first layer's input gradient (13*4); two operations a
    multiply-add. Bytes: 1,000 rows x 2 lanes x 4 B, ``w`` read by the
    pull, ``w`` and ``n`` read and written by each push."""
    from benchmark import bytes_model_dcn as bm

    by_hand = 2 * (3 * (60 + 165 + 1080) - 52)
    assert bm.example_flops(2, [4, 2], [3, 1], 2, 5) == by_hand == 7_726
    settings = {"minibatch": 10, "emb_dim": 2, "bot": [4, 2], "top": [3, 1], "cross_layers": 2, "cross_rank": 5}
    assert bm.step_flops(settings) == 77_260
    assert bm.step_bytes(1000, 2) == 1000 * 8 * 5 and bm.step_bytes(1000, 2, pushes=2) == 1000 * 8 * 9
    # the cell's own: ISSUE 52's 3 x (10,616,832 + 5,243,136 + 170,496) - 6,656 multiply-adds an
    # example, 787.8 GFLOP a microstep of 8,192; 2,560 B a touched row
    cell = bm.example_flops(128, [512, 256, 128], [1024, 1024, 512, 256, 1], 3, 512)
    assert cell == 2 * (3 * (10_616_832 + 5_243_136 + 170_496) - 6_656) == 96_169_472
    assert round(8192 * cell / 1e9, 1) == 787.8 and bm.step_bytes(1, 128) == 2560


def test_the_cell_states_the_sources_sizes():
    from benchmark.harness import manifest as mf

    found = mf.resolve(tiny.manifest_of(CELL), CELL)
    st, app = found["config"]["settings"], mf.load_module(found["app_path"], "app")
    assert sum(st["hot"]) == 214 and max(st["hot"]) == 100 and len(st["hot"]) == 26
    assert (st["emb_dim"], st["cross_layers"], st["cross_rank"], st["eta"], st["eps"]) == (128, 3, 512, 0.004, 1e-8)
    assert st["updater"] == "adagrad" and st["max_nnz_per_example"] * st["minibatch"] == 1 << 21
    assert app.field_rows_of(found["config"]) == st["field_rows"] and 14 + sum(st["field_rows"]) == st["num_keys"]
    assert sum(found["config"]["data"]["cat_vocab"]) == 204_184_588
