"""`correct` has been shown to fail for the sharded matrix-factorization
cell (``mfhw2x2.train``: rank 100 stored 128 lanes wide, mesh ``data`` 2 x
``kv`` 2) as ``test_controls_mf.py`` shows it for the one-chip cell: a sound
tiny run on four devices is correct, pad lanes and all; the bfloat16
control, a table left untouched, a reference that applies only the first
worker's push and a push that writes into the pad lanes each come out as
not correct. And the three collectives' readers and the count of bytes a
chip must receive, on made-up runs. Tiny sizes, CPU; the control at the
cell's own size is ``control.py mfhw2x2.train``."""

import copy
import os
import time

import numpy as np
import pytest

import tiny
from control import control_of
from benchmark import bytes_model_mf_coll
from benchmark.harness import manifest as mf

CELL_2X2 = "mfhw2x2.train"
TINY_2X2 = {"num_users": 20_000, "num_items": 500}


def _tiny_2x2(found):
    found = copy.deepcopy(found)
    found["config"]["settings"].update({**TINY_2X2, "minibatch": 512, "steps_per_call": 4})
    return found


@pytest.mark.parametrize("seed", [21, 2**31 + 23])
def test_mf_2x2_bfloat16_control_fails_the_rows_read_back(seed):
    numbers, limits = control_of(CELL_2X2, seed, "bfloat16", _tiny_2x2)
    failing = {n for n, v in numbers.items() if n in limits and not v <= limits[n]}
    must = {n for n in limits if n.startswith("prefix.") and "_w_gap" in n or n.endswith("_step_gap")}
    assert must and must <= failing, {n: numbers[n] for n in must - failing}


def _run_2x2(monkeypatch, **kw):
    """A tiny run of the cell on four CPU devices. Its files are read faster
    than a second reader thread starts, so the program's pool of files,
    which hands the next file to whichever worker asks first, may give one
    worker's stream both files of a call; the harness wants one file a
    worker a prefix call, as the cell's own 524,288-rating files give it.
    Here the pool hands its files out in the workers' turn."""
    from parameter_server_tpu.parallel.workload import WorkloadPool

    fetch, handed = WorkloadPool.fetch, {}

    def fetch_in_turn(self, worker):
        waited = time.monotonic()
        while handed.get(id(self), 0) % 2 != worker and time.monotonic() - waited < 5.0:
            time.sleep(0.0005)
        got = fetch(self, worker)
        handed[id(self)] = handed.get(id(self), 0) + 1
        return got

    monkeypatch.setattr(WorkloadPool, "fetch", fetch_in_turn)
    workdir = os.path.join(tiny.ROOT, ".bench_work", f"tiny.{CELL_2X2}.{os.getpid()}")
    ctx, kind, app = tiny.tiny_ctx(CELL_2X2, seed=31, seconds=0.5, workdir=workdir, **TINY_2X2, **kw)
    rec = kind.run(ctx, app)
    return ctx, rec, all(c.ok for c in rec["checks"]) and rec["failed"] == 0


def _failed_2x2(rec) -> set:
    return {c.name for c in rec["checks"] if not c.ok}


def test_mf_2x2_sound_run_is_correct_and_counts_its_keys_by_owner(monkeypatch):
    ctx, rec, correct = _run_2x2(monkeypatch)
    assert correct, [c.line() for c in rec["checks"]]
    f = rec["facts"]
    assert (f["data_shards"], f["kv_shards"], f["pushes_per_step"]) == (2, 2, 2)
    pad = next(c for c in rec["checks"] if c.name == "prefix.pad_lanes_nonzero")
    assert pad.value == 0 and pad.limit == 0
    # two workers' keys by the shard that owns their rows: every item's row lies in
    # shard 0, the users' rows in both; a worker's keys add up to its minibatch's
    owned = np.asarray(ctx.config["observed"]["keys_owned"])
    assert owned.shape == (2, 2) and (owned > 0).all()
    assert owned.sum(axis=1) == pytest.approx([f["real_keys"]] * 2, rel=0.05)
    assert (owned[:, 0] > owned[:, 1]).all()


def test_mf_2x2_table_left_untouched_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from parameter_server_tpu.kv.updaters import Sgd

    monkeypatch.setattr(Sgd, "delta", lambda self, rows, g: {k: jnp.zeros_like(v) for k, v in rows.items()})
    _, rec, correct = _run_2x2(monkeypatch)
    assert not correct
    failed = _failed_2x2(rec)
    assert {"prefix.item_step_gap", "prefix.user_step_gap"} <= failed, failed
    for name in ("prefix.item_step_gap", "prefix.user_step_gap"):
        assert next(c for c in rec["checks"] if c.name == name).value == pytest.approx(1.0, abs=1e-3)


def test_mf_2x2_reference_that_applies_only_the_first_workers_push_is_not_correct(monkeypatch):
    """The reference is held to the configuration's order: both workers'
    gradients from one state, both applied. One that drops the second
    worker's push disagrees with a sound program on the rows that worker
    touched."""
    from benchmark.harness.ref_mf import RefMf

    step = RefMf.step
    monkeypatch.setattr(RefMf, "step", lambda self, workers: step(self, workers[:1]))
    _, rec, correct = _run_2x2(monkeypatch)
    assert not correct
    assert {"prefix.loss_gap", "prefix.item_step_gap", "prefix.user_step_gap"} <= _failed_2x2(rec), _failed_2x2(rec)


def test_mf_2x2_push_into_the_pad_lanes_is_not_correct(monkeypatch):
    """A scatter that widens its deltas with anything but zeros moves the
    28 lanes no row owns: every compared lane still agrees, and the exact
    check on the pad lanes fails alone."""
    import jax.numpy as jnp

    from parameter_server_tpu.parallel import spmd

    add = spmd._add_rows

    def add_spilling(table, rows, deltas, ascending):
        wide = jnp.pad(deltas, ((0, 0), (0, table.shape[1] - deltas.shape[1])), constant_values=1e-3)
        return add(table, rows, wide, ascending)

    monkeypatch.setattr(spmd, "_add_rows", add_spilling)
    _, rec, correct = _run_2x2(monkeypatch)
    assert not correct
    assert _failed_2x2(rec) == {"prefix.pad_lanes_nonzero"}, _failed_2x2(rec)


# -- the collectives' readers ---------------------------------------------------
def _coll_reader(name: str):
    return mf.load_module(mf.metric_path(name), "reader")


def _made_up_run(by_scope, owned=None) -> dict:
    class Trace:
        chips = 4

    return {
        "_phase_seconds": by_scope, "trace": Trace(), "facts": {"microsteps": 8},
        "config": {"settings": {"rank": 100}, **({"observed": {"keys_owned": owned}} if owned else {})},
        "peaks": {"ici_bits_per_s": 1600e9},
    }


def test_bytes_a_chip_must_receive_for_the_two_collectives():
    """Worker 0 holds 100 keys of shard 0 and 40 of shard 1, worker 1 80 and
    20. Chip (0, 0) pulls worker 0's 40 rows of shard 1 and is pushed worker
    1's 80 gradients for shard 0; and so on: the mean over the four chips."""
    owned = [[100, 40], [80, 20]]
    got = bytes_model_mf_coll.recv_bytes(owned, 100)
    assert got["pull"] == pytest.approx((40 + 100 + 20 + 80) / 4 * 400)
    assert got["push"] == pytest.approx((80 + 20 + 100 + 40) / 4 * 404)
    # one worker, one shard: nothing crosses a link
    assert bytes_model_mf_coll.recv_bytes([[75_000]], 100) == {"pull": 0.0, "push": 0.0}


def test_collective_readers_on_a_made_up_run():
    by_scope = {
        "ps.pull/mf": 3.2, "ps.pull/mf/psum": 0.64, "ps.push/mf/all_gather": 1.28,
        "ps.push/scatter/mf": 6.4, "ps.push/gather/mf": 0.8, "ps.grad": 1.6, "": 0.1,
    }
    run = _made_up_run(by_scope, owned=[[100, 40], [80, 20]])
    per = 1e3 / 4 / 8  # seconds over the four chips -> ms a chip and microstep
    assert _coll_reader("coll.pull_psum_ms").read(run) == pytest.approx(0.64 * per)
    assert _coll_reader("coll.push_gather_ms").read(run) == pytest.approx(1.28 * per)
    least = bytes_model_mf_coll.recv_bytes([[100, 40], [80, 20]], 100)
    want = 100 * (8 * (least["pull"] + least["push"]) / 1600e9) / (1.92 * per * 1e-3)
    assert _coll_reader("coll.ici_share").read(run) == pytest.approx(want)
    # an unnamed table's scopes (the linear app): the same readers
    run = _made_up_run({"ps.pull": 1.0, "ps.pull/psum": 0.32, "ps.push/all_gather": 0.16, "ps.push/scatter": 2.0})
    assert _coll_reader("coll.pull_psum_ms").read(run) == pytest.approx(0.32 * per)
    assert _coll_reader("coll.push_gather_ms").read(run) == pytest.approx(0.16 * per)
    assert _coll_reader("coll.ici_share").read(run) is None  # no keys counted by owner
    # the table's own ops do not count the collectives: store.mf_hbm_share's sum is as it was
    table_ops = sum(
        s for scope, s in by_scope.items()
        if scope == "ps.pull/mf" or (scope.startswith("ps.push/") and scope.endswith("/mf"))
    )
    assert table_ops == pytest.approx(3.2 + 6.4 + 0.8)


@pytest.mark.parametrize("name", ["coll.pull_psum_ms", "coll.push_gather_ms", "coll.ici_share"])
def test_collective_readers_read_nothing_from_a_parent(name):
    """The parent's programs name no scope for a collective, and a program
    with no names at all gives no map: the readers return None and the
    result line leaves the metric out."""
    parent = {"ps.pull/mf": 3.2, "ps.pull": 0.64, "ps.push": 1.28, "ps.push/scatter/mf": 6.4}
    assert _coll_reader(name).read(_made_up_run(parent, owned=[[100, 40], [80, 20]])) is None
    assert _coll_reader(name).read(_made_up_run(None, owned=[[100, 40], [80, 20]])) is None
    entry = mf.entry(mf.load_manifest()["per_layer"], name, "per-layer metric")
    assert entry["layer"] == "collectives" and entry["moves"] == "ex_rate" and entry["source"] == "device_trace"
    assert CELL_2X2 in entry["workloads"]
