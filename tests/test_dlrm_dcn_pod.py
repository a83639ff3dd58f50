"""The multi-hot form of app ``dlrm`` (DLRM-DCNv2: bags of ids summed a
field, the low-rank cross network, AdaGrad on both halves) through
``PodTrainer`` against the benchmark's plain reference
(``benchmark/harness/ref_dlrm_dcn.py``, which imports nothing of the
program), on the CPU at small sizes: the step on three mesh shapes over
several calls, the bags in both parsers and in the reference pinned by
literal values, a row twice in one bag, the one-hot form left to the bit
what it was, the host's refusal of a short example, the cross layer and the
dense AdaGrad rule against their formulas, the CLI."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import criteo, ref_dlrm, ref_dlrm_dcn  # noqa: E402
from parameter_server_tpu.data import native  # noqa: E402
from parameter_server_tpu.data.batch import BatchBuilder  # noqa: E402
from parameter_server_tpu.data.libsvm import (  # noqa: E402
    CriteoBags, bag_draw, criteo_format, iter_format, split_format,
)
from parameter_server_tpu.data.reader import MinibatchReader, ingest_of  # noqa: E402
from parameter_server_tpu.kv.updaters import Adagrad, dense_adagrad  # noqa: E402
from parameter_server_tpu.models import dlrm, mlp  # noqa: E402
from parameter_server_tpu.parallel import make_mesh  # noqa: E402
from parameter_server_tpu.parallel.trainer import PodTrainer  # noqa: E402
from parameter_server_tpu.utils.config import PSConfig  # noqa: E402
from parameter_server_tpu.utils.metrics import ProgressReporter  # noqa: E402

BATCH = 64
DIM, BOT, TOP, ETA, EPS = 8, [16, 8], [32, 16, 1], 0.01, 1e-8
LAYERS, RANK = 3, 4
VOCAB = [50, 3, 1000, 7, 20000, 4, 900] + [30] * 19
CAP = 500
FIELD_ROWS = [min(v, CAP) for v in VOCAB]  # tables of 3 to 500 rows
HOT = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 5, 1, 6, 2, 5, 1, 1, 1, 4, 7, 3, 2, 3, 1, 1]  # bags of 1 to 7
ENTRIES = 13 + sum(HOT)
SPEC = dict(cat_vocab=VOCAB, zipf_s=1.1, int_mu=2.0, int_sigma=1.5, truth_density=0.3, truth_scale=0.8, base_rate=0.25)
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}
HYPER = dict(emb_dim=DIM, bot=BOT, top=TOP, cross_layers=LAYERS, cross_rank=RANK, eta=ETA, eps=EPS)
SEED = 11


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def make_cfg(data=1, kv=1, steps_per_call=1, seed=SEED, hot=HOT, cross_layers=LAYERS, updater="adagrad"):
    cfg = PSConfig()
    cfg.seed = seed
    d = cfg.dlrm
    d.emb_dim, d.bot, d.top, d.eta, d.eps = DIM, list(BOT), list(TOP), ETA, EPS
    d.field_rows, d.hot = list(FIELD_ROWS), list(hot)
    d.cross_layers, d.cross_rank, d.updater = cross_layers, RANK, updater
    cfg.data.max_nnz_per_example = 13 + sum(hot)
    cfg.solver.minibatch, cfg.solver.steps_per_call, cfg.solver.max_delay = BATCH, steps_per_call, 1
    cfg.parallel.data_shards, cfg.parallel.kv_shards = data, kv
    return dlrm.pod_config(cfg)


def trainer_of(mesh_name, **kw):
    data, kv = MESHES[mesh_name]
    return PodTrainer(make_cfg(data, kv, **kw), mesh=make_mesh(data, kv), reporter=quiet())


def write_files(tmp_path, n_files, per_file, seed=5):
    labels, ints, cats = criteo.make_examples(seed, n_files * per_file, SPEC)
    paths = []
    for i in range(n_files):
        sl = slice(i * per_file, (i + 1) * per_file)
        paths.append(str(tmp_path / f"part-{i}.tsv"))
        criteo.write_tsv(paths[-1], labels[sl], ints[sl], cats[sl])
    return paths, labels, ints, cats


def batches_of(trainer, paths):
    fmt, key_mode = ingest_of(trainer.cfg)
    cfg = trainer.cfg
    builder = BatchBuilder(cfg.data.num_keys, BATCH, cfg.data.max_nnz_per_example, key_mode=key_mode)
    return list(MinibatchReader(paths, fmt, builder))


def train(trainer, paths):
    """One worker: ``train_files``; more: the files' batches in order
    through ``train_batches`` (which worker's stream a pool hands a tiny
    file to depends on how fast the threads start)."""
    if trainer.data_shards == 1:
        trainer.train_files(paths)
    else:
        trainer.train_batches(batches_of(trainer, paths), report_every=10**6)


def record_steps(trainer):
    seen = []
    step_fn = trainer.step_fn

    def recorded(state, batch, seed):
        new_state, out = step_fn(state, batch, seed)
        seen.append(out)
        return new_state, out

    trainer.step_fn = recorded
    return seen


def dense_leaves(trainer, which=0):
    """The dense group's leaves (``which`` 0) or their AdaGrad ``n`` (1) in
    the reference's order: bottom MLP, cross layers, top MLP."""
    tree = trainer.dense()[which]
    out = [np.asarray(layer[k]) for layer in tree["bot"] for k in ("W", "b")]
    out += [np.asarray(layer[k]) for layer in tree.get("cross", []) for k in ("V", "W", "b")]
    return out + [np.asarray(layer[k]) for layer in tree["top"] for k in ("W", "b")]


def close_enough(got, want, move, name, share=2e-4):
    """``got`` against ``want``, both a start plus AdaGrad steps: within
    1e-4 of the largest move (float32 sums in two orders, through a square
    root and a quotient), but for at most ``share`` of the elements, where
    a gradient is a sum that all but cancels: AdaGrad's first step on an
    element is ``eta g / (|g| + eps)``, ``eta`` times the SIGN of g, so two
    sums that differ in their last bits around zero land ``eta`` apart."""
    got, want = np.asarray(got), np.asarray(want)
    off = np.abs(got - want) > 1e-4 * move + 1e-7
    assert off.mean() <= share, (name, float(off.mean()), float(np.abs(got - want).max()), move)


@pytest.mark.parametrize("steps", [1, 8], ids=["one_microstep", "eight_microsteps"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_pod_trainer_matches_the_plain_reference(tmp_path, mesh_name, steps):
    """Losses, probabilities, every row's ``w`` and ``n`` and every dense
    leaf with its ``n`` against the plain reference after 1 microstep (one
    single-step call) and 8 (two scanned calls of 4). The start is the
    reference's to the bit. A loss is a sum of B float32 terms (2e-6
    relative); a probability moves with a logit built by float32 products
    of up to 216 terms through three cross layers (2e-5 absolute); ``n`` is
    a sum of squares, continuous in the gradient (1e-4 of the largest);
    ``w`` as ``close_enough`` says."""
    data, _ = MESHES[mesh_name]
    k = 1 if steps == 1 else 4
    paths, labels, ints, cats = write_files(tmp_path, 1, BATCH * steps * data)
    tr = trainer_of(mesh_name, steps_per_call=k)
    seen = record_steps(tr)
    bags, x = ref_dlrm_dcn.features(ints, cats, FIELD_ROWS, HOT, dlrm.BAG_SEED)
    ref = ref_dlrm_dcn.RefDcn(HYPER, SEED, FIELD_ROWS)
    n_keys = dlrm.num_keys_of(FIELD_ROWS)
    np.testing.assert_array_equal(tr.full_weights("emb"), ref.w)  # the start, to the bit
    for got, want in zip(dense_leaves(tr), ref.dense):
        np.testing.assert_array_equal(got, want)
    w_start, dense_start = ref.w.copy(), ref.dense_flat()
    train(tr, paths)
    want_loss, want_p = [], []
    for s in range(steps):
        workers = []
        for d in range(data):
            sl = slice((s * data + d) * BATCH, (s * data + d + 1) * BATCH)
            workers.append((ref_dlrm_dcn.cut(bags, sl), x[sl], labels[sl]))
        want_p.append([ref.predict(b, v) for b, v, _ in workers])
        want_loss.append(ref.step(workers))
    real = [o for o in seen if np.asarray(o["examples"]).sum() > 0]
    got_loss = np.concatenate([np.atleast_1d(np.asarray(o["loss_sum"])) for o in real])[:steps]
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    got_p = np.concatenate([np.asarray(o["probs"]).reshape(data, -1, BATCH) for o in real], axis=1)[:, :steps]
    np.testing.assert_allclose(got_p, np.asarray(want_p).transpose(1, 0, 2), atol=2e-5)
    w = tr.full_weights("emb")
    n = np.asarray(tr.runtime.state_to_host({"n": tr.state["emb.n"]})["n"])[:n_keys]
    touched = ref_dlrm_dcn.rows_of(bags)
    moved = float(np.abs(ref.w - w_start).max())
    assert moved > 0.5 * ETA and ref.n[touched].min() >= 0 and ref.n.max() > 0
    np.testing.assert_allclose(n, ref.n, atol=1e-4 * float(ref.n.max()), rtol=1e-4)
    close_enough(w[touched], ref.w[touched], moved, "emb.w")
    untouched = np.setdiff1d(np.arange(n_keys), touched)
    np.testing.assert_array_equal(w[untouched], w_start[untouched])
    assert not w[:14].any() and not n[untouched].any()
    for i, (got, got_n, want, want_n) in enumerate(zip(dense_leaves(tr), dense_leaves(tr, 1), ref.dense, ref.dense_n)):
        np.testing.assert_allclose(got_n, want_n, atol=1e-4 * float(want_n.max()) + 1e-12, rtol=1e-4, err_msg=str(i))
        close_enough(got, want, ETA * steps, f"dense leaf {i}", share=2e-3)
    assert float(np.abs(ref.dense_flat() - dense_start).max()) > 0.5 * ETA


# -- the bags ------------------------------------------------------------------
# (seed, f, r, j) -> u(seed, f, r, j): computed once by hand from the definition in
# ``data.libsvm.bag_draw``'s docstring, and pinned
PINNED = [
    ((0, 0, 0, 1), 4764156602392020899),
    ((1, 2, 3, 4), 3504935983707091026),
    ((2**63 + 5, 25, 999_999, 99), 9676986395762119469),
]


def test_bag_draws_are_pinned_in_both_parsers_and_the_reference(tmp_path):
    """Five literal ``(seed, f, r, j) -> row`` values: ``bag_draw``, the
    Python parser, the native parser and the reference's ``bag_rows`` name
    the same row."""
    for args, want in PINNED:
        assert bag_draw(*args) == want
    rows = [1_000_000, 39_060, 17] + [1000] * 23
    hot = [3, 1, 2] + [1] * 21 + [5, 1]
    seed = 2**63 + 5
    first = ref_dlrm.field_first_rows(rows)
    # one line whose field 0 holds id 7, field 2 id 3 (mod 17), field 24 id 999,999 (mod 1000 = 999)
    cats = np.zeros((1, 26), np.uint32)
    cats[0, 0], cats[0, 2], cats[0, 24] = 7, 3 + 17 * 5, 999_999
    path = str(tmp_path / "one.tsv")
    criteo.write_tsv(path, np.ones(1, np.float32), np.ones((1, 13), np.int64), cats)
    fmt = criteo_format(rows, hot, seed)
    want_rows = {  # (field, place in the bag) -> table row, each from the literal draw's definition
        (0, 0): first[0] + 7,
        (0, 1): first[0] + bag_draw(seed, 0, 7, 1) % 1_000_000,
        (0, 2): first[0] + bag_draw(seed, 0, 7, 2) % 1_000_000,
        (2, 1): first[2] + bag_draw(seed, 2, 3, 1) % 17,
        (24, 4): first[24] + bag_draw(seed, 24, 999, 4) % 1000,
    }
    # the five rows as literals (field 0's table starts at row 14, field 2's behind 1,000,000 + 39,060 rows)
    literal = {
        (0, 0): 14 + 7, (0, 1): 14 + 697_351, (0, 2): 14 + 483_657, (2, 1): 14 + 1_039_060 + 8,
        (24, 4): 14 + 1_039_060 + 17 + 21 * 1000 + 287,
    }
    assert want_rows == literal, {k: want_rows[k] - first[k[0]] for k in want_rows}
    (label, keys, vals, slots), = list(iter_format(fmt, path))
    assert len(keys) == 13 + sum(hot) and set(vals[13:]) == {1.0}
    starts = 13 + np.concatenate([[0], np.cumsum(hot)[:-1]])
    for (f, place), row in want_rows.items():
        assert keys[starts[f] + place] + 1 == row  # identity keying: row = key + 1
    assert list(slots[starts[24] : starts[24] + 5]) == [24 + 14] * 5
    if native.native_available():
        (chunk,) = list(native.iter_chunks(path, fmt))
        np.testing.assert_array_equal(chunk[2], keys)
        np.testing.assert_array_equal(chunk[4], slots)
    bags, _ = ref_dlrm_dcn.features(np.ones((1, 13), np.int64), cats, rows, hot, seed)
    for (f, place), row in want_rows.items():
        assert bags[f][0, place] == row


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_criteo_parsers_agree_in_the_multi_hot_layout(tmp_path, seed):
    """Generated lines, and lines with an empty or malformed field (both
    parsers skip the field and its bag: the example is shorter, and it is
    the app that refuses it), under "criteo:<sizes>:<bags>:<seed>"; the
    reference's bags are the parsers' on whole lines."""
    if not native.native_available():
        pytest.skip("no native parser")
    labels, ints, cats = criteo.make_examples(seed, 300, SPEC)
    path = str(tmp_path / "x.tsv")
    criteo.write_tsv(path, labels, ints, cats)
    lines = open(path).read().splitlines()
    for at, col, junk in ((3, 15, ""), (9, 2, "3x7"), (20, 39, "zz"), (21, 14, "DEADBEEF"), (40, 1, "-12")):
        cols = lines[at].split("\t")
        cols[col] = junk
        lines[at] = "\t".join(cols)
    open(path, "w").write("\n".join(lines) + "\n")
    fmt = criteo_format(FIELD_ROWS, HOT, seed)
    chunks = list(native.iter_chunks(path, fmt, chunk_bytes=4096))
    got = [np.concatenate([c[i] for c in chunks]) for i in (0, 2, 3, 4)]
    rows = list(iter_format(fmt, path))
    want = [np.asarray([r[0] for r in rows], np.float32)] + [np.concatenate([r[i] for r in rows]) for i in (1, 2, 3)]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    counts = np.asarray([len(r[1]) for r in rows])
    short = {3: HOT[1], 9: 1, 20: HOT[25]}  # an empty / malformed field costs its whole bag (or its one dense entry)
    assert all(counts[i] == ENTRIES - cost for i, cost in short.items())
    bags, x = ref_dlrm_dcn.features(ints, cats, FIELD_ROWS, HOT, seed)
    whole = np.setdiff1d(np.arange(300), [3, 9, 20, 21, 40])
    assert (counts[whole] == ENTRIES).all()
    for i in whole[:60]:
        np.testing.assert_array_equal(rows[i][1][13:] + 1, np.concatenate([b[i] for b in bags]))
        np.testing.assert_array_equal(rows[i][2][:13], x[i])


def test_a_row_twice_in_one_bag_counts_twice_in_the_sum_and_once_in_the_push(tmp_path):
    """Field 1's table has 3 rows and its bags 4 places: every bag repeats
    a row. The pooled vector counts the row as often as the bag names it
    (the program's logits are the reference's, whose ``pool`` gathers the
    bag entry by entry), and the push updates the row once, by the summed
    gradient: ``n`` after one step is that sum's square, not a sum of
    squares."""
    hot = list(HOT)
    hot[1] = 4
    paths, labels, ints, cats = write_files(tmp_path, 1, BATCH)
    tr = PodTrainer(make_cfg(hot=hot), mesh=make_mesh(1, 1), reporter=quiet())
    seen = record_steps(tr)
    bags, x = ref_dlrm_dcn.features(ints, cats, FIELD_ROWS, hot, dlrm.BAG_SEED)
    assert all(len(set(bag)) < 4 for bag in bags[1])
    ref = ref_dlrm_dcn.RefDcn(HYPER, SEED, FIELD_ROWS)
    want_p = ref.predict(bags, x)
    _, (touched, g_rows), _ = ref.grads(bags, x, labels)
    ref.step([(bags, x, labels)])
    tr.train_files(paths)
    np.testing.assert_allclose(np.asarray(seen[0]["probs"]).ravel()[:BATCH], want_p, atol=2e-5)
    first = int(ref_dlrm.field_first_rows(FIELD_ROWS)[1])
    n = np.asarray(tr.state["emb.n"])[first : first + 3, :DIM]
    at = np.searchsorted(touched, np.arange(first, first + 3))
    np.testing.assert_allclose(n, g_rows[at] ** 2, rtol=1e-4, atol=1e-12)
    np.testing.assert_allclose(n, ref.n[first : first + 3], rtol=1e-4, atol=1e-12)


def test_bags_of_one_without_cross_under_sgd_are_the_one_hot_app_to_the_bit(tmp_path):
    """``hot`` all 1, ``cross_layers`` 0 and ``sgd``, said or left to their
    defaults: the same format string, the same description, and after two
    calls the same state to the bit."""
    paths, *_ = write_files(tmp_path, 1, BATCH * 4)

    def run(explicit: bool):
        cfg = make_cfg(steps_per_call=2, hot=[1] * 26, cross_layers=0, updater="sgd")
        if not explicit:
            cfg.dlrm.hot, cfg.dlrm.cross_rank, cfg.dlrm.eps = PSConfig().dlrm.hot, 77, 0.5  # unread without cross / adagrad
            cfg = dlrm.pod_config(cfg)
        assert cfg.data.format == criteo_format(FIELD_ROWS) and ":" not in cfg.data.format.partition(":")[2]
        tr = PodTrainer(cfg, mesh=make_mesh(1, 1), reporter=quiet())
        assert tr.app.scope_names() == {"emb", "mlp", "bot", "interact", "top"}
        assert tr.app.check_batch is dlrm.check_batch and tr.app.grad is dlrm._grad
        tr.train_files(paths)
        return {k: np.asarray(v) for k, v in tr.state.items()}

    a, b = run(True), run(False)
    assert set(a) == set(b) and "emb.n" not in a and not any(k.startswith("mlp.cross") for k in a)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_a_batch_with_a_short_example_is_refused(tmp_path):
    """A line with an empty one-id field parses to one entry fewer (226 at
    the cell's bag sizes, ``ENTRIES - 1`` here); the app's host check
    refuses the batch and says why."""
    paths, *_ = write_files(tmp_path, 1, BATCH)
    lines = open(paths[0]).read().splitlines()
    cols = lines[7].split("\t")
    cols[14 + 2] = ""  # field 2: a bag of one
    lines[7] = "\t".join(cols)
    bad = str(tmp_path / "short.tsv")
    open(bad, "w").write("\n".join(lines) + "\n")
    tr = trainer_of("1x1")
    match = f"{ENTRIES} entries by position.*bags of {sum(HOT)} ids.*example 7 of the batch carries {ENTRIES - 1}"
    with pytest.raises(ValueError, match=match):
        tr.train_files([bad])
    with pytest.raises(ValueError, match=f"carries {ENTRIES - 1}"):
        tr.evaluate_files([bad])
    tr.train_files(paths)
    # the cell's own count: 13 + 214 = 227, and 226 is refused
    from parameter_server_tpu.data.batch import CSRBatch

    cell_hot = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10, 3, 1, 1]
    assert dlrm.entries_of(cell_hot) == 227
    splits = np.asarray([0, 227, 227 + 226], np.int32)
    batch = CSRBatch(*([None] * 6), row_splits=splits, num_examples=2, num_unique=1, num_entries=453)
    with pytest.raises(ValueError, match="227 entries by position.*example 1 of the batch carries 226"):
        dlrm.check_batch(batch, entries=227)


def test_cross_layer_against_a_three_line_numpy_form():
    rng = np.random.default_rng(3)
    width = 27 * DIM
    layers = mlp.init_cross(width, RANK, 3, 5)
    x0 = rng.normal(size=(6, width)).astype(np.float32)
    x = x0.astype(np.float64)
    for layer in layers:
        y = (x @ np.asarray(layer["V"], np.float64)) @ np.asarray(layer["W"], np.float64) + np.asarray(layer["b"])
        x = x0 * y + x
    got = np.asarray(mlp.cross_apply(layers, jnp.asarray(x0)))
    np.testing.assert_allclose(got, x, rtol=1e-5, atol=1e-5)
    assert [tuple(layer[k].shape) for layer in layers[:1] for k in ("V", "W", "b")] == [(width, RANK), (RANK, width), (width,)]
    # the draws are the reference's: one generator, V, W, b a layer
    want = ref_dlrm_dcn.init_cross(np.random.default_rng(5), width, RANK, 3)
    for layer, (v, w, b) in zip(layers, want):
        for got_a, want_a in zip((layer["V"], layer["W"], layer["b"]), (v, w, b)):
            np.testing.assert_array_equal(got_a, want_a)


def test_dense_adagrad_is_the_tables_rule_on_the_same_numbers():
    """``dense_adagrad`` (the dense group's optax transformation) against
    ``kv.updaters.Adagrad.delta`` over three steps, one of them a zero
    gradient; not ``optax.adagrad`` (eps inside the root, n from 0.1)."""
    import optax

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(5, 4)).astype(np.float32))
    opt, rule = dense_adagrad(0.004, 1e-8), Adagrad(eta=0.004, eps=1e-8)
    state = opt.init({"a": w})
    assert not np.asarray(state["a"]).any()
    rows = {"w": w, "n": jnp.zeros_like(w)}
    params = {"a": w}
    for g in (rng.normal(size=(5, 4)), np.zeros((5, 4)), rng.normal(size=(5, 4)) * 1e-3):
        g = jnp.asarray(g.astype(np.float32))
        updates, state = opt.update({"a": g}, state, params)
        params = optax.apply_updates(params, updates)
        d = rule.delta(rows, g)
        rows = {k: rows[k] + d[k] for k in rows}
        np.testing.assert_array_equal(params["a"], rows["w"])
        np.testing.assert_array_equal(state["a"], rows["n"])
    first = 0.004 * np.sign(np.asarray(w) - np.asarray(params["a"]))
    assert np.abs(first).max() == np.float32(0.004)  # a first step is eta x sign(g), near enough
    other = optax.adagrad(0.004, initial_accumulator_value=0.1, eps=1e-8)
    u, _ = other.update({"a": jnp.ones_like(w)}, other.init({"a": w}), {"a": w})
    assert abs(float(u["a"][0, 0]) + 0.004) > 1e-4  # optax's own starts n at 0.1: another step


def test_format_with_bags_is_parsed_and_refused():
    fmt = criteo_format(FIELD_ROWS, HOT, 123)
    assert split_format(fmt) == ("criteo", CriteoBags(tuple(FIELD_ROWS), tuple(HOT), 123))
    assert split_format(fmt)[1].entries == ENTRIES
    assert criteo_format(FIELD_ROWS, [1] * 26, 123) == criteo_format(FIELD_ROWS)
    sizes = criteo_format(FIELD_ROWS)
    for bad in (sizes + ":1,2,3:0", sizes + ":" + ",".join(["0"] * 26) + ":0", sizes + ":" + ",".join(["2"] * 26),
                sizes + ":" + ",".join(["2"] * 26) + ":x"):
        with pytest.raises(ValueError, match="26 bag sizes"):
            split_format(bad)
    assert ingest_of(make_cfg()) == (criteo_format(FIELD_ROWS, HOT, dlrm.BAG_SEED), "identity")


def test_a_config_the_multi_hot_form_cannot_read_is_refused():
    dlrm.app_from_config(make_cfg())
    for change, match in (
        (lambda c: setattr(c.dlrm, "hot", HOT[:25]), "26 bag sizes"),
        (lambda c: setattr(c.dlrm, "hot", [0] + HOT[1:]), "26 bag sizes"),
        (lambda c: setattr(c.data, "max_nnz_per_example", ENTRIES - 1), f"{ENTRIES} entries"),
        (lambda c: setattr(c.dlrm, "updater", "adam"), "'sgd' or 'adagrad'"),
        (lambda c: setattr(c.dlrm, "cross_rank", 0), "cross_rank >= 1"),
        (lambda c: setattr(c.dlrm, "cross_layers", -1), "cross_layers is a count"),
        (lambda c: setattr(c.data, "format", criteo_format(FIELD_ROWS)), "pod_config fills both in"),
    ):
        cfg = make_cfg()
        change(cfg)
        with pytest.raises(ValueError, match=match):
            dlrm.app_from_config(cfg)


def test_description_names_its_scopes_and_keeps_two_slots():
    app = dlrm.app_from_config(make_cfg())
    assert app.scope_names() == {"emb", "mlp", "bot", "cross", "top", "pool"}
    assert sorted(app.table_keys()) == ["emb.n", "emb.w"] and app.tables[0].updater == Adagrad(eta=ETA, eps=EPS)
    keys = app.dense.keys()
    assert "mlp.cross.0.V" in keys and "mlp_opt.cross.2.b" in keys and "mlp.top.0.W" in keys
    assert len(keys) == 2 * (2 * (len(BOT) + len(TOP)) + 3 * LAYERS)
    both = dlrm.init_mlps(9, DIM, BOT, TOP, LAYERS, RANK)
    assert both["top"][0]["W"].shape == (27 * DIM, TOP[0]) == (dlrm.interaction_width(DIM, cross=True), TOP[0])


def test_pool_bags_sums_each_fields_run():
    rng = np.random.default_rng(1)
    rows = rng.normal(size=(5, sum(HOT), DIM)).astype(np.float32)
    got = np.asarray(dlrm.pool_bags(jnp.asarray(rows), tuple(HOT)))
    at = 0
    for f, h in enumerate(HOT):
        np.testing.assert_allclose(got[:, f], rows[:, at : at + h].sum(axis=1), rtol=1e-6, atol=1e-6)
        at += h
    assert got.shape == (5, 26, DIM)


def _plain_read(pulled, slots, hot):
    """The pooled read with nothing written by hand: one take of the
    ``(B, sum(hot))`` slots, a slice and a sum a field."""
    rows, out, at = jnp.take(pulled, slots, axis=0), [], 0
    for h in hot:
        out.append(rows[:, at : at + h].sum(axis=1))
        at += h
    return jnp.stack(out, axis=1)


def _first_row_of_each_bag(rows, hot):
    """``benchmark/tests/test_controls_dcn.py``'s broken pooling, to the
    letter: every field's vector its bag's first row."""
    at = [sum(hot[:f]) for f in range(len(hot))]
    return rows[:, jnp.asarray(at)]


def _step_inputs(examples, hot, rng):
    """(pulled, params, batch, slots) for ``dlrm._logits``: every bag
    position of every example reads a row of its own."""
    entries = 13 + sum(hot)
    ids = np.zeros((examples, entries), np.int32)
    slots = 1 + np.arange(examples * sum(hot), dtype=np.int32).reshape(examples, sum(hot))
    ids[:, 13:] = slots
    values = np.ones((examples, entries), np.float32)
    values[:, :13] = rng.normal(size=(examples, 13))
    batch = {
        "local_ids": jnp.asarray(ids.reshape(-1)), "values": jnp.asarray(values.reshape(-1)),
        "labels": jnp.asarray(rng.integers(0, 2, examples).astype(np.float32)),
        "example_mask": jnp.ones(examples, bool),
    }
    pulled = {dlrm.TABLE: jnp.asarray(rng.normal(size=(1 + slots.size, DIM)).astype(np.float32))}
    params = jax.tree_util.tree_map(jnp.asarray, dlrm.init_mlps(3, DIM, BOT, TOP, LAYERS, RANK))
    return pulled, params, batch, slots


@pytest.mark.parametrize("kind,examples", [
    ("runs_of_one", 8), ("runs_of_one", 13), ("no_run_of_one", 16), ("no_run_of_one", 5), ("cell_bags", 8),
    ("first_row_seam", 8), ("first_row_seam", 13),
])
def test_pooled_read_differentiates_as_the_plain_form_and_leaves_the_pooling_to_jax_grad(kind, examples, monkeypatch):
    """``read_bags`` takes position-major and ``pool_bags`` carries a
    hand-written backward pass: the gradient with respect to the pulled rows
    is the plain form's (``jax.grad`` of a take, a slice and a sum) on slots
    that repeat inside a bag, across bags and across examples, with and
    without runs of one-id bags, at a batch of whole 8-row tiles and not.
    And the hand-written pass is ``pool_bags``' alone: with the module's
    ``pool_bags`` replaced by a function that keeps each bag's first row (the
    benchmark's control, which tier 1 does not run), the step's logits change
    and every other bag row's gradient is exactly zero, so the control still
    fails a wrong program."""
    rng = np.random.default_rng(examples)
    if kind == "first_row_seam":
        hot = tuple(HOT)
        pulled, params, batch, slots = _step_inputs(examples, hot, rng)
        sound = dlrm._logits(pulled, params, batch, None, hot)
        monkeypatch.setattr(dlrm, "pool_bags", _first_row_of_each_bag)
        broken = dlrm._logits(pulled, params, batch, None, hot)
        assert np.abs(np.asarray(broken) - np.asarray(sound)).max() > 1e-3
        _, _, g_pulled, _ = dlrm._grad(pulled, params, batch, None, hot)
        g = np.asarray(g_pulled[dlrm.TABLE])
        first = np.zeros(sum(hot), bool)
        first[np.cumsum((0, *hot[:-1]))] = True
        assert (g[slots[:, ~first]] == 0.0).all() and (np.abs(g[slots[:, first]]).max(axis=-1) > 0).all()
        return
    hot = {"runs_of_one": tuple(HOT), "no_run_of_one": (3, 2, 7, 2, 4), "cell_bags": (1, 1, 12, 100, 27, 1)}[kind]
    slots = rng.integers(0, 9, size=(examples, sum(hot))).astype(np.int32)  # nine rows: repeats everywhere
    slots[0, :3] = 4  # a row three times in one bag,
    slots[1] = slots[0]  # every bag of an example in another's,
    slots[2, -2:] = slots[2, 0]  # a row in two bags of one example
    pulled = jnp.asarray(rng.normal(size=(12, DIM)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=(examples, len(hot), DIM)).astype(np.float32))
    slots = jnp.asarray(slots)
    np.testing.assert_allclose(dlrm.read_bags(pulled, slots, hot), _plain_read(pulled, slots, hot), rtol=1e-6, atol=1e-5)
    got = jax.grad(lambda p: jnp.sum(ct * dlrm.read_bags(p, slots, hot)))(pulled)
    want = jax.grad(lambda p: jnp.sum(ct * _plain_read(p, slots, hot)))(pulled)
    assert np.asarray(want)[9:].max() == 0.0 and np.abs(np.asarray(want)[:9]).min() > 0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_predict_is_the_references_forward_pass(tmp_path, mesh_name):
    data = MESHES[mesh_name][0]
    per_file = BATCH * 2 * data
    paths, labels, ints, cats = write_files(tmp_path, 2, per_file)
    tr = trainer_of(mesh_name)
    train(tr, paths[:1])
    bags, x = ref_dlrm_dcn.features(ints, cats, FIELD_ROWS, HOT, dlrm.BAG_SEED)
    ref = ref_dlrm_dcn.RefDcn(HYPER, SEED, FIELD_ROWS)
    for s in range(2):
        spans = [slice((s * data + d) * BATCH, (s * data + d + 1) * BATCH) for d in range(data)]
        ref.step([(ref_dlrm_dcn.cut(bags, sl), x[sl], labels[sl]) for sl in spans])
    held = slice(per_file, 2 * per_file)
    got_y, got_p = tr.predict_batches(batches_of(tr, paths[1:]))
    np.testing.assert_array_equal(got_y, labels[held])
    np.testing.assert_allclose(got_p, ref.predict(ref_dlrm_dcn.cut(bags, held), x[held]), atol=5e-5)
    assert tr.evaluate_files(paths[1:])["examples"] == per_file


def test_cli_trains_scores_checkpoints_and_dumps_the_multi_hot_form(tmp_path):
    paths, *_ = write_files(tmp_path, 3, 256)
    cfg = {
        "app": "dlrm", "seed": 3,
        "data": {"files": paths[:2], "val_files": paths[2:], "max_nnz_per_example": ENTRIES},
        "dlrm": {"emb_dim": DIM, "bot": BOT, "top": TOP, "eta": 0.004, "field_rows": FIELD_ROWS, "hot": HOT,
                 "cross_layers": LAYERS, "cross_rank": RANK, "updater": "adagrad", "eps": 1e-8},
        "solver": {"minibatch": 64, "steps_per_call": 2, "epochs": 2},
        "parallel": {"data_shards": 2, "kv_shards": 2},
    }
    p = tmp_path / "dcn.json"
    p.write_text(json.dumps(cfg))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT + ":" + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run(
        [sys.executable, "-m", "parameter_server_tpu.cli", "train", "--app_file", str(p),
         "--model_out", str(tmp_path / "m.npz"), "--ckpt_dir", str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["tables"] == 26 and 0.0 < out["val_auc"] < 1.0 and np.isfinite(out["val_logloss"])
    d = np.load(tmp_path / "m.npz")
    assert d["emb_w"].shape == (dlrm.num_keys_of(FIELD_ROWS), DIM)
    assert d["cross_V0"].shape == (27 * DIM, RANK) and d["cross_W2"].shape == (RANK, 27 * DIM)
    assert d["top_W0"].shape == (27 * DIM, TOP[0]) and d["bot_b0"].shape == (BOT[0],)
    assert (tmp_path / "ck" / "dense.npz").exists()
    # a checkpoint holds the accumulators too: resumed, the run goes on from them
    r = subprocess.run(
        [sys.executable, "-m", "parameter_server_tpu.cli", "train", "--app_file", str(p),
         "--ckpt_dir", str(tmp_path / "ck"), "--resume"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-2000:]
