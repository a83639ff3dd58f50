"""DLRM through ``PodTrainer`` - 26 per-field tables as one table, two MLPs
and the pairwise-dot interaction in the shared parameter-server step -
against the benchmark's plain NumPy reference
(``benchmark/harness/ref_dlrm.py``, which imports nothing of the program),
on the CPU at small sizes: the step on three mesh shapes, the interaction
against a double loop over pairs, predict, the host's refusal of a short
example, the reserved rows, checkpoints across mesh shapes, the per-field
layout in both parsers (and the hashed layout left as it was), the
device-made starting rows, the CLI."""

import hashlib
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

from benchmark.harness import criteo, ref_dlrm  # noqa: E402
from benchmark.harness.ref_ftrl import auc, logloss  # noqa: E402
from parameter_server_tpu.data import native  # noqa: E402
from parameter_server_tpu.data.batch import BatchBuilder  # noqa: E402
from parameter_server_tpu.data.libsvm import criteo_format, iter_format, split_format  # noqa: E402
from parameter_server_tpu.data.reader import MinibatchReader, ingest_of  # noqa: E402
from parameter_server_tpu.models import dlrm, mlp, wide_deep  # noqa: E402
from parameter_server_tpu.parallel import make_mesh  # noqa: E402
from parameter_server_tpu.parallel.trainer import PodTrainer  # noqa: E402
from parameter_server_tpu.utils.config import PSConfig  # noqa: E402
from parameter_server_tpu.utils.metrics import ProgressReporter  # noqa: E402

BATCH = 64
DIM, BOT, TOP, ETA = 16, [32, 16], [64, 32, 1], 0.01
# 26 cardinalities: three tables over the cap, tiny ones whose rows every batch hits
VOCAB = [50, 3, 1000, 7, 20000, 4, 900] + [30] * 19
CAP = 500
FIELD_ROWS = [min(v, CAP) for v in VOCAB]
SPEC = dict(cat_vocab=VOCAB, zipf_s=1.1, int_mu=2.0, int_sigma=1.5, truth_density=0.3, truth_scale=0.8, base_rate=0.25)
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}
HYPER = dict(emb_dim=DIM, bot=BOT, top=TOP, eta=ETA)


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def make_cfg(data=1, kv=1, steps_per_call=1, seed=11, push_mode="per_worker"):
    cfg = PSConfig()
    cfg.seed = seed
    cfg.dlrm.emb_dim, cfg.dlrm.bot, cfg.dlrm.top, cfg.dlrm.eta = DIM, list(BOT), list(TOP), ETA
    cfg.dlrm.field_rows = list(FIELD_ROWS)
    cfg.data.max_nnz_per_example = 39
    cfg.solver.minibatch, cfg.solver.steps_per_call, cfg.solver.max_delay = BATCH, steps_per_call, 1
    cfg.parallel.data_shards, cfg.parallel.kv_shards = data, kv
    cfg.parallel.push_mode = push_mode
    return dlrm.pod_config(cfg)


def trainer_of(mesh_name, **kw):
    data, kv = MESHES[mesh_name]
    return PodTrainer(make_cfg(data, kv, **kw), mesh=make_mesh(data, kv), reporter=quiet())


def write_files(tmp_path, n_files, per_file, seed=5):
    """``n_files`` criteo TSV files of ``per_file`` generated examples, every
    field present; (paths, labels, ints, cats) with the files' examples in
    order."""
    labels, ints, cats = criteo.make_examples(seed, n_files * per_file, SPEC)
    paths = []
    for i in range(n_files):
        sl = slice(i * per_file, (i + 1) * per_file)
        paths.append(str(tmp_path / f"part-{i}.tsv"))
        criteo.write_tsv(paths[-1], labels[sl], ints[sl], cats[sl])
    return paths, labels, ints, cats


def batches_of(trainer, paths):
    """The files' minibatches as the trainer's own reader builds them."""
    fmt, key_mode = ingest_of(trainer.cfg)
    builder = BatchBuilder(trainer.cfg.data.num_keys, BATCH, 39, key_mode=key_mode)
    return list(MinibatchReader(paths, fmt, builder))


def train(trainer, paths):
    """Train the files: through ``train_files`` for one worker; for more,
    the files' batches in order through ``train_batches`` (a microstep takes
    one batch a worker, in stream order), because which worker's stream a
    pool hands a tiny file to depends on how fast the threads start."""
    if trainer.data_shards == 1:
        trainer.train_files(paths)
    else:
        trainer.train_batches(batches_of(trainer, paths), report_every=10**6)


def record_steps(trainer):
    """The losses and probabilities of every dispatched call, microstep by microstep."""
    seen = []
    step_fn = trainer.step_fn

    def recorded(state, batch, seed):
        new_state, out = step_fn(state, batch, seed)
        seen.append(out)
        return new_state, out

    trainer.step_fn = recorded
    return seen


def mlp_flat(trainer):
    params = trainer.dense()[0]
    return np.concatenate([
        np.asarray(x).ravel() for name in ("bot", "top") for layer in params[name] for x in (layer["W"], layer["b"])
    ])


@pytest.mark.parametrize("steps", [1, 8], ids=["one_microstep", "eight_microsteps"])
@pytest.mark.parametrize("mesh_name", list(MESHES))
def test_pod_trainer_matches_the_plain_reference(tmp_path, mesh_name, steps):
    """The program's losses, probabilities, every touched row and every
    dense parameter against the plain reference after 1 microstep (one
    single-step call) and 8 (two scanned calls of 4), a microstep taking a
    batch a worker in the file's order. Tolerances, all float32 on the CPU: a loss is a sum of
    B terms each good to 1e-7 relative (2e-6); a probability moves with a
    logit that 479- and 1024-term float32 dot products build (1e-5
    absolute); a row and a dense parameter is a start plus ``eta`` times a
    sum of up to B x steps gradients, summed by the program in float32 in
    its own order and by the reference in float64 (1e-5 of the largest
    move, beside one rounding of the value)."""
    data, _ = MESHES[mesh_name]
    k = 1 if steps == 1 else 4
    paths, labels, ints, cats = write_files(tmp_path, 1, BATCH * steps * data)
    tr = trainer_of(mesh_name, steps_per_call=k)
    seen = record_steps(tr)
    rows, x = ref_dlrm.features(ints, cats, FIELD_ROWS)
    ref = ref_dlrm.RefDlrm(np.concatenate([np.arange(14), rows.ravel()]), HYPER, 11, FIELD_ROWS)
    idx = ref.index(rows)
    np.testing.assert_array_equal(tr.full_weights("emb")[ref.rows], ref.w0)  # the start, to the bit
    np.testing.assert_array_equal(mlp_flat(tr), ref.mlp_flat_start())
    train(tr, paths)
    want_loss, want_p = [], []
    for s in range(steps):
        workers = []
        for d in range(data):
            sl = slice((s * data + d) * BATCH, (s * data + d + 1) * BATCH)
            workers.append((idx[sl], x[sl], labels[sl]))
        want_p.append([ref.predict(i, v) for i, v, _ in workers])
        want_loss.append(ref.step(workers))
    real = [o for o in seen if np.asarray(o["examples"]).sum() > 0]
    got_loss = np.concatenate([np.atleast_1d(np.asarray(o["loss_sum"])) for o in real])[:steps]
    np.testing.assert_allclose(got_loss, want_loss, rtol=2e-6)
    got_p = np.concatenate([np.asarray(o["probs"]).reshape(data, -1, BATCH) for o in real], axis=1)[:, :steps]
    np.testing.assert_allclose(got_p, np.asarray(want_p).transpose(1, 0, 2), atol=1e-5)
    w = tr.full_weights("emb")
    moved = float(np.abs(ref.w - ref.w0).max())
    assert moved > 1e-4
    np.testing.assert_allclose(w[ref.rows], ref.w, atol=1e-5 * moved + 1e-7, rtol=0)
    assert not w[:14].any()  # the pad's and the dense columns' rows
    untouched = np.setdiff1d(np.arange(len(w)), ref.rows)
    np.testing.assert_array_equal(w[untouched], ref_dlrm.init_rows(11, untouched, DIM, FIELD_ROWS))
    mlp_moved = float(np.abs(ref.mlp_flat() - ref.mlp_flat_start()).max())
    assert mlp_moved > 1e-3
    np.testing.assert_allclose(mlp_flat(tr), ref.mlp_flat(), atol=1e-5 * mlp_moved + 1e-7, rtol=0)


def test_interaction_against_a_double_loop_over_pairs():
    """``interact`` alone: z0, then the dot of vector i with vector j for
    every i > j, row by row, of the 27 vectors [z0; e_1; ...; e_26]."""
    rng = np.random.default_rng(3)
    z0 = rng.normal(size=(5, DIM)).astype(np.float32)
    e = rng.normal(size=(5, 26, DIM)).astype(np.float32)
    got = np.asarray(dlrm.interact(jnp.asarray(z0), jnp.asarray(e)))
    assert got.shape == (5, dlrm.interaction_width(DIM)) == (5, DIM + 351)
    for b in range(5):
        t = np.concatenate([z0[b : b + 1], e[b]]).astype(np.float64)
        want = [float(t[i] @ t[j]) for i in range(27) for j in range(i)]
        np.testing.assert_array_equal(got[b, :DIM], z0[b])
        np.testing.assert_allclose(got[b, DIM:], want, rtol=1e-5, atol=1e-6)


def plain_interact(z0, e):
    """The interaction as ``jax.grad`` alone differentiates it: ``T T^t``,
    a row's entries under the diagonal by a static slice, one concatenate
    (``models.dlrm.interact`` until PR 51: the reference of its selection
    products and of its hand-written backward pass)."""
    t = jnp.concatenate([z0[:, None, :], e], axis=1)
    z = jnp.einsum("bid,bjd->bij", t, t, precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate([z0, *(z[:, i, :i] for i in range(1, t.shape[1]))], axis=1)


def interaction_case(vectors, d, examples=6, seed=51):
    rng = np.random.default_rng(seed)
    z0 = jnp.asarray(rng.normal(size=(examples, d)).astype(np.float32))
    e = jnp.asarray(rng.normal(size=(examples, vectors - 1, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d + vectors * (vectors - 1) // 2, 3)).astype(np.float32))
    return z0, e, w


def loss_through(interact):
    """A loss that weighs every output of the interaction differently, with
    the outputs as aux: the shape of ``models.dlrm._loss``."""

    def loss(z0, e, w):
        r = interact(z0, e)
        return jnp.sum(jnp.tanh(r @ w)), r

    return loss


def assert_relative(got, want, rel):
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * float(np.abs(want).max()))


@pytest.mark.parametrize("d", [8, 128])
@pytest.mark.parametrize("vectors", [3, 5, 27])
def test_interaction_and_its_gradient_against_the_plain_form(vectors, d):
    """The pairs cut by a selection product are the sliced ones to the bit
    (a 0/1 selector at ``HIGHEST`` moves a float32 whole), and the
    ``custom_vjp``'s one product of the symmetrised cotangent is
    ``jax.grad`` of the plain form (the same terms in another order:
    1e-6 of the largest entry) for ``z0`` and for ``e``."""
    z0, e, w = interaction_case(vectors, d)
    np.testing.assert_array_equal(dlrm.interact(z0, e), plain_interact(z0, e))
    got, _ = jax.grad(loss_through(dlrm.interact), argnums=(0, 1), has_aux=True)(z0, e, w)
    want, _ = jax.grad(loss_through(plain_interact), argnums=(0, 1), has_aux=True)(z0, e, w)
    for g, ref, like in zip(got, want, (z0, e)):
        assert g.shape == like.shape and float(np.abs(ref).max()) > 0.1
        assert_relative(g, ref, 1e-6)


def test_interaction_under_jit_and_value_and_grad_with_aux():
    """As ``models.dlrm._grad`` calls it: jitted, ``value_and_grad`` over
    two arguments with the forward values as aux."""
    z0, e, w = interaction_case(27, DIM, examples=BATCH)

    def run(interact):
        return jax.jit(jax.value_and_grad(loss_through(interact), argnums=(0, 1), has_aux=True))(z0, e, w)

    (loss, r), (g_z0, g_e) = run(dlrm.interact)
    (want_loss, want_r), (want_z0, want_e) = run(plain_interact)
    assert r.shape == (BATCH, dlrm.interaction_width(DIM))
    assert_relative(r, want_r, 1e-6)  # two jitted programs: XLA may order a dot's sum its own way
    assert_relative(loss, want_loss, 1e-6)
    assert_relative(g_z0, want_z0, 1e-6)
    assert_relative(g_e, want_e, 1e-6)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_predict_is_the_references_forward_pass(tmp_path, mesh_name):
    """``evaluate_files`` after some training: the evaluator's AUC and
    log-loss are the reference's over the same file, and the predict
    program's probabilities its forward pass (1e-5: float32 dot products)."""
    data = MESHES[mesh_name][0]
    per_file = BATCH * 3 * data
    paths, labels, ints, cats = write_files(tmp_path, 2, per_file)
    tr = trainer_of(mesh_name, steps_per_call=1)
    train(tr, paths[:1])
    rows, x = ref_dlrm.features(ints, cats, FIELD_ROWS)
    ref = ref_dlrm.RefDlrm(rows, HYPER, 11, FIELD_ROWS)
    idx = ref.index(rows)
    for s in range(3):
        spans = [slice((s * data + d) * BATCH, (s * data + d + 1) * BATCH) for d in range(data)]
        ref.step([(idx[sl], x[sl], labels[sl]) for sl in spans])
    held = slice(per_file, 2 * per_file)
    want = ref.predict(idx[held], x[held])
    got_y, got_p = tr.predict_batches(batches_of(tr, paths[1:]))
    np.testing.assert_array_equal(got_y, labels[held])
    np.testing.assert_allclose(got_p, want, atol=1e-5)
    ev = tr.evaluate_files(paths[1:])
    assert ev["examples"] == per_file
    assert ev["auc"] == pytest.approx(auc(labels[held], got_p), abs=1e-9)
    assert ev["logloss"] == pytest.approx(logloss(labels[held], got_p), rel=1e-6)


def test_a_batch_with_a_38_entry_example_is_refused(tmp_path):
    """A line with an empty categorical field parses to 38 entries (the
    parsers skip the field); the app's host check refuses the batch and
    says why, in training and in evaluation."""
    paths, *_ = write_files(tmp_path, 1, BATCH)
    lines = open(paths[0]).read().splitlines()
    cols = lines[7].split("\t")
    cols[20] = ""
    lines[7] = "\t".join(cols)
    bad = str(tmp_path / "short.tsv")
    open(bad, "w").write("\n".join(lines) + "\n")
    tr = trainer_of("1x1")
    with pytest.raises(ValueError, match="39 entries by position.*example 7 of the batch carries 38"):
        tr.train_files([bad])
    with pytest.raises(ValueError, match="carries 38"):
        tr.evaluate_files([bad])
    tr.train_files(paths)  # the whole file trains


def test_sgd_pushes_agree_between_push_modes(tmp_path):
    """Plain SGD is linear in the gradient: the ``aggregate`` push (one
    summed update) and the ``per_worker`` push (one a worker) leave the
    same table on 2 x 2, to float32's rounding of a sum taken in two
    orders; rows 0..13 stay zero under both."""
    paths, *_ = write_files(tmp_path, 2, BATCH * 2)
    tables = {}
    for mode in ("per_worker", "aggregate"):
        tr = trainer_of("2x2", steps_per_call=2, push_mode=mode)
        train(tr, paths)
        tables[mode] = tr.full_weights("emb")
        assert not tables[mode][:14].any()
    np.testing.assert_allclose(tables["aggregate"], tables["per_worker"], atol=1e-7, rtol=1e-6)


def test_checkpoint_round_trip_across_meshes(tmp_path):
    """Save after 4 microsteps on 2 x 2, load into a fresh trainer on 1 x 4:
    the table and both MLPs come back to the bit, and the two trainers
    score a held-out file alike."""
    paths, *_ = write_files(tmp_path, 3, BATCH * 2)
    first = trainer_of("2x2", steps_per_call=2)
    first.train_files(paths[:2])
    first.save(tmp_path / "ck")
    resumed = trainer_of("1x4", steps_per_call=2)
    meta = resumed.load(tmp_path / "ck")
    assert meta["examples_seen"] == 4 * BATCH == resumed.examples_seen
    assert set(resumed.state) == set(first.state) and "mlp.top.2.W" in first.state
    n = first.cfg.data.num_keys
    for k in first.state:
        a, b = np.asarray(first.state[k]), np.asarray(resumed.state[k])
        if k == "emb.w":
            a, b = a[:n], b[:n]
        np.testing.assert_array_equal(a, b, err_msg=k)
    a, b = first.evaluate_files(paths[2:]), resumed.evaluate_files(paths[2:])
    assert a["auc"] == pytest.approx(b["auc"], abs=1e-6) and a["logloss"] == pytest.approx(b["logloss"], rel=1e-5)


# -- the per-field layout in both parsers ------------------------------------
def _flat(chunks):
    return [np.concatenate([c[i] for c in chunks]) for i in (0, 2, 3, 4)]  # labels, keys, vals, slots


def _python_flat(fmt, path):
    rows = list(iter_format(fmt, path))
    return [np.asarray([r[0] for r in rows], np.float32)] + [np.concatenate([r[i] for r in rows]) for i in (1, 2, 3)]


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_criteo_parsers_agree_in_the_per_field_layout(tmp_path, seed):
    """``iter_criteo`` and ``ps_parse_criteo_fields`` under "criteo:<sizes>"
    give the same entries on generated lines and on lines with an empty or
    malformed field (both skip the field: the example is shorter, and it is
    the app that refuses it); a field's key is 13 + off_f + id mod R_f."""
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
    lines.insert(50, "1\t2\t3")  # too few columns: skipped whole
    open(path, "w").write("\n".join(lines) + "\n")
    fmt = criteo_format(FIELD_ROWS)
    got, want = _flat(list(native.iter_chunks(path, fmt))), _python_flat(fmt, path)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert len(got[0]) == 300 and len(got[1]) == 300 * 39 - 3
    rows, x = ref_dlrm.features(ints, cats, FIELD_ROWS)
    whole = np.setdiff1d(np.arange(300), [3, 9, 20, 21, 40])
    splits = np.concatenate([c[1][1:] + 0 for c in native.iter_chunks(path, fmt)])
    starts = np.concatenate([[0], splits[:-1]])
    for i in whole[:50]:
        keys = got[1][starts[i] : starts[i] + 39]
        np.testing.assert_array_equal(keys[:13], np.arange(13))
        np.testing.assert_array_equal(keys[13:] + 1, rows[i])  # identity keying: row = key + 1
        np.testing.assert_array_equal(got[2][starts[i] : starts[i] + 13], x[i])


def test_hashed_criteo_layout_is_what_it_was(tmp_path):
    """The bare "criteo" format (``ctr1.*``, ``wd100m.train``) through the
    parser that learnt the per-field layout: the batches of one generated
    file hash to what the parent commit's parser and builder gave (the
    digest below was taken from a checkout of 935ea10 on this file)."""
    labels, ints, cats = criteo.make_examples(7, 1000, SPEC)
    path = str(tmp_path / "x.tsv")
    criteo.write_tsv(path, labels, ints, cats)
    digests = {}
    for backend in ("native", "python"):
        if backend == "native" and not native.native_available():
            continue
        builder = BatchBuilder(1 << 20, 256, 64, bucket_nnz=True)
        h = hashlib.sha256()
        for b in MinibatchReader([path], "criteo", builder, backend=backend):
            for a in (b.unique_keys, b.local_ids, b.row_splits, b.values, b.labels, b.example_mask):
                h.update(np.ascontiguousarray(a).tobytes())
        digests[backend] = h.hexdigest()
    assert set(digests.values()) == {HASHED_DIGEST}, digests


HASHED_DIGEST = "f4336834b9aa3944e91fbfda2c410aa0ae2cea8db844fc60e5e68e02daa6d4ad"


def test_format_with_sizes_is_parsed_and_refused():
    assert split_format("criteo") == ("criteo", None)
    assert split_format(criteo_format(FIELD_ROWS)) == ("criteo", tuple(FIELD_ROWS))
    for bad in ("criteo:1,2,3", "criteo:" + ",".join(["0"] * 26), "criteo:" + ",".join(["x"] * 26)):
        with pytest.raises(ValueError, match="26 table sizes"):
            split_format(bad)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 23])
def test_device_made_rows_are_the_benchmark_references_function(seed):
    """``dlrm.init_rows`` (the device's) against ``ref_dlrm.init_rows``
    (NumPy), bit for bit: rows 0..13 and the rows past the last table zero,
    field f's rows within +-sqrt(1 / R_f)."""
    n = dlrm.num_keys_of(FIELD_ROWS)
    rows = np.arange(n + 40)
    got = np.asarray(dlrm.init_rows(seed % (2**31 - 1), jnp.arange(n + 40, dtype=jnp.int32), DIM, FIELD_ROWS))
    want = ref_dlrm.init_rows(seed % (2**31 - 1), rows, DIM, FIELD_ROWS)
    np.testing.assert_array_equal(got, want)
    assert not got[:14].any() and not got[n:].any()
    first = ref_dlrm.field_first_rows(FIELD_ROWS)
    for f in (0, 1, 4):
        mine = got[first[f] : first[f] + FIELD_ROWS[f]]
        assert 0 < np.abs(mine).max() <= np.float32(np.sqrt(1.0 / FIELD_ROWS[f]))


def test_a_config_the_app_cannot_read_is_refused():
    good = make_cfg()
    dlrm.app_from_config(good)
    for change, match in (
        (lambda c: setattr(c.dlrm, "field_rows", FIELD_ROWS[:25]), "26 sizes"),
        (lambda c: setattr(c.data, "num_keys", 99), "pod_config fills both in"),
        (lambda c: setattr(c.data, "format", "libsvm"), "pod_config fills both in"),
        (lambda c: setattr(c.data, "max_nnz_per_example", 38), "39 entries"),
        (lambda c: setattr(c.dlrm, "bot", [32, 8]), "bottom MLP ends 16 wide"),
        (lambda c: setattr(c.dlrm, "top", [32, 2]), "one\n?.*logit|one logit"),
    ):
        cfg = make_cfg()
        change(cfg)
        with pytest.raises(ValueError, match=match):
            dlrm.app_from_config(cfg)


def test_description_names_its_scopes_and_scores_auc():
    app = dlrm.app_from_config(make_cfg())
    assert app.scope_names() == {"emb", "mlp", "bot", "interact", "top"}
    assert [t.name for t in app.tables] == ["emb"] and app.tables[0].vdim == DIM
    assert [name for name, _ in app.score] == ["auc", "logloss"]
    assert app.dense.keys()[:2] == ["mlp.bot.0.W", "mlp.bot.0.b"] and len(app.dense.keys()) == 2 * (len(BOT) + len(TOP))


def test_one_mlp_helper_serves_both_apps():
    """``models.mlp``: Wide&Deep's tower is the He-normal draw it always
    was (weights from ``default_rng(seed)`` layer by layer, zero biases, one
    logit out); DLRM's two MLPs draw weights and biases from one generator,
    bottom first, and the bottom's last layer is rectified."""
    rng = np.random.default_rng(4)
    tower = wide_deep.init_mlp(16, [32, 8], seed=4)
    for layer, (i, o) in zip(tower, [(16, 32), (32, 8), (8, 1)]):
        np.testing.assert_array_equal(layer["W"], rng.normal(scale=np.sqrt(2.0 / i), size=(i, o)).astype(np.float32))
        assert not np.asarray(layer["b"]).any()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 16)), jnp.float32)
    assert wide_deep._mlp_apply(tower, x).shape == (5,)
    np.testing.assert_array_equal(wide_deep._mlp_apply(tower, x), mlp.mlp_apply(tower, x)[:, 0])
    both = dlrm.init_mlps(9, DIM, BOT, TOP)
    want = ref_dlrm.RefDlrm(np.arange(20), HYPER, 9, FIELD_ROWS)
    for got, (w, b) in zip(both["bot"] + both["top"], want.bot0 + want.top0):
        np.testing.assert_array_equal(got["W"], w)
        np.testing.assert_array_equal(got["b"], b)
    out = mlp.mlp_apply(both["bot"], x[:, :13], last=jax.nn.relu)
    assert out.shape == (5, DIM) and float(out.min()) >= 0.0 and float(mlp.mlp_apply(both["bot"], x[:, :13]).min()) < 0.0


def test_cli_trains_scores_checkpoints_and_dumps(tmp_path):
    paths, *_ = write_files(tmp_path, 3, 256)
    cfg = {
        "app": "dlrm", "seed": 3,
        "data": {"files": paths[:2], "val_files": paths[2:], "max_nnz_per_example": 39},
        "dlrm": {"emb_dim": DIM, "bot": BOT, "top": TOP, "eta": 0.005, "field_rows": FIELD_ROWS},
        "solver": {"minibatch": 64, "steps_per_call": 2, "epochs": 2},
        "parallel": {"data_shards": 2, "kv_shards": 2},
    }
    p = tmp_path / "dlrm.json"
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
    assert d["emb_w"].shape == (dlrm.num_keys_of(FIELD_ROWS), DIM) and d["top_W0"].shape == (DIM + 351, TOP[0])
    assert (tmp_path / "ck" / "dense.npz").exists()


def test_a_files_last_partial_batch_in_a_short_bucket_is_read_by_position_too(tmp_path):
    """A file of B + 10 examples: its second batch holds 10 examples in the
    smallest entry bucket (2,048 slots for 390 entries, under B x 39), so
    the step zero-extends the entry axis before it cuts it by position;
    the 10 examples train as the reference trains them."""
    paths, labels, ints, cats = write_files(tmp_path, 1, BATCH + 10)
    tr = trainer_of("1x1")
    assert tr.cfg.data.bucket_nnz
    tr.train_files(paths)
    rows, x = ref_dlrm.features(ints, cats, FIELD_ROWS)
    ref = ref_dlrm.RefDlrm(rows, HYPER, 11, FIELD_ROWS)
    idx = ref.index(rows)
    for sl in (slice(0, BATCH), slice(BATCH, BATCH + 10)):
        ref.step([(idx[sl], x[sl], labels[sl])])
    assert tr.examples_seen == BATCH + 10
    moved = float(np.abs(ref.w - ref.w0).max())
    np.testing.assert_allclose(tr.full_weights("emb")[ref.rows], ref.w, atol=1e-5 * moved + 1e-7, rtol=0)
    np.testing.assert_allclose(mlp_flat(tr), ref.mlp_flat(), atol=1e-6, rtol=0)
