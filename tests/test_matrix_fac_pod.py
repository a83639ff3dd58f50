"""Matrix factorization through ``PodTrainer`` and the one ``_microstep``
(``models/matrix_fac.py``'s description over ``user item rating`` files)
against the plain dense reference (``tests/mf_reference.py``): the step on
three meshes, the evaluator's RMSE, the key layout, the batch shape, the
``rating`` format's two parsers, the starting factors, what a table of
64 lanes asks of the store's gather, and ranks the store keeps wider than
themselves (100 and 36 in 128 lanes) against the benchmark's reference on
three meshes. Small sizes, CPU, seeded."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mf_reference import DenseMF
from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.reader import MinibatchReader, ingest_of
from parameter_server_tpu.models import matrix_fac
from parameter_server_tpu.parallel import make_mesh, spmd
from parameter_server_tpu.parallel.trainer import PodTrainer, app_from_config
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

N_USERS, N_ITEMS, RANK, B = 300, 40, 8, 64
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def mf_config(mesh=(1, 1), algo="sgd", steps_per_call=2, eta=0.05, rank=RANK, push_mode="per_worker", **mf):
    cfg = PSConfig()
    cfg.mf.num_users, cfg.mf.num_items, cfg.mf.rank = N_USERS, N_ITEMS, rank
    cfg.mf.algo, cfg.mf.eta, cfg.mf.l2, cfg.mf.batch_size = algo, eta, 0.01, B
    for k, v in mf.items():
        setattr(cfg.mf, k, v)
    cfg.solver.steps_per_call, cfg.solver.max_delay = steps_per_call, 1
    cfg.parallel.data_shards, cfg.parallel.kv_shards = mesh
    cfg.parallel.push_mode = push_mode
    cfg.seed = 2**31 + 11  # a seed past 31 bits, as the benchmark's are
    return matrix_fac.pod_config(cfg)


def trainer_of(cfg):
    return PodTrainer(
        cfg, mesh=make_mesh(cfg.parallel.data_shards, cfg.parallel.kv_shards), reporter=quiet()
    )


def ratings(n, seed=0, n_users=N_USERS, n_items=N_ITEMS):
    """Ratings of a planted rank-3 model; the last user and the last item
    are among them."""
    rng = np.random.default_rng(seed)
    p, q = rng.random((n_users, 3)), rng.random((n_items, 3))
    users, items = rng.integers(0, n_users, n), rng.integers(0, n_items, n)
    users[-1], items[-1] = n_users - 1, n_items - 1
    r = np.sum(p[users] * q[items], axis=1) + 0.05 * rng.normal(size=n)
    return users, items, np.round(r, 4).astype(np.float32)


def write_files(tmp_path, users, items, r, parts):
    """``parts`` files of equal length, in order: one a worker."""
    per, paths = len(r) // parts, []
    for d in range(parts):
        sl = slice(d * per, (d + 1) * per)
        paths.append(str(tmp_path / f"ratings-{d}.txt"))
        matrix_fac.write_ratings(paths[-1], users[sl], items[sl], r[sl])
    return paths, per


@pytest.mark.parametrize("mesh_name", MESHES)
def test_step_against_the_plain_reference(tmp_path, mesh_name):
    """Six minibatches a worker through ``train_files``: the losses' sum,
    and every row of the table (the touched ones moved, by the reference's
    arithmetic; the others as they started)."""
    mesh = MESHES[mesh_name]
    d = mesh[0]
    users, items, r = ratings(B * 6 * d)
    paths, per = write_files(tmp_path, users, items, r, d)
    tr = trainer_of(mf_config(mesh))
    user_f, item_f = matrix_fac.factors(tr)
    ref = DenseMF(user_f, item_f, eta=0.05, l2=0.01)
    out = tr.train_files(paths)
    losses = []
    for s in range(per // B):
        workers = [slice(w * per + s * B, w * per + (s + 1) * B) for w in range(d)]
        losses.append(ref.step([(users[sl], items[sl], r[sl]) for sl in workers]))
    got_u, got_i = matrix_fac.factors(tr)
    assert np.abs(got_u - user_f).max() > 1e-3  # the step moved something
    np.testing.assert_allclose(got_u, ref.U, rtol=0, atol=2e-7)
    np.testing.assert_allclose(got_i, ref.V, rtol=0, atol=2e-7)
    assert out["objv"] == pytest.approx(sum(losses) / len(r), rel=1e-6)
    assert out["rmse"] > 0 and "auc" not in out


def test_evaluate_files_rmse_against_the_reference(tmp_path):
    users, items, r = ratings(B * 5 + 17)  # a ragged last batch
    paths, _ = write_files(tmp_path, users, items, r, 1)
    tr = trainer_of(mf_config())
    ref = DenseMF(*matrix_fac.factors(tr), eta=0.05, l2=0.01)
    got = tr.evaluate_files(paths)
    assert set(got) == {"rmse", "examples"} and got["examples"] == len(r)
    assert got["rmse"] == pytest.approx(ref.rmse(users, items, r), rel=1e-6)
    assert matrix_fac.rmse(tr, users, items, r) == pytest.approx(got["rmse"], rel=1e-6)


@pytest.mark.parametrize("mesh_name", ["1x1", "1x4"])
def test_pad_rows_stay_zero(tmp_path, mesh_name):
    users, items, r = ratings(B * 4)
    paths, _ = write_files(tmp_path, users, items, r, 1)
    tr = trainer_of(mf_config(MESHES[mesh_name]))
    tr.train_files(paths)
    table = np.asarray(tr.state["mf.w"])
    assert not table[0].any()  # the pad row: every padded slot pulls and pushes it
    assert not table[tr.cfg.data.num_keys :].any()  # the kv axis' pad tail
    assert table[1 : tr.cfg.data.num_keys].all()  # every real row started off zero


def test_identity_keys_never_collide_and_the_last_user_has_the_last_row(tmp_path):
    """One rating of the last user and the first item moves exactly two
    rows: row 1 and the last real row; the last item's row lies right
    before the first user's."""
    path = str(tmp_path / "one.txt")
    matrix_fac.write_ratings(path, [N_USERS - 1], [0], [3.0])
    tr = trainer_of(mf_config())
    before = np.asarray(tr.state["mf.w"]).copy()
    tr.train_files([path])
    moved = np.flatnonzero(np.abs(np.asarray(tr.state["mf.w"]) - before).max(axis=1))
    assert moved.tolist() == [1, N_ITEMS + N_USERS]
    assert tr.cfg.data.num_keys == N_ITEMS + N_USERS + 1 == moved[-1] + 1
    # every (user, item) has a row of its own: items 1..N_ITEMS, users behind them
    fmt, key_mode = ingest_of(tr.cfg)
    assert (fmt, key_mode) == (f"rating:{N_ITEMS}", "identity")
    users, items = np.arange(N_USERS), np.arange(N_USERS) % N_ITEMS
    path = str(tmp_path / "all.txt")
    matrix_fac.write_ratings(path, users, items, np.ones(N_USERS))
    builder = BatchBuilder(tr.cfg.data.num_keys, 512, 2, key_mode="identity")
    (b,) = list(MinibatchReader([path], fmt, builder))
    keys = b.unique_keys[1 : b.num_unique]
    assert keys.tolist() == list(range(1, N_ITEMS + 1)) + list(range(N_ITEMS + 1, N_ITEMS + N_USERS + 1))
    with pytest.raises(ValueError, match="identity key"):
        matrix_fac.write_ratings(path, [N_USERS], [0], [1.0])  # one user too many
        list(MinibatchReader([path], fmt, builder))


def test_one_shape_for_batches_of_two_entry_examples(tmp_path):
    """Full batches of 2-entry examples land in one (entries, keys) bucket
    pair whatever their key counts; the ragged last one is no larger."""
    users, items, r = ratings(1024 * 6 + 100, n_users=50_000, n_items=700)
    path = str(tmp_path / "r.txt")
    matrix_fac.write_ratings(path, users, items, r)
    builder = BatchBuilder(1 + 700 + 50_000, 1024, 2, key_mode="identity", bucket_nnz=True)
    batches = list(MinibatchReader([path], "rating:700", builder))
    full = {b.shape for b in batches[:-1]}
    assert full == {(1024, 2048, 2048)}, full
    assert batches[-1].num_examples == 100 and batches[-1].shape <= (1024, 2048, 2048)
    assert all(b.num_entries == 2 * b.num_examples and b.keys_in_order() for b in batches)
    assert all((b.values[: b.num_entries] == 1).all() for b in batches)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_multistep_is_the_single_step_trajectory(tmp_path, mesh_name):
    mesh = MESHES[mesh_name]
    users, items, r = ratings(B * 6 * mesh[0], seed=3)
    paths, _ = write_files(tmp_path, users, items, r, mesh[0])
    tables = []
    for k in (1, 3):
        tr = trainer_of(mf_config(mesh, steps_per_call=k))
        tr.train_files(paths)
        tables.append(np.asarray(tr.state["mf.w"]))
    np.testing.assert_array_equal(*tables)


def test_aggregate_push_equals_per_worker_for_sgd(tmp_path):
    """Plain SGD is linear in the gradient and L2 rides in the gradient:
    one summed push is the workers' pushes in turn, up to float32's order
    of addition."""
    users, items, r = ratings(B * 8, seed=5)
    paths, _ = write_files(tmp_path, users, items, r, 2)
    tables = []
    for mode in ("per_worker", "aggregate"):
        tr = trainer_of(mf_config((2, 2), push_mode=mode))
        tr.train_files(paths)
        tables.append(np.asarray(tr.state["mf.w"]))
    np.testing.assert_allclose(tables[0], tables[1], rtol=0, atol=1e-6)


@pytest.mark.parametrize("algo", ["sgd", "adagrad"])
def test_training_recovers_the_planted_structure(tmp_path, algo):
    """Held-out RMSE falls to under half of where it started, on a mesh."""
    users, items, r = ratings(9000, seed=1)
    paths, _ = write_files(tmp_path, users[:8000], items[:8000], r[:8000], 2)
    cfg = mf_config((2, 2), algo=algo, eta=0.05 if algo == "sgd" else 0.1)
    cfg.solver.epochs = 25
    tr = trainer_of(cfg)
    first = matrix_fac.rmse(tr, users[8000:], items[8000:], r[8000:])
    tr.train_files(paths)
    last = matrix_fac.rmse(tr, users[8000:], items[8000:], r[8000:])
    assert last < 0.5 * first, (first, last)
    assert np.isfinite(matrix_fac.predict(tr, users, items)).all()


def test_repeated_pairs_in_a_batch_are_one_row_of_the_push(tmp_path):
    """A batch that names one (user, item) pair four times pushes each of
    its rows once, with the four gradients summed."""
    path = str(tmp_path / "dup.txt")
    matrix_fac.write_ratings(path, [7, 7, 7, 7], [2, 2, 2, 2], [1.0, 2.0, 3.0, 4.0])
    tr = trainer_of(mf_config(steps_per_call=1))
    ref = DenseMF(*matrix_fac.factors(tr), eta=0.05, l2=0.01)
    tr.train_files([path])
    ref.step([(np.full(4, 7), np.full(4, 2), np.array([1, 2, 3, 4], np.float32))])
    got_u, got_i = matrix_fac.factors(tr)
    np.testing.assert_allclose(got_u[7], ref.U[7], rtol=0, atol=1e-7)
    np.testing.assert_allclose(got_i[2], ref.V[2], rtol=0, atol=1e-7)


def test_checkpoint_round_trip(tmp_path):
    users, items, r = ratings(B * 4)
    paths, _ = write_files(tmp_path, users, items, r, 1)
    tr = trainer_of(mf_config((1, 4)))
    tr.train_files(paths)
    tr.save(str(tmp_path / "ckpt"))
    back = trainer_of(mf_config((2, 2)))  # another mesh: another pad tail
    back.load(str(tmp_path / "ckpt"))
    np.testing.assert_array_equal(
        np.asarray(back.state["mf.w"])[: tr.cfg.data.num_keys],
        np.asarray(tr.state["mf.w"])[: tr.cfg.data.num_keys],
    )
    assert back.examples_seen == len(r)


def test_bad_algo_and_a_config_that_names_another_key_space_are_refused():
    cfg = mf_config(algo="ftrl")
    with pytest.raises(ValueError, match="mf algo"):
        app_from_config(cfg)
    cfg = mf_config()
    cfg.data.num_keys += 1
    with pytest.raises(ValueError, match="1 \\+ mf.num_items \\+ mf.num_users"):
        app_from_config(cfg)
    cfg = mf_config()
    cfg.data.format = "libsvm"
    with pytest.raises(ValueError, match="data.format 'rating'"):
        app_from_config(cfg)


def test_description_carries_link_loss_and_score():
    """Nothing in the step or the trainer asks which app runs: the
    description says what becomes of the logits and how they are scored."""
    from parameter_server_tpu.kv.updaters import Ftrl
    from parameter_server_tpu.models import metrics as M

    mf, lin = app_from_config(mf_config()), spmd.linear_app(Ftrl())
    x = jnp.asarray([-2.0, 0.0, 3.0])
    np.testing.assert_array_equal(mf.link(x), x)
    np.testing.assert_array_equal(lin.link(x), jax.nn.sigmoid(x))
    assert [n for n, _ in mf.score] == ["rmse"] and mf.score[0][1] is M.rmse
    assert [n for n, _ in lin.score] == ["auc", "logloss"]
    assert mf.scope_names() == {"mf"} and mf.tables[0].vdim == RANK
    with pytest.raises(TypeError):  # a description without them is no description
        spmd.StepApp(lin.tables, lin.grad, lin.logits)


# -- the rating format: the C parser and its Python twin ----------------------
def _rating_text(seed: int, n_users: int, n_items: int) -> str:
    """Seeded lines with what a parser can trip on: blank lines, tabs and
    runs of spaces, a rating with a sign and a fraction, an exponent, a
    trailing timestamp, the last user id, CRLF, and no newline at the end."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(500):
        u, i = rng.integers(0, n_users), rng.integers(0, n_items)
        r = rng.choice([f"{rng.normal() * 3:.4f}", f"{rng.random():+.6f}", f"{rng.integers(1, 6)}", f"{rng.random():.3e}"])
        sep = rng.choice([" ", "\t", "  "])
        tail = rng.choice(["", f"{sep}{rng.integers(1e9)}", " "])
        lines.append(f"{u}{sep}{i}{sep}{r}{tail}")
        if rng.random() < 0.05:
            lines.append(rng.choice(["", "   "]))
    lines.append(f"{n_users - 1} {n_items - 1} -0.5")
    lines.append(f"{rng.integers(0, n_users)} 0 +2.25")  # the ragged last line
    return rng.choice(["\n", "\r\n"]).join(lines)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_rating_parsers_agree_to_the_bit(tmp_path, seed):
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.libsvm import iter_format

    n_users, n_items = 50_082_603, 39_780  # the cell's: ids of 8 and 5 digits
    path = tmp_path / "r.txt"
    path.write_bytes(_rating_text(seed, n_users, n_items).encode())
    fmt = f"rating:{n_items}"
    assert native.has_native(fmt) and native.native_available()
    chunks = list(native.iter_chunks(path, fmt, chunk_bytes=1 << 12))  # many chunks, lines cut anywhere
    labels = np.concatenate([c[0] for c in chunks])
    keys = np.concatenate([c[2] for c in chunks])
    vals = np.concatenate([c[3] for c in chunks])
    assert all(c[4] is None for c in chunks)  # slotless
    assert all((np.diff(c[1]) == 2).all() for c in chunks)  # two entries a row
    rows = list(iter_format(fmt, path))
    assert labels.dtype == np.float32 and len(labels) == len(rows) == 502
    np.testing.assert_array_equal(labels.view(np.uint32), np.array([r[0] for r in rows], np.float32).view(np.uint32))
    np.testing.assert_array_equal(keys, np.concatenate([r[1] for r in rows]))
    np.testing.assert_array_equal(vals, np.concatenate([r[2] for r in rows]))
    assert len(np.unique(labels)) > 100 and (labels < 0).any()  # real-valued, signed
    assert keys[-4:].tolist()[:2] == [n_items - 1, n_items + n_users - 1]  # the last user's key
    # the reader's two backends build the same batches
    builder = BatchBuilder(1 + n_items + n_users, 128, 2, key_mode="identity")
    for a, b in zip(MinibatchReader([path], fmt, builder, backend="native"),
                    MinibatchReader([path], fmt, builder, backend="python"), strict=True):
        for f in ("unique_keys", "local_ids", "row_splits", "values", "labels", "example_mask"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


@pytest.mark.parametrize("bad", ["3 x 1.0", "3 2", "-1 2 1.0", "3 2 1.0x", "3 40 1.0", "3.5 2 1.0"])
def test_rating_parsers_refuse_the_same_lines(tmp_path, bad):
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.libsvm import iter_format

    path = tmp_path / "r.txt"
    path.write_text(f"1 1 1.0\n{bad}\n")
    with pytest.raises(ValueError, match="parse error at line 1"):
        list(native.iter_chunks(path, "rating:40"))
    with pytest.raises(ValueError, match="parse error at line 1"):
        list(iter_format("rating:40", path))
    with pytest.raises(ValueError, match="rating:<num_items>"):
        list(iter_format("rating", path))


@pytest.mark.parametrize("fmt", ["libsvm", "criteo", "adfea"])
def test_other_formats_labels_are_still_0_or_1(tmp_path, fmt):
    """A real-valued label is the rating format's alone: the click formats
    fold theirs to 0 / 1, in both parsers."""
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.libsvm import iter_format

    rng = np.random.default_rng(4)
    raw = [f"{x:.3f}" for x in rng.normal(size=40) * 3] + ["1", "0", "-1", "+1"]
    if fmt == "libsvm":
        lines = [f"{y} 3:1.5 9:2" for y in raw]
    elif fmt == "adfea":
        lines = [f"id{n} {y} 7:1 8:2" for n, y in enumerate(raw)]
    else:
        raw = ["1", "0", "1", "0", "0"]
        lines = ["\t".join([y] + ["5"] * 13 + ["ab12"] * 26) for y in raw]
    path = tmp_path / "f.txt"
    path.write_text("\n".join(lines) + "\n")
    got_c = np.concatenate([c[0] for c in native.iter_chunks(path, fmt)])
    got_py = np.array([r[0] for r in iter_format(fmt, path)], np.float32)
    np.testing.assert_array_equal(got_c, got_py)
    assert set(np.unique(got_c)) <= {0.0, 1.0} and len(got_c) == len(raw)
    if fmt != "criteo":
        np.testing.assert_array_equal(got_c, (np.array(raw, float) > 0).astype(np.float32))


# -- the starting factors and the store at 64 lanes ----------------------------
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 23])
def test_device_made_factors_are_the_benchmark_references_function(seed):
    from benchmark.harness import ref_mf

    num_keys = 1 + 39_780 + 50_082_603
    rows = np.array([0, 1, 2, 39_780, 39_781, num_keys - 1, num_keys, num_keys + 300])
    got = np.asarray(matrix_fac.init_factors(seed, jnp.asarray(rows, jnp.int32), 64, num_keys))
    np.testing.assert_array_equal(got, ref_mf.init_factors(seed, rows, 64, num_keys))
    live = got[1:6]
    assert not got[0].any() and not got[6:].any() and (live >= 0).all() and (live < 1 / 8).all()
    assert 0.05 < live.mean() < 0.075 and len(np.unique(live)) > 300


@pytest.mark.parametrize(
    "vdim,sliced",
    [(1, "1, 1"), (16, "1, 16"), (32, "1, 32"), (64, "1, 1, 32"), (128, "1, 128"), (48, "1, 1, 24"), (100, "1, 128"), (300, "1, 384")],
)
def test_take_rows_of_any_width_against_numpy(vdim, sliced):
    """``_take_rows`` is ``jnp.take`` up to 32 lanes, the linear app's and
    Wide&Deep's gather left as it was, and for rows stored in whole 128-lane
    tiles, which the chip keeps row-major; for other widths a gather of the
    widest blocks of 8k lanes, at most 32, that a row splits into. A width
    that splits into none (100, 300) is STORED at the next whole tile
    (``row_stride``; the ONE form for such widths since PR 34: the gather
    of single elements is gone) and comes back cut to its own lanes: the
    same rows, to the bit."""
    rng = np.random.default_rng(vdim)
    stride = spmd.row_stride(vdim)
    table = np.zeros((1000, stride), np.float32)
    table[:, :vdim] = rng.normal(size=(1000, vdim))
    rows = np.concatenate([[0, 0, 999, 1, 999], rng.integers(0, 1000, 60)]).astype(np.int32)
    take = jax.jit(lambda v, i: spmd._take_rows(v, i, vdim))
    got = take(jnp.asarray(table), jnp.asarray(rows))
    assert got.shape == (len(rows), vdim)
    np.testing.assert_array_equal(np.asarray(got), table[rows][:, :vdim])
    # what the lowered gather slices: a whole stored row up to 32 lanes and at whole
    # tiles, a block of the (rows, blocks, lanes) view between; never single elements
    text = take.lower(jnp.asarray(table), jnp.asarray(rows)).as_text()
    assert text.count("slice_sizes") == 1 and f"slice_sizes = array<i64: {sliced}>" in text, text
    if stride == vdim:  # the width unsaid is the slot's own
        np.testing.assert_array_equal(np.asarray(spmd._take_rows(jnp.asarray(table), jnp.asarray(rows))), table[rows])


# -- ranks stored wider than themselves (100 and 36: 128 lanes) ------------------
STRIDED_RANKS = [100, 36]  # neither has a block of 8k lanes: ``row_stride`` sends both to one whole tile


@pytest.mark.parametrize("mesh_name", MESHES)
@pytest.mark.parametrize("rank", STRIDED_RANKS)
def test_a_strided_rank_against_the_benchmarks_reference_in_worker_order(tmp_path, rank, mesh_name):
    """Three device calls of two microsteps, every worker a batch a
    microstep, at a rank the store keeps 128 lanes wide, against the
    benchmark's plain reference (``benchmark/harness/ref_mf.py``: its own
    parse, its own starting factors, a step's workers applied in order).
    The pad lanes never move: exactly zero after training."""
    from benchmark.harness.ref_mf import RefMf, parse_ratings

    mesh = MESHES[mesh_name]
    d, calls, k = mesh[0], 3, 2
    users, items, r = ratings(B * k * calls * d, seed=rank)
    paths, per = write_files(tmp_path, users, items, r, d)
    cfg = mf_config(mesh, rank=rank, steps_per_call=k)
    tr = trainer_of(cfg)
    assert spmd.row_stride(rank) == 128 and tr.state["mf.w"].shape[1] == 128
    num_keys = cfg.data.num_keys
    ref = RefMf(np.arange(num_keys), {"rank": rank, "eta": 0.05, "l2": 0.01}, cfg.seed, num_keys)
    np.testing.assert_array_equal(np.asarray(tr.state["mf.w"])[:num_keys, :rank], ref.w0)  # the start, to the bit
    losses = []
    out = tr.train_files(paths)
    files = [parse_ratings(p) for p in paths]  # the reference's own reading of the program's files
    for s in range(per // B):
        sl = slice(s * B, (s + 1) * B)
        losses.append(ref.step([(1 + it[sl], 1 + N_ITEMS + us[sl], ra[sl]) for us, it, ra in files]))
    table = np.asarray(tr.state["mf.w"])
    got = table[:num_keys, :rank]
    item_rows, user_rows = slice(1, 1 + N_ITEMS), slice(1 + N_ITEMS, num_keys)
    assert np.abs(got - ref.w0).max() > 1e-3  # the steps moved something
    # float32 sums in another order than NumPy's (segment_sum over a batch's 64 pairs
    # against reduceat, the inner product over 100 lanes on the vector unit), six steps
    # deep: an item's row collects about three gradients a batch of magnitude 0.1 at eta
    # 0.05, a user's one in five batches; elements are about 0.05. 5e-7 is eight ulps of
    # the largest element (2^-4: 6e-8 an ulp), and a bfloat16 table would miss by 1e-4
    np.testing.assert_allclose(got[item_rows], ref.w[item_rows], rtol=0, atol=5e-7)
    np.testing.assert_allclose(got[user_rows], ref.w[user_rows], rtol=0, atol=5e-7)
    # the summed loss: float32 sums of 64 squared errors, six of them: relative 1e-6
    assert out["objv"] == pytest.approx(sum(losses) / len(r), rel=1e-6)
    assert not table[:, rank:].any()  # the pad lanes: exactly zero, every row, every shard
    assert not table[0].any() and not table[num_keys:].any()  # and the pad rows


@pytest.mark.parametrize("seed", [0, 2**31 + 23])
@pytest.mark.parametrize("rank", STRIDED_RANKS)
def test_a_slot_made_at_the_stride_is_the_narrow_one_in_its_first_lanes(rank, seed):
    """``hashed_unit`` is a function of (seed, row, lane): the slot the store
    asks for, ``row_stride(rank)`` lanes wide, equals the ``rank``-lane one
    bit for bit in its first ``rank`` lanes and is zero past them; and the
    store pads nothing (``Table.init_slots`` hands the app that width)."""
    num_keys = 1 + 39_780 + 50_082_603
    rows = jnp.asarray([0, 1, 2, 39_780, 39_781, num_keys - 1, num_keys, num_keys + 300], jnp.int32)
    narrow = np.asarray(matrix_fac.init_factors(seed, rows, rank, num_keys))
    wide = np.asarray(matrix_fac.init_factors(seed, rows, rank, num_keys, 128))
    assert narrow.shape == (8, rank) and wide.shape == (8, 128)
    np.testing.assert_array_equal(wide[:, :rank].view(np.uint32), narrow.view(np.uint32))
    assert not wide[:, rank:].any() and narrow[1:6].all()
    cfg = mf_config(rank=rank)
    cfg.seed = seed
    (table,) = app_from_config(cfg).tables
    (slot,) = table.init_slots(1024).values()
    want = matrix_fac.init_factors(seed, jnp.arange(1024, dtype=jnp.int32), rank, cfg.data.num_keys)
    assert slot.shape == (1024, spmd.row_stride(rank)) == (1024, 128)
    np.testing.assert_array_equal(np.asarray(slot)[:, :rank], np.asarray(want))
    assert not np.asarray(slot)[:, rank:].any()
    assert "pad" not in jax.jit(lambda: table.init_slots(1024)).lower().as_text()


@pytest.mark.parametrize("rank", STRIDED_RANKS)
def test_elastic_restore_of_a_strided_table(tmp_path, rank):
    """A table stored 128 lanes wide, trained and saved on (2, 2), restored
    on (1, 4): the same rows to the bit, pad lanes and all, and further
    training from it moves the rows and leaves the pad lanes zero."""
    users, items, r = ratings(B * 8, seed=9)
    paths, _ = write_files(tmp_path, users, items, r, 2)
    tr = trainer_of(mf_config((2, 2), rank=rank))
    tr.train_files(paths)
    tr.save(str(tmp_path / "ckpt"))
    back = trainer_of(mf_config((1, 4), rank=rank))  # another mesh: another shard size, another pad tail
    back.load(str(tmp_path / "ckpt"))
    n = tr.cfg.data.num_keys
    assert back.state["mf.w"].shape[1] == 128
    np.testing.assert_array_equal(np.asarray(back.state["mf.w"])[:n], np.asarray(tr.state["mf.w"])[:n])
    assert back.examples_seen == len(r)
    (tmp_path / "more").mkdir()
    more, _ = write_files(tmp_path / "more", *ratings(B * 4, seed=10), 1)
    before = np.asarray(back.state["mf.w"]).copy()
    back.train_files(more)
    after = np.asarray(back.state["mf.w"])
    assert np.abs(after - before)[:n, :rank].max() > 1e-3 and not after[:, rank:].any() and not after[n:].any()
    np.testing.assert_array_equal(back.full_weights("mf"), after[:n, :rank])


def test_cli_dump_holds_the_factors_by_id(tmp_path):
    """``factors`` splits the one table at the key layout's seam."""
    tr = trainer_of(mf_config())
    user_f, item_f = matrix_fac.factors(tr)
    table = np.asarray(tr.state["mf.w"])
    assert user_f.shape == (N_USERS, RANK) and item_f.shape == (N_ITEMS, RANK)
    np.testing.assert_array_equal(item_f, table[1 : 1 + N_ITEMS])
    np.testing.assert_array_equal(user_f, table[1 + N_ITEMS : 1 + N_ITEMS + N_USERS])
    assert json.dumps(matrix_fac.num_keys_of(N_USERS, N_ITEMS)) == str(tr.cfg.data.num_keys)
