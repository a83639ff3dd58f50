"""Skip-gram with negative sampling through ``PodTrainer`` and the one
``_microstep`` (``models/word2vec.py``'s description over ``sgns`` example
files) against the plain dense reference (``tests/sgns_reference.py``): the
step on three meshes, the evaluator's mean loss, the key layout, an
example's entries by position, the ``sgns`` format's two parsers, the
starting vectors, and a table whose 300-lane rows are stored 384 wide.
Small sizes, CPU, seeded."""

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.data.reader import MinibatchReader, ingest_of
from parameter_server_tpu.models import word2vec
from parameter_server_tpu.parallel import make_mesh, spmd
from parameter_server_tpu.parallel.trainer import PodTrainer, app_from_config
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter
from sgns_reference import DenseSGNS

V, DIM, K, B = 120, 12, 3, 32  # 12 lanes: a width stored as it is
MESHES = {"1x1": (1, 1), "2x2": (2, 2), "1x4": (1, 4)}
ETA = 0.05


def quiet():
    return ProgressReporter(print_fn=lambda *_: None)


def w2v_config(mesh=(1, 1), steps_per_call=2, dim=DIM, vocab=V, negatives=K, eta=ETA, batch=B):
    cfg = PSConfig()
    cfg.w2v.vocab_size, cfg.w2v.dim, cfg.w2v.negatives = vocab, dim, negatives
    cfg.w2v.eta, cfg.w2v.batch_size = eta, batch
    cfg.solver.steps_per_call, cfg.solver.max_delay = steps_per_call, 1
    cfg.parallel.data_shards, cfg.parallel.kv_shards = mesh
    cfg.seed = 2**31 + 11  # a seed past 31 bits, as the benchmark's are
    return word2vec.pod_config(cfg)


def trainer_of(cfg):
    return PodTrainer(
        cfg, mesh=make_mesh(cfg.parallel.data_shards, cfg.parallel.kv_shards), reporter=quiet()
    )


def examples(n, seed=0, vocab=V, k=K):
    """Zipf-ish words: hot words repeat as centres, contexts and negatives
    within a batch; the last word is among each."""
    rng = np.random.default_rng(seed)
    draw = lambda shape: np.minimum((vocab * rng.random(shape) ** 2).astype(np.int64), vocab - 1)  # noqa: E731
    c, o, neg = draw(n), draw(n), draw((n, k))
    c[-1] = o[-2] = neg[-3, 0] = vocab - 1
    return c, o, neg


def write_files(tmp_path, c, o, neg, parts):
    per, paths = len(c) // parts, []
    for d in range(parts):
        sl = slice(d * per, (d + 1) * per)
        paths.append(str(tmp_path / f"pairs-{d}.txt"))
        word2vec.write_examples(paths[-1], c[sl], o[sl], neg[sl])
    return paths, per


def scaled_start(tr, scale=40.0, seed=5):
    """Give the trainer's table vectors large enough that sigmoid is off
    its linear part and the output vectors are not zero: the reference's
    start is read back from the table."""
    rng = np.random.default_rng(seed)
    w = np.asarray(tr.state["sgns.w"]).copy()
    live = slice(1, tr.cfg.data.num_keys)
    w[live, : tr.cfg.w2v.dim] = (rng.normal(size=(tr.cfg.data.num_keys - 1, tr.cfg.w2v.dim)) / scale * 8).astype(np.float32)
    tr.state = {**tr.state, **tr.runtime.state_from_host({"sgns.w": w})}
    return word2vec.vectors(tr)


@pytest.mark.parametrize("mesh_name", MESHES)
def test_step_against_the_plain_reference(tmp_path, mesh_name):
    """Six minibatches a worker through ``train_files``: the losses' sum,
    and every row of both matrices (the touched ones moved, by the
    reference's arithmetic; the others as they started)."""
    mesh = MESHES[mesh_name]
    d = mesh[0]
    c, o, neg = examples(B * 6 * d)
    paths, per = write_files(tmp_path, c, o, neg, d)
    tr = trainer_of(w2v_config(mesh))
    in0, out0 = scaled_start(tr)
    ref = DenseSGNS(in0, out0, eta=ETA)
    out = tr.train_files(paths)
    losses = []
    for s in range(per // B):
        workers = [slice(w * per + s * B, w * per + (s + 1) * B) for w in range(d)]
        losses.append(ref.step([(c[sl], o[sl], neg[sl]) for sl in workers]))
    got_in, got_out = word2vec.vectors(tr)
    assert np.abs(got_in - in0).max() > 1e-3 and np.abs(got_out - out0).max() > 1e-3
    np.testing.assert_allclose(got_in, ref.syn0, rtol=0, atol=2e-7)
    np.testing.assert_allclose(got_out, ref.syn1, rtol=0, atol=2e-7)
    assert out["objv"] == pytest.approx(sum(losses) / len(c), rel=1e-6)
    assert out["sgns_loss"] > 0 and "auc" not in out and "rmse" not in out


def test_evaluate_files_returns_the_references_mean_loss(tmp_path):
    c, o, neg = examples(B * 5 + 17)  # a ragged last batch
    paths, _ = write_files(tmp_path, c, o, neg, 1)
    tr = trainer_of(w2v_config())
    ref = DenseSGNS(*scaled_start(tr), eta=ETA)
    got = tr.evaluate_files(paths)
    assert set(got) == {"sgns_loss", "examples"} and got["examples"] == len(c)
    assert got["sgns_loss"] == pytest.approx(ref.mean_loss(c, o, neg), rel=1e-6)
    # a fresh table's output vectors are zero: every score 0, (1 + k) ln 2 a pair
    fresh = trainer_of(w2v_config()).evaluate_files(paths)
    assert fresh["sgns_loss"] == pytest.approx((1 + K) * np.log(2), rel=1e-6)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_multistep_is_the_single_step_trajectory(tmp_path, mesh_name):
    mesh = MESHES[mesh_name]
    c, o, neg = examples(B * 6 * mesh[0], seed=3)
    paths, _ = write_files(tmp_path, c, o, neg, mesh[0])
    tables = []
    for k in (1, 3):
        tr = trainer_of(w2v_config(mesh, steps_per_call=k))
        scaled_start(tr)
        tr.train_files(paths)
        tables.append(np.asarray(tr.state["sgns.w"]))
    np.testing.assert_array_equal(*tables)


def test_an_examples_entries_are_read_by_position_and_touch_the_references_rows(tmp_path):
    """Repeated centres, a context that is another example's negative (and
    another's centre: the same word id names two rows), a negative drawn
    twice in one example, and a padded tail: the rows that move are the
    reference's, each by the reference's sum, and no other."""
    c = np.array([5, 5, 5, 9, 7])
    o = np.array([7, 9, 7, 5, 7])  # word 7: a context, a negative below, and its own context
    neg = np.array([[9, 11, 11], [7, 3, 2], [1, 1, 1], [7, 7, 0], [5, 9, 119]])
    path = str(tmp_path / "few.txt")
    word2vec.write_examples(path, c, o, neg)
    tr = trainer_of(w2v_config(steps_per_call=1))  # a batch of 32 examples: 27 of them pads
    in0, out0 = scaled_start(tr)
    before = np.asarray(tr.state["sgns.w"]).copy()
    ref = DenseSGNS(in0, out0, eta=ETA)
    tr.train_files([path])
    ref.step([(c, o, neg)])
    got_in, got_out = word2vec.vectors(tr)
    np.testing.assert_allclose(got_in, ref.syn0, rtol=0, atol=1e-7)
    np.testing.assert_allclose(got_out, ref.syn1, rtol=0, atol=1e-7)
    moved = np.flatnonzero(np.abs(np.asarray(tr.state["sgns.w"]) - before).max(axis=1))
    want = sorted({1 + w for w in c} | {1 + V + w for w in np.concatenate([o, neg.ravel()])})
    assert moved.tolist() == want  # input rows of the centres, output rows of the rest
    assert not np.asarray(tr.state["sgns.w"])[0].any()


def test_identity_keys_put_the_two_matrices_in_one_key_space(tmp_path):
    tr = trainer_of(w2v_config())
    fmt, key_mode = ingest_of(tr.cfg)
    assert (fmt, key_mode) == (f"sgns:{V}", "identity")
    assert tr.cfg.data.num_keys == 1 + 2 * V and tr.cfg.data.max_nnz_per_example == 2 + K
    path = str(tmp_path / "all.txt")
    words = np.arange(V)
    word2vec.write_examples(path, words, words[::-1], np.stack([words] * K, axis=1))
    builder = BatchBuilder(tr.cfg.data.num_keys, 128, 2 + K, key_mode="identity")
    (b,) = list(MinibatchReader([path], fmt, builder))
    assert b.unique_keys[1 : b.num_unique].tolist() == list(range(1, 2 * V + 1))
    # entries keep the line's order: centre, context, negatives
    first = b.row_splits[:-1][:V]
    assert (np.diff(b.row_splits[: V + 1]) == 2 + K).all()
    np.testing.assert_array_equal(b.unique_keys[b.local_ids[first]], 1 + words)
    np.testing.assert_array_equal(b.unique_keys[b.local_ids[first + 1]], 1 + V + words[::-1])
    np.testing.assert_array_equal(b.unique_keys[b.local_ids[first + 2 + K - 1]], 1 + V + words)


def test_one_shape_for_batches_of_examples_of_equal_length(tmp_path):
    c, o, neg = examples(1024 * 5 + 100, vocab=50_000, k=5)
    path = str(tmp_path / "p.txt")
    word2vec.write_examples(path, c, o, neg)
    builder = BatchBuilder(1 + 100_000, 1024, 7, key_mode="identity", bucket_nnz=True)
    batches = list(MinibatchReader([path], "sgns:50000", builder))
    # 7 x 1024 entries exactly, no power of two: the builder's static cap, and the keys' one more
    assert {b.shape for b in batches[:-1]} == {(1024, 7168, 7169)}
    assert batches[-1].num_examples == 100
    assert all(b.num_entries == 7 * b.num_examples and b.keys_in_order() for b in batches)
    assert all((b.values[: b.num_entries] == 1).all() and (b.labels[: b.num_examples] == 1).all() for b in batches)


@pytest.mark.parametrize("mesh_name", ["1x1", "1x4"])
@pytest.mark.parametrize("dim", [DIM, 300])
def test_pad_rows_and_pad_lanes_stay_zero(tmp_path, mesh_name, dim):
    """Row 0, the kv axis' pad tail and, where a 300-lane row is stored 384
    wide, the lanes past the row's width: zero before and after training."""
    c, o, neg = examples(B * 4)
    paths, _ = write_files(tmp_path, c, o, neg, 1)
    tr = trainer_of(w2v_config(MESHES[mesh_name], dim=dim))
    assert np.asarray(tr.state["sgns.w"]).shape[1] == spmd.row_stride(dim) == (384 if dim == 300 else dim)
    scaled_start(tr)
    tr.train_files(paths)
    table = np.asarray(tr.state["sgns.w"])
    assert not table[0].any() and not table[tr.cfg.data.num_keys :].any()
    assert not table[:, dim:].any()
    assert table[1 : tr.cfg.data.num_keys, :dim].all()
    assert tr.full_weights("sgns").shape == (tr.cfg.data.num_keys, dim)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_rows_of_300_lanes_against_the_plain_reference(tmp_path, mesh_name):
    """The cell's width: pulled rows and gradients are 300 wide whatever
    the slot's stride, and the step is the reference's."""
    mesh = MESHES[mesh_name]
    c, o, neg = examples(B * 3 * mesh[0], seed=2)
    paths, per = write_files(tmp_path, c, o, neg, mesh[0])
    tr = trainer_of(w2v_config(mesh, dim=300))
    ref = DenseSGNS(*scaled_start(tr, scale=200.0), eta=ETA)
    tr.train_files(paths)
    for s in range(per // B):
        ref.step([(c[sl], o[sl], neg[sl]) for sl in (slice(w * per + s * B, w * per + (s + 1) * B) for w in range(mesh[0]))])
    got_in, got_out = word2vec.vectors(tr)
    assert got_in.shape == (V, 300)
    np.testing.assert_allclose(got_in, ref.syn0, rtol=0, atol=2e-7)
    np.testing.assert_allclose(got_out, ref.syn1, rtol=0, atol=2e-7)


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
def test_training_brings_related_words_together(tmp_path, mesh_name):
    """A planted co-occurrence corpus of two topics (words 0-4 and 5-9),
    through the module's own pair and negative tools and ``PodTrainer``:
    within-topic cosine over across-topic."""
    rng = np.random.default_rng(0)
    corpus = np.concatenate([rng.integers(0, 5, 8) + 5 * rng.integers(0, 2) for _ in range(600)])
    cp = tmp_path / "corpus.txt"
    cp.write_text(" ".join(map(str, corpus)))
    mesh = MESHES[mesh_name]
    cfg = w2v_config(mesh, vocab=16, dim=16, negatives=4, eta=0.005, batch=256)
    cfg.w2v.window, cfg.w2v.block_tokens, cfg.solver.epochs = 2, 2048, 20
    tr = trainer_of(cfg)
    shards = word2vec.examples_from_corpus([str(cp)], str(tmp_path), cfg, shards=mesh[0])
    first = tr.evaluate_files(shards)["sgns_loss"]
    tr.train_files(shards)
    assert tr.evaluate_files(shards)["sgns_loss"] < first
    e, _ = word2vec.vectors(tr)
    cos = lambda a, b: e[a] @ e[b] / (np.linalg.norm(e[a]) * np.linalg.norm(e[b]))  # noqa: E731
    within = np.mean([cos(0, i) for i in range(1, 5)])
    across = np.mean([cos(0, i) for i in range(5, 10)])
    assert within > across + 0.3, (within, across)


def test_checkpoint_round_trip_across_meshes(tmp_path):
    c, o, neg = examples(B * 4)
    paths, _ = write_files(tmp_path, c, o, neg, 1)
    tr = trainer_of(w2v_config((1, 4), dim=300))
    tr.train_files(paths)
    tr.save(str(tmp_path / "ckpt"))
    back = trainer_of(w2v_config((2, 2), dim=300))  # another mesh: another pad tail
    back.load(str(tmp_path / "ckpt"))
    np.testing.assert_array_equal(
        np.asarray(back.state["sgns.w"])[: tr.cfg.data.num_keys],
        np.asarray(tr.state["sgns.w"])[: tr.cfg.data.num_keys],
    )
    assert back.examples_seen == len(c)


def test_a_config_that_names_another_key_space_or_format_is_refused():
    cfg = w2v_config()
    cfg.data.num_keys += 1
    with pytest.raises(ValueError, match="1 \\+ 2 x w2v.vocab_size"):
        app_from_config(cfg)
    cfg = w2v_config()
    cfg.data.format = "libsvm"
    with pytest.raises(ValueError, match="data.format 'sgns'"):
        app_from_config(cfg)


def test_description_predicts_a_log_likelihood_and_scores_the_mean_loss():
    from parameter_server_tpu.models import metrics as M

    app = app_from_config(w2v_config())
    x = jnp.asarray([-2.0, 0.0, 3.0])
    np.testing.assert_array_equal(app.link(x), x)
    assert [n for n, _ in app.score] == ["sgns_loss"] and app.score[0][1] is M.sgns_loss
    assert M.sgns_loss(np.ones(3), np.array([-1.0, -2.0, -6.0])) == 3.0
    assert app.scope_names() == {"sgns"} and app.tables[0].vdim == DIM and len(app.tables) == 1


def test_cli_dump_holds_the_vectors_by_word(tmp_path):
    tr = trainer_of(w2v_config(dim=300))
    in_v, out_v = word2vec.vectors(tr)
    table = np.asarray(tr.state["sgns.w"])
    assert in_v.shape == out_v.shape == (V, 300)
    np.testing.assert_array_equal(in_v, table[1 : 1 + V, :300])
    np.testing.assert_array_equal(out_v, table[1 + V : 1 + 2 * V, :300])
    assert in_v.all() and not out_v.any() and np.abs(in_v).max() < 0.5 / 300


# -- the sgns format: the C parser and its Python twin --------------------------
def _sgns_text(seed: int, vocab: int) -> str:
    """Seeded lines with what a parser can trip on: blank lines, tabs and
    runs of spaces, lines of other lengths than 2 + k, leading zeros, the
    last word id, trailing blanks, CRLF, and no newline at the end."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(500):
        n = rng.choice([7, 7, 7, 3, 12])
        sep = rng.choice([" ", "\t", "  "])
        ids = [str(x) for x in rng.integers(0, vocab, n)]
        if rng.random() < 0.1:
            ids[0] = "00" + ids[0]
        lines.append(sep.join(ids) + rng.choice(["", " ", "\t"]))
        if rng.random() < 0.05:
            lines.append(rng.choice(["", "   "]))
    lines.append(f"{vocab - 1} {vocab - 1} 0 {vocab - 1}")
    lines.append(f"  {rng.integers(0, vocab)} 0 1")  # the ragged last line
    return rng.choice(["\n", "\r\n"]).join(lines)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_sgns_parsers_agree_to_the_bit(tmp_path, seed):
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.libsvm import iter_format

    vocab = 3_000_000  # the cell's: ids of 7 digits
    path = tmp_path / "p.txt"
    path.write_bytes(_sgns_text(seed, vocab).encode())
    fmt = f"sgns:{vocab}"
    assert native.has_native(fmt) and native.native_available()
    chunks = list(native.iter_chunks(path, fmt, chunk_bytes=1 << 12))  # many chunks, lines cut anywhere
    labels = np.concatenate([c[0] for c in chunks])
    keys = np.concatenate([c[2] for c in chunks])
    vals = np.concatenate([c[3] for c in chunks])
    lens = np.concatenate([np.diff(c[1]) for c in chunks])
    assert all(c[4] is None for c in chunks)  # slotless
    rows = list(iter_format(fmt, path))
    assert len(labels) == len(rows) == 502 and (labels == 1).all() and (vals == 1).all()
    np.testing.assert_array_equal(lens, [len(r[1]) for r in rows])
    np.testing.assert_array_equal(keys, np.concatenate([r[1] for r in rows]))
    np.testing.assert_array_equal(vals, np.concatenate([r[2] for r in rows]))
    assert set(lens) == {3, 4, 7, 12}
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    assert (keys[starts] < vocab).all() and (np.delete(keys, starts) >= vocab).all()
    assert keys[-7:-3].tolist() == [vocab - 1, 2 * vocab - 1, vocab, 2 * vocab - 1]
    # the reader's two backends build the same batches
    builder = BatchBuilder(1 + 2 * vocab, 128, 12, key_mode="identity")
    for a, b in zip(MinibatchReader([path], fmt, builder, backend="native"),
                    MinibatchReader([path], fmt, builder, backend="python"), strict=True):
        for f in ("unique_keys", "local_ids", "row_splits", "values", "labels", "example_mask"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


@pytest.mark.parametrize(
    "bad", ["3 x 1", "3 2", "7", "-1 2 1", "3 2 1x", "3 40 1", "40 2 1", "3.5 2 1", "3 2 +1", "3 2 1111111111111111111111"]
)
def test_sgns_parsers_refuse_the_same_lines(tmp_path, bad):
    from parameter_server_tpu.data import native
    from parameter_server_tpu.data.libsvm import iter_format

    path = tmp_path / "p.txt"
    path.write_text(f"1 1 1\n{bad}\n")
    with pytest.raises(ValueError, match="parse error at line 1"):
        list(native.iter_chunks(path, "sgns:40"))
    with pytest.raises(ValueError, match="parse error at line 1"):
        list(iter_format("sgns:40", path))
    with pytest.raises(ValueError, match="sgns:<vocab_size>"):
        list(iter_format("sgns", path))


# -- the starting vectors, the stride, the data tools ----------------------------
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 23])
def test_device_made_vectors_are_the_benchmark_references_function(seed):
    from benchmark.harness import ref_sgns

    vocab = 3_000_000
    rows = np.array([0, 1, 2, vocab, vocab + 1, 2 * vocab, 2 * vocab + 1, 2 * vocab + 600])
    got = np.asarray(word2vec.init_vectors(seed, jnp.asarray(rows, jnp.int32), 300, vocab))
    np.testing.assert_array_equal(got, ref_sgns.init_vectors(seed, rows, 300, vocab))
    live = got[1:4]
    assert not got[0].any() and not got[4:].any() and live.all()
    assert (np.abs(live) < 0.5 / 300).all() and abs(live.mean()) < 1e-4 and len(np.unique(live)) > 850


@pytest.mark.parametrize(
    "vdim,stride", [(1, 1), (16, 16), (32, 32), (48, 48), (64, 64), (128, 128), (100, 128), (300, 384), (384, 384), (1000, 1000), (1004, 1024)]
)
def test_a_slots_stride_is_read_off_the_rows_width(vdim, stride):
    """One decision, in one function: rows the chip reads and writes where
    they lie are stored as they are; a width with no block of 8k lanes and
    no whole tile is stored at the next whole tile, its pad lanes zero."""
    from parameter_server_tpu.kv.updaters import Adagrad

    assert spmd.row_stride(vdim) == stride
    ones = lambda rows, lanes: jnp.broadcast_to((jnp.arange(lanes) < vdim).astype(jnp.float32), (rows, lanes))  # noqa: E731
    t = spmd.Table("t", Adagrad(eta=0.1), vdim, lambda rows, lanes: {"w": ones(rows, lanes), "n": ones(rows, lanes)})
    slots = t.init_slots(8)
    assert {k: v.shape for k, v in slots.items()} == {"t.w": (8, stride), "t.n": (8, stride)}
    assert np.asarray(slots["t.w"])[:, :vdim].all() and not np.asarray(slots["t.w"])[:, vdim:].any()
    assert {k: v.shape for k, v in spmd.Table("t", Adagrad(eta=0.1), vdim).init_slots(8).items()} == {"t.w": (8, stride), "t.n": (8, stride)}
    if stride != vdim:
        with pytest.raises(ValueError, match="row_stride"):
            spmd._take_rows(jnp.zeros((8, vdim)), jnp.zeros((2,), jnp.int32), vdim)
        narrow = spmd.Table("t", Adagrad(eta=0.1), vdim, lambda rows, lanes: {"w": jnp.ones((rows, vdim))})
        with pytest.raises(ValueError, match="row_stride"):  # the store pads nothing: the maker makes the width it is handed
            narrow.init_slots(8)


def test_dynamic_window_keeps_the_pairs_within_each_centres_reach(tmp_path):
    """``PairStream(dynamic_window=True)`` draws a reach b in 1..window a
    centre and keeps the contexts within b of it, across block borders
    too: a subset of the fixed window's pairs, about (window + 1) / 2 /
    window of them, every centre keeping its nearest neighbours."""
    from parameter_server_tpu.models.word2vec import NegativeSampler, PairStream, _window_pairs
    from parameter_server_tpu.parallel.workload import WorkloadPool

    corpus = np.arange(3000) % 997  # every (centre, context) pair names its distance
    f = tmp_path / "corpus.txt"
    f.write_text(" ".join(map(str, corpus)))
    got = []
    s = PairStream(
        0, WorkloadPool([str(f)]), window=5, batch_size=64, num_negatives=2,
        sampler=NegativeSampler(np.bincount(corpus, minlength=997), seed=0),
        block_tokens=100, seed=3, dynamic_window=True,
    )
    while (b := s.next_batch()) is not None:
        m = b["mask"] > 0
        got += list(zip(b["center"][m].tolist(), b["context"][m].tolist()))
    full = list(zip(*(x.tolist() for x in _window_pairs(corpus, 5))))
    from collections import Counter

    assert not Counter(got) - Counter(full)  # a subset, with multiplicity
    assert 0.5 < len(got) / len(full) < 0.7  # E[b] / window = 0.6
    dist = Counter(min((x - c) % 997, (c - x) % 997) for c, x in got)
    assert dist[1] == 2 * (len(corpus) - 1)  # reach >= 1 always: both neighbours
    assert dist[1] > dist[2] > dist[3] > dist[4] > dist[5] > 0 and set(dist) == {1, 2, 3, 4, 5}
    # a reach is the centre's: pair (c -> x) at distance 5 says nothing of (x -> c)
    far = {(c, x) for c, x in got if min((x - c) % 997, (c - x) % 997) == 5}
    assert any((x, c) not in far for c, x in far)


def test_examples_from_corpus_writes_what_the_sgns_parser_reads(tmp_path):
    rng = np.random.default_rng(2)
    corpus = rng.integers(0, 50, 4000)
    cp = tmp_path / "corpus.npy"
    np.save(cp, corpus)
    cfg = w2v_config(vocab=50, negatives=4, batch=128)
    cfg.w2v.window, cfg.w2v.block_tokens = 3, 512
    out = tmp_path / "shards"
    out.mkdir()
    paths = word2vec.examples_from_corpus([str(cp)], str(out), cfg, shards=2)
    assert len(paths) == 2
    rows = [np.loadtxt(p, dtype=np.int64) for p in paths]
    assert all(r.shape[1] == 2 + 4 and r.min() >= 0 and r.max() < 50 for r in rows)
    n = sum(len(r) for r in rows)
    assert 2 * 3999 <= n <= 2 * 3 * 4000 and abs(len(rows[0]) - len(rows[1])) <= 128
    fmt, _ = ingest_of(cfg)
    batches = list(MinibatchReader(paths, fmt, BatchBuilder(cfg.data.num_keys, 128, 6, key_mode="identity")))
    assert sum(b.num_examples for b in batches) == n
