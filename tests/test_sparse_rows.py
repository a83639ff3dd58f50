"""The two sweeps by example of ``ops.sparse`` (``sum_by_example`` /
``spread_by_example``) against ``segment_sum`` / ``take`` on batches from
``BatchBuilder``, under both conventions for the pads' row ids, the
promise they rest on, and the two sweeps by key slot (``take_by_slot`` /
``sum_by_slot``) whole and walked on the same promise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BatchBuilder
from parameter_server_tpu.ops import sparse
from parameter_server_tpu.ops.sparse import (
    csr_grad,
    csr_logits,
    spread_by_example,
    sum_by_example,
    sum_by_slot,
    take_by_slot,
)
from parameter_server_tpu.parallel.spmd import _row_ids_of

NUM_KEYS = 1 << 12

# name -> (batch_size, max_nnz_per_example, bucket_nnz, entries of each example)
SHAPES = {
    "random": (32, 16, True, [0, 3, 1, 0, 0, 9, 16, 2, 1, 1, 7, 0, 5, 12, 1, 0, 4, 4, 0, 1]),
    "empty_head_and_tail": (16, 8, True, [0, 0, 5, 3, 0]),
    "one_entry_rows": (8, 4, True, [1] * 8),
    "long_row": (8, 128, True, [2, 100, 1, 0, 67]),
    "filled_to_the_last_entry": (8, 4, False, [4] * 8),
    "no_entries": (8, 4, False, [0, 0, 0]),
}


def _batch(name):
    batch_size, cap, bucket, counts = SHAPES[name]
    rng = np.random.default_rng(len(name))
    keys = [rng.choice(NUM_KEYS - 2, size=n, replace=False).astype(np.uint64) for n in counts]
    vals = [rng.normal(1.0, 0.5, size=n).astype(np.float32) for n in counts]
    labels = (rng.random(len(counts)) < 0.5).astype(np.float32)
    builder = BatchBuilder(
        num_keys=NUM_KEYS, batch_size=batch_size, max_nnz_per_example=cap,
        key_mode="identity", bucket_nnz=bucket,
    )
    return builder.build(labels, keys, vals)


def _row_ids(b, pads):
    """The entries' row ids as a caller has them: the host's ``CSRBatch``
    gives its pads row 0, the device's ``_row_ids_of`` the last row."""
    if pads == "host":
        return jnp.asarray(b.row_ids)
    fields = {"values": b.values, "labels": b.labels, "row_splits": b.row_splits}
    return _row_ids_of({k: jnp.asarray(v) for k, v in fields.items()})


def _terms(b, lanes, seed=0):
    """Per-entry terms with NON-zero pads: neither op may read one."""
    rng = np.random.default_rng(seed)
    shape = (len(b.values),) if lanes == 1 else (len(b.values), lanes)
    return rng.normal(size=shape).astype(np.float32)


def _per_example(b, lanes, seed=1):
    rng = np.random.default_rng(seed)
    shape = (len(b.labels),) if lanes == 1 else (len(b.labels), lanes)
    return rng.normal(size=shape).astype(np.float32)


CASES = [
    (name, pads, lanes)
    for name in SHAPES for pads in ("host", "device") for lanes in (1, 16)
]


@pytest.mark.parametrize("name", SHAPES)
def test_batches_keep_the_promise(name):
    """What both ops rest on: real entries' row ids never decrease and are
    what ``row_splits`` says, pads (value 0) lie behind them, the rows past
    the batch's examples are empty, and the device's rebuilt row ids agree
    with the host's on every real entry."""
    b = _batch(name)
    n, rows = b.num_entries, len(b.labels)
    assert b.row_splits.shape == (rows + 1,) and b.row_splits[-1] == n
    assert (np.diff(b.row_splits) >= 0).all()
    assert (np.diff(b.row_ids[:n]) >= 0).all()
    np.testing.assert_array_equal(
        b.row_ids[:n], np.repeat(np.arange(rows), np.diff(b.row_splits))
    )
    assert not b.values[n:].any() and not b.row_ids[n:].any()
    dev = np.asarray(_row_ids(b, "device"))
    np.testing.assert_array_equal(dev[:n], b.row_ids[:n])
    assert (dev[n:] == rows - 1).all()
    if name == "filled_to_the_last_entry":
        assert n == len(b.values)  # a split equal to NNZ
    if name == "long_row":
        assert np.diff(b.row_splits).max() > 64


@pytest.mark.parametrize("name,pads,lanes", CASES)
def test_sum_by_example_is_the_segment_sum_of_real_entries(name, pads, lanes):
    b = _batch(name)
    x, n = _terms(b, lanes), b.num_entries
    want = jax.ops.segment_sum(
        jnp.asarray(x[:n]), jnp.asarray(b.row_ids[:n]), num_segments=len(b.labels)
    )
    got = sum_by_example(jnp.asarray(x), _row_ids(b, pads), jnp.asarray(b.row_splits))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got)[np.diff(b.row_splits) == 0].any()  # empty rows: exactly 0


@pytest.mark.parametrize("name,pads,lanes", CASES)
def test_spread_by_example_is_the_take_on_real_entries(name, pads, lanes):
    b = _batch(name)
    v, n = _per_example(b, lanes), b.num_entries
    got = np.asarray(
        spread_by_example(jnp.asarray(v), _row_ids(b, pads), jnp.asarray(b.row_splits))
    )
    assert got.shape[0] == len(b.values)
    np.testing.assert_array_equal(got[:n], v[b.row_ids[:n]])  # copied, not summed: exact
    assert not got[n:].any()


@pytest.mark.parametrize("name,pads,lanes", CASES)
def test_each_op_is_the_others_transpose(name, pads, lanes):
    """``jax.vjp`` of each (its ``custom_vjp`` names the other op) against
    the transpose worked out in NumPy: the cotangent of the sum is the
    take on real entries and 0 on pads, that of the spread the segment sum
    of the real entries."""
    b = _batch(name)
    n, ids, splits = b.num_entries, _row_ids(b, pads), jnp.asarray(b.row_splits)
    x, v = _terms(b, lanes), _per_example(b, lanes)
    _, vjp_sum = jax.vjp(lambda t: sum_by_example(t, ids, splits), jnp.asarray(x))
    want = np.zeros_like(x)
    want[:n] = v[b.row_ids[:n]]
    np.testing.assert_array_equal(vjp_sum(jnp.asarray(v))[0], want)
    _, vjp_spread = jax.vjp(lambda t: spread_by_example(t, ids, splits), jnp.asarray(v))
    want = np.zeros_like(v)
    np.add.at(want, b.row_ids[:n], x[:n])
    np.testing.assert_allclose(vjp_spread(jnp.asarray(x))[0], want, rtol=1e-5, atol=1e-5)


PIECE = 64
SLOTS = 37
# (entry slots, real entries): the inert batch, one entry, exactly one piece,
# one piece + 1, a run that ends inside a later piece, the full axis; 200
# entry slots are no whole number of pieces, 64 are a single piece: one op
WALKS = [(e, r) for e in (256, 200) for r in (0, 1, PIECE, PIECE + 1, 150, e)] + [(PIECE, 40)]
WALK_CASES = [(e, r, lanes) for e, r in WALKS for lanes in (1, 16)]


@pytest.fixture
def pieces(monkeypatch):
    """The sweeps by key slot walk pieces of ``PIECE`` entries."""
    monkeypatch.setattr(sparse, "_WALK_ENTRIES", PIECE)


def _slot_case(entries, real, lanes):
    """(w, x, local_ids, row_splits): per-slot values, per-entry terms with
    NON-zero pads, slots drawn for every entry slot (a pad's too: neither
    op may read one), and row splits that end at ``real``."""
    rng = np.random.default_rng(entries + real)
    shape = () if lanes == 1 else (lanes,)
    w = rng.normal(size=(SLOTS, *shape)).astype(np.float32)
    x = rng.normal(size=(entries, *shape)).astype(np.float32)
    ids = rng.integers(0, SLOTS, entries).astype(np.int32)
    return w, x, ids, jnp.asarray([0, real // 2, real], jnp.int32)


@pytest.mark.parametrize("entries,real,lanes", WALK_CASES)
def test_take_by_slot_is_the_take_on_real_entries(pieces, entries, real, lanes):
    w, x, ids, splits = _slot_case(entries, real, lanes)
    assert sparse.sweep_walks(entries) == (entries > PIECE)
    got = np.asarray(jax.jit(take_by_slot)(w, ids, splits))
    assert got.shape == x.shape
    np.testing.assert_array_equal(got[:real], w[ids[:real]])  # copied: exact
    assert not got[real:].any()


@pytest.mark.parametrize("entries,real,lanes", WALK_CASES)
def test_sum_by_slot_is_the_segment_sum_of_real_entries(pieces, entries, real, lanes):
    w, x, ids, splits = _slot_case(entries, real, lanes)
    want = jax.ops.segment_sum(jnp.asarray(x[:real]), jnp.asarray(ids[:real]), num_segments=SLOTS)
    got = jax.jit(sum_by_slot, static_argnums=3)(x, ids, splits, SLOTS)
    assert got.shape == w.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("entries,real,lanes", WALK_CASES)
def test_each_slot_op_is_the_others_transpose(pieces, entries, real, lanes):
    """``jax.vjp`` of each (a loop with a trip count read off the batch has
    no reverse of autodiff's: its ``custom_vjp`` names the other op)
    against the transpose worked out in NumPy."""
    w, x, ids, splits = _slot_case(entries, real, lanes)
    _, vjp_take = jax.vjp(lambda t: take_by_slot(t, ids, splits), jnp.asarray(w))
    want = np.zeros_like(w)
    np.add.at(want, ids[:real], x[:real])
    np.testing.assert_allclose(vjp_take(jnp.asarray(x))[0], want, rtol=1e-5, atol=1e-5)
    _, vjp_sum = jax.vjp(lambda t: sum_by_slot(t, ids, splits, SLOTS), jnp.asarray(x))
    want = np.zeros_like(x)
    want[:real] = w[ids[:real]]
    np.testing.assert_array_equal(vjp_sum(jnp.asarray(w))[0], want)


@pytest.mark.parametrize("op", ["take", "sum"])
@pytest.mark.parametrize("lanes", [1, 16])
def test_a_single_piece_is_one_op_and_more_are_a_loop(pieces, op, lanes):
    """The form is read off the static entry axis: one piece or less lowers
    to the one gather / scatter-add the step had, more to a ``while`` that
    holds it."""
    def lowered(entries):
        w, x, ids, splits = _slot_case(entries, entries // 2, lanes)
        if op == "take":
            return jax.jit(take_by_slot).lower(w, ids, splits).as_text()
        return jax.jit(sum_by_slot, static_argnums=3).lower(x, ids, splits, SLOTS).as_text()

    kind = '"stablehlo.gather"(' if op == "take" else '"stablehlo.scatter"('
    one, looped = lowered(PIECE), lowered(4 * PIECE)
    assert one.count(kind) == 1 and "stablehlo.while" not in one
    assert looped.count(kind) == 1 and looped.count("stablehlo.while") == 1


@pytest.mark.parametrize("entries,real", WALKS)
def test_the_hosts_count_is_the_device_loops(pieces, entries, real):
    """``walked_entries`` (the counter ``grad.walk_share``'s numerator, on
    the host) against the turns ``_walk``'s loop takes on the same batch."""
    splits = jnp.asarray([0, real], jnp.int32)
    if not sparse.sweep_walks(entries):
        assert sparse.walked_entries(real, entries) == entries
        return
    turns = jax.jit(lambda s: sparse._walk(s, entries, lambda at, real, fresh, n: n + 1, 0))(splits)
    assert sparse.walked_entries(real, entries) == int(turns) * PIECE


@pytest.mark.parametrize("walked", [False, True], ids=["whole", "walked"])
@pytest.mark.parametrize("name", ["random", "long_row", "filled_to_the_last_entry"])
@pytest.mark.parametrize("pads", ["host", "device"])
def test_csr_ops_are_the_dense_matvec_and_its_transpose(monkeypatch, name, pads, walked):
    if walked:  # 16 entries a piece: every batch here is several
        monkeypatch.setattr(sparse, "_WALK_ENTRIES", 16)
    b = _batch(name)
    assert sparse.sweep_walks(len(b.values)) == walked
    rows, slots, n = len(b.labels), len(b.unique_keys), b.num_entries
    dense = np.zeros((rows, slots), np.float64)
    np.add.at(dense, (b.row_ids[:n], b.local_ids[:n]), b.values[:n])
    rng = np.random.default_rng(2)
    w = rng.normal(size=(slots, 1)).astype(np.float32)
    err = rng.normal(size=rows).astype(np.float32)
    ids, splits = _row_ids(b, pads), jnp.asarray(b.row_splits)
    logits = csr_logits(jnp.asarray(w), b.values, b.local_ids, ids, splits)
    np.testing.assert_allclose(logits, dense @ w[:, 0], rtol=1e-4, atol=1e-4)
    g = csr_grad(jnp.asarray(err), b.values, b.local_ids, ids, splits, num_unique=slots)
    assert g.shape == (slots, 1)
    np.testing.assert_allclose(g[:, 0], dense.T @ err, rtol=1e-4, atol=1e-4)


def _parent_wd_loss(pulled, mlp_params, b, row_ids):
    """``wide_deep._loss`` as it stood before the sweeps by example were
    running passes: ``segment_sum`` by ``row_ids`` three times."""
    from parameter_server_tpu.models import wide_deep as wd

    rows = b["labels"].shape[0]
    values = wd._values_of(b)
    contrib = values * jnp.take(pulled["wide"].reshape(-1), b["local_ids"])
    wide = jax.ops.segment_sum(contrib, row_ids, num_segments=rows)
    ent_emb = jnp.take(pulled["emb"], b["local_ids"], axis=0)
    ones = (values != 0).astype(jnp.float32)
    num = jax.ops.segment_sum(ent_emb * ones[:, None], row_ids, num_segments=rows)
    cnt = jax.ops.segment_sum(ones, row_ids, num_segments=rows)
    logits = wide + wd._mlp_apply(mlp_params, num / jnp.maximum(cnt, 1.0)[:, None])
    m = b["example_mask"].astype(jnp.float32)
    return jnp.sum(m * (jax.nn.softplus(logits) - b["labels"] * logits)), logits


@pytest.mark.parametrize("walked", [False, True], ids=["whole", "walked"])
@pytest.mark.parametrize("name", ["random", "long_row", "filled_to_the_last_entry"])
def test_wide_deep_gradients_are_the_parents(monkeypatch, name, walked):
    """``jax.grad`` of Wide&Deep's loss through the four ops, under
    ``jit``, against the same loss through ``take`` and ``segment_sum``:
    every pulled row's and every tower parameter's gradient within 1e-6,
    with the sweeps by key slot whole and walked."""
    from parameter_server_tpu.models import wide_deep as wd
    from parameter_server_tpu.parallel.spmd import CSR_FIELDS

    if walked:
        monkeypatch.setattr(sparse, "_WALK_ENTRIES", 16)
    cb = _batch(name)
    assert sparse.sweep_walks(len(cb.values)) == walked
    b = {f: jnp.asarray(getattr(cb, f)) for f in CSR_FIELDS}
    rng = np.random.default_rng(3)
    slots = len(cb.unique_keys)
    pulled = {
        "wide": jnp.asarray(rng.normal(size=(slots, 1)).astype(np.float32) * 0.1),
        "emb": jnp.asarray(rng.normal(size=(slots, 16)).astype(np.float32) * 0.05),
    }
    mlp = wd.init_mlp(16, [32, 16], seed=4)
    row_ids = _row_ids_of(b)

    def grad(loss):
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(pulled, mlp, b, row_ids)

    (loss, logits), g = grad(wd._loss)
    (loss0, logits0), g0 = grad(_parent_wd_loss)
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    np.testing.assert_allclose(logits, logits0, rtol=1e-6, atol=1e-6)
    for got, want in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
