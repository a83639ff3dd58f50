"""DLRM: per-field embedding tables in the KV store, two MLPs and the
pairwise-dot interaction between them (Naumov et al., "Deep Learning
Recommendation Model for Personalization and Recommendation Systems",
arXiv:1906.00091, sections 2-3; the sizes MLPerf Training's recommendation
benchmark ran it at on the Criteo 1TB click logs are the [dlrm] defaults).

One example is a ``criteo`` line: 13 integer columns, the dense input
``x`` (``sign(v) log(1 + |v|)``, which the parsers put in ``values``), and
26 categorical columns, each with a table of its own:

    z0 = MLP_bot(x)                     13 -> bot..., ReLU after every layer
    e_f = E[off_f + c_f mod R_f]        one emb_dim-wide row a column f
    T = [z0; e_1; ...; e_26]            (27, emb_dim)
    p = (T T^t)[i, j] for i > j         the 351 pairs, row by row
    logit = MLP_top([z0; p])            ReLU after every layer but the last

under the logistic loss, summed over the minibatch, and plain SGD on both
halves: the touched rows take the sum of the minibatch's gradients once
(the store's push) and the MLPs one ``optax.sgd`` step on theirs.

TPU re-expression: ONE table ``emb`` of ``vdim = emb_dim`` over one key
space, as the reference's KV layer has one: row 0 is the pad, rows 1..13
are the integer columns' (their entries carry the dense input; the rows are
pulled with the rest, read by nothing and never move), and column f's
table starts at row ``14 + off_f`` (``data.libsvm.iter_criteo``'s per-field
layout, keyed by identity). An example's 39 entries carry their ROLES IN
THEIR ORDER, as ``models.word2vec``'s do: entries 0..12 give ``x`` from
``values``, entries 13..38 the 26 rows from ``local_ids``. An example with
a field missing has fewer entries and no such order: the app's host check
(``StepApp.check_batch``) refuses its batch.

The multi-hot form (MLPerf Training's DLRM-DCNv2 on the multi-hot Criteo
1TB logs, since v3.0; ``[dlrm].hot``, ``cross_layers``, ``cross_rank``,
``updater``) differs in three places. A column's id stands for a fixed bag
of ``h_f`` rows of its table, which the parsers write out as ``h_f`` entries
(``data.libsvm.CriteoBags``): an example carries ``13 + sum(h_f)`` entries,
still read by position, and the bag's rows are SUMMED into the column's
vector (a row twice in a bag counts twice):

    p_f = sum over the bag of E[row]    x_0 = [z0; p_1; ...; p_26]
    x_{l+1} = x_0 * ((x_l V_l) W_l + b_l) + x_l     (``mlp.cross_apply``)
    logit = MLP_top(x_L)

the low-rank cross network of DCN V2 (Wang et al., arXiv:2008.13535) in the
pairwise dots' place, its layers under the dense group's ``"cross"``; and
both halves step by AdaGrad (``kv.updaters.Adagrad`` / ``dense_adagrad``).
Bags of VARIABLE length would need a field id an entry: not here. The
bags' rows are read POSITION-MAJOR (``read_bags``; since PR 53): the take
by the turned slots writes ``(sum(h_f), B, emb_dim)``, a bag is a run of
its leading axis, and ``pool_bags``' hand-written backward pass turns the
``(B, 26, emb_dim)`` cotangent and writes its planes out once, so that the
two arrays of ``sum(h_f) B emb_dim`` elements (898 MB each at the
benchmark's sizes) are written by the take and read by its transpose, the
scatter-add, and never relaid.

This module holds the model and its description (``dlrm_app``); the step
is ``parallel.spmd``'s and the training loop ``PodTrainer``'s."""

from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax

from parameter_server_tpu.data.batch import CSRBatch
from parameter_server_tpu.data.libsvm import N_CAT, N_INT, criteo_format
from parameter_server_tpu.kv.store import hashed_unit, live_lanes
from parameter_server_tpu.kv.updaters import Adagrad, Sgd, Updater, dense_adagrad
from parameter_server_tpu.models import mlp
from parameter_server_tpu.models.metrics import BINARY_SCORES
from parameter_server_tpu.parallel.spmd import (
    DenseGroup,
    StepApp,
    Table,
    _sub_scope,
    _values_of,
)

TABLE = "emb"  # state entry "emb.w", scopes "ps.pull/emb", "ps.push/*/emb"
DENSE = "mlp"  # state entries "mlp.bot.0.W", ...; scopes "ps.grad/mlp/*"
ENTRIES = N_INT + N_CAT  # an example's entries, the dense columns' first
FIRST_FIELD_ROW = 1 + N_INT  # behind the pad row and the dense columns' rows
# the phases of the dense half, nested under "ps.grad/mlp"
MLP_SCOPES = ("bot", "interact", "top")
# the multi-hot form's: the cross network in the dots' place, and the bags'
# take and sum under the table's own scope, "ps.grad/emb/pool"
DCN_SCOPES = ("bot", "cross", "top", "pool")
# the seed of the bags' draws (``data.libsvm.bag_draw``): which rows an id's
# bag holds is a property of the data set, not of a run
BAG_SEED = 0x2008_1353_5D0C_0002
# every product of the interaction: the configuration states float32 sums of float32 products
_HIGHEST = jax.lax.Precision.HIGHEST


def num_keys_of(field_rows) -> int:
    """Rows of the one key space: the pad row, the 13 dense columns' rows
    and the 26 tables."""
    return FIRST_FIELD_ROW + int(sum(field_rows))


def entries_of(hot) -> int:
    """An example's entries: the dense columns', then every column's bag."""
    return N_INT + int(sum(hot))


def interaction_width(emb_dim: int, cross: bool = False) -> int:
    """The top MLP's input: z0 and the pairs under the diagonal, or the
    cross network's output, as wide as its input ``[z0; p_1; ...; p_26]``."""
    vectors = 1 + N_CAT
    return vectors * emb_dim if cross else emb_dim + vectors * (vectors - 1) // 2


@functools.lru_cache(maxsize=None)
def _pair_selectors(vectors: int) -> tuple[np.ndarray, np.ndarray]:
    """The 0/1 matrices that cut the pairs out of ``T T^t`` seen as
    ``(B, vectors^2)`` and put their cotangent back. ``cut`` is
    ``(vectors^2, pairs)``: column p holds its one at ``i * vectors + j``
    for the p-th pair ``i > j``, row by row. ``back`` is ``(pairs,
    vectors^2)`` with row p's ones at ``(i, j)`` and at ``(j, i)``: a
    cotangent times it is ``dZ + dZ^t`` at once (the two have disjoint
    supports and the diagonal stays zero). The vectors are numbered as
    ``_pairs`` holds them, ``z0`` LAST (vector 0 of the model at place
    ``vectors - 1``, e_k at ``k - 1``): the order of the pairs is the
    model's."""
    i, j = np.tril_indices(vectors, -1)  # the model's numbering, row by row
    at_i, at_j = (i - 1) % vectors, (j - 1) % vectors
    p = np.arange(len(i))
    cut = np.zeros((vectors * vectors, len(i)), np.float32)
    cut[at_i * vectors + at_j, p] = 1.0
    back = np.zeros((len(i), vectors * vectors), np.float32)
    back[p, at_i * vectors + at_j] = 1.0
    back[p, at_j * vectors + at_i] = 1.0
    return cut, back


def _vectors(z0: jax.Array, e: jax.Array) -> jax.Array:
    """(B, F + 1, d): the interaction's vectors as ``_pairs`` holds them,
    ``e``'s first and ``z0`` last (``_pair_selectors`` numbers them so)."""
    return jnp.concatenate([e, z0[:, None, :]], axis=1)


@jax.custom_vjp
def _pairs(z0: jax.Array, e: jax.Array) -> jax.Array:
    """(B, d) and (B, F, d) -> (B, (F + 1) F / 2): the entries of ``T T^t``
    strictly under the diagonal, cut by ONE selection product where 26
    slices and a concatenate each read the lane-padded ``(B, 27, 27)``
    again. Exact: a 0/1 entry is a bfloat16 number, and at ``HIGHEST`` the
    three bfloat16 pieces of a float32 sum back to it."""
    t = _vectors(z0, e)
    vectors = t.shape[1]
    cut, _ = _pair_selectors(vectors)
    z = jnp.einsum("bid,bjd->bij", t, t, precision=_HIGHEST)
    return jnp.dot(z.reshape(-1, vectors * vectors), cut, precision=_HIGHEST)


def _pairs_fwd(z0, e):
    return _pairs(z0, e), (z0, e)


def _pairs_bwd(residual, g):
    """``dT = (dZ + dZ^t) T``: one selection product builds the symmetrised
    cotangent from the pairs' (no pad, no transpose, no add), one batched
    product applies it, where ``jax.grad`` emits ``dZ T`` and ``dZ^t T``
    and adds them. With ``z0`` the last vector, ``e``'s share is the head
    of ``dT``: the same tiles, no copy."""
    t = _vectors(*residual)
    vectors = t.shape[1]
    _, back = _pair_selectors(vectors)
    s = jnp.dot(g, back, precision=_HIGHEST).reshape(-1, vectors, vectors)
    dt = jnp.einsum("bij,bjd->bid", s, t, precision=_HIGHEST)
    return dt[:, -1], dt[:, :-1]


_pairs.defvjp(_pairs_fwd, _pairs_bwd)


def interact(z0: jax.Array, e: jax.Array) -> jax.Array:
    """(B, d) and (B, F, d) -> (B, d + (F + 1) F / 2): ``z0`` beside the
    dots of every pair of the F + 1 vectors ``[z0; e]``, the entries of
    ``T T^t`` strictly under the diagonal, row by row (``_pairs``: cut by a
    selection product, differentiated by hand)."""
    return jnp.concatenate([z0, _pairs(z0, e)], axis=1)


def _by_position(flat: jax.Array, examples: int, entries: int = ENTRIES) -> jax.Array:
    """(NNZ,) -> (B, entries): entry j of example i. Every example carries
    exactly ``entries`` entries (``check_batch`` holds the host to it) and
    the real entries are the head of the entry axis, so example i's begin
    at ``entries * i``: a slice and a reshape, where a take by
    ``row_splits`` would gather 320,000 elements one by one (2.4 ms of the
    step on the chip). A short bucket (a file's last, partial batch) is
    zero-extended: its missing examples are masked."""
    need = examples * entries
    if flat.shape[0] < need:
        flat = jnp.pad(flat, (0, need - flat.shape[0]))
    return flat[:need].reshape(examples, entries)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pool_bags(rows: jax.Array, hot: tuple[int, ...]) -> jax.Array:
    """(B, sum(hot), d) -> (B, F, d): column f's vector, the sum of its
    bag's ``hot[f]`` rows, which lie one behind the other in the entry
    axis. Summed POSITION-MAJOR, as runs of the leading axis of ``(sum(hot),
    B, d)``: that is how the step's take writes the rows (the swap here folds
    with the caller's), so the one array of ``sum(hot) B d`` elements is never
    relaid; the backward pass is written by hand for the same reason."""
    planes, out, at = jnp.swapaxes(rows, 0, 1), [], 0
    for h in hot:
        out.append(planes[at] if h == 1 else jnp.sum(planes[at : at + h], axis=0))
        at += h
    return jnp.stack(out, axis=1)


def _pool_bags_fwd(rows, hot):
    return pool_bags(rows, hot), None


def _pool_bags_bwd(hot, _, g):
    """A bag's rows each take their column's cotangent. The SMALL array
    turns, ``(B, F, d)`` to ``(F, B, d)``, and its planes are written out
    once, position-major, as the scatter-add behind reads them: ``jax.grad``
    of the sums builds the ``(sum(hot), B, d)`` cotangent batch-minor (the
    dense half's layout carried backward) and copies it row-major."""
    gp = jnp.swapaxes(g, 0, 1)
    planes = jnp.concatenate(
        [jnp.broadcast_to(gp[f][None], (h, *gp.shape[1:])) for f, h in enumerate(hot)], axis=0
    )
    return (jnp.swapaxes(planes, 0, 1),)


pool_bags.defvjp(_pool_bags_fwd, _pool_bags_bwd)


def read_bags(pulled: jax.Array, slots: jax.Array, hot: tuple[int, ...]) -> jax.Array:
    """(U, d) pulled rows and (B, sum(hot)) slots -> (B, F, d): every
    column's bag taken and summed. The SLOTS turn (int32, ``d`` times
    smaller than the rows), so the take writes ``(sum(hot), B, d)``
    row-major, which is its natural ``(sum(hot) B, d)`` with no copy where
    ``B`` is whole 8-row tiles, and its transpose, the scatter-add, reads
    the cotangent the same way; ``(B, sum(hot), d)`` exists only as the
    seam to ``pool_bags`` (looked up in the module when called: a run with
    another pooling in its place is differentiated by ``jax.grad``). A
    batch's ``local_ids`` lie inside its own key axis, so the take need
    select no fill in (``mode="clip"``)."""
    planes = jnp.take(pulled, slots.T, axis=0, mode="clip")
    return pool_bags(jnp.swapaxes(planes, 0, 1), hot)


def _logits(pulled, params, b, row_ids, hot: tuple[int, ...] | None = None):
    """(B,) logits: the examples' entries by position. A padded example's
    entries are zeros (the pad row, a dense input of 0); its loss is
    masked. ``hot``: the columns' bag sizes in the multi-hot form; the
    interaction is the cross network where ``params`` holds one."""
    examples = b["labels"].shape[0]
    entries = ENTRIES if hot is None else entries_of(hot)
    x = _by_position(_values_of(b), examples, entries)[:, :N_INT]
    slots = _by_position(b["local_ids"], examples, entries)[:, N_INT:]
    if hot is None:
        e = jnp.take(pulled[TABLE], slots, axis=0)  # (B, 26, d)
    else:
        with _sub_scope(TABLE), jax.named_scope("pool"):
            e = read_bags(pulled[TABLE], slots, hot)
    with _sub_scope(DENSE):
        with jax.named_scope("bot"):
            z0 = mlp.mlp_apply(params["bot"], x, last=jax.nn.relu)
        if "cross" in params:
            with jax.named_scope("cross"):
                x0 = jnp.concatenate([z0[:, None, :], e], axis=1).reshape(examples, -1)
                r = mlp.cross_apply(params["cross"], x0)
        else:
            with jax.named_scope("interact"):
                r = interact(z0, e)
        with jax.named_scope("top"):
            return mlp.mlp_apply(params["top"], r)[:, 0]


def _loss(pulled, params, b, row_ids, hot=None):
    logits = _logits(pulled, params, b, row_ids, hot)
    m = b["example_mask"].astype(jnp.float32)
    loss = jnp.sum(m * (jax.nn.softplus(logits) - b["labels"] * logits))
    return loss, logits


def _grad(pulled, params, b, row_ids, hot=None):
    """One differentiable forward; ``jax.grad`` gives the pulled rows'
    gradient (the transpose of the take by ``local_ids``: a row's gradient
    summed over the minibatch, zero for the rows no entry reads: the pad's
    and the dense columns') and the MLPs'."""
    (loss, logits), (g_pulled, g_mlp) = jax.value_and_grad(
        functools.partial(_loss, hot=hot), argnums=(0, 1), has_aux=True
    )(pulled, params, b, row_ids)
    return loss, logits, g_pulled, g_mlp


def check_batch(b: CSRBatch, entries: int = ENTRIES) -> None:
    """On the host: every example of the batch carries its 39 entries (13
    and its columns' bags in the multi-hot form), so that an entry's
    position says its field. The criteo parsers skip an empty or malformed
    field, and the example is then shorter."""
    counts = np.diff(b.row_splits[: b.num_examples + 1])
    if (counts != entries).any():
        i = int(np.flatnonzero(counts != entries)[0])
        fields = f"{N_CAT} categorical fields" + (
            "" if entries == ENTRIES else f"' bags of {entries - N_INT} ids in all"
        )
        raise ValueError(
            f"app dlrm reads an example's {entries} entries by position "
            f"({N_INT} dense columns, then {fields}): example "
            f"{i} of the batch carries {int(counts[i])}, so a field of its line "
            "is empty or malformed"
        )


def init_rows(seed: int, rows: jax.Array, emb_dim: int, field_rows, lanes: int | None = None):
    """Starting rows of the table: column f's uniform in
    +-sqrt(1 / R_f) as a hash of (seed, row, lane)
    (``kv.store.hashed_unit`` times the column's bound: one rounding); the
    pad row, the dense columns' rows and the rows past the last table
    zero. The bound of a row is found by its table's first row: 26
    compare-and-selects, fused into the one pass that makes the slot."""
    bound = jnp.zeros(rows.shape, jnp.float32)
    first = FIRST_FIELD_ROW
    for r in field_rows:
        bound = jnp.where(rows >= first, jnp.float32(np.sqrt(1.0 / r)), bound)
        first += int(r)
    bound = jnp.where(rows >= first, 0.0, bound)
    keep = live_lanes(bound > 0, emb_dim, lanes)
    return jnp.where(keep, hashed_unit(seed, rows, lanes or emb_dim) * bound[:, None], 0.0)


def init_mlps(
    seed: int, emb_dim: int, bot: list[int], top: list[int],
    cross_layers: int = 0, cross_rank: int = 0,
) -> dict:
    """{"bot": layers 13 -> bot..., "top": layers (emb_dim + 351) ->
    top...}, and between them ``"cross"``, ``cross_layers`` low-rank cross
    layers 27 emb_dim wide (which the top MLP then reads), where there are
    any: all drawn from one generator of ``seed``, bottom first."""
    rng = np.random.default_rng(seed)
    out = {"bot": mlp.init_mlp([N_INT, *bot], rng, mlp.xavier_normal)}
    width = interaction_width(emb_dim, cross=cross_layers > 0)
    if cross_layers:
        out["cross"] = mlp.init_cross(width, cross_rank, cross_layers, rng)
    out["top"] = mlp.init_mlp([width, *top], rng, mlp.xavier_normal)
    return out


def dlrm_app(
    updater: Updater, opt, emb_dim: int, mlp_init, emb_init=None,
    hot: tuple[int, ...] | None = None,
) -> StepApp:
    """The app's description for the shared parameter-server step: table
    ``emb`` (``vdim`` ``emb_dim``) under ``updater``, the two MLPs (and the
    cross network between them, where ``mlp_init`` makes one) as the
    replicated dense group ``mlp`` under ``opt``. ``mlp_init()`` makes
    ``{"bot": layers, "top": layers}``; ``emb_init(rows, lanes)`` the
    table's starting ``{"w": ...}`` as the store keeps it (zeros without
    it). ``hot``: the columns' bag sizes, for the multi-hot form."""
    if hot is None:
        grad, logits, check, scopes = _grad, _logits, check_batch, MLP_SCOPES
    else:
        grad, logits = functools.partial(_grad, hot=hot), functools.partial(_logits, hot=hot)
        check, scopes = functools.partial(check_batch, entries=entries_of(hot)), DCN_SCOPES
    return StepApp(
        tables=(Table(TABLE, updater, emb_dim, emb_init),),
        grad=grad,
        logits=logits,
        dense=DenseGroup(DENSE, mlp_init, opt),
        link=jax.nn.sigmoid,
        score=BINARY_SCORES,
        scopes=scopes,
        check_batch=check,
    )


def app_from_config(cfg) -> StepApp:
    """The description from a PSConfig's [dlrm] section: ``updater`` (plain
    SGD, or AdaGrad with ``eps``) at ``eta`` on the summed gradient for the
    table and for the dense group, the rows and the dense group started
    from ``cfg.seed``. The files are ``criteo`` lines in the per-field
    layout and the key space is the pad row, the dense columns' rows and
    the 26 tables (``pod_config`` sets both)."""
    d = cfg.dlrm
    rows_of = tuple(int(r) for r in d.field_rows)
    if len(rows_of) != N_CAT or min(rows_of, default=0) < 1:
        raise ValueError(
            f"app dlrm keeps a table a categorical column: dlrm.field_rows "
            f"names {N_CAT} sizes of at least 1, got {list(d.field_rows)}"
        )
    if len(d.hot) != N_CAT or min(d.hot, default=0) < 1:
        raise ValueError(
            f"dlrm.hot names {N_CAT} bag sizes of at least 1 (all 1: the "
            f"one-hot form), got {list(d.hot)}"
        )
    hot = tuple(int(h) for h in d.hot)
    hot = None if all(h == 1 for h in hot) else hot  # the one-hot form
    fmt = criteo_format(rows_of, d.hot, BAG_SEED)
    if cfg.data.format != fmt or cfg.data.num_keys != num_keys_of(rows_of):
        raise ValueError(
            f"app dlrm reads data.format {fmt!r} into data.num_keys = 1 + "
            f"{N_INT} + sum(dlrm.field_rows) = {num_keys_of(rows_of)} rows; the "
            f"config says {cfg.data.format!r} and {cfg.data.num_keys} "
            "(models.dlrm.pod_config fills both in)"
        )
    if cfg.data.max_nnz_per_example < entries_of(d.hot):
        raise ValueError(
            f"an example carries {entries_of(d.hot)} entries; data.max_nnz_per_example "
            f"is {cfg.data.max_nnz_per_example}"
        )
    if not d.bot or d.bot[-1] != d.emb_dim or not d.top or d.top[-1] != 1:
        raise ValueError(
            f"the bottom MLP ends {d.emb_dim} wide (dlrm.emb_dim: its output "
            f"is one of the interaction's vectors) and the top MLP in one "
            f"logit; got bot {list(d.bot)}, top {list(d.top)}"
        )
    if d.cross_layers < 0 or (d.cross_layers and d.cross_rank < 1):
        raise ValueError(
            f"dlrm.cross_layers is a count (0: the pairwise dots) of cross layers of "
            f"dlrm.cross_rank >= 1; got {d.cross_layers} and {d.cross_rank}"
        )
    if d.updater == "sgd":
        updater, opt = Sgd(eta=d.eta), optax.sgd(d.eta)
    elif d.updater == "adagrad":
        updater, opt = Adagrad(eta=d.eta, eps=d.eps), dense_adagrad(d.eta, d.eps)
    else:
        raise ValueError(f"dlrm.updater is 'sgd' or 'adagrad', got {d.updater!r}")
    return dlrm_app(
        updater, opt, d.emb_dim,
        mlp_init=lambda: init_mlps(
            cfg.seed, d.emb_dim, list(d.bot), list(d.top), d.cross_layers, d.cross_rank
        ),
        emb_init=lambda rows, lanes: {"w": init_rows(
            cfg.seed, jnp.arange(rows, dtype=jnp.int32), d.emb_dim, rows_of, lanes
        )},
        hot=hot,
    )


def pod_config(cfg):
    """A copy of ``cfg`` with [dlrm]'s settings where the shared loop reads
    them: ``criteo`` files in the per-field layout (the format names the
    26 sizes, and the bag sizes and their seed in the multi-hot form;
    ``data.reader.ingest_of`` keys it by identity), the key space's size."""
    cfg = copy.deepcopy(cfg)
    cfg.app = "dlrm"
    cfg.data.format = criteo_format(cfg.dlrm.field_rows, cfg.dlrm.hot, BAG_SEED)
    cfg.data.num_keys = num_keys_of(cfg.dlrm.field_rows)
    return cfg


def dump_model(trainer, path: str) -> str:
    """Inference weights as one npz: the table (``emb_w``, by table row)
    and the dense group's layers (``bot_W0``, ``bot_b0``, ..., ``top_W0``,
    ...; ``cross_V0``, ``cross_W0``, ``cross_b0``, ... where there is a
    cross network)."""
    host = {"emb_w": trainer.full_weights(TABLE)}
    for name, layers in trainer.dense()[0].items():
        for i, layer in enumerate(layers):
            for k, v in layer.items():
                host[f"{name}_{k}{i}"] = np.asarray(v)
    np.savez(path, **host)
    return path
