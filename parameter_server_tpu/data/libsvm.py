"""libsvm / criteo text parsers — Python reference implementations.

Reference analog: src/data/text_parser.cc (libsvm, criteo, adfea formats,
slot-aware). The C++ fast path lives in native/parser.cpp and must produce
bit-identical output (same hashing; see utils.hashing). This module is the
correctness reference and the fallback when the extension isn't built.
"""

from __future__ import annotations

import gzip
from collections.abc import Iterator
from dataclasses import dataclass
from pathlib import Path

import numpy as np

Row = tuple[float, np.ndarray, np.ndarray, np.ndarray]  # label, keys, vals, slots


def _open(path: str | Path):
    p = Path(path)
    if p.suffix == ".gz":
        return gzip.open(p, "rt")
    return p.open("r")


def iter_libsvm(path: str | Path) -> Iterator[Row]:
    """Parse ``label idx:val idx:val ...``; labels -1/0/+1 -> 0/1.

    Ref: ParseLibsvm in src/data/text_parser.cc. Slot id is 0 for all
    features (libsvm has no feature groups).
    """
    with _open(path) as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            label = 1.0 if float(parts[0]) > 0 else 0.0
            n = len(parts) - 1
            keys = np.empty(n, dtype=np.uint64)
            vals = np.empty(n, dtype=np.float32)
            for i, tok in enumerate(parts[1:]):
                k, _, v = tok.partition(":")
                keys[i] = int(k)
                vals[i] = float(v) if v else 1.0
            yield label, keys, vals, np.zeros(n, dtype=np.uint64)


N_INT, N_CAT = 13, 26  # the criteo format's integer and categorical columns

_U64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """``utils.hashing.splitmix64`` of one Python integer, modulo 2^64."""
    z = (x + 0x9E3779B97F4A7C15) & _U64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _U64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _U64
    return z ^ (z >> 31)


def bag_draw(seed: int, f: int, r: int, j: int) -> int:
    """``u(seed, f, r, j)``: the 64-bit draw behind place ``j >= 1`` of the
    bag that id ``r`` of categorical column ``f`` (0-based) stands for in
    the multi-hot criteo format:

        u = splitmix64(splitmix64(splitmix64(seed + f * 2^32) ^ r) + j)

    every sum modulo 2^64, ``splitmix64`` the finalizer of
    ``utils.hashing`` (it adds 0x9E3779B97F4A7C15 first). The bag of
    ``r`` is ``[r, u(.., 1) mod R_f, ..., u(.., h_f - 1) mod R_f]``: a
    fixed, uniform bag an id, a function of ``(seed, f, r)`` alone."""
    return _splitmix64((_splitmix64(_splitmix64((seed + (f << 32)) & _U64) ^ r) + j) & _U64)


@dataclass(frozen=True)
class CriteoBags:
    """What "criteo:<26 sizes>:<26 bag sizes>:<seed>" names: the per-field
    layout's table sizes, each column's bag size and the seed of the
    bags' draws (``bag_draw``)."""

    rows: tuple[int, ...]
    hot: tuple[int, ...]
    seed: int

    @property
    def entries(self) -> int:
        """Entries an example with every field present carries."""
        return N_INT + sum(self.hot)


def iter_criteo(
    path: str | Path,
    field_rows: "tuple[int, ...] | CriteoBags | None" = None,
) -> Iterator[Row]:
    """Parse Criteo CTR TSV: label, 13 integer slots, 26 categorical slots.

    Ref: ParseCriteo in src/data/text_parser.cc. Integer slot j becomes key
    ``raw value`` in slot j+1; categorical slot j becomes its hex id in slot
    j+14 — the slot salt keeps columns decorrelated in the hashed space.
    Missing fields are skipped (reference behavior).

    ``field_rows`` (the format "criteo:<26 sizes>", ``criteo_format``)
    keeps a table a categorical column instead, the 26 one behind the
    other in one id space behind the integer columns' 13 keys: column j's
    key is ``13 + off_j + id mod field_rows[j]``, ``off_j`` the rows of the
    columns before it, so that identity keying (+1 for the pad row) puts
    integer column j at table row 1 + j and column j's value at row
    ``14 + off_j + id mod field_rows[j]``: no two columns share a row.

    A ``CriteoBags`` (the multi-hot format, ``criteo_format`` with bag
    sizes) turns column j's id into a bag of ``hot[j]`` entries of that
    column, in this order: ``r = id mod R_j`` itself, then ``bag_draw(seed,
    j, r, k) mod R_j`` for k = 1..hot[j] - 1, each keyed as above. A row may
    come twice in a bag; it is then two entries. An example with every
    field present carries 13 + sum(hot) entries, and an entry's position
    says its column and its place in the bag.
    """
    first, bags = None, None
    if isinstance(field_rows, CriteoBags):
        bags, field_rows = field_rows, field_rows.rows
    if field_rows is not None:
        first = [N_INT + sum(field_rows[:j]) for j in range(N_CAT)]
    with _open(path) as f:
        for line in f:
            cols = line.rstrip("\n").split("\t")
            if len(cols) < 40:
                continue
            label = 1.0 if cols[0] == "1" else 0.0
            keys, vals, slots = [], [], []
            for j in range(13):  # integer features: log-ish value encoding
                c = cols[1 + j]
                try:
                    x = int(c)
                except ValueError:
                    continue  # malformed fields are skipped (ref behavior)
                keys.append(j)  # one weight per integer column...
                vals.append(np.sign(x) * np.log1p(abs(x)))  # ...scaled by value
                slots.append(j + 1)
            for j in range(26):  # categorical: one-hot by hashed id
                c = cols[14 + j]
                if c == "":
                    continue
                try:
                    k = int(c, 16)
                except ValueError:
                    continue
                if first is not None:
                    r = k % field_rows[j]
                    k = first[j] + r
                keys.append(k)
                vals.append(1.0)
                slots.append(j + 14)
                if bags is not None:
                    for place in range(1, bags.hot[j]):
                        keys.append(first[j] + bag_draw(bags.seed, j, r, place) % field_rows[j])
                        vals.append(1.0)
                        slots.append(j + 14)
            yield (
                label,
                np.array(keys, dtype=np.uint64),
                np.array(vals, dtype=np.float32),
                np.array(slots, dtype=np.uint64),
            )


def iter_adfea(path: str | Path) -> Iterator[Row]:
    """Parse the adfea ad-feature format: ``line_id label fea:grp fea:grp ...``.

    Ref: ParseAdfea in src/data/text_parser.cc. Each token after the line id
    and click label is ``feature_id:group_id``; the group id is the slot
    (feature group) and the value is implicitly 1.0 (pure one-hot ad
    features). A token without ``:`` gets slot 0. The leading line id is
    metadata and is dropped.
    """
    with _open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) < 2:
                continue
            label = 1.0 if float(parts[1]) > 0 else 0.0
            n = len(parts) - 2
            keys = np.empty(n, dtype=np.uint64)
            slots = np.zeros(n, dtype=np.uint64)
            for i, tok in enumerate(parts[2:]):
                k, _, g = tok.partition(":")
                keys[i] = int(k)
                if g:
                    slots[i] = int(g)
            yield label, keys, np.ones(n, dtype=np.float32), slots


def iter_rating(path: str | Path, num_items: int) -> Iterator[Row]:
    """Parse ``user item rating [more...]`` lines, the matrix-factorization
    app's triples (ids from 0; what follows the rating, a timestamp say, is
    dropped). The label is the rating as read - the one format whose label
    is real-valued - and a row holds two entries of value 1 in one id
    space, the item first: key ``item`` and key ``num_items + user``, so
    that identity keying (+1 for the pad row) puts items at table rows
    1..num_items and users behind them. A line that does not start with
    two unsigned integers and a number, or an item id at or past
    ``num_items`` (it would name a user's row), raises ValueError."""
    with _open(path) as f:
        for n, line in enumerate(f):
            parts = line.split()
            if not parts:
                continue
            try:
                user, item, rating = int(parts[0]), int(parts[1]), float(parts[2])
                ok = user >= 0 and 0 <= item < num_items
            except (ValueError, IndexError):
                ok = False
            if not ok:
                raise ValueError(f"parse error at line {n} of {path} (rating)")
            yield (
                rating,
                np.array([item, num_items + user], dtype=np.uint64),
                np.ones(2, dtype=np.float32),
                np.zeros(2, dtype=np.uint64),
            )


def iter_sgns(path: str | Path, vocab_size: int) -> Iterator[Row]:
    """Parse ``centre context neg_1 ... neg_k`` lines of word ids from 0,
    the skip-gram app's examples with their negatives drawn. No label and
    no values are written: the label is 1.0 and every entry's value 1.0. A
    row holds one entry a word IN THE LINE'S ORDER (the app reads an
    entry's role off its position): key ``centre`` for the first, key
    ``vocab_size + word`` for every other, so that identity keying (+1 for
    the pad row) puts the input vectors at table rows 1..V and the output
    vectors behind them. Fewer than three ids on a line, a token that is no
    unsigned integer, or an id at or past ``vocab_size`` raises
    ValueError."""
    with _open(path) as f:
        for n, line in enumerate(f):
            # tokens between spaces and tabs, as the C parser splits them
            parts = line.rstrip("\r\n").replace("\t", " ").split(" ")
            parts = [t for t in parts if t]
            if not parts:
                continue
            ok = len(parts) >= 3 and all(
                t.isascii() and t.isdigit() and len(t) <= 18 for t in parts
            )
            ids = [int(t) for t in parts] if ok else []
            ok = ok and max(ids) < vocab_size
            if not ok:
                raise ValueError(f"parse error at line {n} of {path} (sgns)")
            keys = np.array(ids, dtype=np.uint64)
            keys[1:] += np.uint64(vocab_size)
            yield (
                1.0, keys, np.ones(len(ids), dtype=np.float32),
                np.zeros(len(ids), dtype=np.uint64),
            )


FORMATS = {"libsvm": iter_libsvm, "criteo": iter_criteo, "adfea": iter_adfea}

# Two formats carry ids of a dense space (``BatchBuilder``'s key_mode
# "identity"), not features to hash, and are read with the size of the
# first id range behind the name, because the second range's keys lie
# behind it: ``user item rating`` lines as "rating:<num_items>"
# (``rating_format``), ``centre context negatives...`` lines as
# "sgns:<vocab_size>" (``sgns_format``). The criteo format becomes a third
# when it is read with the 26 columns' table sizes behind its name,
# "criteo:<rows of column 1>,...,<rows of column 26>" (``criteo_format``;
# ``iter_criteo``'s per-field layout); bare, it is hashed as ever. With the
# 26 columns' bag sizes and a seed behind the sizes,
# "criteo:<26 sizes>:<26 bag sizes>:<seed>", every id stands for a fixed
# bag of rows of its column's table (``CriteoBags``; the multi-hot form).
RATING = "rating"
SGNS = "sgns"
CRITEO = "criteo"
_SIZED = {RATING: ("num_items", iter_rating), SGNS: ("vocab_size", iter_sgns)}


def rating_format(num_items: int) -> str:
    return f"{RATING}:{int(num_items)}"


def sgns_format(vocab_size: int) -> str:
    return f"{SGNS}:{int(vocab_size)}"


def criteo_format(field_rows, hot=None, seed: int = 0) -> str:
    """The per-field format's name: the one-hot form where ``hot`` is
    unsaid or 1 for every column, else the multi-hot form."""
    fmt = f"{CRITEO}:" + ",".join(str(int(r)) for r in field_rows)
    if hot is None or all(int(h) == 1 for h in hot):
        return fmt
    return fmt + ":" + ",".join(str(int(h)) for h in hot) + f":{int(seed)}"


def _sizes(text: str) -> "tuple[int, ...] | None":
    sizes = text.split(",")
    if len(sizes) != N_CAT or not all(r.isdigit() and int(r) > 0 for r in sizes):
        return None
    return tuple(int(r) for r in sizes)


def split_format(fmt: str) -> tuple[str, "int | tuple[int, ...] | CriteoBags | None"]:
    """(format name, its parameter or None): ("rating", 39780) of
    "rating:39780", ("criteo", None) of "criteo", ("criteo", (r_1, ...,
    r_26)) of "criteo:r_1,...,r_26", ("criteo", CriteoBags) of
    "criteo:r_1,...,r_26:h_1,...,h_26:seed"."""
    name, _, arg = fmt.partition(":")
    if name == CRITEO and arg:
        rows, colon, bags = arg.partition(":")
        sizes = _sizes(rows)
        if sizes is None:
            raise ValueError(
                f"the per-field criteo format is read as '{CRITEO}:<{N_CAT} "
                f"table sizes, comma-separated>' (data.libsvm.criteo_format), "
                f"got {fmt!r}"
            )
        if not colon:
            return name, sizes
        hot, _, seed = bags.partition(":")
        if _sizes(hot) is None or not seed.isdigit() or int(seed) > _U64:
            raise ValueError(
                f"the multi-hot criteo format is read as '{CRITEO}:<{N_CAT} table "
                f"sizes>:<{N_CAT} bag sizes>:<seed>' (data.libsvm.criteo_format), "
                f"got {fmt!r}"
            )
        return name, CriteoBags(sizes, _sizes(hot), int(seed))
    if name in _SIZED:
        if not arg.isdigit():
            raise ValueError(
                f"the {name!r} format is read as '{name}:<{_SIZED[name][0]}>' "
                f"(data.libsvm.{name}_format), got {fmt!r}"
            )
        return name, int(arg)
    return fmt, None


def iter_format(fmt: str, path: str | Path) -> Iterator[Row]:
    name, arg = split_format(fmt)
    if name in _SIZED:
        return _SIZED[name][1](path, arg)
    if name == CRITEO and arg is not None:
        return iter_criteo(path, arg)
    if name not in FORMATS:
        raise ValueError(
            f"unknown data format {fmt!r}; known: {sorted([*FORMATS, *_SIZED])}"
        )
    return FORMATS[name](path)
