"""ctypes bindings for the native (C++) text parsers.

Reference analog: src/data/text_parser.cc — the reference's parsing is
C++; this keeps the rebuild's ingest hot path native too. The extension is
built on demand with ``make`` (g++) from ``native/parser.cpp``; if the
build fails, its stderr is printed and callers fall back to the Python
parsers in data/libsvm.py, which produce identical rows.

Chunked protocol: files are read in ~2 MiB chunks cut at line boundaries
(measured-best: chunk + its parsed outputs stay LLC-resident — 2 MiB runs
~1.2x faster than 8 MiB and ~2.4x faster than 32 MiB on the dev box);
each chunk is parsed in one C call into flat CSR arrays (labels,
row_splits, keys, vals, slots). The hot path is copy-free end to end:
readinto a reusable padded bytearray, AVX2 counts size the output arrays
exactly, and the C parser writes them directly (measured ~370 MB/s per
stream through this wrapper vs ~520 raw C on the 1-core dev box; the
pre-rewrite wrapper delivered ~210)."""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from collections.abc import Iterator
from pathlib import Path

import numpy as np

from parameter_server_tpu.data.libsvm import N_CAT, N_INT, CriteoBags, split_format

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_ENV = "PS_TPU_NATIVE_LIB"

FlatRows = tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, "np.ndarray | None"]
# (labels (R,), row_splits (R+1,), keys (N,), vals (N,), slots (N,) or
#  None for SLOTLESS_FORMATS — all slot ids are 0 there)

# Formats with a native fast path; the single source of truth for the
# reader's backend="auto" choice and parse_chunk dispatch.
NATIVE_FORMATS = {
    "libsvm": "ps_parse_libsvm",
    "criteo": "ps_parse_criteo",
    "adfea": "ps_parse_adfea",
    # read as "rating:<num_items>" (data.libsvm.split_format): the C parser
    # takes the item count as a thirteenth argument
    "rating": "ps_parse_rating",
    # "sgns:<vocab_size>", the same way
    "sgns": "ps_parse_sgns",
}
# "criteo:<26 table sizes>" (the per-field layout): the sizes as a
# thirteenth argument, a pointer to 26 uint64
_CRITEO_FIELDS = "ps_parse_criteo_fields"
# "criteo:<26 table sizes>:<26 bag sizes>:<seed>" (the multi-hot form): the
# bag sizes and the seed behind the table sizes
_CRITEO_BAGS = "ps_parse_criteo_bags"


def has_native(fmt: str) -> bool:
    """Whether ``fmt`` (a name, or "rating:<num_items>") has a C parser."""
    return fmt.partition(":")[0] in NATIVE_FORMATS

_lib: ctypes.CDLL | None = None
_lib_tried = False


def _build() -> Path | None:
    """Build ``libpsdata.so`` from ``parser.cpp`` with ``make`` unless an
    up-to-date one is already there. A failed build returns None — callers
    with ``backend="auto"`` then use the Python parsers — but never
    quietly: the compiler's stderr goes to this process's stderr."""
    so = _NATIVE_DIR / "libpsdata.so"
    src = _NATIVE_DIR / "parser.cpp"
    if not src.exists():  # deployed artifact without sources: use as-is
        return so if so.exists() else None
    if so.exists() and so.stat().st_mtime >= src.stat().st_mtime:
        return so
    try:
        subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            check=True,
            capture_output=True,
            text=True,
            timeout=120,
        )
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", None) or ""
        print(
            f"[native] building {so} failed ({e}); falling back to the "
            f"Python parsers\n{detail}",
            file=sys.stderr,
            flush=True,
        )
        return None
    return so if so.exists() else None


_load_lock = threading.Lock()


def _tune_malloc() -> None:
    """Raise glibc's mmap threshold so the multi-MB per-chunk output
    arrays are served from the (warm, reusable) heap instead of fresh
    mmaps — each fresh mmap pays a page-fault per 4 KiB on first touch,
    measured at ~9% of ingest wall time. Process-wide, so honoring an
    escape hatch; the reference's C++ loaders get the same effect from
    arena reuse."""
    if os.environ.get("PS_TPU_NO_MALLOPT"):
        return
    try:
        libc = ctypes.CDLL(None)
        libc.mallopt(ctypes.c_int(-3), ctypes.c_int(256 << 20))  # M_MMAP_THRESHOLD
    except (OSError, AttributeError):
        pass  # non-glibc platform: harmless to skip


def load_native() -> ctypes.CDLL | None:
    """Load (building if needed) the native parser library, or None. The
    first caller loads it under a lock: reader threads that start together
    (a cache build's pool) would otherwise see "tried, none" while the first
    of them is still building, and parse in Python."""
    if _lib is not None:
        return _lib
    with _load_lock:
        return _load_native_locked()


def _load_native_locked() -> ctypes.CDLL | None:
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    _tune_malloc()
    path = os.environ.get(_LIB_ENV)
    so = Path(path) if path else _build()
    if so is None or not Path(so).exists():
        return None
    try:
        lib = ctypes.CDLL(str(so))
    except OSError:
        return None
    i64, u64p = ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64)
    f32p, i64p = ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64)
    # what a parser takes behind the twelve arguments they all share
    sized = {
        NATIVE_FORMATS["rating"]: [ctypes.c_uint64],  # num_items
        NATIVE_FORMATS["sgns"]: [ctypes.c_uint64],  # vocab_size
        _CRITEO_FIELDS: [u64p],  # the 26 table sizes
        _CRITEO_BAGS: [u64p, u64p, ctypes.c_uint64],  # ..., the 26 bag sizes, the seed
    }
    for fn in [*NATIVE_FORMATS.values(), _CRITEO_FIELDS, _CRITEO_BAGS]:
        f = getattr(lib, fn, None)
        if f is None:
            continue  # older prebuilt artifact: _parse_region says so
        f.restype = ctypes.c_int
        f.argtypes = [
            ctypes.c_char_p, i64,  # buf, len
            i64, i64,  # max_rows, max_nnz
            f32p, i64p,  # labels, row_splits
            u64p, f32p, u64p,  # keys, vals, slots
            i64p, i64p, i64p,  # out_rows, out_nnz, err_line
            *sized.get(fn, []),
        ]
    try:
        c4 = lib.ps_count4
        c4.restype = None
        c4.argtypes = [
            ctypes.c_char_p, i64,
            ctypes.c_byte, ctypes.c_byte, ctypes.c_byte, ctypes.c_byte,
            i64p,
        ]
    except AttributeError:
        pass  # older prebuilt artifact: _counts falls back to bytes.count
    try:
        hl = lib.ps_hash_localize
    except AttributeError:
        hl = None  # older prebuilt artifact without the kernel
    if hl is not None:
        hl.restype = ctypes.c_int
        hl.argtypes = [
            u64p, u64p, i64,  # raw keys, slots (or None), n
            ctypes.c_uint64, ctypes.c_int,  # num_keys, identity flag
            i64p, ctypes.POINTER(ctypes.c_int32), i64p,  # unique, inverse, n_uniq
        ]
    _lib = lib
    return _lib


def native_available() -> bool:
    return load_native() is not None


def hash_localize(
    raw_keys: np.ndarray,
    slots: np.ndarray | None,
    num_keys: int,
    identity: bool = False,
) -> tuple[np.ndarray, np.ndarray] | None:
    """GIL-free hash + localize (ref: the reference's C++ Localizer): hash
    raw keys into [1, num_keys) (or +1 in identity mode) and return
    (sorted unique gids int64, 0-based inverse int32) — exactly
    ``np.unique(hash_keys(...), return_inverse=True)``. Returns None when
    the kernel is unavailable or inapplicable (no library, num_keys >
    2^32, identity key out of range) — callers fall back to numpy, which
    also reproduces the exact error message for the range case."""
    lib = load_native()
    if lib is None or not hasattr(lib, "ps_hash_localize"):
        return None
    if num_keys < 2:
        return None  # numpy path owns the clean num_keys>=2 ValueError
    raw = np.ascontiguousarray(raw_keys, dtype=np.uint64)
    n = len(raw)
    unique = np.empty(max(n, 1), dtype=np.int64)
    inverse = np.empty(max(n, 1), dtype=np.int32)
    n_uniq = ctypes.c_int64()
    u64p = ctypes.POINTER(ctypes.c_uint64)
    sl = None
    if slots is not None:
        sl = np.ascontiguousarray(slots, dtype=np.uint64)
    rc = lib.ps_hash_localize(
        raw.ctypes.data_as(u64p),
        sl.ctypes.data_as(u64p) if sl is not None else None,
        n,
        ctypes.c_uint64(num_keys),
        1 if identity else 0,
        unique.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        inverse.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(n_uniq),
    )
    if rc == -4:
        raise MemoryError("ps_hash_localize: allocation failed")
    if rc != 0:  # -3 identity range error, -5 num_keys > 2^32
        return None
    u = n_uniq.value
    return unique[:u], inverse[:n]


# Formats whose slot id is constant 0 (libsvm): the slots array is pure
# zeros, so the wrapper returns None instead of copying megabytes of
# zeros per chunk — downstream (BatchBuilder.build_flat) treats None as
# salt 0, which hashes identically.
SLOTLESS_FORMATS = frozenset({"libsvm", "rating", "sgns"})

# readable slack the C parsers may overread past the parse length (the
# AVX2 span parsers issue one unguarded 8-byte load per token)
_PAD = 8


# fourth needle per format for ps_count4 (first three are \n, \r, and the
# format's entry marker); counts[3] refines the entry bound for libsvm
# (space-preceded bare ``k`` entries) and adfea (ws-preceded entries)
_COUNT_NEEDLES = {
    "libsvm": b": ", "criteo": b"\t\0", "adfea": b" \t", "rating": b" \t",
    "sgns": b" \t",
}


def _counts(lib, fmt: str, ba: bytearray, length: int, entries: int = N_INT + N_CAT) -> tuple[int, int]:
    """(rows_cap, nnz_cap): exact row bound from the line-terminator
    count, entry bound from format-specific marker counts — one AVX2
    pass in C (python's bytes.count pays per-occurrence overhead that at
    CTR colon densities costs more than the parse itself). The output
    arrays are then allocated EXACTLY once and written by C directly (no
    scratch, no copy-out — measured, the copy-out pass was the largest
    wrapper cost). libsvm's colon count is exact except for bare ``k``
    entries (implicit 1.0) — those undershoot and take the grow retry in
    _parse_region."""
    c3, c4 = _COUNT_NEEDLES[fmt]
    if hasattr(lib, "ps_count4"):
        out = (ctypes.c_int64 * 4)()
        lib.ps_count4(
            (ctypes.c_char * len(ba)).from_buffer(ba), length,
            0x0A, 0x0D, c3, c4, out,
        )
        out = list(out)
    else:  # older prebuilt artifact
        out = [ba.count(bytes([c]), 0, length) for c in (0x0A, 0x0D, c3, c4)]
    rows_cap = out[0] + out[1] + 1
    if fmt == "libsvm":
        # colons are exact for ``k:v`` entries; bare ``k`` entries carry no
        # colon but are each preceded by >= 1 space, so the space count is
        # the complementary bound — max of the two avoids the grow-retry
        # cliff on colon-free chunks (tab-separated bare keys still
        # undershoot and take the retry, whose jump below is linear)
        nnz_cap = max(out[2], out[3]) + 1
    elif fmt == "criteo":
        # hard bound: <= 39 features a row, <= ``entries`` with bags
        nnz_cap = entries * rows_cap + 1
    elif fmt == "rating":
        nnz_cap = 2 * rows_cap  # exactly two entries a row
    elif fmt == "sgns":
        # a line's first id, and one more behind each ws byte at most
        nnz_cap = out[2] + out[3] + rows_cap
    else:  # adfea: every entry is preceded by at least one ws byte
        nnz_cap = out[2] + out[3] + 1
    return rows_cap, nnz_cap


def _parse_region(fmt: str, ba: bytearray, length: int) -> FlatRows:
    """Parse ba[:length] (complete lines; last byte a line terminator;
    ba must extend >= _PAD bytes past length). The region is passed by
    POINTER — no slice copy — and outputs are written by the C parser
    straight into exactly-sized fresh arrays."""
    lib = load_native()
    if lib is None:
        raise RuntimeError("native parser not available")
    fmt, arg = split_format(fmt)
    if fmt not in NATIVE_FORMATS:
        raise ValueError(f"native parser: unknown format {fmt!r}")
    def u64s(xs):
        return (ctypes.c_uint64 * len(xs))(*xs)

    entries = N_INT + N_CAT
    if arg is None:
        fn_name, extra = NATIVE_FORMATS[fmt], ()
    elif isinstance(arg, CriteoBags):
        fn_name, entries = _CRITEO_BAGS, arg.entries
        extra = (u64s(arg.rows), u64s(arg.hot), ctypes.c_uint64(arg.seed))
    elif isinstance(arg, tuple):
        fn_name, extra = _CRITEO_FIELDS, (u64s(arg),)
    else:
        fn_name, extra = NATIVE_FORMATS[fmt], (ctypes.c_uint64(arg),)
    fn = getattr(lib, fn_name, None)
    if fn is None:
        raise RuntimeError(f"the native library has no {fn_name}")
    rows_cap, nnz_cap = _counts(lib, fmt, ba, length, entries)
    want_slots = fmt not in SLOTLESS_FORMATS
    buf_p = (ctypes.c_char * len(ba)).from_buffer(ba)
    while True:
        labels = np.empty(rows_cap, dtype=np.float32)
        splits = np.empty(rows_cap + 1, dtype=np.int64)
        keys = np.empty(nnz_cap, dtype=np.uint64)
        vals = np.empty(nnz_cap, dtype=np.float32)
        slots = np.empty(nnz_cap, dtype=np.uint64) if want_slots else None
        out_rows = ctypes.c_int64()
        out_nnz = ctypes.c_int64()
        err_line = ctypes.c_int64(-1)
        rc = fn(
            buf_p,
            length,
            rows_cap,
            nnz_cap,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            splits.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            (
                slots.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
                if want_slots
                else None
            ),
            ctypes.byref(out_rows),
            ctypes.byref(out_nnz),
            ctypes.byref(err_line),
            *extra,
        )
        if rc == -1:
            # nnz bound undershoot (bare-key libsvm): rows_cap is exact
            # (newline count), so only the entry bound can overflow. Jump
            # straight to a bytes-per-entry estimate (entries are >= ~6
            # bytes in practice) so a badly-undershot seed converges in
            # one or two retries instead of O(log n) full re-parses. The
            # hard floor is 2 bytes/entry; hitting it twice means the C
            # side's capacity accounting is broken — raise, don't spin
            new_cap = min(max(2 * nnz_cap + 64, length // 6), length // 2 + 1)
            if new_cap == nnz_cap:
                raise RuntimeError(
                    "native parser capacity overflow (internal bug)"
                )
            nnz_cap = new_cap
            continue
        break
    if rc == -2:
        raise ValueError(f"parse error at line {err_line.value} of chunk ({fmt})")
    if rc != 0:
        raise RuntimeError(f"native parser failed (rc={rc}, fmt={fmt})")
    r, n = out_rows.value, out_nnz.value
    # views, not copies: the arrays are freshly allocated per call and
    # exactly sized up to blank-line slack
    return (
        labels[:r],
        splits[: r + 1],
        keys[:n],
        vals[:n],
        slots[:n] if want_slots else None,
    )


def parse_chunk(fmt: str, chunk: bytes, max_rows_hint: int = 0) -> FlatRows:
    """Parse a buffer of complete lines via the C parser. ``slots`` in the
    returned tuple is None for SLOTLESS_FORMATS. (max_rows_hint is
    retained for API compatibility; capacities are exact now.)"""
    del max_rows_hint
    length = len(chunk)
    ba = bytearray(length + 1 + _PAD)
    ba[:length] = chunk
    if length == 0 or chunk[-1:] not in (b"\n", b"\r"):
        ba[length] = 0x0A  # the C parsers require closed lines
        length += 1
    return _parse_region(fmt, ba, length)


def iter_chunks(
    path: str | Path, fmt: str, chunk_bytes: int = 2 << 20
) -> Iterator[FlatRows]:
    """Stream a text file (optionally .gz) through the native parser.

    Zero-copy streaming: one reusable bytearray holds [carried tail |
    fresh read | pad]; reads land via readinto, the parsed region is
    passed to C by pointer, and only the sub-line tail is memmoved to the
    front between chunks — the old bytes-concatenate + slice path copied
    every byte twice per chunk."""
    import gzip

    p = Path(path)
    opener = gzip.open if p.suffix == ".gz" else open
    with opener(p, "rb") as f:
        cap = chunk_bytes + (chunk_bytes >> 2) + _PAD
        ba = bytearray(cap)
        mv = memoryview(ba)
        tail = 0
        while True:
            if tail + _PAD + 1 >= cap:  # single line longer than the buffer
                cap *= 2
                nba = bytearray(cap)
                nba[:tail] = mv[:tail]
                ba, mv = nba, memoryview(nba)
            # reserve _PAD + 1 bytes past the read: the EOF branch may
            # append a closing 0x0A, and the appended terminator must
            # still leave the full _PAD slack _parse_region documents
            n = f.readinto(mv[tail : cap - _PAD - 1])
            total = tail + (n or 0)
            if not n:
                if total and bytes(mv[:total]).strip():
                    if ba[total - 1] not in (0x0A, 0x0D):
                        ba[total] = 0x0A
                        total += 1
                    yield _parse_region(fmt, ba, total)
                return
            # cut at the last newline of either convention so CR-terminated
            # files stream in chunks instead of accumulating to EOF; a chunk
            # ending exactly at '\r' stays in the tail — the next read may
            # begin with '\n' (a CRLF split across chunk boundaries)
            stop = total - 1 if ba[total - 1] == 0x0D else total
            cut = max(ba.rfind(b"\n", 0, stop), ba.rfind(b"\r", 0, stop))
            if cut < 0:
                tail = total
                continue
            yield _parse_region(fmt, ba, cut + 1)
            rest = total - (cut + 1)
            if 0 < rest <= cut + 1:  # disjoint ranges: plain slice copy
                mv[:rest] = mv[cut + 1 : total]
            elif rest:  # tail longer than the parsed prefix (huge line):
                # materialize first — overlapping memoryview assignment is
                # memcpy underneath, and overlap direction is unspecified
                mv[:rest] = bytes(mv[cut + 1 : total])
            tail = rest
