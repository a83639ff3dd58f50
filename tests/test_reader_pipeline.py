"""``iter(reader)`` as two stages on two threads of the reader's own: the
batches and their order are those of ``parsed()`` built one by one, the
stages overlap, an error of either reaches the caller once, and neither
thread outlives the iteration, finished, failed or abandoned."""

import itertools
import shutil
import sys
import threading
import time

import numpy as np
import pytest

from parameter_server_tpu.data import native
from parameter_server_tpu.data.batch import BatchBuilder, eval_builder
from parameter_server_tpu.data.reader import MinibatchReader
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.parallel.trainer import PodTrainer
from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter

NUM_KEYS = 1 << 10
FIELDS = ("unique_keys", "local_ids", "row_ids", "values", "labels", "example_mask", "row_splits")
BACKENDS = ["native", "python"]


def _files(tmp_path, sizes=(200, 130, 77), nnz: int = 8) -> list:
    """Files whose batches of 64 straddle their borders and end in a
    partial one (407 examples: six batches and 23 rows)."""
    paths = []
    for i, n in enumerate(sizes):
        labels, keys, vals, _ = make_sparse_logistic(n, 300, nnz_per_example=nnz, noise=0.3, seed=5 + i)
        paths.append(str(tmp_path / f"part{i}.svm"))
        write_libsvm(paths[-1], labels, keys, vals)
    return paths


def _reader(files, backend="native", min_count=0, batch_size=64, prefetch=4) -> MinibatchReader:
    builder = BatchBuilder(
        num_keys=NUM_KEYS, batch_size=batch_size, max_nnz_per_example=16, freq_min_count=min_count,
    )
    return MinibatchReader(files, "libsvm", builder, prefetch=prefetch, backend=backend)


_before: set = set()


@pytest.fixture(autouse=True)
def _only_this_tests_threads():
    """A reader thread that an earlier test of the worker left to the
    garbage collector is not this test's."""
    _before.clear()
    _before.update(_reader_threads())
    yield
    _before.clear()


def _reader_threads() -> list:
    return [t for t in threading.enumerate() if t.name.startswith("ps-reader") and t not in _before]


def _none_left(within: float = 5.0) -> bool:
    """No reader thread of either stage is alive, at the latest ``within``
    seconds from now."""
    deadline = time.monotonic() + within
    while _reader_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    return not _reader_threads()


def _same(a, b) -> None:
    for field in FIELDS:
        np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
    assert (a.num_examples, a.num_unique, a.num_entries) == (b.num_examples, b.num_unique, b.num_entries)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("min_count", [0, 2])
def test_the_batches_are_the_parsed_pieces_built_in_order(tmp_path, backend, min_count):
    """A frequency filter admits by the order of the builds: one build
    thread that takes the pieces in the parse's order keeps it."""
    files = _files(tmp_path)
    own = list(_reader(files, backend, min_count))
    split = _reader(files, backend, min_count)
    built = [split.build(p) for p in split.parsed()]
    assert [b.num_examples for b in own] == [64] * 6 + [23]
    assert len(built) == len(own)
    for a, b in zip(own, built):
        _same(a, b)
    assert _none_left(0.0)  # an exhausted iteration has joined both threads


def test_the_two_stages_run_side_by_side_on_two_named_threads(tmp_path):
    """Several 2 MiB chunks: while the parse thread is in a later chunk the
    build thread is in the batches of the one before."""
    files = _files(tmp_path, sizes=(60_000,), nnz=12)  # about 7 MB of text
    t = trace.configure(str(tmp_path / "spans"), process_name="pipeline-test")
    try:
        it = iter(_reader(files, batch_size=1024))
        first = next(it)
        names = sorted(th.name for th in _reader_threads())
        n = first.num_examples + sum(b.num_examples for b in it)
        evs = t.events()
    finally:
        trace.configure(None)
    assert n == 60_000
    assert names == ["ps-reader-build", "ps-reader-parse"]
    spans = {
        name: [(e["tid"], e["ts"], e["ts"] + e["dur"]) for e in evs if e["name"] == name]
        for name in ("reader.parse", "reader.build")
    }
    assert len(spans["reader.parse"]) >= 4 and len(spans["reader.build"]) == 59
    (parse_tid,) = {tid for tid, _, _ in spans["reader.parse"]}
    (build_tid,) = {tid for tid, _, _ in spans["reader.build"]}
    assert parse_tid != build_tid
    together = sum(
        max(0.0, min(pe, be) - max(ps, bs))
        for _, ps, pe in spans["reader.parse"] for _, bs, be in spans["reader.build"]
    )
    assert together > 0


def _failing_build(reader: MinibatchReader, at: int) -> None:
    """The ``at``-th build of ``reader`` (counted from 0) raises."""
    calls = itertools.count()
    real = reader.builder.build_flat if reader.use_native else reader.builder.build
    attr = "build_flat" if reader.use_native else "build"

    def build(*rows):
        if next(calls) == at:
            raise ValueError("bad batch")
        return real(*rows)

    setattr(reader.builder, attr, build)


def _failing_parse(reader: MinibatchReader, at: int) -> None:
    """The parse of ``reader`` raises in place of its ``at``-th piece."""
    pieces = reader._pieces

    def failing():
        for i, piece in enumerate(pieces()):
            if i == at:
                raise OSError("bad file")
            yield piece

    reader._pieces = failing


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("stage,error", [("build", ValueError), ("parse", OSError)])
def test_an_error_of_either_stage_reaches_the_caller_once(tmp_path, backend, stage, error):
    reader = _reader(_files(tmp_path), backend)
    (_failing_build if stage == "build" else _failing_parse)(reader, 3)
    it, got = iter(reader), []
    with pytest.raises(error, match="bad"):
        for batch in it:
            got.append(batch)
    # at the batch where it happened, behind the batches made before it
    assert [b.num_examples for b in got] == [64] * 3
    assert next(it, None) is None  # once: the iteration is over
    assert _none_left(0.0)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("prefetch", [1, 4])
def test_a_caller_that_leaves_takes_both_threads_with_it(tmp_path, backend, prefetch):
    """After one batch of sixteen both stages are ahead, blocked on full
    queues (``prefetch`` 1) or about to be (sixteen, because at ``prefetch``
    4 the two queues, the build and the caller hold ten: of seven the parse
    thread could be through and gone before the caller looks): closing the
    iterator lets them go, closes the parse behind the build, and joins
    them."""
    it = iter(_reader(_files(tmp_path, sizes=(400, 330, 277)), backend, prefetch=prefetch))
    assert next(it).num_examples == 64
    assert sorted(t.name for t in _reader_threads()) == ["ps-reader-build", "ps-reader-parse"]
    it.close()
    assert _none_left(0.0)


def test_a_dropped_slice_or_a_failed_caller_leaves_no_thread(tmp_path):
    files = _files(tmp_path)
    assert len(list(itertools.islice(_reader(files, prefetch=1), 2))) == 2
    assert _none_left()  # the slice's iterator dropped: closed where its last reference went
    with pytest.raises(RuntimeError):
        for _ in _reader(files, prefetch=1):
            raise RuntimeError("the caller's own")
    assert _none_left()


def test_parsed_alone_ends_its_one_thread(tmp_path):
    """A training stream's way in: one parse thread, gone with the iteration."""
    pieces = _reader(_files(tmp_path), prefetch=1).parsed()
    next(pieces)
    assert [t.name for t in _reader_threads()] == ["ps-reader-parse"]
    pieces.close()
    assert _none_left(0.0)


def test_readers_started_and_left_at_every_point_under_fast_switching(tmp_path):
    """More threads than cores, the interpreter switching every few
    microseconds: iterations exhausted and abandoned after every count of
    batches give the same batches and leave no thread."""
    files = _files(tmp_path)
    want = list(_reader(files))
    failures: list = []

    def run(k: int) -> None:
        try:
            for take in range(len(want) + 1):
                it = iter(_reader(files, prefetch=1 + k % 3))
                got = list(itertools.islice(it, take))
                it.close()
                for a, b in zip(got, want):
                    _same(a, b)
                assert len(got) == take
        except BaseException as e:  # read below, on the test's thread
            failures.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=run, args=(k,)) for k in range(12)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers) and not failures, failures
    assert _none_left(0.0)


def test_evaluate_files_scores_what_the_built_pieces_score(tmp_path):
    """The evaluator's scores over files are those of the same batches in
    the same order, to the bit: what one thread doing both stages gave."""
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 12
    cfg.solver.minibatch = 64
    cfg.solver.epochs = 1
    cfg.parallel.data_shards = 2
    cfg.parallel.kv_shards = 2
    t = PodTrainer(cfg, reporter=ProgressReporter(print_fn=lambda *_: None))
    files = _files(tmp_path)
    t.train_files(files, report_every=100)
    reader = MinibatchReader(files, "libsvm", eval_builder(cfg, "hash"))
    one_by_one = [reader.build(p) for p in reader.parsed()]
    ys, ps = t.predict_batches(one_by_one)
    ev = t.evaluate_files(files)
    assert ev == t.evaluate_batches(one_by_one) and ev["examples"] == 407 == len(ys)
    ys2, ps2 = t.predict_batches(MinibatchReader(files, "libsvm", eval_builder(cfg, "hash")))
    np.testing.assert_array_equal(ys, ys2)
    np.testing.assert_array_equal(ps, ps2)
    assert _none_left(0.0)


def test_the_session_parses_with_the_built_library():
    """``conftest.pytest_configure`` builds ``native/libpsdata.so`` once,
    before any worker imports it: where there is a compiler no test of the
    session falls back to the Python parsers for a library half written by
    another worker."""
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no compiler here: the Python parsers are the session's")
    assert native.native_available()
