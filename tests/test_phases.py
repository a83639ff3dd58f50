"""Phase names: ``ps.*`` scopes in the step and predict programs and what
``op_scopes()`` reads back from them; ``trace.phase`` in its three planes;
the timers the reader's thread, the feed, the trainer and the evaluator
leave behind."""

import contextlib
import glob
import hashlib
import os
import resource
import sys
import threading
import time

import jax
import numpy as np
import pytest

from parameter_server_tpu.data.batch import BUCKET_FLOOR, BatchBuilder, eval_builder
from parameter_server_tpu.data.pipeline import PrefetchPipeline
from parameter_server_tpu.data.reader import MinibatchReader
from parameter_server_tpu.data.synthetic import make_sparse_logistic, write_libsvm
from parameter_server_tpu.kv import store
from parameter_server_tpu.models.linear import updater_from_config
from parameter_server_tpu.parallel import spmd
from parameter_server_tpu.parallel.mesh import make_mesh
from parameter_server_tpu.parallel.trainer import PodTrainer
from parameter_server_tpu.utils import trace
from parameter_server_tpu.utils.config import PSConfig
from parameter_server_tpu.utils.metrics import ProgressReporter, Timer, timers

PHASES = ("ps.row_ids", "ps.pull", "ps.grad", "ps.push/scatter")
NUM_KEYS, B, NNZ, U = 1 << 10, 16, 64, 65


def _count(name: str) -> int:
    return timers.snapshot().get(name, {"count": 0})["count"]


def _total(name: str) -> float:
    return timers.snapshot().get(name, {"total_s": 0.0})["total_s"]


def _batch(data: int, lead: tuple = ()) -> dict:
    """Compact-wire batch fields, (data, *lead, ...): every example has
    NNZ // B entries, keys below NUM_KEYS."""
    rng = np.random.default_rng(3)
    one = {
        "unique_keys": rng.permutation(NUM_KEYS)[:U].astype(np.int32),
        "local_ids": rng.integers(0, U, NNZ).astype(np.int32),
        "row_splits": (np.arange(B + 1) * (NNZ // B)).astype(np.int32),
        "values": rng.normal(size=NNZ).astype(np.float32),
        "labels": rng.integers(0, 2, B).astype(np.float32),
        "example_mask": np.ones(B, bool),
    }
    return {k: np.broadcast_to(v, (data, *lead, *v.shape)).copy() for k, v in one.items()}


@pytest.fixture
def no_programs(monkeypatch):
    """``op_scopes`` as a fresh process sees it."""
    monkeypatch.setattr(spmd, "_ran", [])


class TestDeviceScopes:
    @pytest.mark.parametrize("program", ["step", "multistep", "predict"])
    def test_program_text_names_the_phases(self, program, no_programs):
        mesh = make_mesh(2, 2)
        updater = updater_from_config(PSConfig())
        state = spmd.shard_state(updater.init(NUM_KEYS, 1), mesh)
        assert spmd.op_scopes() == {}  # nothing has run
        if program == "predict":
            fn = spmd.make_spmd_predict_step(updater, mesh, NUM_KEYS)
            call = lambda st: fn(st, _batch(2))  # noqa: E731
        elif program == "step":
            fn = spmd.make_spmd_train_step(updater, mesh, NUM_KEYS)
            call = lambda st: fn(st, _batch(2), 0)  # noqa: E731
        else:
            fn = spmd.make_spmd_train_multistep(updater, mesh, NUM_KEYS)
            call = lambda st: fn(st, _batch(2, (3,)), 0)  # noqa: E731
        call(state)
        want = PHASES[:3] if program == "predict" else PHASES
        (ran,) = spmd._ran
        lowered = ran.jitted.lower(*ran.args)
        text = lowered.as_text(debug_info=True)
        for phase in want:
            # the push's scan body is lowered as a function of its own, so
            # its stages stand there without the "ps.push/" before them
            head, _, stage = phase.partition("/")
            assert f'"{head}/' in text and f'"{stage}/' in text, phase
        module, scopes = spmd.hlo_scopes(lowered.compile().as_text())
        assert set(want) <= set(scopes.values())
        assert spmd.op_scopes() == {module: scopes}
        call(spmd.shard_state(updater.init(NUM_KEYS, 1), mesh))  # the train steps donate their state
        assert len(spmd._ran) == 1  # the same shapes again: nothing new to read

    def test_store_pull_and_push_carry_the_same_names(self):
        updater = updater_from_config(PSConfig())
        state = updater.init(NUM_KEYS, 1)
        idx = jax.numpy.arange(8, dtype=jax.numpy.int32)
        grad = jax.numpy.ones((8, 1), jax.numpy.float32)
        _, pull = spmd.hlo_scopes(store.pull.lower(updater, state, idx).compile().as_text())
        _, push = spmd.hlo_scopes(store.push.lower(updater, state, idx, grad).compile().as_text())
        assert "ps.pull" in pull.values()
        assert {"ps.push/gather", "ps.push/update", "ps.push/scatter"} <= set(push.values())

    def test_scope_of_an_op_name(self):
        f = spmd._scope_of
        assert f("jit(_jitted)/while/body/closed_call/ps.push/while/body/closed_call/scatter/scatter-add") == "ps.push/scatter"
        assert f("jit(_jitted)/ps.push/all_gather") == "ps.push"
        assert f("jit(step)/ps.pull/jit(_take)/gather") == "ps.pull"  # the primitive, not the stage
        assert f("jit(step)/ps.push/gather") == "ps.push"  # a primitive named like a stage
        assert f("jit(step)/ps.push/gather/jit(_take)/gather") == "ps.push/gather"
        assert f("jit(_jitted)/while") == "" and f("") == ""

    def test_a_named_table_or_group_is_told_by_the_apps_names(self):
        names = frozenset({"emb", "mlp", "while"})
        f = lambda op_name: spmd._scope_of(op_name, names)  # noqa: E731
        assert f("jit(_jitted)/ps.pull/emb/jit(_take)/gather") == "ps.pull/emb"
        assert f("jit(_jitted)/ps.push/scatter/emb/scatter-add") == "ps.push/scatter/emb"
        assert f("jit(_jitted)/ps.grad/transpose(jvp(mlp))/dot_general") == "ps.grad/mlp"
        # names come from the app that ran the program (StepApp.scope_names), not
        # from whatever was traced earlier in the process: another app's are no scope
        assert spmd._scope_of("jit(_jitted)/ps.pull/emb/jit(_take)/gather") == "ps.pull"
        assert spmd._scope_of("jit(_jitted)/ps.pull/while/body/gather") == "ps.pull"
        from parameter_server_tpu.models import wide_deep

        cfg = PSConfig()
        cfg.app = "wide_deep"
        assert wide_deep.app_from_config(cfg).scope_names() == {"wide", "emb", "mlp"}
        assert spmd.linear_app(updater_from_config(PSConfig())).scope_names() == frozenset()

    def test_forgotten_programs_are_remembered_when_they_run_again(self, no_programs):
        mesh = make_mesh(1, 1)
        updater = updater_from_config(PSConfig())
        fn = spmd.make_spmd_predict_step(updater, mesh, NUM_KEYS)
        state = spmd.shard_state(updater.init(NUM_KEYS, 1), mesh)
        fn(state, _batch(1))
        assert len(spmd._ran) == 1
        spmd.forget_programs()
        assert spmd.op_scopes() == {}
        fn(state, _batch(1))  # shapes this stepper has seen before
        assert len(spmd._ran) == 1 and len(spmd.op_scopes()) == 1

    def test_programs_that_share_a_module_name_merge(self, monkeypatch):
        monkeypatch.setattr(spmd, "_ran", [
            spmd._RanProgram(None, (), ("jit__jitted", {"fusion.1": "ps.pull", "fusion.2": "ps.grad", "copy.1": ""})),
            spmd._RanProgram(None, (), ("jit__jitted", {"fusion.1": "ps.row_ids", "fusion.2": "ps.grad", "fusion.3": "ps.push"})),
        ])
        assert spmd.op_scopes() == {"jit__jitted": {
            "fusion.1": "", "fusion.2": "ps.grad", "copy.1": "", "fusion.3": "ps.push",
        }}


class TestPhase:
    def test_off_is_a_timer_and_nothing_else(self):
        assert not trace.enabled()
        before = _count("test.phase_off")
        ph = trace.phase("test.phase_off", step=3)
        assert ph._span is trace._NOOP  # no Span allocated
        with ph:
            time.sleep(0.002)
        snap = timers.snapshot()["test.phase_off"]
        assert snap["count"] == before + 1 and snap["total_s"] >= 0.002

    def test_count_of_a_phase_that_finishes_no_unit(self):
        before = _count("test.phase_partial")
        with trace.phase("test.phase_partial") as ph:
            ph.count = 0
        assert _count("test.phase_partial") == before
        assert timers.snapshot()["test.phase_partial"]["total_s"] > 0

    def test_armed_tracer_records_one_span_of_that_name(self, tmp_path):
        t = trace.configure(str(tmp_path), process_name="phase-test")
        try:
            with trace.phase("test.phase_armed", step=7):
                pass
            evs = [e for e in t.events() if e["name"] == "test.phase_armed"]
        finally:
            trace.configure(None)
        assert len(evs) == 1 and evs[0]["ph"] == "X" and evs[0]["cat"] == "test"
        assert evs[0]["args"]["step"] == 7

    def test_name_is_a_host_event_of_the_profilers_trace(self, tmp_path):
        with jax.profiler.trace(str(tmp_path)):
            with trace.phase("test.phase_profiled"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        prof = jax.profiler.ProfileData.from_file(path)
        hosts = [p for p in prof.planes if not p.name.startswith("/device:")]
        names = {ev.name for p in hosts for ln in p.lines for ev in ln.events}
        assert "test.phase_profiled" in names


def _spin(seconds: float) -> None:
    """Python that holds a CPU (and the interpreter lock) for ``seconds``."""
    until = time.perf_counter() + seconds
    while time.perf_counter() < until:
        sum(range(200))


@contextlib.contextmanager
def _spinning_threads(n: int):
    """``n`` threads that spin in Python until the block ends."""
    stop = threading.Event()
    threads = [threading.Thread(target=lambda: [_spin(0.01) for _ in iter(stop.is_set, True)]) for _ in range(n)]
    for t in threads:
        t.start()
    try:
        yield
    finally:
        stop.set()
        for t in threads:
            t.join()


class TestPhaseCpu:
    """``<name>.cpu``: the CPU seconds of the phase's own thread, beside the
    wall seconds of ``<name>``, for the phases ``trace._CPU_TWINS`` names
    while a tracing plane is on (here the tracer, armed)."""

    @pytest.fixture(autouse=True)
    def _twins_for_the_tests_names_and_the_tracer_armed(self, monkeypatch, tmp_path):
        names = "sleep spin alone beside counts own ends short outer inner plane keys".split()
        monkeypatch.setattr(trace, "_CPU_TWINS", trace._CPU_TWINS | {f"test.cpu_{n}" for n in names})
        trace.configure(str(tmp_path), process_name="phase-cpu-test")
        yield
        trace.configure(None)

    def test_a_sleep_is_wall_and_no_cpu(self):
        w0, c0 = _total("test.cpu_sleep"), _total("test.cpu_sleep.cpu")
        with trace.phase("test.cpu_sleep"):
            time.sleep(0.05)
        assert _total("test.cpu_sleep") - w0 >= 0.05
        assert _total("test.cpu_sleep.cpu") - c0 < 0.01

    def test_a_spin_is_cpu(self):
        """Held to another clock of the kernel's, the thread's resource usage: a
        spin that lasts until that clock has counted 50 ms leaves them in the
        twin, however long a busy machine (six xdist workers) makes it take."""
        def used() -> float:
            ru = resource.getrusage(resource.RUSAGE_THREAD)
            return ru.ru_utime + ru.ru_stime

        w0, c0 = _total("test.cpu_spin"), _total("test.cpu_spin.cpu")
        with trace.phase("test.cpu_spin"):
            until = used() + 0.05
            while used() < until:
                sum(range(200))
        wall, cpu = _total("test.cpu_spin") - w0, _total("test.cpu_spin.cpu") - c0
        assert 0.04 <= cpu <= wall + 1e-3

    def test_a_wait_for_the_interpreter_lock_is_wall_and_no_cpu(self):
        """Native work with the lock released, then a little Python, as the
        parse thread does: beside two threads that spin in Python the phase's
        wall grows by the waits for the lock and its CPU stays what the
        thread costs alone."""
        data, rounds = bytes(2 << 20), 20

        def work(name: str):
            def run():
                for _ in range(rounds):
                    with trace.phase(name):
                        hashlib.sha256(data).digest()  # the lock released
                        sum(range(2000))
            return run

        def reading(name: str) -> tuple:
            w0, c0 = _total(name), _total(name + ".cpu")
            thread = threading.Thread(target=work(name))
            thread.start()
            thread.join()
            return _total(name) - w0, _total(name + ".cpu") - c0

        for attempt in range(5):  # a busy machine moves the CPU side too: the thread shares its core
            _, alone = reading("test.cpu_alone")
            with _spinning_threads(2):
                wall, cpu = reading("test.cpu_beside")
            if wall >= 2 * cpu and abs(cpu - alone) <= alone / 3:
                break
        assert wall >= 2 * cpu, (wall, cpu, alone)
        assert abs(cpu - alone) <= alone / 3, (wall, cpu, alone)

    def test_the_two_counts_are_one(self):
        n0, c0, t0 = _count("test.cpu_counts"), _count("test.cpu_counts.cpu"), _total("test.cpu_counts.cpu")
        for units in (1, 0, 5):
            with trace.phase("test.cpu_counts") as ph:
                ph.count = units
                _spin(0.001)
        assert _count("test.cpu_counts") - n0 == _count("test.cpu_counts.cpu") - c0 == 6
        assert _total("test.cpu_counts.cpu") - t0 >= 0.002  # the phase that finished no unit is timed too

    def test_a_phase_reads_its_own_threads_seconds(self):
        with _spinning_threads(1):
            for _ in range(5):  # a busy machine starves the spinning thread
                c0, p0 = _total("test.cpu_own.cpu"), _total("process.cpu")
                with trace.phase("test.cpu_own"):
                    time.sleep(0.1)
                own, process = _total("test.cpu_own.cpu") - c0, _total("process.cpu") - p0
                assert own < 0.02  # the other thread's 100 ms are in the process's clock alone
                if process >= 0.05:
                    break
        assert process >= 0.05 and process > 2 * own

    def test_a_twin_reads_the_clock_at_both_ends_of_its_phase(self, monkeypatch):
        """Two fresh readings a phase, however short it is and however close
        its neighbour: none is handed on from one phase to the next."""
        reads = []
        real = time.thread_time
        monkeypatch.setattr(trace.time, "thread_time", lambda: reads.append(1) or real())
        c0 = _total("test.cpu_ends.cpu")
        with trace.phase("test.cpu_ends"):
            _spin(0.03)  # three ticks of the coarsest clock met (10 ms, the chip machine's)
        assert len(reads) == 2 and _total("test.cpu_ends.cpu") - c0 > 0
        for _ in range(3):
            with trace.phase("test.cpu_short"):
                pass
        assert len(reads) == 8 and _count("test.cpu_short.cpu") >= 3

    def test_a_phase_outside_the_list_has_no_twin_and_reads_no_clock_but_the_wall(self, monkeypatch):
        """Waits and the phases around other phases: the thread's CPU clock is
        a system call with the interpreter lock held, paid only where a metric
        reads the answer."""
        reads = []
        monkeypatch.setattr(trace.time, "thread_time", lambda: reads.append(1) or 0.0)
        n0 = _count("test.wall_alone")
        with trace.phase("test.wall_alone"):
            with trace.phase("reader.put_wait"):
                pass
        assert _count("test.wall_alone") - n0 == 1 and not reads
        snap = timers.snapshot()
        assert "test.wall_alone.cpu" not in snap and "reader.put_wait.cpu" not in snap

    def test_nested_phases_each_count_their_own_interval(self):
        o0, i0 = _total("test.cpu_outer.cpu"), _total("test.cpu_inner.cpu")
        with trace.phase("test.cpu_outer"):
            _spin(0.01)
            with trace.phase("test.cpu_inner"):
                _spin(0.02)
        outer, inner = _total("test.cpu_outer.cpu") - o0, _total("test.cpu_inner.cpu") - i0
        assert outer >= inner > 0

    def test_the_twin_rides_the_telemetry_plane_and_the_export(self):
        from parameter_server_tpu.utils.metrics import merge_telemetry, telemetry_snapshot
        from parameter_server_tpu.utils.timeseries import render_openmetrics

        with trace.phase("test.cpu_plane"):
            _spin(0.005)
        snap = telemetry_snapshot(roll_peaks=False)
        one = snap["timers"]["test.cpu_plane.cpu"]
        two = merge_telemetry([snap, snap])["timers"]
        assert two["test.cpu_plane.cpu"] == {"total_s": 2 * one["total_s"], "count": 2 * one["count"]}
        assert two["process.cpu"]["total_s"] == 2 * snap["timers"]["process.cpu"]["total_s"]  # a cluster's CPU seconds
        text = render_openmetrics(snap)
        assert "ps_timer_test_cpu_plane_cpu_seconds_total " in text and "ps_timer_test_cpu_plane_seconds_total " in text
        assert "ps_timer_process_cpu_seconds_total " in text

    def test_process_cpu_is_in_every_snapshot(self):
        a = timers.snapshot()["process.cpu"]
        until = time.process_time() + 0.02  # the process's own clock: a loaded machine hands a wall spin less
        while time.process_time() < until:
            sum(range(200))
        b = timers.snapshot()["process.cpu"]
        assert set(a) == set(b) == {"total_s", "count"}
        assert b["total_s"] >= a["total_s"] + 0.01  # never decreases, and the spin is in it
        assert b["count"] == a["count"] + 1  # the snapshots taken: a window's difference is at least 1
        assert set(Timer().snapshot()) == {"total_s", "count"}  # a timer's two keys, as ever
        with trace.phase("test.cpu_keys"):
            pass
        assert set(timers.snapshot()["test.cpu_keys.cpu"]) == {"total_s", "count"}  # and its twin's


class TestPhaseCpuOnlyWhileTracing:
    """The thread's CPU clock is a system call with the interpreter lock held
    (12 us on the chip machine): no phase pays it with both planes off."""

    def test_with_tracing_off_a_listed_phase_reads_the_wall_alone(self, monkeypatch):
        assert not trace.enabled() and "reader.parse" in trace._CPU_TWINS
        reads = []
        monkeypatch.setattr(trace.time, "thread_time", lambda: reads.append(1) or 0.0)
        n0, c0 = _count("reader.parse"), _count("reader.parse.cpu")
        with trace.phase("reader.parse"):
            pass
        assert not reads
        assert (_count("reader.parse") - n0, _count("reader.parse.cpu") - c0) == (1, 0)

    def test_inside_a_profiler_session_a_listed_phase_has_its_twin(self, tmp_path):
        n0, c0 = _count("eval.score"), _count("eval.score.cpu")
        with jax.profiler.trace(str(tmp_path)):
            with trace.phase("eval.score"):
                _spin(0.03)
            with trace.phase("eval.pass"):
                pass
        with trace.phase("eval.score"):  # the session over: the wall alone again
            pass
        snap = timers.snapshot()
        assert (_count("eval.score") - n0, _count("eval.score.cpu") - c0) == (2, 1)
        assert snap["eval.score.cpu"]["total_s"] > 0 and "eval.pass.cpu" not in snap


class _Stream:
    def __init__(self, n: int):
        self.left = n

    def next_batch(self):
        if not self.left:
            return None
        self.left -= 1
        return self.left

    def _empty(self):
        return -1


class TestFeedTimers:
    def test_one_build_a_batch_one_stack_an_item(self):
        b0, s0 = _count("feed.build"), _count("feed.stack")
        with PrefetchPipeline([_Stream(6), _Stream(4)], prepare=list, depth=8) as p:
            items = []
            while (it := p.get()) is not None:
                items.append(it)
        assert len(items) == 6
        assert _count("feed.build") - b0 == 10  # not the probes that found the streams drained
        assert _count("feed.stack") - s0 == 6

    def test_a_group_is_one_stacked_item(self):
        s0 = _count("feed.stack")
        with PrefetchPipeline(
            [_Stream(7)], prepare=list, depth=8, group_size=3, assemble=list
        ) as p:
            n = 0
            while p.get() is not None:
                n += 1
        assert n == 3  # 3 + 3 + (1 padded to 3)
        assert _count("feed.stack") - s0 == 3

    def test_a_full_queue_adds_to_put_wait(self):
        w0 = timers.snapshot().get("feed.put_wait", {"count": 0, "total_s": 0.0})
        with PrefetchPipeline([_Stream(5)], prepare=list, depth=1) as p:
            time.sleep(0.3)  # the producer runs ahead of a consumer that is not there
            while p.get() is not None:
                pass
        w1 = timers.snapshot()["feed.put_wait"]
        assert w1["count"] > w0["count"] and w1["total_s"] - w0["total_s"] > 0.1


READER_PHASES = ("reader.parse", "reader.build", "reader.parsed_wait", "reader.put_wait")


def _reader(tmp_path, backend: str = "auto", prefetch: int = 4) -> MinibatchReader:
    """512 examples in batches of 64."""
    builder = BatchBuilder(num_keys=NUM_KEYS, batch_size=64, max_nnz_per_example=16)
    return MinibatchReader(_files(tmp_path, 8, "r"), "libsvm", builder, prefetch=prefetch, backend=backend)


class TestReaderTimers:
    @pytest.mark.parametrize("backend", ["native", "python"])
    def test_one_build_a_batch_and_a_parse_a_chunk(self, tmp_path, backend):
        before = {n: _count(n) for n in READER_PHASES}
        batches = list(_reader(tmp_path, backend))
        grew = {n: _count(n) - before[n] for n in before}
        assert sum(b.num_examples for b in batches) == 512 and len(batches) == 8
        # a batch is one build on the build thread and one wait for its parsed piece
        assert grew["reader.build"] == grew["reader.parsed_wait"] == 8
        # the file is one chunk (the step that finds its end counts nothing);
        # the Python parsers hand over a row at a time: no phase a row
        assert grew["reader.parse"] == (1 if backend == "native" else 0)

    def test_a_full_queue_adds_to_put_wait(self, tmp_path):
        n0, s0 = _count("reader.put_wait"), _total("reader.put_wait")
        it = iter(_reader(tmp_path, prefetch=1))
        first = next(it)
        time.sleep(0.3)  # the reader's thread runs ahead of a caller that is not there
        rest = list(it)
        assert first.num_examples == 64 and len(rest) == 7
        assert _count("reader.put_wait") > n0 and _total("reader.put_wait") - s0 > 0.1

    def test_an_abandoned_reader_ends_its_wait(self, tmp_path):
        n0 = _count("reader.put_wait")
        it = iter(_reader(tmp_path, prefetch=1))
        next(it)
        time.sleep(0.3)  # until a stage has filled its queue and blocks: one that had not yet is let go unblocked
        it.close()  # the caller leaves: the thread blocked on its full queue is let go
        deadline = time.time() + 5
        while _count("reader.put_wait") == n0 and time.time() < deadline:
            time.sleep(0.01)
        assert _count("reader.put_wait") > n0

    def test_armed_tracer_spans_say_how_many_examples(self, tmp_path):
        t = trace.configure(str(tmp_path / "spans"), process_name="reader-test")
        try:
            list(_reader(tmp_path))
            evs = [e for e in t.events() if e["name"] in READER_PHASES]
        finally:
            trace.configure(None)
        # a chunk's count is known once it is parsed: set inside the phase;
        # the step that finds the file at its end is a span too, and says nothing
        assert [e["args"].get("examples") for e in evs if e["name"] == "reader.parse"] == [512, None]
        assert [e["args"]["examples"] for e in evs if e["name"] == "reader.build"] == [64] * 8
        assert {e["cat"] for e in evs} == {"reader"}
        # two threads of the reader's own, a stage each
        tids = {name: {e["tid"] for e in evs if e["name"] == name} for name in READER_PHASES[:3]}
        assert all(len(v) == 1 for v in tids.values())
        assert tids["reader.build"] == tids["reader.parsed_wait"] != tids["reader.parse"]

    @pytest.mark.parametrize("backend", ["native", "python"])
    @pytest.mark.parametrize("min_count", [0, 2])
    def test_parsed_batches_built_by_the_caller_are_the_readers_own(self, tmp_path, backend, min_count):
        """``parsed()`` hands over what the reader's thread parsed and
        ``build`` finishes it where it is called: the same batches as the
        reader's own, to the bit, a frequency filter's admissions (which
        depend on the order of the builds) included."""
        def reader():
            builder = BatchBuilder(
                num_keys=NUM_KEYS, batch_size=64, max_nnz_per_example=16, freq_min_count=min_count,
            )
            return MinibatchReader(_files(tmp_path, 8, "r"), "libsvm", builder, backend=backend)

        own, split = list(reader()), reader()
        b0 = _count("reader.build")
        pieces = list(split.parsed())
        assert _count("reader.build") == b0  # parsed, not built
        built = [split.build(p) for p in pieces]
        assert _count("reader.build") - b0 == len(built) == len(own) == 8
        for a, b in zip(built, own):
            for field in ("unique_keys", "local_ids", "row_ids", "values", "labels", "example_mask", "row_splits"):
                np.testing.assert_array_equal(getattr(a, field), getattr(b, field))
            assert (a.num_examples, a.num_unique, a.num_entries) == (b.num_examples, b.num_unique, b.num_entries)

    def test_a_training_stream_parses_and_builds_on_two_threads(self, tmp_path):
        """``_WorkerStream.next_batch`` builds on the thread that calls it,
        the pipeline's producer thread (under its ``feed.build``), what the
        reader's thread parsed: neither thread does both."""
        from parameter_server_tpu.parallel.trainer import _WorkerStream
        from parameter_server_tpu.parallel.workload import WorkloadPool

        builder = BatchBuilder(num_keys=NUM_KEYS, batch_size=64, max_nnz_per_example=16)
        stream = _WorkerStream(0, WorkloadPool(_files(tmp_path, 8, "r")), "libsvm", builder, backend="native")
        t = trace.configure(str(tmp_path / "spans"), process_name="stream-test")
        try:
            with PrefetchPipeline([stream], prepare=list) as p:
                n = 0
                while (item := p.get()) is not None:
                    n += item[0].num_examples
            evs = t.events()
        finally:
            trace.configure(None)
        assert n == 512
        tids = {name: {e["tid"] for e in evs if e["name"] == name} for name in ("reader.parse", "reader.build", "feed.build")}
        assert all(len(v) == 1 for v in tids.values())
        assert tids["reader.build"] == tids["feed.build"] != tids["reader.parse"]

    def test_names_are_host_events_of_the_readers_two_threads(self, tmp_path):
        reader = _reader(tmp_path, prefetch=1)
        with jax.profiler.trace(str(tmp_path / "prof")):
            with trace.phase("test.reader_caller"):
                it = iter(reader)
                next(it)
                time.sleep(0.2)  # a full queue, so that the thread waits
                list(it)
        (path,) = glob.glob(os.path.join(str(tmp_path / "prof"), "plugins", "profile", "*", "*.xplane.pb"))
        prof = jax.profiler.ProfileData.from_file(path)
        threads = [
            {ev.name for ev in ln.events}
            for p in prof.planes if not p.name.startswith("/device:") for ln in p.lines
        ]
        (parses,) = [names for names in threads if "reader.parse" in names]
        (builds,) = [names for names in threads if "reader.build" in names]
        # each stage waits on its own full queue; the build alone waits for a piece
        assert {"reader.parse", "reader.put_wait"} <= parses and "reader.parsed_wait" not in parses
        assert {"reader.build", "reader.parsed_wait", "reader.put_wait"} <= builds
        assert "test.reader_caller" not in parses | builds  # neither is the thread that iterates
        # tools/host_gaps.py tells the stages apart by these names
        tools = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools")
        sys.path.insert(0, tools)
        try:
            import host_gaps
        finally:
            sys.path.remove(tools)
        roles = sorted(th.role for th in host_gaps.host_threads(prof, 0.0, float("inf")))
        assert roles == ["reader/build", "reader/parse", "test"]


def _files(tmp_path, nnz_per_example: int, tag: str, examples: int = 512) -> list:
    labels, keys, vals, _ = make_sparse_logistic(
        examples, 300, nnz_per_example=nnz_per_example, noise=0.3, seed=5
    )
    path = tmp_path / f"{tag}.svm"
    write_libsvm(path, labels, keys, vals)
    return [str(path)]


def _trainer(minibatch: int = 128, **data) -> PodTrainer:
    cfg = PSConfig()
    cfg.data.num_keys = 1 << 12
    cfg.solver.minibatch = minibatch
    cfg.solver.epochs = 1
    cfg.parallel.data_shards = 2
    cfg.parallel.kv_shards = 2
    for k, v in data.items():
        setattr(cfg.data, k, v)
    return PodTrainer(cfg, reporter=ProgressReporter(print_fn=lambda *_: None))


EVAL_LEAVES = ("eval.open_reader", "eval.read", "eval.stack", "eval.enqueue", "eval.retire", "eval.score")


class TestTrainerAndEvaluatorTimers:
    def test_one_pass_is_one_open_and_one_score(self, tmp_path):
        t = _trainer()
        files = _files(tmp_path, 8, "a")
        t.train_files(files, report_every=100)
        names = ("eval.open", "eval.score", "eval.dispatch", "eval.retire", "eval.new_shapes")
        before = {n: _count(n) for n in names + ("eval.pass",)}
        ev = t.evaluate_files(files)
        after = {n: _count(n) - before[n] for n in before}
        calls = -(-ev["examples"] // (128 * 2))  # predict calls: two data shards a call
        assert ev["examples"] == 512 and calls == 2
        assert after == {
            "eval.open": 1, "eval.score": 1, "eval.dispatch": calls - 1, "eval.retire": calls,
            "eval.new_shapes": 1,  # the pass's one shape, first dispatched here
            "eval.pass": 1,
        }
        t.evaluate_files(files)
        assert _count("eval.new_shapes") - before["eval.new_shapes"] == 1  # and not again

    @pytest.mark.parametrize("source", ["files", "batches"])
    def test_the_leaves_add_up_to_the_pass(self, tmp_path, source):
        t = _trainer(minibatch=1024)  # a size at which the calls, not the loop, are the pass
        files = _files(tmp_path, 8, "p", examples=16 * 1024 + 512)
        if source == "files":
            evaluate = lambda: t.evaluate_files(files)  # noqa: E731
        else:
            batches = list(MinibatchReader(files, "libsvm", eval_builder(t.cfg, "hash")))
            evaluate = lambda: t.evaluate_batches(batches)  # noqa: E731
        evaluate()  # the pass that compiles
        whole = ("eval.pass", "eval.open", "eval.dispatch", "eval.new_shapes")
        # The leaves are wall-clock timers and the pass is 20-60 ms: where the test's
        # process is descheduled between two leaves (six xdist workers on as many
        # cores) the unnamed remainder takes the whole stall and passes a tenth of
        # the pass. Every pass is held to its counts and its order; the share is
        # held over up to five passes, of which a stall would have to hit every one
        # between two leaves.
        for attempt in range(5):
            before = {n: (_count(n), _total(n)) for n in EVAL_LEAVES + whole}
            ev = evaluate()
            count = {n: _count(n) - before[n][0] for n in before}
            spent = {n: _total(n) - before[n][1] for n in before}
            batches_read = -(-ev["examples"] // 1024)  # 17: the last one is short
            calls = -(-batches_read // 2)  # two data shards a call: 9, the last one half inert
            assert ev["examples"] == 16 * 1024 + 512 and calls == 9
            assert count == {
                "eval.pass": 1, "eval.open_reader": 1, "eval.score": 1,
                "eval.read": calls,  # a group a call; not the probe that finds the stream at its end
                "eval.stack": calls, "eval.enqueue": calls, "eval.retire": calls,
                "eval.open": 1, "eval.dispatch": calls - 1, "eval.new_shapes": 0,
            }
            leaves = sum(spent[n] for n in EVAL_LEAVES)
            assert leaves <= spent["eval.pass"], spent
            # the two enclosing phases hold their calls' leaves
            assert spent["eval.open"] + spent["eval.dispatch"] >= spent["eval.stack"] + spent["eval.enqueue"]
            assert spent["eval.open"] >= spent["eval.open_reader"]
            if 0.9 * spent["eval.pass"] <= leaves:
                break
        assert 0.9 * spent["eval.pass"] <= leaves, (attempt, spent)

    def test_the_three_trainer_phases_keep_their_timers(self, tmp_path):
        before = {n: _count(n) for n in ("trainer.fetch", "trainer.dispatch", "trainer.retire")}
        _trainer().train_files(_files(tmp_path, 8, "b"), report_every=100)
        grew = {n: _count(n) - before[n] for n in before}
        assert grew["trainer.fetch"] == grew["trainer.dispatch"] == grew["trainer.retire"] >= 2

    def test_the_call_that_ends_an_epoch_has_the_epochs_shape(self, tmp_path):
        """The all-inert call by which every host learns that the pod's
        streams are dry is shaped like the streams' newest batches: one
        device shape an epoch of one bucket and ONE step program for
        ``spmd.op_scopes`` to read names off (a call of the smallest bucket
        was a second program of the same module name, whose fusions,
        numbered otherwise, blanked the step's in the merged map)."""
        t = _trainer(bucket_nnz=True)
        spmd.forget_programs()
        t.train_files(_files(tmp_path, 40, "large"), report_every=100)  # 20,480 entries: over the floor
        (shape,) = t._dispatched_shapes
        assert shape[1] > BUCKET_FLOOR
        assert len(spmd._ran) == 1
        spmd.forget_programs()

    def test_a_second_bucket_is_one_new_shape(self, tmp_path):
        t = _trainer(bucket_nnz=True)
        small, large = _files(tmp_path, 4, "small"), _files(tmp_path, 40, "large")
        n0 = _count("trainer.new_shapes")
        t.train_files(small, report_every=100)
        n1 = _count("trainer.new_shapes")
        t.train_files(small, report_every=100)
        n2 = _count("trainer.new_shapes")
        t.train_files(large, report_every=100)
        n3 = _count("trainer.new_shapes")
        assert len(t._dispatched_shapes) == n3 - n0
        assert {name for name, _, _ in t._dispatched_shapes} == {"trainer.new_shapes"}
        assert n2 == n1  # the shapes of the first epoch again: nothing new
        assert n3 - n2 == len(t._dispatched_shapes) - (n1 - n0) >= 1
        # the progress report no longer carries the static traffic estimate
        assert not hasattr(t, "est_step_traffic")
        assert all("est_collective_bytes" not in r for r in t.reporter.history)


class TestCpuTwinsOfTheHostPath:
    def test_every_working_phase_of_a_run_has_its_cpu_twin(self, tmp_path):
        """The reader's two threads, the feed's stacker, the trainer's loop and
        the evaluator's caller each leave ``<name>.cpu`` beside ``<name>`` for
        the phases they work in while a tracing plane is on: as many units,
        and no more seconds than the wall's (a clock tick apart)."""
        before = timers.snapshot()
        trace.configure(str(tmp_path / "trace"), process_name="host-path-test")  # a tracing plane on
        try:
            list(_reader(tmp_path, "native"))
            with PrefetchPipeline([_Stream(6)], prepare=list, depth=8) as p:
                while p.get() is not None:
                    pass
            t = _trainer()
            files = _files(tmp_path, 8, "c")
            t.train_files(files, report_every=100)
            t.evaluate_files(files)
        finally:
            trace.configure(None)
        after = timers.snapshot()
        zero = {"total_s": 0.0, "count": 0}
        snap = {k: {f: v[f] - before.get(k, zero)[f] for f in v} for k, v in after.items()}
        for name in ("reader.parse", "reader.build", "feed.stack", "trainer.dispatch", "eval.score"):
            wall, cpu = snap[name], snap[name + ".cpu"]
            assert cpu["count"] == wall["count"] > 0, name
            assert 0 <= cpu["total_s"] <= 1.01 * wall["total_s"] + 1e-3, (name, wall, cpu)
        # the waits and the phases around other phases keep to the wall
        for name in ("reader.parsed_wait", "reader.put_wait", "trainer.retire", "eval.read", "eval.pass", "eval.dispatch"):
            assert snap[name]["count"] > 0 and name + ".cpu" not in snap, name
