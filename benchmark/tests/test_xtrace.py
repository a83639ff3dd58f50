"""The reduction from trace to numbers: on a small recorded TPU trace
(``data/ctr1_train.xplane.pb``: 3.4 s of ``ctr1.train`` on a TPU v5 lite,
cut down by ``shrink_trace.py``) and on hand-made planes for the cases the
recording does not hold (two chips, collectives, idle edges)."""

import os

import pytest

import tiny  # noqa: F401
from benchmark.harness import xtrace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "ctr1_train.xplane.pb")


def profile_of(text: str):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(text))


def plane(name: str, lines: dict) -> str:
    """lines: line name -> [(event name, start_ns, duration_ns)]"""
    names, body = {}, []
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        es = " ".join(
            f"events {{ metadata_id: {names.setdefault(n, len(names) + 1)} "
            f"offset_ps: {int(s * 1000)} duration_ps: {int(d * 1000)} }}" for n, s, d in evs
        )
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 {es} }}')
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}' for n, i in names.items())
    return f'planes {{ name: "{name}" {meta} {" ".join(body)} }}'


def test_union_and_gaps():
    busy, gaps = xtrace.union_seconds([(0, 4), (2, 6), (10, 12), (12, 13), (20, 21)])
    assert busy == 6 + 3 + 1
    assert gaps == [(6, 4), (13, 7)]
    assert xtrace.union_seconds([]) == (0.0, [])


def test_busy_idle_ops_and_containers():
    ms = 1_000_000
    prof = profile_of(
        plane("/device:TPU:0", {
            "XLA Ops": [
                ("%while.1 = (s32[]) while(", 10 * ms, 80 * ms),  # spans its body
                ("%fusion.2 = f32[1024]{0} fusion(f32[1024]{0} %z, s32[64]{0} %i)", 10 * ms, 30 * ms),
                ("%fusion.3 = f32[64]{0} fusion(f32[64]{0} %g)", 40 * ms, 20 * ms),
                ("%fusion.2 = f32[1024]{0} fusion(f32[1024]{0} %z, s32[64]{0} %i)", 60 * ms, 30 * ms),
                ("%fusion.3 = f32[64]{0} fusion(f32[64]{0} %g)", 120 * ms, 10 * ms),  # past the window
            ],
            "XLA Modules": [("jit_step(1)", 10 * ms, 80 * ms)],
        })
        + plane("/host:CPU", {"python": [("bench.window_open", 0, 1), ("bench.retire", 95 * ms, 1),
                                         ("bench.window_close", 100 * ms, 1)]})
    )
    marks = xtrace.collect_marks(prof)
    r = xtrace.reduce_window(prof, marks["bench.window_open"][0], marks["bench.window_close"][0])
    assert r.chips == 1 and r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.080)  # the container counts towards busy, once
    assert set(r.ops) == {n for n in r.ops if "while" not in n} and len(r.ops) == 2
    two = [v for k, v in r.ops.items() if "fusion.2" in k][0]
    assert two[0] == pytest.approx(0.060) and two[1] == 2
    # idle: the window's two edges, longest first
    assert [round(d, 3) for _, d in r.gaps] == [0.01, 0.01]
    assert r.modules["jit_step(1)"][1] == 1


def test_two_chips_and_exposed_collectives():
    ms = 1_000_000
    dev = lambda n, ops: plane(f"/device:TPU:{n}", {"XLA Ops": ops})  # noqa: E731
    prof = profile_of(
        dev(0, [("%fusion.1 = f32[8]{0} fusion(", 0, 50 * ms),
                ("%all-gather.1 = f32[16]{0} all-gather(", 40 * ms, 30 * ms)])  # 20 ms exposed
        + dev(1, [("%fusion.1 = f32[8]{0} fusion(", 0, 100 * ms),
                  ("%all-reduce.2 = f32[8]{0} all-reduce(", 10 * ms, 20 * ms)])  # hidden
    )
    r = xtrace.reduce_window(prof, 0, 100 * ms)
    assert r.chips == 2
    assert r.busy_by_chip == pytest.approx([0.070, 0.100])
    assert r.busy_s == pytest.approx(0.085)
    assert r.collective_s == pytest.approx((0.030 + 0.020) / 2)
    assert r.collective_exposed_s == pytest.approx(0.020 / 2)


def test_no_device_plane_reads_nothing():
    prof = profile_of(plane("/host:CPU", {"python": [("bench.window_open", 0, 1)]}))
    r = xtrace.reduce_window(prof, 0, 1e9)
    assert r.chips == 0 and r.busy_s == 0.0


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace in this checkout")
def test_recorded_tpu_trace():
    prof = xtrace.load(RECORDED)
    marks = xtrace.collect_marks(prof)
    assert "bench.window_open" in marks and len(marks["bench.retire"]) >= 2
    t0 = marks["bench.window_open"][0]
    t1 = [m for m in marks["bench.retire"] if m > t0][1]  # two whole device calls
    r = xtrace.reduce_window(prof, t0, t1)
    assert r.chips == 1
    assert r.window_s == pytest.approx(2 * 1.5828, rel=2e-3)  # 8 microsteps of 197.9 ms a call
    assert 1.0 - r.busy_s / r.window_s < 1e-3  # the flagship is device-bound
    assert not any(xtrace.is_container(n) for n in r.ops)
    top = sorted(r.ops.items(), key=lambda kv: -kv[1][0])[:3]
    # the three fusions that lead the step: two scatter-adds into the 2^30-row
    # z and n, and the rebuild of row ids from row_splits
    assert sum("f32[1073741824]" in n for n, _ in top) == 2
    assert sum("s32[8193]" in n for n, _ in top) == 1
    for _, (sec, count) in top:
        assert count % 16 == 0 and sec / 16 == pytest.approx(0.052, rel=0.1)  # 52 ms a microstep each
