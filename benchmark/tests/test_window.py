"""The window arithmetic on made-up stamps: whole units only, and one
injected stall moves the rate by d/T and no more."""

import pytest

import tiny  # noqa: F401  (puts the repo root on sys.path)
from benchmark.harness import window


def steady(n, dt=1.6, t0=100.0):
    return [t0 + i * dt for i in range(n)]


def test_whole_units_only():
    stamps, work = steady(40), [65536] * 40
    w = window.summarize(stamps, work, open_at=1, seconds=45.0)
    # 45 / 1.6 = 28.1: the window closes at the 29th retire after it opened
    assert w["units"] == 29
    assert w["work"] == 29 * 65536
    assert w["elapsed_s"] == pytest.approx(29 * 1.6)
    assert w["rate"] == pytest.approx(65536 / 1.6)


def test_rate_does_not_depend_on_where_seconds_falls():
    stamps, work = steady(60), [65536] * 60
    rates = {round(window.summarize(stamps, work, 1, s)["rate"], 6) for s in (10.0, 10.7, 11.3, 44.9, 45.0)}
    assert len(rates) == 1


def test_one_stall_costs_d_over_t():
    d = 0.27
    stamps = steady(40)
    stamps = stamps[:10] + [t + d for t in stamps[10:]]  # one stall before call 10
    w = window.summarize(stamps, [65536] * 40, 1, 45.0)
    clean = 65536 / 1.6
    lost = 1.0 - w["rate"] / clean
    assert lost == pytest.approx(d / w["elapsed_s"], rel=1e-9)
    assert lost < d / 45.0


def test_stall_outside_the_window_costs_nothing():
    stamps = steady(40)
    stamps = [t - 5.0 for t in stamps[:2]] + stamps[2:]  # slow warm-up before it opened
    w = window.summarize(stamps, [65536] * 40, open_at=2, seconds=20.0)
    assert w["rate"] == pytest.approx(65536 / 1.6)


def test_never_closed_is_an_error():
    with pytest.raises(RuntimeError, match="did not close"):
        window.summarize(steady(5), [1] * 5, 1, 45.0)


def test_stamp_lines_mark_what_was_inside():
    stamps, work = steady(8), [10] * 8
    w = window.summarize(stamps, work, 1, 4.0)
    rows = window.stamp_lines(stamps, work, 1, w["close_at"])
    assert [r["inside"] for r in rows] == [False, False, True, True, True, False, False, False]
    assert sum(r["work"] for r in rows if r["inside"]) == w["work"]
