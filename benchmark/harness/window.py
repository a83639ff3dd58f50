"""Window arithmetic on stamps, apart from any clock so that it can be
tested on made-up stamps.

A window is made of whole units of work: device calls for training, passes
for evaluation, round trips for the wire. ``stamps[i]`` is the host-clock
time at which unit ``i`` was complete (for training: the retire, a
blocking read of the call's result) and ``work[i]`` what it completed
(examples, keys). The window opens AT ``stamps[open_at]`` (that unit is
outside) and closes at the first later stamp at or after ``seconds``; the
rate is the work of the units inside over the time between the two
stamps. No partial unit, no pipeline fill and no drain is inside.
"""

from __future__ import annotations


def close_index(stamps: list, open_at: int, seconds: float):
    """Index of the stamp that closes the window, or None if not yet."""
    if open_at >= len(stamps):
        return None
    t_open = stamps[open_at]
    for i in range(open_at + 1, len(stamps)):
        if stamps[i] - t_open >= seconds:
            return i
    return None


def summarize(stamps: list, work: list, open_at: int, seconds: float) -> dict:
    """The closed window's numbers. Raises if it never closed."""
    close_at = close_index(stamps, open_at, seconds)
    if close_at is None:
        raise RuntimeError(
            f"window did not close: {len(stamps)} stamps, opened at {open_at}, "
            f"{seconds} s asked"
        )
    elapsed = stamps[close_at] - stamps[open_at]
    done = sum(work[open_at + 1 : close_at + 1])
    return {
        "open_at": open_at,
        "close_at": close_at,
        "t_open": stamps[open_at],
        "t_close": stamps[close_at],
        "elapsed_s": elapsed,
        "units": close_at - open_at,
        "work": done,
        "rate": done / elapsed,
    }


def stamp_lines(stamps: list, work: list, open_at: int, close_at: int) -> list:
    """One printable record per unit: index, seconds since the window
    opened, the unit's own duration, its work, and whether it was inside."""
    t_open = stamps[open_at]
    rows = []
    for i, (t, w) in enumerate(zip(stamps, work)):
        rows.append({
            "unit": i,
            "t": round(t - t_open, 6),
            "dt": round(t - stamps[i - 1], 6) if i else None,
            "work": w,
            "inside": open_at < i <= close_at,
        })
    return rows
