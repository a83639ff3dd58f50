"""Host time where the work happens, for the readers of the reader's
thread (``reader.parse`` / ``reader.build``: timers taken between the
window's snapshots, ``run["timers"]``) and of the evaluator's leaves
(``eval.*``: totals of the whole process, as ``eval.open_ms`` takes them,
because the ``eval`` kind snapshots no timers at its stamps). A program
without the timers (a parent) reads None."""

from __future__ import annotations

# what one evaluate_files pass is made of, on the caller's thread
EVAL_LEAVES = ("eval.open_reader", "eval.read", "eval.stack", "eval.enqueue", "eval.retire", "eval.score")


def ms_a_built_batch(run: dict, name: str):
    """Milliseconds of the program's timer ``name`` for each batch the
    reader threads built in the window (the count of ``reader.build``)."""
    spent, built = run["timers"].get(name), run["timers"].get("reader.build")
    if spent is None or built is None or not built["count"]:
        return None
    return 1e3 * spent["total_s"] / built["count"]


def reader_busy_share(run: dict):
    """Parse + build seconds of the window in % of the window on
    ``data_shards`` reader threads (a stream has one at a time)."""
    parse, build = run["timers"].get("reader.parse"), run["timers"].get("reader.build")
    if parse is None or build is None:
        return None
    threads = int(run["facts"].get("data_shards", 1))
    return 100.0 * (parse["total_s"] + build["total_s"]) / (run["window"]["elapsed_s"] * threads)


def unnamed_share(snap: dict):
    """% of ``eval.pass`` that none of its six leaves covers, over every
    pass of a process whose timers are ``snap``; ``eval.new_shapes`` (the
    warm pass's compile, inside ``eval.enqueue``) comes off both."""
    whole = snap.get("eval.pass")
    if whole is None or any(leaf not in snap for leaf in EVAL_LEAVES):
        return None
    compile_s = snap.get("eval.new_shapes", {"total_s": 0.0})["total_s"]
    passes = whole["total_s"] - compile_s
    if passes <= 0:
        return None
    named = sum(snap[leaf]["total_s"] for leaf in EVAL_LEAVES) - compile_s
    return 100.0 * (1.0 - named / passes)


def process_timers():
    """The program's timers over the whole process, or None without the
    program."""
    try:
        from parameter_server_tpu.utils.metrics import timers
    except ImportError:
        return None
    return timers.snapshot()
