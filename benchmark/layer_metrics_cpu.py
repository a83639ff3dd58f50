"""CPU seconds beside wall seconds, for the readers of the program's
``<name>.cpu`` timers (while a profiler session runs, ``trace.phase`` reads
``time.thread_time()`` at the two ends of its working phases: the seconds
the phase's own thread was on a CPU) and of ``process.cpu``
(``time.process_time()`` at every snapshot: every thread of the process).
Wall minus CPU is the time a thread stood inside a phase without running:
the interpreter lock, the machine's run queue, the disk.

The timers are the window's where the kind took its snapshots
(``run["timers"]`` holds the phase) and the whole process's where it did
not (the ``eval`` kind). A twin counts the units of its phase that ran
inside the session, so a phase's wall seconds are taken for as many units
as its twin counted: all of them over a train window, which lies inside
the session, and in the ``eval`` kind the window's share of the process's
(the prefix and the warm pass run before the session starts). A program
without the ``.cpu`` timers (a parent) reads None."""

from __future__ import annotations

from benchmark.layer_metrics_host import process_timers

ZERO = {"total_s": 0.0, "count": 0}
# the threads that make a batch and hand it to the chip, one working phase each
TRAIN_LEAVES = ("reader.parse", "reader.build", "feed.stack", "trainer.dispatch")
# the evaluator's caller stacks and enqueues where a trainer's feed and loop do
EVAL_LEAVES = ("reader.parse", "reader.build", "eval.stack", "eval.enqueue")
# what the evaluator's caller works at; eval.read and eval.retire are waits by design
EVAL_CALLER = ("eval.open_reader", "eval.stack", "eval.enqueue", "eval.score")
# the warm pass's compile is set-up: the phase it comes off, and its own
EVAL_SETUP = {"eval.enqueue": "eval.new_shapes"}


def timers_of(run: dict, name: str) -> dict:
    """The timers to read the phase ``name`` from: the window's where they
    hold it, else the process's (empty without the program)."""
    windowed = run.get("timers") or {}
    if name in windowed:
        return windowed
    return process_timers() or {}


def seconds(snap: dict, names, less: dict | None = None):
    """(wall, CPU) seconds of the phases ``names`` in ``snap`` over the units
    their twins counted, each after taking off the phase inside it that
    ``less`` names; None where a phase or its twin is missing."""
    if any(n not in snap or n + ".cpu" not in snap for n in names):
        return None
    wall = cpu = 0.0
    for n in names:
        w, c, inner = snap[n], snap[n + ".cpu"], (less or {}).get(n, "")
        twinned = c["count"] / w["count"] if w["count"] else 1.0
        wall += (w["total_s"] - snap.get(inner, ZERO)["total_s"]) * twinned
        cpu += c["total_s"] - snap.get(inner + ".cpu", ZERO)["total_s"]
    return wall, cpu


def offcpu_share(run: dict, names, less: dict | None = None):
    """% of the phases' wall seconds in which their thread was on no CPU."""
    both = seconds(timers_of(run, names[0]), names, less)
    if both is None or both[0] <= 0:
        return None
    return 100.0 * (1.0 - both[1] / both[0])


def cpu_ms_a_built_batch(run: dict):
    """CPU milliseconds the host spends on a batch over all its threads:
    the working leaves' CPU over the batches built (``reader.build.cpu``'s
    count: those built inside the session)."""
    snap = timers_of(run, "reader.build")
    if run["facts"].get("mode") == "eval":  # no feed, no loop: the evaluator's caller stacks and enqueues
        both = seconds(snap, EVAL_LEAVES, less=EVAL_SETUP)
    else:
        both = seconds(snap, TRAIN_LEAVES)
    if both is None or not snap["reader.build.cpu"]["count"]:
        return None
    return 1e3 * both[1] / snap["reader.build.cpu"]["count"]


def cpu_cores(run: dict):
    """CPU seconds of the whole process a second of the window."""
    spent = (run.get("timers") or {}).get("process.cpu")
    if spent is None:
        return None
    return spent["total_s"] / run["window"]["elapsed_s"]


def named_cpu_share(run: dict):
    """% of the process's CPU seconds in the window that the feed's and the
    loop's working leaves account for."""
    snap = run.get("timers") or {}
    both, spent = seconds(snap, TRAIN_LEAVES), snap.get("process.cpu")
    if both is None or spent is None or spent["total_s"] <= 0:
        return None
    return 100.0 * both[1] / spent["total_s"]
