"""Share of the window the dispatch loop spent waiting for the prefetch
pipeline: the program's timer ``trainer.fetch`` over the window, in %."""


def read(run):
    t = run["timers"].get("trainer.fetch")
    if t is None:
        return None
    return 100.0 * t["total_s"] / run["window"]["elapsed_s"]
