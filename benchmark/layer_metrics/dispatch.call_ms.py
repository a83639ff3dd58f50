"""Mean host time to issue one device call (bucket agreement, H2D, the
jitted call's enqueue): the program's timer ``trainer.dispatch``."""


def read(run):
    t = run["timers"].get("trainer.dispatch")
    if t is None or not t["count"]:
        return None
    return 1e3 * t["total_s"] / t["count"]
