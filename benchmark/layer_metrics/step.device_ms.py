"""Device busy time per microstep (one minibatch per data shard): the
trace's union of op intervals, averaged over the chips, over the window's
microsteps."""


def read(run):
    n = run["facts"].get("microsteps")
    if not n:
        return None
    return 1e3 * run["trace"].busy_s / n
