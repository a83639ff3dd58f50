"""Pushes the server's apply thread coalesced into one updater step, on
average: the server's own counters ``pushes`` / ``apply_batches`` over the
run's client phase."""


def read(run):
    c = run["counters"]
    if not c.get("apply_batches"):
        return None
    return c["pushes"] / c["apply_batches"]
