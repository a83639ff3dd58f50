"""Wall time of the batch solver's KKT refresh, ms a refreshed block: the
time between the retires around each refresh call the solve retired (the
window's, and the one behind its close that the traffic kind waits for),
over those calls' blocks. One call is in flight, so the time between two
retires is the later call's own; on the chip that is its device time and
the host's dispatch of it. None where the run refreshed nothing."""


def read(run):
    v = run["facts"].get("refresh_ms")
    return None if v is None else float(v)
