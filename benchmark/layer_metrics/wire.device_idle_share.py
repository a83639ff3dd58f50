"""device.idle_share under a name of its own, because on the wire cell it
moves ``wire_rate``: % of the window in which no op ran on the chip."""


def read(run):
    tr = run["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
