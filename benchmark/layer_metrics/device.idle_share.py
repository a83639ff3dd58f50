"""Share of the window in which no op ran on a chip, averaged over the
chips, in %: 100 x (1 - busy_s / window_s), both from the trace. (The
driver works the same share out of the ``device`` block as a fraction.)"""


def read(run):
    tr = run["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
