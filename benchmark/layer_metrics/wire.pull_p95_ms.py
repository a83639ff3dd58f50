"""95th percentile of a pull's round trip (issue to rows returned), at the
clients, over the window's pulls."""

import numpy as np


def read(run):
    v = run["facts"].get("pull_ms")
    if v is None or len(v) < 20:
        return None
    return float(np.percentile(v, 95))
