"""95th percentile of a push's round trip (issue to acknowledgement), at
the clients, over the window's pushes. Per-layer here: in a closed loop a
tail is the reciprocal of the rate."""

import numpy as np


def read(run):
    v = run["facts"].get("push_ms")
    if v is None or len(v) < 20:
        return None
    return float(np.percentile(v, 95))
