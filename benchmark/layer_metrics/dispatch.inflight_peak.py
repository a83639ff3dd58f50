"""Most device calls in flight at once: ``PodTrainer.max_inflight``
(max_delay + 1 when the SSP gate binds)."""


def read(run):
    v = run["facts"].get("inflight_peak")
    return None if v is None else float(v)
