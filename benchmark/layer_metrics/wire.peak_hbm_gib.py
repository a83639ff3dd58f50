"""device.peak_hbm_gib under a name of its own (moves ``wire_rate``)."""


def read(run):
    return run["device"]["memory_peak_bytes"] / 2**30
