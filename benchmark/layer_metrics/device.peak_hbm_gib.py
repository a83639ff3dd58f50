"""Peak bytes in use on the fullest chip, in GiB:
``device.memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(run):
    return run["device"]["memory_peak_bytes"] / 2**30
