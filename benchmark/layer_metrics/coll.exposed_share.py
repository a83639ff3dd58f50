"""Share of the window in which a chip ran a collective and nothing else
(the collectives' time not hidden behind compute), averaged over chips,
in %. Nothing to read on a 1x1 mesh."""


def read(run):
    f = run["facts"]
    if f.get("data_shards", 1) * f.get("kv_shards", 1) <= 1:
        return None
    return 100.0 * run["trace"].collective_exposed_s / run["trace"].window_s
