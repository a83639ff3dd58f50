"""Examples/s of native parse + BatchBuilder on one stream over one file,
timed by the benchmark in set-up. The device's rate may not pass
data_shards x this without the feed showing in feed.fetch_wait_share."""


def read(run):
    return run["facts"].get("build_rate")
