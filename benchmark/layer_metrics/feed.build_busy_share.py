"""How busy the feed's producer threads were: the program's timer
``feed.build`` (one ``next_batch()``: parse + ``BatchBuilder``, timed in
each stream's thread) over the window, in % of the window on
``data_shards`` threads. At 100 the feed sets the pace."""

from benchmark.layer_metrics_scopes import timer_share


def read(run):
    return timer_share(run, "feed.build", threads=int(run["facts"].get("data_shards", 1)))
