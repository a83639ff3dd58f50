"""How busy the threads that parse and build were: the program's timers
``reader.parse`` + ``reader.build`` over the window, in % of the window on
``data_shards`` reader threads. At 100 a stream's one reader thread is the
wall. (``feed.build_busy_share`` times the pipeline thread's wait for this
thread, not the work.)"""

from benchmark.layer_metrics_host import reader_busy_share


def read(run):
    return reader_busy_share(run)
