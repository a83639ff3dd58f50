"""How busy the feed's one stacker thread was: the program's timer
``feed.stack`` (``prepare`` + ``assemble`` for every emitted item) over the
window, in %. One thread serves every stream, so 100 is a wall."""

from benchmark.layer_metrics_scopes import timer_share


def read(run):
    return timer_share(run, "feed.stack")
