"""Share of the window the dispatch loop spent blocked on the chip: the
program's timer ``trainer.retire`` (the blocking read of the oldest call's
losses) over the window, in %. Near 100 the host keeps ahead of the device."""

from benchmark.layer_metrics_scopes import timer_share


def read(run):
    return timer_share(run, "trainer.retire")
