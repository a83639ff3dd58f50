"""% of the loop thread's ``trainer.dispatch`` seconds (bucket agreement,
H2D, the jitted call's enqueue) in which it was on no CPU: 100 x (1 -
``trainer.dispatch.cpu`` / ``trainer.dispatch``). What ``dispatch.call_ms``
holds beyond its own work: a wait for the lock the feed's threads hold, or
for the runtime."""

from benchmark.layer_metrics_cpu import offcpu_share


def read(run):
    return offcpu_share(run, ("trainer.dispatch",))
