"""% of the stacker thread's ``feed.stack`` seconds (``prepare`` +
``assemble`` of every emitted item, the H2D copies among them) in which it
was on no CPU: 100 x (1 - ``feed.stack.cpu`` / ``feed.stack``)."""

from benchmark.layer_metrics_cpu import offcpu_share


def read(run):
    return offcpu_share(run, ("feed.stack",))
