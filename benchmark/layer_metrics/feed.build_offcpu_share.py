"""% of the build thread's ``reader.build`` seconds (localize + the
builder, one a batch) in which it was on no CPU: 100 x (1 -
``reader.build.cpu`` / ``reader.build``)."""

from benchmark.layer_metrics_cpu import offcpu_share


def read(run):
    return offcpu_share(run, ("reader.build",))
