"""% of the parse thread's ``reader.parse`` seconds in which it was on no
CPU: 100 x (1 - ``reader.parse.cpu`` / ``reader.parse``). The thread reads
and parses in native code with the interpreter lock released and takes the
lock back for the NumPy glue between the calls: a share that grows beside
more Python threads is the wait for the lock (or the machine), a wall that
grows with the share unmoved is the parse itself slowing."""

from benchmark.layer_metrics_cpu import offcpu_share


def read(run):
    return offcpu_share(run, ("reader.parse",))
