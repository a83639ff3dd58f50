"""% of the process's CPU seconds in the window that happen inside the
feed's and the loop's working phases (``reader.parse.cpu`` +
``reader.build.cpu`` + ``feed.stack.cpu`` + ``trainer.dispatch.cpu`` over
``process.cpu``). The rest burns beneath and between the calls that have a
name: the runtime's threads, the waits' polling, and in a traced run the
profiler's Python tracer."""

from benchmark.layer_metrics_cpu import named_cpu_share


def read(run):
    return named_cpu_share(run)
