"""CPU milliseconds the host spends on a built batch, over all the threads
that touch it: ``reader.parse.cpu`` + ``reader.build.cpu`` +
``feed.stack.cpu`` + ``trainer.dispatch.cpu`` over the count of
``reader.build``; in the evaluator ``eval.stack.cpu`` + ``eval.enqueue.cpu``
(less ``eval.new_shapes.cpu``, the warm pass's compile) stand in the last
two's place. What a batch costs whatever the number of threads: the floor a
better-threaded feed could reach, and times the chip's batches a second the
cores it needs."""

from benchmark.layer_metrics_cpu import cpu_ms_a_built_batch


def read(run):
    return cpu_ms_a_built_batch(run)
