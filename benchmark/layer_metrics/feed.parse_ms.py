"""Read + native parse a built batch, ms: the program's timer
``reader.parse`` (one step of ``iter_chunks`` in a reader's thread: a 2 MiB
chunk read, parsed and merged with the rows left over) between the window's
snapshots, over the batches the reader threads built in the window."""

from benchmark.layer_metrics_host import ms_a_built_batch


def read(run):
    return ms_a_built_batch(run, "reader.parse")
