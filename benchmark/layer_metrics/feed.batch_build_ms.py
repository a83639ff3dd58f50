"""One ``BatchBuilder.build_flat`` in a reader's thread, ms (hash, unique,
localize, bucket): the program's timer ``reader.build`` between the
window's snapshots, total over count."""

from benchmark.layer_metrics_host import ms_a_built_batch


def read(run):
    return ms_a_built_batch(run, "reader.build")
