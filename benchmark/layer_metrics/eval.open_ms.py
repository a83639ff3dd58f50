"""Mean start-up of an ``evaluate_files`` pass, ms: the program's timer
``eval.open`` (entry to the return of the first predict call: builder,
reader, first parse + build, stack, H2D, enqueue), during which the device
idles. Total over count across ALL passes of the process, the warm one
included: the ``eval`` kind takes no timer snapshots at its stamps, and the
cell's passes are identical by construction (``eval.passes_differ`` is 0) -
but for the warm pass's first predict call, which compiles or fetches the
program and belongs to set-up: the program times that call as
``eval.new_shapes``, and its total is taken off."""

from benchmark.layer_metrics_scopes import process_timer_ms


def read(run):
    return process_timer_ms("eval.open", less="eval.new_shapes")
