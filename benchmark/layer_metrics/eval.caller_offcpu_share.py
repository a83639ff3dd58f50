"""% of the seconds the evaluator's caller WORKS (``eval.open_reader`` +
``eval.stack`` + ``eval.enqueue`` + ``eval.score``; not ``eval.read`` and
``eval.retire``, which are waits by design) in which its thread was on no
CPU; the warm pass's compile (``eval.new_shapes``) comes off both sides, as
``eval.unnamed_share`` takes it. The twins count the session's passes (the
window's); the wall seconds are the process's, as the other ``eval.*``
readers', taken for as many units, until the ``eval`` kind snapshots at its
stamps."""

from benchmark.layer_metrics_cpu import EVAL_CALLER, EVAL_SETUP, offcpu_share


def read(run):
    return offcpu_share(run, EVAL_CALLER, less=EVAL_SETUP)
