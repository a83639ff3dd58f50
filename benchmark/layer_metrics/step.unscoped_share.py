"""Share, in %, of the window's device op seconds that map to no ``ps.*``
scope of the program (input copies, loop bookkeeping, an instruction two
programs scope differently): what the ``step.*_ms`` readers do not see."""

from benchmark.layer_metrics_scopes import UNSCOPED, phase_seconds


def read(run):
    by_scope = phase_seconds(run)
    if not by_scope:
        return None
    return 100.0 * by_scope.get(UNSCOPED, 0.0) / sum(by_scope.values())
