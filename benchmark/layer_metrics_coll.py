"""Device time under the scopes the program gives the step's two
collectives (``ps.pull/<table>/psum``, ``ps.push/<table>/all_gather``; no
table's name where the app has one unnamed table), for the ``coll.*``
readers."""

from benchmark.layer_metrics_scopes import phase_seconds


def collective_ms(run: dict, phase: str, name: str):
    """Milliseconds a chip and microstep of the window's ops whose scope
    path starts at ``phase`` and ends in ``name``; None where the window's
    programs name no such scope (a parent without it, a 1x1 mesh)."""
    by_scope = phase_seconds(run)
    n = run["facts"].get("microsteps")
    if not by_scope or not n:
        return None
    mine = [s for scope, s in by_scope.items() if scope.startswith(phase + "/") and scope.rsplit("/", 1)[1] == name]
    if not mine:
        return None
    return 1e3 * sum(mine) / max(run["trace"].chips, 1) / n
