"""Device time under one of the program's nested scope names, for readers of
scopes only some programs have (a table's, a dense group's)."""

from benchmark.layer_metrics_scopes import phase_ms, phase_seconds


def named_phase_ms(run: dict, scope: str):
    """``phase_ms`` of ``scope``, or None where the window's programs name
    no such scope (a parent without it, another app)."""
    by_scope = phase_seconds(run)
    if not by_scope or not any(s == scope or s.startswith(scope + "/") for s in by_scope):
        return None
    return phase_ms(run, scope)
