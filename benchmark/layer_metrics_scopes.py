"""Device time by the program's own phase names, for the ``step.*``
readers: joins the trace's per-op seconds (keyed by HLO text,
``%fusion.68 = f32[...] fusion(...)``) with the program's
``parallel.spmd.op_scopes()`` (module -> instruction name -> ``ps.*`` scope
path, read from the optimised HLO of the executables that ran). Asking for
the scopes compiles the programs again - a fetch from the persistent cache -
which is why it happens here, after the window."""

from __future__ import annotations

import re

_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_MODULE_RUN = re.compile(r"\(\d+\)$")  # "jit__jitted(3676848758191281982)"
UNSCOPED = ""


def scope_map(run: dict):
    """{instruction name: scope path} over the programs the window ran, or
    None where the program has no ``op_scopes`` (a parent without the
    names) or knows none of the window's programs. A name that two of the
    window's programs scope differently counts as unscoped."""
    try:
        from parameter_server_tpu.parallel import spmd
    except ImportError:
        return None
    op_scopes = getattr(spmd, "op_scopes", None)
    if op_scopes is None:
        return None
    by_module = op_scopes()
    merged: dict = {}
    found = False
    for module_run in run["trace"].modules:
        scopes = by_module.get(_MODULE_RUN.sub("", module_run))
        if scopes is None:
            continue
        found = True
        for name, scope in scopes.items():
            merged[name] = scope if merged.get(name, scope) == scope else UNSCOPED
    return merged if found else None


def seconds_by_scope(ops: dict, scopes: dict) -> dict:
    """{scope path: seconds} of ``ops`` ({HLO text: [seconds, count]});
    what maps to no scope is under ``UNSCOPED``."""
    out: dict = {}
    for text, (seconds, _) in ops.items():
        m = _INSTRUCTION.match(text)
        scope = scopes.get(m.group(1), UNSCOPED) if m else UNSCOPED
        out[scope] = out.get(scope, 0.0) + seconds
    return out


def phase_seconds(run: dict):
    """``seconds_by_scope`` of the run's window, summed over the chips;
    kept on the run, so the six readers compile once. None: no names."""
    if "_phase_seconds" not in run:
        scopes = scope_map(run)
        run["_phase_seconds"] = None if scopes is None else seconds_by_scope(run["trace"].ops, scopes)
    return run["_phase_seconds"]


def phase_ms(run: dict, prefix: str):
    """Milliseconds a chip and microstep of the window's ops whose scope
    path is ``prefix`` or lies under it, as ``step.device_ms`` divides."""
    by_scope = phase_seconds(run)
    n = run["facts"].get("microsteps")
    if by_scope is None or not n:
        return None
    seconds = sum(s for scope, s in by_scope.items() if scope == prefix or scope.startswith(prefix + "/"))
    return 1e3 * seconds / max(run["trace"].chips, 1) / n


def timer_share(run: dict, name: str, threads: int = 1):
    """The program's named timer ``name`` over the window, as a share in %
    of the window's time on ``threads`` threads; None where the program has
    no such timer."""
    t = run["timers"].get(name)
    if t is None:
        return None
    return 100.0 * t["total_s"] / (run["window"]["elapsed_s"] * threads)


def process_timer_ms(name: str, less: str = ""):
    """Mean milliseconds of the program's named timer ``name`` over the
    whole process (the ``eval`` kind takes no snapshots at its stamps),
    after taking off the total of the timer ``less`` where there is one:
    time that ``name`` encloses and that belongs to set-up."""
    try:
        from parameter_server_tpu.utils.metrics import timers
    except ImportError:
        return None
    snap = timers.snapshot()
    t = snap.get(name)
    if t is None or not t["count"]:
        return None
    inside = snap.get(less, {"total_s": 0.0})["total_s"]
    return 1e3 * (t["total_s"] - inside) / t["count"]
