"""What more than one per-layer reader needs."""

from __future__ import annotations

import re


def table_rows_per_chip(run: dict) -> int:
    rows = int(run["config"]["settings"]["num_keys"])
    return rows // int(run["facts"].get("kv_shards", 1))


def table_op_seconds(run: dict) -> float:
    """Seconds, per chip, of the ops whose HLO names an operand or result
    with the table's per-chip rows: the gathers from and scatters into
    ``z`` and ``n``, and any whole-table copy."""
    rows = table_rows_per_chip(run)
    pat = re.compile(rf"\[{rows}(,1)?\]")
    total = sum(sec for name, (sec, _) in run["trace"].ops.items() if pat.search(name))
    return total / max(run["trace"].chips, 1)
