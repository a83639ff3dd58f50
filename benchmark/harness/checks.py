"""The numbers that decide ``correct``: each with a limit of its own, each
printed beside that limit in every run."""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass
class Check:
    name: str
    value: float
    limit: float  # passes when value <= limit
    note: str = ""

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

    def line(self) -> str:
        return (
            f"[check] {self.name}: {self.value:.6g} <= {self.limit:.6g} "
            f"{'ok' if self.ok else 'FAILED'}{'  # ' + self.note if self.note else ''}"
        )


def worst_gap(got, want) -> float:
    """Largest |got - want| measured against max(|want|, median |want|),
    row by row: some rows are all but zero."""
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    if want.size == 0:
        return 0.0
    mag = np.abs(want)
    nz = mag[mag > 0]
    floor = float(np.median(nz)) if nz.size else 1.0
    return float(np.max(np.abs(got - want) / np.maximum(mag, floor)))


def norm_gap(got, want) -> float:
    """| ||got|| - ||want|| | / ||want||: the gap between the two norms,
    not the norm of the difference."""
    import numpy as np

    a = float(np.linalg.norm(np.asarray(got, np.float64)))
    b = float(np.linalg.norm(np.asarray(want, np.float64)))
    return abs(a - b) / b if b > 0 else abs(a)
