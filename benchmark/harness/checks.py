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


def element_gaps(got, want, scale=None):
    """|got - want| of every element against max(|want|, median |want|):
    some rows are all but zero. ``scale``, where given, is a further floor
    an element: the size of what was summed into it, for a value that is
    what a sum of far larger terms left over."""
    import numpy as np

    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape or not np.isfinite(got).all():
        return np.full(max(want.size, 1), np.inf)
    mag = np.abs(want)
    nz = mag[mag > 0]
    mag = np.maximum(mag, float(np.median(nz)) if nz.size else 1.0)
    if scale is not None:
        mag = np.maximum(mag, np.asarray(scale, np.float64).ravel())
    return np.abs(got - want) / mag


def worst_gap(got, want, scale=None) -> float:
    """The largest of ``element_gaps``; 0 where there is nothing to compare."""
    import numpy as np

    return float(np.max(element_gaps(got, want, scale))) if np.size(want) else 0.0


def norm_gap(got, want) -> float:
    """| ||got|| - ||want|| | / ||want||: the gap between the two norms,
    not the norm of the difference."""
    import numpy as np

    a = float(np.linalg.norm(np.asarray(got, np.float64)))
    b = float(np.linalg.norm(np.asarray(want, np.float64)))
    return abs(a - b) / b if b > 0 else abs(a)
