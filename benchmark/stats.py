"""Percentile arithmetic for request timings. A request that failed,
was refused or did not finish has no timing: it counts as the worst."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional

WORST = math.inf


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of ``values``; ``inf``
    entries (failures) sort last, so they are the tail."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def with_failures(values: List[Optional[float]]) -> List[float]:
    """Timings with every missing one (None) replaced by the worst."""
    return [WORST if v is None else v for v in values]


def median(values: Iterable[float]) -> float:
    return percentile(values, 50.0)


def supported_percentile(n: int) -> float:
    """The highest of (99, 95, 90, 50) with ten samples beyond it."""
    for q in (99.0, 95.0, 90.0):
        if n * (100.0 - q) / 100.0 >= 10:
            return q
    return 50.0
