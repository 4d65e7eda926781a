"""The arithmetic of the end-to-end metrics (no I/O, no clock)."""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or below it."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return s[max(math.ceil(q * len(s)) - 1, 0)]


def supports(n: int, q: float, beyond: int = 10) -> bool:
    """A percentile is reported with at least ten samples beyond it."""
    return n - math.ceil(q * n) >= beyond


def latencies_ms(records: list, penalty_ms: float) -> list:
    """Completion minus the time the request was due, for every request of
    the window; a failed, refused or wrong request counts as `penalty_ms`
    (beyond any percentile that a sound run reports)."""
    return [(r["done"] - r["due"]) * 1e3 if r["ok"] else penalty_ms
            for r in records]


def completed_qps(records: list, t_open: float, seconds: float,
                  whole_batches: bool) -> tuple:
    """(queries/s, queries counted). Counts the correct queries that
    completed inside the window. A single closed-loop client of batches
    stops the clock at its last whole batch; otherwise the clock runs the
    whole window."""
    inside = [r for r in records
              if r["ok"] and r["done"] - t_open <= seconds]
    n = sum(r["queries"] for r in inside)
    if not inside:
        return 0.0, 0
    span = (max(r["done"] for r in inside) - t_open) if whole_batches \
        else seconds
    return n / span, n
