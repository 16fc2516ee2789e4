"""End-to-end numbers from the load generator's records.

Every request due in the window counts.  An open-loop request's latency runs
from when it was due, not from when the generator fired it, so a stall
charges the requests queued behind it.  A request that was not answered 200
by the end of the wait (the window plus the grace) is a miss: it is ranked
above every answered request, at the latency it would have had if it were
answered at the end of the wait.
"""
from __future__ import annotations

import math


def latencies(plan_due: list, rows: dict, wait_end: float) -> list:
    """Seconds per due request; ``rows`` maps request index to its record."""
    out = []
    for i, due in enumerate(plan_due):
        row = rows.get(i)
        if answered(row) and row["done"] <= wait_end:
            out.append(row["done"] - due)
        else:
            out.append(wait_end - due)
    return out


def answered(row) -> bool:
    return (row is not None and row["status"] == 200
            and isinstance(row["body"], dict))


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]

