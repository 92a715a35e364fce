"""Helpers of the readers of the program's own counters and span totals:
the change across the window of a value of ``IngestGateway.stats()``
(``block="gateway"``) or ``QueryPlanner.stats()`` (``"planner"``), as
the harness snapshots them at the window's edges.  None where the program
does not report the value (a program without spans)."""

from __future__ import annotations


def delta(run, block: str, key: str):
    a, b = run.before.get(block), run.after.get(block)
    if a is None or b is None or key not in a or key not in b:
        return None
    return b[key] - a[key]


def span_delta(run, block: str, name: str):
    """(Δcount, Δtotal_s) of one span name's totals, or None."""
    a = (run.before.get(block) or {}).get("spans")
    b = (run.after.get(block) or {}).get("spans")
    if a is None or b is None:
        return None
    zero = {"count": 0, "total_s": 0.0}
    ta, tb = a.get(name, zero), b.get(name, zero)
    return tb["count"] - ta["count"], tb["total_s"] - ta["total_s"]


def ms_per_span(run, block: str, name: str):
    """Mean milliseconds of one span over the window; None without one."""
    d = span_delta(run, block, name)
    if d is None or d[0] <= 0:
        return None
    return 1e3 * d[1] / d[0]
