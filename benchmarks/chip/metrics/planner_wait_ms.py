"""Mean milliseconds an uncached read waited in the query planner, from
its submit to its result (coalesce sleep and fused dispatches included):
Δ``planner.wait`` total / count over the window (program span totals)."""

import _program


def read(run):
    return _program.ms_per_span(run, "planner", "planner.wait")
