"""Fused device dispatches the query planner issued per read across the
window: the change in ``QueryPlanner.stats()["dispatches"]`` over the
change in ``requests`` (program counters)."""


def read(run):
    if "planner" not in run.before:
        return None
    a, b = run.before["planner"], run.after["planner"]
    reqs = b["requests"] - a["requests"]
    if reqs <= 0:
        return None
    return (b["dispatches"] - a["dispatches"]) / reqs
