"""95th percentile of every read due inside the window, from its due time
to its full reply (host clock; a failed read counts as the slowest)."""

import numpy as np

import _common


def read(run):
    recs = _common.records(run, "reads_open")
    lat = []
    for r in recs:
        inwin = (r["t_due"] >= run.t0) & (r["t_due"] < run.t_end)
        ms = (r["t_done"] - r["t_due"]) * 1e3
        ms = np.where(r["status"] == 200, ms, np.inf)
        lat.append(ms[inwin])
    if not lat:
        return None
    return _common.nearest_rank(np.concatenate(lat), 0.95)
