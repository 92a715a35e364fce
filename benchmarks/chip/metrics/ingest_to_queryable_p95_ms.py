"""95th percentile over the probes due inside the window, from the probe
POST's due time to the end of the first read that reflects it (host clock;
resolution: the probe stream's poll interval; an unseen probe counts as
the slowest)."""

import numpy as np

import _common


def read(run):
    recs = _common.records(run, "probes")
    lat = []
    for r in recs:
        inwin = (r["t_due"] >= run.t0) & (r["t_due"] < run.t_end)
        ms = (r["t_seen"] - r["t_due"]) * 1e3
        lat.append(np.where(np.isnan(ms), np.inf, ms)[inwin])
    if not lat:
        return None
    return _common.nearest_rank(np.concatenate(lat), 0.95)
