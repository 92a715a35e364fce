"""Median client round trip of the acknowledged POSTs sent inside the
window: a POST returns once its batch is queued, so this is the HTTP
layer's parse and the gateway's admission (host clock)."""

import _common


def read(run):
    kinds = ("ingest_closed", "ingest_open")
    ok = lambda r: (r["status"] == 200) & (r["t_send"] >= run.t0) & (r["t_send"] < run.t_end)  # noqa: E731
    rtt = _common.column(run, kinds, "t_done", ok) - _common.column(run, kinds, "t_send", ok)
    return _common.nearest_rank(rtt * 1e3, 0.5)
