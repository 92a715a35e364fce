"""Values acknowledged (HTTP 200) to POSTs sent inside the window, over the
window's seconds, counted by the clients (host clock)."""

import _common


def read(run):
    kinds = ("ingest_closed", "ingest_open")
    ok = lambda r: (r["status"] == 200) & (r["t_send"] >= run.t0) & (r["t_send"] < run.t_end)  # noqa: E731
    n = _common.column(run, kinds, "n", ok)
    if n.size == 0:
        return None
    return float(n.sum()) / run.seconds
