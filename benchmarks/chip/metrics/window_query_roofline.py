"""Share of the HBM roofline of the windowed reads: the least time the
bytes each windowed dispatch in the window needs
(``work/window_query.py``: ``(1 + c(w)) K 2 m 4``) take at the chip's peak
bandwidth, over the device time of the window executables
(``jit_query_impl`` inside ``bench.window_query`` spans, ``jit_rollup_impl``
inside ``bench.window_rollup`` spans) in the traced window."""

import _common


def read(run):
    if run.trace is None or not run.config.get("num_slices"):
        return None
    span = run.trace["in_span_s"]
    device_s = (span.get("bench.window_query|jit_query_impl", 0.0)
                + span.get("bench.window_rollup|jit_rollup_impl", 0.0))
    if device_s <= 0:
        return None
    work = _common.load("work/window_query.py")
    peaks = _common.load("peaks.py")
    cfg = run.config
    total = 0
    for kind, sealed, w, t_start, _t_end in run.reads:
        if kind in ("window_query", "window_rollup") and run.t0 <= t_start < run.t_end:
            total += work.needed_bytes(sealed, w, cfg["num_slices"], run.rows,
                                       cfg["num_buckets"])
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / device_s
