"""Share of the HBM roofline of the ingest step: the least time the bytes
every tick in the window needs (``work/ingest.py``: ``8 N + 8 U``) take at
the chip's peak bandwidth, over the device time of every execution of the
engine's ingest executable (``jit_ingest_impl``) in the traced window."""

import numpy as np

import _common


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace["module_s"].get("jit_ingest_impl", 0.0)
    if device_s <= 0:
        return None
    work = _common.load("work/ingest.py")
    peaks = _common.load("peaks.py")
    cfg = run.config
    level = run.key_level
    total = 0
    for kind, _v, t_start, _t_end, ids in run.log:
        if kind != "ingest" or not (run.t0 <= t_start < run.t_end):
            continue
        keys, vals = [], []
        for bid in ids:
            i = run.bodies.index.get(bid)
            if i is None:
                continue
            v = run.bodies.values[i]
            keys.append(np.full(v.size, run.key_row[run.bodies.keys[i]], np.int64))
            vals.append(v)
        if not vals:
            continue
        rows = np.concatenate(keys)
        values = np.concatenate(vals)
        u = work.distinct_cells(rows, values, cfg["relative_accuracy"], level[rows],
                                cfg["bucket_offset"], cfg["num_buckets"])
        total += work.needed_bytes(values.size, u)
    bw = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * (total / bw) / device_s
