"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

``Tracer`` records one window with JAX's profiler (host tracer on, Python
tracer off) and marks the window's edges with host spans
(``bench.window_start`` / ``bench.window_end``), so every interval below is
clipped to the measured window on the trace's own clock.  ``reduce`` reads
the file with ``jax.profiler.ProfileData`` and returns:

* ``window_s`` -- the window's length between the two marks;
* ``busy_s``   -- the union of the intervals in which an operation ran on
  each TPU (its ``XLA Ops`` line), averaged over the chips;
* ``module_s`` -- device seconds of each compiled module (``XLA Modules``
  line, the name without its ``(id)`` suffix), summed over chips;
* ``in_span_s`` -- device seconds of each module's executions by the
  innermost ``bench.*`` host span that encloses them, keyed
  ``"<span>|<module>"`` (a blocking read's span encloses its own device
  work);
* ``breakdown`` -- the ten device ops that took the most time (named
  ``<module>/<HLO instruction>``), and the ten
  longest idle gaps of chip 0, each named by the ``bench.*`` host span that
  covers most of it (``no bench span`` where none does).
"""

from __future__ import annotations

import re
import shutil
from pathlib import Path

import numpy as np

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


class Tracer:
    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)

    @staticmethod
    def mark(name: str) -> None:
        import jax

        with jax.profiler.TraceAnnotation(name):
            pass

    def stop(self) -> dict:
        import jax

        jax.profiler.stop_trace()
        files = sorted(self.out_dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.out_dir}")
        try:
            return reduce(files[-1])
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of ``(n, 2)`` [start, end) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = []
    s, e = iv[0]
    for a, b in iv[1:]:
        if a > e:
            out.append((s, e))
            s, e = a, b
        elif b > e:
            e = b
    out.append((s, e))
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.size == 0:
        return iv.reshape(0, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return c[c[:, 1] > c[:, 0]]


def load(path) -> dict:
    """The trace's raw events: device ops and modules per chip, host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips: dict[int, dict] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind is None:
                    continue
                for e in line.events:
                    chip[kind].append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"chips": chips, "spans": spans}


def reduce(path) -> dict:
    return summarise(load(path))


def summarise(raw: dict) -> dict:
    spans = raw["spans"]
    marks = {n: s for n, s, _ in spans if n in ("bench.window_start", "bench.window_end")}
    if len(marks) != 2:
        raise RuntimeError(f"trace lacks the window marks (found {sorted(marks)})")
    w0, w1 = marks["bench.window_start"], marks["bench.window_end"]
    window_ns = w1 - w0
    work = [(s, e, n) for n, s, e in spans if n not in marks]
    w_start = np.asarray([s for s, _, _ in work], np.float64)
    w_end = np.asarray([e for _, e, _ in work], np.float64)
    w_len = w_end - w_start
    chips = raw["chips"]
    if not chips:
        raise RuntimeError("trace has no TPU device plane")
    busy, module_ns, in_span, op_ns = [], {}, {}, {}
    gaps0 = None
    for cid in sorted(chips):
        ops = chips[cid]["ops"] or chips[cid]["modules"]
        iv = _clip(np.asarray([(s, e) for _, s, e in ops], np.float64).reshape(-1, 2),
                   w0, w1)
        u = _union(iv)
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        if gaps0 is None:
            edges = np.concatenate([[w0], u.reshape(-1), [w1]]).reshape(-1, 2)
            gaps0 = edges[edges[:, 1] > edges[:, 0]]
        mods = sorted(chips[cid]["modules"], key=lambda t: t[1])
        m_start = np.asarray([s for _, s, _ in mods], np.float64)
        m_end = np.asarray([e for _, _, e in mods], np.float64)
        for name, s, e in chips[cid]["ops"]:
            d = min(e, w1) - max(s, w0)
            if d <= 0:
                continue
            i = int(np.searchsorted(m_start, s, side="right")) - 1
            mod = _SUFFIX.sub("", mods[i][0]) if i >= 0 and s <= m_end[i] else "?"
            op = f"{mod}/{name.split(' = ')[0].lstrip('%')}"
            op_ns[op] = op_ns.get(op, 0.0) + d
        for name, s, e in chips[cid]["modules"]:
            d = min(e, w1) - max(s, w0)
            if d <= 0:
                continue
            mod = _SUFFIX.sub("", name)
            module_ns[mod] = module_ns.get(mod, 0.0) + d
            mid = 0.5 * (s + e)
            inside = np.flatnonzero((w_start <= mid) & (mid <= w_end))
            if inside.size:  # the innermost enclosing span
                owner = f"{work[inside[np.argmin(w_len[inside])]][2]}|{mod}"
                in_span[owner] = in_span.get(owner, 0.0) + d
    gaps = sorted(((float(b - a), a, b) for a, b in gaps0), reverse=True)[:10]
    idle = []
    for length, a, b in gaps:
        best, cover = "no bench span", 0.0
        for s, e, n in work:
            o = min(b, e) - max(a, s)
            if o > cover:
                best, cover = n, o
        idle.append([best, length / 1e9])
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": float(np.mean(busy)) / 1e9,
        "chips": len(chips),
        "module_s": {k: v / 1e9 for k, v in module_ns.items()},
        "in_span_s": {k: v / 1e9 for k, v in in_span.items()},
        "window_ns": (w0, w1),
        "breakdown": {"device_ops": [[n, v / 1e9] for n, v in top_ops],
                      "idle_gaps": idle},
    }
