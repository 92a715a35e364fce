"""Helpers the metric readers share (client records by stream kind)."""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parent.parent


def records(run, kind: str) -> list[dict]:
    return [recs for s, _, recs, _ in run.results if s["kind"] == kind]


def column(run, kinds, col: str, where=None) -> np.ndarray:
    """One column of every record of the given stream kinds, filtered."""
    parts = []
    for kind in kinds:
        for recs in records(run, kind):
            if recs.get(col) is None or recs[col].size == 0:
                continue
            keep = np.ones(recs[col].size, bool) if where is None else where(recs)
            parts.append(recs[col][keep])
    return np.concatenate(parts) if parts else np.zeros(0)


def nearest_rank(x: np.ndarray, q: float) -> float | None:
    """The nearest-rank ``q`` quantile (the value with ``ceil(q n)`` at or
    below it); None when there is no sample."""
    if x.size == 0:
        return None
    s = np.sort(x)
    return float(s[max(0, math.ceil(q * s.size) - 1)])


def in_window(run, col: str):
    return lambda r: (r[col] >= run.t0) & (r[col] < run.t_end)


def load(rel: str):
    """A module of the benchmark by its path (``work/ingest.py``, ``peaks.py``)."""
    spec = importlib.util.spec_from_file_location(rel.replace("/", "_"), _ROOT / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def idle_pct(run):
    """Share of the traced window in which no operation ran on the device:
    ``1 - busy / window``, busy being the union of the ``XLA Ops`` intervals
    (averaged over the chips)."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
