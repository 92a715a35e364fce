"""Thin proxies around the program's window and its snapshots.

The gateway drains into ``WindowProxy`` and the query planner reads through
it, so the benchmark sees every call into the window layer without an edit
to the program:

* host spans (``jax.profiler.TraceAnnotation``, traced runs only) around
  ``record_batches``, ``publish``, ``advance_slice``, ``snapshot`` and each
  fused read a snapshot dispatches;
* a log of the state changes in order: each ingest tick with the identity
  of every batch in it (key, size and three of its values), each slice seal
  and reset, each with the window version it produced; and a log of the
  snapshot reads (kind, captured seal count, window slices, host times).

The reference uses the state log only to place each acknowledged POST in
its slice; the values come from the reference's own copy of the bodies.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np


def _span(enabled: bool, name: str):
    if not enabled:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def batch_id(key: str, values) -> tuple:
    """(key, size, first, middle, last value) of a float32 batch."""
    v = np.asarray(values, np.float32).reshape(-1)
    n = v.size
    return (key, n, float(v[0]), float(v[n // 2]), float(v[-1]))


class SnapshotProxy:
    """A ``BankSnapshot`` whose fused reads are spanned and logged."""

    def __init__(self, snap, owner: "WindowProxy"):
        self._snap = snap
        self._owner = owner

    def __getattr__(self, name):
        return getattr(self._snap, name)

    def _read(self, kind: str, fn, qs, slices=None, window=None):
        w = None
        if slices is not None or window is not None:
            w = self._snap.window.resolve_window(window=window, slices=slices)
        t0 = time.monotonic()
        with _span(self._owner.spans, f"bench.{kind}"):
            if w is None:
                out = fn(qs)
            else:
                out = fn(qs, slices=w)
        self._owner.reads.append((kind, self._snap.sealed, w, t0, time.monotonic()))
        return out

    def row_quantiles(self, qs):
        return self._read("row_quantiles", self._snap.row_quantiles, qs)

    def windowed_row_quantiles(self, qs, *, window=None, slices=None):
        return self._read("window_query", self._snap.windowed_row_quantiles, qs,
                          slices, window)

    def rollup_quantiles(self, qs):
        return self._read("rollup", self._snap.rollup_quantiles, qs)

    def windowed_rollup(self, qs, *, window=None, slices=None):
        return self._read("window_rollup", self._snap.windowed_rollup, qs,
                          slices, window)


class WindowProxy:
    """The ``KeyedWindow`` the gateway and the planner are given."""

    def __init__(self, window, *, spans: bool = False):
        self._w = window
        self.spans = spans
        # ("ingest" | "seal" | "reset", version after, t_start, t_end, batch ids)
        self.log: list[tuple] = []
        self.reads: list[tuple] = []
        # (kind, t_start, thread id) of the state change under way, if any
        self.busy: tuple | None = None
        self._lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._w, name)

    @property
    def version(self) -> int:
        return self._w.version

    def _event(self, kind: str, span: str, fn, ids=()):
        t0 = time.monotonic()
        self.busy = (kind, t0, threading.get_ident())
        try:
            with _span(self.spans, span):
                out = fn()
        finally:
            self.busy = None
        with self._lock:
            self.log.append((kind, self._w.version, t0, time.monotonic(), ids))
        return out

    def record_batches(self, batches) -> int:
        batches = list(batches)
        ids = [batch_id(k, v) for k, v, _ in batches if np.size(v)]
        return self._event("ingest", "bench.record_batches",
                           lambda: self._w.record_batches(batches), ids)

    def advance_slice(self) -> int:
        return self._event("seal", "bench.advance_slice", self._w.advance_slice)

    def reset(self) -> None:
        return self._event("reset", "bench.reset", self._w.reset)

    def publish(self) -> int:
        with _span(self.spans, "bench.publish"):
            return self._w.publish()

    def snapshot(self):
        with _span(self.spans, "bench.snapshot"):
            return SnapshotProxy(self._w.snapshot(), self)
