"""Named host spans on the profiler's clock, with always-on totals.

``span(name, **args)`` is a context manager that does two things:

* it opens a ``jax.profiler.TraceAnnotation``: while a profiler session
  runs, the span lands on the host plane of the session's ``.xplane.pb``,
  beside the device ops and on their clock (the profiler keeps it in
  memory and writes it at ``stop_trace``); with no session it costs about
  a microsecond;
* it always adds to its name's totals: ``count``, ``total_s`` and
  ``self_s``, the total less the time its child spans on the same thread
  covered.

``totals(*prefixes)`` sums the totals of the names with those prefixes
over every thread, into a fresh dict.  The totals are process-wide.

No lock is taken: each thread adds only to its own table (keyed by thread
ident; a thread that is given a dead thread's ident carries on with its
table), and a table entry is replaced whole by one store, which the
interpreter lock makes atomic against a concurrent ``totals``.
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["span", "totals"]

# thread ident -> {name: (count, total_s, self_s)}
_tables: dict[int, dict[str, tuple[int, float, float]]] = {}
_local = threading.local()


class span:
    """One named host span (a context manager; see the module docstring).

    ``set(**args)`` adds arguments to the profiler's record of the span,
    for values known only once the work is done.  ``t1`` holds the span's
    end on ``time.monotonic``'s clock once it has closed.
    """

    __slots__ = ("name", "t1", "_ann", "_t0", "_child", "_table", "_stack")

    def __init__(self, name: str, **args):
        self.name = name
        self._ann = TraceAnnotation(name, **args)

    def set(self, **args) -> None:
        self._ann.set_metadata(**args)

    def __enter__(self) -> "span":
        try:
            self._stack, self._table = _local.stack, _local.table
        except AttributeError:
            self._stack = _local.stack = []
            self._table = _local.table = _tables.setdefault(threading.get_ident(), {})
        self._ann.__enter__()
        self._child = 0.0
        self._stack.append(self)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        dt = self.t1 - self._t0
        self._ann.__exit__(*exc)
        stack = self._stack
        stack.pop()
        if stack:
            stack[-1]._child += dt
        count, total, own = self._table.get(self.name, (0, 0.0, 0.0))
        self._table[self.name] = (count + 1, total + dt, own + dt - self._child)


def totals(*prefixes: str) -> dict[str, dict]:
    """``{name: {"count", "total_s", "self_s"}}`` summed over every thread,
    for the span names that start with one of ``prefixes``."""
    out: dict[str, dict] = {}
    for table in list(_tables.values()):
        for name, (count, total, own) in list(table.items()):
            if not name.startswith(prefixes):
                continue
            t = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            t["count"] += count
            t["total_s"] += total
            t["self_s"] += own
    return out
