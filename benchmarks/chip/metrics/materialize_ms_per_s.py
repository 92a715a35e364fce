"""Milliseconds the window spent reading parked collapse outputs back
(``window.materialize_events``) per second of window; None when no drain
tick ran in it (program span totals)."""

import _program


def read(run):
    tick = _program.span_delta(run, "gateway", "gateway.tick")
    d = _program.span_delta(run, "gateway", "window.materialize_events")
    if tick is None or d is None or tick[0] <= 0:
        return None
    return 1e3 * d[1] / (run.t_end - run.t0)
