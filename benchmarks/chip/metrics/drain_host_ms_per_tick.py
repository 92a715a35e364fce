"""Mean milliseconds of a drain tick outside the engine's ingest call:
(Δ``gateway.tick`` total − Δ``engine.ingest`` total) / Δ``gateway.tick``
count over the window -- the grab, row ids and concatenation, the parked
events' read-back and the publish (program span totals)."""

import _program


def read(run):
    tick = _program.span_delta(run, "gateway", "gateway.tick")
    call = _program.span_delta(run, "gateway", "engine.ingest")
    if tick is None or call is None or tick[0] <= 0:
        return None
    return 1e3 * (tick[1] - call[1]) / tick[0]
