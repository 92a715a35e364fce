"""Mean milliseconds a windowed read spent enqueueing on the live ring
slab: taking the ring lock, checking its cover, enqueueing the fused read
(``window.ring_read``), Δ total / Δ count over the window (program span
totals).  None where no windowed read fell in the window, or the program
has no such span."""

import _program


def read(run):
    return _program.ms_per_span(run, "gateway", "window.ring_read")
