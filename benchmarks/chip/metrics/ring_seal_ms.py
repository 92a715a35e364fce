"""Mean milliseconds of one slice seal under the ring lock: the donated
seal and merge-tree enqueues and the bank's reset (``window.ring_seal``),
Δ total / Δ count over the window (program span totals).  None where no
seal fell in the window, or the program has no such span."""

import _program


def read(run):
    return _program.ms_per_span(run, "gateway", "window.ring_seal")
