"""Mean milliseconds the gateway's drain loop waited between ticks:
Δ``drain_wait_s`` / Δ``ticks`` over the window -- the rest of a tick's
interval after a tick that finished inside it, and the wait on an empty
queue (program counters).  Near 0 when every tick overruns its interval."""

import _program


def read(run):
    wait = _program.delta(run, "gateway", "drain_wait_s")
    ticks = _program.delta(run, "gateway", "ticks")
    if wait is None or ticks is None or ticks <= 0:
        return None
    return 1e3 * wait / ticks
