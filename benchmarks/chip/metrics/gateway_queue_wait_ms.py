"""Mean milliseconds a batch waited in the gateway's queue, from its
admission to the drain tick that took it: Δ``queue_wait_s`` /
Δ``batches_taken`` over the window (program counters)."""

import _program


def read(run):
    wait = _program.delta(run, "gateway", "queue_wait_s")
    taken = _program.delta(run, "gateway", "batches_taken")
    if wait is None or taken is None or taken <= 0:
        return None
    return 1e3 * wait / taken
