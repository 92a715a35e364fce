"""Share of the parsed values the gateway admitted over the window:
100 Δ``accepted_values`` / Δ(``accepted_values`` + ``rejected_values``)
(program counters)."""

import _program


def read(run):
    acc = _program.delta(run, "gateway", "accepted_values")
    rej = _program.delta(run, "gateway", "rejected_values")
    if acc is None or rej is None or acc + rej <= 0:
        return None
    return 100.0 * acc / (acc + rej)
