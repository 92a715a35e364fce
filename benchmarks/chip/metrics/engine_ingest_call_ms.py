"""Mean milliseconds of one ``SketchEngine.ingest`` call over the window:
padding, host-to-device copies and the executable call, as long as the
host waits in it (program span totals)."""

import _program


def read(run):
    return _program.ms_per_span(run, "gateway", "engine.ingest")
