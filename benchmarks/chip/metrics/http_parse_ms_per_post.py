"""Mean milliseconds of ``http.ingest.parse`` per POST over the window:
the body read, ``json.loads`` and validation of every parsed POST, those
the gateway then refuses included (program span totals)."""

import _program


def read(run):
    return _program.ms_per_span(run, "gateway", "http.ingest.parse")
