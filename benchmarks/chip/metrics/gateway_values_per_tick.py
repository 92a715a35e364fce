"""Values the gateway ingested per engine call across the window: the
change in ``IngestGateway.stats()["ingested_values"]`` over the change in
``engine_calls`` (program counters)."""


def read(run):
    a, b = run.before["gateway"], run.after["gateway"]
    calls = b["engine_calls"] - a["engine_calls"]
    if calls <= 0:
        return None
    return (b["ingested_values"] - a["ingested_values"]) / calls
