"""Telemetry: device recorder, host aggregator rollups, keyed per-metric
windows, watchdog guards."""


import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.ddsketch import DDSketch
from repro.telemetry import (
    OVERFLOW_KEY,
    HostAggregator,
    KeyedAggregator,
    KeyedWindow,
    LossSpikeGuard,
    StragglerWatchdog,
    TelemetryConfig,
    init_telemetry,
    record,
)


def test_record_and_flush_matches_direct_sketch(rng):
    tcfg = TelemetryConfig()
    state = init_telemetry(tcfg)
    data1 = (rng.pareto(1.0, 512) + 1).astype(np.float32)
    data2 = (rng.pareto(1.0, 512) + 1).astype(np.float32)
    state = record(state, {"token_loss": jnp.asarray(data1)}, tcfg)
    state = record(state, {"token_loss": jnp.asarray(data2)}, tcfg)

    agg = HostAggregator(tcfg.spec)
    win = agg.flush(state, 0, 2)

    direct = DDSketch(tcfg.spec.relative_accuracy, max_bins=None)
    direct.extend(np.concatenate([data1, data2]))
    for q in (0.5, 0.95, 0.99):
        assert win.sketches["token_loss"].quantile(q) == pytest.approx(
            direct.quantile(q), rel=1e-6
        )


def test_nan_masked_losses_ignored():
    tcfg = TelemetryConfig()
    state = init_telemetry(tcfg)
    vals = jnp.asarray([1.0, jnp.nan, 2.0, jnp.nan], jnp.float32)
    state = record(state, {"token_loss": vals}, tcfg)
    assert float(state.sketches["token_loss"].count) == 2


def test_rollup_equals_union(rng):
    """Windows roll up losslessly (Algorithm 4) — 1s->1min claim (§1)."""
    tcfg = TelemetryConfig()
    agg = HostAggregator(tcfg.spec)
    alldata = []
    for w in range(5):
        state = init_telemetry(tcfg)
        d = (rng.lognormal(0, 2, 256)).astype(np.float32)
        alldata.append(d)
        state = record(state, {"token_loss": jnp.asarray(d)}, tcfg)
        agg.flush(state, w, w + 1)
    direct = DDSketch(tcfg.spec.relative_accuracy, max_bins=None)
    direct.extend(np.concatenate(alldata))
    roll = agg.rollup("token_loss")
    for q in (0.25, 0.5, 0.9, 0.99):
        assert roll.quantile(q) == pytest.approx(direct.quantile(q), rel=1e-6)
    # last-2-window rollup sees only its windows
    roll2 = agg.rollup("token_loss", last_k=2)
    assert roll2.count == 512


def test_aggregator_state_roundtrip(rng):
    tcfg = TelemetryConfig()
    agg = HostAggregator(tcfg.spec)
    state = init_telemetry(tcfg)
    state = record(
        state, {"token_loss": jnp.asarray(rng.pareto(1.0, 100).astype(np.float32) + 1)}, tcfg
    )
    agg.flush(state, 0, 1)
    agg2 = HostAggregator.from_state_dict(agg.state_dict())
    assert agg2.totals["token_loss"].quantile(0.5) == agg.totals[
        "token_loss"
    ].quantile(0.5)


# --------------------------------------------------------------------- #
# keyed per-metric windows (SketchBank-backed)
# --------------------------------------------------------------------- #
def test_keyed_window_flush_matches_direct_per_key(rng):
    tcfg = TelemetryConfig()
    window = KeyedWindow(tcfg.spec, capacity=8)
    agg = KeyedAggregator(tcfg.spec)
    keys = ["/chat", "/embed", "/rank"]
    direct = {k: DDSketch(tcfg.spec.relative_accuracy, max_bins=None) for k in keys}
    for _ in range(3):  # three flush intervals
        ks = [keys[i] for i in rng.integers(0, 3, 500)]
        vals = (rng.pareto(1.0, 500) + 1.0).astype(np.float32)
        for k, v in zip(ks, vals):
            direct[k].add(float(v))
        window.record(ks, vals)
        agg.flush(window)
    assert sorted(agg.keys()) == sorted(keys)
    for k in keys:
        for q in (0.5, 0.95, 0.99):
            assert agg.quantiles(k, [q])[0] == pytest.approx(
                direct[k].quantile(q), rel=1e-6
            )
        assert agg.totals[k].count == direct[k].count


def test_keyed_window_single_key_and_local_query(rng):
    window = KeyedWindow(TelemetryConfig().spec, capacity=4)
    vals = (rng.pareto(1.0, 300) + 1.0).astype(np.float32)
    window.record("gpu0", vals)  # single string key broadcast to the batch
    p50 = window.quantiles("gpu0", [0.5])[0]
    assert p50 == pytest.approx(float(np.quantile(vals, 0.5, method="lower")), rel=0.011)
    with pytest.raises(KeyError):
        window.quantiles("never-seen", [0.5])


def test_keyed_window_overflow_collapses_not_raises(rng):
    """More distinct keys than capacity: the surplus lands in OVERFLOW_KEY
    (static bank shape survives), nothing is dropped or raised."""
    window = KeyedWindow(TelemetryConfig().spec, capacity=2)
    agg = KeyedAggregator(window.spec)
    for i in range(5):
        window.record(f"key{i}", np.full(10, float(i + 1), np.float32))
    assert sorted(window.keys()) == ["key0", "key1"]
    agg.flush(window)
    assert agg.totals[OVERFLOW_KEY].count == 30  # key2..key4 collapsed
    assert agg.totals["key0"].count == 10
    # stable keys keep their rows across windows after flush/reset
    window.record("key1", np.ones(7, np.float32))
    agg.flush(window)
    assert agg.totals["key1"].count == 17


def test_collapse_transition_events(rng):
    """Every auto-collapse logs (key, old->new level, window, clamped mass)
    and the events survive the flush into the aggregator."""
    tcfg = TelemetryConfig()
    window = KeyedWindow(tcfg.spec, capacity=4)
    agg = KeyedAggregator(window.spec)
    narrow = (rng.pareto(1.0, 100) + 1.0).astype(np.float32)
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 400)).astype(np.float32)
    window.record("cold", narrow)
    assert list(window.events) == []  # nothing clamped, nothing logged
    window.record("hot", wide)
    events = list(window.events)
    assert events, "the 24-decade stream must trigger at least one collapse"
    assert {e.key for e in events} == {"hot"}
    assert events[0].old_level == 0 and events[0].new_level == 1
    assert events[0].window == 0
    assert events[0].clamped_mass > 0
    # consecutive transitions chain (old == previous new)
    for prev, nxt in zip(events, events[1:]):
        assert nxt.old_level == prev.new_level
    # levels()/alphas() agree with the last transition
    assert window.levels()["hot"] == events[-1].new_level

    agg.flush(window)  # drains the window log into the aggregator
    assert list(window.events) == []
    assert [e.key for e in agg.events_for("hot")] == ["hot"] * len(events)
    assert agg.events_for("cold") == []

    # next window: events carry the new window index, levels chain on
    window.record("hot", wide * 1e3)  # pushes past the adapted range again
    later = [e for e in window.events]
    for e in later:
        assert e.window == 1
        assert e.old_level >= events[-1].new_level


def test_collapse_events_transfer_in_one_batch_per_kind(rng, monkeypatch):
    """Parked (fired, clamped) outputs reach the host in one batched
    ``device_get`` of every tick's ``fired`` and one of the ``clamped`` of
    the ticks that fired, and log exactly what per-tick readbacks log."""
    import jax

    wide = (10.0 ** rng.uniform(-15.0, 9.0, 400)).astype(np.float32)
    narrow = (rng.pareto(1.0, 100) + 1.0).astype(np.float32)
    ticks = [("cold", narrow), ("hot", wide), ("cold", narrow), ("hot", wide * 1e3)]

    step = KeyedWindow(TelemetryConfig().spec, capacity=4)
    want, fired_ticks = [], 0
    for key, v in ticks:
        step.record(key, v)
        new = list(step.events)[len(want):]
        fired_ticks += bool(new)
        want += new
    assert 0 < fired_ticks < len(ticks)

    window = KeyedWindow(TelemetryConfig().spec, capacity=4)
    for key, v in ticks:
        window.record(key, v)
    real, calls = jax.device_get, []

    def counted(x):
        calls.append(len(x))
        return real(x)

    monkeypatch.setattr(jax, "device_get", counted)
    assert list(window.events) == want
    assert calls == [len(ticks), fired_ticks]


def test_collapse_events_disabled(rng):
    window = KeyedWindow(
        TelemetryConfig().spec, capacity=2, track_collapse_events=False
    )
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 400)).astype(np.float32)
    window.record("hot", wide)
    assert list(window.events) == []  # host materialization skipped
    assert window.levels()["hot"] >= 1  # ...but the collapse itself happened


def test_straggler_watchdog(rng):
    wd = StragglerWatchdog(ratio_threshold=1.5, min_samples=8)
    for step in range(32):
        for h in range(4):
            base = 0.10 if h != 2 else 0.25  # host2 is 2.5x slower
            wd.observe(f"host{h}", base + rng.normal(0, 0.002))
    assert wd.stragglers() == ["host2"]
    assert wd.tail_ratio() > 1.5  # fleet skewed by the straggler


def test_straggler_none_when_healthy(rng):
    wd = StragglerWatchdog(min_samples=8)
    for step in range(32):
        for h in range(4):
            wd.observe(f"host{h}", 0.1 + rng.normal(0, 0.002))
    assert wd.stragglers() == []
    assert wd.tail_ratio() < 1.2


def test_loss_spike_guard():
    guard = LossSpikeGuard(window=16, spike_factor=3.0, warmup=4)
    def sk(scale):
        s = DDSketch(0.01)
        s.extend(np.random.default_rng(0).lognormal(0, 0.3, 200) * scale)
        return s
    for _ in range(6):
        out = guard.check(sk(1.0))
        assert not out["spike"]
    out = guard.check(sk(10.0))
    assert out["spike"]
    # recovery: normal windows don't keep flagging
    out = guard.check(sk(1.0))
    assert not out["spike"]
