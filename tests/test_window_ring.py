"""Windowed quantiles over the device bank ring: fused range merge parity.

The tentpole contract, test by test:

* the fused ``bank_range_merge`` kernel (ref and interpreted Pallas) is
  bit-exact vs the sequential oracle — iterated ``fold_pairs_ref`` per
  slice row then a per-bucket sum — across mixed per-row collapse deltas
  (hypothesis sweep + seeded cases);
* ``WindowRing`` window queries are bit-exact vs host-looped sequential
  ``sketch_bank.merge`` folds + ``quantiles`` across mappings x weights x
  per-row collapse levels, through ring wraparound, with empty slices
  (all-NaN rows) handled;
* a W=64-slice window query is ONE device dispatch: exactly one
  ``bank_range_merge`` trace, and a second window size reuses the same
  compiled executable (no new cache miss);
* ``KeyedWindow`` slice turnover preserves per-key collapse levels and the
  ``window=``/``slices=`` validators raise ``ValueError`` (the HTTP 400
  contract) on every malformed input;
* the HTTP tier: ``?window=``/``?slices=`` on /quantiles and /rollup, 400
  JSON bodies (never a traceback), NaN -> null, /stats engine block;
* the ingest gateway's monotonic slice clock advances the ring from the
  drain tick and ``flush()`` never advances it;
* sharded parity: the same ring over a row-sharded engine answers windowed
  queries bit-exactly vs the single-device engine (subprocess-covered on
  single-device hosts);
* reads on the live slab: a cover taken at seal count ``s`` stays
  bit-identical for ``S - w + 1`` more seals (``WindowRing.cover_intact``),
  a snapshot read inside that answers as the host ``DDSketch`` merge of its
  slices, and a read enqueued before a donated seal returns the pre-seal
  answer.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import sketch_bank as sb
from repro.core.ddsketch import DDSketch
from repro.engine import SketchEngine, StaleCover, WindowRing
from repro.kernels import ops
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    bank_range_merge_ref,
    fold_pairs_ref,
)
from repro.telemetry.keyed import KeyedWindow, parse_duration

multi = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (covered by test_sharded_window_subprocess)",
)

QS = [0.0, 0.25, 0.5, 0.95, 0.99, 1.0]
MAPPINGS = ["log", "linear", "cubic"]
# small geometry keeps the 7 one-hot folds cheap under interpret mode
SMALL = BucketSpec(num_buckets=128, offset=-64)


def _stream(seed, n, k, *, weights=False, fractional=False, decades=3.0):
    rng = np.random.default_rng(seed)
    x = (10.0 ** rng.uniform(-decades / 2, decades / 2, n)).astype(np.float32)
    x *= np.where(rng.random(n) < 0.3, -1.0, 1.0).astype(np.float32)
    x[rng.random(n) < 0.02] = 0.0
    s = rng.integers(0, k, n).astype(np.int32)
    w = None
    if weights:
        w = rng.integers(1, 5, n).astype(np.float32)
        if fractional:
            w *= np.float32(0.25)
    return x, s, w


def _slice_bank(spec, k, seed, *, n=200, levels=None, weights=False,
                fractional=False):
    """One sealed-slice bank: optional per-row pre-collapse, then a stream."""
    bank = sb.empty(spec, k)
    if levels is not None:
        bank = sb.collapse_to(bank, jnp.asarray(levels, jnp.int32), spec=spec)
    if n:
        x, s, w = _stream(seed, n, k, weights=weights, fractional=fractional)
        bank = sb.add(
            bank, jnp.asarray(x), jnp.asarray(s),
            None if w is None else jnp.asarray(w), spec=spec,
        )
    return bank


def _merge_all(banks, spec):
    out = banks[0]
    for b in banks[1:]:
        out = sb.merge(out, b, spec=spec)
    return out


# --------------------------------------------------------------------- #
# kernel parity: fused range merge vs iterated pair folds
# --------------------------------------------------------------------- #
def _sequential_fold_oracle(counts, deltas, spec):
    """Fold each slice row ``deltas[d, r]`` times with fold_pairs_ref,
    then sum the slice axis — the unfused reference the kernel replaces."""
    d_slices, r_rows, _ = counts.shape
    out = np.zeros(counts.shape[1:], np.float32)
    for d in range(d_slices):
        for r in range(r_rows):
            row = jnp.asarray(counts[d, r], jnp.float32)[None, :]
            for _ in range(int(deltas[d, r])):
                row = fold_pairs_ref(row, spec=spec)
            out[r] += np.asarray(row)[0]
    return out


@pytest.mark.parametrize("force", ["ref", "interpret"])
def test_range_merge_matches_sequential_folds(force):
    rng = np.random.default_rng(7)
    d_slices, r_rows = 5, 6
    counts = rng.integers(0, 100, (d_slices, r_rows, SMALL.num_buckets))
    counts = counts.astype(np.float32)
    deltas = rng.integers(0, MAX_COLLAPSE_LEVEL + 1, (d_slices, r_rows))
    got = ops.bank_range_merge(
        jnp.asarray(counts), jnp.asarray(deltas.astype(np.int32)),
        spec=SMALL, row_tile=4, bucket_tile=64, force=force,
    )
    np.testing.assert_array_equal(
        np.asarray(got), _sequential_fold_oracle(counts, deltas, SMALL)
    )


@pytest.mark.parametrize("mapping", MAPPINGS)
def test_range_merge_spec_offsets(mapping):
    """The fold math leans on the spec offset; sweep shipped mappings and
    an offset-0 / centred pair of geometries."""
    for spec in (BucketSpec(mapping=mapping),
                 BucketSpec(num_buckets=256, offset=0, mapping=mapping)):
        rng = np.random.default_rng(11)
        counts = rng.integers(0, 50, (3, 4, spec.num_buckets)).astype(np.float32)
        deltas = rng.integers(0, MAX_COLLAPSE_LEVEL + 1, (3, 4)).astype(np.int32)
        got = ops.bank_range_merge(
            jnp.asarray(counts), jnp.asarray(deltas), spec=spec, force="ref"
        )
        np.testing.assert_array_equal(
            np.asarray(got), _sequential_fold_oracle(counts, deltas, spec)
        )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    d_slices=st.integers(1, 6),
    deltas=st.lists(
        st.integers(0, MAX_COLLAPSE_LEVEL), min_size=1, max_size=18
    ),
)
def test_range_merge_property(seed, d_slices, deltas):
    """Hypothesis: integer counts, arbitrary mixed per-(slice, row) deltas
    — fused result equals the iterated-fold oracle bit for bit."""
    rng = np.random.default_rng(seed)
    r_rows = max(1, len(deltas) // max(d_slices, 1))
    counts = rng.integers(0, 1000, (d_slices, r_rows, SMALL.num_buckets))
    counts = counts.astype(np.float32)
    dmat = np.asarray(
        (deltas * (d_slices * r_rows))[: d_slices * r_rows], np.int32
    ).reshape(d_slices, r_rows)
    got = ops.bank_range_merge(
        jnp.asarray(counts), jnp.asarray(dmat), spec=SMALL, force="ref"
    )
    np.testing.assert_array_equal(
        np.asarray(got), _sequential_fold_oracle(counts, dmat, SMALL)
    )


def test_range_merge_ref_rejects_bad_shapes():
    counts = jnp.zeros((2, 3, SMALL.num_buckets))
    with pytest.raises(ValueError):
        bank_range_merge_ref(counts, jnp.zeros((3, 2), jnp.int32), spec=SMALL)


# --------------------------------------------------------------------- #
# ring parity: fused window query vs sequential engine merges
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mapping", MAPPINGS)
@pytest.mark.parametrize("weights", [False, True])
def test_window_query_matches_sequential_merge(mapping, weights):
    spec = BucketSpec(mapping=mapping)
    k, s_ring, n_seals = 6, 8, 11  # 11 seals -> wraparound past S=8
    rng = np.random.default_rng(3)
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    host_slices = []
    for t in range(n_seals):
        levels = rng.integers(0, 3, k) if t % 2 else None
        slice_bank = _slice_bank(
            spec, k, seed=100 + t, levels=levels, weights=weights
        )
        host_slices.append(slice_bank)
        ring.seal(slice_bank)
    live = _slice_bank(spec, k, seed=999, weights=weights)
    for w in (1, 2, 3, 5, 8):
        got = np.asarray(ring.quantiles(live, QS, window_slices=w))
        want_banks = host_slices[n_seals - (w - 1):] + [live]
        merged = _merge_all(want_banks, spec)
        want = np.asarray(
            sb.quantiles(merged, jnp.asarray(QS, jnp.float32), spec=spec)
        )
        np.testing.assert_array_equal(got, want, err_msg=f"window={w}")


def test_window_query_fractional_weights_close():
    """Non-integer counts may reassociate across the slice axis: allclose,
    not bit-exact (the integer-count contract is the exact one)."""
    spec = BucketSpec()
    k, s_ring = 4, 4
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    host = []
    for t in range(5):
        b = _slice_bank(spec, k, seed=t, weights=True, fractional=True)
        host.append(b)
        ring.seal(b)
    live = _slice_bank(spec, k, seed=77, weights=True, fractional=True)
    got = np.asarray(ring.quantiles(live, QS, window_slices=4))
    want = np.asarray(
        sb.quantiles(_merge_all(host[-3:] + [live], spec),
                     jnp.asarray(QS, jnp.float32), spec=spec)
    )
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_window_rollup_matches_sequential():
    spec = BucketSpec()
    k, s_ring = 5, 4
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    host = []
    for t in range(6):
        b = _slice_bank(spec, k, seed=50 + t,
                        levels=(np.arange(k) % 3 if t == 2 else None))
        host.append(b)
        ring.seal(b)
    live = _slice_bank(spec, k, seed=51)
    got = np.asarray(ring.rollup(live, QS, window_slices=3))
    merged = _merge_all(host[-2:] + [live], spec)
    want = np.asarray(eng.rollup_quantiles(merged, QS))
    np.testing.assert_array_equal(got, want)


def test_empty_slices_and_rows_are_nan():
    spec = BucketSpec()
    k = 3
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, 4)
    # nothing sealed, empty live bank -> every quantile NaN
    empty = eng.new_bank()
    assert np.isnan(np.asarray(ring.quantiles(empty, QS, window_slices=4))).all()
    # one sealed slice with data only in row 0: row 0 real, rows 1.. NaN
    one_row = sb.add(
        sb.empty(spec, k),
        jnp.asarray([1.0, 2.0, 3.0], jnp.float32),
        jnp.zeros(3, jnp.int32),
        spec=spec,
    )
    ring.seal(one_row)
    ring.seal(eng.new_bank())  # an entirely empty sealed slice in range
    got = np.asarray(ring.quantiles(empty, QS, window_slices=4))
    assert not np.isnan(got[0]).any()
    assert np.isnan(got[1:]).all()
    # excluding the live head changes nothing here (it is empty)
    got2 = np.asarray(
        ring.quantiles(empty, QS, window_slices=4, include_live=False)
    )
    np.testing.assert_array_equal(got, got2)


# --------------------------------------------------------------------- #
# the dispatch-count acceptance: W=64 window, ONE fused device program
# --------------------------------------------------------------------- #
def test_w64_window_is_one_dispatch():
    spec = SMALL
    k, s_ring = 4, 64
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    for t in range(s_ring):
        ring.seal(_slice_bank(spec, k, seed=t, n=20))
    live = _slice_bank(spec, k, seed=1000, n=20)

    def merge_traces():
        return ops.dispatch_stats()["range_merge_calls"].get(
            "bank_range_merge", 0
        )

    before, cache_before = merge_traces(), eng.cache_info()
    got = np.asarray(ring.quantiles(live, QS, window_slices=64))
    # 64 slices merged by ONE fused range-merge trace (a host loop would
    # have issued 63 pairwise merge dispatches plus a query)
    assert merge_traces() == before + 1
    assert eng.cache_info()["misses"] == cache_before["misses"] + 1
    # a different window size rides the SAME executable: padded node cover
    # keeps the geometry fixed, so no new trace and no new miss
    mid = eng.cache_info()
    np.asarray(ring.quantiles(live, QS, window_slices=7))
    assert merge_traces() == before + 1
    after = eng.cache_info()
    assert after["misses"] == mid["misses"]
    assert after["hits"] == mid["hits"] + 1
    # and the answer is still the host-merge oracle's (spot check W=64)
    banks = [_slice_bank(spec, k, seed=t, n=20) for t in range(1, s_ring)]
    want = np.asarray(
        sb.quantiles(_merge_all(banks + [live], spec),
                     jnp.asarray(QS, jnp.float32), spec=spec)
    )
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------- #
# ring bookkeeping
# --------------------------------------------------------------------- #
def test_ring_validates_construction_and_windows():
    eng = SketchEngine(SMALL, 2)
    with pytest.raises(ValueError):
        WindowRing(eng, 3)
    with pytest.raises(ValueError):
        WindowRing(eng, 1)
    ring = WindowRing(eng, 4)
    with pytest.raises(ValueError):
        ring.query_args(0)
    with pytest.raises(ValueError):
        ring.query_args(5)
    with pytest.raises(ValueError):
        ring.range_nodes(0, 1)  # nothing sealed yet


def test_range_cover_is_logarithmic():
    eng = SketchEngine(SMALL, 2)
    s_ring = 16
    ring = WindowRing(eng, s_ring)
    for t in range(2 * s_ring + 3):  # deep wraparound
        ring.seal(eng.new_bank())
        lo_min = max(0, ring.sealed - s_ring)
        for lo in range(lo_min, ring.sealed + 1):
            cover = ring.range_nodes(lo, ring.sealed)
            assert len(cover) <= ring.max_range_nodes
    st_ = ring.stats()
    assert st_["sealed"] == 2 * s_ring + 3
    assert st_["occupancy"] == s_ring
    # amortized tree maintenance: ~1 extra merge per seal on average
    assert st_["node_merges"] <= 2 * st_["sealed"]


# --------------------------------------------------------------------- #
# KeyedWindow: slice turnover, duration parsing, validation
# --------------------------------------------------------------------- #
def test_parse_duration():
    assert parse_duration("250ms") == pytest.approx(0.25)
    assert parse_duration("30s") == 30.0
    assert parse_duration("5m") == 300.0
    assert parse_duration("1.5h") == 5400.0
    assert parse_duration("45") == 45.0
    # compound forms concatenate tokens
    assert parse_duration("1h30m") == 5400.0
    assert parse_duration("1m30.5s") == 90.5
    assert parse_duration("2h5m30s500ms") == 7530.5
    assert parse_duration(" 1H30M ") == 5400.0  # case/space tolerant
    for bad in ("zzz", "", "-3s", "0s", "5 parsecs", None):
        with pytest.raises(ValueError):
            parse_duration(bad)
    # compound rejects name the offending token
    with pytest.raises(ValueError, match="'5'"):
        parse_duration("5x30s")  # unit-less token inside a compound
    with pytest.raises(ValueError, match="-30m"):
        parse_duration("1h-30m")  # negative token
    with pytest.raises(ValueError, match="magnitude"):
        parse_duration(".")
    with pytest.raises(ValueError, match="positive"):
        parse_duration("0ms0s")  # sums to zero


def test_keyed_window_slice_turnover_preserves_levels():
    win = KeyedWindow(BucketSpec(), capacity=4, num_slices=4)
    # huge dynamic range forces per-key collapse in the live bank
    win.record(["a"] * 3, np.asarray([1e-30, 1.0, 1e30], np.float32))
    lvl_before = int(np.asarray(win.bank.level)[win.key_to_row["a"]])
    assert lvl_before > 0
    win.advance_slice()
    lvl_after = int(np.asarray(win.bank.level)[win.key_to_row["a"]])
    assert lvl_after == lvl_before  # donated reset recycles, levels survive
    assert win.ring.sealed == 1
    # the sealed slice stays queryable through the window path
    vals = win.windowed_quantiles("a", [0.5], slices=2)
    assert not np.isnan(vals[0])
    # live-only window no longer sees the sealed data
    live_only = win.windowed_quantiles("a", [0.5], slices=1)
    assert np.isnan(live_only[0])


def test_keyed_window_resolve_and_validation():
    win = KeyedWindow(
        BucketSpec(), capacity=4, num_slices=8, slice_seconds=60.0
    )
    win.record(["a"], np.asarray([1.0], np.float32))
    assert win.resolve_window(slices="3") == 3
    assert win.resolve_window(window="5m") == 5
    assert win.resolve_window(window="90s") == 2  # rounds up
    for kwargs in (
        {},  # neither
        {"window": "5m", "slices": 2},  # both
        {"window": "zzz"},
        {"slices": "many"},
        {"slices": 0},
        {"slices": 9},  # wider than the ring
        {"window": "9h"},  # wider than the ring via duration
    ):
        with pytest.raises(ValueError):
            win.resolve_window(**kwargs)
    no_clock = KeyedWindow(BucketSpec(), capacity=4, num_slices=8)
    with pytest.raises(ValueError):
        no_clock.resolve_window(window="5m")  # duration needs slice_seconds
    ringless = KeyedWindow(BucketSpec(), capacity=4)
    with pytest.raises(ValueError):
        ringless.resolve_window(slices=2)
    with pytest.raises(ValueError):
        ringless.advance_slice()
    with pytest.raises(KeyError):
        win.windowed_quantiles("nope", [0.5], slices=2)


def test_keyed_window_windowed_matches_ring_oracle():
    spec = BucketSpec()
    win = KeyedWindow(spec, capacity=4, num_slices=4)
    per_slice = []
    for t in range(5):
        x, _, _ = _stream(200 + t, 120, 1)
        x = np.abs(x) + 1e-3
        win.record(["a"] * x.size, x)
        per_slice.append(x)
        win.advance_slice()
    x_live, _, _ = _stream(300, 40, 1)
    x_live = np.abs(x_live) + 1e-3
    win.record(["a"] * x_live.size, x_live)
    got = win.windowed_quantiles("a", QS, slices=3)
    vals = np.concatenate(per_slice[-2:] + [x_live])
    bank = sb.add(
        sb.empty(spec, 1), jnp.asarray(vals), jnp.zeros(vals.size, jnp.int32),
        spec=spec,
    )
    want = np.asarray(
        sb.quantiles(bank, jnp.asarray(QS, jnp.float32), spec=spec)
    )[0]
    np.testing.assert_array_equal(np.asarray(got), want)
    # the all-keys and rollup paths agree with the single-key case here
    assert win.windowed_all_quantiles(QS, slices=3)["a"] == got
    np.testing.assert_array_equal(
        np.asarray(win.windowed_rollup(QS, slices=3)), want
    )
    stats = win.engine_stats()
    assert stats["ring"]["sealed"] == 5
    assert stats["executable_cache"]["executables"] > 0


# --------------------------------------------------------------------- #
# HTTP contract: ?window=/?slices=, 400 bodies, /stats engine block
# --------------------------------------------------------------------- #
def _get(url):
    try:
        with urllib.request.urlopen(url) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture
def http_window():
    from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
    from repro.telemetry.keyed import KeyedAggregator

    win = KeyedWindow(
        BucketSpec(), capacity=4, num_slices=4, slice_seconds=60.0
    )
    tele = TelemetryFacade(win, KeyedAggregator(win.spec))
    with QuantileHTTPServer(tele) as srv:
        yield win, srv


def test_http_windowed_queries(http_window):
    win, srv = http_window
    win.record(["ep"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    win.advance_slice()
    code, body = _get(srv.url + "/quantiles?endpoint=ep&slices=2&q=0.5")
    assert code == 200 and body["slices"] == "2"
    assert body["quantiles"][0] == pytest.approx(2.0, rel=0.02)
    code, body = _get(srv.url + "/quantiles?endpoint=ep&window=2m&q=0.5")
    assert code == 200 and body["window"] == "2m"
    assert body["quantiles"][0] == pytest.approx(2.0, rel=0.02)
    code, body = _get(srv.url + "/rollup?slices=2&q=0.95")
    assert code == 200
    assert body["quantiles"][0] == pytest.approx(3.0, rel=0.03)
    # empty window -> JSON null, never a bare NaN token
    code, body = _get(srv.url + "/quantiles?endpoint=ep&slices=1&q=0.5")
    assert code == 200 and body["quantiles"] == [None]


def test_http_windowed_validation_is_400_json(http_window):
    win, srv = http_window
    win.record(["ep"], np.asarray([1.0], np.float32))
    for path in (
        "/quantiles?endpoint=ep&window=zzz",
        "/quantiles?endpoint=ep&window=5x",
        "/quantiles?endpoint=ep&slices=banana",
        "/quantiles?endpoint=ep&slices=0",
        "/quantiles?endpoint=ep&slices=99",  # wider than the ring
        "/quantiles?endpoint=ep&window=9h",
        "/quantiles?endpoint=ep&window=1m&slices=2",  # both
        "/rollup?window=nope",
        "/rollup?slices=11",
    ):
        code, body = _get(srv.url + path)
        assert code == 400, path
        assert "error" in body, path
    code, body = _get(srv.url + "/quantiles?endpoint=ghost&slices=2")
    assert code == 404


def test_http_stats_engine_block(http_window):
    win, srv = http_window
    win.record(["ep"], np.asarray([1.0], np.float32))
    win.advance_slice()
    code, body = _get(srv.url + "/stats")
    assert code == 200
    eng = body["engine"]
    assert eng["ring"]["sealed"] == 1
    assert eng["ring"]["num_slices"] == 4
    assert set(eng["executable_cache"]) == {"executables", "hits", "misses"}


def test_http_windowed_unsupported_source_is_400():
    """A duck-typed telemetry source without the windowed surface gets a
    clean 400, not an AttributeError traceback."""
    from repro.launch.http_api import QuantileHTTPServer

    class Bare:
        def endpoint_quantiles(self, endpoint, qs):
            return [0.0] * len(qs)

    with QuantileHTTPServer(Bare()) as srv:
        code, body = _get(srv.url + "/quantiles?endpoint=ep&slices=2")
        assert code == 400 and "not supported" in body["error"]


# --------------------------------------------------------------------- #
# gateway slice clock
# --------------------------------------------------------------------- #
def test_gateway_slice_clock_advances_ring():
    from repro.launch.ingest_gateway import IngestGateway

    win = KeyedWindow(BucketSpec(), capacity=4, num_slices=4)
    gw = IngestGateway(win, start=False, slice_interval_s=30.0)
    gw.submit("ep", [1.0, 2.0, 3.0])
    gw.flush()
    # flush() drains but NEVER advances the slice clock
    assert gw.stats()["slice_advances"] == 0
    assert win.ring.sealed == 0
    # force the monotonic deadline into the past: the drain tick's
    # _maybe_advance_slice seals exactly the elapsed intervals
    gw._next_slice_t -= 30.0
    assert gw._maybe_advance_slice() == 1
    assert win.ring.sealed == 1
    assert gw.stats()["slice_advances"] == 1
    # the sealed ingest is queryable through the window path
    vals = win.windowed_quantiles("ep", [0.5], slices=2)
    assert vals[0] == pytest.approx(2.0, rel=0.02)
    gw.stop()


def test_gateway_slice_clock_requires_ring():
    from repro.launch.ingest_gateway import IngestGateway

    win = KeyedWindow(BucketSpec(), capacity=4)  # no ring
    with pytest.raises(ValueError):
        IngestGateway(win, start=False, slice_interval_s=1.0)
    with pytest.raises(ValueError):
        IngestGateway(
            KeyedWindow(BucketSpec(), capacity=4, num_slices=4),
            start=False,
            slice_interval_s=0.0,
        )


# --------------------------------------------------------------------- #
# sharded parity: the slab rides the keys axis
# --------------------------------------------------------------------- #
@multi
@pytest.mark.parametrize("weights", [False, True])
def test_sharded_window_parity(weights):
    from repro.engine import ShardedEngine

    spec = BucketSpec()
    k, s_ring, shards = 8, 4, 4
    single = SketchEngine(spec, k)
    sharded = ShardedEngine(spec, k, num_shards=shards)
    ring_s = WindowRing(single, s_ring)
    ring_m = WindowRing(sharded, s_ring)
    for t in range(6):  # wraps past S=4
        b = _slice_bank(spec, k, seed=400 + t, weights=weights,
                        levels=(np.arange(k) % 2 if t == 3 else None))
        ring_s.seal(b)
        ring_m.seal(sharded._place(b))
    live = _slice_bank(spec, k, seed=444, weights=weights)
    for w in (1, 2, 4):
        want = np.asarray(ring_s.quantiles(live, QS, window_slices=w))
        got = np.asarray(
            ring_m.quantiles(sharded._place(live), QS, window_slices=w)
        )[:k]
        np.testing.assert_array_equal(got, want, err_msg=f"window={w}")
        want_r = np.asarray(ring_s.rollup(live, QS, window_slices=w))
        got_r = np.asarray(
            ring_m.rollup(sharded._place(live), QS, window_slices=w)
        )
        np.testing.assert_array_equal(got_r, want_r, err_msg=f"rollup w={w}")


@pytest.mark.skipif(
    len(jax.devices()) >= 4, reason="in-process multi-device run covers this"
)
def test_sharded_window_subprocess():
    """Single-device fallback: re-run the sharded window parity on 8
    simulated CPU devices so the tier-1 gate always exercises it."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", __file__, "-q",
         "-k", "sharded_window_parity", "-p", "no:cacheprovider"],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# --------------------------------------------------------------------- #
# reads on the live slab: the cover's lifetime, snapshots, donated seals
# --------------------------------------------------------------------- #
def _distinct_slice(spec, k, t):
    """Slice ``t``'s bank: values no other slice holds, so every node that
    a seal rewrites changes bits."""
    vals = np.asarray([1.5 * (t + 1), 2.5 * (t + 1) + 0.25], np.float32)
    return sb.add(sb.empty(spec, k), jnp.asarray(vals),
                  jnp.zeros(vals.size, jnp.int32), spec=spec)


@pytest.mark.parametrize("s_ring", [4, 8])
def test_cover_intact_rule_exhaustive(s_ring):
    """Every seal count ``s``, delay ``d`` and window ``w``: the nodes that
    ``query_args_at(s, w)`` names are bit-identical after ``d`` more seals
    exactly while ``s + d < e + S`` (``e`` the end of the cover's oldest
    block), and ``cover_intact`` never passes a changed cover.  Where the
    oldest slice is read as a leaf of a full cover that bound is
    ``d <= S - w + 1``, and the rule is exact there."""
    spec, k = SMALL, 2
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    top = 3 * s_ring
    history = [jax.tree.map(np.asarray, ring.slab)]
    rule = {}  # (s, w, d) -> cover_intact as the ring said at seal s + d
    for t in range(top + s_ring + 2):
        for s in range(max(0, t - s_ring - 1), min(t, top) + 1):
            for w in range(1, s_ring + 1):
                rule[(s, w, t - s)] = ring.cover_intact(s, w)
        ring.seal(_distinct_slice(spec, k, t))
        history.append(jax.tree.map(np.asarray, ring.slab))
    exact_cases = {w: 0 for w in range(2, s_ring + 1)}
    for (s, w, d), said in rule.items():
        nodes, valid = ring.query_args_at(s, w)
        cover = nodes[valid > 0].tolist()
        same = all(
            np.array_equal(a[cover], b[cover])
            for a, b in zip(jax.tree.leaves(history[s]),
                            jax.tree.leaves(history[s + d]))
        )
        if cover:
            lo = s - min(w - 1, s)
            first = cover[0]
            level = max(j for j in range(ring.tree_levels + 1)
                        if first >= ring._base[j])
            end = lo + (1 << level)
            assert same == (s + d < end + s_ring), (s, w, d, cover)
        else:
            assert same
        if said:
            assert same, ("rule passed a changed cover", s, w, d)
        if w >= 2 and s >= w - 1 and cover[0] < s_ring:
            assert said == same, (s, w, d)
            exact_cases[w] += 1
    # the exact case occurs for every window, so the bound cannot widen
    assert all(exact_cases.values()), exact_cases


def _host_merge(slices, spec):
    """Host ``DDSketch`` merge (Algorithm 4) of per-slice host sketches."""
    out = DDSketch(spec.relative_accuracy, max_bins=None, mapping=spec.mapping)
    for vals in slices:
        sk = DDSketch(spec.relative_accuracy, max_bins=None, mapping=spec.mapping)
        sk.extend(float(v) for v in vals)
        out.merge(sk)
    return out


def test_snapshot_read_after_seals_answers_as_host_merge():
    """A snapshot taken at seal count ``s`` answers a ``w``-slice window
    after up to ``S - w + 1`` further seals as the host ``DDSketch`` merge
    of the slices it covers plus its live slice, read from the live slab;
    one seal more and it refuses (``StaleCover``)."""
    spec, s_ring = BucketSpec(), 4
    keys = ("a", "b")
    rng = np.random.default_rng(5)
    for w in range(1, s_ring + 1):
        win = KeyedWindow(spec, capacity=4, num_slices=s_ring)
        sealed: list[dict] = []

        def fill():
            vals = {k: (rng.pareto(1.5, 40) + 1.0).astype(np.float32) for k in keys}
            for k in keys:
                win.record(k, vals[k])
            return vals

        for _ in range(s_ring + 2):  # wrapped past S
            sealed.append(fill())
            win.advance_slice()
        live = fill()
        snap = win.snapshot()
        s = snap.sealed
        covered = sealed[s - (w - 1):s] if w > 1 else []
        for d in range(s_ring - w + 3):
            if d <= s_ring - w + 1:
                got = snap.windowed_all_quantiles(QS, slices=w)
                for k in keys:
                    want = _host_merge([c[k] for c in covered] + [live[k]], spec)
                    assert got[k] == pytest.approx(want.quantiles(QS), rel=1e-5), (w, d, k)
                fleet = _host_merge([c[k] for c in covered + [live] for k in keys], spec)
                assert snap.windowed_rollup(QS, slices=w) == pytest.approx(
                    fleet.quantiles(QS), rel=1e-5), (w, d)
            else:
                with pytest.raises(StaleCover):
                    snap.windowed_row_quantiles(QS, slices=w)
            fill()
            win.advance_slice()


def test_read_enqueued_before_donated_seal_returns_pre_seal_answer():
    """A window read enqueued on the slab, then a seal that donates the
    slab and rewrites the very node the read names: the read still answers
    from the slab as it was (the runtime orders the donation after it)."""
    spec, k, s_ring = BucketSpec(), 3, 4
    eng = SketchEngine(spec, k)
    ring = WindowRing(eng, s_ring)
    banks = [_slice_bank(spec, k, seed=600 + t) for t in range(s_ring + 1)]
    for b in banks[:s_ring]:
        ring.seal(b)
    live = _slice_bank(spec, k, seed=699)
    nodes = np.zeros(ring.max_range_nodes, np.int32)  # node 0: leaf of slice 0
    valid = np.zeros(ring.max_range_nodes, np.float32)
    valid[0] = 1.0
    old = ring.slab
    pending = eng.window_query(ring.slab, live, nodes, valid, True, QS)
    ring.seal(banks[s_ring])  # slice 4 -> leaf 0, slab donated
    assert old.pos.is_deleted()  # the seal really consumed the read's slab
    want = np.asarray(sb.quantiles(_merge_all([banks[0], live], spec),
                                   jnp.asarray(QS, jnp.float32), spec=spec))
    np.testing.assert_array_equal(np.asarray(pending), want)
    after = np.asarray(eng.window_query(ring.slab, live, nodes, valid, True, QS))
    assert not np.array_equal(after, want, equal_nan=True)
