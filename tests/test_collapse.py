"""Adaptive resolution (UDDSketch uniform collapse) across the stack.

Covers the collapse lifecycle end to end: the fold kernel vs its XLA
oracle, level-shifted inserts, the conservation + degraded-alpha property
of ``collapse``, mixed-level merges (bit-exact vs collapse-then-merge),
the 12+-decade acceptance stream that the old edge-bucket clamp could not
serve, host uniform-collapse mode, host<->device round-trips at any level,
and the keyed-telemetry auto-collapse / row-recycling behaviour.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.core.ddsketch import DDSketch
from repro.kernels.fold_pairs import fold_pairs_pallas
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    fold_pairs_ref,
    histogram_ref,
    segment_histogram_ref,
)
from repro.kernels.ddsketch_seg_hist import segment_histogram_pallas
from repro.telemetry.keyed import KeyedAggregator, KeyedWindow

SPEC = BucketSpec(relative_accuracy=0.01, num_buckets=2048, offset=-1024)
QS = (0.01, 0.25, 0.5, 0.75, 0.95, 0.99)


def _exact_q(sorted_vals, q):
    return sorted_vals[int(q * (len(sorted_vals) - 1))]


# --------------------------------------------------------------------- #
# fold_pairs kernel vs oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("offset", [-1024, -1023, -512, 0])
@pytest.mark.parametrize("rows", [None, 1, 5, 16])
def test_fold_kernel_matches_ref(offset, rows, rng):
    spec = BucketSpec(offset=offset)
    shape = (spec.num_buckets,) if rows is None else (rows, spec.num_buckets)
    counts = jnp.asarray(rng.integers(0, 9, shape).astype(np.float32))
    ref = fold_pairs_ref(counts, spec=spec)
    ker = fold_pairs_pallas(counts, spec=spec, interpret=True)
    assert ref.shape == counts.shape
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert float(ref.sum()) == float(counts.sum())  # folding only moves mass


@pytest.mark.parametrize("row_tile,bucket_tile", [(1, 128), (4, 256), (16, 2048)])
def test_fold_kernel_tile_sweep(row_tile, bucket_tile, rng):
    counts = jnp.asarray(rng.integers(0, 9, (7, SPEC.num_buckets)).astype(np.float32))
    ref = fold_pairs_ref(counts, spec=SPEC)
    ker = fold_pairs_pallas(
        counts, spec=SPEC, row_tile=row_tile, bucket_tile=bucket_tile, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_fold_rejects_escaping_geometry():
    with pytest.raises(ValueError, match="uniform collapse"):
        fold_pairs_ref(jnp.zeros(64), spec=BucketSpec(num_buckets=64, offset=4))


def test_fold_equals_level1_insert(rng):
    """Folding a level-0 histogram == inserting at level 1 directly."""
    x = jnp.asarray((rng.pareto(1.0, 4000) + 1.0).astype(np.float32))
    h0 = histogram_ref(x, spec=SPEC)
    h1 = histogram_ref(x, None, jnp.ones(4000, jnp.int32), spec=SPEC)
    np.testing.assert_array_equal(
        np.asarray(fold_pairs_ref(h0, spec=SPEC)), np.asarray(h1)
    )


# --------------------------------------------------------------------- #
# level-shifted insert kernels vs oracles
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mapping", ["log", "linear", "cubic"])
def test_hist_kernels_with_levels_match_ref(mapping, rng):
    spec = BucketSpec(mapping=mapping)
    x = jnp.asarray((rng.lognormal(0, 8, 3000)).astype(np.float32))
    levs = jnp.asarray(rng.integers(0, MAX_COLLAPSE_LEVEL + 1, 3000).astype(np.int32))
    one = segment_histogram_pallas(  # a single sketch: every lane in row 0
        x, jnp.zeros(3000, jnp.int32), None, levs, num_segments=1, spec=spec,
        interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(histogram_ref(x, None, levs, spec=spec)), np.asarray(one[0])
    )
    s = jnp.asarray(rng.integers(-1, 7, 3000).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(segment_histogram_ref(x, s, None, levs, num_segments=5, spec=spec)),
        np.asarray(
            segment_histogram_pallas(
                x, s, None, levs, num_segments=5, spec=spec, interpret=True
            )
        ),
    )


# --------------------------------------------------------------------- #
# collapse conservation + degraded-alpha property (hypothesis)
# --------------------------------------------------------------------- #
signed_values = st.lists(
    st.floats(min_value=1e-4, max_value=1e4, allow_nan=False).map(float)
    | st.floats(min_value=-1e4, max_value=-1e-4, allow_nan=False).map(float)
    | st.just(0.0),
    min_size=1,
    max_size=200,
)


@given(data=signed_values, lev=st.integers(min_value=1, max_value=3))
@settings(max_examples=60, deadline=None)
def test_collapse_conserves_and_degrades_gracefully(data, lev):
    """collapse preserves count/sum/min/max exactly; quantiles stay within
    the degraded alpha_L = (g-1)/(g+1), g = gamma**(2**L)."""
    sk = js.add(js.empty(SPEC), jnp.asarray(data, jnp.float32), spec=SPEC)
    c = js.collapse_to(sk, lev, spec=SPEC)
    assert float(c.count) == float(sk.count)
    assert float(c.summ) == float(sk.summ)
    assert float(c.vmin) == float(sk.vmin)
    assert float(c.vmax) == float(sk.vmax)
    assert int(c.level) == lev
    # 1% slack on alpha absorbs float32 key rounding at bucket borders
    # (same allowance as the seed's level-0 guarantee test)
    alpha = js.effective_alpha(SPEC, lev) * 1.01
    srt = np.sort(np.asarray(data, np.float32))
    for q in QS:
        est = float(js.quantile(c, q, spec=SPEC))
        true = float(_exact_q(srt, q))
        assert abs(est - true) <= alpha * abs(true) + 1e-6, (q, est, true)


@given(data=signed_values, lev=st.integers(min_value=1, max_value=3))
@settings(max_examples=60, deadline=None)
def test_mixed_level_merge_equals_collapse_then_merge(data, lev):
    """merge(a@0, b@L) must equal merge(collapse_to(a, L), b) bit-exactly."""
    arr = jnp.asarray(data, jnp.float32)
    a = js.add(js.empty(SPEC), arr, spec=SPEC)
    b = js.collapse_to(
        js.add(js.empty(SPEC), arr * 2.0, spec=SPEC), lev, spec=SPEC
    )
    got = js.merge(a, b, spec=SPEC)
    want = js.merge(js.collapse_to(a, lev, spec=SPEC), b, spec=SPEC)
    for f_got, f_want in zip(got, want):
        np.testing.assert_array_equal(np.asarray(f_got), np.asarray(f_want))
    assert int(got.level) == lev


# --------------------------------------------------------------------- #
# acceptance: 12+ decades into a 2048-bucket device sketch
# --------------------------------------------------------------------- #
def test_wide_stream_keeps_level_adjusted_alpha(rng):
    """A 24-decade stream overflows the static level-0 range on both sides;
    auto-collapse must absorb it with zero clamping and keep every quantile
    within the level-adjusted alpha.  The old clamp-into-edge-buckets
    behaviour (still reachable with auto_collapse=False) fails this."""
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 20_000)).astype(np.float32)
    srt = np.sort(wide)

    sk = js.add(js.empty(SPEC), jnp.asarray(wide), spec=SPEC, auto_collapse=True)
    lvl = int(sk.level)
    assert lvl >= 1  # the stream cannot fit at base resolution
    assert float(sk.overflow) == 0 and float(sk.underflow) == 0
    assert float(sk.count) == len(wide)
    alpha = js.effective_alpha(SPEC, lvl) * 1.01  # f32 key-border slack
    for q in QS:
        est = float(js.quantile(sk, q, spec=SPEC))
        true = float(_exact_q(srt, q))
        assert abs(est - true) <= alpha * abs(true) + 1e-12, (q, est, true)

    # contrast: the clamping path loses the low tail entirely
    clamped = js.add(js.empty(SPEC), jnp.asarray(wide), spec=SPEC)
    assert float(clamped.overflow) > 0 and float(clamped.underflow) > 0
    est = float(js.quantile(clamped, 0.01, spec=SPEC))
    true = float(_exact_q(srt, 0.01))
    assert abs(est - true) > alpha * abs(true)


def test_wide_stream_bank_rows_collapse_independently(rng):
    """Only the row fed the wide stream degrades; neighbours stay at
    level 0 with full resolution."""
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 8000)).astype(np.float32)
    narrow = (rng.pareto(1.0, 8000) + 1.0).astype(np.float32)
    vals = np.concatenate([wide, narrow])
    ids = np.concatenate([np.zeros(8000, np.int32), np.ones(8000, np.int32)])
    bank = sb.add(
        sb.empty(SPEC, 3),
        jnp.asarray(vals),
        jnp.asarray(ids),
        spec=SPEC,
        auto_collapse=True,
    )
    levels = np.asarray(bank.level)
    assert levels[0] >= 1 and levels[1] == 0 and levels[2] == 0
    assert float(bank.overflow.sum()) == 0 and float(bank.underflow.sum()) == 0
    # each row answers at its own resolution
    srt_w, srt_n = np.sort(wide), np.sort(narrow)
    out = np.asarray(sb.quantiles(bank, jnp.asarray(QS), spec=SPEC))
    for j, q in enumerate(QS):
        a0 = js.effective_alpha(SPEC, int(levels[0])) * 1.01
        assert abs(out[0, j] - _exact_q(srt_w, q)) <= a0 * abs(_exact_q(srt_w, q)) + 1e-12
        assert abs(out[1, j] - _exact_q(srt_n, q)) <= 0.0101 * abs(_exact_q(srt_n, q))


def test_bank_mixed_level_merge_bitexact(rng):
    """Acceptance: merging banks at different collapse levels equals the
    collapse-then-merge reference bit-exactly, row by row."""
    k = 5
    x = (rng.lognormal(0, 2, 4000)).astype(np.float32)
    ids = rng.integers(0, k, 4000).astype(np.int32)
    b1 = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(ids), spec=SPEC)
    mask = jnp.asarray([True, False, True, False, True])
    b2 = sb.collapse(
        sb.add(sb.empty(SPEC, k), jnp.asarray(x * 3), jnp.asarray(ids), spec=SPEC),
        mask,
        spec=SPEC,
    )
    got = sb.merge(b1, b2, spec=SPEC)
    want = sb.merge(sb.collapse_to(b1, b2.level, spec=SPEC), b2, spec=SPEC)
    for f_got, f_want in zip(got, want):
        np.testing.assert_array_equal(np.asarray(f_got), np.asarray(f_want))
    np.testing.assert_array_equal(np.asarray(got.level), np.asarray(mask, np.int32))


# --------------------------------------------------------------------- #
# reactive auto_collapse
# --------------------------------------------------------------------- #
def test_auto_collapse_fires_on_clamped_mass(rng):
    sk = js.add(js.empty(SPEC), jnp.asarray([1e30] * 5, jnp.float32), spec=SPEC)
    assert float(sk.overflow) == 5
    fired = js.auto_collapse(sk, spec=SPEC, threshold=4.0)
    assert int(fired.level) == 1
    assert float(fired.overflow) == 0  # counters meter post-collapse pressure
    held = js.auto_collapse(sk, spec=SPEC, threshold=5.0)
    assert int(held.level) == 0
    assert float(held.overflow) == 5


def test_auto_collapse_respects_level_cap(rng):
    sk = js.empty(SPEC)._replace(
        overflow=jnp.asarray(99.0, jnp.float32),
        level=jnp.asarray(MAX_COLLAPSE_LEVEL, jnp.int32),
    )
    out = js.auto_collapse(sk, spec=SPEC, threshold=0.0)
    assert int(out.level) == MAX_COLLAPSE_LEVEL


# --------------------------------------------------------------------- #
# host tier: uniform-collapse mode + mixed-gamma merge + round-trips
# --------------------------------------------------------------------- #
def test_host_uniform_collapse_caps_bins(rng):
    data = (10.0 ** rng.uniform(-15.0, 9.0, 5000)).astype(np.float64)
    sk = DDSketch(0.01, max_bins=256, collapse="uniform")
    sk.extend(data)
    assert sk.num_bins() <= 256
    assert sk.collapse_level >= 1
    assert sk.count == len(data)
    srt = np.sort(data)
    for q in QS:
        est = sk.quantile(q)
        true = float(_exact_q(srt, q))
        assert abs(est - true) <= sk.effective_alpha * 1.01 * abs(true) + 1e-12


def test_host_mixed_level_merge_matches_collapse_then_merge(rng):
    data = (rng.pareto(1.0, 3000) + 1.0).astype(np.float64)
    a = DDSketch(0.01, max_bins=None)
    a.extend(data)
    b = DDSketch(0.01, max_bins=None)
    b.extend(data * 2)
    b.collapse_to(2)

    ref = a.copy()
    ref.collapse_to(2)
    ref.merge(b)

    a.merge(b)  # aligns internally
    assert a.collapse_level == 2
    assert a.count == ref.count
    assert dict(a.store.items_ascending()) == dict(ref.store.items_ascending())
    for q in QS:
        assert a.quantile(q) == ref.quantile(q)
    # the finer operand is never mutated
    assert b.collapse_level == 2


def test_host_serialization_roundtrips_level(rng):
    sk = DDSketch(0.01, max_bins=128, collapse="uniform")
    sk.extend(10.0 ** rng.uniform(-12.0, 10.0, 1000))
    back = DDSketch.from_dict(sk.to_dict())
    assert back.collapse_level == sk.collapse_level
    assert back._collapse_mode == "uniform"
    assert back.count == sk.count
    for q in QS:
        assert back.quantile(q) == sk.quantile(q)
    # pre-collapse dicts (no level keys) still load
    d = sk.to_dict()
    del d["collapse"], d["collapse_level"]
    legacy = DDSketch.from_dict(d)
    assert legacy.collapse_level == 0


def test_from_host_rejects_level_beyond_device_cap(rng):
    """The host tier has no level cap; reinterpreting deeper-level keys in
    device geometry would silently corrupt every bucket, so it raises."""
    host = DDSketch(0.01, max_bins=None)
    host.extend(rng.pareto(1.0, 50) + 1.0)
    host.collapse_to(MAX_COLLAPSE_LEVEL + 1)
    with pytest.raises(ValueError, match="beyond the device cap"):
        js.from_host(host, SPEC)


def test_device_host_roundtrip_at_level(rng):
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 4000)).astype(np.float32)
    sk = js.add(js.empty(SPEC), jnp.asarray(wide), spec=SPEC, auto_collapse=True)
    host = js.to_host(sk, SPEC)
    assert host.collapse_level == int(sk.level)
    assert host.count == len(wide)
    for q in QS:
        assert host.quantile(q) == pytest.approx(
            float(js.quantile(sk, q, spec=SPEC)), rel=1e-5
        )
    back = js.from_host(host, SPEC)
    assert int(back.level) == int(sk.level)
    np.testing.assert_array_equal(np.asarray(back.pos), np.asarray(sk.pos))
    np.testing.assert_array_equal(np.asarray(back.neg), np.asarray(sk.neg))


# --------------------------------------------------------------------- #
# empty-row quantile pinning (satellite): NaN on both tiers, both APIs
# --------------------------------------------------------------------- #
def test_empty_quantiles_are_nan_everywhere():
    assert np.isnan(float(js.quantile(js.empty(SPEC), 0.5, spec=SPEC)))
    bank = sb.empty(SPEC, 3)
    assert np.isnan(np.asarray(sb.quantile(bank, 0.5, spec=SPEC))).all()
    assert np.isnan(np.asarray(sb.quantiles(bank, jnp.asarray([0.5, 0.99]), spec=SPEC))).all()
    # partially-fed bank: only fed rows answer — including via sb.quantile
    bank = sb.add(bank, jnp.asarray([1.0, 2.0]), jnp.asarray([1, 1]), spec=SPEC)
    single = np.asarray(sb.quantile(bank, 0.5, spec=SPEC))
    assert np.isnan(single[0]) and np.isnan(single[2]) and np.isfinite(single[1])
    # collapsing an empty sketch keeps NaN answers
    c = js.collapse(js.empty(SPEC), spec=SPEC)
    assert np.isnan(float(js.quantile(c, 0.5, spec=SPEC)))


# --------------------------------------------------------------------- #
# keyed telemetry: auto-collapse between flushes + row recycling
# --------------------------------------------------------------------- #
def test_keyed_window_autocollapse_and_level_report(rng):
    window = KeyedWindow(SPEC, capacity=4)
    agg = KeyedAggregator(SPEC)
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 2000)).astype(np.float32)
    narrow = (rng.pareto(1.0, 2000) + 1.0).astype(np.float32)
    window.record("hot", wide)
    window.record("cold", narrow)
    levels = window.levels()
    assert levels["hot"] >= 1 and levels["cold"] == 0
    assert window.alphas()["cold"] == pytest.approx(0.01)
    assert window.alphas()["hot"] > 0.01
    agg.flush(window)
    # levels survive the window reset: the next window inserts at the
    # adapted resolution, so nothing clamps this time
    assert window.levels()["hot"] == levels["hot"]
    window.record("hot", wide)
    assert float(window.bank.overflow.sum() + window.bank.underflow.sum()) == 0
    agg.flush(window)
    # host rollup merged a clamped window with a clean one; alpha reports
    # the degraded guarantee
    assert agg.totals["hot"].count == 2 * len(wide)
    assert agg.alphas()["hot"] > 0.01
    assert agg.alphas()["cold"] == pytest.approx(0.01)


def test_keyed_window_evicts_idle_keys(rng):
    window = KeyedWindow(SPEC, capacity=2, evict_after=1)
    agg = KeyedAggregator(SPEC)
    window.record("a", np.ones(5, np.float32))
    window.record("b", np.ones(5, np.float32))
    row_a = window.key_to_row["a"]
    agg.flush(window)  # window 0 -> 1; both idle 1 <= evict_after, kept
    assert sorted(window.keys()) == ["a", "b"]
    window.record("b", np.ones(5, np.float32))
    agg.flush(window)  # window 1 -> 2; "a" idle 2 > 1, evicted
    assert window.keys() == ["b"]
    # the freed row is reusable by a brand-new key at level 0
    window.record("c", np.ones(5, np.float32))
    assert window.key_to_row["c"] == row_a
    assert window.levels()["c"] == 0
    agg.flush(window)
    # aggregator rollups survive eviction (host tier is unbounded)
    assert agg.totals["a"].count == 5
    assert agg.totals["b"].count == 10
    assert agg.totals["c"].count == 5


def test_keyed_window_evicted_hot_row_resets_level(rng):
    window = KeyedWindow(SPEC, capacity=1, evict_after=1)
    agg = KeyedAggregator(SPEC)
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 500)).astype(np.float32)
    window.record("hot", wide)
    assert window.levels()["hot"] >= 1
    rid = window.key_to_row["hot"]
    agg.flush(window)
    agg.flush(window)  # hot idle past evict_after -> evicted
    assert "hot" not in window.key_to_row
    window.record("fresh", np.ones(3, np.float32))
    assert window.key_to_row["fresh"] == rid
    assert window.levels()["fresh"] == 0
