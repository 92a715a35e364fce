"""Fused single-dispatch ingest: full-state parity with the two-pass sort
and matmul pipelines across mappings x collapse levels x weights, the Pallas
kernel vs the XLA twin in interpret mode across tile shapes, adversarial
streams (all-unique / all-duplicate / inert engine padding), and the
dispatch contracts (REPRO_INSERT_METHOD override, full-ingest heuristic,
tall-bank fallback observability)."""

import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import sketch_bank as sb
from repro.kernels import ops
from repro.kernels.ddsketch_ingest import ddsketch_ingest_pallas
from repro.kernels.ddsketch_scatter import MAX_RESIDENT_ROWS
from repro.kernels.ref import MAX_COLLAPSE_LEVEL, BucketSpec, fused_ingest_ref

MAPPINGS = ["log", "linear", "cubic"]
METHODS = ("matmul", "sort", "fused")


def _data(n, rng):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    x *= np.where(rng.random(n) < 0.4, -1.0, 1.0).astype(np.float32)
    specials = np.array([np.nan, np.inf, -np.inf, -1.0, 0.0, 1e-38, 1e38])
    idx = rng.choice(n, size=min(7, n), replace=False)
    x[idx] = specials[: len(idx)].astype(np.float32)
    return x


def _assert_banks_equal(a, b):
    for name, fa, fb in zip(a._fields, a, b):
        if name == "summ":
            # float sum order differs between the dense small-K stats path
            # and the fused segment reduction; signed streams cancel, so
            # the drift bounds against the row's |wx| mass, not the sum
            np.testing.assert_allclose(
                np.asarray(fa), np.asarray(fb), rtol=1e-5, atol=1e-2,
                err_msg="field 'summ' differs",
            )
        else:
            np.testing.assert_array_equal(
                np.asarray(fa), np.asarray(fb),
                err_msg=f"field {name!r} differs",
            )


def _add_each_method(bank, x, s, w, spec):
    return [
        sb.add(bank, x, s, w, spec=spec, method=method) for method in METHODS
    ]


# --------------------------------------------------------------------- #
# full-state parity: one fused dispatch == histogram pass + stats pass
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("num_segments", [1, 5, 37])
@pytest.mark.parametrize("mapping", MAPPINGS)
def test_add_impl_full_state_parity(num_segments, mapping, rng):
    """All nine bank fields agree across the three pipelines on the ref
    tier — counters and extrema bit-for-bit, the float summ to ulps —
    including the stats the fused path now produces inside the ingest
    dispatch."""
    spec = BucketSpec(mapping=mapping)
    n = 4000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-2, num_segments + 3, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    bank = sb.collapse_to(
        sb.empty(spec, num_segments),
        jnp.asarray(
            rng.integers(0, MAX_COLLAPSE_LEVEL + 1, num_segments), jnp.int32
        ),
        spec=spec,
    )
    got_m, got_s, got_f = _add_each_method(bank, x, s, w, spec)
    _assert_banks_equal(got_m, got_f)
    _assert_banks_equal(got_s, got_f)


def test_add_impl_parity_unit_weights(rng):
    spec = BucketSpec()
    n, k = 3000, 9
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    got_m, got_s, got_f = _add_each_method(sb.empty(spec, k), x, s, None, spec)
    _assert_banks_equal(got_m, got_f)
    _assert_banks_equal(got_s, got_f)


@settings(max_examples=30, deadline=None)
@given(
    mapping=st.sampled_from(MAPPINGS),
    level=st.integers(min_value=0, max_value=MAX_COLLAPSE_LEVEL),
    weighted=st.booleans(),
    data=st.lists(
        st.tuples(
            st.floats(
                min_value=-1e6, max_value=1e6, allow_nan=False, width=32
            ),
            st.integers(min_value=-1, max_value=5),
            st.integers(min_value=0, max_value=4),
        ),
        min_size=1,
        max_size=80,
    ),
)
def test_fused_parity_property(mapping, level, weighted, data):
    """Property sweep: any stream (signed, tiny, zero, out-of-range ids),
    any collapse level, weighted or not — the fused pipeline's bank state
    equals both two-pass pipelines exactly."""
    spec = BucketSpec(mapping=mapping)
    k = 4
    x = jnp.asarray(np.array([d[0] for d in data], np.float32))
    s = jnp.asarray(np.array([d[1] for d in data], np.int32))
    w = (
        jnp.asarray(np.array([d[2] for d in data], np.float32))
        if weighted
        else None
    )
    bank = sb.collapse_to(
        sb.empty(spec, k), jnp.full(k, level, jnp.int32), spec=spec
    )
    got_m, got_s, got_f = _add_each_method(bank, x, s, w, spec)
    _assert_banks_equal(got_m, got_f)
    _assert_banks_equal(got_s, got_f)


# --------------------------------------------------------------------- #
# adversarial streams
# --------------------------------------------------------------------- #
def test_all_unique_stream_parity(rng):
    """Every lane lands in its own bucket — the worst case for the sort
    pipeline's compaction and the fused kernel's one-hot binning alike."""
    spec = BucketSpec(num_buckets=512, offset=-256)
    k, n = 3, 600
    x = jnp.asarray(
        np.geomspace(1.0, 1e12, n).astype(np.float32)
        * np.where(np.arange(n) % 2 == 0, 1.0, -1.0).astype(np.float32)
    )
    s = jnp.asarray((np.arange(n) % k).astype(np.int32))
    got_m, got_s, got_f = _add_each_method(sb.empty(spec, k), x, s, None, spec)
    _assert_banks_equal(got_m, got_f)
    _assert_banks_equal(got_s, got_f)
    assert float(got_f.summ.sum()) == pytest.approx(float(x.sum()), rel=1e-6)


def test_all_duplicate_stream_parity(rng):
    """Every lane hits the SAME (row, bucket) cell: maximal accumulation
    depth through the fused one-hot matmul."""
    spec = BucketSpec()
    n = 5000
    x = jnp.full(n, 3.7, jnp.float32)
    s = jnp.zeros(n, jnp.int32)
    w = jnp.asarray(rng.integers(1, 3, n).astype(np.float32))
    got_m, got_s, got_f = _add_each_method(sb.empty(spec, 1), x, s, w, spec)
    _assert_banks_equal(got_m, got_f)
    _assert_banks_equal(got_s, got_f)
    assert float(got_f.pos.sum()) == float(w.sum())
    assert float(got_f.vmin[0]) == pytest.approx(3.7, rel=1e-6)
    assert float(got_f.vmax[0]) == pytest.approx(3.7, rel=1e-6)


def test_inert_padding_lanes_contribute_nothing(rng):
    """The engine pads batches to power-of-two with (NaN, -1, 0) lanes; the
    fused path must treat them as inert in the histograms AND every stat
    (a padded vmin/vmax leak would poison the row extrema forever)."""
    spec = BucketSpec()
    k, n, pad = 6, 1000, 1048
    x = _data(n, rng)
    s = rng.integers(0, k, n).astype(np.int32)
    w = rng.integers(1, 4, n).astype(np.float32)
    xp = np.concatenate([x, np.full(pad, np.nan, np.float32)])
    sp = np.concatenate([s, np.full(pad, -1, np.int32)])
    wp = np.concatenate([w, np.zeros(pad, np.float32)])
    bank = sb.empty(spec, k)
    want = sb.add(
        bank, jnp.asarray(x), jnp.asarray(s), jnp.asarray(w), spec=spec,
        method="fused",
    )
    got = sb.add(
        bank, jnp.asarray(xp), jnp.asarray(sp), jnp.asarray(wp), spec=spec,
        method="fused",
    )
    _assert_banks_equal(want, got)


# --------------------------------------------------------------------- #
# Pallas kernel (interpret mode) vs the XLA twin, across tile shapes
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "value_tile,bucket_tile", [(1024, 512), (256, 128), (2048, 2048)]
)
def test_kernel_interpret_matches_ref(value_tile, bucket_tile, rng):
    spec = BucketSpec(num_buckets=512, offset=-256)
    n, k = 3000, 6
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-1, k + 1, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    lev = jnp.asarray(
        rng.integers(0, MAX_COLLAPSE_LEVEL + 1, n).astype(np.int32)
    )
    want_hist, want = fused_ingest_ref(x, s, w, lev, num_segments=k, spec=spec)
    got_hist, got = ddsketch_ingest_pallas(
        x, s, w, lev, num_segments=k, spec=spec,
        value_tile=value_tile, bucket_tile=bucket_tile, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_hist), np.asarray(want_hist))
    for name in ("zero", "overflow", "underflow", "vmin", "vmax"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)),
            err_msg=f"stat {name!r} differs",
        )
    # summ accumulates in tile order inside the kernel: ulp-level drift
    np.testing.assert_allclose(
        np.asarray(got.summ), np.asarray(want.summ), rtol=1e-5
    )


def test_kernel_interpret_empty_and_tiny(rng):
    spec = BucketSpec(num_buckets=128, offset=-64)
    for n in (0, 1, 7):
        x = jnp.asarray(_data(n, rng) if n else np.zeros(0, np.float32))
        s = jnp.asarray(np.zeros(n, np.int32))
        want_hist, want = fused_ingest_ref(x, s, num_segments=2, spec=spec)
        got_hist, got = ddsketch_ingest_pallas(
            x, s, num_segments=2, spec=spec, interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(got_hist), np.asarray(want_hist)
        )
        np.testing.assert_array_equal(np.asarray(got.vmin), np.asarray(want.vmin))
        np.testing.assert_allclose(
            np.asarray(got.summ), np.asarray(want.summ), rtol=1e-5
        )


# --------------------------------------------------------------------- #
# dispatch contracts
# --------------------------------------------------------------------- #
def test_insert_method_env_override(monkeypatch):
    for pick in ("matmul", "sort", "fused"):
        monkeypatch.setenv("REPRO_INSERT_METHOD", pick)
        # the override wins regardless of sizes, tier or ingest kind
        assert ops.insert_method(10, 4, 128) == pick
        assert ops.insert_method(1 << 20, 128, 4096, on_tpu=True) == pick
        assert ops.insert_method(0, 1, 64, full_ingest=True) == pick
    monkeypatch.setenv("REPRO_INSERT_METHOD", "bogus")
    with pytest.raises(ValueError, match="REPRO_INSERT_METHOD"):
        ops.insert_method(10, 4, 128)
    monkeypatch.delenv("REPRO_INSERT_METHOD")
    assert ops.insert_method(10, 4, 128) == "matmul"


def test_insert_method_full_ingest_heuristic():
    # XLA ref tier: fused subsumes the stats pass once the batch amortizes
    # the scatter plumbing; below the crossover matmul still wins
    assert ops.insert_method(1 << 20, 128, 4096, on_tpu=False,
                             full_ingest=True) == "fused"
    assert ops.insert_method((1 << 14) - 1, 128, 4096, on_tpu=False,
                             full_ingest=True) == "matmul"
    # TPU: fused wins while the bucket-tile count stays under the sort
    # factor; a huge-m small-N ingest flips to the compacting sort path
    assert ops.insert_method(1 << 20, 128, 4096, on_tpu=True,
                             full_ingest=True) == "fused"
    assert ops.insert_method(1 << 10, 16, 32768, on_tpu=True,
                             full_ingest=True) == "sort"
    # banks taller than the resident-row ceiling never fuse
    assert ops.insert_method(1 << 20, 4096, 2048, on_tpu=True,
                             full_ingest=True) == "matmul"
    # hist-only callers keep the two-way rule: fused is opt-in there
    assert ops.insert_method(1 << 20, 128, 4096, on_tpu=False) == "sort"


def test_picked_insert_method_dense_stats_downgrade():
    """Small banks keep the two-pass sort path on the ref tier: the dense
    (K, N) masked stats beat the fused segment reductions there."""
    assert sb.picked_insert_method(1 << 18, 8, 2048) == "sort"
    assert sb.picked_insert_method(1 << 18, 128, 2048) == "fused"
    # the kernel tier has no dense-stats regime: fused stands
    assert sb.picked_insert_method(1 << 18, 8, 2048, on_tpu=True) == "fused"


def test_fused_auto_falls_back_for_tall_banks(monkeypatch, rng):
    """Banks taller than MAX_RESIDENT_ROWS route to the XLA ref — and the
    fallback is observable: RuntimeWarning once per site plus a counter in
    ops.dispatch_stats() (the PR-7 fix for the silent path change)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_stats()
    k = MAX_RESIDENT_ROWS // 2 + 8
    n = 2048
    spec = BucketSpec(num_buckets=64, offset=-32)
    x = jnp.asarray((rng.pareto(1.0, n) + 1.0).astype(np.float32))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    with pytest.warns(RuntimeWarning, match="MAX_RESIDENT_ROWS"):
        pos, neg, stats = ops.fused_ingest(x, s, num_segments=k, spec=spec)
    wpos, wneg, wstats = ops.fused_ingest(
        x, s, num_segments=k, spec=spec, force="ref"
    )
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(wpos))
    np.testing.assert_array_equal(np.asarray(neg), np.asarray(wneg))
    np.testing.assert_array_equal(
        np.asarray(stats.zero), np.asarray(wstats.zero)
    )
    assert ops.dispatch_stats()["tall_bank_fallbacks"]["fused_ingest"] == 1
    # warn-once: the second trace counts but stays quiet
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.fused_ingest(x, s, num_segments=k, spec=spec)
    assert ops.dispatch_stats()["tall_bank_fallbacks"]["fused_ingest"] == 2
    ops.reset_dispatch_stats()
    assert ops.dispatch_stats() == {
        "tall_bank_fallbacks": {},
        "range_merge_calls": {},
        "query_fallbacks": {},
        "subtile_fallbacks": {},
    }


def test_engine_fused_method_parity(rng):
    """method="fused" threads through the engine's AOT executables (with
    its inert pow-2 padding) and matches the sort-pipeline engine state."""
    from repro.engine import SketchEngine

    spec = BucketSpec()
    k, n = 32, 3000  # odd n: exercises the engine's padding lanes
    vals = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    ids = rng.integers(0, k, n).astype(np.int32)
    eng_f = SketchEngine(spec, k, method="fused")
    eng_s = SketchEngine(spec, k, method="sort")
    got = eng_f.add(eng_f.new_bank(), vals, ids)
    want = eng_s.add(eng_s.new_bank(), vals, ids)
    _assert_banks_equal(got, want)
    qs = np.asarray([0.5, 0.95], np.float32)
    np.testing.assert_allclose(
        np.asarray(eng_f.quantiles(got, qs)),
        np.asarray(eng_s.quantiles(want, qs)),
    )
