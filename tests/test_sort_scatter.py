"""Sort–reduce–scatter ingest pipeline: exact agreement with the
matmul-histogram path across mappings, weights, levels, segment counts and
hostile inputs; the scatter kernel vs its XLA oracle in interpret mode; and
the ops dispatch contracts (method heuristic + size-aware force=None)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.kernels import ops
from repro.kernels.ddsketch_scatter import MAX_RESIDENT_ROWS, ddsketch_scatter_pallas
from repro.kernels.ref import (
    BucketSpec,
    compact_triples,
    composite_keys,
    scatter_histogram_ref,
    segment_histogram_ref,
)

MAPPINGS = ["log", "linear", "cubic"]


def _data(n, rng):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    x *= np.where(rng.random(n) < 0.4, -1.0, 1.0).astype(np.float32)
    specials = np.array([np.nan, np.inf, -np.inf, -1.0, 0.0, 1e-38, 1e38])
    idx = rng.choice(n, size=min(7, n), replace=False)
    x[idx] = specials[: len(idx)].astype(np.float32)
    return x


def _matmul_pair(x, s, w, lev, k, spec):
    pos = segment_histogram_ref(
        jnp.where(x > spec.min_indexable, x, -1.0), s, w, lev,
        num_segments=k, spec=spec,
    )
    neg = segment_histogram_ref(
        jnp.where(x < -spec.min_indexable, -x, -1.0), s, w, lev,
        num_segments=k, spec=spec,
    )
    return pos, neg


# --------------------------------------------------------------------- #
# pipeline parity: compact + scatter == the sign-masked segmented histograms
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("num_segments", [1, 3, 37])
@pytest.mark.parametrize("mapping", MAPPINGS)
def test_pipeline_matches_matmul_ref(num_segments, mapping, rng):
    spec = BucketSpec(mapping=mapping)
    n = 4000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-2, num_segments + 3, n).astype(np.int32))
    keys, wts = compact_triples(x, s, num_segments=num_segments, spec=spec)
    both = scatter_histogram_ref(
        keys, wts, num_rows=2 * num_segments, num_buckets=spec.num_buckets
    )
    pos, neg = _matmul_pair(x, s, None, None, num_segments, spec)
    np.testing.assert_array_equal(np.asarray(both[:num_segments]), np.asarray(pos))
    np.testing.assert_array_equal(np.asarray(both[num_segments:]), np.asarray(neg))
    assert float(both.sum()) > 0


def test_pipeline_weighted_and_levelled(rng):
    spec = BucketSpec()
    n, k = 3000, 11
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    lev = jnp.asarray(rng.integers(0, 4, n).astype(np.int32))
    keys, wts = compact_triples(x, s, w, lev, num_segments=k, spec=spec)
    both = scatter_histogram_ref(keys, wts, num_rows=2 * k, num_buckets=spec.num_buckets)
    pos, neg = _matmul_pair(x, s, w, lev, k, spec)
    np.testing.assert_array_equal(np.asarray(both[:k]), np.asarray(pos))
    np.testing.assert_array_equal(np.asarray(both[k:]), np.asarray(neg))


def test_compact_triples_unique_live_keys(rng):
    """The reduce stage really compacts: every live key appears once."""
    spec = BucketSpec()
    k, n = 5, 4000
    x = jnp.asarray(np.abs(_data(n, rng)))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    keys, wts = compact_triples(x, s, num_segments=k, spec=spec)
    live = np.asarray(keys)[np.asarray(keys) < 2 * k * spec.num_buckets]
    assert live.size == np.unique(live).size
    assert live.size < n  # pareto data concentrates: real compaction happened
    # total mass is conserved through the reduce
    total = float(np.asarray(wts)[np.asarray(keys) < 2 * k * spec.num_buckets].sum())
    pos, neg = _matmul_pair(x, s, None, None, k, spec)
    assert total == float(pos.sum() + neg.sum())


def test_compact_triples_packs_runs_to_front(rng):
    """The packed layout is what lets the kernel path statically slice the
    streamed axis to min(N, 2Km+1): everything past that bound must be
    empty, and the slice must lose nothing."""
    spec = BucketSpec(num_buckets=256, offset=-128)
    k, n = 3, 5000  # 2Km + 1 = 1537 << n: real compaction headroom
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-1, k + 1, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 4, n).astype(np.float32))
    for weights in (None, w):
        keys, wts = compact_triples(x, s, weights, num_segments=k, spec=spec)
        cap = 2 * k * spec.num_buckets + 1
        live = np.asarray(keys) < 2 * k * spec.num_buckets
        assert not live[cap:].any()  # all live runs sit inside the bound
        assert (np.asarray(wts)[cap:] == 0).all()
        full = scatter_histogram_ref(keys, wts, num_rows=2 * k,
                                     num_buckets=spec.num_buckets)
        sliced = scatter_histogram_ref(keys[:cap], wts[:cap], num_rows=2 * k,
                                       num_buckets=spec.num_buckets)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(sliced))


def test_compact_triples_weighted_fast_path_parity(rng):
    """The two-pass weighted path (sort keys + permutation, gather weights)
    compacts identically to the payload sort: exact run totals for
    integer-valued weights, ulp-close for fractional ones."""
    spec = BucketSpec(num_buckets=256, offset=-128)
    k, n = 4, 5000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-1, k + 1, n).astype(np.int32))
    int_w = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    frac_w = jnp.asarray(rng.random(n).astype(np.float32))

    keys_fast, wts_fast = compact_triples(x, s, int_w, num_segments=k, spec=spec)
    keys_pay, wts_pay = compact_triples(
        x, s, int_w, num_segments=k, spec=spec, payload_sort=True
    )
    np.testing.assert_array_equal(np.asarray(keys_fast), np.asarray(keys_pay))
    np.testing.assert_array_equal(np.asarray(wts_fast), np.asarray(wts_pay))

    keys_fast, wts_fast = compact_triples(x, s, frac_w, num_segments=k, spec=spec)
    keys_pay, wts_pay = compact_triples(
        x, s, frac_w, num_segments=k, spec=spec, payload_sort=True
    )
    np.testing.assert_array_equal(np.asarray(keys_fast), np.asarray(keys_pay))
    np.testing.assert_allclose(
        np.asarray(wts_fast), np.asarray(wts_pay), rtol=1e-6
    )
    # downstream parity: the scattered bank is what actually matters
    full_fast = scatter_histogram_ref(
        keys_fast, wts_fast, num_rows=2 * k, num_buckets=spec.num_buckets
    )
    full_pay = scatter_histogram_ref(
        keys_pay, wts_pay, num_rows=2 * k, num_buckets=spec.num_buckets
    )
    np.testing.assert_allclose(
        np.asarray(full_fast), np.asarray(full_pay), rtol=1e-6
    )


def test_composite_keys_int32_overflow_guard():
    spec = BucketSpec(num_buckets=2048)
    with pytest.raises(ValueError, match="int32"):
        composite_keys(
            jnp.ones(4), jnp.zeros(4, jnp.int32), None,
            num_segments=1 << 22, spec=spec,
        )


def test_compact_triples_empty_batch():
    spec = BucketSpec()
    keys, wts = compact_triples(jnp.zeros((0,)), jnp.zeros((0,), jnp.int32),
                                num_segments=4, spec=spec)
    assert keys.shape == (0,) and wts.shape == (0,)
    out = scatter_histogram_ref(keys, wts, num_rows=8, num_buckets=spec.num_buckets)
    assert float(out.sum()) == 0.0


# --------------------------------------------------------------------- #
# the scatter kernel vs its oracle (interpret mode)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "triple_tile,bucket_tile", [(256, 128), (512, 2048), (2048, 256), (1024, 512)]
)
def test_scatter_kernel_matches_ref(triple_tile, bucket_tile, rng):
    spec = BucketSpec()
    k, n = 19, 3000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    keys, wts = compact_triples(x, s, w, num_segments=k, spec=spec)
    ref = scatter_histogram_ref(keys, wts, num_rows=2 * k, num_buckets=spec.num_buckets)
    ker = ddsketch_scatter_pallas(
        keys, wts, num_rows=2 * k, num_buckets=spec.num_buckets,
        triple_tile=triple_tile, bucket_tile=bucket_tile, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@pytest.mark.parametrize("num_buckets", [1000, 2000])
def test_scatter_kernel_non_multiple_bucket_count(num_buckets, rng):
    """Acceptance: the scatter kernel pads non-multiple bucket axes."""
    spec = BucketSpec(num_buckets=num_buckets, offset=-num_buckets // 2)
    k, n = 7, 2000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    keys, wts = compact_triples(x, s, num_segments=k, spec=spec)
    ref = scatter_histogram_ref(keys, wts, num_rows=2 * k, num_buckets=num_buckets)
    ker = ddsketch_scatter_pallas(
        keys, wts, num_rows=2 * k, num_buckets=num_buckets,
        triple_tile=512, bucket_tile=512, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_scatter_kernel_duplicate_keys_accumulate(rng):
    """Raw (uncompacted) integer-weight triples still accumulate exactly."""
    spec = BucketSpec()
    keys = jnp.asarray(rng.integers(0, 64, 500).astype(np.int32))
    w = jnp.asarray(rng.integers(1, 4, 500).astype(np.float32))
    ref = scatter_histogram_ref(keys, w, num_rows=2, num_buckets=spec.num_buckets)
    ker = ddsketch_scatter_pallas(
        keys, w, num_rows=2, num_buckets=spec.num_buckets,
        triple_tile=128, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_scatter_kernel_guards():
    spec = BucketSpec()
    with pytest.raises(ValueError, match="MAX_RESIDENT_ROWS"):
        ddsketch_scatter_pallas(
            jnp.zeros(8, jnp.int32), jnp.zeros(8),
            num_rows=MAX_RESIDENT_ROWS + 1, num_buckets=spec.num_buckets,
            interpret=True,
        )
    with pytest.raises(ValueError, match="same size"):
        ddsketch_scatter_pallas(
            jnp.zeros(8, jnp.int32), jnp.zeros(9),
            num_rows=8, num_buckets=spec.num_buckets, interpret=True,
        )
    out = ddsketch_scatter_pallas(
        jnp.zeros((0,), jnp.int32), jnp.zeros((0,)),
        num_rows=8, num_buckets=spec.num_buckets, interpret=True,
    )
    assert out.shape == (8, spec.num_buckets) and float(out.sum()) == 0.0


# --------------------------------------------------------------------- #
# ops dispatch: method pin/auto + size-aware force=None
# --------------------------------------------------------------------- #
def test_bank_histograms_methods_agree(rng):
    spec = BucketSpec()
    k, n = 13, 3000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    for weights in (None, w):
        a = ops.bank_histograms(x, s, weights, num_segments=k, spec=spec,
                                method="matmul", force="ref")
        b = ops.bank_histograms(x, s, weights, num_segments=k, spec=spec,
                                method="sort", force="ref")
        for ga, gb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
    with pytest.raises(ValueError, match="method"):
        ops.bank_histograms(x, s, num_segments=k, spec=spec, method="radix")
    with pytest.raises(ValueError, match="single-row"):
        ops.bank_histograms(x, None, num_segments=k, spec=spec)


def test_bank_add_method_parity_full_state(rng):
    spec = BucketSpec()
    k, n = 9, 3000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-1, k + 1, n).astype(np.int32))
    for auto in (False, True):
        a = sb.add(sb.empty(spec, k), x, s, spec=spec, method="matmul",
                   auto_collapse=auto)
        b = sb.add(sb.empty(spec, k), x, s, spec=spec, method="sort",
                   auto_collapse=auto)
        for fa, fb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_single_sketch_add_method_parity(rng):
    spec = BucketSpec()
    x = jnp.asarray(_data(2000, rng))
    w = jnp.asarray(rng.integers(0, 3, 2000).astype(np.float32))
    a = js.add(js.empty(spec), x, w, spec=spec, method="matmul")
    b = js.add(js.empty(spec), x, w, spec=spec, method="sort")
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_insert_method_heuristic():
    # TPU: the output-tile count must outgrow log2(N) for sort to pay off
    assert ops.insert_method(1 << 20, 1, 2048, on_tpu=True) == "matmul"
    assert ops.insert_method(1 << 20, 128, 4096, on_tpu=True) == "sort"
    assert ops.insert_method(1 << 20, 4096, 2048, on_tpu=True) == "matmul"  # > row cap
    # weighted streams payload-sort: the crossover sits twice as far out
    assert ops.insert_method(1 << 20, 128, 2048, on_tpu=True) == "sort"
    assert ops.insert_method(1 << 20, 128, 2048, unit_weights=False,
                             on_tpu=True) == "matmul"
    # XLA ref tier: one key pass + one reducing scatter beats two of each
    # once the batch amortizes the plumbing (weighted or not)
    assert ops.insert_method(1 << 20, 128, 4096, on_tpu=False) == "sort"
    assert ops.insert_method(1 << 14, 1, 2048, on_tpu=False) == "sort"
    assert ops.insert_method((1 << 14) - 1, 128, 4096, on_tpu=False) == "matmul"
    assert ops.insert_method(1 << 20, 128, 4096, unit_weights=False,
                             on_tpu=False) == "sort"
    assert ops.insert_method(0, 128, 4096, on_tpu=True) == "matmul"


def test_size_aware_dispatch_crossover(monkeypatch):
    """Regression (satellite): force=None on TPU used to launch the Pallas
    kernel even for sub-tile batches where padding to value_tile dominates;
    auto now routes them to the XLA ref.  The crossover is value_tile."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_stats()
    with pytest.warns(RuntimeWarning, match="below one tile"):
        assert ops._impl(None, 2047, 2048, "site") == "ref"
    assert ops._impl(None, 2048, 2048, "site") == "pallas"
    assert ops._impl(None, 0, 2048, "site") == "ref"
    # the sub-tile detour is counted, not silent
    assert ops.dispatch_stats()["subtile_fallbacks"] == {"site": 2}
    assert ops.fallback_count() == 2
    # pinned values always pass through untouched (and uncounted)
    assert ops._impl("ref", 1 << 20, 2048, "site") == "ref"
    assert ops._impl("interpret", 4, 2048, "site") == "interpret"
    # off TPU the reference is the plan, not a detour
    monkeypatch.setattr(ops, "_on_tpu", lambda: False)
    assert ops._impl(None, 1 << 20, 2048, "site") == "ref"
    assert ops._impl(None, 4, 2048, "site") == "ref"
    assert ops.fallback_count() == 2
    ops.reset_dispatch_stats()


def test_scatter_auto_falls_back_for_tall_banks(monkeypatch, rng):
    """Regression: force=None promises a working path, so auto must route
    banks taller than MAX_RESIDENT_ROWS to the XLA ref instead of letting
    the resident-row kernel raise."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    rows = MAX_RESIDENT_ROWS + 8
    keys = jnp.asarray(rng.integers(0, rows * 64, 4096).astype(np.int32))
    w = jnp.ones(4096, jnp.float32)
    out = ops.ddsketch_scatter(keys, w, num_rows=rows, num_buckets=64)
    ref = scatter_histogram_ref(keys, w, num_rows=rows, num_buckets=64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_force_validation_still_enforced(rng):
    import jax

    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU guard")
    spec = BucketSpec()
    x = jnp.ones(64)
    with pytest.raises(RuntimeError, match="pallas"):
        ops.bank_histograms(x, jnp.zeros(64, jnp.int32), num_segments=2,
                            spec=spec, force="pallas")
    with pytest.raises(ValueError, match="force"):
        ops.ddsketch_scatter(jnp.zeros(8, jnp.int32), jnp.zeros(8),
                             num_rows=2, num_buckets=spec.num_buckets,
                             force="jit")


def test_bank_histograms_interpret_matches_ref(rng):
    spec = BucketSpec()
    k, n = 6, 2500
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    for method in ("matmul", "sort"):
        a = ops.bank_histograms(x, s, num_segments=k, spec=spec,
                                method=method, force="ref")
        b = ops.bank_histograms(x, s, num_segments=k, spec=spec,
                                method=method, force="interpret")
        for ga, gb in zip(a, b):
            np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
