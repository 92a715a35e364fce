"""Segmented Pallas kernel vs pure-jnp oracle: exact agreement across
mappings, tile configurations, segment counts, row layouts, weights, and
hostile inputs (interpret mode on CPU); the row-sparse pair table; plus the
ops-dispatch contract."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ddsketch_seg_hist import (
    pair_bound,
    pair_table,
    segment_histogram_pallas,
)
from repro.kernels.ops import ddsketch_histogram, segment_histogram
from repro.kernels.ref import BucketSpec, histogram_ref, segment_histogram_ref

MAPPINGS = ["log", "linear", "cubic"]


def _data(n, rng):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    specials = np.array([np.nan, np.inf, -np.inf, -1.0, 0.0, 1e-38, 1e38])
    idx = rng.choice(n, size=min(7, n), replace=False)
    x[idx] = specials[: len(idx)].astype(np.float32)
    return x


@pytest.mark.parametrize("num_segments", [1, 3, 37, 64])
@pytest.mark.parametrize("mapping", MAPPINGS)
def test_seg_kernel_matches_ref(num_segments, mapping, rng):
    spec = BucketSpec(mapping=mapping)
    n = 4000
    x = jnp.asarray(_data(n, rng))
    # include out-of-range ids on both sides: they must contribute nothing
    s = jnp.asarray(rng.integers(-2, num_segments + 3, n).astype(np.int32))
    ref = segment_histogram_ref(x, s, num_segments=num_segments, spec=spec)
    ker = segment_histogram_pallas(
        x, s, num_segments=num_segments, spec=spec, interpret=True
    )
    assert ker.shape == (num_segments, spec.num_buckets)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert float(ref.sum()) > 0


def test_seg_rows_equal_per_segment_histograms(rng):
    """Row k of the segmented histogram == plain histogram of segment k."""
    spec = BucketSpec()
    n, k = 3000, 11
    x = _data(n, rng)
    s = rng.integers(0, k, n).astype(np.int32)
    seg = np.asarray(
        segment_histogram_ref(
            jnp.asarray(x), jnp.asarray(s), num_segments=k, spec=spec
        )
    )
    for i in range(k):
        only_i = np.where(s == i, x, -1.0).astype(np.float32)
        np.testing.assert_array_equal(
            seg[i], np.asarray(histogram_ref(jnp.asarray(only_i), spec=spec))
        )


@pytest.mark.parametrize(
    "value_tile,row_tile,bucket_tile",
    [(256, 8, 128), (512, 16, 2048), (2048, 4, 256), (1024, 128, 512)],
)
def test_seg_kernel_tilings(value_tile, row_tile, bucket_tile, rng):
    spec = BucketSpec()
    n, k = 3000, 19  # k deliberately not a row_tile multiple (pad rows)
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    w = jnp.asarray(rng.integers(0, 5, n).astype(np.float32))
    ref = segment_histogram_ref(x, s, w, num_segments=k, spec=spec)
    ker = segment_histogram_pallas(
        x,
        s,
        w,
        num_segments=k,
        spec=spec,
        value_tile=value_tile,
        row_tile=row_tile,
        bucket_tile=bucket_tile,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_seg_kernel_rejects_bad_shapes():
    spec = BucketSpec(num_buckets=2048)
    with pytest.raises(ValueError, match="same size"):
        segment_histogram_pallas(
            jnp.ones(8), jnp.zeros(9, jnp.int32), num_segments=4, spec=spec,
            interpret=True,
        )


@pytest.mark.parametrize("num_buckets,bucket_tile", [(2048, 1000), (1000, 512), (1000, 1024)])
def test_seg_kernel_non_multiple_bucket_tile(num_buckets, bucket_tile, rng):
    """Regression: a bucket_tile that does not divide num_buckets used to be
    a hard error; the bucket axis is now padded internally and sliced off."""
    spec = BucketSpec(num_buckets=num_buckets, offset=-num_buckets // 2)
    n, k = 3000, 5
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    ref = segment_histogram_ref(x, s, num_segments=k, spec=spec)
    ker = segment_histogram_pallas(
        x, s, num_segments=k, spec=spec, bucket_tile=bucket_tile, interpret=True
    )
    assert ker.shape == (k, num_buckets)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert float(ker.sum()) > 0


def test_seg_kernel_empty_and_all_masked():
    spec = BucketSpec()
    x = jnp.asarray([-1.0, 0.0, jnp.nan, 5.0], jnp.float32)
    s = jnp.asarray([0, 1, 2, -1], jnp.int32)  # the only positive has id -1
    ker = segment_histogram_pallas(x, s, num_segments=3, spec=spec, interpret=True)
    assert float(ker.sum()) == 0.0


def test_kernels_zero_length_input_returns_zeros():
    """Regression: an empty batch used to build a zero-length value grid
    (nv=0), crashing pallas_call and skipping the output-tile init."""
    from repro.kernels.ddsketch_hist import histogram_pallas

    spec = BucketSpec()
    empty_vals = jnp.zeros((0,), jnp.float32)
    seg = segment_histogram_pallas(
        empty_vals, jnp.zeros((0,), jnp.int32), num_segments=5, spec=spec,
        interpret=True,
    )
    assert seg.shape == (5, spec.num_buckets) and float(seg.sum()) == 0.0
    single = histogram_pallas(empty_vals, spec=spec, interpret=True)
    assert single.shape == (spec.num_buckets,) and float(single.sum()) == 0.0


def test_ops_seg_dispatch_ref_on_cpu(rng):
    spec = BucketSpec()
    x = jnp.asarray(_data(512, rng))
    s = jnp.asarray(rng.integers(0, 5, 512).astype(np.int32))
    out = segment_histogram(x, s, num_segments=5, spec=spec)  # auto -> ref
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(segment_histogram_ref(x, s, num_segments=5, spec=spec)),
    )
    out2 = segment_histogram(x, s, num_segments=5, spec=spec, force="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_force_pallas_raises_off_tpu(rng):
    """Regression: force="pallas" used to compile the TPU kernel on CPU
    (interpret=False) and die mid-lowering; now it raises up front."""
    if jax.default_backend() == "tpu":
        pytest.skip("on TPU force='pallas' is the real compiled path")
    spec = BucketSpec()
    x = jnp.asarray(rng.pareto(1.0, 64).astype(np.float32) + 1.0)
    with pytest.raises(RuntimeError, match="pallas"):
        ddsketch_histogram(x, spec=spec, force="pallas")
    with pytest.raises(RuntimeError, match="pallas"):
        segment_histogram(
            x, jnp.zeros(64, jnp.int32), num_segments=2, spec=spec, force="pallas"
        )


def test_force_rejects_unknown_value(rng):
    x = jnp.ones(8, jnp.float32)
    with pytest.raises(ValueError, match="force"):
        ddsketch_histogram(x, spec=BucketSpec(), force="jit")


# --------------------------------------------------------------------- #
# row-sparse grid: served layouts, the pair table, the sort skip
# --------------------------------------------------------------------- #
def _runs(rng, num_segments, lengths):
    """One row per run, runs in random row order (a tick of one-key POSTs)."""
    rows = rng.choice(num_segments, size=len(lengths), replace=False)
    return np.concatenate(
        [np.full(n, r, np.int32) for r, n in zip(rows, lengths)]
    )


def _exact_pairs(s, valid, row_tile, value_tile):
    """(row tile, value tile) pairs holding a valid lane, in sorted order."""
    order = np.argsort(np.where(valid, s, np.iinfo(np.int32).max), kind="stable")
    lanes = np.arange(len(s))
    keep = valid[order]
    return sorted({(int(r) // row_tile, int(i) // value_tile)
                   for r, i in zip(s[order][keep], lanes[keep])})


def test_seg_kernel_served_runs_tall_bank(rng):
    """The served shape: a 4096-row bank, each run one row in random order,
    run lengths straddling value tiles; integer weights stay exact."""
    spec = BucketSpec(num_buckets=2048)
    k = 4096
    s = _runs(rng, k, [3000, 2048, 1, 5000, 2047, 700, 4100])
    n = s.size
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    w = rng.integers(1, 4, n).astype(np.float32)
    ref = segment_histogram_ref(jnp.asarray(x), jnp.asarray(s), jnp.asarray(w),
                                num_segments=k, spec=spec)
    ker = segment_histogram_pallas(jnp.asarray(x), jnp.asarray(s), jnp.asarray(w),
                                   num_segments=k, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert float(ker.sum()) == float(w.sum())


@pytest.mark.parametrize("value_tile", [256, 2048])
def test_seg_kernel_uniform_rows_many_tiles(value_tile, rng):
    """Uniformly random rows over 64 row tiles: every value tile holds lanes
    of most row tiles, the pair bound's worst case."""
    spec = BucketSpec()
    k, n = 509, 6000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    ref = segment_histogram_ref(x, s, num_segments=k, spec=spec)
    ker = segment_histogram_pallas(x, s, num_segments=k, spec=spec,
                                   value_tile=value_tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_bank_histograms_matmul_one_stream_hostile(rng):
    """Both signs, zeros, NaN/inf and out-of-range ids in one stream through
    the stacked-layout launch equal the per-sign reference histograms."""
    spec = BucketSpec()
    k, n = 300, 5000
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    x *= np.where(rng.random(n) < 0.4, -1.0, 1.0).astype(np.float32)
    x[rng.choice(n, 60, replace=False)] = np.float32(0.0)
    x[:6] = [np.nan, np.inf, -np.inf, 1e-38, -1e-38, 1e38]
    s = _runs(rng, k, [1200, 900, 1500, 1400])
    s[rng.choice(n, 40, replace=False)] = rng.choice([-3, -1, k, k + 7], 40)
    w = rng.integers(0, 5, n).astype(np.float32)
    x, s, w = jnp.asarray(x), jnp.asarray(s), jnp.asarray(w)
    for weights in (None, w):
        pos, neg = ops.bank_histograms(x, s, weights, num_segments=k, spec=spec,
                                       method="matmul", force="interpret")
        want_pos = segment_histogram_ref(
            jnp.where(x > spec.min_indexable, x, -1.0), s, weights,
            num_segments=k, spec=spec)
        want_neg = segment_histogram_ref(
            jnp.where(x < -spec.min_indexable, -x, -1.0), s, weights,
            num_segments=k, spec=spec)
        np.testing.assert_array_equal(np.asarray(pos), np.asarray(want_pos))
        np.testing.assert_array_equal(np.asarray(neg), np.asarray(want_neg))
        assert float(pos.sum()) > 0 and float(neg.sum()) > 0


def test_bank_histograms_row_runs_tpu_interpret(tpu_interpret, rng):
    """The served dispatch in TPU interpret mode, which keeps output blocks in
    VMEM as the chip does: a row tile revisited after another must be zeroed
    on its first visit, not read back from the aliased zeros."""
    spec = BucketSpec()
    k = 40
    s = _runs(rng, k, [1500, 2048, 700, 3000, 900])
    x = (rng.pareto(1.0, s.size) + 1.0).astype(np.float32)
    x *= np.where(s % 3 == 0, -1.0, 1.0).astype(np.float32)
    x, s = jnp.asarray(x), jnp.asarray(s)
    pos, neg = ops.bank_histograms(x, s, num_segments=k, spec=spec, method="matmul")
    for got, vals in ((pos, jnp.where(x > 0, x, -1.0)), (neg, jnp.where(x < 0, -x, -1.0))):
        want = segment_histogram_ref(vals, s, num_segments=k, spec=spec)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seg_kernel_one_row_tile(rng):
    """A bank of one row tile: its pairs are the value tiles that hold a lane."""
    spec = BucketSpec()
    k, n = 6, 5000
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(-1, k + 1, n).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(segment_histogram_ref(x, s, num_segments=k, spec=spec)),
        np.asarray(segment_histogram_pallas(x, s, num_segments=k, spec=spec,
                                            interpret=True)),
    )


@pytest.mark.parametrize(
    "layout", ["runs", "uniform", "one_row", "all_invalid", "sparse_invalid"]
)
@pytest.mark.parametrize("value_tile", [128, 512])
def test_pair_table_lists_exact_pairs(layout, value_tile, rng):
    row_tile, k, n = 8, 200, 4096
    if layout == "runs":
        s = _runs(rng, k, [700, 1, 1500, 129, 1766])
    elif layout == "uniform":
        s = rng.integers(0, k, n).astype(np.int32)
    elif layout == "one_row":
        s = np.full(n, 77, np.int32)
    elif layout == "all_invalid":
        s = np.full(n, -1, np.int32)
    else:
        s = np.where(rng.random(n) < 0.97, -1, rng.integers(0, k, n)).astype(np.int32)
    rows_padded = k + (-k) % row_tile
    valid = (s >= 0) & (s < k)
    rows = np.sort(np.where(valid, s, rows_padded)).astype(np.int32)
    rt, vt, count = pair_table(jnp.asarray(rows), num_row_tiles=rows_padded // row_tile,
                               row_tile=row_tile, value_tile=value_tile)
    want = _exact_pairs(s, valid, row_tile, value_tile)
    count = int(count)
    assert count == len(want)
    assert count <= pair_bound(k, n, row_tile=row_tile, value_tile=value_tile)
    assert rt.shape == vt.shape == (pair_bound(k, n, row_tile=row_tile,
                                               value_tile=value_tile),)
    got = list(zip(np.asarray(rt)[:count].tolist(), np.asarray(vt)[:count].tolist()))
    assert got == want
    if count:  # the tail repeats the last pair: no new block, no DMA
        assert set(zip(np.asarray(rt)[count:], np.asarray(vt)[count:])) <= {want[-1]}


def test_bank_histograms_matmul_is_one_launch(rng):
    """Both sign stores of a matmul ingest come from one kernel launch."""
    spec = BucketSpec()
    x = jnp.asarray(_data(4096, rng))
    s = jnp.asarray(rng.integers(0, 100, 4096).astype(np.int32))
    jaxpr = str(jax.make_jaxpr(
        lambda x, s: ops.bank_histograms(x, s, num_segments=100, spec=spec,
                                         method="matmul", force="interpret")
    )(x, s))
    assert jaxpr.count("pallas_call[") == 1
