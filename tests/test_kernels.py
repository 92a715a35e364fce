"""Pallas kernel vs pure-jnp oracle: exact agreement across shapes, dtypes,
mappings, weights, and tile configurations (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.ddsketch_hist import histogram_pallas
from repro.kernels.ops import ddsketch_histogram
from repro.kernels.ref import BucketSpec, bucket_index, histogram_ref
from repro.core.mapping import make_mapping

SHAPES = [(7,), (128,), (1000,), (2048,), (5000,), (16, 257), (4, 4, 129)]
MAPPINGS = ["log", "linear", "cubic"]


def _data(shape, rng, kind="pareto"):
    n = int(np.prod(shape))
    if kind == "pareto":
        x = rng.pareto(1.0, n) + 1.0
    else:
        x = rng.lognormal(0, 3, n)
    # sprinkle non-finite and non-positive entries (must be ignored)
    specials = np.array([np.nan, np.inf, -np.inf, -1.0, 0.0, 1e-38, 1e38])
    idx = rng.choice(n, size=min(7, n), replace=False)
    x[idx] = specials[: len(idx)]
    return x.reshape(shape).astype(np.float32)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("mapping", MAPPINGS)
def test_kernel_matches_ref(shape, mapping, rng):
    spec = BucketSpec(mapping=mapping)
    x = jnp.asarray(_data(shape, rng))
    ref = histogram_ref(x, spec=spec)
    ker = histogram_pallas(x, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    assert float(ref.sum()) > 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.bfloat16, jnp.float16])
def test_kernel_dtypes(dtype, rng):
    spec = BucketSpec()
    x = jnp.asarray(rng.pareto(1.0, 513) + 1.0).astype(dtype)
    ref = histogram_ref(x, spec=spec)
    ker = histogram_pallas(x, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_kernel_weights(rng):
    spec = BucketSpec()
    x = jnp.asarray(_data((777,), rng))
    w = jnp.asarray(rng.integers(0, 5, 777).astype(np.float32))
    ref = histogram_ref(x, w, spec=spec)
    ker = histogram_pallas(x, w, spec=spec, interpret=True)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


@pytest.mark.parametrize("value_tile,bucket_tile", [(256, 128), (512, 2048), (2048, 256)])
def test_kernel_tilings(value_tile, bucket_tile, rng):
    spec = BucketSpec()
    x = jnp.asarray(_data((3000,), rng))
    ref = histogram_ref(x, spec=spec)
    ker = histogram_pallas(
        x, spec=spec, value_tile=value_tile, bucket_tile=bucket_tile, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_kernel_rejects_bad_tiling():
    with pytest.raises(ValueError):
        histogram_pallas(
            jnp.ones(8), spec=BucketSpec(num_buckets=2048), bucket_tile=1000,
            interpret=True,
        )


@pytest.mark.parametrize("mapping", MAPPINGS)
def test_bucket_index_matches_host_mapping(mapping, rng):
    """Vectorized index math == scalar host mapping (float32 tolerance: the
    kernel computes in f32, the host in f64 — keys may differ by at most 1
    bucket near boundaries, which preserves 2-alpha accuracy; exact
    agreement holds away from boundaries)."""
    spec = BucketSpec(mapping=mapping)
    m = make_mapping(mapping, spec.relative_accuracy)
    x = (rng.pareto(1.0, 4000) + 1.0).astype(np.float32)
    idx = np.asarray(bucket_index(jnp.asarray(x), spec))
    host_keys = np.array([m.key(float(v)) for v in x])
    host_idx = np.clip(host_keys - spec.offset, 0, spec.num_buckets - 1)
    assert np.abs(idx - host_idx).max() <= 1
    assert (idx == host_idx).mean() > 0.99


def test_log_mapping_log_is_within_one_ulp(rng):
    """The "log" mapping's natural log (``ref._ln``) is built from adds and
    multiplies so it holds ~1 ulp on every backend, bucket edges included
    (a backend's own ``log`` may not: on a TPU v5e it is off by thousands)."""
    from repro.kernels.ref import _ln

    spec = BucketSpec()
    edges = spec.gamma ** np.arange(-1000.0, 1000.0)
    eps = np.array([-3e-5, -1e-6, 0.0, 1e-6, 3e-5])
    x = np.concatenate([
        (edges[:, None] * (1.0 + eps)).ravel(),
        np.exp(rng.uniform(-87.0, 88.0, 50_000)),
        [1.0, 2.0, 0.5, np.float32(0.17905134)],
    ]).astype(np.float32)
    got = np.asarray(_ln(jnp.asarray(x))).astype(np.float64)
    want = np.log(x.astype(np.float64))
    ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    nz = want != 0.0
    assert np.max(np.abs(got - want)[nz] / ulp[nz]) <= 1.0
    assert got[~nz].tolist() == [0.0] * int((~nz).sum())


def test_ops_dispatch_ref_on_cpu(rng):
    spec = BucketSpec()
    x = jnp.asarray(_data((512,), rng))
    out = ddsketch_histogram(x, spec=spec)  # auto -> ref on CPU
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(histogram_ref(x, spec=spec))
    )
    out2 = ddsketch_histogram(x, spec=spec, force="interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_kernel_empty_and_all_masked():
    spec = BucketSpec()
    x = jnp.asarray([-1.0, 0.0, jnp.nan], jnp.float32)
    ker = histogram_pallas(x, spec=spec, interpret=True)
    assert float(ker.sum()) == 0.0
