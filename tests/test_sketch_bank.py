"""SketchBank: K sketches in stacked arrays must behave exactly like K
independent DeviceSketches — same buckets, same aux stats, same quantiles —
while inserting via a single segmented dispatch and merging via '+'."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.kernels.ref import BucketSpec

from util import run_with_devices

SPEC = BucketSpec(relative_accuracy=0.01, num_buckets=2048, offset=-1024)
QS = (0.0, 0.01, 0.25, 0.5, 0.95, 0.99, 1.0)


def _mixed_stream(rng, n, k):
    """Positive/negative/zero/non-finite soup with ids straddling [0, k)."""
    x = np.concatenate(
        [
            rng.pareto(1.0, n // 2) + 1.0,
            -(rng.lognormal(0, 2, n - n // 2 - 8)),
            np.zeros(4),
            [np.nan, np.inf, -np.inf, 1e-38],
        ]
    ).astype(np.float32)
    rng.shuffle(x)
    s = rng.integers(-1, k + 2, n).astype(np.int32)
    return x, s


def test_bank_matches_independent_sketches(rng):
    k, n = 13, 6000
    x, s = _mixed_stream(rng, n, k)
    w = rng.integers(0, 4, n).astype(np.float32)
    bank = sb.add(
        sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), jnp.asarray(w), spec=SPEC
    )
    for i in range(k):
        mask = s == i
        sk = js.add(
            js.empty(SPEC),
            jnp.asarray(np.where(mask, x, np.nan)),
            jnp.asarray(w),
            spec=SPEC,
        )
        np.testing.assert_array_equal(np.asarray(sk.pos), np.asarray(bank.pos[i]))
        np.testing.assert_array_equal(np.asarray(sk.neg), np.asarray(bank.neg[i]))
        assert float(sk.zero) == float(bank.zero[i])
        assert float(sk.overflow) == float(bank.overflow[i])
        # summ is a float accumulation: the bank's dense small-K stats path
        # reassociates the reduction vs the scalar sketch's .sum()
        assert float(sk.summ) == pytest.approx(float(bank.summ[i]), rel=1e-5)
        assert float(sk.vmin) == float(bank.vmin[i])
        assert float(sk.vmax) == float(bank.vmax[i])


def test_bank_quantiles_match_single_sketch_quantiles(rng):
    k, n = 9, 8000
    x, s = _mixed_stream(rng, n, k)
    bank = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC)
    got = np.asarray(sb.quantiles(bank, jnp.asarray(QS), spec=SPEC))
    assert got.shape == (k, len(QS))
    for i in range(k):
        row = sb.row(bank, i)
        want = [float(js.quantile(row, q, spec=SPEC)) for q in QS]
        np.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-7)


def test_bank_add_is_single_dispatch_at_k4096(rng):
    """K=4096 in one call — and exactly ONE histogram dispatch inside:
    the jaxpr of bank.add must contain no Python-loop unrolling over K
    (the segmented scatter appears a constant number of times, vs >= K
    scatters if add looped)."""
    k, n = 4096, 20_000
    x = jnp.asarray((rng.pareto(1.0, n) + 1.0).astype(np.float32))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    bank = sb.add(sb.empty(SPEC, k), x, s, spec=SPEC)
    assert bank.pos.shape == (k, SPEC.num_buckets)
    assert float(bank.counts.sum()) == n

    jaxpr = jax.make_jaxpr(
        lambda b, v, i: sb.add(b, v, i, spec=SPEC)
    )(sb.empty(SPEC, k), x, s)
    n_scatters = str(jaxpr).count("scatter-add")
    assert 0 < n_scatters < 8, (
        f"expected O(1) scatter-adds regardless of K, found {n_scatters}"
    )


def test_bank_merge_is_elementwise_sum(rng):
    k, n = 7, 4000
    x, s = _mixed_stream(rng, n, k)
    b1 = sb.add(
        sb.empty(SPEC, k),
        jnp.asarray(x[: n // 2]),
        jnp.asarray(s[: n // 2]),
        spec=SPEC,
    )
    b2 = sb.add(
        sb.empty(SPEC, k),
        jnp.asarray(x[n // 2 :]),
        jnp.asarray(s[n // 2 :]),
        spec=SPEC,
    )
    merged = sb.merge(b1, b2, spec=SPEC)
    both = sb.add(b1, jnp.asarray(x[n // 2 :]), jnp.asarray(s[n // 2 :]), spec=SPEC)
    np.testing.assert_array_equal(np.asarray(merged.pos), np.asarray(both.pos))
    np.testing.assert_array_equal(np.asarray(merged.neg), np.asarray(both.neg))
    np.testing.assert_array_equal(np.asarray(merged.zero), np.asarray(both.zero))
    got = np.asarray(sb.quantiles(merged, jnp.asarray(QS), spec=SPEC))
    want = np.asarray(sb.quantiles(both, jnp.asarray(QS), spec=SPEC))
    np.testing.assert_array_equal(got, want)


def test_bank_kernel_path_matches_ref_path(rng, request):
    k, n = 33, 5000
    x, s = _mixed_stream(rng, n, k)
    ref_bank = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC)
    request.getfixturevalue("tpu_interpret")  # default dispatch -> kernel
    ker_bank = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC)
    from repro.kernels import ops

    assert sb.picked_insert_method(n, k, SPEC.num_buckets) == "fused"
    assert ops.fallback_count() == 0
    for name, f_ref, f_ker in zip(ref_bank._fields, ref_bank, ker_bank):
        if name == "summ":
            # the fused kernel sums in tile order, not lane order (its
            # documented contract): reassociation error is bounded by the
            # row's sum of |x|; every other field is bit-exact
            ok = np.isfinite(x) & (s >= 0) & (s < k)
            mass = np.bincount(s[ok], np.abs(x[ok]).astype(np.float64), minlength=k)
            err = np.abs(np.asarray(f_ker, np.float64) - np.asarray(f_ref, np.float64))
            assert (err <= 1e-6 * mass).all(), (err, mass)
        else:
            np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_ker))


def test_bank_row_and_set_row_roundtrip(rng):
    k = 5
    x, s = _mixed_stream(rng, 2000, k)
    bank = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC)
    single = js.add(js.empty(SPEC), jnp.asarray(np.abs(x) + 1.0), spec=SPEC)
    bank2 = sb.set_row(bank, 2, single)
    np.testing.assert_array_equal(np.asarray(sb.row(bank2, 2).pos), np.asarray(single.pos))
    # other rows untouched
    np.testing.assert_array_equal(np.asarray(sb.row(bank2, 1).pos), np.asarray(bank.pos[1]))


def test_bank_to_from_host_per_row(rng):
    k = 4
    x, s = _mixed_stream(rng, 3000, k)
    bank = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC)
    hosts = [sb.to_host(bank, SPEC, i) for i in range(k)]
    counts = np.asarray(bank.counts)
    for i in range(k):
        assert hosts[i].count == int(round(float(counts[i])))
        for q in (0.25, 0.5, 0.99):
            assert hosts[i].quantile(q) == pytest.approx(
                float(sb.quantiles(bank, jnp.asarray([q]), spec=SPEC)[i, 0]),
                rel=1e-5,
                abs=1e-7,
            )
    back = sb.from_host(hosts, SPEC)
    np.testing.assert_array_equal(np.asarray(back.pos), np.asarray(bank.pos))
    np.testing.assert_array_equal(np.asarray(back.neg), np.asarray(bank.neg))


def test_bank_empty_rows_quantile_nan():
    bank = sb.empty(SPEC, 3)
    out = np.asarray(sb.quantiles(bank, jnp.asarray([0.5, 0.99]), spec=SPEC))
    assert np.isnan(out).all()
    # one row fed -> only that row answers
    bank = sb.add(bank, jnp.asarray([1.0, 2.0]), jnp.asarray([1, 1]), spec=SPEC)
    out = np.asarray(sb.quantiles(bank, jnp.asarray([0.5]), spec=SPEC))
    assert np.isnan(out[0, 0]) and np.isnan(out[2, 0])
    assert np.isfinite(out[1, 0])


def test_bank_add_jittable_and_donatable(rng):
    k = 6
    data = jnp.asarray((rng.pareto(1.0, 256) + 1).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, k, 256).astype(np.int32))
    addf = jax.jit(
        lambda b, v, i: sb.add(b, v, i, spec=SPEC), donate_argnums=(0,)
    )
    bank = sb.empty(SPEC, k)
    for _ in range(3):
        bank = addf(bank, data, ids)
    assert float(bank.counts.sum()) == 3 * 256


# --------------------------------------------------------------------- #
# cross-device mergeability: the whole bank psums like one sketch
# --------------------------------------------------------------------- #
def test_bank_psum_merge_across_devices():
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core import sketch_bank as sb
from repro.kernels.ref import BucketSpec

SPEC = BucketSpec()
K = 16
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("d",))
rng = np.random.default_rng(0)
data = (rng.pareto(1.0, 8 * 500) + 1.0).astype(np.float32)
ids = rng.integers(0, K, 8 * 500).astype(np.int32)

def per_device(vals, sids):  # local shards
    bank = sb.add(sb.empty(SPEC, K), vals, sids, spec=SPEC)
    return sb.allreduce(bank, "d", spec=SPEC)

fn = shard_map(per_device, mesh=mesh, in_specs=(P("d"), P("d")), out_specs=P(),
               check_vma=False)
merged = jax.jit(fn)(jnp.asarray(data), jnp.asarray(ids))

whole = sb.add(sb.empty(SPEC, K), jnp.asarray(data), jnp.asarray(ids), spec=SPEC)
np.testing.assert_array_equal(np.asarray(merged.pos), np.asarray(whole.pos))
assert float(merged.counts.sum()) == 8 * 500
print("bank psum merge OK")
"""
    out = run_with_devices(script, 8)
    assert "bank psum merge OK" in out
