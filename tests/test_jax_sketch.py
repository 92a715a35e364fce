"""Device-tier sketch: host/device equivalence + psum mergeability.

The device sketch must agree with the paper-exact host sketch whenever no
value falls outside the static bucket range, and its merge must be the
plain '+' that makes it all-reducible (tested for real under shard_map on
8 fake devices, in a subprocess so the main process keeps 1 device).
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core.ddsketch import DDSketch
from repro.core.oracle import exact_quantile, relative_error
from repro.kernels.ref import BucketSpec

from util import run_with_devices

SPEC = BucketSpec(relative_accuracy=0.01, num_buckets=2048, offset=-1024)
QS = (0.01, 0.25, 0.5, 0.75, 0.95, 0.99, 1.0)


def _host_equiv(values):
    host = DDSketch(SPEC.relative_accuracy, max_bins=None, mapping=SPEC.mapping)
    host.extend(values)
    return host


values_in_range = st.lists(
    st.floats(min_value=1e-4, max_value=1e4, allow_nan=False).map(float)
    | st.floats(min_value=-1e4, max_value=-1e-4, allow_nan=False).map(float)
    | st.just(0.0),
    min_size=1,
    max_size=200,
)


@given(data=values_in_range)
@settings(max_examples=100, deadline=None)
def test_host_device_equivalence(data):
    sk = js.add(js.empty(SPEC), jnp.asarray(data, jnp.float32), spec=SPEC)
    host = _host_equiv(np.asarray(data, np.float32))
    for q in QS:
        dev = float(js.quantile(sk, q, spec=SPEC))
        hst = host.quantile(q)
        assert dev == pytest.approx(hst, rel=1e-5, abs=1e-7), (q, dev, hst)


def test_alpha_guarantee_device(rng):
    data = (rng.pareto(1.0, 5000) + 1.0).astype(np.float32)
    sk = js.add(js.empty(SPEC), jnp.asarray(data), spec=SPEC)
    s = np.sort(data)
    for q in QS:
        est = float(js.quantile(sk, q, spec=SPEC))
        assert relative_error(est, exact_quantile(s, q)) <= 0.0101


def test_merge_is_elementwise_sum(rng):
    a = (rng.pareto(1.0, 1000) + 1).astype(np.float32)
    b = (rng.lognormal(0, 1, 1000)).astype(np.float32)
    sa = js.add(js.empty(SPEC), jnp.asarray(a), spec=SPEC)
    sb = js.add(js.empty(SPEC), jnp.asarray(b), spec=SPEC)
    merged = js.merge(sa, sb, spec=SPEC)
    both = js.add(sa, jnp.asarray(b), spec=SPEC)
    assert np.array_equal(np.asarray(merged.pos), np.asarray(both.pos))
    assert float(merged.count) == 2000
    for q in QS:
        assert float(js.quantile(merged, q, spec=SPEC)) == float(
            js.quantile(both, q, spec=SPEC)
        )


def test_weights_and_nonfinite(rng):
    vals = jnp.asarray([1.0, jnp.nan, 10.0, jnp.inf, -5.0, 0.0], jnp.float32)
    w = jnp.asarray([2.0, 7.0, 1.0, 3.0, 1.0, 4.0], jnp.float32)
    sk = js.add(js.empty(SPEC), vals, w, spec=SPEC)
    # nan/inf weights contribute nothing
    assert float(sk.count) == 2 + 1 + 1 + 4
    assert float(sk.zero) == 4
    assert float(sk.neg.sum()) == 1


def test_overflow_counted():
    sk = js.add(js.empty(SPEC), jnp.asarray([1e30], jnp.float32), spec=SPEC)
    assert float(sk.overflow) == 1


# --------------------------------------------------------------------- #
# host <-> device round-trip semantics for the two lossy corners:
# the overflow counter and float32 count rounding (intended behaviour,
# pinned here so changes are deliberate)
# --------------------------------------------------------------------- #
def test_overflow_not_roundtripped_but_values_are():
    """``overflow`` is device-only diagnostics: the overflowing VALUE is
    still counted (clamped into the top bucket, so it flushes to the host
    sketch and survives a round-trip), but the host tier has no overflow
    notion, so ``from_host`` restarts the counter at zero."""
    vals = jnp.asarray([2.0, 1e30], jnp.float32)
    sk = js.add(js.empty(SPEC), vals, spec=SPEC)
    assert float(sk.overflow) == 1
    assert float(sk.count) == 2  # the clamped value is still in pos

    host = js.to_host(sk, SPEC)
    assert host.count == 2  # flush keeps the clamped count...
    back = js.from_host(host, SPEC)
    assert float(back.count) == 2
    assert float(back.overflow) == 0  # ...but the overflow tally resets
    # the clamped mass sits in the top bucket after the round-trip
    assert float(back.pos[-1]) == float(sk.pos[-1]) == 1


def test_to_host_rounds_float32_counts_to_int():
    """Fractional float32 window counts round to the nearest int on flush:
    the host store is integer-valued (paper counters).  Weights summing to
    an integer are exact; a lone 0.5-weight rounds away (0.5 -> 0 via
    banker's rounding on `round`)."""
    w = jnp.asarray([0.25, 0.25, 0.5, 1.0], jnp.float32)
    v = jnp.asarray([2.0, 2.0, 2.0, 2.0], jnp.float32)
    sk = js.add(js.empty(SPEC), v, w, spec=SPEC)
    assert float(sk.count) == 2.0  # device keeps exact float mass
    host = js.to_host(sk, SPEC)
    assert host.count == 2  # integer on host (same bucket: 2.0 total)

    lone = js.add(js.empty(SPEC), jnp.asarray([3.0]), jnp.asarray([0.5]), spec=SPEC)
    host2 = js.to_host(lone, SPEC)
    assert host2.count == 0  # sub-half mass vanishes on flush — by design
    assert float(lone.count) == 0.5  # ...while the device window keeps it


def test_bank_row_overflow_roundtrip_matches_single():
    """Bank rows obey the same to_host/from_host semantics as singles."""
    from repro.core import sketch_bank as sb

    vals = jnp.asarray([2.0, 1e30, 5.0, -3.0], jnp.float32)
    ids = jnp.asarray([0, 0, 1, 1], jnp.int32)
    bank = sb.add(sb.empty(SPEC, 2), vals, ids, spec=SPEC)
    assert float(bank.overflow[0]) == 1 and float(bank.overflow[1]) == 0

    hosts = [sb.to_host(bank, SPEC, k) for k in range(2)]
    assert hosts[0].count == 2 and hosts[1].count == 2
    back = sb.from_host(hosts, SPEC)
    np.testing.assert_array_equal(np.asarray(back.pos), np.asarray(bank.pos))
    np.testing.assert_array_equal(np.asarray(back.neg), np.asarray(bank.neg))
    assert float(back.overflow.sum()) == 0  # device-only counter resets
    assert float(back.vmin[1]) == -3.0 and float(back.vmax[1]) == 5.0


def test_to_host_from_host_roundtrip(rng):
    data = np.concatenate(
        [rng.pareto(1.0, 500) + 1, -(rng.pareto(1.0, 300) + 1), np.zeros(11)]
    ).astype(np.float32)
    sk = js.add(js.empty(SPEC), jnp.asarray(data), spec=SPEC)
    host = js.to_host(sk, SPEC)
    assert host.count == len(data)
    back = js.from_host(host, SPEC)
    assert np.array_equal(np.asarray(back.pos), np.asarray(sk.pos))
    assert np.array_equal(np.asarray(back.neg), np.asarray(sk.neg))
    for q in QS:
        assert host.quantile(q) == pytest.approx(
            float(js.quantile(sk, q, spec=SPEC)), rel=1e-5
        )


def test_quantiles_batch(rng):
    data = (rng.pareto(1.0, 2000) + 1).astype(np.float32)
    sk = js.add(js.empty(SPEC), jnp.asarray(data), spec=SPEC)
    batch = np.asarray(js.quantiles(sk, jnp.asarray(QS), spec=SPEC))
    single = [float(js.quantile(sk, q, spec=SPEC)) for q in QS]
    assert np.allclose(batch, single)


def test_add_is_jittable_and_donatable(rng):
    data = jnp.asarray((rng.pareto(1.0, 256) + 1).astype(np.float32))
    add = jax.jit(lambda s, v: js.add(s, v, spec=SPEC), donate_argnums=(0,))
    sk = js.empty(SPEC)
    for _ in range(3):
        sk = add(sk, data)
    assert float(sk.count) == 3 * 256


# --------------------------------------------------------------------- #
# cross-device mergeability: the paper's headline property == psum
# --------------------------------------------------------------------- #
def test_psum_merge_across_devices():
    script = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.core import jax_sketch as js
from repro.core.ddsketch import DDSketch
from repro.kernels.ref import BucketSpec

SPEC = BucketSpec()
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("d",))
rng = np.random.default_rng(0)
data = (rng.pareto(1.0, 8 * 500) + 1.0).astype(np.float32)

def per_device(vals):  # vals: (500,) local shard
    sk = js.add(js.empty(SPEC), vals, spec=SPEC)
    return js.allreduce(sk, "d", spec=SPEC)

fn = shard_map(per_device, mesh=mesh, in_specs=P("d"), out_specs=P(), check_vma=False)
merged = jax.jit(fn)(jnp.asarray(data))

host = DDSketch(SPEC.relative_accuracy, max_bins=None)
host.extend(data)
for q in (0.25, 0.5, 0.95, 0.99):
    dev = float(js.quantile(jax.tree.map(lambda x: x[0] if x.ndim else x, merged), q, spec=SPEC)) \
        if False else float(js.quantile(merged, q, spec=SPEC))
    assert abs(dev - host.quantile(q)) <= 1e-5 * abs(host.quantile(q)) + 1e-7, (q, dev, host.quantile(q))
print("psum merge OK")
"""
    out = run_with_devices(script, 8)
    assert "psum merge OK" in out
