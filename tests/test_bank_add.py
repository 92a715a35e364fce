"""Whole bank insert: ``sketch_bank.add_impl``'s full state (both sign stores
and the six aux stats) against the independent ``ref.bank_add_ref`` oracle,
across mappings x collapse levels x weights and adversarial streams
(all-unique / all-duplicate / inert engine padding), on the XLA reference
tier and on the kernel tier (TPU kernels in interpret mode, steered by the
``tpu_interpret`` fixture)."""

import numpy as np
import pytest

import jax.numpy as jnp

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import sketch_bank as sb
from repro.kernels import ops
from repro.kernels.ref import MAX_COLLAPSE_LEVEL, BucketSpec, bank_add_ref

MAPPINGS = ["log", "linear", "cubic"]
TIERS = ["ref", "interpret"]
KERNEL_LANES = 2048  # one value tile: below it auto dispatch takes the ref


def _data(n, rng):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    x *= np.where(rng.random(n) < 0.4, -1.0, 1.0).astype(np.float32)
    specials = np.array([np.nan, np.inf, -np.inf, -1.0, 0.0, 1e-38, 1e38])
    idx = rng.choice(n, size=min(7, n), replace=False)
    x[idx] = specials[: len(idx)].astype(np.float32)
    return x


def _use_tier(tier, request):
    if tier == "interpret":
        request.getfixturevalue("tpu_interpret")  # default dispatch -> kernel


def _check_tier(tier):
    if tier == "interpret":
        assert ops.fallback_count() == 0  # the kernel ran, not the ref


def _ref_add(bank, x, s, w, spec):
    """``bank`` plus one insert, built from ``bank_add_ref``."""
    k = bank.num_sketches
    s = jnp.asarray(s)
    lev = bank.level[jnp.clip(s, 0, k - 1)]
    hist, st_ = bank_add_ref(x, s, w, lev, num_segments=k, spec=spec)
    cd = bank.pos.dtype
    return sb.SketchBank(
        pos=bank.pos + hist[:k].astype(cd),
        neg=bank.neg + hist[k:].astype(cd),
        zero=bank.zero + st_.zero.astype(cd),
        overflow=bank.overflow + st_.overflow.astype(cd),
        underflow=bank.underflow + st_.underflow.astype(cd),
        summ=bank.summ + st_.summ,
        vmin=jnp.minimum(bank.vmin, st_.vmin),
        vmax=jnp.maximum(bank.vmax, st_.vmax),
        level=bank.level,
    )


def _assert_banks_equal(a, b):
    for name, fa, fb in zip(a._fields, a, b):
        if name == "summ":
            # float sum order differs between the dense small-K stats path
            # and the oracle's segment reduction; signed streams cancel, so
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


# --------------------------------------------------------------------- #
# full-state parity: histogram pass + stats pass == the one-pass oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("num_segments", [1, 5, 37])
@pytest.mark.parametrize("mapping", MAPPINGS)
def test_add_impl_full_state_parity(num_segments, mapping, tier, rng, request):
    """All nine bank fields equal the oracle's — counters and extrema
    bit-for-bit, the float summ to ulps — over rows at mixed collapse
    levels, weighted lanes and out-of-range ids."""
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
    want = _ref_add(bank, x, s, w, spec)
    _use_tier(tier, request)
    _assert_banks_equal(sb.add(bank, x, s, w, spec=spec), want)
    _check_tier(tier)


def test_add_impl_parity_unit_weights(rng):
    spec = BucketSpec()
    n, k = 3000, 9
    x = jnp.asarray(_data(n, rng))
    s = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    bank = sb.empty(spec, k)
    _assert_banks_equal(
        sb.add(bank, x, s, spec=spec), _ref_add(bank, x, s, None, spec)
    )


@pytest.mark.parametrize("tier", TIERS)
def test_add_impl_parity_property(tier, request):
    """Property sweep: any stream (signed, tiny, zero, out-of-range ids),
    any collapse level, weighted or not — ``add_impl``'s bank state equals
    the oracle's.  Streams are padded with inert lanes to one value tile so
    the kernel tier runs the kernel."""
    if not HAVE_HYPOTHESIS:
        pytest.skip("hypothesis not installed")
    _use_tier(tier, request)

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
    def check(mapping, level, weighted, data):
        spec = BucketSpec(mapping=mapping)
        k = 4
        pad = KERNEL_LANES - len(data)
        x = np.array([d[0] for d in data] + [np.nan] * pad, np.float32)
        s = np.array([d[1] for d in data] + [-1] * pad, np.int32)
        w = np.array([d[2] for d in data] + [0] * pad, np.float32)
        x, s = jnp.asarray(x), jnp.asarray(s)
        w = jnp.asarray(w) if weighted else None
        bank = sb.collapse_to(
            sb.empty(spec, k), jnp.full(k, level, jnp.int32), spec=spec
        )
        _assert_banks_equal(
            sb.add(bank, x, s, w, spec=spec), _ref_add(bank, x, s, w, spec)
        )

    check()
    _check_tier(tier)


# --------------------------------------------------------------------- #
# adversarial streams
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("tier", TIERS)
def test_all_unique_stream_parity(tier, rng, request):
    """Every lane lands in its own (row, bucket) cell: keys ~2.3 buckets
    apart per row and sign."""
    spec = BucketSpec()
    k, n = 3, 2400
    x = jnp.asarray(
        np.geomspace(1.0, 1e8, n).astype(np.float32)
        * np.where(np.arange(n) % 2 == 0, 1.0, -1.0).astype(np.float32)
    )
    s = jnp.asarray((np.arange(n) % k).astype(np.int32))
    bank = sb.empty(spec, k)
    want = _ref_add(bank, x, s, None, spec)
    _use_tier(tier, request)
    got = sb.add(bank, x, s, spec=spec)
    _check_tier(tier)
    _assert_banks_equal(got, want)
    live = (np.asarray(got.pos) > 0).sum() + (np.asarray(got.neg) > 0).sum()
    assert int(live) == n
    # float32 reassociation over a cancelling stream: bound by the mass
    xs = np.asarray(x, np.float64)
    assert abs(float(got.summ.sum()) - xs.sum()) <= 1e-6 * np.abs(xs).sum()


@pytest.mark.parametrize("tier", TIERS)
def test_all_duplicate_stream_parity(tier, rng, request):
    """Every lane hits the SAME (row, bucket) cell: maximal accumulation
    depth through one one-hot matmul."""
    spec = BucketSpec()
    n = 5000
    x = jnp.full(n, 3.7, jnp.float32)
    s = jnp.zeros(n, jnp.int32)
    w = jnp.asarray(rng.integers(1, 3, n).astype(np.float32))
    bank = sb.empty(spec, 1)
    want = _ref_add(bank, x, s, w, spec)
    _use_tier(tier, request)
    got = sb.add(bank, x, s, w, spec=spec)
    _check_tier(tier)
    _assert_banks_equal(got, want)
    assert float(got.pos.sum()) == float(w.sum())
    assert float(got.vmin[0]) == pytest.approx(3.7, rel=1e-6)
    assert float(got.vmax[0]) == pytest.approx(3.7, rel=1e-6)


@pytest.mark.parametrize("tier", TIERS)
def test_inert_padding_lanes_contribute_nothing(tier, rng, request):
    """The engine pads batches to power-of-two with (NaN, -1, 0) lanes; the
    insert must treat them as inert in the histograms AND every stat (a
    padded vmin/vmax leak would poison the row extrema forever)."""
    spec = BucketSpec()
    k, n, pad = 6, 1000, 1048
    x = _data(n, rng)
    s = rng.integers(0, k, n).astype(np.int32)
    w = rng.integers(1, 4, n).astype(np.float32)
    xp = np.concatenate([x, np.full(pad, np.nan, np.float32)])
    sp = np.concatenate([s, np.full(pad, -1, np.int32)])
    wp = np.concatenate([w, np.zeros(pad, np.float32)])
    bank = sb.empty(spec, k)
    want = _ref_add(bank, jnp.asarray(x), s, jnp.asarray(w), spec)
    _use_tier(tier, request)
    got = sb.add(
        bank, jnp.asarray(xp), jnp.asarray(sp), jnp.asarray(wp), spec=spec
    )
    _check_tier(tier)
    _assert_banks_equal(want, got)
