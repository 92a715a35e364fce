"""The int32/int64 count escape hatch: exact on-device accumulation past
float32's 2^24 ceiling behind the same empty/add/serialization API."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.kernels.ref import BucketSpec
from repro.telemetry.keyed import KeyedWindow

SPEC = BucketSpec()
CEIL = float(1 << 24)


def test_int32_counts_exact_past_f32_ceiling():
    """Each *batch* histogram is float32 (exact to 2^24 per add call); the
    integer accumulator is what lets the running total cross the ceiling."""
    vals = jnp.asarray([2.0])
    f32 = js.empty(SPEC)
    i32 = js.empty(SPEC, counts_dtype=jnp.int32)
    for w in (CEIL, 1.0):
        f32 = js.add(f32, vals, jnp.asarray([w]), spec=SPEC)
        i32 = js.add(i32, vals, jnp.asarray([w]), spec=SPEC)
    assert i32.pos.dtype == jnp.int32
    # float32 swallows the +1 (2^24 + 1 is not representable); int32 keeps it
    assert float(f32.count) == CEIL
    assert int(i32.count) == int(CEIL) + 1


def test_int32_bank_add_merge_collapse_preserve_dtype(rng, request):
    x = jnp.asarray((rng.pareto(1.0, 2000) + 1.0).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 4, 2000).astype(np.int32))
    bank = sb.add(sb.empty(SPEC, 4, counts_dtype=jnp.int32), x, s, spec=SPEC)
    assert bank.pos.dtype == jnp.int32 and bank.zero.dtype == jnp.int32
    merged = sb.merge(bank, bank, spec=SPEC)
    assert merged.pos.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(merged.counts), 2 * np.asarray(bank.counts)
    )
    folded = sb.collapse(bank, spec=SPEC)
    assert folded.pos.dtype == jnp.int32
    assert int(folded.counts.sum()) == int(bank.counts.sum())  # mass conserved
    # the kernel fold accumulates in f32, so integer banks stay on the ref
    # even where the default dispatch picks kernels
    request.getfixturevalue("tpu_interpret")
    folded_k = sb.collapse(bank, spec=SPEC)
    np.testing.assert_array_equal(np.asarray(folded_k.pos), np.asarray(folded.pos))


def test_int_bank_quantiles_match_float_bank(rng, request):
    x = jnp.asarray((rng.pareto(1.0, 3000) + 1.0).astype(np.float32))
    s = jnp.asarray(rng.integers(0, 3, 3000).astype(np.int32))
    qs = jnp.asarray([0.1, 0.5, 0.99])
    f32 = sb.add(sb.empty(SPEC, 3), x, s, spec=SPEC)
    i32 = sb.add(sb.empty(SPEC, 3, counts_dtype=jnp.int32), x, s, spec=SPEC)
    np.testing.assert_array_equal(
        np.asarray(sb.quantiles(f32, qs, spec=SPEC)),
        np.asarray(sb.quantiles(i32, qs, spec=SPEC)),
    )
    ref = np.asarray(sb.quantiles(i32, qs, spec=SPEC))
    request.getfixturevalue("tpu_interpret")  # default dispatch -> kernel
    np.testing.assert_array_equal(ref, np.asarray(sb.quantiles(i32, qs, spec=SPEC)))


def test_int32_host_roundtrip_exact():
    sk = js.add(
        js.empty(SPEC, counts_dtype=jnp.int32),
        jnp.asarray([3.0, -4.0, 3.0]),
        jnp.asarray([CEIL, 7.0, 2.0]),
        spec=SPEC,
    )
    host = js.to_host(sk, SPEC)
    assert host.count == int(CEIL) + 9
    back = js.from_host(host, SPEC, counts_dtype=jnp.int32)
    assert back.pos.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(back.pos), np.asarray(sk.pos))
    np.testing.assert_array_equal(np.asarray(back.neg), np.asarray(sk.neg))
    banks = sb.from_host([host, host], SPEC, counts_dtype=jnp.int32)
    assert banks.pos.dtype == jnp.int32 and banks.num_sketches == 2


def test_int64_refused_without_x64():
    """Regression: with jax_enable_x64 off, int64 silently canonicalizes to
    int32 — half the advertised headroom, wrapping past ~2.1e9.  The request
    must raise instead of degrading."""
    if jax.config.jax_enable_x64:
        sk = js.empty(SPEC, counts_dtype=jnp.int64)  # x64 on: honored exactly
        assert sk.pos.dtype == jnp.dtype("int64")
        return
    with pytest.raises(ValueError, match="x64"):
        js.empty(SPEC, counts_dtype=jnp.int64)
    with pytest.raises(ValueError, match="x64"):
        sb.empty(SPEC, 2, counts_dtype=jnp.int64)


def test_keyed_window_counts_dtype_threads_through():
    win = KeyedWindow(SPEC, capacity=4, counts_dtype=jnp.int32)
    win.record(["a", "b", "a"], [1.0, 2.0, 3.0])
    assert win.bank.pos.dtype == jnp.int32
    assert win.quantiles("a", [0.5])[0] > 0
    win.reset()
    assert win.bank.pos.dtype == jnp.int32  # dtype survives window resets
