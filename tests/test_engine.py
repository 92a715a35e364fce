"""SketchEngine: persistent compiled executables, buffer donation, fused
reactive ingest — parity vs the jit-per-call ``sketch_bank`` paths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import sketch_bank as sb
from repro.engine import SketchEngine, make_engine
from repro.kernels.ref import BucketSpec

SPEC = BucketSpec()
QS = [0.0, 0.25, 0.5, 0.95, 0.99, 1.0]


def _stream(rng, n, k, *, signed=True, weights=False):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    if signed:
        x *= np.where(rng.random(n) < 0.3, -1.0, 1.0).astype(np.float32)
    s = rng.integers(0, k, n).astype(np.int32)
    w = rng.integers(1, 5, n).astype(np.float32) if weights else None
    return x, s, w


@pytest.mark.parametrize("weights", [False, True])
def test_engine_add_matches_sketch_bank(rng, weights):
    k = 12
    x, s, w = _stream(rng, 4000, k, weights=weights)
    eng = SketchEngine(SPEC, k)
    bank = eng.add(eng.new_bank(), x, s, w)
    ref = sb.add(
        sb.empty(SPEC, k),
        jnp.asarray(x),
        jnp.asarray(s),
        None if w is None else jnp.asarray(w),
        spec=SPEC,
    )
    for got, want in zip(bank, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k", [1, 16, 511])
def test_engine_ingest_matches_sketch_bank_kernel_tier(k, rng, tpu_interpret):
    """The engine's AOT ingest executable (inert pow-2 padding included) on
    the kernel tier equals ``sb.add`` on the same tier, and so do the
    quantiles read from it."""
    from repro.kernels import ops

    x, s, w = _stream(rng, 3000, k, weights=True)  # pads to 4096 lanes
    eng = SketchEngine(SPEC, k)
    got, fired, clamped = eng.ingest(eng.new_bank(), x, s, w)
    assert fired is None and clamped is None
    want = sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s),
                  jnp.asarray(w), spec=SPEC)
    assert ops.fallback_count() == 0
    for name, g, wv in zip(got._fields, got, want):
        if name == "summ":
            # the padding lanes change the length the small-bank (dense)
            # stats reduce over, so the float sum may reassociate: bound
            # the drift by the row's |w x| mass
            mass = np.bincount(s, np.abs(w * x).astype(np.float64), minlength=k)
            err = np.abs(np.asarray(g, np.float64) - np.asarray(wv, np.float64))
            assert (err <= 1e-6 * mass).all(), (err, mass)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(wv),
                                          err_msg=f"field {name!r}")
    np.testing.assert_array_equal(
        np.asarray(eng.quantiles(got, QS)),
        np.asarray(sb.quantiles(want, jnp.asarray(QS, jnp.float32), spec=SPEC)),
    )


def test_engine_quantiles_match_sketch_bank(rng):
    k = 7
    x, s, _ = _stream(rng, 3000, k)
    eng = SketchEngine(SPEC, k)
    bank = eng.add(eng.new_bank(), x, s)
    want = np.asarray(
        sb.quantiles(
            sb.add(sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), spec=SPEC),
            jnp.asarray(QS, jnp.float32),
            spec=SPEC,
        )
    )
    np.testing.assert_array_equal(np.asarray(eng.quantiles(bank, QS)), want)


def test_ingest_donates_bank_buffers(rng):
    """The tentpole claim: state-in/state-out updates reuse the input
    buffers instead of allocating a fresh bank per call."""
    k = 16
    eng = SketchEngine(SPEC, k)
    bank = eng.new_bank()
    x, s, _ = _stream(rng, 512, k)
    bank = eng.add(bank, x, s)  # first call compiles; donation from call 2 on
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in bank]
    old = bank
    bank = eng.add(bank, x, s)
    assert [leaf.unsafe_buffer_pointer() for leaf in bank] == ptrs
    # the donated input is dead — using it is an error, not silent reuse
    with pytest.raises(RuntimeError):
        _ = np.asarray(old.pos)


def test_executables_cached_across_calls_and_shapes(rng):
    k = 8
    eng = SketchEngine(SPEC, k)
    bank = eng.new_bank()
    x, s, _ = _stream(rng, 1000, k)
    for cut in (1000, 1000, 999, 998, 500):  # 999/998/500 pad to shared buckets
        bank = eng.add(bank, x[:cut], s[:cut])
    info = eng.cache_info()
    assert info["executables"] == 2  # pad buckets: 1024 and 512
    assert info["hits"] == 3
    # quantile executables key on len(qs)
    eng.quantiles(bank, QS)
    eng.quantiles(bank, QS)
    eng.quantiles(bank, [0.5])
    info = eng.cache_info()
    assert info["executables"] == 4
    assert info["hits"] == 4


def test_ragged_padding_is_invisible(rng):
    """Padded lanes (NaN value / id -1 / weight 0) contribute nothing."""
    k = 5
    x, s, w = _stream(rng, 777, k, weights=True)  # pads to 1024
    eng = SketchEngine(SPEC, k)
    bank = eng.add(eng.new_bank(), x, s, w)
    ref = sb.add(
        sb.empty(SPEC, k), jnp.asarray(x), jnp.asarray(s), jnp.asarray(w), spec=SPEC
    )
    for got, want in zip(bank, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_reactive_ingest_matches_two_step(rng):
    """ingest(threshold=...) == add + auto_collapse, in one executable,
    and reports which rows fired with the clamped mass that triggered."""
    k = 4
    wide = (10.0 ** rng.uniform(-15.0, 9.0, 2000)).astype(np.float32)
    ids = np.zeros(2000, np.int32)
    eng = SketchEngine(SPEC, k)
    bank, fired, clamped = eng.ingest(eng.new_bank(), wide, ids, threshold=0.0)
    ref = sb.add(sb.empty(SPEC, k), jnp.asarray(wide), jnp.asarray(ids), spec=SPEC)
    clamp_ref = np.asarray(ref.overflow + ref.underflow)
    ref = sb.auto_collapse(ref, spec=SPEC, threshold=0.0)
    for got, want in zip(bank, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    fired = np.asarray(fired)
    assert fired[0] and not fired[1:].any()
    np.testing.assert_array_equal(np.asarray(clamped), clamp_ref)


def test_collapse_to_and_reset_keep_levels(rng):
    k = 6
    eng = SketchEngine(SPEC, k)
    bank = eng.new_bank()
    x, s, _ = _stream(rng, 400, k)
    bank = eng.add(bank, x, s)
    bank = eng.collapse_to(bank, 2)
    assert (np.asarray(bank.level) == 2).all()
    total = float(np.asarray(bank.counts).sum())
    assert total == pytest.approx(400.0)

    bank = eng.reset(bank)  # levels survive
    assert (np.asarray(bank.level) == 2).all()
    assert float(np.asarray(bank.counts).sum()) == 0.0
    assert np.isinf(np.asarray(bank.vmin)).all()

    fresh = np.zeros(k, np.int32)
    bank = eng.reset(bank, fresh)  # explicit levels (the eviction path)
    assert (np.asarray(bank.level) == 0).all()


def test_engine_merge_matches_sketch_bank(rng):
    k = 9
    xa, sa, _ = _stream(rng, 1500, k)
    xb, sb_ids, _ = _stream(rng, 1500, k)
    eng = SketchEngine(SPEC, k)
    a = eng.add(eng.new_bank(), xa, sa)
    b = eng.add(eng.new_bank(), xb, sb_ids)
    b = eng.collapse_to(b, 1)  # exercise mixed-level alignment
    merged = eng.merge(a, b)
    ref = sb.merge(
        sb.add(sb.empty(SPEC, k), jnp.asarray(xa), jnp.asarray(sa), spec=SPEC),
        sb.collapse_to(
            sb.add(sb.empty(SPEC, k), jnp.asarray(xb), jnp.asarray(sb_ids), spec=SPEC),
            1,
            spec=SPEC,
        ),
        spec=SPEC,
    )
    for got, want in zip(merged, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int_counts_dtype_engine(rng):
    k = 3
    x, s, _ = _stream(rng, 600, k, signed=False)
    eng = SketchEngine(SPEC, k, counts_dtype=jnp.int32)
    bank = eng.add(eng.new_bank(), x, s)
    assert bank.pos.dtype == jnp.int32
    assert int(np.asarray(bank.counts).sum()) == 600


def test_make_engine_factory_single_device():
    eng = make_engine(SPEC, 4, num_shards=None)
    assert type(eng) is SketchEngine
    eng1 = make_engine(SPEC, 4, num_shards=1)
    assert type(eng1) is SketchEngine


def test_table_cache_is_per_spec_and_committed():
    from repro.engine.tables import device_value_table

    t1 = device_value_table(SPEC)
    t2 = device_value_table(BucketSpec())  # equal spec -> same cache entry
    assert t1 is t2
    assert isinstance(t1, jax.Array)
    t3 = device_value_table(BucketSpec(mapping="cubic"))
    assert t3 is not t1
