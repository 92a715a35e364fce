"""Fused bank quantile query: the batched XLA twin vs the per-row vmap
formulation it replaced, the Pallas kernel vs the twin in interpret mode,
and a host-parity property sweep (weights x collapse levels x mappings)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.core.ddsketch import DDSketch
from repro.kernels import ops
from repro.kernels.bank_quantiles import bank_quantiles_pallas
from repro.kernels.ref import MAX_COLLAPSE_LEVEL, BucketSpec, bank_quantiles_ref

MAPPINGS = ["log", "linear", "cubic"]
QS = [0.0, 0.01, 0.25, 0.5, 0.75, 0.95, 0.99, 1.0]


def _bank(spec, k, n, rng, *, weights=False, levels=False):
    x = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    x *= np.where(rng.random(n) < 0.4, -1.0, 1.0).astype(np.float32)
    x[rng.choice(n, size=3, replace=False)] = [0.0, np.nan, np.inf]
    s = rng.integers(0, k, n).astype(np.int32)
    w = (
        jnp.asarray(rng.integers(1, 5, n).astype(np.float32))
        if weights
        else None
    )
    bank = sb.empty(spec, k)
    if levels:
        bank = sb.collapse_to(
            bank,
            jnp.asarray(rng.integers(0, MAX_COLLAPSE_LEVEL + 1, k), jnp.int32),
            spec=spec,
        )
    return sb.add(bank, jnp.asarray(x), jnp.asarray(s), w, spec=spec)


def _fused(bank, qs, spec, **kw):
    return ops.bank_quantiles(
        bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
        jnp.asarray(qs, jnp.float32), spec=spec, **kw,
    )


@pytest.mark.parametrize("mapping", MAPPINGS)
def test_fused_ref_matches_vmapped_rows(mapping, rng):
    """The batched twin is bit-identical to vmapping the single-sketch
    Algorithm 2 over rows — the formulation sketch_bank.quantiles used."""
    spec = BucketSpec(mapping=mapping)
    bank = _bank(spec, 9, 4000, rng, weights=True, levels=True)
    qf = jnp.asarray(QS, jnp.float32)
    want = jax.vmap(
        lambda sk: js.quantiles(sk, qf, spec=spec)
    )(js.DeviceSketch(*bank))
    got = _fused(bank, QS, spec, force="ref")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


@pytest.mark.parametrize("row_tile", [1, 4, 8, 16])
def test_kernel_matches_ref_across_row_tiles(row_tile, rng):
    spec = BucketSpec()
    bank = _bank(spec, 11, 3000, rng, weights=True, levels=True)
    table = jnp.asarray(js.bucket_value_table(spec), jnp.float32)
    ref = bank_quantiles_ref(
        bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
        jnp.asarray(QS, jnp.float32), table,
    )
    ker = bank_quantiles_pallas(
        bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
        jnp.asarray(QS, jnp.float32), table, row_tile=row_tile, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_kernel_empty_rows_and_bank(rng):
    spec = BucketSpec()
    bank = sb.empty(spec, 5)
    out = np.asarray(_fused(bank, [0.5, 0.99], spec, force="interpret"))
    assert np.isnan(out).all()
    # one live row among empties
    bank = sb.add(bank, jnp.asarray([3.0, 4.0, 5.0]), jnp.asarray([2, 2, 2]),
                  spec=spec)
    out = np.asarray(_fused(bank, [0.0, 0.5, 1.0], spec, force="interpret"))
    assert np.isnan(out[[0, 1, 3, 4]]).all()
    assert out[2, 0] == 3.0 and out[2, 2] == 5.0  # exact extrema
    # zero-row bank answers an empty (0, Q) array
    zero_bank = sb.empty(spec, 0)
    assert _fused(zero_bank, [0.5], spec, force="interpret").shape == (0, 1)


def test_sketch_bank_quantiles_uses_fused_path(rng, request):
    spec = BucketSpec()
    bank = _bank(spec, 9, 2000, rng)  # >= row_tile rows: the kernel's menu
    a = sb.quantiles(bank, jnp.asarray(QS), spec=spec)
    request.getfixturevalue("tpu_interpret")  # default dispatch -> kernel
    b = sb.quantiles(bank, jnp.asarray(QS), spec=spec)
    assert ops.fallback_count() == 0
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    one = sb.quantile(bank, 0.5, spec=spec)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(a[:, QS.index(0.5)]))


def test_keyed_window_all_quantiles_matches_per_key(rng):
    """One fused bank query answers every live key — the serving path behind
    Server.live_endpoint_quantiles."""
    from repro.telemetry.keyed import KeyedWindow

    spec = BucketSpec()
    win = KeyedWindow(spec, capacity=8)
    keys = [f"/v1/ep{i}" for i in rng.integers(0, 5, 500)]
    win.record(keys, (rng.pareto(1.0, 500) + 1.0).astype(np.float32))
    qs = [0.5, 0.95, 0.99]
    fused = win.all_quantiles(qs)
    assert set(fused) == set(win.keys())
    for key in win.keys():
        np.testing.assert_array_equal(
            np.asarray(fused[key], np.float32),
            np.asarray(win.quantiles(key, qs), np.float32),
        )


def _host_twin(spec, level, vals, weights):
    host = DDSketch(
        spec.relative_accuracy,
        mapping=spec.mapping,
        store="dense",
        collapse_level=level,
    )
    for v, w in zip(vals, weights):
        host.add(float(v), int(w))
    return host


@settings(max_examples=40, deadline=None)
@given(
    mapping=st.sampled_from(MAPPINGS),
    level=st.integers(min_value=0, max_value=MAX_COLLAPSE_LEVEL),
    data=st.lists(
        st.tuples(
            # width=32 bounds must be float32-representable
            st.floats(min_value=float(np.float32(1e-3)), max_value=1e6,
                      allow_nan=False, width=32),
            st.integers(min_value=1, max_value=4),
            st.booleans(),
        ),
        min_size=1,
        max_size=60,
    ),
    q=st.floats(min_value=0.0, max_value=1.0, allow_nan=False),
)
def test_fused_kernel_matches_host_quantile(mapping, level, data, q):
    """Acceptance property: sketch_bank.quantiles (fused kernel, interpret
    mode) matches host DDSketch.quantile across levels 0..6, weights, and
    all three mappings.  Both tiers bound the same exact quantile within
    the level-degraded alpha', so they sit within ~2*alpha' of each other;
    rank edges may still land in adjacent buckets (one extra gamma' step),
    hence the 2(1+gamma') slack below."""
    spec = BucketSpec(mapping=mapping)
    vals = np.asarray([v if sign else -v for v, _, sign in data], np.float32)
    weights = np.asarray([w for _, w, _ in data], np.float32)
    host = _host_twin(spec, level, vals, weights)
    bank = sb.collapse_to(
        sb.empty(spec, 2), jnp.asarray([level, 0], jnp.int32), spec=spec
    )
    bank = sb.add(
        bank,
        jnp.asarray(vals),
        jnp.zeros(len(vals), jnp.int32),
        jnp.asarray(weights),
        spec=spec,
    )
    got = float(_fused(bank, [q], spec, force="interpret")[0, 0])
    want = host.quantile(q)
    alpha = js.effective_alpha(spec, level)
    tol = 2.0 * (1.0 + alpha) * alpha * abs(want) + 1e-6
    assert abs(got - want) <= tol, (mapping, level, q, got, want)
