"""DDSketch host tier: the paper's guarantees, tested as stated.

* Proposition 3: Quantile(q) is alpha-accurate for ALL q — hypothesis
  sweeps values and q.
* Algorithm 4 / full mergeability: merged sketches answer exactly like a
  single sketch over the union, regardless of merge order.
* Proposition 4 / collapse: quantiles above the collapsed mass keep the
  guarantee.
* §3.3: empirical sketch size vs the Pareto bound.
"""

import math

import numpy as np
import pytest
from _hypothesis_compat import assume, given, settings, st  # noqa: F401

from repro.core.ddsketch import DDSketch
from repro.core.oracle import exact_quantile, relative_error

ALPHA = 0.01

floats_pos = st.floats(min_value=1e-100, max_value=1e100, allow_nan=False)
floats_any = st.floats(min_value=-1e100, max_value=1e100, allow_nan=False)
datasets = st.lists(floats_pos, min_size=1, max_size=400)
qs_strategy = st.floats(min_value=0.0, max_value=1.0)


@given(data=datasets, q=qs_strategy)
@settings(max_examples=200, deadline=None)
def test_alpha_accurate_all_quantiles(data, q):
    """Proposition 3 (unbounded sketch)."""
    sk = DDSketch(ALPHA, max_bins=None)
    sk.extend(data)
    actual = exact_quantile(np.sort(np.asarray(data)), q)
    est = sk.quantile(q)
    assert relative_error(est, actual) <= ALPHA + 1e-9


@given(data=st.lists(floats_any, min_size=1, max_size=400), q=qs_strategy)
@settings(max_examples=200, deadline=None)
def test_alpha_accurate_with_negatives_and_zero(data, q):
    """§2.2 extension to all of R: negative store + zero bucket."""
    sk = DDSketch(ALPHA, max_bins=None)
    sk.extend(data)
    actual = exact_quantile(np.sort(np.asarray(data)), q)
    est = sk.quantile(q)
    assert abs(est - actual) <= ALPHA * abs(actual) + 1e-12


@pytest.mark.parametrize("data, q, want", [
    ([0.0, 1.0], 0.9999999999999999, 0.0),
    ([1.0, 2.0], 0.9999999999999999, 1.0),
    ([1.0, 2.0, 3.0], 0.5, 2.0),
    ([1.0, 2.0], 1.0, 2.0),
])
def test_exact_quantile_takes_rank_floor(data, q, want):
    """The oracle's rank is floor(q (n - 1)), also where 1 + q (n - 1)
    rounds up to the next integer (q within an ulp of 1)."""
    assert exact_quantile(np.asarray(data), q) == want
    sk = DDSketch(ALPHA, max_bins=None)
    sk.extend(data)
    assert abs(sk.quantile(q) - want) <= ALPHA * abs(want)


@given(
    parts=st.lists(st.lists(floats_pos, min_size=1, max_size=100), min_size=2, max_size=5),
    q=qs_strategy,
)
@settings(max_examples=100, deadline=None)
def test_full_mergeability(parts, q):
    """Algorithm 4: merge of k sketches == one sketch of the union; and the
    merge is order-independent (the psum requirement)."""
    union = [v for p in parts for v in p]
    ref = DDSketch(ALPHA)
    ref.extend(union)

    merged = DDSketch(ALPHA)
    for p in parts:
        sk = DDSketch(ALPHA)
        sk.extend(p)
        merged.merge(sk)

    rev = DDSketch(ALPHA)
    for p in reversed(parts):
        sk = DDSketch(ALPHA)
        sk.extend(p)
        rev.merge(sk)

    assert merged.count == ref.count == len(union)
    assert merged.quantile(q) == pytest.approx(ref.quantile(q), rel=1e-12)
    assert rev.quantile(q) == pytest.approx(ref.quantile(q), rel=1e-12)


def test_merge_requires_same_gamma():
    a, b = DDSketch(0.01), DDSketch(0.02)
    b.add(1.0)
    with pytest.raises(ValueError):
        a.merge(b)


def test_collapse_preserves_upper_quantiles(rng):
    """Proposition 4: with m buckets, quantiles q with x_q*gamma^(m-1) >= x_1
    stay alpha-accurate.  Pareto data + small m stresses the collapse."""
    data = rng.pareto(1.0, 20000) + 1.0
    sk = DDSketch(ALPHA, max_bins=128)
    sk.extend(data)
    s = np.sort(data)
    x1 = s[-1]
    gamma = (1 + ALPHA) / (1 - ALPHA)
    for q in (0.5, 0.9, 0.95, 0.99, 0.999, 1.0):
        xq = exact_quantile(s, q)
        if x1 <= xq * gamma ** (sk.max_bins - 1):
            assert relative_error(sk.quantile(q), xq) <= ALPHA + 1e-9


def test_deletion(rng):
    data = list(rng.pareto(1.0, 500) + 1.0)
    sk = DDSketch(ALPHA, max_bins=None)
    sk.extend(data)
    for v in data[:100]:
        sk.delete(v)
    rest = np.sort(data[100:])
    for q in (0.1, 0.5, 0.9):
        assert relative_error(sk.quantile(q), exact_quantile(rest, q)) <= ALPHA + 1e-9
    with pytest.raises(ValueError):
        DDSketch(ALPHA).delete(5.0)


def test_weighted_add_equals_repeats():
    a, b = DDSketch(ALPHA), DDSketch(ALPHA)
    for v, w in [(1.5, 3), (10.0, 5), (0.2, 2)]:
        a.add(v, w)
        for _ in range(w):
            b.add(v)
    for q in (0.0, 0.3, 0.7, 1.0):
        assert a.quantile(q) == b.quantile(q)
    assert a.count == b.count and a.sum == pytest.approx(b.sum)


def test_min_max_sum_avg(rng):
    data = rng.lognormal(0, 2, 1000)
    sk = DDSketch(ALPHA)
    sk.extend(data)
    assert sk.min == data.min() and sk.max == data.max()
    assert sk.avg == pytest.approx(data.mean(), rel=1e-9)
    assert sk.quantile(0.0) == data.min()
    assert sk.quantile(1.0) == pytest.approx(data.max(), rel=ALPHA)


def test_serialization_roundtrip(rng):
    data = np.concatenate([rng.pareto(1.0, 200) + 1, -rng.pareto(1.0, 100) - 1, [0.0] * 7])
    sk = DDSketch(ALPHA, max_bins=256)
    sk.extend(data)
    sk2 = DDSketch.from_dict(sk.to_dict())
    for q in np.linspace(0, 1, 21):
        assert sk2.quantile(q) == sk.quantile(q)
    assert sk2.count == sk.count and sk2.zero_count == sk.zero_count


@pytest.mark.parametrize("store", ["dense", "sparse"])
@pytest.mark.parametrize("mapping", ["log", "linear", "cubic"])
def test_all_mapping_store_combos(rng, store, mapping):
    data = rng.pareto(1.0, 3000) + 1.0
    sk = DDSketch(ALPHA, max_bins=2048, mapping=mapping, store=store)
    sk.extend(data)
    s = np.sort(data)
    for q in (0.5, 0.95, 0.99):
        assert relative_error(sk.quantile(q), exact_quantile(s, q)) <= ALPHA + 1e-9


def test_pareto_size_bound(rng):
    """§3.3: for Pareto(a=1), bins <= 51·(4·ln n + 11) + 1 w.h.p. — and the
    observed count is far below it (paper Fig. 7: ~900 bins at n=1e10)."""
    n = 1_000_000
    data = rng.pareto(1.0, n) + 1.0
    sk = DDSketch(0.01, max_bins=None)
    sk.extend(data)
    bound = 51 * (4 * math.log(n) + 11) + 1
    assert sk.num_bins() <= bound
    assert sk.num_bins() < 1500  # empirically ~600-800 at n=1e6


def test_exponential_size_bound(rng):
    """§3.3 Exponential example: 0.01-accurate upper-half order statistics
    of 1e6 samples fit in a sketch of size 273."""
    data = rng.exponential(1.0, 1_000_000)
    sk = DDSketch(0.01, max_bins=None)
    sk.extend(data)
    upper_half_bins = sum(
        1 for k, _ in sk.store.items_ascending() if k >= sk.mapping.key(np.median(data))
    )
    assert upper_half_bins <= 273
