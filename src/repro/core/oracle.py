"""Exact quantiles (ground truth for tests/benchmarks).

Uses the paper's definition: the q-quantile of a multiset S of size n is the
item of rank floor(1 + q(n-1)) ("lower quantile", §1 footnote 1).
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["exact_quantile", "exact_quantiles", "rank_of", "relative_error", "rank_error"]


def exact_quantile(sorted_values: np.ndarray, q: float) -> float:
    n = len(sorted_values)
    if n == 0:
        return math.nan
    # rank floor(q (n - 1)), 0-based; ``1 + q (n - 1)`` would round up to
    # the next integer for q within an ulp of 1
    return float(sorted_values[int(math.floor(q * (n - 1)))])


def exact_quantiles(values, qs) -> list[float]:
    s = np.sort(np.asarray(values, dtype=np.float64))
    return [exact_quantile(s, q) for q in qs]


def rank_of(sorted_values: np.ndarray, value: float) -> int:
    """R(x): number of elements <= x."""
    return int(np.searchsorted(sorted_values, value, side="right"))


def relative_error(estimate: float, actual: float) -> float:
    if actual == 0.0:
        return 0.0 if estimate == 0.0 else math.inf
    return abs(estimate - actual) / abs(actual)


def rank_error(sorted_values: np.ndarray, estimate: float, q: float) -> float:
    """|R~(v) - R(v)| / n, the (normalized) rank error of an estimate."""
    n = len(sorted_values)
    true_rank = math.floor(1 + q * (n - 1))
    est_rank = rank_of(sorted_values, estimate)
    # the estimate's rank is an interval [#(< v), #(<= v)]; take nearest edge
    lo = int(np.searchsorted(sorted_values, estimate, side="left"))
    hi = est_rank
    if lo <= true_rank <= hi:
        return 0.0
    return min(abs(lo - true_rank), abs(hi - true_rank)) / n
