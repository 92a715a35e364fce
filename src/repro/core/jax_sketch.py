"""Device-tier DDSketch: a jit/vmap/psum-compatible twin of ``DDSketch``.

The paper's headline property — *full mergeability* (Algorithm 4: merging is
a per-key sum because bucket boundaries are data-independent) — is exactly
the algebraic requirement of ``jax.lax.psum``: an associative, commutative
combiner.  A DDSketch with a fixed bucket range is therefore an ordinary
dense array that can live *inside* a pjit-compiled train step, sharded or
replicated like any activation, and cross-device merging is a single
all-reduce.

Differences vs. the host tier (``repro.core.ddsketch.DDSketch``), all
documented in DESIGN.md §3:

* **Static shape, dynamic resolution.** ``jax.lax`` cannot grow a dict, so
  the bucket *array* is fixed at trace time (``BucketSpec``), but the
  *resolution* is dynamic: every sketch carries a ``level`` counter
  (UDDSketch's uniform collapse, Epicoco et al. 2020).  ``collapse``
  folds adjacent bucket pairs — key pairs (2j-1, 2j) merge into j — which
  logically squares gamma, doubling the indexable range while degrading
  the guarantee to alpha' = 2*alpha/(1 + alpha^2).  Values whose shifted
  key still escapes the array clamp into the edge buckets and are tallied
  in ``overflow`` / ``underflow`` so callers can detect guarantee loss and
  trigger ``auto_collapse``; ``add(..., auto_collapse=True)`` collapses
  *before* inserting so no value is ever misplaced (at the default
  geometry level 3 indexes every float32 normal).
* **float32 counts.** Exact for window counts below 2^24; the telemetry
  layer flushes windows into the (int64, dynamically-sized) host sketch,
  mirroring the paper's agent -> aggregator pipeline.
* **Insertion is a vectorized histogram**, not a scalar scatter loop; the
  Pallas kernel path (``repro.kernels``) tiles it through VMEM.

Collapse lifecycle: sketches start at level 0 (base gamma).  ``collapse``
is one fold; ``collapse_to`` folds up to a target level; ``auto_collapse``
is the reactive form (fold once when clamped mass exceeds a threshold);
``merge``/``allreduce`` align mixed levels by collapsing the finer operand
first — which is why both now take ``spec``.  Levels are capped at
``MAX_COLLAPSE_LEVEL`` (= 6).

Both tiers share the key mappings; cross-tier equality is tested in
``tests/test_jax_sketch.py``, collapse semantics in ``tests/test_collapse.py``.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ddsketch import DDSketch
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    approx_log2,
    fold_pairs_ref,
    shift_key,
)

__all__ = [
    "BucketSpec",
    "DeviceSketch",
    "MAX_COLLAPSE_LEVEL",
    "empty",
    "add",
    "merge",
    "allreduce",
    "collapse",
    "collapse_to",
    "auto_collapse",
    "quantile",
    "quantiles",
    "to_host",
    "from_host",
    "bucket_values",
    "bucket_value_table",
    "effective_alpha",
]


def _counts_dtype(counts_dtype) -> jnp.dtype:
    """Validate a requested counter dtype against the live jax config.

    With ``jax_enable_x64`` off (the default) jax silently canonicalizes
    int64 -> int32; for a *counter* dtype that would silently halve the
    advertised headroom and wrap past ~2.1e9 — so refuse rather than
    degrade."""
    requested = jnp.dtype(counts_dtype)
    resolved = jax.dtypes.canonicalize_dtype(requested)
    if resolved != requested:
        raise ValueError(
            f"counts_dtype={requested} resolves to {resolved} under the "
            "current jax config; enable jax_enable_x64 for 64-bit counters "
            "or request int32 explicitly"
        )
    return resolved


class DeviceSketch(NamedTuple):
    """DDSketch state as a pytree of arrays (counts float32, level int32).

    ``pos[i]`` counts values x whose level-shifted key minus offset == i
    (clamped); ``neg`` mirrors it for negative values keyed on |x| (collapse
    direction handled at query time by walking descending keys first, per
    paper §2.2).  ``level`` is the uniform-collapse level: bucket i covers
    the union of 2**level base buckets, i.e. gamma_eff = gamma**(2**level).
    """

    pos: jnp.ndarray  # (m,) bucket counts for positive values
    neg: jnp.ndarray  # (m,) bucket counts for negative values (keys of |x|)
    zero: jnp.ndarray  # () count of |x| <= min_indexable
    overflow: jnp.ndarray  # () count of |x| clamped into the top bucket
    underflow: jnp.ndarray  # () count of |x| clamped into bucket 0
    summ: jnp.ndarray  # () running sum (for avg, as in §1's count/sum rollups)
    vmin: jnp.ndarray  # () exact running min   (§2.2 "keep separate track")
    vmax: jnp.ndarray  # () exact running max
    level: jnp.ndarray  # () int32 uniform-collapse level

    @property
    def count(self) -> jnp.ndarray:
        return self.pos.sum() + self.neg.sum() + self.zero


def empty(spec: BucketSpec, counts_dtype=jnp.float32) -> DeviceSketch:
    """Fresh sketch state.  ``counts_dtype`` is the bucket/counter dtype:
    float32 (default) is exact to 2^24 per window; int32/int64 raise that
    ceiling for long-horizon on-device accumulation (integer weights
    assumed — fractional weights truncate on accumulate).  Per-``add``
    batch histograms stay float32 (exact to 2^24 per call); the accumulator
    is what crosses the ceiling.  int64 requires ``jax_enable_x64`` (raises
    otherwise rather than silently degrading to int32).  ``summ`` and the
    extrema stay float32 either way."""
    m = spec.num_buckets
    cd = _counts_dtype(counts_dtype)
    return DeviceSketch(
        pos=jnp.zeros(m, cd),
        neg=jnp.zeros(m, cd),
        zero=jnp.zeros((), cd),
        overflow=jnp.zeros((), cd),
        underflow=jnp.zeros((), cd),
        summ=jnp.zeros((), jnp.float32),
        vmin=jnp.asarray(jnp.inf, jnp.float32),
        vmax=jnp.asarray(-jnp.inf, jnp.float32),
        level=jnp.zeros((), jnp.int32),
    )


def effective_alpha(spec: BucketSpec, level: int) -> float:
    """Guarantee after ``level`` uniform collapses: gamma_eff = gamma**(2**L).

    One collapse step maps alpha -> 2*alpha/(1 + alpha^2); iterated, the
    closed form is alpha_L = (g - 1)/(g + 1) with g = gamma**(2**L).
    """
    g = spec.gamma ** (1 << int(level))
    return (g - 1.0) / (g + 1.0)


def _raw_keys(x: jnp.ndarray, valid: jnp.ndarray, spec: BucketSpec) -> jnp.ndarray:
    """Level-0 integer keys of |x| for valid pos/neg lanes (1 elsewhere)."""
    mag = jnp.where(valid, jnp.abs(x), 1.0)
    key = jnp.ceil(approx_log2(mag, spec.mapping) * jnp.float32(spec.multiplier))
    return key.astype(jnp.int32)


def _needed_levels(k0: jnp.ndarray, spec: BucketSpec) -> jnp.ndarray:
    """Per-value minimal collapse level whose shifted key fits the array.

    Monotone in the level (keys shrink toward {0, 1} as L grows and the
    array straddles key 0 for the shipped geometries), so the first fitting
    level is the argmax of a fits mask over 0..MAX_COLLAPSE_LEVEL.  Values
    that fit at no level return 0 (they clamp and count as over/underflow).
    """
    top = spec.offset + spec.num_buckets - 1
    levels = jnp.arange(MAX_COLLAPSE_LEVEL + 1, dtype=jnp.int32)
    shifted = shift_key(k0[:, None], levels[None, :])
    fits = (shifted >= spec.offset) & (shifted <= top)
    first = jnp.argmax(fits, axis=1).astype(jnp.int32)
    return jnp.where(fits.any(axis=1), first, 0)


@partial(jax.jit, static_argnames=("spec", "auto_collapse"))
def add(
    sketch: DeviceSketch,
    values: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
    auto_collapse: bool = False,
) -> DeviceSketch:
    """Vectorized Algorithm 1 over a batch of values (any shape).

    Non-finite entries are ignored.  Positive / negative / near-zero routing
    follows the host implementation exactly.  With ``auto_collapse=True``
    the sketch first collapses to the smallest level at which every batch
    value is indexable (capped at ``MAX_COLLAPSE_LEVEL``), so nothing is
    clamped and the level-adjusted alpha guarantee holds for the whole
    stream; without it, out-of-range keys clamp into the edge buckets and
    are tallied in ``overflow`` / ``underflow``.  Both sign stores come
    from one ``kernels.ops.bank_histograms`` call (a bank of one sketch).
    """
    x = values.reshape(-1).astype(jnp.float32)
    raw_w = None if weights is None else weights.reshape(-1).astype(jnp.float32)
    w = jnp.ones_like(x) if raw_w is None else raw_w
    finite = jnp.isfinite(x)
    w = jnp.where(finite, w, 0.0)

    is_pos = finite & (x > spec.min_indexable)
    is_neg = finite & (x < -spec.min_indexable)
    is_zero = finite & ~is_pos & ~is_neg

    k0 = _raw_keys(x, is_pos | is_neg, spec)
    if auto_collapse:
        needed = jnp.where(is_pos | is_neg, _needed_levels(k0, spec), 0)
        target = jnp.maximum(sketch.level, jnp.max(needed, initial=0))
        sketch = collapse_to(sketch, target, spec=spec)
    lev = sketch.level
    shifts = jnp.broadcast_to(lev, x.shape)

    from repro.kernels import ops

    pos_hist, neg_hist = ops.bank_histograms(
        x, None, raw_w, shifts, num_segments=1, spec=spec
    )

    # clamp accounting: shifted keys that escape [offset, offset + m - 1]
    top_key = spec.offset + spec.num_buckets - 1
    k_lev = shift_key(k0, lev)
    over = (is_pos | is_neg) & (k_lev > top_key)
    under = (is_pos | is_neg) & (k_lev < spec.offset)

    any_valid = finite.any()
    xmasked = jnp.where(finite & (w > 0), x, jnp.inf)
    vmin = jnp.minimum(sketch.vmin, jnp.where(any_valid, xmasked.min(), jnp.inf))
    xmasked = jnp.where(finite & (w > 0), x, -jnp.inf)
    vmax = jnp.maximum(sketch.vmax, jnp.where(any_valid, xmasked.max(), -jnp.inf))

    cd = sketch.pos.dtype
    return DeviceSketch(
        pos=sketch.pos + pos_hist[0].astype(cd),
        neg=sketch.neg + neg_hist[0].astype(cd),
        zero=sketch.zero + (w * is_zero).sum().astype(cd),
        overflow=sketch.overflow + (w * over).sum().astype(cd),
        underflow=sketch.underflow + (w * under).sum().astype(cd),
        summ=sketch.summ + (w * jnp.where(finite, x, 0.0)).sum(),
        vmin=vmin,
        vmax=vmax,
        level=lev,
    )


# --------------------------------------------------------------------- #
# uniform collapse (UDDSketch): resolution as a dynamic property
# --------------------------------------------------------------------- #
def _fold(counts, spec: BucketSpec):
    # integer-count banks always fold on the exact XLA path: the Pallas fold
    # accumulates in float32, which would silently round counts above 2^24 —
    # the very regime integer counts_dtype exists for.
    if counts.dtype == jnp.float32:
        from repro.kernels import ops

        return ops.fold_pairs(counts, spec=spec)
    return fold_pairs_ref(counts, spec=spec)


def collapse(
    sketch: DeviceSketch, *, spec: BucketSpec
) -> DeviceSketch:
    """One uniform-collapse step: fold pos/neg bucket pairs, level += 1.

    Preserves count / sum / min / max exactly (folding only moves counts
    between buckets); quantiles degrade from alpha_L to alpha_{L+1} =
    2*alpha_L/(1 + alpha_L^2).  Unconditional — callers gate on
    ``MAX_COLLAPSE_LEVEL`` (``collapse_to`` / ``auto_collapse`` do).
    """
    return sketch._replace(
        pos=_fold(sketch.pos, spec),
        neg=_fold(sketch.neg, spec),
        level=sketch.level + 1,
    )


def collapse_to(
    sketch: DeviceSketch, target, *, spec: BucketSpec
) -> DeviceSketch:
    """Fold until ``level >= target`` (clamped to ``MAX_COLLAPSE_LEVEL``).

    ``target`` may be traced; the loop is a fixed-shape ``while_loop`` so
    this composes with jit/vmap/shard_map.
    """
    target = jnp.clip(jnp.asarray(target, jnp.int32), 0, MAX_COLLAPSE_LEVEL)
    return jax.lax.while_loop(
        lambda s: s.level < target,
        lambda s: collapse(s, spec=spec),
        sketch,
    )


def auto_collapse(
    sketch: DeviceSketch,
    *,
    spec: BucketSpec,
    threshold: float = 0.0,
) -> DeviceSketch:
    """Reactive collapse: fold once when clamped mass exceeds ``threshold``.

    Triggers when ``overflow + underflow > threshold`` (and the level cap
    allows); the clamp counters reset on fire so they meter *post-collapse*
    pressure.  Already-clamped mass stays in the edge buckets (it cannot be
    re-keyed) — this trades the current window's tails for accuracy of
    everything inserted afterwards, which is exactly right for windowed
    telemetry where the level persists across window resets.
    """
    fire = (sketch.overflow + sketch.underflow > threshold) & (
        sketch.level < MAX_COLLAPSE_LEVEL
    )
    folded = collapse(sketch, spec=spec)
    folded = folded._replace(
        overflow=jnp.zeros_like(sketch.overflow),
        underflow=jnp.zeros_like(sketch.underflow),
    )
    return jax.tree.map(lambda a, b: jnp.where(fire, a, b), folded, sketch)


def merge(a: DeviceSketch, b: DeviceSketch, *, spec: BucketSpec) -> DeviceSketch:
    """Algorithm 4 generalized to mixed resolutions.

    Aligns both operands to the coarser level by collapsing the finer one
    (Cafaro et al. 2021's mixed-gamma merge: gamma_a**(2^da) == gamma_b
    exactly when levels differ by da), then sums per bucket.  At equal
    levels this is the plain '+' (hence still psum-able after alignment).
    """
    target = jnp.maximum(a.level, b.level)
    a = collapse_to(a, target, spec=spec)
    b = collapse_to(b, target, spec=spec)
    return DeviceSketch(
        pos=a.pos + b.pos,
        neg=a.neg + b.neg,
        zero=a.zero + b.zero,
        overflow=a.overflow + b.overflow,
        underflow=a.underflow + b.underflow,
        summ=a.summ + b.summ,
        vmin=jnp.minimum(a.vmin, b.vmin),
        vmax=jnp.maximum(a.vmax, b.vmax),
        level=a.level,
    )


def allreduce(sketch: DeviceSketch, axis_name, *, spec: BucketSpec) -> DeviceSketch:
    """Cross-device Algorithm 4: full mergeability == all-reducibility.

    Every device first collapses to the fleet-max level (pmax), making the
    bucket arrays commensurate; the remaining combine is a plain psum.
    ``axis_name`` may be a single mesh axis or a tuple (e.g. merge within a
    pod over ('data','model') then globally over 'pod').
    """
    target = jax.lax.pmax(sketch.level, axis_name)
    sketch = collapse_to(sketch, target, spec=spec)
    return DeviceSketch(
        pos=jax.lax.psum(sketch.pos, axis_name),
        neg=jax.lax.psum(sketch.neg, axis_name),
        zero=jax.lax.psum(sketch.zero, axis_name),
        overflow=jax.lax.psum(sketch.overflow, axis_name),
        underflow=jax.lax.psum(sketch.underflow, axis_name),
        summ=jax.lax.psum(sketch.summ, axis_name),
        vmin=jax.lax.pmin(sketch.vmin, axis_name),
        vmax=jax.lax.pmax(sketch.vmax, axis_name),
        level=target,
    )


# --------------------------------------------------------------------- #
# per-level bucket value tables (engine-cached per-spec constants)
# --------------------------------------------------------------------- #
def bucket_value_table(spec: BucketSpec) -> np.ndarray:
    """(MAX_COLLAPSE_LEVEL + 1, m) per-level midpoint estimates.

    Hosted by the engine's per-spec constant cache (``repro.engine.tables``)
    so repeated query traces — and every engine executable — share one host
    construction and one device upload per spec.  Deferred import: the
    engine imports this module at load time.
    """
    from repro.engine.tables import bucket_value_table as _table

    return _table(spec)


def bucket_values(spec: BucketSpec) -> np.ndarray:
    """Level-0 per-bucket estimates (back-compat view of the table)."""
    return bucket_value_table(spec)[0]


def quantile_impl(sketch: DeviceSketch, q, *, spec: BucketSpec) -> jnp.ndarray:
    """Algorithm 2 over (negatives desc-by-key, zero, positives asc-by-key).

    Vectorized: the three stores concatenate into one monotone value line
    (selected from the per-level value table by the sketch's live level);
    the answer is the first bucket whose cumulative count exceeds q(n-1)
    (found with a searchsorted on the cumsum instead of the paper's loop).
    Pure/traceable body; the jitted front door is ``quantile``.
    """
    from repro.engine.tables import device_value_table

    table = device_value_table(spec)
    vals = table[jnp.clip(sketch.level, 0, MAX_COLLAPSE_LEVEL)]
    line_vals = jnp.concatenate([-vals[::-1], jnp.zeros((1,), jnp.float32), vals])
    line_counts = jnp.concatenate(
        [sketch.neg[::-1], sketch.zero[None], sketch.pos]
    ).astype(jnp.float32)  # integer counts_dtype: rank math stays f32
    n = line_counts.sum()
    qf = jnp.asarray(q, jnp.float32)
    rank = qf * jnp.maximum(n - 1.0, 0.0)
    cum = jnp.cumsum(line_counts)
    idx = jnp.searchsorted(cum, rank, side="right")
    idx = jnp.clip(idx, 0, line_vals.shape[0] - 1)
    est = line_vals[idx]
    est = jnp.clip(est, sketch.vmin, sketch.vmax)  # exact-extrema clamp
    # extrema answered exactly (§2.2), mirroring the host tier
    est = jnp.where(qf <= 0.0, sketch.vmin, jnp.where(qf >= 1.0, sketch.vmax, est))
    return jnp.where(n > 0, est, jnp.nan)


quantile = partial(jax.jit, static_argnames=("spec",))(quantile_impl)


@partial(jax.jit, static_argnames=("spec",))
def quantiles(sketch: DeviceSketch, qs: jnp.ndarray, *, spec: BucketSpec) -> jnp.ndarray:
    return jax.vmap(lambda q: quantile_impl(sketch, q, spec=spec))(jnp.asarray(qs))


# --------------------------------------------------------------------- #
# host <-> device conversion (telemetry window flush / checkpoint restore)
# --------------------------------------------------------------------- #
def to_host(sketch: DeviceSketch, spec: BucketSpec) -> DDSketch:
    """Flush a device window into the exact, unbounded host sketch.

    Bucket keys map 1:1 at the same collapse level (same mapping, same
    logical gamma**(2**level)), so this is lossless at any level — it is
    Algorithm 4 with one operand stored dense-with-offset.  The device-only
    ``overflow`` / ``underflow`` diagnostics do not transfer.
    """
    host = DDSketch(
        relative_accuracy=spec.relative_accuracy,
        max_bins=None,
        mapping=spec.mapping,
        store="dense",
        collapse_level=int(sketch.level),
    )
    pos = np.asarray(sketch.pos)
    neg = np.asarray(sketch.neg)
    for i in np.flatnonzero(pos):
        host.store.add(spec.offset + int(i), int(round(float(pos[i]))))
    for i in np.flatnonzero(neg):
        host.negative_store.add(spec.offset + int(i), int(round(float(neg[i]))))
    host.zero_count = int(round(float(sketch.zero)))
    vmin, vmax = float(sketch.vmin), float(sketch.vmax)
    host.min = vmin if math.isfinite(vmin) else math.inf
    host.max = vmax if math.isfinite(vmax) else -math.inf
    host.sum = float(sketch.summ)
    return host


def from_host(
    host: DDSketch, spec: BucketSpec, counts_dtype=jnp.float32
) -> DeviceSketch:
    """Load host-sketch counts into device geometry (keys clamp into range).

    The host's ``collapse_level`` becomes the device level; store keys are
    already level-keys on both tiers, so in-range keys round-trip
    bit-exactly.  ``counts_dtype`` restores into a chosen counter dtype
    (host counts are exact int64 — an int32/int64 device target keeps them
    exact past float32's 2^24 ceiling).  The host tier has no level cap, so
    a host sketch beyond ``MAX_COLLAPSE_LEVEL`` cannot be represented on
    device — reinterpreting its keys at a lower level would silently
    corrupt every bucket, so this raises instead.
    """
    if int(host.collapse_level) > MAX_COLLAPSE_LEVEL:
        raise ValueError(
            f"host sketch is at collapse level {host.collapse_level}, beyond "
            f"the device cap MAX_COLLAPSE_LEVEL={MAX_COLLAPSE_LEVEL}; its "
            "level-keys cannot be represented in device geometry"
        )
    cd = _counts_dtype(counts_dtype)
    sk = empty(spec, counts_dtype=cd)
    level = int(host.collapse_level)
    pos = np.zeros(spec.num_buckets, np.float64)
    neg = np.zeros(spec.num_buckets, np.float64)
    for key, cnt in host.store.items_ascending():
        pos[np.clip(key - spec.offset, 0, spec.num_buckets - 1)] += cnt
    for key, cnt in host.negative_store.items_ascending():
        neg[np.clip(key - spec.offset, 0, spec.num_buckets - 1)] += cnt
    return DeviceSketch(
        pos=jnp.asarray(pos, cd),
        neg=jnp.asarray(neg, cd),
        zero=jnp.asarray(host.zero_count, cd),
        overflow=sk.overflow,
        underflow=sk.underflow,
        summ=jnp.asarray(float(host.sum), jnp.float32),
        vmin=jnp.asarray(host.min if host.count else np.inf, jnp.float32),
        vmax=jnp.asarray(host.max if host.count else -np.inf, jnp.float32),
        level=jnp.asarray(level, jnp.int32),
    )
