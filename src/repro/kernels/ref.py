"""Pure-jnp oracles for the DDSketch bucket kernels.

These define the *semantics* the Pallas kernels must match bit-for-bit
(same float32 index math), and serve as the XLA fallback path on hardware
without Pallas support. Shared by tests (assert_allclose vs kernels) and by
``repro.core.jax_sketch``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "BucketSpec",
    "IngestStats",
    "MAX_COLLAPSE_LEVEL",
    "bucket_index",
    "histogram_ref",
    "segment_histogram_ref",
    "fold_pairs_ref",
    "fold_destination_range",
    "approx_log2",
    "level_keys",
    "shift_key",
    "bank_add_ref",
    "bank_quantiles_ref",
    "bank_range_merge_ref",
    "multi_fold_destinations",
]

# Hard ceiling on the uniform-collapse level (UDDSketch, Epicoco et al. 2020).
# At level L every bucket covers gamma**(2**L); with the default geometry
# (alpha=0.01, m=2048, offset=-1024) level 3 already indexes every float32
# normal, so 6 leaves ample headroom while keeping the per-level
# bucket-value tables small trace-time constants.
MAX_COLLAPSE_LEVEL = 6


@dataclass(frozen=True)
class BucketSpec:
    """Static device-sketch geometry (trace-time constants).

    The device sketch covers keys [offset, offset + num_buckets); keys below
    collapse into bucket 0 (the static analogue of Algorithm 3's
    collapse-lowest), keys above clamp into the top bucket and are counted
    as overflow by the caller.
    """

    relative_accuracy: float = 0.01
    num_buckets: int = 2048
    offset: int = -1024  # key of bucket 0
    mapping: str = "log"  # "log" | "linear" | "cubic"

    @property
    def gamma(self) -> float:
        return (1.0 + self.relative_accuracy) / (1.0 - self.relative_accuracy)

    @property
    def multiplier(self) -> float:
        """key = ceil(_log(x) * multiplier); _log is log2-based for the
        interpolated mappings and natural-log based for "log"."""
        if self.mapping == "log":
            return 1.0 / math.log(self.gamma)
        if self.mapping == "linear":
            return 1.0 / math.log(self.gamma)
        if self.mapping == "cubic":
            from repro.core.mapping import _CUBIC_CORR

            return _CUBIC_CORR / math.log2(self.gamma)
        raise ValueError(f"unknown mapping {self.mapping}")

    @property
    def min_indexable(self) -> float:
        # float32-safe: stay inside normal range (kernels bit-cast f32)
        return 1e-37

    def key_bounds(self) -> tuple[int, int]:
        return self.offset, self.offset + self.num_buckets - 1

    def bucket_value(self, key) -> jnp.ndarray:
        """Relative-error midpoint estimate for (vector of) keys."""
        from repro.core.mapping import make_mapping

        m = make_mapping(self.mapping, self.relative_accuracy)
        import numpy as np

        keys = np.atleast_1d(np.asarray(key))
        return jnp.asarray([m.value(int(k)) for k in keys])


# --------------------------------------------------------------------- #
_CUBIC_A = 6.0 / 35.0
_CUBIC_B = -3.0 / 5.0
_CUBIC_C = 10.0 / 7.0


# Cephes ``logf`` (S. L. Moshier): ln(1 + t) = t - t^2/2 + t^3 * P(t) on
# t in [sqrt(1/2) - 1, sqrt(2) - 1), and ln 2 split in two so that e * ln 2
# keeps full precision.  Peak relative error ~1 ulp over the normal range.
_LOGF_P = (
    7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1, -1.2420140846e-1,
    1.4249322787e-1, -1.6668057665e-1, 2.0000714765e-1, -2.4999993993e-1,
    3.3333331174e-1,
)
_LN2_HI = 0.693359375
_LN2_LO = -2.12194440e-4


def _ln(x: jnp.ndarray) -> jnp.ndarray:
    """Natural log of positive normal float32 values, to ~1 ulp, from
    bit operations, adds and multiplies only.

    ``jnp.log`` lowers to each backend's own approximation; on a TPU v5e it
    is off by up to ~4e3 ulp, enough to key values up to ~3e-5 (relative)
    from a bucket edge into the neighbouring bucket and break the alpha
    guarantee.  This form gives the same ~1 ulp on every backend.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 126  # x = f * 2**e, f in [0.5, 1)
    f = jax.lax.bitcast_convert_type((bits & 0x7FFFFF) | 0x3F000000, jnp.float32)
    small = f < jnp.float32(0.70710678118654752440)
    e = jnp.where(small, e - 1, e).astype(jnp.float32)
    t = jnp.where(small, f + f - 1.0, f - 1.0)
    z = t * t
    p = jnp.float32(_LOGF_P[0])
    for c in _LOGF_P[1:]:
        p = p * t + jnp.float32(c)
    y = p * t * z + jnp.float32(_LN2_LO) * e - 0.5 * z
    return (t + y) + jnp.float32(_LN2_HI) * e


def approx_log2(x: jnp.ndarray, mapping: str) -> jnp.ndarray:
    """Mapping-specific monotone log approximation (float32 semantics).

    "log": natural log to ~1 ulp (``_ln``; converted by the multiplier).
    "linear"/"cubic": exponent bits + mantissa interpolation — the paper's
    §2.2 'costless log2 from the binary representation' trick, expressed as
    a bitcast so it lowers to TPU integer ops.
    """
    x = x.astype(jnp.float32)
    if mapping == "log":
        return _ln(x)  # natural log; multiplier = 1/ln(gamma)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    f = (bits & 0x7FFFFF).astype(jnp.float32) * (2.0 ** -23)
    if mapping == "linear":
        return e.astype(jnp.float32) + f
    poly = ((_CUBIC_A * f + _CUBIC_B) * f + _CUBIC_C) * f
    return e.astype(jnp.float32) + poly


def shift_key(key: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Base (level-0) integer key -> collapse-level key: ceil(key / 2**level).

    Uniform collapse folds bucket pairs (2j-1, 2j) -> j, so the level-L key
    of a value is ceil(key_0 / 2**L) (ceil(ceil(y)/n) == ceil(y/n)).  The
    arithmetic right shift computes the floor for either sign, so the ceil
    is two negations — exact int32 math shared by ref and Pallas paths.
    """
    return -((-key) >> levels)


def level_keys(x: jnp.ndarray, levels: jnp.ndarray, spec: BucketSpec) -> jnp.ndarray:
    """int32 collapse-level keys of ``|x|`` (1.0 stands in for values that
    cannot be indexed: zeros, tiny magnitudes, NaN/inf).

    The Pallas ingest kernels take these keys as an input instead of taking
    the log in-kernel: the log is then always XLA's, the one the reference
    tier uses, so kernel and reference bucket every value identically on any
    backend (a kernel-side log may round differently near a bucket edge).
    """
    x = x.astype(jnp.float32)
    mag = jnp.where(
        jnp.isfinite(x) & (jnp.abs(x) > spec.min_indexable), jnp.abs(x), 1.0
    )
    key = jnp.ceil(approx_log2(mag, spec.mapping) * jnp.float32(spec.multiplier))
    return shift_key(key.astype(jnp.int32), levels)


def bucket_index(
    x: jnp.ndarray, spec: BucketSpec, levels: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Clamped bucket index for positive values (callers pre-mask others).

    ``levels`` (per-value int32 collapse levels, broadcastable against x)
    shifts keys into the collapsed geometry instead of clamping base keys.
    """
    key = jnp.ceil(approx_log2(x, spec.mapping) * jnp.float32(spec.multiplier))
    k = key.astype(jnp.int32)
    if levels is not None:
        k = shift_key(k, levels)
    return jnp.clip(k - spec.offset, 0, spec.num_buckets - 1)


@partial(jax.jit, static_argnames=("spec",))
def histogram_ref(
    values: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
) -> jnp.ndarray:
    """Oracle: bucket-count vector for positive finite values.

    Non-positive / non-finite entries contribute nothing (the jax_sketch
    wrapper routes them to the zero/negative/nan counters).  ``levels``
    (per-value int32 collapse levels) indexes values in the collapsed
    geometry — level 0 reproduces the base behaviour bit-for-bit.
    """
    x = values.reshape(-1).astype(jnp.float32)
    w = (
        jnp.ones_like(x)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    lev = None if levels is None else levels.reshape(-1).astype(jnp.int32)
    mask = jnp.isfinite(x) & (x > spec.min_indexable)
    idx = bucket_index(jnp.where(mask, x, 1.0), spec, lev)
    contrib = jnp.where(mask, w, 0.0)
    return jnp.zeros(spec.num_buckets, jnp.float32).at[idx].add(contrib)


@partial(jax.jit, static_argnames=("num_segments", "spec"))
def segment_histogram_ref(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
) -> jnp.ndarray:
    """Oracle: per-segment bucket counts, shape ``(num_segments, m)``.

    Row ``k`` is exactly ``histogram_ref(values[segment_ids == k])`` — one
    fixed-geometry DDSketch bucket array per segment, flattened into a single
    scatter-add so K sketches cost one XLA dispatch.  Entries whose segment
    id falls outside ``[0, num_segments)`` contribute nothing (same contract
    as the non-positive / non-finite masking).  ``levels`` holds *per-value*
    collapse levels — callers with per-row levels gather ``row_levels[s]``
    once outside (so the kernel twin needs no in-kernel gather).
    """
    x = values.reshape(-1).astype(jnp.float32)
    s = segment_ids.reshape(-1).astype(jnp.int32)
    w = (
        jnp.ones_like(x)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    lev = None if levels is None else levels.reshape(-1).astype(jnp.int32)
    mask = (
        jnp.isfinite(x)
        & (x > spec.min_indexable)
        & (s >= 0)
        & (s < num_segments)
    )
    idx = bucket_index(jnp.where(mask, x, 1.0), spec, lev)
    contrib = jnp.where(mask, w, 0.0)
    flat = jnp.clip(s, 0, num_segments - 1) * spec.num_buckets + idx
    out = jnp.zeros(num_segments * spec.num_buckets, jnp.float32).at[flat].add(contrib)
    return out.reshape(num_segments, spec.num_buckets)


# --------------------------------------------------------------------- #
# whole bank insert: histograms + aux stats
# --------------------------------------------------------------------- #
class IngestStats(NamedTuple):
    """Per-row auxiliary statistics of one ingest batch, each ``(K,)``.

    Exactly the six non-bucket fields ``sketch_bank.add_impl`` maintains:
    the caller folds them into the bank with ``+`` (counters / sum) and
    ``minimum`` / ``maximum`` (extrema).  Rows untouched by the batch report
    0 for the counters and ``+inf`` / ``-inf`` for ``vmin`` / ``vmax`` —
    the identities of those folds.
    """

    zero: jnp.ndarray  # weight of |x| <= min_indexable lanes
    overflow: jnp.ndarray  # weight of lanes whose shifted key clamps high
    underflow: jnp.ndarray  # weight of lanes whose shifted key clamps low
    summ: jnp.ndarray  # sum of w * x over valid lanes
    vmin: jnp.ndarray  # min x over contributing (w > 0) lanes
    vmax: jnp.ndarray  # max x over contributing (w > 0) lanes


@partial(jax.jit, static_argnames=("num_segments", "spec"))
def bank_add_ref(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
) -> tuple[jnp.ndarray, IngestStats]:
    """Oracle for a whole bank insert: ``(hist (2K, m), IngestStats)``.

    What ``sketch_bank.add_impl`` adds to a bank, from one independent
    formulation: positives land in rows ``[0, K)`` of the histogram,
    negatives (keyed on ``|x|``) in rows ``[K, 2K)``, through one reducing
    scatter.  The same elementwise key pass feeds the clamp accounting —
    overflow / underflow are lanes whose shifted key escapes
    ``[offset, offset + m - 1]`` — and the six per-row stats come from one
    stacked ``segment_sum`` (zero / overflow / underflow / summ) plus one
    stacked ``segment_min`` (``vmin`` and ``vmax = -min(-x)``).

    Counters are exact (sums of ``w * {0, 1}``); ``summ`` accumulates in
    lane order like ``jax.ops.segment_sum``, matching ``add_impl``'s
    segment-stats path bit-for-bit.
    """
    m = spec.num_buckets
    k = num_segments
    x = values.reshape(-1).astype(jnp.float32)
    if segment_ids is None:
        s = jnp.zeros(x.shape, jnp.int32)
    else:
        s = segment_ids.reshape(-1).astype(jnp.int32)
    w = (
        jnp.ones_like(x)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    lev = (
        jnp.zeros(x.shape, jnp.int32)
        if levels is None
        else levels.reshape(-1).astype(jnp.int32)
    )
    valid = jnp.isfinite(x) & (s >= 0) & (s < k)
    w = jnp.where(valid, w, 0.0)
    sc = jnp.clip(s, 0, max(k - 1, 0))
    is_pos = valid & (x > spec.min_indexable)
    is_neg = valid & (x < -spec.min_indexable)
    is_zero = valid & ~is_pos & ~is_neg

    # one elementwise key pass feeds the histogram AND the clamp accounting
    # (lanes off is_pos | is_neg carry no weight and no clamp flag, whatever
    # their key)
    k_lev = level_keys(x, lev, spec)
    idx = jnp.clip(k_lev - spec.offset, 0, m - 1)
    top_key = spec.offset + m - 1
    over = (is_pos | is_neg) & (k_lev > top_key)
    under = (is_pos | is_neg) & (k_lev < spec.offset)

    sentinel = 2 * k * m
    flat = jnp.where(
        is_pos | is_neg, sc * m + idx + jnp.where(is_neg, k * m, 0), sentinel
    )
    hist = (
        jnp.zeros(sentinel + 1, jnp.float32)
        .at[flat]
        .add(jnp.where(is_pos | is_neg, w, 0.0))[:sentinel]
        .reshape(2 * k, m)
    )

    # stacked reductions: one segment_sum over 4 columns, one segment_min
    # over (x, -x) — six per-row stats for two passes over the lanes
    wx = w * jnp.where(valid, x, 0.0)
    sums = jax.ops.segment_sum(
        jnp.stack([w * is_zero, w * over, w * under, wx], axis=1),
        sc,
        num_segments=k,
    )
    contributes = valid & (w > 0)
    ext = jax.ops.segment_min(
        jnp.stack(
            [
                jnp.where(contributes, x, jnp.inf),
                jnp.where(contributes, -x, jnp.inf),
            ],
            axis=1,
        ),
        sc,
        num_segments=k,
    )
    stats = IngestStats(
        zero=sums[:, 0],
        overflow=sums[:, 1],
        underflow=sums[:, 2],
        summ=sums[:, 3],
        vmin=ext[:, 0],
        vmax=-ext[:, 1],
    )
    return hist, stats


# --------------------------------------------------------------------- #
# fused bank quantile query (Algorithm 2 over all rows and qs at once)
# --------------------------------------------------------------------- #
def _bank_quantiles_math(pos, neg, zero, vmin, vmax, level, qs, table):
    """Shared formulation of the fused query; see ``bank_quantiles_ref``.

    Operates on a ``(K, m)`` row block with per-row scalars shaped ``(K, 1)``.
    ``qs`` is static-length; the loop unrolls, answering every q off one
    cumsum per row.  The rank search is a per-row binary ``searchsorted``
    over the mirrored line's cumsum, and the answer value is gathered
    straight out of ``table`` at the one ``(row, lane)`` each q actually
    reads — the dense per-level value plane and the mirrored value line are
    never materialized.  The Pallas kernel (``kernels/bank_quantiles.py``)
    selects the same elements with lane scans and one-hots, so the two
    agree bit-for-bit on integer-valued counts — the interpret-mode parity
    suite pins this.
    """
    num_levels = table.shape[0]
    m = pos.shape[1]
    lclip = jnp.clip(level, 0, num_levels - 1)
    line_counts = jnp.concatenate([neg[:, ::-1], zero, pos], axis=1)
    n = jnp.sum(line_counts, axis=1, keepdims=True)
    cum = jnp.cumsum(line_counts, axis=1)
    search = jax.vmap(lambda c, r: jnp.searchsorted(c, r, side="right"))
    tflat = table.reshape(-1)
    lrow = lclip.reshape(-1, 1) * m  # row offset into the flat table
    cols = []
    for qi in range(qs.shape[-1]):
        qf = qs.reshape(-1)[qi]
        rank = qf * jnp.maximum(n - 1.0, 0.0)
        idx = search(cum, rank.reshape(-1)).reshape(-1, 1)
        idx = jnp.clip(idx, 0, 2 * m)
        # line lane j maps to -vals[m-1-j] / 0 / vals[j-m-1]; read the
        # one table cell behind it instead of building the line
        vneg = -jnp.take(tflat, lrow + jnp.clip(m - 1 - idx, 0, m - 1))
        vpos = jnp.take(tflat, lrow + jnp.clip(idx - m - 1, 0, m - 1))
        est = jnp.where(idx < m, vneg, jnp.where(idx == m, 0.0, vpos))
        est = jnp.clip(est, vmin, vmax)  # exact-extrema clamp
        est = jnp.where(qf <= 0.0, vmin, jnp.where(qf >= 1.0, vmax, est))
        cols.append(jnp.where(n > 0, est, jnp.nan))
    return jnp.concatenate(cols, axis=1)


@jax.jit
def bank_quantiles_ref(
    pos: jnp.ndarray,
    neg: jnp.ndarray,
    zero: jnp.ndarray,
    vmin: jnp.ndarray,
    vmax: jnp.ndarray,
    level: jnp.ndarray,
    qs: jnp.ndarray,
    table: jnp.ndarray,
) -> jnp.ndarray:
    """Oracle: per-row quantiles ``(K, len(qs))`` in one fused pass.

    Semantically identical to vmapping ``jax_sketch.quantile`` over rows and
    qs (same value line, same cumsum + right-searchsorted, same extrema /
    empty-row handling), but each row's ``(2m+1)`` value line and cumsum are
    materialized once for *all* qs instead of once per (row, q) pair.
    ``table`` is the per-level bucket-value table ``(L+1, m)``; counts may be
    any dtype (cast to float32 for the rank arithmetic).
    """
    qf = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    return _bank_quantiles_math(
        pos.astype(jnp.float32),
        neg.astype(jnp.float32),
        zero.astype(jnp.float32).reshape(-1, 1),
        vmin.reshape(-1, 1),
        vmax.reshape(-1, 1),
        level.astype(jnp.int32).reshape(-1, 1),
        qf,
        table.astype(jnp.float32),
    )


# --------------------------------------------------------------------- #
# uniform collapse: fold adjacent bucket pairs (UDDSketch Algorithm 2)
# --------------------------------------------------------------------- #
def fold_destination_range(spec: BucketSpec) -> tuple[int, int]:
    """(lowest, highest) destination index of one uniform-collapse fold.

    Bucket i holds key ``offset + i``; the fold sends key k to ceil(k/2),
    i.e. index ``(offset + i + 1) // 2 - offset``.  Raises if any
    destination falls outside [0, m) — with the shipped geometries
    (offset <= 0 <= offset + m - 1) destinations always land inside.
    """
    lo = (spec.offset + 1) // 2 - spec.offset
    hi = (spec.offset + spec.num_buckets) // 2 - spec.offset
    if lo < 0 or hi > spec.num_buckets - 1:
        raise ValueError(
            f"fold_pairs destinations [{lo}, {hi}] escape the bucket array "
            f"[0, {spec.num_buckets - 1}] for offset={spec.offset}; uniform "
            "collapse needs offset <= 0 <= offset + num_buckets - 1"
        )
    return lo, hi


@partial(jax.jit, static_argnames=("spec",))
def fold_pairs_ref(counts: jnp.ndarray, *, spec: BucketSpec) -> jnp.ndarray:
    """Oracle: one uniform-collapse step over the bucket axis.

    ``counts`` is ``(..., m)``; output has the same shape with
    ``out[..., ceil((offset+i)/2) - offset] += counts[..., i]``.  Every
    destination receives at most two sources, so the result is exact in
    float32 regardless of accumulation order (the Pallas twin must match
    bit-for-bit).
    """
    fold_destination_range(spec)  # static geometry check
    m = spec.num_buckets
    keys = jnp.arange(m, dtype=jnp.int32) + spec.offset
    dst = ((keys + 1) >> 1) - spec.offset  # ceil(k/2) - offset, in [0, m)
    flat = counts.reshape(-1, m)
    out = jnp.zeros_like(flat).at[:, dst].add(flat)
    return out.reshape(counts.shape)


# --------------------------------------------------------------------- #
# fused slice-range merge: fold every slice row to its per-row target
# level and reduce the slice axis (windowed-quantile tentpole)
# --------------------------------------------------------------------- #
def multi_fold_destinations(spec: BucketSpec, delta: int):
    """Static ``(m,)`` destination indices of a ``delta``-level fold.

    ``shift_key`` nests (ceil(ceil(k/2)/2) == ceil(k/4)), so folding
    ``delta`` levels at once sends bucket i (key ``offset + i``) straight to
    ``ceil((offset + i) / 2**delta) - offset`` — identical to iterating
    ``fold_pairs_ref`` ``delta`` times.  With the shipped geometries
    (offset <= 0 <= offset + m - 1, what ``fold_destination_range``
    enforces) every destination stays inside [0, m) for any delta, which
    this asserts statically.
    """
    import numpy as np

    keys = np.arange(spec.num_buckets, dtype=np.int64) + spec.offset
    dst = -((-keys) >> delta) - spec.offset  # ceil(k / 2**delta) - offset
    if dst.min() < 0 or dst.max() > spec.num_buckets - 1:
        raise ValueError(
            f"multi-level fold (delta={delta}) destinations "
            f"[{dst.min()}, {dst.max()}] escape [0, {spec.num_buckets - 1}] "
            f"for offset={spec.offset}"
        )
    return dst.astype(np.int32)


@partial(jax.jit, static_argnames=("spec",))
def bank_range_merge_ref(
    counts: jnp.ndarray,
    deltas: jnp.ndarray,
    *,
    spec: BucketSpec,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Oracle for the fused range merge: ``(D, R, m) -> (R, m)``.

    Row r of the output is the per-bucket sum of the D slice rows
    ``counts[d, r]`` after folding each one ``deltas[d, r]`` collapse
    levels — i.e. Algorithm 4's merge over the slice axis with the
    UDDSketch level reconciliation applied per (slice, row).  Callers pass
    ``deltas[d, r] = target_level[r] - level[d, r]`` (pre-clipped to
    ``[0, MAX_COLLAPSE_LEVEL]``; this clips again defensively).  ``valid``
    is an optional ``(D,)`` 0/1 slice mask: dead slices contribute
    *nothing* (their counts need not be zeroed — masking rides the
    contraction weights and a delta sentinel, never a pass over the data).

    Two runtime paths behind a ``lax.cond``:

    * **steady state** (every live delta is 0 — the common case: slice
      levels already agree with the range max): the whole merge is ONE
      mask contraction over the slice axis, a single pass over the data;
    * **reconciliation**: folds are linear, so slices are grouped by delta
      with a per-row one-hot contraction of the D axis (one data pass,
      (L, D) @ (D, m) per row), then each group is folded once —
      ``MAX_COLLAPSE_LEVEL`` static scatters total instead of one per
      (slice, delta).

    Exact for integer-valued counts in any accumulation order (the same
    2^24 float32 contract as the dense stores), so the fused result is
    bit-identical to sequential ``sketch_bank.merge`` folds; the Pallas
    twin must match this bit-for-bit.
    """
    fold_destination_range(spec)  # static geometry check
    c = counts.astype(jnp.float32)
    d = jnp.clip(deltas.astype(jnp.int32), 0, MAX_COLLAPSE_LEVEL)
    if valid is None:
        v = jnp.ones((c.shape[0],), jnp.float32)
    else:
        v = valid.astype(jnp.float32).reshape(-1)
        d = jnp.where(v[:, None] > 0, d, -1)  # sentinel: matches no level

    def steady(cc):
        # no folds anywhere: merge == one weighted sum over the slice axis
        return jnp.tensordot(v, cc, axes=1, precision=jax.lax.Precision.HIGHEST)

    def reconcile(cc):
        levels = jnp.arange(MAX_COLLAPSE_LEVEL + 1, dtype=jnp.int32)
        onehot = (d[:, :, None] == levels).astype(jnp.float32)  # (D, R, L)
        grouped = jnp.einsum(
            "drm,drl->lrm", cc, onehot, precision=jax.lax.Precision.HIGHEST
        )
        out = grouped[0]
        for delta in range(1, MAX_COLLAPSE_LEVEL + 1):
            dst = jnp.asarray(multi_fold_destinations(spec, delta))
            out = out.at[:, dst].add(grouped[delta])
        return out

    return jax.lax.cond(jnp.all(d <= 0), steady, reconcile, c)
