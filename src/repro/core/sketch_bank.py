"""SketchBank: K independent device DDSketches as stacked ``(K, m)`` arrays.

The paper's production setting is one quantile sketch *per metric key* (per
endpoint, per customer, per host).  Because DDSketch bucket boundaries are
data-independent, a bank of K fixed-geometry sketches is just a dense
``(K, m)`` array, and inserting a stream of ``(value, sketch_id)`` pairs is a
*segmented* histogram — one kernel/ref dispatch regardless of K, instead of
K launches of ``jax_sketch.add``.  Large batches take the sort–reduce–
scatter ingest pipeline instead (compact duplicate ``(row, bucket)`` keys
on device, then scatter U <= min(N, 2·K·m) unique triples), so insert cost
stops growing multiplicatively with the bank size; ``add(..., method=...)``
pins a pipeline.  Everything else the single sketch enjoys lifts row-wise:

* ``merge`` / ``allreduce`` stay per-bucket '+' (Algorithm 4) after the
  rows align their collapse levels, now over ``(K, m)`` — the bank is
  psum-able exactly like one sketch;
* ``quantiles`` runs Algorithm 2 fused over all K rows *and* all qs at once
  (each row tile builds its (2m+1) value line and cumsum once — the Pallas
  ``bank_quantiles`` kernel on TPU, its XLA twin elsewhere);
* ``row`` / ``to_host`` / ``from_host`` move single rows across tiers
  losslessly (same bucket geometry as ``DeviceSketch``);
* **resolution is per-row**: each row carries its own uniform-collapse
  ``level`` (UDDSketch), so one hot tenant with a 20-decade stream can
  degrade to alpha' while its neighbours keep full resolution.  ``collapse``
  folds selected rows, ``auto_collapse`` reacts to clamped mass, and
  ``add(..., auto_collapse=True)`` pre-collapses rows so nothing clamps.

Per-row auxiliary stats (zero / overflow / underflow / sum / min / max) are
maintained with ``jax.ops.segment_*`` reductions, mirroring
``jax_sketch.add``'s scalar counters.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import jax_sketch
from repro.core.ddsketch import DDSketch
from repro.core.jax_sketch import DeviceSketch
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    shift_key,
)

__all__ = [
    "SketchBank",
    "empty",
    "add",
    "add_impl",
    "picked_insert_method",
    "quantiles_impl",
    "merge",
    "allreduce",
    "collapse",
    "collapse_to",
    "auto_collapse",
    "row",
    "set_row",
    "quantile",
    "quantiles",
    "to_host",
    "from_host",
]


# Banks at or below this many rows compute their per-row aux stats with
# dense masked reductions over a (K, N) one-hot instead of K-segment
# scatter passes: XLA lowers the dense form to vectorized reduces, which on
# CPU is an order of magnitude faster for the small-bank geometries (the
# TelemetryBank's one-row-per-stream tier), while the scatter form stays
# the right shape for wide multi-tenant banks.  Counters are bit-exact in
# both forms for 0/1 weights; the float ``summ`` may reassociate.  The
# element cap bounds the (K, N) temporaries (and the K-fold redundant
# reduction work) when a small bank ingests a huge batch — past it the
# O(N) segment path wins on memory.
_DENSE_STATS_MAX_ROWS = 16
_DENSE_STATS_MAX_ELEMENTS = 1 << 22  # K * N ceiling (16 MiB of f32 per temp)


class SketchBank(NamedTuple):
    """K stacked DDSketch states (leading axis = sketch id).

    Field order mirrors ``DeviceSketch`` exactly, so ``DeviceSketch(*bank)``
    is a bank-of-rows view suitable for vmapping row-wise operations.
    """

    pos: jnp.ndarray  # (K, m) bucket counts for positive values
    neg: jnp.ndarray  # (K, m) bucket counts for negative values (keys of |x|)
    zero: jnp.ndarray  # (K,) counts of |x| <= min_indexable
    overflow: jnp.ndarray  # (K,) counts of |x| clamped into the top bucket
    underflow: jnp.ndarray  # (K,) counts of |x| clamped into bucket 0
    summ: jnp.ndarray  # (K,) running sums
    vmin: jnp.ndarray  # (K,) exact running mins
    vmax: jnp.ndarray  # (K,) exact running maxs
    level: jnp.ndarray  # (K,) int32 per-row uniform-collapse levels

    @property
    def num_sketches(self) -> int:
        return self.pos.shape[0]

    @property
    def counts(self) -> jnp.ndarray:
        """Per-sketch total counts, shape (K,)."""
        return self.pos.sum(axis=1) + self.neg.sum(axis=1) + self.zero


def empty(spec: BucketSpec, num_sketches: int, counts_dtype=jnp.float32) -> SketchBank:
    """Fresh bank state.  ``counts_dtype`` is the bucket/counter dtype:
    float32 (default) is exact to 2^24 per window; int32/int64 raise that
    ceiling for long-horizon on-device accumulation (integer weights
    assumed; int64 requires ``jax_enable_x64`` — raises otherwise).
    ``summ`` and the extrema stay float32 either way."""
    k, m = num_sketches, spec.num_buckets
    cd = jax_sketch._counts_dtype(counts_dtype)
    return SketchBank(
        pos=jnp.zeros((k, m), cd),
        neg=jnp.zeros((k, m), cd),
        zero=jnp.zeros(k, cd),
        overflow=jnp.zeros(k, cd),
        underflow=jnp.zeros(k, cd),
        summ=jnp.zeros(k, jnp.float32),
        vmin=jnp.full(k, jnp.inf, jnp.float32),
        vmax=jnp.full(k, -jnp.inf, jnp.float32),
        level=jnp.zeros(k, jnp.int32),
    )


def _dense_stats_applies(n: int, k: int) -> bool:
    return 0 < k <= _DENSE_STATS_MAX_ROWS and k * n <= _DENSE_STATS_MAX_ELEMENTS


def picked_insert_method(
    n: int,
    k: int,
    num_buckets: int,
    *,
    unit_weights: bool = True,
    on_tpu: bool | None = None,
) -> str:
    """The pipeline ``add_impl(..., method=None)`` resolves to.

    ``kernels.ops.insert_method`` plus this module's one adjustment: on the
    ref tier (``on_tpu`` false; None asks the default backend) a small bank
    (the dense (K, N) stats regime) keeps the two-pass sort path, since the
    dense masked reductions beat the fused segment stats there.  Benches
    record this so every timing row names the pipeline the auto heuristic
    actually ran.
    """
    from repro.kernels import ops

    if on_tpu is None:
        on_tpu = ops._on_tpu()
    method = ops.insert_method(
        n, k, num_buckets, unit_weights=unit_weights, on_tpu=on_tpu,
        full_ingest=True,
    )
    if method == "fused" and _dense_stats_applies(n, k) and not on_tpu:
        method = "sort"
    return method


def add_impl(
    bank: SketchBank,
    values: jnp.ndarray,
    sketch_ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
    auto_collapse: bool = False,
    method: str | None = None,
) -> SketchBank:
    """Vectorized Algorithm 1 over ``(value, sketch_id)`` pairs (any shape).

    Pure/traceable body — the jitted front door is ``add``; the engine AOT-
    compiles this impl into persistent donated executables.

    One bank-histogram dispatch updates all K rows; there is no Python loop
    over sketches anywhere.  Non-finite values and out-of-range ids are
    ignored; positive / negative / near-zero routing matches
    ``jax_sketch.add`` exactly.  Each value is keyed at its *row's* collapse
    level (per-value levels gathered once, outside the kernel).  With
    ``auto_collapse=True`` every touched row first collapses to the smallest
    level at which all of its batch values are indexable, so nothing clamps.

    ``method`` pins the insert pipeline: ``"matmul"`` runs the segmented
    one-hot histogram once over both sign stores, ``"sort"`` compacts a combined composite-key
    stream (sort–reduce) and scatters U <= min(N, 2·K·m) unique triples —
    the input-stationary path whose cost stops growing with the bank size —
    and ``"fused"`` produces the histograms *and* the six aux stats in one
    dispatch (``kernels.ops.fused_ingest``), skipping this function's
    second pass over the lanes entirely.  ``None`` auto-selects from
    (N, K, m) with the fused path on the menu (``picked_insert_method``);
    all pipelines produce identical counts — bit-for-bit except fractional
    float weights on the Pallas sort path (duplicate-key accumulation order
    differs) and the float ``summ``, whose lane-accumulation order varies
    across stats formulations (dense small-K masked matmul vs segment sum
    vs the fused kernel's tile-order pass) at ulp level; see
    ``kernels.ops.bank_histograms`` / ``fused_ingest``.
    """
    k = bank.num_sketches
    x = values.reshape(-1).astype(jnp.float32)
    s = sketch_ids.reshape(-1).astype(jnp.int32)
    raw_w = None if weights is None else weights.reshape(-1).astype(jnp.float32)
    w = jnp.ones_like(x) if raw_w is None else raw_w
    valid = jnp.isfinite(x) & (s >= 0) & (s < k)
    w = jnp.where(valid, w, 0.0)
    sc = jnp.clip(s, 0, k - 1)  # safe ids; invalid lanes carry zero weight

    is_pos = valid & (x > spec.min_indexable)
    is_neg = valid & (x < -spec.min_indexable)
    is_zero = valid & ~is_pos & ~is_neg

    dense_stats = _dense_stats_applies(x.size, k)
    sel = (
        (sc[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None])
        if dense_stats
        else None
    )  # (K, N) row-membership mask; invalid lanes already carry zero weight

    k0 = jax_sketch._raw_keys(x, is_pos | is_neg, spec)
    if auto_collapse:
        needed = jnp.where(is_pos | is_neg, jax_sketch._needed_levels(k0, spec), 0)
        if dense_stats:
            per_row = jnp.max(
                jnp.where(sel, needed[None, :], 0), axis=1, initial=0
            )
        else:
            per_row = jax.ops.segment_max(needed, sc, num_segments=k)
        target = jnp.maximum(bank.level, jnp.maximum(per_row, 0))
        bank = collapse_to(bank, target, spec=spec)
    shifts = bank.level[sc]  # per-value levels for the segmented kernels

    from repro.kernels import ops

    if method is None:
        method = picked_insert_method(
            x.size, k, spec.num_buckets,
            unit_weights=raw_w is None,
        )

    if method == "fused":
        # one dispatch: histograms + aux stats; no second pass below
        pos_hist, neg_hist, st = ops.fused_ingest(
            x,
            s,
            raw_w,
            shifts,
            num_segments=k,
            spec=spec,
        )
        cd = bank.pos.dtype
        return SketchBank(
            pos=bank.pos + pos_hist.astype(cd),
            neg=bank.neg + neg_hist.astype(cd),
            zero=bank.zero + st.zero.astype(cd),
            overflow=bank.overflow + st.overflow.astype(cd),
            underflow=bank.underflow + st.underflow.astype(cd),
            summ=bank.summ + st.summ,
            vmin=jnp.minimum(bank.vmin, st.vmin),
            vmax=jnp.maximum(bank.vmax, st.vmax),
            level=bank.level,
        )

    pos_hist, neg_hist = ops.bank_histograms(
        x,
        s,
        raw_w,
        shifts,
        num_segments=k,
        spec=spec,
        method=method,
    )

    # clamp accounting: shifted keys that escape [offset, offset + m - 1]
    top_key = spec.offset + spec.num_buckets - 1
    k_lev = shift_key(k0, shifts)
    over = (is_pos | is_neg) & (k_lev > top_key)
    under = (is_pos | is_neg) & (k_lev < spec.offset)

    wx = w * jnp.where(valid, x, 0.0)
    contributes = valid & (w > 0)
    if dense_stats:
        onehot = sel.astype(jnp.float32)

        def seg_sum(v, _sc):
            return onehot @ v

        lane = sel & contributes[None, :]
        vmin_new = jnp.min(
            jnp.where(lane, x[None, :], jnp.inf), axis=1, initial=jnp.inf
        )
        vmax_new = jnp.max(
            jnp.where(lane, x[None, :], -jnp.inf), axis=1, initial=-jnp.inf
        )
    else:
        seg_sum = partial(jax.ops.segment_sum, num_segments=k)
        vmin_new = jax.ops.segment_min(
            jnp.where(contributes, x, jnp.inf), sc, num_segments=k
        )
        vmax_new = jax.ops.segment_max(
            jnp.where(contributes, x, -jnp.inf), sc, num_segments=k
        )

    cd = bank.pos.dtype
    return SketchBank(
        pos=bank.pos + pos_hist.astype(cd),
        neg=bank.neg + neg_hist.astype(cd),
        zero=bank.zero + seg_sum(w * is_zero, sc).astype(cd),
        overflow=bank.overflow + seg_sum(w * over, sc).astype(cd),
        underflow=bank.underflow + seg_sum(w * under, sc).astype(cd),
        summ=bank.summ + seg_sum(wx, sc),
        vmin=jnp.minimum(bank.vmin, vmin_new),
        vmax=jnp.maximum(bank.vmax, vmax_new),
        level=bank.level,
    )


add = partial(
    jax.jit, static_argnames=("spec", "auto_collapse", "method")
)(add_impl)


# --------------------------------------------------------------------- #
# per-row uniform collapse (UDDSketch lifted over the bank axis)
# --------------------------------------------------------------------- #
_fold = jax_sketch._fold  # same (m,)/(K, m) fold dispatch on both tiers


def collapse(
    bank: SketchBank,
    rows: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
) -> SketchBank:
    """One uniform-collapse step on the selected rows (all rows if None).

    ``rows`` is a (K,) boolean mask.  Selected rows fold their pos/neg
    bucket pairs and bump their level; unselected rows are untouched —
    count / sum / min / max are preserved exactly either way.
    """
    mask = (
        jnp.ones(bank.num_sketches, bool)
        if rows is None
        else jnp.asarray(rows, bool)
    )
    pos_f = _fold(bank.pos, spec)
    neg_f = _fold(bank.neg, spec)
    return bank._replace(
        pos=jnp.where(mask[:, None], pos_f, bank.pos),
        neg=jnp.where(mask[:, None], neg_f, bank.neg),
        level=jnp.where(mask, bank.level + 1, bank.level),
    )


def collapse_to(
    bank: SketchBank, target, *, spec: BucketSpec
) -> SketchBank:
    """Fold each row until its level reaches ``target`` (scalar or (K,)).

    Clamped to ``MAX_COLLAPSE_LEVEL``; a fixed-shape ``while_loop`` over
    the laggard rows, so mixed-level alignment composes with jit/shard_map.
    """
    target = jnp.broadcast_to(
        jnp.clip(jnp.asarray(target, jnp.int32), 0, MAX_COLLAPSE_LEVEL),
        bank.level.shape,
    )
    return jax.lax.while_loop(
        lambda b: (b.level < target).any(),
        lambda b: collapse(b, b.level < target, spec=spec),
        bank,
    )


def auto_collapse(
    bank: SketchBank,
    *,
    spec: BucketSpec,
    threshold: float = 0.0,
) -> SketchBank:
    """Reactive collapse: fold rows whose clamped mass exceeds ``threshold``.

    Row semantics match ``jax_sketch.auto_collapse``: fires on
    ``overflow + underflow > threshold`` (level cap permitting), resets the
    firing rows' clamp counters, leaves the rest untouched.
    """
    fire = (bank.overflow + bank.underflow > threshold) & (
        bank.level < MAX_COLLAPSE_LEVEL
    )
    folded = collapse(bank, fire, spec=spec)
    return folded._replace(
        overflow=jnp.where(fire, jnp.zeros_like(bank.overflow), bank.overflow),
        underflow=jnp.where(fire, jnp.zeros_like(bank.underflow), bank.underflow),
    )


def merge(a: SketchBank, b: SketchBank, *, spec: BucketSpec) -> SketchBank:
    """Algorithm 4 over all K rows, generalized to mixed resolutions.

    Each row pair aligns to the coarser of the two levels (the finer row
    collapses first — Cafaro et al. 2021), then sums per bucket; rows at
    equal levels reduce to the plain '+'."""
    target = jnp.maximum(a.level, b.level)
    a = collapse_to(a, target, spec=spec)
    b = collapse_to(b, target, spec=spec)
    return SketchBank(
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


def allreduce(bank: SketchBank, axis_name, *, spec: BucketSpec) -> SketchBank:
    """Cross-device Algorithm 4 for the whole bank.

    Rows first collapse to the fleet-max level per row (pmax), then one
    psum per field combines the commensurate bucket arrays."""
    target = jax.lax.pmax(bank.level, axis_name)
    bank = collapse_to(bank, target, spec=spec)
    return SketchBank(
        pos=jax.lax.psum(bank.pos, axis_name),
        neg=jax.lax.psum(bank.neg, axis_name),
        zero=jax.lax.psum(bank.zero, axis_name),
        overflow=jax.lax.psum(bank.overflow, axis_name),
        underflow=jax.lax.psum(bank.underflow, axis_name),
        summ=jax.lax.psum(bank.summ, axis_name),
        vmin=jax.lax.pmin(bank.vmin, axis_name),
        vmax=jax.lax.pmax(bank.vmax, axis_name),
        level=target,
    )


# --------------------------------------------------------------------- #
# row access (host <-> device tier moves are per row, like single sketches)
# --------------------------------------------------------------------- #
def row(bank: SketchBank, k: int) -> DeviceSketch:
    """Row ``k`` as a standalone DeviceSketch (shares the bucket geometry)."""
    return DeviceSketch(*(field[k] for field in bank))


def set_row(bank: SketchBank, k: int, sketch: DeviceSketch) -> SketchBank:
    """Functional update: replace row ``k`` with a DeviceSketch's state."""
    return SketchBank(
        *(bf.at[k].set(sf) for bf, sf in zip(bank, sketch))
    )


def to_host(bank: SketchBank, spec: BucketSpec, k: int) -> DDSketch:
    """Flush row ``k`` into the exact, unbounded host sketch (lossless for
    integer-weight counts below 2^24; see ``jax_sketch.to_host``).  The
    row's collapse level transfers as the host ``collapse_level``."""
    return jax_sketch.to_host(row(bank, k), spec)


def from_host(
    hosts: Sequence[DDSketch], spec: BucketSpec, counts_dtype=jnp.float32
) -> SketchBank:
    """Stack host sketches into a bank, one per row (keys clamp into range).

    Like ``jax_sketch.from_host``, the device-only ``overflow`` /
    ``underflow`` counters have no host-tier equivalent and restart at zero;
    per-row levels come from each host's ``collapse_level``.
    ``counts_dtype`` restores counts into a chosen counter dtype (int32 /
    int64 keep exact host counts past float32's 2^24 ceiling).
    """
    rows = [jax_sketch.from_host(h, spec, counts_dtype=counts_dtype) for h in hosts]
    if not rows:
        return empty(spec, 0, counts_dtype=counts_dtype)
    return SketchBank(*(jnp.stack(f) for f in zip(*rows)))


# --------------------------------------------------------------------- #
# queries: Algorithm 2 fused over all K rows and all qs at once
# --------------------------------------------------------------------- #
def quantiles_impl(
    bank: SketchBank,
    qs: jnp.ndarray,
    *,
    spec: BucketSpec,
    table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-row quantile estimates, shape ``(K, len(qs))``.

    Pure/traceable body — the jitted front door is ``quantiles``; the
    engine AOT-compiles this impl into persistent executables.

    The fused bank query (``kernels.ops.bank_quantiles``): each row tile
    materializes its ``(2m+1)`` neg/zero/pos value line and cumulative
    counts *once* and answers every q off that cumsum — no per-(row, q)
    rebuilds, no Python loop anywhere.  Bit-identical to querying each row
    as a standalone DeviceSketch at its own collapse level; all-empty rows
    answer NaN.  On TPU this is the Pallas row-tile kernel; elsewhere the
    fused XLA twin (``kernels.ops``' automatic choice).
    """
    qf = jnp.atleast_1d(jnp.asarray(qs, jnp.float32))
    from repro.kernels import ops

    return ops.bank_quantiles(
        bank.pos,
        bank.neg,
        bank.zero,
        bank.vmin,
        bank.vmax,
        bank.level,
        qf,
        spec=spec,
        table=table,
    )


quantiles = partial(jax.jit, static_argnames=("spec",))(quantiles_impl)


@partial(jax.jit, static_argnames=("spec",))
def quantile(
    bank: SketchBank, q, *, spec: BucketSpec
) -> jnp.ndarray:
    """One quantile for every row, shape ``(K,)`` (NaN for empty rows)."""
    return quantiles_impl(bank, jnp.asarray([q]), spec=spec)[
        :, 0
    ]
