"""Public jit'd entry points for the DDSketch kernels.

``ddsketch_histogram`` (one sketch), ``segment_histogram`` (a bank of K
sketches), ``fold_pairs`` (the uniform-collapse resolution fold),
``ddsketch_scatter`` (the scatter stage of the sort–reduce–scatter ingest)
and ``bank_quantiles`` (the fused bank query) dispatch to the compiled
Pallas kernels on TPU and to the pure-XLA reference elsewhere.  The
semantics contracts are the ``repro.kernels.ref`` oracles; tests sweep
shapes, dtypes, mappings and tile configurations asserting exact agreement.

``force`` pins an implementation:

* ``"ref"``        — pure-XLA scatter path (any backend),
* ``"interpret"``  — interpret-mode Pallas (correctness tool, any backend),
* ``"pallas"``     — the compiled Mosaic kernel; **TPU only** (the kernel
  targets TPU tiling/VMEM — compiling it on CPU/GPU fails mid-lowering, so
  requesting it off-TPU raises immediately instead),
* ``None``         — auto: compiled kernel on TPU *when the batch fills at
  least one tile* (padding a sub-tile batch to ``value_tile`` costs more
  than the XLA scatter it replaces), reference elsewhere.  Every TPU detour
  to the reference (sub-tile batch, bank taller than the resident rows,
  sub-tile query row axis) is counted in ``dispatch_stats()`` and warned
  once per site, so a served path that left the chip's kernels shows it.

``bank_histograms`` is the bank-insert front door: it routes a batch of
``(value, segment)`` pairs to the matmul-histogram formulation (one
row-sparse launch over both sign stores: a stable sort of the lanes by row,
then one step per bucket tile and (row tile, value tile) pair that holds a
lane, at most ``nr + nv - 1`` pairs), to the sort–reduce–scatter pipeline
(O(N log N) sort + compaction to U <= min(N, 2·K·m) triples), or to the
fused single-dispatch ingest (``fused_ingest``: bucketize + bin + aux stats
in one program) based on the ``(N, K, m)`` arithmetic-intensity ratio;
``method=`` pins a pipeline the same way ``force=`` pins a backend, and the
``REPRO_INSERT_METHOD`` environment variable overrides the auto heuristic
process-wide (benchmark attribution / emergency pinning).
"""

from __future__ import annotations

import math
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels.bank_quantiles import bank_quantiles_pallas
from repro.kernels.bank_range_merge import bank_range_merge_pallas
from repro.kernels.ddsketch_hist import histogram_pallas
from repro.kernels.ddsketch_ingest import ddsketch_ingest_pallas
from repro.kernels.ddsketch_scatter import MAX_RESIDENT_ROWS, ddsketch_scatter_pallas
from repro.kernels.ddsketch_seg_hist import segment_histogram_pallas
from repro.kernels.fold_pairs import fold_pairs_pallas
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    IngestStats,
    bank_quantiles_ref,
    bank_range_merge_ref,
    compact_triples,
    composite_keys,
    fold_pairs_ref,
    fused_ingest_ref,
    histogram_ref,
    scatter_histogram_ref,
    segment_histogram_ref,
)

__all__ = [
    "ddsketch_histogram",
    "segment_histogram",
    "fold_pairs",
    "ddsketch_scatter",
    "bank_histograms",
    "fused_ingest",
    "bank_quantiles",
    "bank_range_merge",
    "insert_method",
    "dispatch_stats",
    "fallback_count",
    "reset_dispatch_stats",
    "BucketSpec",
    "IngestStats",
]

_FORCE_VALUES = (None, "pallas", "interpret", "ref")
_METHOD_VALUES = (None, "matmul", "sort", "fused")
_METHOD_ENV = "REPRO_INSERT_METHOD"

# fallback observability: every auto dispatch that leaves the compiled
# kernel on TPU warns once per (kind, site) and counts here.  Counts are per
# *trace* (the decision is made on static shapes at trace time), so an AOT
# executable that falls back registers once, not once per call.
_DISPATCH_STATS: dict[str, dict[str, int]] = {
    # bank row axis taller than MAX_RESIDENT_ROWS on a resident-row kernel
    "tall_bank_fallbacks": {},
    # per-*trace* count of fused range-merge dispatches: the windowed-query
    # acceptance test asserts a W-slice window query registers exactly one
    # (one device program, not W-1 host-looped merges)
    "range_merge_calls": {},
    # query row axis below row_tile (bank_quantiles / bank_range_merge)
    "query_fallbacks": {},
    # ingest batch below one value / triple tile
    "subtile_fallbacks": {},
}
_FALLBACK_KINDS = ("tall_bank_fallbacks", "query_fallbacks", "subtile_fallbacks")
_WARNED: set[tuple[str, str]] = set()


def dispatch_stats() -> dict:
    """Snapshot of auto-dispatch fallback counters (copies, safe to keep)."""
    return {k: dict(v) for k, v in _DISPATCH_STATS.items()}


def fallback_count() -> int:
    """Total TPU detours to the reference recorded so far (all kinds)."""
    return sum(sum(_DISPATCH_STATS[k].values()) for k in _FALLBACK_KINDS)


def reset_dispatch_stats() -> None:
    """Clear fallback counters AND the warn-once latches (tests/benches)."""
    for v in _DISPATCH_STATS.values():
        v.clear()
    _WARNED.clear()


def _note_fallback(kind: str, site: str, detail: str) -> None:
    """Count (and warn once per site) an auto dispatch landing on the ref."""
    counts = _DISPATCH_STATS[kind]
    counts[site] = counts.get(site, 0) + 1
    if (kind, site) not in _WARNED:
        _WARNED.add((kind, site))
        warnings.warn(
            f"{site}: {detail}; auto dispatch is falling back to the XLA "
            "reference path (correct but off the compiled kernel).  Recorded "
            f"in ops.dispatch_stats()[{kind!r}]; warning once per site.",
            RuntimeWarning,
            stacklevel=3,
        )


def _note_tall_bank_fallback(site: str, num_rows: int) -> None:
    _note_fallback(
        "tall_bank_fallbacks",
        site,
        f"bank row axis ({num_rows} rows) exceeds MAX_RESIDENT_ROWS="
        f"{MAX_RESIDENT_ROWS}; shard the bank, shrink it, or pin "
        'method="matmul"',
    )


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _check_force(force: str | None) -> None:
    if force not in _FORCE_VALUES:
        raise ValueError(f"force must be one of {_FORCE_VALUES}, got {force!r}")
    if force == "pallas" and not _on_tpu():
        raise RuntimeError(
            'force="pallas" requests the compiled TPU kernel but the default '
            f"backend is {jax.default_backend()!r}; use force=\"interpret\" "
            'for correctness checks or force="ref" for the XLA fallback'
        )


def _impl(
    force: str | None,
    n: int,
    tile: int,
    site: str,
    kind: str = "subtile_fallbacks",
) -> str:
    """Resolve ``force=None`` to a concrete implementation, size-aware.

    Pinned values pass through.  Auto picks the compiled kernel only on TPU
    *and* only when the streamed axis fills at least one tile (``n >=
    tile``): below that, padding to the tile dominates the launch and the
    XLA reference is strictly cheaper.  That TPU detour is counted under
    ``kind`` for ``site`` (warn once).  The crossover is pinned by a unit
    test in ``tests/test_sort_scatter.py``.
    """
    if force is not None:
        return force
    if not _on_tpu():
        return "ref"
    if n < tile:
        detail = (
            f"bank row axis ({n} rows) is below row_tile={tile}"
            if kind == "query_fallbacks"
            else f"batch ({n} lanes) is below one tile ({tile} lanes)"
        )
        _note_fallback(kind, site, detail)
        return "ref"
    return "pallas"


def insert_method(
    n: int,
    num_segments: int,
    num_buckets: int,
    unit_weights: bool = True,
    on_tpu: bool | None = None,
    full_ingest: bool = False,
) -> str:
    """Pick ``"matmul"``, ``"sort"`` or ``"fused"`` for a bank insert.

    ``full_ingest=True`` means the caller wants histogram *and* aux stats
    (``sketch_bank.add_impl``), so the fused single-dispatch path is on the
    menu; histogram-only callers (``full_ingest=False``) never auto-pick
    ``"fused"`` — its fused stats would be pure overhead there (pinning
    ``method="fused"`` still works and simply drops the stats).

    The ``REPRO_INSERT_METHOD`` environment variable overrides the
    heuristic process-wide (any of ``matmul | sort | fused``) — the
    benchmark-attribution / emergency-pinning knob; invalid values raise.

    On TPU the matmul-histogram kernel stable-sorts the lanes by row, then
    runs one step per bucket tile and (row tile, value tile) pair that
    holds a lane — at most ``ceil(2K/TR) + ceil(N/TV) - 1`` pairs, so it
    streams ``ceil(m/TB) * (N + TV * touched_row_tiles)`` lanes at most,
    plus the sort, whatever the bank height; the sort pipeline pays
    N·log2(N) once and then streams only U <= 2·K·m compacted triples; the
    fused kernel keeps all 2K rows resident so its streamed work is
    ``ceil(m/TB) * N`` with no sort stage and no second stats pass.  For
    full ingests with the rows resident, fused wins unless the bucket-tile
    count outgrows the sort factor (huge m, small N); banks taller than the
    resident-row ceiling take matmul, the one kernel that tiles the rows.
    The histogram-only rule below (``out_tiles`` against ``sort_cost``)
    prices a grid that visits every output tile and is not yet
    recalibrated for the row-sparse one.

    On the XLA reference tier the sort pipeline folds into one key pass +
    one reducing scatter; the fused path adds the stacked stats reductions
    to that same single lane pass, so for full ingests it subsumes the
    separate ``add_impl`` stats pass (measured ~1.5x over sort at N=1M,
    K=128 on CPU — ``benchmarks/bank_bench.bench_fused_ingest``).  The
    N >= 2^14 crossover vs matmul is shared: below it the batch cannot
    amortize the scatter plumbing.  ``unit_weights`` only matters for the
    TPU sort heuristic, where weighted streams must payload-sort.
    """
    env = os.environ.get(_METHOD_ENV)
    if env:
        if env not in _METHOD_VALUES[1:]:
            raise ValueError(
                f"{_METHOD_ENV}={env!r}: must be one of {_METHOD_VALUES[1:]}"
            )
        return env
    if on_tpu is None:
        on_tpu = _on_tpu()
    if n == 0:
        return "matmul"
    logn = max(math.log2(n), 1.0)
    if on_tpu:
        if 2 * num_segments > MAX_RESIDENT_ROWS:
            return "matmul"
        # weighted streams payload-sort (keys + weights move together),
        # roughly doubling the sort stage the pipeline must amortize
        sort_cost = (4.0 if unit_weights else 8.0) * logn
        if full_ingest:
            bucket_tiles = math.ceil(num_buckets / 512)
            return "fused" if bucket_tiles <= sort_cost else "sort"
        out_tiles = math.ceil(2 * num_segments / 8) * math.ceil(num_buckets / 512)
        return "sort" if out_tiles > sort_cost else "matmul"
    if n < (1 << 14):
        return "matmul"
    return "fused" if full_ingest else "sort"


def ddsketch_histogram(
    values: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
    value_tile: int = 2048,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> jnp.ndarray:
    """Bucket counts (m,) of the positive finite entries of ``values``.

    ``levels`` holds per-value int32 collapse levels; omitted = level 0."""
    _check_force(force)
    impl = _impl(force, values.size, value_tile, "ddsketch_histogram")
    if impl == "ref":
        return histogram_ref(values, weights, levels, spec=spec)
    return histogram_pallas(
        values,
        weights,
        levels,
        spec=spec,
        value_tile=value_tile,
        bucket_tile=bucket_tile,
        interpret=impl == "interpret",
    )


def segment_histogram(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
    value_tile: int = 2048,
    row_tile: int = 8,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> jnp.ndarray:
    """Per-segment bucket counts ``(num_segments, m)`` — one dispatch for a
    whole bank of K sketches regardless of K.  ``levels`` holds *per-value*
    int32 collapse levels (gather per-row levels outside); omitted = level 0."""
    _check_force(force)
    impl = _impl(force, values.size, value_tile, "segment_histogram")
    if impl == "ref":
        return segment_histogram_ref(
            values, segment_ids, weights, levels, num_segments=num_segments, spec=spec
        )
    return segment_histogram_pallas(
        values,
        segment_ids,
        weights,
        levels,
        num_segments=num_segments,
        spec=spec,
        value_tile=value_tile,
        row_tile=row_tile,
        bucket_tile=bucket_tile,
        interpret=impl == "interpret",
    )


def fold_pairs(
    counts: jnp.ndarray,
    *,
    spec: BucketSpec,
    row_tile: int = 8,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> jnp.ndarray:
    """One uniform-collapse fold of ``counts`` (``(K, m)`` or ``(m,)``):
    bucket pairs with keys (2j-1, 2j) merge into key j, halving the sketch
    resolution (gamma -> gamma**2).  Exact: every destination bucket sums at
    most two sources, so Pallas and XLA paths agree bit-for-bit."""
    _check_force(force)
    if force == "ref" or (force is None and not _on_tpu()):
        return fold_pairs_ref(counts, spec=spec)
    return fold_pairs_pallas(
        counts,
        spec=spec,
        row_tile=row_tile,
        bucket_tile=bucket_tile,
        interpret=force == "interpret",
    )


def ddsketch_scatter(
    keys: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    num_rows: int,
    num_buckets: int,
    triple_tile: int = 512,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> jnp.ndarray:
    """Accumulate composite-key triples into ``(num_rows, num_buckets)``.

    The scatter stage of the ingest pipeline; keys outside
    ``[0, num_rows * num_buckets)`` (the compaction sentinels) contribute
    nothing.  Bit-exact vs ``ref.scatter_histogram_ref`` for unique keys —
    what ``ref.compact_triples`` emits."""
    _check_force(force)
    impl = _impl(force, keys.size, triple_tile, "ddsketch_scatter")
    if impl != "ref" and num_rows > MAX_RESIDENT_ROWS and force is None:
        # auto never hands a too-tall bank to the resident kernel — but it
        # no longer changes paths silently (warn once + counted)
        _note_tall_bank_fallback("ddsketch_scatter", num_rows)
        impl = "ref"
    if impl == "ref":
        return scatter_histogram_ref(
            keys, weights, num_rows=num_rows, num_buckets=num_buckets
        )
    return ddsketch_scatter_pallas(
        keys,
        weights,
        num_rows=num_rows,
        num_buckets=num_buckets,
        triple_tile=triple_tile,
        bucket_tile=bucket_tile,
        interpret=impl == "interpret",
    )


def bank_histograms(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
    method: str | None = None,  # "matmul" | "sort" | "fused" | None(auto)
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
    value_tile: int = 2048,
    row_tile: int = 8,
    bucket_tile: int = 512,
    triple_tile: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Both sign stores of a bank insert: ``(pos, neg)``, each ``(K, m)``.

    The single entry point behind ``DeviceSketch.add`` / ``SketchBank.add``:
    sign routing (positives keyed on x, negatives on ``|x|``, everything
    else contributing nothing) happens here, and ``method`` picks the
    pipeline — ``"matmul"`` routes each sign into the stacked ``(2K, m)``
    layout and runs the segmented histogram once over it; ``"sort"`` is the
    sort–reduce–scatter ingest pipeline over one combined composite-key
    stream into the same layout.  On the Pallas tiers the pipeline is
    materialized literally — ``ref.compact_triples`` (sort + segment-sum)
    feeds the ``ddsketch_scatter`` kernel U <= min(N, 2·K·m) unique
    triples — while the XLA twin folds the sort+reduce *into* the reducing
    scatter-add (order-free exact accumulation needs no physical sort), so
    on the ref tier both pipelines pay one key pass + one scatter.

    ``method="fused"`` routes through ``fused_ingest`` (one program:
    bucketize + bin + aux stats) and drops the stats — correct anywhere,
    but the stats work is wasted on this histogram-only surface, so
    ``method=None`` never auto-picks it here (``insert_method`` only offers
    fused to ``full_ingest`` callers like ``sketch_bank.add_impl``, which
    calls ``fused_ingest`` directly to keep the stats).

    ``method=None`` auto-selects via ``insert_method``; all pipelines
    produce identical counts.  On the XLA tier the match is bit-for-bit
    for *arbitrary* weights (per output bucket the contributing lanes
    accumulate in the same order as the matmul path); on the Pallas tiers
    the unstable compaction sort reorders duplicate-key accumulation, so
    bit-exactness there holds for unit or integer-valued weights (fractional
    weights may differ in final ulps).  ``segment_ids=None`` is the
    single-sketch case (requires ``num_segments == 1``).
    """
    _check_force(force)
    if method not in _METHOD_VALUES:
        raise ValueError(f"method must be one of {_METHOD_VALUES}, got {method!r}")
    if segment_ids is None and num_segments != 1:
        raise ValueError(
            "segment_ids may be omitted only for a single-row bank "
            f"(num_segments=1), got num_segments={num_segments}"
        )
    n = int(values.size)
    if method is None:
        method = insert_method(
            n, num_segments, spec.num_buckets, unit_weights=weights is None
        )
    if method == "fused":
        pos, neg, _ = fused_ingest(
            values,
            segment_ids,
            weights,
            levels,
            num_segments=num_segments,
            spec=spec,
            bucket_tile=bucket_tile,
            force=force,
        )
        return pos, neg
    if method == "matmul":
        x = values.reshape(-1).astype(jnp.float32)
        if segment_ids is None:
            kw = dict(spec=spec, value_tile=value_tile, bucket_tile=bucket_tile,
                      force=force)
            pos_vals = jnp.where(x > spec.min_indexable, x, -1.0)
            neg_vals = jnp.where(x < -spec.min_indexable, -x, -1.0)
            pos = ddsketch_histogram(pos_vals, weights, levels, **kw)[None]
            neg = ddsketch_histogram(neg_vals, weights, levels, **kw)[None]
            return pos, neg
        # one launch over the stacked (2K, m) layout: positives key into rows
        # [0, K), negatives (on |x|) into [K, 2K), every other lane into -1
        s = segment_ids.reshape(-1).astype(jnp.int32)
        ok = (s >= 0) & (s < num_segments)
        rows = jnp.where(ok & (x > spec.min_indexable), s,
                         jnp.where(ok & (x < -spec.min_indexable),
                                   s + num_segments, -1))
        both = segment_histogram(
            jnp.abs(x), rows, weights, levels,
            num_segments=2 * num_segments, spec=spec, value_tile=value_tile,
            row_tile=row_tile, bucket_tile=bucket_tile, force=force,
        )
        return both[:num_segments], both[num_segments:]
    impl = _impl(force, n, triple_tile, "bank_histograms[sort]")
    if impl != "ref" and 2 * num_segments > MAX_RESIDENT_ROWS and force is None:
        # bank too tall for the resident-row scatter kernel (warn once)
        _note_tall_bank_fallback("bank_histograms[sort]", 2 * num_segments)
        impl = "ref"
    if impl == "ref":
        # XLA twin of the pipeline: scatter-add already reduces by key, so
        # the sort + segment-sum stages are the identity here — one
        # composite-key pass and one reducing scatter.
        keys = composite_keys(
            values, segment_ids, levels, num_segments=num_segments, spec=spec
        )
        wts = (
            jnp.ones(keys.shape, jnp.float32)
            if weights is None
            else weights.reshape(-1).astype(jnp.float32)
        )
        both = scatter_histogram_ref(
            keys, wts, num_rows=2 * num_segments, num_buckets=spec.num_buckets
        )
    else:
        keys, wts = compact_triples(
            values, segment_ids, weights, levels, num_segments=num_segments, spec=spec
        )
        # the runs are packed to the front, so the streamed axis shrinks to
        # the compacted bound min(N, 2Km + 1) — this slice is the whole
        # point of the pipeline on the kernel tiers: the scatter kernel
        # streams U-ish lanes per bucket tile, not N
        cap = min(n, 2 * num_segments * spec.num_buckets + 1)
        both = ddsketch_scatter_pallas(
            keys[:cap],
            wts[:cap],
            num_rows=2 * num_segments,
            num_buckets=spec.num_buckets,
            triple_tile=triple_tile,
            bucket_tile=bucket_tile,
            interpret=impl == "interpret",
        )
    return both[:num_segments], both[num_segments:]


def fused_ingest(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
    value_tile: int = 512,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> tuple[jnp.ndarray, jnp.ndarray, IngestStats]:
    """The fused single-dispatch ingest: ``(pos, neg, IngestStats)``.

    One program produces both ``(K, m)`` sign stores AND the six per-row
    aux stats (zero / overflow / underflow / summ / vmin / vmax) the bank
    maintains — ``sketch_bank.add_impl`` folds them in directly instead of
    making a second pass over the lanes.  Semantics contract is
    ``ref.fused_ingest_ref`` (histograms and the integer-weight counters
    bit-exact across tiers; the float ``summ`` may differ in final ulps on
    the Pallas tiers, where it accumulates in tile order).

    Banks whose combined pos/neg row axis exceeds ``MAX_RESIDENT_ROWS``
    fall back from the resident-row kernel to the reference (warn-once,
    counted in ``dispatch_stats()``); ``force="pallas"`` on such a bank
    raises in the kernel instead.
    """
    _check_force(force)
    impl = _impl(force, values.size, value_tile, "fused_ingest")
    if impl != "ref" and 2 * num_segments > MAX_RESIDENT_ROWS and force is None:
        _note_tall_bank_fallback("fused_ingest", 2 * num_segments)
        impl = "ref"
    if impl == "ref":
        both, stats = fused_ingest_ref(
            values, segment_ids, weights, levels,
            num_segments=num_segments, spec=spec,
        )
    else:
        both, stats = ddsketch_ingest_pallas(
            values,
            segment_ids,
            weights,
            levels,
            num_segments=num_segments,
            spec=spec,
            value_tile=value_tile,
            bucket_tile=bucket_tile,
            interpret=impl == "interpret",
        )
    return both[:num_segments], both[num_segments:], stats


def bank_quantiles(
    pos: jnp.ndarray,
    neg: jnp.ndarray,
    zero: jnp.ndarray,
    vmin: jnp.ndarray,
    vmax: jnp.ndarray,
    level: jnp.ndarray,
    qs: jnp.ndarray,
    *,
    spec: BucketSpec,
    row_tile: int = 8,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
    table: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fused Algorithm 2 over all K rows and all qs: ``(K, len(qs))``.

    One cumsum + lane-count searchsorted per row tile answers every q; per
    row collapse levels select the bucket-value line from the per-spec
    engine table cache.  Pallas and XLA paths share the formulation and
    agree bit-for-bit; counts of any dtype are cast to float32 for rank
    math.  ``table`` lets AOT callers (the engine) thread the per-level
    value table as an explicit executable argument instead of a closure
    constant; ``None`` fetches the engine's cached per-spec copy."""
    _check_force(force)
    if table is None:
        from repro.engine.tables import device_value_table  # deferred: no cycle

        table = device_value_table(spec)
    impl = _impl(force, pos.shape[0], row_tile, "bank_quantiles",
                 "query_fallbacks")
    if impl == "ref":
        return bank_quantiles_ref(pos, neg, zero, vmin, vmax, level, qs, table)
    return bank_quantiles_pallas(
        pos,
        neg,
        zero,
        vmin,
        vmax,
        level,
        qs,
        table,
        row_tile=row_tile,
        interpret=impl == "interpret",
    )


def bank_range_merge(
    counts: jnp.ndarray,
    deltas: jnp.ndarray,
    *,
    spec: BucketSpec,
    valid: jnp.ndarray | None = None,
    row_tile: int = 8,
    bucket_tile: int = 512,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
) -> jnp.ndarray:
    """Fused slice-range merge: ``counts (D, R, m), deltas (D, R) -> (R, m)``.

    The windowed-quantile tentpole: fold every slice row ``counts[d, r]``
    by ``deltas[d, r]`` uniform-collapse levels (reconciling the window's
    mixed per-row resolutions to the range max) and reduce the slice axis —
    a whole W-slice range merge in ONE dispatch, instead of W-1 host-looped
    ``sketch_bank.merge`` calls.  ``valid`` is an optional ``(D,)`` 0/1
    slice mask: dead slices contribute nothing WITHOUT their counts being
    zeroed first (masking is folded into the merge itself, saving a full
    pass over the slab).  Deltas are clipped to ``[0, MAX_COLLAPSE_LEVEL]``
    before masking.  Exact for integer-valued counts in any accumulation
    order, so Pallas and XLA paths agree bit-for-bit (contract:
    ``ref.bank_range_merge_ref``).

    Each trace increments ``dispatch_stats()["range_merge_calls"]`` — the
    one-dispatch observability hook the window tests assert on.
    """
    _check_force(force)
    calls = _DISPATCH_STATS["range_merge_calls"]
    calls["bank_range_merge"] = calls.get("bank_range_merge", 0) + 1
    impl = _impl(force, counts.shape[1], row_tile, "bank_range_merge",
                 "query_fallbacks")
    if impl == "ref":
        return bank_range_merge_ref(counts, deltas, spec=spec, valid=valid)
    d = jnp.clip(deltas.astype(jnp.int32), 0, MAX_COLLAPSE_LEVEL)
    if valid is not None:
        # sentinel delta -1 matches no level gate in the kernel, so dead
        # slices drop out with their counts untouched
        d = jnp.where(jnp.asarray(valid).reshape(-1)[:, None] > 0, d, -1)
    return bank_range_merge_pallas(
        counts,
        d,
        spec=spec,
        row_tile=row_tile,
        bucket_tile=bucket_tile,
        interpret=impl == "interpret",
    )
