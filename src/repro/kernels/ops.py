"""Public jit'd entry points for the DDSketch kernels.

``segment_histogram`` (per-row bucket counts of a bank of K sketches),
``bank_histograms`` (both sign stores of an insert, through one
``segment_histogram`` launch), ``fold_pairs`` (the uniform-collapse
resolution fold), ``bank_quantiles`` (the fused bank query) and
``bank_range_merge`` (the fused slice-range merge) dispatch to the compiled
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
  to the reference (sub-tile batch, sub-tile query row axis) is counted in
  ``dispatch_stats()`` and warned once per site, so a served path that left
  the chip's kernels shows it.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.kernels.bank_quantiles import bank_quantiles_pallas
from repro.kernels.bank_range_merge import bank_range_merge_pallas
from repro.kernels.ddsketch_seg_hist import segment_histogram_pallas
from repro.kernels.fold_pairs import fold_pairs_pallas
from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    bank_quantiles_ref,
    bank_range_merge_ref,
    fold_pairs_ref,
    segment_histogram_ref,
)

__all__ = [
    "segment_histogram",
    "fold_pairs",
    "bank_histograms",
    "bank_quantiles",
    "bank_range_merge",
    "dispatch_stats",
    "fallback_count",
    "reset_dispatch_stats",
    "BucketSpec",
]

_FORCE_VALUES = (None, "pallas", "interpret", "ref")

# fallback observability: every auto dispatch that leaves the compiled
# kernel on TPU warns once per (kind, site) and counts here.  Counts are per
# *trace* (the decision is made on static shapes at trace time), so an AOT
# executable that falls back registers once, not once per call.
_DISPATCH_STATS: dict[str, dict[str, int]] = {
    # per-*trace* count of fused range-merge dispatches: the windowed-query
    # acceptance test asserts a W-slice window query registers exactly one
    # (one device program, not W-1 host-looped merges)
    "range_merge_calls": {},
    # query row axis below row_tile (bank_quantiles / bank_range_merge)
    "query_fallbacks": {},
    # ingest batch below one value tile
    "subtile_fallbacks": {},
}
_FALLBACK_KINDS = ("query_fallbacks", "subtile_fallbacks")
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
    test in ``tests/test_seg_kernels.py``.
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


def bank_histograms(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
    force: str | None = None,  # "pallas" | "interpret" | "ref" | None(auto)
    value_tile: int = 2048,
    row_tile: int = 8,
    bucket_tile: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Both sign stores of a bank insert: ``(pos, neg)``, each ``(K, m)``.

    The single entry point behind ``DeviceSketch.add`` / ``SketchBank.add``.
    Sign routing happens here: positives key into rows ``[0, K)`` of a
    stacked ``(2K, m)`` layout, negatives (keyed on ``|x|``) into rows
    ``[K, 2K)``, and every other lane (zero, tiny, non-finite, out-of-range
    id) into no row.  One ``segment_histogram`` launch fills the whole
    layout: the kernel stable-sorts the lanes by row, then runs one step
    per bucket tile and (row tile, value tile) pair that holds a lane, at
    most ``nr + nv - 1`` pairs.  ``segment_ids=None`` is the single-sketch
    case (requires ``num_segments == 1``): every lane belongs to sketch 0,
    so the launch fills rows 0 (positive) and 1 (negative).
    """
    _check_force(force)
    if segment_ids is None and num_segments != 1:
        raise ValueError(
            "segment_ids may be omitted only for a single-row bank "
            f"(num_segments=1), got num_segments={num_segments}"
        )
    x = values.reshape(-1).astype(jnp.float32)
    if segment_ids is None:
        s = jnp.zeros(x.shape, jnp.int32)
    else:
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
