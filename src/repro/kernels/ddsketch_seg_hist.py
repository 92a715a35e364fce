"""Pallas TPU kernel for segmented DDSketch insertion (a bank of K sketches).

The multi-tenant setting of the paper (one sketch per metric key: per
endpoint, per customer, per host) turns Algorithm 1 into a *segmented*
histogram: ``B[seg[v], key(x[v])] += w[v]``.  Because every sketch in the
bank shares the same data-independent bucket geometry, the bank is an
ordinary dense ``(K, m)`` array and one kernel launch fills all K rows.

Per grid step (extends the compare-against-iota one-hot trick): the match
condition is two one-hots, ``(bucket_idx == bucket_ids) & (row == row_ids)``;
instead of materializing the rank-3 ``(TR, TB, TV)`` match tensor, contract
over the value axis with a matmul: ``A[r, v] = w[v] * (row[v] == r)``
(TR, TV) against ``M[v, b] = (idx[v] == b)`` (TV, TB) — an MXU-friendly
product whose products are exact (w * {0,1}).  Bucket indices come from
``ref.level_keys`` in XLA, so kernel and reference bucket every value alike.

Row-sparse grid.  A served tick touches few rows of a tall bank (a POST
carries one key), so the kernel visits only the (row tile, value tile)
pairs that hold a lane:

1. Ahead of the kernel, in XLA, lanes that contribute nothing (non-finite,
   ``<= min_indexable``, out-of-range row) get the sentinel row
   ``rows_padded``, and the lanes are stable-sorted by row, carrying bucket
   index and weight.  Stability keeps each (row, bucket) cell's lanes in
   their original order.
2. Each row tile's lanes are then one contiguous run ``[a, b)`` of the
   sorted stream, covering value tiles ``a // TV .. (b - 1) // TV``.
   ``pair_table`` lists those pairs row-tile-major: exactly the pairs that
   intersect, at most ``touched_row_tiles + nv - 1 <= nr + nv - 1``.
3. Grid = (bucket_tiles, pairs), pairs innermost; the pair count is a
   traced (dynamic) grid bound, so no tick recompiles.  The index maps read
   the table from SMEM (scalar prefetch); consecutive pairs of one row tile
   revisit the same output block, which accumulates in VMEM and is zeroed
   on its first visit.
4. The output aliases a zeroed buffer, so row tiles no pair visits read
   zero.

VMEM budget per step (defaults TV=2048, TR=8, TB=512, f32):
  rows+indices+weights 24 KiB + A (TR,TV) 64 KiB + M (TV,TB) 4 MiB
  + out tile (TR,TB) 16 KiB  << 16 MiB.

Validated in interpret mode against ``ref.segment_histogram_ref`` across
mappings, tile shapes, row layouts and segment counts in
``tests/test_seg_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import BucketSpec, level_keys

__all__ = ["segment_histogram_pallas", "pair_table", "pair_bound"]


def pair_bound(
    num_segments: int, n: int, *, row_tile: int = 8, value_tile: int = 2048
) -> int:
    """Most (row tile, value tile) pairs a launch over ``n`` lanes can visit."""
    nr = -(-num_segments // row_tile)
    nv = max(-(-n // value_tile), 1)
    return nr + nv - 1


def pair_table(
    rows_sorted: jnp.ndarray, *, num_row_tiles: int, row_tile: int, value_tile: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(row_tiles, value_tiles, count)`` of the pairs that hold a lane.

    ``rows_sorted`` is the nondecreasing row of every lane; rows at or past
    ``num_row_tiles * row_tile`` (the sentinel) form no pair.  The two
    ``(nr + nv - 1,)`` tables list the pairs row-tile-major; entries past
    ``count`` repeat the last pair (the first slot names row tile
    ``nr - 1`` when no lane is valid).
    """
    nr = num_row_tiles
    nv = rows_sorted.shape[0] // value_tile
    tile_starts = jnp.arange(nr + 1, dtype=jnp.int32) * row_tile
    bounds = jnp.searchsorted(
        rows_sorted, tile_starts, side="left", method="scan_unrolled"
    )
    a, b = bounds[:-1], bounds[1:]
    first_vt = a // value_tile
    per_tile = jnp.where(b > a, (b - 1) // value_tile - first_vt + 1, 0)
    ends = jnp.cumsum(per_tile)
    count = ends[-1]
    slot = jnp.minimum(jnp.arange(nr + nv - 1, dtype=jnp.int32),
                       jnp.maximum(count - 1, 0))
    rt = jnp.minimum(
        jnp.searchsorted(ends, slot, side="right", method="scan_unrolled"), nr - 1
    )
    vt = jnp.clip(first_vt[rt] + slot - (ends - per_tile)[rt], 0, nv - 1)
    return rt, vt, count


def _seg_hist_kernel(
    rt_ref,
    vt_ref,
    count_ref,
    row_ref,
    idx_ref,
    w_ref,
    zeros_ref,
    out_ref,
    *,
    row_tile: int,
    bucket_tile: int,
):
    del vt_ref, zeros_ref  # read by the index maps / aliased to the output
    j = pl.program_id(0)  # bucket-tile index
    p = pl.program_id(1)  # pair index (sequential reduction per row tile)
    rt = rt_ref[p]

    @pl.when((p == 0) | (rt != rt_ref[jnp.maximum(p - 1, 0)]))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(p < count_ref[0])
    def _accumulate():
        row = row_ref[...]  # (1, TV) int32, sentinel past the bank
        idx = idx_ref[...]  # (1, TV) int32 bucket index
        w = w_ref[...]  # (1, TV) float32
        tv = row.shape[1]
        # A[r, v] = w[v] if row[v] == global row r else 0        (TR, TV)
        rows = (
            jax.lax.broadcasted_iota(jnp.int32, (row_tile, tv), 0) + rt * row_tile
        )
        a = jnp.where(row == rows, w, 0.0)
        # M[v, b] = 1 if idx[v] == global bucket b else 0        (TV, TB)
        cols = (
            jax.lax.broadcasted_iota(jnp.int32, (tv, bucket_tile), 1)
            + j * bucket_tile
        )
        m = (idx.reshape(tv, 1) == cols).astype(jnp.float32)
        # contract over the value axis; products are w * {0,1} so the sum is
        # a plain weight accumulation — HIGHEST precision keeps f32 on the MXU.
        out_ref[...] += jax.lax.dot_general(
            a,
            m,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments",
        "spec",
        "value_tile",
        "row_tile",
        "bucket_tile",
        "interpret",
    ),
)
def segment_histogram_pallas(
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
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-segment bucket counts ``(num_segments, m)`` in one launch.

    Matches ``ref.segment_histogram_ref`` exactly for integer weights (same
    masking, same float32 index math); non-positive / non-finite values and
    out-of-range segment ids contribute nothing.  ``num_segments`` is padded
    up to a ``row_tile`` multiple and the bucket axis up to a
    ``bucket_tile`` multiple internally (pad buckets match no index, so they
    stay zero); both pads are dropped before returning.  ``levels`` holds
    *per-value* int32 collapse levels (callers with per-row levels gather
    ``row_levels[segment_ids]`` once outside); omitted it defaults to level
    0, matching the uncollapsed indexing.  Work is one grid step per
    (bucket tile, visited pair), at most ``pair_bound`` pairs.
    """
    if values.size != segment_ids.size:
        raise ValueError(
            f"values ({values.size} elements) and segment_ids "
            f"({segment_ids.size} elements) must have the same size"
        )
    if values.size == 0:  # zero-length value grid would skip the tile init
        return jnp.zeros((num_segments, spec.num_buckets), jnp.float32)
    x = values.reshape(-1).astype(jnp.float32)
    s = segment_ids.reshape(-1).astype(jnp.int32)
    w = (
        jnp.ones_like(x)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    lev = (
        jnp.zeros_like(s)
        if levels is None
        else levels.reshape(-1).astype(jnp.int32)
    )
    n = x.shape[0]
    pad = (-n) % value_tile
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=-1.0)  # masked below
        s = jnp.pad(s, (0, pad), constant_values=-1)
        w = jnp.pad(w, (0, pad), constant_values=0.0)
        lev = jnp.pad(lev, (0, pad), constant_values=0)
    rows_padded = num_segments + ((-num_segments) % row_tile)
    buckets_padded = spec.num_buckets + ((-spec.num_buckets) % bucket_tile)
    nr = rows_padded // row_tile
    nb = buckets_padded // bucket_tile

    mask = (
        jnp.isfinite(x)
        & (x > spec.min_indexable)
        & (s >= 0)
        & (s < num_segments)
    )
    row = jnp.where(mask, s, rows_padded)  # sentinel: past every row tile
    # XLA's log, as on the reference tier
    idx = jnp.clip(level_keys(x, lev, spec) - spec.offset, 0, spec.num_buckets - 1)
    row, idx, w = jax.lax.sort((row, idx, w), num_keys=1, is_stable=True)
    rt, vt, count = pair_table(
        row, num_row_tiles=nr, row_tile=row_tile, value_tile=value_tile
    )
    steps = jnp.maximum(count, 1)  # one step zeroes a block when no lane is valid
    # lanes ride as one (1, N) row so each (1, TV) block is tile-aligned
    row, idx, w = (a.reshape(1, -1) for a in (row, idx, w))
    lane_block = pl.BlockSpec(
        (1, value_tile), lambda j, p, rt_t, vt_t, c: (0, vt_t[p])
    )

    out = pl.pallas_call(
        functools.partial(
            _seg_hist_kernel, row_tile=row_tile, bucket_tile=bucket_tile
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb, steps),
            in_specs=[lane_block, lane_block, lane_block,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(
                (row_tile, bucket_tile), lambda j, p, rt_t, vt_t, c: (rt_t[p], j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((rows_padded, buckets_padded), jnp.float32),
        input_output_aliases={6: 0},  # untouched row tiles keep the zeros
        interpret=interpret,
    )(
        rt,
        vt,
        count.reshape(1),
        row,
        idx,
        w,
        jnp.zeros((rows_padded, buckets_padded), jnp.float32),
    )
    return out[:num_segments, : spec.num_buckets]
