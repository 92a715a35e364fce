"""Pallas TPU kernel for segmented DDSketch insertion (a bank of K sketches).

The multi-tenant setting of the paper (one sketch per metric key: per
endpoint, per customer, per host) turns Algorithm 1 into a *segmented*
histogram: ``B[seg[v], key(x[v])] += w[v]``.  Because every sketch in the
bank shares the same data-independent bucket geometry, the bank is an
ordinary dense ``(K, m)`` array and one kernel launch fills all K rows in a
single pass over the values — the batched analogue of ``ddsketch_hist``.

Formulation (extends the compare-against-iota one-hot trick):

* per value lane, compute the bucket index exactly as ``ref.bucket_index``
  (same float32 math, so host/device/kernel agree bit-for-bit);
* the match condition becomes two one-hots,
  ``(bucket_idx == bucket_ids) & (segment_id == row_ids)``; instead of
  materializing the rank-3 ``(TR, TB, TV)`` match tensor, contract over the
  value axis with a matmul: ``A[r, v] = w[v] * (seg[v] == r)`` (TR, TV)
  against ``M[v, b] = (idx[v] == b)`` (TV, TB) — an MXU-friendly
  (TR, TV) x (TV, TB) product whose products are exact (w * {0,1}).

Grid = (row_tiles, bucket_tiles, value_tiles); the value axis is innermost
(sequential reduction), so each (row, bucket) output tile is revisited on
consecutive steps and accumulated in place in VMEM while value/weight/id
tiles stream through once per output tile.

VMEM budget per step (defaults TV=2048, TR=8, TB=512, f32):
  values+weights+ids 24 KiB + A (TR,TV) 64 KiB + M (TV,TB) 4 MiB
  + out tile (TR,TB) 16 KiB  << 16 MiB.

Validated in interpret mode against ``ref.segment_histogram_ref`` across
mappings, tile shapes, and segment counts in ``tests/test_seg_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import BucketSpec, level_keys

__all__ = ["segment_histogram_pallas"]


def _seg_hist_kernel(
    vals_ref,
    w_ref,
    seg_ref,
    key_ref,
    out_ref,
    *,
    spec: BucketSpec,
    row_tile: int,
    bucket_tile: int,
    num_segments: int,
):
    i = pl.program_id(0)  # row-tile index (parallel)
    j = pl.program_id(1)  # bucket-tile index (parallel)
    k = pl.program_id(2)  # value-tile index (sequential reduction)

    x = vals_ref[...]  # (1, TV) float32
    w = w_ref[...]  # (1, TV) float32
    seg = seg_ref[...]  # (1, TV) int32
    k0 = key_ref[...]  # (1, TV) int32 collapse-level keys (ref.level_keys)

    mask = (
        jnp.isfinite(x)
        & (x > spec.min_indexable)
        & (seg >= 0)
        & (seg < num_segments)
    )
    idx = jnp.clip(k0 - spec.offset, 0, spec.num_buckets - 1)
    w = jnp.where(mask, w, 0.0)

    tv = x.shape[1]
    # A[r, v] = w[v] if seg[v] == global row r else 0        (TR, TV)
    rows = jax.lax.broadcasted_iota(jnp.int32, (row_tile, tv), 0) + i * row_tile
    a = jnp.where(seg == rows, w, 0.0)
    # M[v, b] = 1 if idx[v] == global bucket b else 0        (TV, TB)
    cols = (
        jax.lax.broadcasted_iota(jnp.int32, (tv, bucket_tile), 1)
        + j * bucket_tile
    )
    m = (idx.reshape(tv, 1) == cols).astype(jnp.float32)
    # contract over the value axis; products are w * {0,1} so the sum is a
    # plain weight accumulation — HIGHEST precision keeps f32 on the MXU.
    partial = jax.lax.dot_general(
        a,
        m,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


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

    Matches ``ref.segment_histogram_ref`` exactly (same masking, same
    float32 index math); non-positive / non-finite values and out-of-range
    segment ids contribute nothing.  ``num_segments`` is padded up to a
    ``row_tile`` multiple and the bucket axis up to a ``bucket_tile``
    multiple internally (pad buckets match no index, so they stay zero);
    both pads are dropped before returning.  ``levels`` holds *per-value*
    int32 collapse levels (callers with per-row levels gather
    ``row_levels[segment_ids]`` once outside); omitted it defaults to level
    0, matching the uncollapsed indexing.
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
        x = jnp.pad(x, (0, pad), constant_values=-1.0)  # masked out in-kernel
        s = jnp.pad(s, (0, pad), constant_values=-1)
        w = jnp.pad(w, (0, pad), constant_values=0.0)
        lev = jnp.pad(lev, (0, pad), constant_values=0)
    rows_padded = num_segments + ((-num_segments) % row_tile)
    buckets_padded = spec.num_buckets + ((-spec.num_buckets) % bucket_tile)
    nv = x.shape[0] // value_tile
    nr = rows_padded // row_tile
    nb = buckets_padded // bucket_tile
    # lanes ride as one (1, N) row so each (1, TV) block is tile-aligned
    kl = level_keys(x, lev, spec)  # XLA's log, as on the reference tier
    x, s, w, kl = (a.reshape(1, -1) for a in (x, s, w, kl))

    out = pl.pallas_call(
        functools.partial(
            _seg_hist_kernel,
            spec=spec,
            row_tile=row_tile,
            bucket_tile=bucket_tile,
            num_segments=num_segments,
        ),
        grid=(nr, nb, nv),
        in_specs=[
            pl.BlockSpec((1, value_tile), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, value_tile), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, value_tile), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, value_tile), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((row_tile, bucket_tile), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, buckets_padded), jnp.float32),
        interpret=interpret,
    )(x, w, s, kl)
    return out[:num_segments, : spec.num_buckets]
