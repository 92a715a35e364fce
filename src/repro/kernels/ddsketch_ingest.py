"""Pallas TPU kernel for the fused single-dispatch bank ingest.

The sort–reduce–scatter pipeline runs as three device programs (XLA
bucketize -> XLA sort/segment-sum -> ``ddsketch_scatter`` kernel) with full
HBM round trips of the (N,)-sized intermediates between stages, and then
``sketch_bank.add_impl`` makes a *second* pass over the lanes for the aux
stats (zero / overflow / underflow / sum / extrema).  This kernel collapses
the whole ingest into **one** program: each value tile, with its int32
collapse-level keys (``ref.level_keys``: the reference's own XLA log and
``shift_key``, one elementwise pass ahead of the kernel), is binned into the
combined ``(2K, m)`` pos/neg layout with the input-stationary resident-row
trick of ``ddsketch_scatter`` and folded into the six per-row aux stats —
no sort stage and no second stats pass over the lanes.

Layout: grid = (bucket_tiles, value_tiles), value axis innermost
(sequential reduction).  Three outputs:

* ``hist`` ``(2K_pad, bucket_tile)`` block at ``(0, j)`` — the full bank
  row axis stays resident in VMEM (``MAX_RESIDENT_ROWS`` guard as in
  ``ddsketch_scatter``); per step the sign-routed row one-hot
  ``A[r, v] = w[v] * (row(v) == r)`` contracts against the bucket one-hot
  ``M[v, b]`` on the MXU.
* ``sums`` ``(8, K_pad)`` at ``(0, 0)`` — rows 0..3 hold zero / overflow /
  underflow / summ; accumulated additively via an ``(8, TV) x (TV, K_pad)``
  one-hot matmul, only on the ``j == 0`` sweep so each lane is counted once.
* ``ext`` ``(8, K_pad)`` at ``(0, 0)`` — rows 0..1 hold ``min(x)`` and
  ``min(-x)`` (``vmax = -min(-x)``); accumulated with ``minimum`` over the
  sublane-axis reduction of the masked ``(TV, K_pad)`` broadcast, again only
  on ``j == 0``.

VMEM: at the worst case 2K = 1024 resident rows (K_pad = 512) and TB=512,
Mosaic's scoped allocation for one step is 4.0 MiB at the default TV=512
and 22.2 MiB at TV=1024 (compiled for a described v5e), against the
16 MiB default scoped limit — hence ``value_tile`` 512.  The lanes ride as
one (1, N) row so each (1, TV) block is tile-aligned, and the extrema's
"contributes" mask is formed after the lane->sublane reshape of the f32
weights (Mosaic cannot relayout a bool lane vector).

Counter outputs (sums of ``w * {0, 1}``) and extrema match
``ref.fused_ingest_ref`` exactly; the float ``summ`` row accumulates in
matmul/tile order instead of lane order, so it may differ from the ref in
final ulps (same caveat as the dense-stats path).  Validated in interpret
mode in ``tests/test_fused_ingest.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ddsketch_scatter import MAX_RESIDENT_ROWS
from repro.kernels.ref import BucketSpec, IngestStats, level_keys

__all__ = ["ddsketch_ingest_pallas"]


def _ingest_kernel(
    vals_ref,
    w_ref,
    seg_ref,
    key_ref,
    hist_ref,
    sums_ref,
    ext_ref,
    *,
    spec: BucketSpec,
    num_segments: int,
    bucket_tile: int,
):
    j = pl.program_id(0)  # bucket-tile index (parallel)
    v = pl.program_id(1)  # value-tile index (sequential reduction)

    x = vals_ref[...]  # (1, TV) float32
    w = w_ref[...]  # (1, TV) float32
    seg = seg_ref[...]  # (1, TV) int32
    k_lev = key_ref[...]  # (1, TV) int32 collapse-level keys (ref.level_keys)

    k = num_segments
    valid = jnp.isfinite(x) & (seg >= 0) & (seg < k)
    w = jnp.where(valid, w, 0.0)
    sc = jnp.clip(seg, 0, k - 1)
    is_pos = valid & (x > spec.min_indexable)
    is_neg = valid & (x < -spec.min_indexable)
    is_zero = valid & ~is_pos & ~is_neg

    # the keys feed the histogram index AND the clamp accounting
    idx = jnp.clip(k_lev - spec.offset, 0, spec.num_buckets - 1)
    top_key = spec.offset + spec.num_buckets - 1
    over = (is_pos | is_neg) & (k_lev > top_key)
    under = (is_pos | is_neg) & (k_lev < spec.offset)

    tv = x.shape[1]
    rows_resident = hist_ref.shape[0]
    # sign routing into the combined (2K, m) layout: positives in rows
    # [0, K), negatives in [K, 2K)
    r = sc + jnp.where(is_neg, k, 0)
    wh = jnp.where(is_pos | is_neg, w, 0.0)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows_resident, tv), 0)
    a = jnp.where(r == rr, wh, 0.0)
    cols = (
        jax.lax.broadcasted_iota(jnp.int32, (tv, bucket_tile), 1)
        + j * bucket_tile
    )
    m1 = (idx.reshape(tv, 1) == cols).astype(jnp.float32)
    partial = jax.lax.dot_general(
        a,
        m1,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(v == 0)
    def _init_hist():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    hist_ref[...] += partial

    @pl.when((j == 0) & (v == 0))
    def _init_stats():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        ext_ref[...] = jnp.full_like(ext_ref, jnp.inf)

    # stats only on the first bucket sweep: every lane counted exactly once
    @pl.when(j == 0)
    def _stats():
        kp = sums_ref.shape[1]
        kcols = jax.lax.broadcasted_iota(jnp.int32, (tv, kp), 1)
        sel = sc.reshape(tv, 1) == kcols  # (TV, KP) segment one-hot
        wx = w * jnp.where(valid, x, 0.0)
        zeros = jnp.zeros_like(x)
        data = jnp.concatenate(
            [w * is_zero, w * over, w * under, wx, zeros, zeros, zeros, zeros],
            axis=0,
        )  # (8, TV): zero / overflow / underflow / summ + sublane pad
        sums_ref[...] += jax.lax.dot_general(
            data,
            sel.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        # w is already zero off the valid lanes, so w > 0 is the whole
        # "contributes" test; reshape the f32 lanes to a column and compare
        # there (Mosaic cannot relayout a bool lane vector to sublanes)
        xc = x.reshape(tv, 1)
        contrib = sel & (w.reshape(tv, 1) > 0.0)
        xin = jnp.where(contrib, xc, jnp.inf)
        nxin = jnp.where(contrib, -xc, jnp.inf)
        vmin_p = jnp.min(xin, axis=0, keepdims=True)  # (1, KP)
        nmax_p = jnp.min(nxin, axis=0, keepdims=True)  # (1, KP): -vmax
        inf_row = jnp.full_like(vmin_p, jnp.inf)
        ext = jnp.concatenate(
            [vmin_p, nmax_p, inf_row, inf_row, inf_row, inf_row, inf_row,
             inf_row],
            axis=0,
        )  # (8, KP)
        ext_ref[...] = jnp.minimum(ext_ref[...], ext)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments",
        "spec",
        "value_tile",
        "bucket_tile",
        "interpret",
    ),
)
def ddsketch_ingest_pallas(
    values: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    num_segments: int,
    spec: BucketSpec,
    value_tile: int = 512,
    bucket_tile: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, IngestStats]:
    """Fused bank ingest: ``(hist (2K, m), IngestStats)`` in ONE dispatch.

    Matches ``ref.fused_ingest_ref`` (counters and extrema exactly; the
    float ``summ`` up to accumulation order).  ``num_segments`` doubled (the
    combined pos/neg row axis) must fit the resident-row ceiling; the ops
    front door falls back to the reference beyond it.  The row axis is
    padded to the sublane minimum, the bucket axis to a ``bucket_tile``
    multiple, the segment axis of the stats tiles to a lane multiple, and
    the lanes to a ``value_tile`` multiple with inert fills (NaN value /
    id -1 / weight 0 / level 0); all pads are sliced off before returning.
    """
    if 2 * num_segments > MAX_RESIDENT_ROWS:
        raise ValueError(
            f"2 * num_segments = {2 * num_segments} exceeds "
            f"MAX_RESIDENT_ROWS={MAX_RESIDENT_ROWS}; the fused ingest kernel "
            "keeps the combined pos/neg row axis resident in VMEM — use the "
            "sort or matmul pipeline for banks this tall"
        )
    k = num_segments
    x = values.reshape(-1).astype(jnp.float32)
    s = (
        jnp.zeros(x.shape, jnp.int32)
        if segment_ids is None
        else segment_ids.reshape(-1).astype(jnp.int32)
    )
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
    if x.size != s.size or x.size != w.size or x.size != lev.size:
        raise ValueError(
            f"values ({x.size}), segment_ids ({s.size}), weights ({w.size}) "
            f"and levels ({lev.size}) must have the same size"
        )
    empty_stats = IngestStats(
        zero=jnp.zeros(k, jnp.float32),
        overflow=jnp.zeros(k, jnp.float32),
        underflow=jnp.zeros(k, jnp.float32),
        summ=jnp.zeros(k, jnp.float32),
        vmin=jnp.full(k, jnp.inf, jnp.float32),
        vmax=jnp.full(k, -jnp.inf, jnp.float32),
    )
    if x.size == 0:  # zero-length value grid would skip the tile inits
        return jnp.zeros((2 * k, spec.num_buckets), jnp.float32), empty_stats
    n = x.shape[0]
    pad = (-n) % value_tile
    if pad:  # inert lanes: NaN value / id -1 / weight 0 contribute nothing
        x = jnp.pad(x, (0, pad), constant_values=jnp.nan)
        s = jnp.pad(s, (0, pad), constant_values=-1)
        w = jnp.pad(w, (0, pad), constant_values=0.0)
        lev = jnp.pad(lev, (0, pad), constant_values=0)
    rows_padded = 2 * k + ((-2 * k) % 8)
    buckets_padded = spec.num_buckets + ((-spec.num_buckets) % bucket_tile)
    k_padded = k + ((-k) % 128)  # stats lane axis
    nv = x.shape[0] // value_tile
    nb = buckets_padded // bucket_tile
    # lanes ride as one (1, N) row: a (1, TV) block spans the whole
    # second-minor dimension, the layout Mosaic tiles without padding rows
    kl = level_keys(x, lev, spec)  # XLA's log, as on the reference tier
    x, s, w, kl = (a.reshape(1, -1) for a in (x, s, w, kl))

    hist, sums, ext = pl.pallas_call(
        functools.partial(
            _ingest_kernel,
            spec=spec,
            num_segments=k,
            bucket_tile=bucket_tile,
        ),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((1, value_tile), lambda j, v: (0, v)),
            pl.BlockSpec((1, value_tile), lambda j, v: (0, v)),
            pl.BlockSpec((1, value_tile), lambda j, v: (0, v)),
            pl.BlockSpec((1, value_tile), lambda j, v: (0, v)),
        ],
        out_specs=[
            pl.BlockSpec((rows_padded, bucket_tile), lambda j, v: (0, j)),
            pl.BlockSpec((8, k_padded), lambda j, v: (0, 0)),
            pl.BlockSpec((8, k_padded), lambda j, v: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_padded, buckets_padded), jnp.float32),
            jax.ShapeDtypeStruct((8, k_padded), jnp.float32),
            jax.ShapeDtypeStruct((8, k_padded), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, s, kl)
    stats = IngestStats(
        zero=sums[0, :k],
        overflow=sums[1, :k],
        underflow=sums[2, :k],
        summ=sums[3, :k],
        vmin=ext[0, :k],
        vmax=-ext[1, :k],
    )
    return hist[: 2 * k, : spec.num_buckets], stats
