"""Pallas TPU kernel for DDSketch insertion (Algorithm 1, batched).

The paper's hot loop is ``B[ceil(log_gamma(x))] += 1`` per value.  On CPU the
reference implementations do a scalar log + hash-map increment; neither maps
to a TPU (no fast random scatter; scalar loops waste the VPU).  The
TPU-native formulation (DESIGN.md §3):

* the *mapping* is evaluated vectorized — either a true log ("log"
  mapping) or the paper's §2.2 "costless log2 from the float's binary
  representation" trick, which lowers to integer bitcast/shift/mask ops
  ("linear"/"cubic" mappings, the DDSketch-fast variants).  It runs as one
  XLA elementwise pass (``ref.level_keys``) feeding the kernel int32 keys,
  so the kernel buckets with the reference's own log;
* the *scatter* becomes a compare-against-iota one-hot reduction: a
  (bucket_tile, value_tile) boolean match matrix is contracted against the
  weights along the value axis.  Everything stays in VMEM/VREGs.

Grid = (bucket_tiles, value_tiles); the value axis is the innermost
(sequential reduction) dimension, so each output tile is revisited on
consecutive steps and accumulated in place, while value/weight tiles stream
through VMEM once per bucket tile.

VMEM budget per step (defaults TV=2048, TB=512, f32):
  values 8 KiB + weights 8 KiB + match matrix 4 MiB + out tile 2 KiB << 16 MiB.

Validated in interpret mode against ``repro.kernels.ref.histogram_ref``
(bit-identical float32 index math) across shapes/dtypes/mappings in
``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import BucketSpec, level_keys

__all__ = ["histogram_pallas"]


def _hist_kernel(
    vals_ref, w_ref, key_ref, out_ref, *, spec: BucketSpec, bucket_tile: int
):
    i = pl.program_id(0)  # bucket-tile index (parallel)
    j = pl.program_id(1)  # value-tile index (sequential reduction)

    x = vals_ref[...]  # (1, TV) float32
    w = w_ref[...]  # (1, TV) float32
    k0 = key_ref[...]  # (1, TV) int32 collapse-level keys (ref.level_keys)

    mask = jnp.isfinite(x) & (x > spec.min_indexable)
    idx = jnp.clip(k0 - spec.offset, 0, spec.num_buckets - 1)
    w = jnp.where(mask, w, 0.0)

    # one-hot match: bucket ids for this tile as rows, values as lanes
    tv = x.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bucket_tile, tv), 0)
    bucket_ids = rows + i * bucket_tile
    match = idx == bucket_ids  # (1,TV) vs (TB,TV) -> (TB,TV)
    partial = jnp.sum(jnp.where(match, w, 0.0), axis=1, keepdims=True)  # (TB, 1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("spec", "value_tile", "bucket_tile", "interpret")
)
def histogram_pallas(
    values: jnp.ndarray,
    weights: jnp.ndarray | None = None,
    levels: jnp.ndarray | None = None,
    *,
    spec: BucketSpec,
    value_tile: int = 2048,
    bucket_tile: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Bucket-count vector (m,) for the positive finite entries of ``values``.

    Matches ``ref.histogram_ref`` exactly (same masking, same float32 index
    math); non-positive / non-finite entries contribute nothing.  ``levels``
    holds per-value collapse levels (int32, same size as ``values``); omitted
    it defaults to level 0, reproducing the uncollapsed indexing bit-for-bit.
    """
    if spec.num_buckets % bucket_tile:
        raise ValueError(
            f"num_buckets={spec.num_buckets} must be a multiple of "
            f"bucket_tile={bucket_tile}"
        )
    if values.size == 0:  # zero-length value grid would skip the tile init
        return jnp.zeros(spec.num_buckets, jnp.float32)
    x = values.reshape(-1).astype(jnp.float32)
    w = (
        jnp.ones_like(x)
        if weights is None
        else weights.reshape(-1).astype(jnp.float32)
    )
    lev = (
        jnp.zeros_like(x, dtype=jnp.int32)
        if levels is None
        else levels.reshape(-1).astype(jnp.int32)
    )
    n = x.shape[0]
    pad = (-n) % value_tile
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=-1.0)  # masked out in-kernel
        w = jnp.pad(w, (0, pad), constant_values=0.0)
        lev = jnp.pad(lev, (0, pad), constant_values=0)
    nv = x.shape[0] // value_tile
    nb = spec.num_buckets // bucket_tile
    # lanes ride as one (1, N) row so each (1, TV) block is tile-aligned
    kl = level_keys(x, lev, spec)  # XLA's log, as on the reference tier
    x, w, kl = (a.reshape(1, -1) for a in (x, w, kl))

    out = pl.pallas_call(
        functools.partial(_hist_kernel, spec=spec, bucket_tile=bucket_tile),
        grid=(nb, nv),
        in_specs=[
            pl.BlockSpec((1, value_tile), lambda i, j: (0, j)),
            pl.BlockSpec((1, value_tile), lambda i, j: (0, j)),
            pl.BlockSpec((1, value_tile), lambda i, j: (0, j)),
        ],
        # buckets on the sublane axis: each step's (TB, 1) column is the
        # lane reduction's natural layout, no lane<->sublane relayout
        out_specs=pl.BlockSpec((bucket_tile, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((spec.num_buckets, 1), jnp.float32),
        interpret=interpret,
    )(x, w, kl)
    return out.reshape(spec.num_buckets)
