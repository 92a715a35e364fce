"""Pallas TPU kernel for the fused slice-range merge (windowed quantiles).

A window query over slices [i, j) of a ``(S, K, m)`` bank-ring slab must
(1) reconcile every slice row to the range's per-row max collapse level —
fold row (d, r) by ``delta[d, r] = target[r] - level[d, r]`` levels — and
(2) sum the slice axis per bucket.  Done naively that is W-1 host-looped
``merge`` dispatches (each a full collapse_to + add); here it is ONE
program over the stacked ``(D, R, m)`` counts.

Formulation: ``shift_key`` nests (ceil(ceil(k/2)/2) == ceil(k/4)), so a
``delta``-level fold is a single one-hot matrix ``F_delta[i, b] =
(ceil((offset + i)/2**delta) - offset == b)`` — the same
compare-against-iota MXU trick as ``fold_pairs``, with the fold matrix per
delta built from iotas in-kernel (never materialized in HBM).  The slice
axis D is the innermost *sequential* grid dimension: each (row-tile,
bucket-tile) output block is visited D times and accumulates

    out[r, b] += sum_delta  (delta[d, r] == delta) * (counts[d, r] @ F_delta)[b]

with the delta == 0 term taken as a direct column slice (no matmul).  The
products are counts * {0, 1}, so every accumulation is an exact f32 sum of
integer-valued counts — bit-identical to ``ref.bank_range_merge_ref`` and
to sequential ``sketch_bank.merge`` folds.

Grid = (row_tiles, bucket_tiles, D); block shapes: counts ``(1, TR, m)``
for the folds plus the same counts as a ``(1, TR, TB)`` block for the
identity term (a lane-offset slice of a loaded value does not lower on
Mosaic; a second block spec lets the DMA deliver it), deltas
``(1, TR, 1)``, out ``(TR, TB)`` revisited across d.

VMEM budget per step (defaults TR=8, TB=512, m=2048, f32):
  counts (TR, m) 64 KiB + F (m, TB) 4 MiB + out tile 16 KiB << 16 MiB.

Validated in interpret mode against ``ref.bank_range_merge_ref`` across
mappings, offsets, and tile shapes in ``tests/test_window_ring.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import (
    MAX_COLLAPSE_LEVEL,
    BucketSpec,
    fold_destination_range,
)

__all__ = ["bank_range_merge_pallas"]


def _range_merge_kernel(
    counts_ref, tile_ref, deltas_ref, out_ref, *, offset: int, bucket_tile: int
):
    j = pl.program_id(1)  # bucket-tile index (parallel)
    d = pl.program_id(2)  # slice index (sequential; accumulates into out)

    x = counts_ref[0]  # (TR, m) float32
    delta = deltas_ref[0]  # (TR, 1) int32 per-row fold depth of this slice
    m = x.shape[1]

    # delta == 0 contribution: identity fold — the output tile's own
    # columns, delivered as a second (TR, TB) block of the same counts
    acc = jnp.where(delta == 0, tile_ref[0], 0.0)
    # delta >= 1 contributions: one one-hot fold matrix per level, built
    # from iotas (same exact int math as ref.multi_fold_destinations)
    src = jax.lax.broadcasted_iota(jnp.int32, (m, bucket_tile), 0)
    cols = (
        jax.lax.broadcasted_iota(jnp.int32, (m, bucket_tile), 1)
        + j * bucket_tile
    )
    for lev in range(1, MAX_COLLAPSE_LEVEL + 1):
        dst = -((-(offset + src)) >> lev) - offset  # ceil(k/2**lev) - offset
        f = (dst == cols).astype(jnp.float32)  # (m, TB) one-hot fold matrix
        folded = jax.lax.dot_general(
            x,
            f,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        acc = acc + jnp.where(delta == lev, folded, 0.0)

    @pl.when(d == 0)
    def _init():
        out_ref[...] = acc

    @pl.when(d > 0)
    def _accumulate():
        out_ref[...] = out_ref[...] + acc


@functools.partial(
    jax.jit, static_argnames=("spec", "row_tile", "bucket_tile", "interpret")
)
def bank_range_merge_pallas(
    counts: jnp.ndarray,
    deltas: jnp.ndarray,
    *,
    spec: BucketSpec,
    row_tile: int = 8,
    bucket_tile: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused slice-range merge: ``counts (D, R, m), deltas (D, R) -> (R, m)``.

    Matches ``ref.bank_range_merge_ref`` bit-for-bit.  Rows are padded up
    to a ``row_tile`` multiple internally (pad rows: zero counts, delta 0)
    and dropped before returning; deltas are clipped to
    ``<= MAX_COLLAPSE_LEVEL`` only — a negative delta is the dead-slice
    sentinel and matches none of the kernel's per-level gates, so that
    slice contributes nothing without its counts being zeroed.
    """
    fold_destination_range(spec)  # static geometry check
    m = spec.num_buckets
    if spec.num_buckets % bucket_tile:
        raise ValueError(
            f"num_buckets={spec.num_buckets} must be a multiple of "
            f"bucket_tile={bucket_tile}"
        )
    if counts.ndim != 3 or counts.shape[2] != m:
        raise ValueError(f"counts must be (D, R, {m}), got {counts.shape}")
    num_slices, r = counts.shape[:2]
    if deltas.shape != (num_slices, r):
        raise ValueError(
            f"deltas must be {(num_slices, r)}, got {deltas.shape}"
        )
    x = counts.astype(jnp.float32)
    dl = jnp.minimum(deltas.astype(jnp.int32), MAX_COLLAPSE_LEVEL)
    rows_padded = r + ((-r) % row_tile)
    if rows_padded != r:
        x = jnp.pad(x, ((0, 0), (0, rows_padded - r), (0, 0)))
        dl = jnp.pad(dl, ((0, 0), (0, rows_padded - r)))
    dl = dl[:, :, None]  # (D, Rp, 1): per-row scalars ride as a lane block
    nr = rows_padded // row_tile
    nb = m // bucket_tile

    out = pl.pallas_call(
        functools.partial(
            _range_merge_kernel, offset=spec.offset, bucket_tile=bucket_tile
        ),
        grid=(nr, nb, num_slices),
        in_specs=[
            pl.BlockSpec((1, row_tile, m), lambda i, j, d: (d, i, 0)),
            pl.BlockSpec((1, row_tile, bucket_tile), lambda i, j, d: (d, i, j)),
            pl.BlockSpec((1, row_tile, 1), lambda i, j, d: (d, i, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, bucket_tile), lambda i, j, d: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, m), jnp.float32),
        interpret=interpret,
    )(x, x, dl)
    return out[:r]
