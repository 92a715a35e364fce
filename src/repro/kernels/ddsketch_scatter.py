"""Pallas TPU kernel for the scatter stage of the sort–reduce–scatter ingest.

The single-sketch matmul-histogram kernel streams the *full* value array
once per bucket tile, and the segmented one once per (row tile, value tile)
pair that holds a lane, so both pay for every lane.  The ingest pipeline (``ref.compact_triples``) first sorts composite
``sign_base + seg*m + bucket`` keys and reduces duplicate runs, so only
U <= min(N, 2·K·m) unique ``(key, weight)`` triples reach the device — the
post-collapse regime UDDSketch observes (streams concentrate into a few
hundred live buckets) makes U tiny relative to N.

This kernel is the back end: accumulate the compacted triples into the
combined ``(2K, m)`` pos/neg bucket layout.  TPUs have no fast random
scatter, so the add is the same compare-against-iota trick as the histogram
kernels — but *input-stationary*: the grid runs over (bucket_tiles,
triple_tiles) only, the full bank row axis stays resident in the output
tile's sublane dimension, and each triple tile is streamed once per bucket
tile instead of once per (row, bucket) tile.  Per step, the decomposed rows
build ``A[r, t] = w[t] * (row(t) == r)`` (R, TT) against the bucket one-hot
``M[t, b] = (bucket(t) == b)`` (TT, TB); the MXU contraction accumulates the
(R, TB) output tile in place.

Because the rows are not tiled, ``rows_padded * bucket_tile`` floats must
fit in VMEM next to A and M — fine for the telemetry-bank regime (2K <=
1024 rows); the ops dispatcher falls back to the matmul-histogram kernel
beyond that.

VMEM: at the worst case R = 2K = 1024 resident rows and TB=512, Mosaic's
scoped allocation for one step is 4.0 MiB at the default TT=512, 22.0 MiB
at TT=1024 and 38.6 MiB at TT=2048 (compiled for a described v5e) — the
default scoped limit is 16 MiB, so the triple tile stays at 512.  The
(1, N) lane layout keeps each (1, TT) block tile-aligned.

With unique keys (what ``compact_triples`` emits) every output bucket
receives one real add plus zeros, so the kernel matches
``ref.scatter_histogram_ref`` bit-for-bit; with duplicate keys it still
accumulates exactly for integer-valued weights.  Validated in interpret
mode in ``tests/test_sort_scatter.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["MAX_RESIDENT_ROWS", "ddsketch_scatter_pallas"]

# Row ceiling of the resident (rows, bucket_tile) output tile: at 1024 rows
# and the default 512-lane triple / value tiles, this kernel and the fused
# ingest each take ~4 MiB of scoped VMEM on a v5e (16 MiB default limit).
MAX_RESIDENT_ROWS = 1024


def _scatter_kernel(
    keys_ref,
    w_ref,
    out_ref,
    *,
    num_rows: int,
    num_buckets: int,
    bucket_tile: int,
):
    j = pl.program_id(0)  # bucket-tile index (parallel)
    t = pl.program_id(1)  # triple-tile index (sequential reduction)

    k = keys_ref[...]  # (1, TT) int32 composite keys
    w = w_ref[...]  # (1, TT) float32 run weights

    valid = (k >= 0) & (k < num_rows * num_buckets)
    kk = jnp.where(valid, k, 0)
    r = kk // num_buckets  # combined pos/neg row in [0, 2K)
    b = kk - r * num_buckets  # bucket in [0, m)
    w = jnp.where(valid, w, 0.0)

    tt = k.shape[1]
    rows_resident = out_ref.shape[0]
    # A[rr, t] = w[t] if triple t lands in resident row rr        (R, TT)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows_resident, tt), 0)
    a = jnp.where(r == rr, w, 0.0)
    # M[t, bb] = 1 if triple t lands in global bucket bb          (TT, TB)
    cols = (
        jax.lax.broadcasted_iota(jnp.int32, (tt, bucket_tile), 1)
        + j * bucket_tile
    )
    m = (b.reshape(tt, 1) == cols).astype(jnp.float32)
    partial = jax.lax.dot_general(
        a,
        m,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_rows",
        "num_buckets",
        "triple_tile",
        "bucket_tile",
        "interpret",
    ),
)
def ddsketch_scatter_pallas(
    keys: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    num_rows: int,
    num_buckets: int,
    triple_tile: int = 512,
    bucket_tile: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Accumulate ``(key, weight)`` triples into ``(num_rows, num_buckets)``.

    Matches ``ref.scatter_histogram_ref``: keys outside
    ``[0, num_rows * num_buckets)`` contribute nothing.  The bucket axis is
    padded to a ``bucket_tile`` multiple and the row axis to the sublane
    minimum internally; pads are sliced off before returning.
    """
    if num_rows > MAX_RESIDENT_ROWS:
        raise ValueError(
            f"num_rows={num_rows} exceeds MAX_RESIDENT_ROWS="
            f"{MAX_RESIDENT_ROWS}; the scatter kernel keeps every bank row "
            "resident in VMEM — use the segmented matmul-histogram kernel "
            "for banks this tall"
        )
    if keys.size != weights.size:
        raise ValueError(
            f"keys ({keys.size} elements) and weights ({weights.size} "
            "elements) must have the same size"
        )
    if keys.size == 0:  # zero-length triple grid would skip the tile init
        return jnp.zeros((num_rows, num_buckets), jnp.float32)
    k = keys.reshape(-1).astype(jnp.int32)
    w = weights.reshape(-1).astype(jnp.float32)
    n = k.shape[0]
    pad = (-n) % triple_tile
    if pad:
        k = jnp.pad(k, (0, pad), constant_values=-1)  # masked out in-kernel
        w = jnp.pad(w, (0, pad), constant_values=0.0)
    rows_padded = num_rows + ((-num_rows) % 8)
    buckets_padded = num_buckets + ((-num_buckets) % bucket_tile)
    nt = k.shape[0] // triple_tile
    nb = buckets_padded // bucket_tile
    # triples ride as one (1, N) row so each (1, TT) block is tile-aligned
    k = k.reshape(1, -1)
    w = w.reshape(1, -1)

    out = pl.pallas_call(
        functools.partial(
            _scatter_kernel,
            num_rows=num_rows,
            num_buckets=num_buckets,
            bucket_tile=bucket_tile,
        ),
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((1, triple_tile), lambda j, t: (0, t)),
            pl.BlockSpec((1, triple_tile), lambda j, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((rows_padded, bucket_tile), lambda j, t: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, buckets_padded), jnp.float32),
        interpret=interpret,
    )(k, w)
    return out[:num_rows, :num_buckets]
