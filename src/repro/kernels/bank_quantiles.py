"""Pallas TPU kernel for the fused bank quantile query (Algorithm 2, batched).

The read side of the multi-tenant bank: answer Q quantiles for all K rows in
one launch.  The grid runs over row tiles; each step builds its rows'
cumulative counts *once* in VMEM and answers every q off them with a
lane-wise compare-and-count (``#{cum <= rank}`` == right-searchsorted) plus
a one-hot value select — no gathers, no per-q rebuilds.

The reference walks one mirrored ``(2m+1)``-lane line (reversed negative
store, zero bucket, positive store).  Mosaic lowers neither the lane flip
nor ``cumsum``, so the kernel never builds that line: it keeps the two
stores in their own ``m``-lane layout, takes the positive store's prefix
sums and the negative store's suffix sums (``total - prefix + self``) with
a log-step ``pltpu.roll`` scan, and counts line lanes store by store.  A
rank's line index ``i`` maps back to ``-vals[m-1-i]`` / ``0`` /
``vals[i-m-1]``, read with a lane one-hot.

Per-row collapse levels select the bucket-value row from the
``(MAX_COLLAPSE_LEVEL + 1, m)`` table with a level one-hot, so mixed-level
banks query correctly in a single pass.  The qs ride in SMEM as scalars.

Grid = (row_tiles,); VMEM per step (defaults TR=8, m=2048, Q<=8, f32):
  pos+neg (TR, m) 128 KiB + table 56 KiB + scans/values 4 x (TR, m) 256 KiB
  << 16 MiB.

Every count is integer-valued (the stores' 2^24 float32 contract), so the
scan's summation order is exact and the kernel agrees bit-for-bit with
``ref.bank_quantiles_ref``; validated in interpret mode across mappings,
levels, weights, and row tiles in ``tests/test_bank_quantiles_kernel.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bank_quantiles_pallas"]


def _lane_cumsum(x: jnp.ndarray, lanes: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along lanes: Hillis–Steele over ``pltpu.roll``."""
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(lanes >= shift, pltpu.roll(x, shift, 1), 0.0)
        shift *= 2
    return x


def _bankq_kernel(q_ref, pos_ref, neg_ref, zero_ref, vmin_ref, vmax_ref,
                  lev_ref, table_ref, out_ref):
    pos = pos_ref[...]  # (TR, m)
    neg = neg_ref[...]  # (TR, m)
    zero = zero_ref[...]  # (TR, 1)
    vmin = vmin_ref[...]  # (TR, 1)
    vmax = vmax_ref[...]  # (TR, 1)
    tr, m = pos.shape
    num_levels = table_ref.shape[0]
    lclip = jnp.clip(lev_ref[...], 0, num_levels - 1)  # (TR, 1) int32
    vals = jnp.zeros_like(pos)
    for lev in range(num_levels):
        vals = jnp.where(lclip == lev, table_ref[lev:lev + 1, :], vals)

    lanes = jax.lax.broadcasted_iota(jnp.int32, (tr, m), 1)
    pcum = _lane_cumsum(pos, lanes)  # line lanes m+1.. minus `below`
    ncum = _lane_cumsum(neg, lanes)
    total_neg = ncum[:, m - 1:m]
    sneg = total_neg - ncum + neg  # suffix sums: line lanes 0..m-1, mirrored
    below = total_neg + zero  # line lane m
    n = below + pcum[:, m - 1:m]

    nq = out_ref.shape[1]
    qlanes = jax.lax.broadcasted_iota(jnp.int32, (tr, nq), 1)
    out = jnp.zeros((tr, nq), jnp.float32)
    for qi in range(nq):
        qf = q_ref[0, qi]
        rank = qf * jnp.maximum(n - 1.0, 0.0)
        # searchsorted(line_cum, rank, side="right") == #{line_cum <= rank},
        # counted over the three segments of the (never built) line
        idx = (
            jnp.sum((sneg <= rank).astype(jnp.int32), axis=1, keepdims=True)
            + (below <= rank).astype(jnp.int32)
            + jnp.sum((below + pcum <= rank).astype(jnp.int32), axis=1,
                      keepdims=True)
        )
        idx = jnp.clip(idx, 0, 2 * m)
        lane = jnp.where(idx < m, m - 1 - idx, idx - m - 1)
        v = jnp.sum(jnp.where(lanes == lane, vals, 0.0), axis=1, keepdims=True)
        est = jnp.where(idx < m, -v, jnp.where(idx == m, 0.0, v))
        est = jnp.clip(est, vmin, vmax)  # exact-extrema clamp
        est = jnp.where(qf <= 0.0, vmin, jnp.where(qf >= 1.0, vmax, est))
        out = jnp.where(qlanes == qi, jnp.where(n > 0, est, jnp.nan), out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def bank_quantiles_pallas(
    pos: jnp.ndarray,
    neg: jnp.ndarray,
    zero: jnp.ndarray,
    vmin: jnp.ndarray,
    vmax: jnp.ndarray,
    level: jnp.ndarray,
    qs: jnp.ndarray,
    table: jnp.ndarray,
    *,
    row_tile: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-row quantile estimates ``(K, len(qs))`` in one launch.

    Matches ``ref.bank_quantiles_ref`` bit-for-bit on integer-valued counts
    (empty rows answer NaN, extremes answer vmin/vmax exactly).  Rows are
    padded to a ``row_tile`` multiple internally (pad rows are empty -> NaN)
    and sliced off.
    """
    k, m = pos.shape
    qf = jnp.atleast_1d(jnp.asarray(qs, jnp.float32)).reshape(1, -1)
    nq = qf.shape[1]
    if k == 0:
        return jnp.zeros((0, nq), jnp.float32)
    rows_padded = k + ((-k) % row_tile)
    pad = rows_padded - k

    def rows(a, fill=0.0):
        a = a.astype(jnp.float32).reshape(k, -1)
        return jnp.pad(a, ((0, pad), (0, 0)), constant_values=fill)

    nr = rows_padded // row_tile
    out = pl.pallas_call(
        _bankq_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((row_tile, m), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, m), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec(table.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, nq), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_padded, nq), jnp.float32),
        interpret=interpret,
    )(
        qf,
        rows(pos),
        rows(neg),
        rows(zero),
        rows(vmin, fill=jnp.inf),
        rows(vmax, fill=-jnp.inf),
        jnp.pad(level.astype(jnp.int32).reshape(k, 1), ((0, pad), (0, 0))),
        table.astype(jnp.float32),
    )
    return out[:k]
