"""Bytes a windowed read needs, whatever implements it.

A read over ``w`` slices merges the live bank with the sealed slices
``[sealed - (w - 1), sealed)``.  A merge-tree ring that keeps aligned
blocks of ``2**j`` slices covers that range with ``c(w)`` nodes, the
greedy dyadic cover (largest aligned block at the range's left edge,
repeated).  Each node and the live bank are ``K`` rows of ``2 m`` float32
counts, so the read needs ``(1 + c(w)) * K * 2 m * 4`` bytes.  The cover
is the benchmark's own copy of that arithmetic: a query that pads its
cover reads more than this, and the roofline share shows it.
"""

from __future__ import annotations


def cover_size(sealed: int, window_slices: int, num_slices: int) -> int:
    """c(w): nodes of the greedy aligned-block cover of the window."""
    levels = num_slices.bit_length() - 1
    span = min(window_slices - 1, sealed)
    lo, hi, count = sealed - span, sealed, 0
    while lo < hi:
        j = 0
        while j < levels and lo % (1 << (j + 1)) == 0 and lo + (1 << (j + 1)) <= hi:
            j += 1
        lo += 1 << j
        count += 1
    return count


def needed_bytes(sealed: int, window_slices: int, num_slices: int,
                 rows: int, num_buckets: int) -> int:
    c = cover_size(sealed, window_slices, num_slices)
    return (1 + c) * rows * 2 * num_buckets * 4
