"""Bytes an ingest tick needs, whatever implements it.

A tick reads N value lanes (a float32 value and an int32 row id each) and
updates U distinct (row, sign, bucket) cells, each read and written once
as float32: ``8 N + 8 U`` bytes.  U is counted from the tick's own values
with the benchmark's copy of the log mapping (``key = ceil(ln|x| /
ln gamma)`` at level 0, ``ceil(key / 2**L)`` at level ``L``, clamped to the
bank's key range), not from what a kernel happens to touch.
"""

from __future__ import annotations

import numpy as np


def bucket_keys(values: np.ndarray, alpha: float, level: np.ndarray,
                offset: int, num_buckets: int) -> np.ndarray:
    """Bucket key of each value at its row's collapse level."""
    gamma = (1.0 + alpha) / (1.0 - alpha)
    k = np.ceil(np.log(np.abs(values.astype(np.float64))) / np.log(gamma))
    k = np.ceil(k / (2.0 ** level))
    return np.clip(k, offset, offset + num_buckets - 1).astype(np.int64)


def distinct_cells(rows: np.ndarray, values: np.ndarray, alpha: float,
                   level: np.ndarray, offset: int, num_buckets: int) -> int:
    """U: distinct (row, sign, bucket) cells a tick's lanes land in."""
    if values.size == 0:
        return 0
    keys = bucket_keys(values, alpha, level, offset, num_buckets) - offset
    cell = (rows.astype(np.int64) * 2 + (values < 0)) * num_buckets + keys
    return int(np.unique(cell).size)


def needed_bytes(n_lanes: int, n_cells: int) -> int:
    return 8 * int(n_lanes) + 8 * int(n_cells)
