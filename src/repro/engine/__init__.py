"""SketchEngine tier: persistent compiled call paths between core and kernels.

kernels → engine → core → telemetry → serve: the engine owns the compiled
executables (AOT-lowered once per path × geometry), the donated
state-in/state-out ingest, the per-spec constant caches, and the
row-sharded multi-device banks.
"""

from repro.engine.tables import (
    bucket_value_table,
    device_value_table,
    padded_row_count,
)
from repro.engine.engine import SketchEngine, shared_engine, window_merge_bank
from repro.engine.ring import StaleCover, WindowRing
from repro.engine.sharded import ShardedBank, ShardedEngine, make_engine

__all__ = [
    "SketchEngine",
    "ShardedEngine",
    "ShardedBank",
    "StaleCover",
    "WindowRing",
    "make_engine",
    "shared_engine",
    "window_merge_bank",
    "bucket_value_table",
    "device_value_table",
    "padded_row_count",
]
