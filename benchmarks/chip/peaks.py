"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Copied from ``repro.launch.roofline.PEAKS`` so the yardstick cannot move
with the program.  Source: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s.  JAX reports a v5e chip's ``device_kind`` as "TPU v5 lite".  A
kind missing from the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       "add a sourced row to PEAKS") from None
