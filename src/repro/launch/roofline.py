"""Three-term roofline model from a compiled SPMD module (DESIGN.md §7).

  compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
  memory term     = HLO_bytes / HBM_bw                 (per chip)
  collective term = wire_bytes / link_bw               (per chip)

``cost_analysis()`` on an SPMD-compiled executable reports per-device FLOPs
and bytes.  Collective bytes are NOT in cost_analysis: we parse the
post-partitioning HLO text.  Post-optimization HLO omits operand shapes in
the call (``all-reduce(%dot.1)``), so sizes are read from each op's RESULT
shape, with ring cost factors expressed against the result:

  all-reduce         2(n-1)/n x result   (result == operand buffer)
  all-gather         (n-1)/n  x result   (result is the gathered buffer)
  reduce-scatter     (n-1)    x result   (result is the local shard)
  all-to-all         (n-1)/n  x result
  collective-permute        1 x result

The group size n comes from replica_groups (both the explicit {{0,1,...}}
and the iota [g,n]<=[N] forms are parsed).

Peaks come from ``PEAKS``, one table keyed by ``device_kind`` with its
source; a device missing from it is an error, never a default, so a CPU
run can produce no roofline share.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "PEAKS",
    "Peaks",
    "peaks",
    "CollectiveStats",
    "parse_collectives",
    "roofline_terms",
    "fmt_row",
]


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one device kind."""

    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # interchip bytes/s per link


# Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interchip
# interconnect per chip over four links (50 GB/s per link).  JAX reports a
# v5e chip's device_kind as "TPU v5 lite".
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str | None = None) -> Peaks:
    """The ``PEAKS`` row of ``device_kind`` (default: ``jax.devices()[0]``).

    Raises ``KeyError`` for a kind with no published row — a CPU run, or a
    chip nobody has sourced yet — instead of guessing.
    """
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            "sourced row to repro.launch.roofline.PEAKS"
        ) from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# shape token like  bf16[16,4096,32,128]{3,2,1,0}  or f32[] or token[]
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
# op line:  %name = <result shape or tuple> all-reduce(...operands...), ...
_OP_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+(" + "|".join(_COLL_KINDS) + r")(-start|-done)?\("
)
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(token_list: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(token_list):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_ITOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclass
class CollectiveStats:
    ops: dict = field(default_factory=dict)  # kind -> count
    raw_bytes: dict = field(default_factory=dict)  # kind -> operand bytes
    wire_bytes: float = 0.0  # ring-weighted per-device bytes

    def add(self, kind: str, result_bytes: int, n: int):
        self.ops[kind] = self.ops.get(kind, 0) + 1
        self.raw_bytes[kind] = self.raw_bytes.get(kind, 0) + result_bytes
        if n <= 1:
            factor = 0.0 if kind != "collective-permute" else 1.0
        elif kind == "all-reduce":
            factor = 2.0 * (n - 1) / n
        elif kind == "all-gather":
            factor = (n - 1) / n
        elif kind == "reduce-scatter":
            factor = float(n - 1)
        elif kind == "all-to-all":
            factor = (n - 1) / n
        else:  # collective-permute
            factor = 1.0
        self.wire_bytes += factor * result_bytes


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result, kind, suffix = m.group(1), m.group(2), m.group(3)
        # -done ops repeat the -start payload: count each async pair once
        if suffix == "-done":
            continue
        # result type; for async -start tuples, the payload is the largest
        # element (the tuple repeats operand+result for bookkeeping)
        if suffix == "-start" and result.startswith("("):
            sizes = [
                _shape_bytes(f"{dt}[{dims}]")
                for dt, dims in _SHAPE_RE.findall(result)
            ]
            result_bytes = max(sizes) if sizes else 0
        else:
            result_bytes = _shape_bytes(result)
        stats.add(kind, result_bytes, _group_size(line))
    return stats


def roofline_terms(
    flops: float,
    hbm_bytes: float,
    wire_bytes: float,
    *,
    hw: Peaks,
) -> dict:
    compute_s = flops / hw.peak_flops
    memory_s = hbm_bytes / hw.hbm_bw
    collective_s = wire_bytes / hw.ici_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=terms.get)
    bound = dominant.replace("_s", "")
    step_s = max(compute_s, memory_s, collective_s)
    terms.update(
        {
            "bound": bound,
            "step_s_lower_bound": step_s,
            # fraction of peak FLOPs achievable if the dominant term is the
            # only cost (perfect overlap of the other two)
            "roofline_mfu": compute_s / step_s if step_s > 0 else 0.0,
        }
    )
    return terms


def collective_shape_histogram(hlo_text: str, top: int = 12) -> list[dict]:
    """Per-(kind, result-shape) wire-byte histogram — the §Perf diagnosis
    tool: tells you WHICH tensor's collective dominates."""
    agg: dict = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        bytes_ = _shape_bytes(result)
        n = _group_size(line)
        key = (kind, result.split("{")[0], n)
        cnt, tot = agg.get(key, (0, 0.0))
        agg[key] = (cnt + 1, tot + bytes_)
    rows = [
        {"kind": k, "shape": s, "group": n, "count": c, "gbytes": round(t / 1e9, 3)}
        for (k, s, n), (c, t) in agg.items()
    ]
    rows.sort(key=lambda r: -r["gbytes"])
    return rows[:top]


def fmt_row(name: str, terms: dict, extra: str = "") -> str:
    return (
        f"{name:46s} compute={terms['compute_s']*1e3:9.2f}ms "
        f"memory={terms['memory_s']*1e3:9.2f}ms "
        f"collective={terms['collective_s']*1e3:9.2f}ms "
        f"bound={terms['bound']:10s} mfu_bound={terms['roofline_mfu']*100:5.1f}% {extra}"
    )
