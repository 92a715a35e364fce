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
    "ingest_bytes_model",
    "attained_bandwidth",
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


def ingest_bytes_model(
    method: str,
    n: int,
    num_segments: int,
    num_buckets: int,
    *,
    unit_weights: bool = True,
    counter_bytes: int = 4,
) -> dict:
    """Bytes-moved model for one full bank ingest (histograms + aux stats).

    First-order HBM-traffic accounting for the three ``ops.insert_method``
    pipelines as ``sketch_bank.add_impl`` executes them on the XLA
    reference tier (the CPU-measurable configuration tracked in
    ``BENCH_baseline.json``; on the Pallas tiers the sort path's scatter
    stage streams the *compacted* bound ``U <= min(N, 2Km + 1)`` instead of
    N — strictly less traffic, same structure).  Lanes are
    values + ids + levels (+ weights) at 4 bytes each; the bank update
    reads and writes both ``(K, m)`` stores and the six ``(K,)`` stat rows.

    * ``fused`` — ONE pass over the lanes: the single dispatch bucketizes,
      bins and reduces the stats in-register, so lane traffic is
      ``lane_bytes * N`` total.
    * ``sort`` — the key pass re-reads the lanes and writes N int32 keys,
      the reducing scatter re-reads keys + weights, and ``add_impl``'s
      separate stats pass re-reads the lanes and streams six segment
      reductions (4 sums + 2 extrema, each moving data + ids) — ~5x the
      fused path's lane traffic.
    * ``matmul`` — one histogram pass over the lanes into the stacked
      ``(2K, m)`` layout plus the same separate stats pass.

    Returns ``{"method", "hbm_bytes", "terms": {stage: bytes}}``; feed
    ``hbm_bytes`` and a measured wall-clock to ``attained_bandwidth`` for
    the roofline position.
    """
    lane = 12 + (0 if unit_weights else 4)  # values + ids + levels (+ w)
    cells = 2 * num_segments * num_buckets * counter_bytes
    stats = 6 * num_segments * 4
    # the separate add_impl stats pass: re-read lanes, then 4 segment-sums
    # + 2 segment-extrema each streaming (data + ids) = 6 * 8 bytes/lane
    stats_pass = lane * n + 48 * n + 2 * stats
    if method == "fused":
        terms = {
            "lane_pass": lane * n,
            "hist_update": 2 * cells,
            "stats_update": 2 * stats,
        }
    elif method == "sort":
        terms = {
            "key_pass": lane * n + 4 * n,
            "scatter": 8 * n + 2 * cells,
            "stats_pass": stats_pass,
        }
    elif method == "matmul":
        terms = {
            "hist_pass": lane * n + 2 * cells,
            "stats_pass": stats_pass,
        }
    else:
        raise ValueError(f"unknown ingest method {method!r}")
    return {
        "method": method,
        "hbm_bytes": float(sum(terms.values())),
        "terms": terms,
    }


def attained_bandwidth(model_bytes: float, seconds: float, *, hw: Peaks) -> dict:
    """Measured bandwidth for a modeled byte count, vs the chip's HBM peak.

    ``attained_gbps`` is what the measured wall-clock implies the modeled
    bytes moved at; ``hbm_frac`` positions that against ``hw.hbm_bw``.
    Only meaningful for a time measured on the chip ``hw`` describes.
    """
    if seconds <= 0:
        return {"attained_gbps": 0.0, "hbm_frac": 0.0}
    bps = model_bytes / seconds
    return {"attained_gbps": bps / 1e9, "hbm_frac": bps / hw.hbm_bw}


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
