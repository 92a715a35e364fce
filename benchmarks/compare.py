"""Perf-trajectory regression gate: compare two ``run.py --json`` files.

Usage:
  PYTHONPATH=src python -m benchmarks.compare BENCH_baseline.json NEW.json \
      --factor 2.0

Rows are matched within each bench by their identity fields (every
non-timing field: sizes, dup ratio, impl tag, ...); timing fields
are any key carrying a unit token (``ms`` / ``us`` / ``ns``), normalized to
milliseconds.  A row regresses when a timing grows by more than ``factor``
vs the committed baseline.

CI runners are not the machine the baseline was measured on, so by default
the candidate is first *calibrated*: every ratio is divided by the median
ratio across all compared timings.  A uniformly slower machine then sits at
1.0 and only benches that regressed relative to the rest of the suite trip
the gate (``--no-calibrate`` compares raw wall-clock).  Absolute timings
below ``--min-ms`` in the baseline are noise-dominated and skipped —
per-element metrics (``*_per_*`` keys: ns_per_value, us_per_query, ...)
are averages over long timed runs, so they are always compared no matter
how small; benches contributing zero compared timings are called out.

Under GitHub Actions the gate also *reports*: a per-bench markdown table
lands in the job's step summary (``$GITHUB_STEP_SUMMARY``) and every
regression over the factor emits a ``::error`` workflow annotation, so a
tripped gate names the offending bench on the PR without digging in logs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_UNIT_MS = {"ms": 1.0, "us": 1e-3, "ns": 1e-6}

# measured outputs (as opposed to configuration): they drift with the code
# under test, so keying row identity on them would silently unmatch rows
# and let regressions slip past the gate
_MEASURED_FIELDS = {
    "live_buckets",
    "speedup",
    "loop_measured_K",
    "hist_calls_per_trace",
    # ingest_http robustness counters: outputs under test (throttles, shed
    # mass, and the conservation flag move with load behaviour, not config)
    "http_429",
    "http_5xx",
    "shed_mass",
    "max_queue_depth",
    "conserved",
    # query_http read-path counters: coalescer/cache effectiveness and the
    # 304 count are outputs of the planner under test, not configuration
    "http_304",
    "query_dispatches",
    "errors",
}


def _timing_unit(key: str) -> float | None:
    for tok in key.split("_"):
        if tok in _UNIT_MS:
            return _UNIT_MS[tok]
    return None


def _identity(row: dict) -> tuple:
    return tuple(
        sorted(
            (k, v)
            for k, v in row.items()
            if _timing_unit(k) is None
            and k not in _MEASURED_FIELDS
            and isinstance(v, (str, int, bool))
        )
    )


def compare(
    baseline: dict,
    candidate: dict,
    *,
    factor: float = 2.0,
    min_ms: float = 0.05,
    calibrate: bool = True,
) -> tuple[list[str], list[str], list[dict]]:
    """Return (regressions, notes, timings).

    Empty ``regressions`` == gate passes.  ``timings`` carries one dict per
    compared timing — ``{bench, label, key, base_ms, new_ms, ratio,
    regressed}`` with ``ratio`` already calibrated — for reporting layers
    (the GitHub step summary) on top of the pass/fail strings.
    """
    pairs = []  # (bench, key, label, base_ms, cand_ms)
    unmatched = 0
    uncovered: list[str] = []
    for bench, base_rows in baseline.items():
        cand_rows = {_identity(r): r for r in candidate.get(bench, [])}
        covered = 0
        for row in base_rows:
            other = cand_rows.get(_identity(row))
            if other is None:
                unmatched += 1
                continue
            for key, val in row.items():
                unit = _timing_unit(key)
                if unit is None or not isinstance(val, (int, float)):
                    continue
                new = other.get(key)
                if not isinstance(new, (int, float)):
                    continue
                base_ms, new_ms = val * unit, new * unit
                if base_ms <= 0:
                    continue
                # per-element metrics are averages over long runs, not
                # noise: exempt them from the absolute-timing cutoff
                if base_ms < min_ms and "_per_" not in key:
                    continue
                label = f"{bench} {dict(_identity(row))} {key}"
                pairs.append((bench, key, label, base_ms, new_ms))
                covered += 1
        if base_rows and not covered:
            uncovered.append(bench)
    notes: list[str] = []
    if unmatched:
        notes.append(
            f"{unmatched} baseline row(s) had no candidate match (renamed or "
            "reconfigured benches?) and were skipped"
        )
    if uncovered:
        notes.append(
            "benches with NO compared timings (gate blind spots): "
            + ", ".join(sorted(uncovered))
        )
    if not pairs:
        notes.append("no comparable timings found (new bench set?); gate passes")
        return [], notes, []
    ratios = sorted(new / base for _, _, _, base, new in pairs)
    median = ratios[len(ratios) // 2]
    scale = median if calibrate and median > 0 else 1.0
    if calibrate:
        notes.append(
            f"machine calibration: median ratio {median:.2f}x across "
            f"{len(pairs)} timings (ratios divided by it)"
        )
    regressions = []
    timings = []
    for bench, key, label, base_ms, new_ms in pairs:
        ratio = (new_ms / base_ms) / scale
        regressed = ratio > factor
        timings.append(
            {
                "bench": bench,
                "key": key,
                "label": label,
                "base_ms": base_ms,
                "new_ms": new_ms,
                "ratio": ratio,
                "regressed": regressed,
            }
        )
        if regressed:
            regressions.append(
                f"{label}: {base_ms:.3f} ms -> {new_ms:.3f} ms "
                f"({ratio:.2f}x calibrated, factor {factor}x)"
            )
    return regressions, notes, timings


def _annotate_github(timings: list[dict], factor: float) -> None:
    """``::error`` workflow annotations: one per regressed timing, so the
    gate names the offending bench directly on the PR checks page."""
    for t in timings:
        if not t["regressed"]:
            continue
        print(
            f"::error title=Perf regression in {t['bench']}::"
            f"{t['label']}: {t['base_ms']:.3f} ms -> {t['new_ms']:.3f} ms "
            f"({t['ratio']:.2f}x calibrated, gate {factor}x)"
        )


def write_step_summary(
    timings: list[dict], notes: list[str], factor: float, path: str
) -> None:
    """Append the per-bench markdown table GitHub renders as the job's
    step summary: worst calibrated ratio per bench, regressed rows called
    out — the perf trajectory at a glance."""
    by_bench: dict[str, list[dict]] = {}
    for t in timings:
        by_bench.setdefault(t["bench"], []).append(t)
    lines = [
        "## Perf trajectory vs committed baseline",
        "",
        *(f"> {note}" for note in notes),
        "",
        "| bench | timings | worst calibrated ratio | status |",
        "| --- | ---: | ---: | --- |",
    ]
    for bench in sorted(by_bench):
        rows = by_bench[bench]
        worst = max(rows, key=lambda t: t["ratio"])
        bad = [t for t in rows if t["regressed"]]
        status = f"🔴 {len(bad)} regression(s)" if bad else "✅"
        lines.append(
            f"| {bench} | {len(rows)} | {worst['ratio']:.2f}x "
            f"(`{worst['key']}`) | {status} |"
        )
    regressed = [t for t in timings if t["regressed"]]
    if regressed:
        lines += [
            "",
            f"### Regressions over {factor}x",
            "",
            "| timing | baseline | candidate | calibrated |",
            "| --- | ---: | ---: | ---: |",
            *(
                f"| {t['label']} | {t['base_ms']:.3f} ms | "
                f"{t['new_ms']:.3f} ms | {t['ratio']:.2f}x |"
                for t in regressed
            ),
        ]
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("baseline")
    p.add_argument("candidate")
    p.add_argument("--factor", type=float, default=2.0)
    p.add_argument("--min-ms", type=float, default=0.05)
    p.add_argument("--no-calibrate", action="store_true",
                   help="compare raw wall-clock (same-machine runs only)")
    args = p.parse_args()
    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.candidate) as f:
        candidate = json.load(f)
    regressions, notes, timings = compare(
        baseline,
        candidate,
        factor=args.factor,
        min_ms=args.min_ms,
        calibrate=not args.no_calibrate,
    )
    for note in notes:
        print(f"[compare] {note}")
    summary_path = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary_path:
        write_step_summary(timings, notes, args.factor, summary_path)
    if regressions:
        print(f"[compare] {len(regressions)} regression(s) over {args.factor}x:")
        for r in regressions:
            print(f"[compare]   {r}")
        _annotate_github(timings, args.factor)
        sys.exit(1)
    print("[compare] no regressions; perf trajectory holds")


if __name__ == "__main__":
    main()
