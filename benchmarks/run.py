"""Benchmark orchestrator: one bench per paper table/figure + kernel timings
+ the roofline table (from dry-run artifacts when present).

Usage:
  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # smaller sweeps
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI-sized sweeps
  PYTHONPATH=src python -m benchmarks.run --only fig10_rel_err
  PYTHONPATH=src python -m benchmarks.run --smoke --json BENCH_smoke.json

``--json`` writes every bench's rows to one JSON file (schema:
{"bench_name": [row, ...], ...}) so CI can upload the per-PR perf
trajectory as an artifact.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (
    bank_bench,
    ingest_bench,
    kernels_bench,
    serve_bench,
    sketches,
    telemetry_bench,
    window_bench,
)

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def _emit(rows: list[dict]) -> None:
    if not rows:
        return
    cols = list(rows[0].keys())
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))
    print()


def roofline_rows() -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        t = rec["roofline"]
        rows.append(
            {
                "bench": "roofline",
                "arch": rec["arch"],
                "shape": rec["shape"],
                "mesh": rec["mesh"],
                "tag": rec.get("tag", ""),
                "compute_ms": round(t["compute_s"] * 1e3, 2),
                "memory_ms": round(t["memory_s"] * 1e3, 2),
                "collective_ms": round(t["collective_s"] * 1e3, 2),
                "bound": t["bound"],
                "mfu_bound_pct": round(t["roofline_mfu"] * 100, 1),
                "hbm_GiB": round(rec["memory"]["peak_hbm_bytes"] / 2**30, 2),
                "useful_flops_pct": round(rec["useful_flops_frac"] * 100, 1),
            }
        )
    return rows


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--smoke", action="store_true",
                   help="tiny sweeps for CI: every bench runs, sizes minimal")
    p.add_argument("--only", default=None)
    p.add_argument("--json", default=None, metavar="PATH",
                   help="also write all rows to PATH as JSON (CI artifact)")
    args = p.parse_args()
    # persistent compile cache: $JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    enable_compile_cache(os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))

    if args.smoke:
        benches = {
            "fig6_size": lambda: sketches.bench_size(ns=(10_000,)),
            "fig7_bins": lambda: sketches.bench_bins(ns=(10_000, 100_000)),
            "fig8_add": lambda: sketches.bench_add(n=10_000),
            "fig9_merge": lambda: sketches.bench_merge(n_each=5_000, pairs=3),
            "fig10_rel_err": lambda: sketches.bench_rel_err(n=10_000),
            "fig11_rank_err": lambda: sketches.bench_rank_err(n=10_000),
            "kernel_insert": lambda: kernels_bench.bench_device_insert(n=50_000),
            "kernel_merge": lambda: kernels_bench.bench_device_merge(iters=10),
            "kernel_quantile": lambda: kernels_bench.bench_quantile_query(iters=10),
            "bank_insert": lambda: bank_bench.bench_bank_insert(
                n=50_000, ks=(1, 64, 4096), loop_cap=8, iters=3
            ),
            "bank_quantiles": lambda: bank_bench.bench_bank_quantiles(
                k=256, n=50_000, iters=3
            ),
            "fold_pairs": lambda: bank_bench.bench_fold_pairs(
                ks=(1, 64, 256), iters=3
            ),
            "collapse_insert": lambda: bank_bench.bench_collapse_insert(
                n=50_000, iters=3
            ),
            # donation + persistent-executable evidence (the engine tentpole):
            # the jit-per-call vs engine delta is the per-record dispatch +
            # K×m allocation cost, tracked in BENCH_baseline.json
            "engine_ingest": lambda: bank_bench.bench_engine_ingest(
                k=4096, n=2048, records=30, iters=3
            ),
            "sharded_ingest": lambda: bank_bench.bench_sharded_ingest(
                k=1024, n=4096, records=10, iters=2, shards=(1, 2, 8)
            ),
            # the fleet tier: 1/2/8 coordinated jax.distributed processes
            # (gloo CPU collectives), one device each — the multi-host
            # ingest + rollup trajectory tracked in BENCH_baseline.json
            "sharded_ingest_fleet": lambda: bank_bench.bench_fleet_ingest(
                k=1024, n=4096, records=10, iters=2, processes=(1, 2, 8)
            ),
            # train-telemetry recorder: dict-of-sketches vs TelemetryBank
            # (traced hist dispatches + ms/step, tracked in BENCH_baseline)
            "telemetry_record": lambda: telemetry_bench.bench_telemetry_record(
                iters=5
            ),
            # write-path acceptance: HTTP ingest throughput/latency plus the
            # sustained-overload row (zero 5xx, bounded queue, clean 429s,
            # mass conservation) tracked in BENCH_baseline.json
            "ingest_http": lambda: ingest_bench.bench_ingest_http(
                clients=(1, 8), reqs_per_client=8, overload_reqs=8
            ),
            # read-path acceptance: 8/32-poller storms against sustained
            # ingest — snapshot+coalesce+cache vs the lock-serialized
            # baseline (committed bars: >=3x req/s, >0.9 cache hit rate)
            "query_http": lambda: serve_bench.bench_query_http(
                pollers=(8, 32), reqs_per_poller=25
            ),
            # windowed-quantile acceptance rows: the flagship S=64, K=128,
            # m=4096 fused-vs-host-loop speedup (committed bar: >= 5x) and
            # the flat-vs-S window-advance cost, tracked in BENCH_baseline
            "window_query": lambda: window_bench.bench_window_query(
                configs=((8, 64, 2048), (64, 128, 4096)), iters=3
            ),
            "window_advance": lambda: window_bench.bench_window_advance(
                ss=(8, 64), k=64, m=2048, iters=10
            ),
            "roofline": roofline_rows,
        }
    elif args.quick:
        benches = {
            "fig6_size": lambda: sketches.bench_size(ns=(10_000, 100_000)),
            "fig7_bins": lambda: sketches.bench_bins(ns=(10_000, 100_000, 1_000_000)),
            "fig8_add": lambda: sketches.bench_add(n=50_000),
            "fig9_merge": lambda: sketches.bench_merge(n_each=20_000, pairs=5),
            "fig10_rel_err": lambda: sketches.bench_rel_err(n=50_000),
            "fig11_rank_err": lambda: sketches.bench_rank_err(n=50_000),
            "kernel_insert": lambda: kernels_bench.bench_device_insert(n=200_000),
            "kernel_merge": kernels_bench.bench_device_merge,
            "kernel_quantile": kernels_bench.bench_quantile_query,
            "bank_insert": lambda: bank_bench.bench_bank_insert(
                n=200_000, loop_cap=16, iters=5
            ),
            "bank_quantiles": lambda: bank_bench.bench_bank_quantiles(
                k=1024, n=200_000, iters=5
            ),
            "fold_pairs": lambda: bank_bench.bench_fold_pairs(iters=5),
            "collapse_insert": lambda: bank_bench.bench_collapse_insert(
                n=100_000, iters=5
            ),
            "engine_ingest": lambda: bank_bench.bench_engine_ingest(
                k=4096, n=2048, records=50, iters=3
            ),
            "sharded_ingest": lambda: bank_bench.bench_sharded_ingest(
                k=2048, n=8192, records=15, iters=3, shards=(1, 2, 8)
            ),
            "sharded_ingest_fleet": lambda: bank_bench.bench_fleet_ingest(
                k=2048, n=8192, records=15, iters=3, processes=(1, 2, 8)
            ),
            "telemetry_record": lambda: telemetry_bench.bench_telemetry_record(
                iters=10
            ),
            "ingest_http": lambda: ingest_bench.bench_ingest_http(
                clients=(1, 4, 16), reqs_per_client=16
            ),
            "query_http": lambda: serve_bench.bench_query_http(
                pollers=(8, 32), reqs_per_poller=40
            ),
            "window_query": lambda: window_bench.bench_window_query(
                configs=((8, 64, 2048), (64, 128, 4096), (256, 128, 2048)),
                iters=3,
            ),
            "window_advance": lambda: window_bench.bench_window_advance(
                ss=(8, 64, 256), iters=10
            ),
            "roofline": roofline_rows,
        }
    else:
        benches = {
            "fig6_size": sketches.bench_size,
            "fig7_bins": sketches.bench_bins,
            "fig8_add": sketches.bench_add,
            "fig9_merge": sketches.bench_merge,
            "fig10_rel_err": sketches.bench_rel_err,
            "fig11_rank_err": sketches.bench_rank_err,
            "kernel_insert": kernels_bench.bench_device_insert,
            "kernel_merge": kernels_bench.bench_device_merge,
            "kernel_quantile": kernels_bench.bench_quantile_query,
            "bank_insert": bank_bench.bench_bank_insert,
            "bank_quantiles": bank_bench.bench_bank_quantiles,
            "fold_pairs": bank_bench.bench_fold_pairs,
            "collapse_insert": bank_bench.bench_collapse_insert,
            "engine_ingest": lambda: bank_bench.bench_engine_ingest(
                k=4096, n=2048, records=100, iters=5
            ),
            "sharded_ingest": lambda: bank_bench.bench_sharded_ingest(
                k=4096, n=16384, records=20, iters=3, shards=(1, 2, 4, 8)
            ),
            "sharded_ingest_fleet": lambda: bank_bench.bench_fleet_ingest(
                k=4096, n=16384, records=20, iters=3, processes=(1, 2, 8)
            ),
            "telemetry_record": lambda: telemetry_bench.bench_telemetry_record(
                seq=2048, iters=10
            ),
            "ingest_http": lambda: ingest_bench.bench_ingest_http(
                clients=(1, 4, 16, 32), reqs_per_client=32, overload_reqs=16
            ),
            "query_http": lambda: serve_bench.bench_query_http(
                pollers=(8, 32, 64), reqs_per_poller=50
            ),
            "window_query": lambda: window_bench.bench_window_query(
                configs=((8, 64, 2048), (64, 128, 4096), (256, 128, 2048)),
                iters=5,
            ),
            "window_advance": window_bench.bench_window_advance,
            "roofline": roofline_rows,
        }

    failed = []
    results: dict[str, list[dict]] = {}
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"== {name} ==")
        try:
            rows = fn()
            results[name] = rows
            _emit(rows)
        except Exception as e:  # keep going; report at the end
            failed.append((name, repr(e)))
            print(f"ERROR in {name}: {e!r}\n")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"wrote {sum(len(v) for v in results.values())} rows to {args.json}")
    if failed:
        print(f"{len(failed)} benches failed: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
