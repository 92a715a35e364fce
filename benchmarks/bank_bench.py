"""SketchBank benchmarks: single segmented dispatch vs a Python loop over K.

The claim under test is the tentpole of the bank design: inserting a stream
of (value, sketch_id) pairs into K sketches costs *one* dispatch (the
segmented histogram contracts values into all K rows at once), while the
naive serving path launches ``jax_sketch.add`` K times.  The sweep over
K in {1, 64, 4096} shows the loop path scaling linearly in K while the bank
path stays flat, plus a throughput row for the vectorized K-row quantile
query (Algorithm 2 over the whole bank).

CPU wall-clock of the jit'd XLA reference path (the TPU-portable
semantics), matching kernels_bench's methodology.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import jax_sketch as js
from repro.core import sketch_bank as sb
from repro.kernels import ops
from repro.kernels.ref import BucketSpec
from repro.launch.roofline import attained_bandwidth, ingest_bytes_model, peaks

# device programs one full bank ingest launches per pipeline: the fused
# path is ONE dispatch (bucketize + bin + aux stats); sort pays key pass +
# reducing scatter + the separate stats pass; matmul pays one histogram pass
# over the stacked sign stores + the stats pass
DISPATCHES_PER_INGEST = {"fused": 1, "sort": 3, "matmul": 2}


def _time(fn, *args, iters=10) -> float:
    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_bank_insert(
    n: int = 500_000, ks=(1, 64, 4096), loop_cap: int = 64, iters: int = 10
) -> list[dict]:
    """Bank add (one dispatch) vs a K-loop of jax_sketch.add, sweeping K.

    The loop path is only timed up to ``loop_cap`` sketches (beyond that it
    is extrapolated linearly — at K=4096 actually running it would dominate
    the whole suite, which is rather the point).
    """
    spec = BucketSpec()
    rng = np.random.default_rng(0)
    values = jnp.asarray((rng.pareto(1.0, n) + 1.0).astype(np.float32))
    rows = []
    for k in ks:
        ids = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
        bank_fn = jax.jit(
            lambda v, s, k=k: sb.add(sb.empty(spec, k), v, s, spec=spec)
        )
        bank_secs = _time(bank_fn, values, ids, iters=iters)
        picked = sb.picked_insert_method(n, k, spec.num_buckets)

        # naive path: one jax_sketch.add per sketch over its own slice
        k_loop = min(k, loop_cap)
        ids_np = np.asarray(ids)
        slices = [
            jnp.asarray(np.where(ids_np == i, np.asarray(values), np.nan))
            for i in range(k_loop)
        ]

        def loop_fn(slabs):
            return [
                js.add(js.empty(spec), slab, spec=spec).pos for slab in slabs
            ]

        loop_secs = _time(jax.jit(loop_fn), slices, iters=max(1, iters // 2))
        loop_est = loop_secs * (k / k_loop)
        rows.append(
            {
                "bench": "bank_insert",
                "K": k,
                "n": n,
                "bank_ms": round(bank_secs * 1e3, 3),
                "loop_ms": round(loop_est * 1e3, 3),
                "loop_measured_K": k_loop,
                "speedup": round(loop_est / bank_secs, 1),
                "picked_method": picked,
                "impl": "xla_ref",
            }
        )
    return rows


def bench_insert_methods(
    configs=((1_000_000, 128, 4096), (200_000, 64, 2048)), iters: int = 3
) -> list[dict]:
    """Three-way histogram pipelines — matmul vs sort–scatter vs fused —
    over (N, K, m).

    The tentpole claim: the matmul formulation pays for every (row, bucket)
    output tile per value — O(K·m·N) — while the ingest pipeline pays one
    O(N log N) sort plus a scatter of U <= min(N, 2·K·m) compacted triples,
    and the fused pipeline folds the key pass into the binning dispatch
    itself (its aux-stats half, the bigger win, is timed by
    ``bench_fused_ingest`` — this sweep isolates the histogram cost).
    CPU wall-clock of the jit'd ref paths (``force="ref"``), which is what
    the auto heuristic dispatches between off-TPU; the ``dup`` axis sweeps
    the duplicate ratio — "high" concentrates the stream into a few hundred
    live buckets per row (the post-collapse regime of UDDSketch streams),
    "low" spreads it across the full bucket range.  ``live_buckets`` counts
    distinct (row, bucket, sign) cells actually hit, so ``n / live_buckets``
    is the measured duplicate ratio; ``picked_method`` records what the
    hist-only heuristic would auto-select at this (N, K, m).
    """
    rows = []
    for n, k, m in configs:
        spec = BucketSpec(num_buckets=m, offset=-m // 2)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
        picked = ops.insert_method(n, k, m)
        for dup, decades in (("high", 1.3), ("low", 14.0)):
            sgn = np.where(rng.random(n) < 0.3, -1.0, 1.0)
            vals = jnp.asarray(
                (10.0 ** rng.uniform(0.0, decades, n) * sgn).astype(np.float32)
            )
            pos, neg = ops.bank_histograms(
                vals, ids, num_segments=k, spec=spec, method="matmul", force="ref"
            )
            live = int((np.asarray(pos) > 0).sum() + (np.asarray(neg) > 0).sum())
            for method in ("matmul", "sort", "fused"):
                fn = jax.jit(
                    lambda v, s, method=method: ops.bank_histograms(
                        v, s, num_segments=k, spec=spec, method=method, force="ref"
                    )
                )
                secs = _time(fn, vals, ids, iters=iters)
                rows.append(
                    {
                        "bench": "insert_methods",
                        "n": n,
                        "K": k,
                        "m": m,
                        "dup": dup,
                        "live_buckets": live,
                        "method": method,
                        "picked_method": picked,
                        "ms": round(secs * 1e3, 3),
                        "mvals_per_s": round(n / secs / 1e6, 1),
                        "impl": "xla_ref",
                    }
                )
    return rows


def bench_fused_ingest(
    configs=((1_000_000, 128, 4096), (200_000, 64, 2048)), iters: int = 3
) -> list[dict]:
    """Full ``add_impl`` ingest — histograms AND aux stats — per pipeline.

    This is the fusion tentpole's acceptance row: unlike
    ``bench_insert_methods`` (histograms only), every timing here includes
    the six per-row aux stats (zero/overflow/underflow/sum/min/max).  The
    sort and matmul pipelines pay a separate stats pass over the lanes —
    six more segment reductions — while the fused pipeline produces bank
    deltas in ONE dispatch, so ``dispatches_per_ingest`` drops 3 -> 1 and
    the lane traffic drops ~5x (see ``launch.roofline.ingest_bytes_model``).

    Each row carries ``model_mb``, the modeled bytes moved.  On a TPU it
    adds ``attained_gbps`` (what the measured wall-clock implies those
    bytes moved at) and ``hbm_frac`` (that rate against the chip's HBM
    peak from ``roofline.PEAKS``); a CPU run writes neither.  ``speedup`` is vs the sort pipeline of the same
    (config, dup) — the committed acceptance bar is fused >= 1.3x on the
    high-duplication N=1M / K=128 row.  ``picked_method`` is what
    ``method=None`` auto-resolves to for the config.
    """
    rows = []
    hw = peaks() if jax.default_backend() == "tpu" else None
    for n, k, m in configs:
        spec = BucketSpec(num_buckets=m, offset=-m // 2)
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
        base = sb.empty(spec, k)
        picked = sb.picked_insert_method(n, k, m)
        for dup, decades in (("high", 1.3), ("low", 14.0)):
            sgn = np.where(rng.random(n) < 0.3, -1.0, 1.0)
            vals = jnp.asarray(
                (10.0 ** rng.uniform(0.0, decades, n) * sgn).astype(np.float32)
            )
            secs_by: dict[str, float] = {}
            for method in ("matmul", "sort", "fused"):
                fn = jax.jit(
                    lambda b, v, s, method=method: sb.add_impl(
                        b, v, s, spec=spec, method=method
                    )
                )
                secs_by[method] = _time(fn, base, vals, ids, iters=iters)
            for method, secs in secs_by.items():
                model = ingest_bytes_model(method, n, k, m)
                row = {
                    "bench": "fused_ingest",
                    "n": n,
                    "K": k,
                    "m": m,
                    "dup": dup,
                    "method": method,
                    "picked_method": picked,
                    "dispatches_per_ingest": DISPATCHES_PER_INGEST[method],
                    "ms": round(secs * 1e3, 3),
                    "mvals_per_s": round(n / secs / 1e6, 1),
                    "model_mb": round(model["hbm_bytes"] / 1e6, 1),
                    "speedup": round(secs_by["sort"] / secs, 2),
                    "impl": "xla_ref" if hw is None else "pallas",
                }
                if hw is not None:
                    bw = attained_bandwidth(model["hbm_bytes"], secs, hw=hw)
                    row["attained_gbps"] = round(bw["attained_gbps"], 2)
                    row["hbm_frac"] = round(bw["hbm_frac"], 4)
                rows.append(row)
    return rows


def bench_fold_pairs(ks=(1, 64, 1024), iters: int = 10) -> list[dict]:
    """Uniform-collapse fold over a whole bank (one XLA/Pallas dispatch).

    This is the per-collapse overhead a hot row pays when its stream
    outgrows the bucket range: a (K, m) pair-fold, independent of how much
    mass the bank holds.
    """
    from repro.kernels.ref import fold_pairs_ref

    spec = BucketSpec()
    rng = np.random.default_rng(0)
    rows = []
    for k in ks:
        counts = jnp.asarray(
            rng.integers(0, 9, (k, spec.num_buckets)).astype(np.float32)
        )
        fn = jax.jit(lambda c: fold_pairs_ref(c, spec=spec))
        secs = _time(fn, counts, iters=iters)
        rows.append(
            {
                "bench": "fold_pairs",
                "K": k,
                "us_per_fold": round(secs * 1e6, 2),
                "ns_per_row": round(secs / k * 1e9, 2),
                "impl": "xla_ref",
            }
        )
    return rows


def bench_collapse_insert(n: int = 200_000, iters: int = 5) -> list[dict]:
    """Collapse-heavy insert: a 24-decade stream that cannot fit at level 0.

    ``auto_collapse=True`` pays the needed-level scan plus the in-loop
    folds; the plain path clamps (silently losing the tails).  The ratio is
    the price of keeping the alpha guarantee on long-tailed streams.
    """
    spec = BucketSpec()
    rng = np.random.default_rng(0)
    wide = jnp.asarray(
        (10.0 ** rng.uniform(-15.0, 9.0, n)).astype(np.float32)
    )
    rows = []
    for auto in (False, True):
        fn = jax.jit(
            lambda v, auto=auto: js.add(
                js.empty(spec), v, spec=spec, auto_collapse=auto
            )
        )
        secs = _time(fn, wide, iters=iters)
        rows.append(
            {
                "bench": "collapse_insert",
                "n": n,
                "auto_collapse": auto,
                "ms_per_insert": round(secs * 1e3, 3),
                "ns_per_value": round(secs / n * 1e9, 3),
                "impl": "xla_ref",
            }
        )
    return rows


def bench_engine_ingest(
    k: int = 4096, n: int = 2048, records: int = 50, iters: int = 3
) -> list[dict]:
    """Per-record ingest cost: jit-per-call ``sketch_bank.add`` vs the
    engine's persistent donated executable.

    The loop is the serving hot path — many small ``record`` batches into a
    big bank.  The jit path pays per-call dispatch (static-arg hashing,
    trace-cache lookup) and allocates a fresh K×m bank every record (two
    new (4096, 2048) float32 buffers = 64 MiB of churn per call at the
    defaults); the engine path calls one AOT-compiled executable that
    donates the bank, so the update is in place.  Identical math — the
    parity suite (tests/test_engine.py) pins that — so the delta is pure
    dispatch + allocation overhead.
    """
    from repro.engine import SketchEngine

    spec = BucketSpec()
    rng = np.random.default_rng(0)
    vals_np = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    ids_np = rng.integers(0, k, n).astype(np.int32)
    vals, ids = jnp.asarray(vals_np), jnp.asarray(ids_np)

    def jit_path():
        bank = sb.empty(spec, k)
        for _ in range(records):
            bank = sb.add(bank, vals, ids, spec=spec)
        return bank

    eng = SketchEngine(spec, k)

    def engine_path():
        bank = eng.new_bank()
        for _ in range(records):
            bank = eng.add(bank, vals_np, ids_np)
        return bank

    picked = sb.picked_insert_method(n, k, spec.num_buckets)
    rows = []
    for name, fn in (("jit_per_call", jit_path), ("engine", engine_path)):
        secs = _time(fn, iters=iters) / records
        rows.append(
            {
                "bench": "engine_ingest",
                "K": k,
                "n_per_record": n,
                "records": records,
                "path": name,
                "picked_method": picked,
                "ms_per_record": round(secs * 1e3, 4),
                "records_per_s": round(1.0 / secs, 1),
                "impl": "xla_ref",
            }
        )
    return rows


_SHARDED_WORKER_FLAG = "--sharded-worker"


def _sharded_worker(cfg: dict) -> list[dict]:
    """Runs inside the fake-multi-device subprocess; prints JSON rows."""
    from repro.engine import ShardedBank, SketchEngine

    spec = BucketSpec()
    k, n, records = cfg["k"], cfg["n"], cfg["records"]
    rng = np.random.default_rng(0)
    vals = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    ids = rng.integers(0, k, n).astype(np.int32)
    rows = []
    for shards in cfg["shards"]:
        if shards > len(jax.devices()):
            continue
        if shards == 1:
            eng = SketchEngine(spec, k)
            # carry the donated state across timed calls (rebound through
            # the holder), symmetric with the ShardedBank branch below —
            # no per-iteration bank allocation in either path
            holder = [eng.new_bank()]

            def ingest(eng=eng, holder=holder):
                s = holder[0]
                for _ in range(records):
                    s = eng.add(s, vals, ids)
                holder[0] = s
                return s

            secs = _time(ingest, iters=cfg["iters"]) / records
            q_secs = _time(lambda: eng.quantiles(holder[0], [0.5, 0.95, 0.99]),
                           iters=cfg["iters"])
        else:
            bank = ShardedBank(spec, k, num_shards=shards)

            def ingest(bank=bank):
                for _ in range(records):
                    bank.add(vals, ids)
                return bank.state

            secs = _time(ingest, iters=cfg["iters"]) / records
            q_secs = _time(lambda: bank.engine.quantiles(
                bank.state, jnp.asarray([0.5, 0.95, 0.99])), iters=cfg["iters"])
        rows.append(
            {
                "bench": "sharded_ingest",
                "K": k,
                "n_per_record": n,
                "shards": shards,
                "ms_per_record": round(secs * 1e3, 4),
                "quantiles_ms": round(q_secs * 1e3, 4),
                "impl": "shard_map_xla_ref",
            }
        )
    return rows


def _refuse_cpu_children_on_tpu(sweep: str) -> None:
    """The multi-device sweeps re-exec children pinned to fake CPU devices
    (the parent already holds the backend).  On a TPU host their rows would
    time the CPU under a chip run's name, so refuse instead."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{sweep}: this sweep times CPU child processes on simulated "
            "devices; on a TPU host it would report CPU rows as a chip run. "
            "Run the sharded path on the chip through chip_smoke.py "
            "--four-chips instead"
        )


def bench_sharded_ingest(
    k: int = 4096, n: int = 4096, records: int = 20, iters: int = 3,
    shards=(1, 2, 8), n_devices: int = 8,
) -> list[dict]:
    """Row-sharded ingest across simulated CPU devices (subprocess).

    XLA device counts are fixed at process start, so the sweep re-execs
    this module with ``--xla_force_host_platform_device_count`` and parses
    the rows back.  On one physical CPU the fake devices share cores —
    the row tracks the *dispatch/collective* overhead trajectory of the
    shard_map path (the capacity win needs real devices), with the
    shards=1 engine row as the in-process baseline.
    """
    _refuse_cpu_children_on_tpu("sharded_ingest")
    cfg = {"k": k, "n": n, "records": records, "iters": iters,
           "shards": list(shards)}
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bank_bench", _SHARDED_WORKER_FLAG,
         json.dumps(cfg)],
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded worker failed (rc={proc.returncode}):\n{proc.stderr[-3000:]}"
        )
    return json.loads(proc.stdout.splitlines()[-1])


_FLEET_WORKER_FLAG = "--fleet-worker"
_FLEET_SKIP_RC = 75  # worker could not join the fleet; the point is skipped


def _fleet_worker(cfg: dict) -> int:
    """One process of a coordinated ``jax.distributed`` fleet (gloo CPU).

    Every process runs the same ingest loop over the same host stream —
    the SPMD contract; each uploads only the lanes its shard owns — with
    ``barrier``-fenced timed regions so the reported wall-clock is the
    fleet's (slowest process bounds), then process 0 prints the rows.
    """
    from repro.launch import distributed as dist

    try:
        dist.initialize(
            cfg.get("coordinator"),
            cfg["processes"],
            cfg.get("process_id"),
            local_device_count=1,
            timeout_s=cfg.get("timeout_s", 120),
        )
    except Exception as e:  # noqa: BLE001 - bootstrap failure -> skip point
        print(f"[fleet] bootstrap failed: {e!r}", file=sys.stderr)
        return _FLEET_SKIP_RC
    from repro.engine import ShardedBank

    spec = BucketSpec()
    k, n = cfg["k"], cfg["n"]
    records, iters = cfg["records"], cfg["iters"]
    shards = cfg["processes"]  # one device per process: shards == processes
    rng = np.random.default_rng(0)
    vals = (rng.pareto(1.0, n) + 1.0).astype(np.float32)
    ids = rng.integers(0, k, n).astype(np.int32)
    bank = ShardedBank(spec, k, num_shards=shards)
    bank.add(vals, ids)  # compile + warm
    jax.block_until_ready(bank.state)
    dist.barrier("fleet_warm")
    t0 = time.perf_counter()
    for _ in range(iters):
        for _ in range(records):
            bank.add(vals, ids)
        jax.block_until_ready(bank.state)
    dist.barrier("fleet_ingest")
    ingest = (time.perf_counter() - t0) / (iters * records)
    qs = [0.5, 0.95, 0.99]
    bank.rollup_quantiles(qs)  # compile the psum path
    dist.barrier("fleet_rollup_warm")
    t0 = time.perf_counter()
    for _ in range(iters):
        bank.rollup_quantiles(qs)
    dist.barrier("fleet_rollup")
    rollup = (time.perf_counter() - t0) / iters
    if dist.process_index() == 0:
        print(json.dumps([
            {
                "bench": "sharded_ingest",
                "K": k,
                "n_per_record": n,
                "processes": shards,
                "shards": shards,
                "ms_per_record": round(ingest * 1e3, 4),
                "rollup_ms": round(rollup * 1e3, 4),
                "impl": "jax_distributed_gloo",
            }
        ]))
    dist.barrier("fleet_done")
    dist.shutdown()
    return 0


def _fleet_point(
    k: int, n: int, records: int, iters: int, p_count: int
) -> list[dict]:
    """Launch ``p_count`` coordinated worker processes; parse proc 0's rows."""
    env = dict(os.environ)
    for var in ("XLA_FLAGS", "REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
                "REPRO_PROCESS_ID", "REPRO_LOCAL_DEVICES"):
        env.pop(var, None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), "..", "src")
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    cfg = {"k": k, "n": n, "records": records, "iters": iters,
           "processes": p_count, "timeout_s": 120}
    if p_count > 1:
        with socket.socket() as sock:
            sock.bind(("localhost", 0))
            cfg["coordinator"] = f"localhost:{sock.getsockname()[1]}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "benchmarks.bank_bench", _FLEET_WORKER_FLAG,
             json.dumps({**cfg, "process_id": pid})],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."),
        )
        for pid in range(p_count)
    ]
    outs = [proc.communicate(timeout=1800) for proc in procs]
    rcs = [proc.returncode for proc in procs]
    if any(rc == _FLEET_SKIP_RC for rc in rcs):
        print(f"[fleet] {p_count}-process point skipped "
              "(jax.distributed could not bootstrap)", file=sys.stderr)
        return []
    if any(rc != 0 for rc in rcs):
        report = "\n".join(
            f"--- process {i} (rc={rc}) ---\n{e[-2000:]}"
            for i, (rc, (_, e)) in enumerate(zip(rcs, outs))
        )
        raise RuntimeError(f"fleet point ({p_count} processes) failed\n{report}")
    return json.loads(outs[0][0].strip().splitlines()[-1])


def bench_fleet_ingest(
    k: int = 1024, n: int = 4096, records: int = 10, iters: int = 2,
    processes=(1, 2, 8),
) -> list[dict]:
    """Multi-*process* sharded ingest: 1/2/8 coordinated OS processes.

    Unlike ``bench_sharded_ingest`` (fake devices in one process), each
    point here is a real ``jax.distributed`` fleet — separate processes,
    gloo collectives, coordinator handshake — with one device per process,
    so shard count == process count.  On one physical CPU the processes
    share cores; the rows track the *cross-process* dispatch/collective
    overhead trajectory (ingest is collective-free by design — the routed
    batch is never replicated — while ``rollup_ms`` carries the one psum).
    Points whose fleet cannot bootstrap are skipped with a note.
    """
    _refuse_cpu_children_on_tpu("sharded_ingest_fleet")
    rows: list[dict] = []
    for p_count in processes:
        rows.extend(_fleet_point(k, n, records, iters, p_count))
    return rows


def bench_bank_quantiles(k: int = 4096, n: int = 500_000, iters: int = 10) -> list[dict]:
    """Fused Algorithm 2 over all K rows and all qs (single query pass)."""
    spec = BucketSpec()
    rng = np.random.default_rng(0)
    values = jnp.asarray((rng.pareto(1.0, n) + 1.0).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, k, n).astype(np.int32))
    bank = jax.block_until_ready(
        sb.add(sb.empty(spec, k), values, ids, spec=spec)
    )
    qs = jnp.asarray([0.5, 0.95, 0.99])
    fn = jax.jit(lambda b, q: sb.quantiles(b, q, spec=spec))
    secs = _time(fn, bank, qs, iters=iters)
    return [
        {
            "bench": "bank_quantiles",
            "K": k,
            "qs": 3,
            "ms_per_query_pass": round(secs * 1e3, 3),
            "us_per_sketch": round(secs / k * 1e6, 3),
            "impl": "fused_cumsum_searchsorted",
        }
    ]


if __name__ == "__main__":
    # subprocess entries: the sharded sweep re-execs with XLA_FLAGS (device
    # counts are fixed at process start); the fleet sweep re-execs one
    # worker per simulated host
    if len(sys.argv) >= 3 and sys.argv[1] == _SHARDED_WORKER_FLAG:
        print(json.dumps(_sharded_worker(json.loads(sys.argv[2]))))
    elif len(sys.argv) >= 3 and sys.argv[1] == _FLEET_WORKER_FLAG:
        sys.exit(_fleet_worker(json.loads(sys.argv[2])))
    else:
        for row in bench_engine_ingest():
            print(row)
