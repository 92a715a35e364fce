#!/usr/bin/env python3
"""Bring-up smoke of the sketch service on a TPU, through its entry points.

    python chip_smoke.py                # phases "small" and "tall", one chip
    python chip_smoke.py --four-chips   # the row-sharded bank over 4 chips,
                                        # vs the same stream on one chip

Each phase builds the served stack a deployment runs -- ``KeyedWindow``
(device bank, optional slice ring) -> ``IngestGateway`` -> ``QuantileHTTPServer``
on 127.0.0.1 -- POSTs a seeded stream (``data/datasets.py`` shapes, keys
Zipf(0.99)-skewed), drives the drain ticks, and GETs ``/live``,
``/quantiles?window=``, ``/rollup`` (plain and windowed) and, after a flush
into the host aggregator, plain ``/quantiles``.  It fails (exit code != 0)
on any of:

* an answer outside alpha_L (the level-adjusted guarantee, plus the 1e-5
  that float32 key rounding at a bucket edge can add) of the exact quantile
  of the values that key / the fleet / the window received;
* an answer more than one bucket away from a host ``DDSketch``
  (``core/ddsketch.py``, Algorithms 1-4) fed the same stream;
* a non-200 reply; gateway drain errors, shed mass, or ingested != submitted;
* a fallback to the XLA reference in ``kernels.ops.dispatch_stats()``;
* a main-path engine executable without a ``tpu_custom_call`` (a Pallas
  kernel compiled by Mosaic);
* a platform other than ``tpu``: there is no CPU branch.

Some keys span 20+ decades: their first value (1e-10) lies below the bank's
level-0 range, so reactive collapse folds the row (``fold_pairs``) before
their ``span``-shaped traffic (up to 1.9e12) arrives.  That first value is
clamped into the range's bottom bucket (counted as underflow) but keeps its
rank: an answer that reads rank 0 of such a key is not checked (a cold key
may hold only a handful of values).  The qs are dyadic so that the device's
float32 rank ``q * (n - 1)`` is exact, like the host's.

Earlier stdout lines carry one JSON record per phase (kernels seen, compile
seconds as set-up time, max relative error, gateway stats, peak device
bytes); the last line is the run's verdict and device, and only a run that
passed every check prints it.  The persistent compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import re
import sys
import time
from pathlib import Path
from urllib.parse import quote
from urllib.error import HTTPError
from urllib.request import urlopen

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

KEY_QS = (0.5, 0.75, 0.9375, 0.984375)  # dyadic: exact float32 ranks
ROLLUP_QS = (0.5, 0.75, 0.9375)  # fewer fraction bits: fleet-sized n
WIDE_EVERY = 16  # keys i % WIDE_EVERY == 7 span 20+ decades
NEG_EVERY = 5  # keys i % NEG_EVERY == 1 carry negated "power" values
TRIGGER = 1e-10  # below the level-0 range: folds a wide key on first sight
# the device keys values in float32 (ceil(log(x) * multiplier)): a value
# within ~1e-6 (relative) of a bucket edge may land in the neighbouring
# bucket, which can add that much to the alpha_L bound
KEY_ROUNDING = 1e-5
# the ingest kernel every phase must run (per shard in the sharded phase)
INGEST_KERNEL = "segment_histogram"
# engine executables of the main path; each must hold a Mosaic kernel
# (snapshot / reset / slab_seal only move data)
KERNEL_PATHS = ("ingest", "quantiles", "rollup", "window_query",
                "window_rollup", "slab_merge_node")


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# --------------------------------------------------------------------- #
# the stream
# --------------------------------------------------------------------- #
def _zipf_probs(num_keys: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Zipf(0.99) popularity over the keys (YCSB's default constant); the
    rank -> key order is a seeded permutation so hot keys are scattered."""
    p = 1.0 / np.arange(1, num_keys + 1, dtype=np.float64) ** 0.99
    perm = rng.permutation(num_keys)
    probs = np.empty(num_keys)
    probs[perm] = p / p.sum()
    return probs, perm


def make_stream(num_keys: int, ticks: int, per_tick: int, seed: int):
    """``ticks`` lists of ``(key, float32 values)`` batches, deterministic
    in ``seed``.  Key families: wide keys (one trigger value in tick 0,
    then ``span`` traffic), negated ``power`` keys, ``pareto`` keys."""
    from repro.data.datasets import make_dataset

    rng = np.random.default_rng(seed)
    probs, perm = _zipf_probs(num_keys, rng)
    wide = np.arange(num_keys) % WIDE_EVERY == 7
    neg = (np.arange(num_keys) % NEG_EVERY == 1) & ~wide
    stream = []
    for t in range(ticks):
        n = per_tick - (int(wide.sum()) if t == 0 else 0)
        lane_key = rng.choice(num_keys, size=n, p=probs)
        sub = seed * 1000 + t
        vals = make_dataset("pareto", n, seed=sub).astype(np.float32)
        power = make_dataset("power", n, seed=sub).astype(np.float32)
        vals = np.where(neg[lane_key], -power, vals)
        if t > 0:
            span = make_dataset("span", n, seed=sub).astype(np.float32)
            vals = np.where(wide[lane_key], span, vals)
        order = np.argsort(lane_key, kind="stable")
        lane_key, vals = lane_key[order], vals[order]
        cuts = np.flatnonzero(np.diff(lane_key)) + 1
        batches = [
            (f"/k{int(ks[0]):04d}", vs)
            for ks, vs in zip(np.split(lane_key, cuts), np.split(vals, cuts))
        ]
        if t == 0:
            batches += [
                (f"/k{i:04d}", np.asarray([TRIGGER], np.float32))
                for i in np.flatnonzero(wide)
            ]
        stream.append(batches)
    hot = [f"/k{int(i):04d}" for i in perm[:16]]
    wide_keys = [f"/k{int(i):04d}" for i in np.flatnonzero(wide)]
    return stream, hot, wide_keys


# --------------------------------------------------------------------- #
# references: exact order statistics and the plain host DDSketch
# --------------------------------------------------------------------- #
class Reference:
    """Per-(key, tick) values and host ``DDSketch``es of the stream."""

    def __init__(self, spec):
        self.spec = spec
        self.values: dict[tuple[str, int], list[np.ndarray]] = {}
        self.sketches: dict[tuple[str, int], object] = {}

    def add(self, tick: int, key: str, vals: np.ndarray) -> None:
        from repro.core.ddsketch import DDSketch

        self.values.setdefault((key, tick), []).append(vals)
        sk = self.sketches.get((key, tick))
        if sk is None:
            # max_bins=None: the unbounded Algorithms 1-4 sketch
            sk = DDSketch(self.spec.relative_accuracy, max_bins=None,
                          mapping=self.spec.mapping)
            self.sketches[(key, tick)] = sk
        for v in vals.tolist():
            sk.add(v)

    def exact(self, keys, ticks) -> np.ndarray:
        parts = [v for k in keys for t in ticks for v in self.values.get((k, t), [])]
        return np.sort(np.concatenate(parts)) if parts else np.zeros(0, np.float32)

    def host(self, keys, ticks, level: int):
        from repro.core.ddsketch import DDSketch

        out = DDSketch(self.spec.relative_accuracy, max_bins=None,
                       mapping=self.spec.mapping)
        for k in keys:
            for t in ticks:
                sk = self.sketches.get((k, t))
                if sk is not None:
                    out.merge(sk)
        out.collapse_to(level)
        return out


class Checker:
    """alpha_L vs exact, one-bucket vs host; tracks the worst errors."""

    def __init__(self, spec):
        self.spec = spec
        self.max_rel_err = 0.0  # |est - exact| / |exact|
        self.max_err_over_bound = 0.0  # the same over alpha_L
        self.checked = 0
        self.host_exact = 0  # answers bit-equal to the host sketch's
        self.skipped_clamped = 0  # rank-0 reads of a clamped first value
        self.failures: list[str] = []  # every answer outside its bound

    def _expect(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)

    def check(self, what: str, got, exact_sorted: np.ndarray, host, level: int,
              qs, clamped_first: bool = False) -> None:
        """``clamped_first``: the smallest value was clamped on ingest, so
        an answer that reads rank 0 holds the clamp, not the value."""
        from repro.core.jax_sketch import effective_alpha

        alpha = effective_alpha(self.spec, level)
        n = exact_sorted.size
        _check(len(got) == len(qs), f"{what}: {len(got)} answers for {len(qs)} qs")
        if n == 0:  # nothing recorded there: the answer is empty (NaN/null)
            _check(all(v is None or math.isnan(v) for v in got),
                   f"{what}: {got} for a key with no values in range")
            return
        for q, est in zip(qs, got):
            _check(est is not None and math.isfinite(est),
                   f"{what} q={q}: non-finite answer {est!r}")
            _check(q * (n - 1) < 2**24, f"{what}: rank {q}*{n - 1} not exact in f32")
            rank = int(math.floor(q * (n - 1)))
            if clamped_first and rank == 0:
                self.skipped_clamped += 1
                continue
            exact = float(exact_sorted[rank])
            err = abs(est - exact)
            rel = err / abs(exact) if exact else err
            self.max_rel_err = max(self.max_rel_err, rel)
            self.max_err_over_bound = max(self.max_err_over_bound, rel / alpha)
            self._expect(err <= (alpha + KEY_ROUNDING) * abs(exact),
                   f"{what} q={q}: {est!r} vs exact {exact!r} exceeds "
                   f"alpha_L={alpha:.6g} (level {level})")
            want = host.quantile(q)
            # independent host ingestion keys in float64, the device in
            # float32: a value on a bucket edge may land one bucket over
            self._expect(
                abs(est - want) <= 2.0 * (1.0 + alpha) * alpha * abs(want) + 1e-30,
                f"{what} q={q}: {est!r} vs host DDSketch {want!r} "
                f"(level {level}) is more than one bucket apart")
            self.host_exact += abs(est - want) <= 1e-6 * abs(want)
            self.checked += 1


# --------------------------------------------------------------------- #
# HTTP
# --------------------------------------------------------------------- #
def _get(url: str) -> dict:
    try:
        with urlopen(url, timeout=120) as resp:
            _check(resp.status == 200, f"GET {url}: HTTP {resp.status}")
            return json.loads(resp.read())
    except HTTPError as e:
        raise SmokeFailure(f"GET {url}: HTTP {e.code} {e.read()[:300]!r}") from e


def _qparam(qs) -> str:
    return ",".join(repr(float(q)) for q in qs)


# --------------------------------------------------------------------- #
# compile time and kernels seen
# --------------------------------------------------------------------- #
class CompileClock:
    """Sums JAX's own trace/lower/compile duration events (set-up time)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


_KERNEL_RE = re.compile(r"jit\((\w+?)_pallas\)/pallas_call")


def executable_kernels(engine) -> dict[str, list[str]]:
    """``{executable path: [Pallas kernels compiled into it]}``."""
    out: dict[str, list[str]] = {}
    for key, exe in engine._cache.items():
        text = exe.as_text()
        kernels = sorted({
            m.group(1)
            for line in text.splitlines()
            if "tpu_custom_call" in line
            for m in [_KERNEL_RE.search(line)] if m
        })
        if not kernels and "tpu_custom_call" in text:
            kernels = ["unnamed"]
        out.setdefault(str(key[0]), [])
        out[str(key[0])] = sorted(set(out[str(key[0])]) | set(kernels))
    return out


# --------------------------------------------------------------------- #
# one phase: stack up, ingest over HTTP, query over HTTP, check
# --------------------------------------------------------------------- #
def run_phase(name: str, *, capacity: int, ticks: int, per_tick: int, seed: int,
              num_slices: int | None = None, num_shards: int | None = None,
              windows=("1m", "3m", "1h"), sample_keys: int = 48,
              mirror=None) -> dict:
    """Run one phase end to end; raises ``SmokeFailure`` on any check.

    ``mirror(tick, key_to_row, batches, sealed)`` is called after each
    drain tick (the four-chip phase feeds its one-chip twin from it).
    Returns the phase record plus the live objects the caller may probe.
    """
    from repro.core.jax_sketch import BucketSpec
    from repro.kernels import ops
    from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
    from repro.launch.ingest_client import IngestClient
    from repro.launch.ingest_gateway import IngestGateway
    from repro.telemetry.keyed import KeyedAggregator, KeyedWindow

    spec = BucketSpec()  # alpha = 0.01, m = 2048: the paper's geometry
    ops.reset_dispatch_stats()
    window = KeyedWindow(spec, capacity=capacity, num_shards=num_shards,
                         num_slices=num_slices,
                         slice_seconds=None if num_slices is None else 60.0)
    agg = KeyedAggregator(spec)
    gw = IngestGateway(window, max_queue_values=4 * per_tick, start=False)
    stream, hot, wide_keys = make_stream(capacity, ticks, per_tick, seed)
    wide = set(wide_keys)
    ref = Reference(spec)
    checker = Checker(spec)
    submitted = 0
    t_phase = time.perf_counter()
    with QuantileHTTPServer(TelemetryFacade(window, agg), gateway=gw) as server:
        client = IngestClient(server.url, max_retries=0)
        for t, batches in enumerate(stream):
            for key, vals in batches:
                receipt = client.ingest(key, vals.tolist())
                _check(receipt.get("status") == "accepted"
                       and receipt.get("shed") == 0,
                       f"POST /ingest {key}: {receipt}")
                submitted += vals.size
                ref.add(t, key, vals)
            gw.flush()  # one drain tick: every queued batch, one engine call
            sealed = num_slices is not None and t < ticks - 1
            if mirror is not None:
                mirror(t, dict(window.key_to_row), batches, sealed)
            if sealed:
                window.advance_slice()  # what the gateway's slice clock calls
        st = gw.stats()
        _check(st["drain_errors"] == 0, f"drain errors: {st['last_drain_error']}")
        _check(st["shed_mass"] == 0 and st["expired_batches"] == 0,
               f"shed mass: {st}")
        _check(st["ingested_values"] == submitted == st["accepted_values"],
               f"ingested {st['ingested_values']} != submitted {submitted}")
        _check(st["engine_calls"] == ticks, f"{st['engine_calls']} engine calls "
               f"for {ticks} drain ticks")
        levels = window.levels()
        _check(all(levels[k] >= 1 for k in wide_keys),
               "reactive collapse did not fold the wide keys")
        live_ticks = [ticks - 1] if num_slices else list(range(ticks))
        live_mass = sum(v.size for t in live_ticks for _, v in stream[t])
        _check(window.total_mass() == float(live_mass),
               f"live bank mass {window.total_mass()} != {live_mass} sent")

        url = server.url
        rng = np.random.default_rng(seed + 1)
        keys = sorted(k for k in window.key_to_row if k != "__other__")
        sample = sorted(set(hot) | set(wide_keys)
                        | set(rng.choice(keys, size=min(sample_keys, len(keys)),
                                         replace=False).tolist()))
        answers = {}

        # /live: every key's live-slice quantiles in one fused query
        live = _get(f"{url}/live?q={_qparam(KEY_QS)}")["endpoints"]
        _check(set(live) == set(keys), "/live key set differs from the keys sent")
        for k in keys:
            checker.check(f"/live {k}", live[k], ref.exact([k], live_ticks),
                          ref.host([k], live_ticks, levels[k]), levels[k], KEY_QS,
                          clamped_first=k in wide and 0 in live_ticks)
        answers["live"] = live
        # /rollup: the fleet view of the live slice
        top = max(levels.values())
        roll = _get(f"{url}/rollup?q={_qparam(ROLLUP_QS)}")["quantiles"]
        checker.check("/rollup", roll, ref.exact(keys, live_ticks),
                      ref.host(keys, live_ticks, top), top, ROLLUP_QS)
        answers["rollup"] = roll
        # windows over the slice ring: per key and fleet-wide
        for w in windows if num_slices else ():
            nw = window.resolve_window(window=w)
            wt = list(range(max(0, ticks - nw), ticks))
            for k in sample:
                got = _get(f"{url}/quantiles?endpoint={quote(k)}"
                           f"&q={_qparam(KEY_QS)}&window={w}")["quantiles"]
                checker.check(f"/quantiles {k} window={w}", got,
                              ref.exact([k], wt), ref.host([k], wt, levels[k]),
                              levels[k], KEY_QS, clamped_first=k in wide and 0 in wt)
            got = _get(f"{url}/rollup?q={_qparam(ROLLUP_QS)}&window={w}")["quantiles"]
            checker.check(f"/rollup window={w}", got, ref.exact(keys, wt),
                          ref.host(keys, wt, top), top, ROLLUP_QS)
        stats = _get(f"{url}/stats")
        _check(stats["gateway"]["drain_errors"] == 0, "drain errors after reads")
        kernels = executable_kernels(window.engine)
        fallbacks = ops.dispatch_stats()
        _check(ops.fallback_count() == 0,
               f"auto dispatch fell back to the XLA reference: {fallbacks}")
        # host tier: flush the live bank into the aggregator, read it back
        agg.flush(window)
        for k in sample:
            if not ref.exact([k], live_ticks).size:
                continue  # nothing live to flush: the aggregator never saw k
            got = _get(f"{url}/quantiles?endpoint={quote(k)}"
                       f"&q={_qparam(KEY_QS)}")["quantiles"]
            checker.check(f"/quantiles {k}", got, ref.exact([k], live_ticks),
                          ref.host([k], live_ticks, levels[k]), levels[k], KEY_QS,
                          clamped_first=k in wide and 0 in live_ticks)
    gw.stop()
    record = {
        "phase": name,
        "keys": len(keys),
        "engine_rows": window.engine.num_sketches,
        "shards": 1 if num_shards is None else num_shards,
        "ticks": ticks,
        "values_per_tick": per_tick,
        "submitted": submitted,
        "answers_checked": checker.checked,
        "rank0_clamped_skipped": checker.skipped_clamped,
        "host_bit_equal_frac": checker.host_exact / max(checker.checked, 1),
        "max_rel_err": checker.max_rel_err,
        "max_err_over_alpha_L": checker.max_err_over_bound,
        "levels_seen": sorted(set(levels.values())),
        "kernels": kernels,
        "gateway": {k: st[k] for k in ("ticks", "engine_calls", "accepted_values",
                                       "ingested_values", "shed_mass",
                                       "drain_errors", "max_queue_depth")},
        "dispatch_fallbacks": ops.fallback_count(),
        "phase_wall_s": time.perf_counter() - t_phase,
        "failed_answers": len(checker.failures),
        "first_failures": checker.failures[:8],
    }
    return {"record": record, "window": window, "answers": answers,
            "keys": keys}


def _device_checks(result: dict, name: str) -> None:
    """Chip-only checks: every main-path executable holds a Mosaic kernel,
    and the phase's ingest kernel is among them."""
    kernels = result["record"]["kernels"]
    for path, ks in kernels.items():
        if path in KERNEL_PATHS:
            _check(bool(ks), f"{name}: engine executable {path!r} has no "
                   "tpu_custom_call (no compiled Pallas kernel)")
    used = {k for ks in kernels.values() for k in ks}
    _check(INGEST_KERNEL in used,
           f"{name}: {INGEST_KERNEL} kernel not compiled ({sorted(used)})")


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------- #
# phases as the chip runs them (sizes overridable for off-chip rehearsal)
# --------------------------------------------------------------------- #
def phase_small(seed: int, **sizes) -> dict:
    """K=512 (1024 stacked rows -> segment_histogram), 64-slice ring (a
    (127, 512, 2048) x 2 slab, ~1.06 GB, plus its snapshot)."""
    cfg = dict(capacity=511, num_slices=64, ticks=6, per_tick=65536) | sizes
    return run_phase("small", seed=seed, **cfg)


def phase_tall(seed: int, **sizes) -> dict:
    """K=4096, the deployments' bank (8192 stacked rows -> segment_histogram)."""
    cfg = dict(capacity=4095, ticks=4, per_tick=16384) | sizes
    return run_phase("tall", seed=seed + 100, **cfg)


def _same(a, b) -> bool:
    """Equal floats, or both empty (NaN / JSON null)."""
    def empty(v):
        return v is None or math.isnan(v)

    return (empty(a) and empty(b)) or (not empty(a) and not empty(b) and a == b)


def phase_sharded(seed: int, *, num_shards: int = 4, **sizes) -> dict:
    """Row-sharded bank over ``num_shards`` chips (``keys`` mesh, 512 rows
    and a segment_histogram ingest per shard, psum rollup), compared with
    the same stream in a one-chip ``SketchEngine(K=capacity+1)``: per-key
    and rollup quantiles must be equal."""
    import jax

    from repro.core.jax_sketch import BucketSpec
    from repro.engine import SketchEngine

    cfg = dict(capacity=2047, ticks=4, per_tick=65536, num_slices=8,
               windows=("1m", "3m", "8m")) | sizes
    one = SketchEngine(BucketSpec(), cfg["capacity"] + 1)
    state = {"bank": one.new_bank()}

    def mirror(tick, key_to_row, batches, sealed):
        state["key_to_row"] = key_to_row  # the rows /live answered from
        vals = np.concatenate([v for _, v in batches])
        rows = np.concatenate([np.full(v.size, key_to_row[k], np.int32)
                               for k, v in batches])
        state["bank"], _, _ = one.ingest(state["bank"], vals, rows, threshold=0.0)
        if sealed:
            state["bank"] = one.reset(state["bank"])  # levels survive, as in a seal

    res = run_phase("sharded", seed=seed + 200, num_shards=num_shards,
                    mirror=mirror, **cfg)
    window = res["window"]
    devs = set(window.engine.mesh.devices.flat)
    _check(len(devs) == num_shards,
           f"keys mesh spans {len(devs)} devices, want {num_shards}")
    _check(all(len(leaf.sharding.device_set) == num_shards
               for leaf in jax.tree.leaves(window.bank)),
           "bank leaves are not spread over every shard")
    per_key = np.asarray(one.quantiles(state["bank"], KEY_QS))
    rollup = np.asarray(one.rollup_quantiles(state["bank"], ROLLUP_QS))
    got_live, got_roll = res["answers"]["live"], res["answers"]["rollup"]
    # not window.key_to_row: the host-tier flush at the end of the phase
    # evicts keys that sat idle in the live slice
    key_to_row = state["key_to_row"]
    mismatch = [
        k for k in res["keys"]
        if not all(_same(float(a), b)
                   for a, b in zip(per_key[key_to_row[k]], got_live[k]))
    ]
    _check(not mismatch, f"sharded per-key quantiles differ from one chip for "
           f"{len(mismatch)} keys, e.g. {mismatch[:3]}")
    _check(all(_same(float(a), b) for a, b in zip(rollup, got_roll)),
           f"sharded rollup {got_roll} != one-chip rollup {rollup.tolist()}")
    res["record"]["one_chip_equal"] = {"keys": len(res["keys"]),
                                       "rollup_qs": len(ROLLUP_QS)}
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded bank over 4 chips and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform!r}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT / ".jax_cache")
    print(json.dumps({"compile_cache": cache, "device_kind": devices[0].device_kind,
                      "devices": len(devices)}), flush=True)
    clock = CompileClock()
    phases = (
        [("sharded", phase_sharded)] if args.four_chips
        else [("small", phase_small), ("tall", phase_tall)]
    )
    failed = []
    for name, phase in phases:
        c0 = clock.seconds
        try:
            res = phase(args.seed)
            rec = res["record"]
            rec["compile_s"] = clock.seconds - c0  # set-up, not a device metric
            rec["peak_bytes_in_use"] = _peak_bytes()
            print(json.dumps(rec), flush=True)
            _device_checks(res, name)
            _check(not rec["failed_answers"], f"{name}: {rec['failed_answers']} "
                   f"answers out of bounds, e.g. {rec['first_failures'][:2]}")
        except SmokeFailure as e:  # go on: the other phases still report
            print(json.dumps({"phase": name, "failed": str(e)}), flush=True)
            failed.append(name)
        res = None
        gc.collect()
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
