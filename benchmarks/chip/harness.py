"""One run of one cell: the served stack, its load, its window, its check.

The process that imports this holds the chip.  It builds the stack as a
deployment runs it -- ``KeyedWindow`` (a slice ring where the configuration
has one) behind ``WindowProxy``, ``IngestGateway`` with its own drain
thread and slice clock, ``QuantileHTTPServer`` with its ``QueryPlanner`` on
127.0.0.1 -- while the load generators (``loadgen.py``, separate processes
that never import JAX) build their bodies from the seed.  Set-up then
folds the wide keys, fills the ring, and compiles every shape the cell's
traffic uses.  The window runs for ``seconds``; nothing compiles inside it.
Afterwards the gateway drains, the state is read back over HTTP, and every
answer is held to the plain reference (``reference.py``).

Everything that belongs to one configuration, traffic mix or metric is a
file found by name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path
from urllib.parse import parse_qs, urlparse

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE / "metrics"))

import proxy  # noqa: E402
import reference  # noqa: E402
import stream  # noqa: E402

# quantiles every answer of the post-window check asks for: both extremes
# (a dropped or altered value moves them) and dyadic inner ranks
CHECK_QS = (0.0, 0.25, 0.5, 0.75, 0.9375, 0.984375, 1.0)
LEAD_S = 0.5  # gateway start (slice clock origin) to the window's start


class GcClock:
    """Pauses of the server process's cyclic garbage collector, by
    generation (``gc.callbacks``): a host stall the device trace shows only
    as an idle gap."""

    def __init__(self):
        import gc

        self.pauses: list[tuple[int, float, float]] = []  # (generation, start, s)
        self._t = 0.0
        self._gc = gc
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.pauses.append((info["generation"], self._t, time.monotonic() - self._t))

    def summary(self, t0: float, t1: float) -> dict:
        inwin = [(g, d) for g, t, d in self.pauses if t0 <= t < t1]
        full = [d for g, d in inwin if g == 2]
        return {"collections": len(inwin), "gen2": len(full),
                "gen2_max_s": max(full, default=0.0),
                "total_s": sum(d for _, d in inwin)}

    def close(self) -> None:
        self._gc.callbacks.remove(self._on_gc)


class CompileClock:
    """JAX's own compile-duration events: set-up seconds and a count, so a
    compile inside the window shows."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1


class StallWatch:
    """Host stalls of the server process, and what it was doing in them.

    A heartbeat thread wakes every ``BEAT_S`` and keeps its own late
    wake-ups (a thread holding the GIL, or the process not scheduled, makes
    it late), and the stack of the thread inside a window state change
    (``WindowProxy.busy``) once that change has run for ``LONG_S``.  It
    reads other threads' frames only while it holds the GIL.
    """

    BEAT_S = 0.02
    LONG_S = 0.25

    def __init__(self, win):
        self.win = win
        self.late: list[tuple[float, float]] = []  # (wake time, seconds late)
        self.long_calls: list[tuple[str, float, list[str]]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)
        self._thread.start()

    def _beat(self) -> None:
        seen = None
        t_prev = time.monotonic()
        while not self._stop.is_set():
            time.sleep(self.BEAT_S)
            now = time.monotonic()
            if now - t_prev > 2 * self.BEAT_S:
                self.late.append((now, now - t_prev - self.BEAT_S))
            t_prev = now
            busy = self.win.busy
            if busy is not None and busy is not seen and now - busy[1] > self.LONG_S:
                seen = busy
                frame = sys._current_frames().get(busy[2])
                if frame is not None:
                    stack = [f"{Path(f.filename).name}:{f.lineno} {f.name}"
                             for f in traceback.extract_stack(frame)[-8:]]
                    self.long_calls.append((busy[0], busy[1], stack))
                del frame

    def close(self) -> None:
        self._stop.set()
        self._thread.join()

    def summary(self, t0: float, t1: float) -> dict:
        late = [d for t, d in self.late if t0 <= t < t1]
        return {"heartbeat_late_max_s": max(late, default=0.0),
                "heartbeat_late_over_long_s": sum(d > self.LONG_S for d in late),
                "long_calls": [{"kind": k, "t": t - t0, "stack": st}
                               for k, t, st in self.long_calls if t0 <= t < t1][:4]}


# --------------------------------------------------------------------- #
# specs found by name
# --------------------------------------------------------------------- #
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, name: str, root: Path = ROOT):
    """(cell, config file contents, traffic file contents, metric entries)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------- #
# load generators
# --------------------------------------------------------------------- #
def stream_keys(config: dict, traffic: dict) -> int:
    """The configuration's keys (``hosts``); probe keys take rows beside them."""
    probes = sum(s.get("probe_keys", 0) for s in traffic["streams"])
    if config["hosts"] + probes > config["capacity"]:
        raise ValueError(f"{config['hosts']} keys and {probes} probe keys "
                         f"exceed the bank's {config['capacity']} rows")
    return config["hosts"]


class Generators:
    """The traffic mix's generator processes (started before JAX is)."""

    def __init__(self, config: dict, traffic: dict, seed: int, tmp: Path):
        self.procs: list[tuple[dict, int, subprocess.Popen, Path]] = []
        nkeys = stream_keys(config, traffic)
        env = {k: v for k, v in os.environ.items() if not k.startswith("JAX")}
        for si, s in enumerate(traffic["streams"]):
            for p in range(s["processes"]):
                out = tmp / f"gen{si}_{p}"
                spec = {"stream": s, "seed": seed, "proc": p, "nprocs": s["processes"],
                        "num_keys": nkeys, "families": config["families"],
                        "out": str(out)}
                path = tmp / f"gen{si}_{p}.json"
                path.write_text(json.dumps(spec))
                proc = subprocess.Popen(
                    [sys.executable, str(HERE / "loadgen.py"), str(path)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
                self.procs.append((s, p, proc, out))

    def wait_ready(self) -> None:
        for s, p, proc, _ in self.procs:
            line = proc.stdout.readline().strip()
            if line != "ready":
                raise RuntimeError(f"generator {s['kind']}[{p}] failed to start: {line!r}")

    def go(self, url: str, t0: float, seconds: float) -> None:
        msg = json.dumps({"url": url, "t0": t0, "seconds": seconds}) + "\n"
        for _, _, proc, _ in self.procs:
            proc.stdin.write(msg)
            proc.stdin.flush()

    def results(self, timeout: float) -> list[tuple[dict, int, dict, dict | None]]:
        """(stream, proc, request records, body pool) of every process."""
        out = []
        deadline = time.monotonic() + timeout
        for s, p, proc, path in self.procs:
            proc.wait(max(1.0, deadline - time.monotonic()))
            if proc.returncode != 0:
                raise RuntimeError(f"generator {s['kind']}[{p}] exited {proc.returncode}")
            recs = dict(np.load(str(path) + ".npz", allow_pickle=False))
            pool_path = Path(str(path) + ".pool.npz")
            pool = dict(np.load(pool_path)) if pool_path.exists() else None
            out.append((s, p, recs, pool))
        return out

    def stop(self) -> None:
        for _, _, proc, _ in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
            for f in (proc.stdin, proc.stdout):
                if f is not None:
                    f.close()


# --------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------- #
def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build_window(config: dict, variant: str | None, spans: bool):
    import jax.numpy as jnp

    from repro.core.jax_sketch import BucketSpec
    from repro.telemetry.keyed import KeyedWindow

    spec = BucketSpec(relative_accuracy=config["relative_accuracy"],
                      num_buckets=config["num_buckets"], offset=config["bucket_offset"])
    counts = jnp.bfloat16 if variant == "control" else jnp.float32
    window = KeyedWindow(spec, capacity=config["capacity"],
                         collapse_threshold=config["collapse_threshold"],
                         num_slices=config.get("num_slices"),
                         slice_seconds=config.get("slice_seconds"),
                         counts_dtype=counts)
    return spec, proxy.WindowProxy(window, spans=spans)


def allocate_keys(win, config: dict, traffic: dict, bodies: reference.Bodies) -> None:
    """Give every key its row and fold the wide keys, then reset.

    One value per key: ``fold_trigger`` (below the level-0 range, so the
    row folds once and its ``span`` values fit) for wide keys, the key's
    own family for the rest; probe keys get the value just under their
    first probe.  The reset empties the bank and keeps rows and levels.
    """
    fam = config["families"]
    nkeys = stream_keys(config, traffic)
    wide, neg = stream.families(nkeys, fam)
    batches = []
    for i in range(nkeys):
        v = fam["fold_trigger"] if wide[i] else (-1.0 if neg[i] else 1.0)
        batches.append((stream.key_name(i), np.asarray([v], np.float32), None))
    for s in traffic["streams"]:
        for j in range(s.get("probe_keys", 0)):
            v = s["first_value"] / s["growth"]
            batches.append((f"/p{j}", np.asarray([v], np.float32), None))
    for k, v, _ in batches:
        bodies.add(k, v)
    win.record_batches(batches)
    win.reset()


def prefill(win, config: dict, traffic: dict, seed: int, bodies: reference.Bodies,
            max_lanes: int) -> None:
    """Seal ``num_slices`` slices of the traffic's stream into the ring."""
    s_ingest = next(s for s in traffic["streams"] if s["kind"].startswith("ingest"))
    sizes = s_ingest["values_per_post"]
    mean = sizes["low"] if sizes["high"] == sizes["low"] else \
        (sizes["high"] - sizes["low"]) / math.log(sizes["high"] / sizes["low"])
    per_slice = max(1, int(config["prefill"]["values_per_slice"] / mean))
    nkeys = stream_keys(config, traffic)
    keys, vals, _ = stream.make_pool(
        per_slice * config["num_slices"], stream.key_probs(nkeys, seed), sizes,
        config["families"], stream.sub_seed(seed, 2), text=False)
    for sl in range(config["num_slices"]):
        batch, lanes = [], 0
        for b in range(sl * per_slice, (sl + 1) * per_slice):
            v = vals[b]
            if lanes + v.size > max_lanes:
                win.record_batches(batch)
                batch, lanes = [], 0
            batch.append((stream.key_name(keys[b]), v, None))
            bodies.add(stream.key_name(keys[b]), v)
            lanes += v.size
        win.record_batches(batch)
        win.advance_slice()


def warm(win, config: dict, traffic: dict, max_queue: int) -> None:
    """Compile every shape the window will run: the padded ingest ladder
    (on a scratch bank) and, where the traffic reads, the planner's padded
    q-sets on the live and windowed paths and the snapshot copies."""
    import jax

    from repro.engine.tables import next_pow2

    engine = win.engine
    scratch = engine.new_bank()
    n = 32
    while n <= next_pow2(max_queue, 32):
        scratch, _, _ = engine.ingest(
            scratch, np.ones(n, np.float32), np.ones(n, np.int32),
            threshold=config["collapse_threshold"])
        n *= 2
    jax.block_until_ready(scratch)
    del scratch
    if not any(s["kind"] in ("reads_open", "probes") for s in traffic["streams"]):
        return
    snap = win.snapshot()
    for q in (1, 2, 4, 8):
        qs = list(np.linspace(0.0, 1.0, q))
        snap.row_quantiles(qs)
        snap.rollup_quantiles(qs)
        if win.ring is not None:
            snap.windowed_row_quantiles(qs, slices=2)
            snap.windowed_rollup(qs, slices=2)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# --------------------------------------------------------------------- #
# the check: read back over HTTP, hold every answer to the reference
# --------------------------------------------------------------------- #
def _get(url: str):
    import http.client

    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=120)
    try:
        conn.request("GET", u.path + ("?" + u.query if u.query else ""))
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, resp.getheader("ETag"), body
    finally:
        conn.close()


def _qparam(qs) -> str:
    return ",".join(repr(float(q)) for q in qs)


class Check:
    """Holds answers (parsed replies, with the version that answered them)
    against the reference at that version."""

    def __init__(self, config, traffic, bodies, log, win, seed):
        self.config = config
        self.seed = seed
        self.alpha = config["relative_accuracy"]
        self.state = reference.State(bodies, log)
        nkeys = stream_keys(config, traffic)
        self.stream_keys = [stream.key_name(i) for i in range(nkeys)]
        probe = [f"/p{j}" for s in traffic["streams"]
                 for j in range(s.get("probe_keys", 0))]
        self.keys = self.stream_keys + probe
        self.exact = reference.Exact(bodies, self.keys)
        wide, _ = stream.families(nkeys, config["families"])
        self.level = {k: int(wide[i]) for i, k in enumerate(self.stream_keys)}
        self.level.update({k: 0 for k in probe})
        self.fleet_level = int(wide.any())
        self.checker = reference.Checker(self.alpha)
        self.win = win
        self.unchecked = 0  # answers at a version the log does not hold
        self._counts: dict[tuple, np.ndarray] = {}

    def slices(self, params: dict) -> int | None:
        if "slices" in params:
            return int(params["slices"][0])
        if "window" in params:
            return int(self.win.resolve_window(window=params["window"][0]))
        return None

    def answer(self, path: str, etag: str | None, payload: dict) -> None:
        u = urlparse(path)
        params = parse_qs(u.query)
        version = int(str(etag).strip('"')) if etag else None
        if version not in self.state._at:
            self.unchecked += 1
            return
        w = self.slices(params)
        qs = [float(q) for q in params["q"][0].split(",")]
        if (version, w) not in self._counts:
            self._counts[(version, w)] = self.state.window(version, w)
        counts = self._counts[(version, w)]
        if u.path == "/rollup":
            exact = self.exact.fleet(counts, qs)
            self.checker.check(path, payload["quantiles"], exact, self.fleet_level)
        elif u.path == "/quantiles":
            k = params["endpoint"][0]
            exact = self.exact.per_key(counts, [k], qs)[k]
            self.checker.check(path, payload["quantiles"], exact, self.level[k])
        elif u.path == "/live":
            got = payload["endpoints"]
            if set(got) != set(self.keys):
                self.checker.empty_mismatch += len(set(got) ^ set(self.keys))
            exact = self.exact.per_key(counts, self.keys, qs)
            for k in self.keys:
                if k in got:
                    self.checker.check(f"/live {k}", got[k], exact[k], self.level[k])

    def window_keys(self) -> list[str]:
        """Keys whose windowed answers are read back: all of them, or a
        sample of ``check_window_keys`` drawn from the seed, with the probe
        keys and the hottest key in it."""
        n = self.config.get("check_window_keys")
        if n is None or n >= len(self.keys):
            return self.keys
        rng = np.random.default_rng(stream.sub_seed(self.seed, 21))
        probs = stream.key_probs(len(self.stream_keys), self.seed)
        pick = {int(np.argmax(probs))}
        pick.update(rng.choice(len(self.stream_keys), size=n, replace=False).tolist())
        return [self.stream_keys[i] for i in sorted(pick)] + \
            self.keys[len(self.stream_keys):]

    def read_back(self, url: str) -> int:
        """Every key's live answers, the live rollup, and per key and fleet
        over each of the configuration's windows; returns failed GETs."""
        paths = [f"/live?q={_qparam(CHECK_QS)}", f"/rollup?q={_qparam(CHECK_QS)}"]
        if self.win.ring is not None:
            keys = self.window_keys()
            for w in self.config["check_windows"]:
                paths.append(f"/rollup?q={_qparam(CHECK_QS)}&window={w}")
                paths += [f"/quantiles?endpoint={k}&q={_qparam(CHECK_QS)}&window={w}"
                          for k in keys]
        failed = 0
        for p in paths:
            status, etag, body = _get(url + p)
            if status != 200:
                failed += 1
                if len(self.checker.first_bad) < 8:
                    self.checker.first_bad.append(f"GET {p}: {status} {body[:200]!r}")
                continue
            self.answer(p, etag, json.loads(body))
        return failed


# --------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------- #
class Run:
    """Everything a run measured, for the metric readers (``metrics/``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _window_counters(gw, facade) -> dict:
    out = {"gateway": gw.stats()}
    if facade.planner is not None:
        out["planner"] = facade.planner.stats()
    return out


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, gens: Generators | None = None, tmp: Path | None = None,
             variant: str | None = None, fault=None, specs=None, log=print) -> dict:
    """Run one cell once; returns the result line's object.

    ``variant="control"`` runs the program with bfloat16 counts (the
    control of the correctness check); ``fault(window_proxy, gateway)``
    plants a fault (the check's tests); ``specs`` gives ``(cell, config,
    traffic)`` directly (small sizes in the tests).  None of the three is
    used by a measured run.
    """
    import jax

    from repro.kernels import ops
    from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
    from repro.launch.ingest_gateway import IngestGateway
    from repro.telemetry.keyed import KeyedAggregator

    cell, config, traffic = specs if specs is not None else cell_spec(bench, cell_name)
    own_tmp = tmp is None
    tmp = Path(tempfile.mkdtemp(prefix="ddsketch-bench-")) if own_tmp else tmp
    own_gens = gens is None
    gens = Generators(config, traffic, seed, tmp) if own_gens else gens
    server = gw = gc_clock = watch = None
    try:
        cache = enable_compile_cache()
        clock = CompileClock()
        gc_clock = GcClock()
        devices = jax.devices()[: cell["chips"]]
        ops.reset_dispatch_stats()
        gcfg = config["gateway"]
        spec, win = build_window(config, variant, spans=trace)
        bodies = reference.Bodies()
        allocate_keys(win, config, traffic, bodies)
        if config.get("num_slices"):
            prefill(win, config, traffic, seed, bodies, gcfg["max_queue_values"])
        warm(win, config, traffic, gcfg["max_queue_values"])
        jax.block_until_ready(win.bank)
        t_warm = time.monotonic()
        gens.wait_ready()
        facade = TelemetryFacade(win, KeyedAggregator(spec))
        gw = IngestGateway(win, max_queue_values=gcfg["max_queue_values"],
                           tick_interval_s=gcfg["tick_interval_s"],
                           shed_policy=gcfg["shed_policy"],
                           slice_interval_s=config.get("slice_interval_s"))
        if fault is not None:
            fault(win, gw)
        server = QuantileHTTPServer(facade, gateway=gw).start()
        watch = StallWatch(win)
        t0 = time.monotonic() + LEAD_S
        t_end = t0 + seconds
        gens.go(server.url, t0, seconds)
        tracer = None
        if trace:
            import trace_reduce

            tracer = trace_reduce.Tracer(tmp / "trace")
            tracer.start()
        compile_s_setup, compile_events0 = clock.seconds, clock.events
        misses0 = win.engine.cache_info()["misses"]
        time.sleep(max(0.0, t0 - time.monotonic()))
        if tracer is not None:
            tracer.mark("bench.window_start")
        before = _window_counters(gw, facade)
        time.sleep(max(0.0, t_end - time.monotonic()))
        after = _window_counters(gw, facade)
        if tracer is not None:
            tracer.mark("bench.window_end")
        compiles_in_window = (clock.events - compile_events0
                              + win.engine.cache_info()["misses"] - misses0)
        watch.close()
        results = gens.results(timeout=90.0)
        memory_peak = peak_bytes(devices)
        trace_summary = tracer.stop() if tracer is not None else None
        gw.stop()
        final = gw.stats()
        # ------------------------------------------------------------ #
        n_setup_bodies = len(bodies)
        check = _prepare_check(config, traffic, bodies, results, win, seed)
        failed_reads_back = check.read_back(server.url)
        server.shutdown()
        server = None
        run = Run(config=config, seconds=seconds, t0=t0, t_end=t_end,
                  setup_s=t0 - t_start, results=results, before=before, after=after,
                  log=win.log, reads=win.reads, trace=trace_summary,
                  device_kind=devices[0].device_kind, rows=win.engine.num_sketches,
                  bodies=bodies, n_setup_bodies=n_setup_bodies,
                  key_row={k: i for i, k in enumerate(check.keys)},
                  key_level=np.asarray([check.level[k] for k in check.keys]))
        summary = _summarise(run, check, final, compiles_in_window,
                             failed_reads_back, {k: v for k, v in ops.dispatch_stats().items()
                                                 if k.endswith("fallbacks") and v})
        summary["detail"]["after_window_s"] = time.monotonic() - t_end
        summary["detail"]["host_gc"] = gc_clock.summary(t0, t_end)
        summary["detail"]["host_stalls"] = watch.summary(t0, t_end)
        watch = None
        out = {
            "correct": summary["correct"],
            "attempted": summary["attempted"],
            "failed": summary["failed"],
            "metrics": {},
            "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                       "count": len(devices), "memory_peak_bytes": memory_peak},
        }
        for m in metrics_for(bench, cell_name, trace):
            value = load_reader(m["name"])(run)
            if value is not None and math.isfinite(value):
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if trace_summary is not None:
            out["device"]["busy_s"] = trace_summary["busy_s"]
            out["device"]["window_s"] = trace_summary["window_s"]
            out["breakdown"] = trace_summary["breakdown"]
        log(json.dumps({"cell": cell_name, "seed": seed, "compile_cache": cache,
                        "compile_s_setup": compile_s_setup,
                        "setup_to_warm_s": t_warm - t_start,
                        **summary["detail"]}))
        out["checks"] = summary["checks"]
        return out
    finally:
        if gc_clock is not None:
            gc_clock.close()
        if watch is not None:
            watch.close()
        if server is not None:
            server.shutdown()
        elif gw is not None:
            gw.stop(flush=False)
        if own_gens:
            gens.stop()
        if own_tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def _prepare_check(config, traffic, bodies, results, win, seed) -> Check:
    """Register every generated body with the reference (pools, probes)."""
    for s, p, recs, pool in results:
        if pool is not None:
            base = len(bodies)
            recs["body_global"] = recs["body"] + base if recs["body"].size else recs["body"]
            names = [stream.key_name(k) for k in pool["keys"].tolist()]
            for k, v in zip(names, np.split(pool["values"], np.cumsum(pool["sizes"])[:-1])):
                bodies.add(k, v)
        if s["kind"] == "probes":
            ids = []
            for i in recs["idx"].tolist():
                v = s["first_value"] * s["growth"] ** (i // s["probe_keys"])
                ids.append(bodies.add(f"/p{i % s['probe_keys']}", [v]))
            recs["body_global"] = np.asarray(ids, np.int64)
    return Check(config, traffic, bodies, win.log, win, seed)


def _summarise(run: Run, check: Check, final: dict, compiles_in_window: int,
               failed_reads_back: int, fallbacks: dict) -> dict:
    """Attempted / failed requests, the accounting, and every compared
    number beside its limit."""
    t0, t_end = run.t0, run.t_end
    acked = np.zeros(len(run.bodies), np.int64)
    attempted = failed = 0
    probes_unseen = 0
    for s, _, recs, _ in run.results:
        st = recs["status"]
        if s["kind"] in ("ingest_closed", "ingest_open", "probes"):
            ok = st == 200
            np.add.at(acked, recs["body_global"][ok], 1)
        if s["kind"] == "reads_open":
            due = recs["t_due"]
            inwin = (due >= t0) & (due < t_end)
            attempted += int(inwin.sum())
            failed += int((inwin & (st != 200)).sum())
            answers = json.loads(str(recs["answers"]))
            for path, etag, body in answers.values():
                check.answer(path, etag, json.loads(body))
        elif s["kind"] == "probes":
            attempted += st.size
            failed += int((st != 200).sum())
            probes_unseen += int(np.isnan(recs["t_seen"]).sum())
        else:
            t_send = recs["t_send"]
            inwin = (t_send >= t0) & (t_send < t_end)
            attempted += int(inwin.sum())
            failed += int((inwin & (st != 200) & (st != 429)).sum())
    state = check.state
    # set-up bodies (key rows, prefill) went in once, through the window;
    # every other body as often as a POST of it was acknowledged
    expected = acked.copy()
    expected[: run.n_setup_bodies] = 1
    post_mismatch = int(np.abs(state.ingested - expected).sum())
    acked_values = int(sum(run.bodies.values[i].size * int(c)
                           for i, c in enumerate(acked.tolist()) if c))
    ingest_gap = abs(int(final["ingested_values"]) - acked_values)
    checks = {
        "answers_out_of_bound": {"value": check.checker.out_of_bound, "limit": 0},
        "empty_mismatch": {"value": check.checker.empty_mismatch, "limit": 0},
        "ingest_gap_values": {"value": int(ingest_gap), "limit": 0},
        "post_mismatch": {"value": post_mismatch, "limit": 0},
        "unknown_batches": {"value": state.unknown + state.gaps, "limit": 0},
        "failed_requests": {"value": failed + failed_reads_back, "limit": 0},
        "probes_unseen": {"value": probes_unseen, "limit": 0},
        "window_compiles": {"value": int(compiles_in_window), "limit": 0},
        "drain_errors": {"value": int(final["drain_errors"]), "limit": 0},
    }
    ticks = _tick_profile(run)
    if _smallest_post(run.results) >= run.config["kernel_tile_lanes"]:
        # no POST of the mix is below one tile, so no tick may be: a
        # sub-tile tick runs the XLA path and means lanes went missing
        checks["subtile_ticks"] = {"value": ticks["subtile_ticks"], "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and \
        check.checker.checked > 0 and check.unchecked == 0
    detail = {
        "clients": _client_detail(run),
        "dispatch_fallbacks": fallbacks,
        "ticks": ticks,
        "answers_checked": check.checker.checked,
        "err_over_bound": check.checker.worst,
        "answers_unchecked": check.unchecked,
        "first_bad": check.checker.first_bad,
        "acked_values": acked_values,
        "gateway": {k: final[k] for k in ("ticks", "engine_calls", "accepted_values",
                                          "ingested_values", "rejected_batches",
                                          "max_queue_depth", "slice_advances")},
    }
    return {"correct": bool(correct), "attempted": attempted,
            "failed": failed + failed_reads_back, "checks": checks, "detail": detail}


def _pad_lanes(n: int) -> int:
    """The engine's padded batch: the next power of two, at least 32."""
    return max(32, 1 << (int(n) - 1).bit_length())


def _smallest_post(results) -> int:
    sizes = [1 if s["kind"] == "probes" else s["values_per_post"]["low"]
             for s, _, _, _ in results if s["kind"] != "reads_open"]
    return min(sizes, default=0)


def _tick_profile(run: Run) -> dict:
    """The window's ingest ticks from the state log: how many, how many were
    padded below the ingest kernel's tile (``kernel_tile_lanes``; the
    program runs those on its XLA path), and the longest state changes
    (ingest ticks and seals: offset in the window, seconds, lanes, ingest
    ticks since the last seal)."""
    tile = run.config["kernel_tile_lanes"]
    since, rows = 0, []
    for kind, _v, a, b, ids in run.log:
        lanes = sum(int(i[1]) for i in ids)
        if kind == "ingest":
            since += 1
        if run.t0 <= a < run.t_end:
            rows.append((kind, a - run.t0, b - a, lanes, since))
        if kind != "ingest":
            since = 0
    ingest = [r for r in rows if r[0] == "ingest"]
    return {"ingest_ticks": len(ingest),
            "subtile_ticks": sum(_pad_lanes(r[3]) < tile for r in ingest),
            "longest": [list(r) for r in sorted(rows, key=lambda r: -r[2])[:6]]}


def _client_detail(run: Run) -> dict:
    """Per stream kind: requests in the window, replies by status, latency
    median and 95th percentile, and how late the generators sent (median,
    99th percentile, and its growth from the first to the last quarter of
    the window: a growing lateness means the offered rate is not held)."""
    out = {}
    for kind in sorted({s["kind"] for s, _, _, _ in run.results}):
        parts = [recs for s, _, recs, _ in run.results if s["kind"] == kind]
        start = "t_due" if "t_due" in parts[0] else "t_send"
        t = np.concatenate([r[start] for r in parts])
        done = np.concatenate([r.get("t_done", r.get("t_seen")) for r in parts])
        st = np.concatenate([r["status"] for r in parts])
        inwin = (t >= run.t0) & (t < run.t_end)
        lat = (done - t)[inwin] * 1e3
        lat = lat[np.isfinite(lat)]
        d = {"n": int(inwin.sum()),
             "status": {str(k): int(v) for k, v in zip(*np.unique(st[inwin], return_counts=True))}}
        if lat.size:
            d["latency_ms_p50"] = float(np.percentile(lat, 50))
            d["latency_ms_p95"] = float(np.percentile(lat, 95))
        if "t_due" in parts[0] and "t_send" in parts[0]:
            due = np.concatenate([r["t_due"] for r in parts])[inwin]
            late = (np.concatenate([r["t_send"] for r in parts])[inwin] - due) * 1e3
            if late.size:
                q = (due - run.t0) / run.seconds
                first, last = late[q < 0.25], late[q >= 0.75]
                d["lateness_ms_p50"] = float(np.median(late))
                d["lateness_ms_p99"] = float(np.percentile(late, 99))
                if first.size and last.size:
                    d["lateness_growth_ms"] = float(np.median(last) - np.median(first))
        out[kind] = d
    return out

