"""Program spans (``repro.tracing``) on the write path and the ring.

A traced run must put the spans on the profiler's host plane, nested as
the work nests, and tie each POST to the tick that ingested it by the
gateway's batch ``seq``; with no profiler session the span totals must
reconcile with the gateway's own counters.  The ring's spans
(``window.ring_read`` inside a windowed ``planner.dispatch``,
``window.ring_seal`` inside ``window.advance_slice``) count what the
ring's counters count and reach ``/stats``.
"""

import json
import threading
import time
from urllib.request import Request, urlopen

import jax
import numpy as np
import pytest

from repro.core.jax_sketch import BucketSpec
from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
from repro.launch.ingest_gateway import GatewayOverloaded, IngestGateway
from repro.telemetry.keyed import KeyedAggregator, KeyedWindow
from repro.tracing import span, totals


def _window():
    return KeyedWindow(BucketSpec(), capacity=8)


def _post(url, key, values):
    req = Request(f"{url}/ingest", data=json.dumps({"key": key, "values": values}).encode(),
                  method="POST", headers={"Content-Type": "application/json"})
    with urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


def _strict_json(raw: bytes):
    def refuse(c):
        raise AssertionError(f"non-standard JSON constant {c!r}")

    return json.loads(raw, parse_constant=refuse)


def _host_lines(trace_dir):
    """Every host line of the trace: [(name, start_ns, end_ns, args)]."""
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    lines = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                              for e in line.events])
    return lines


def _count(name):
    return totals(name).get(name, {"count": 0})["count"]


def _await_count(name, n, timeout_s=10.0):
    """A handler thread closes its ``http.ingest`` span just after the client
    has its reply: wait until ``name`` has closed ``n`` times in all."""
    deadline = time.monotonic() + timeout_s
    while _count(name) < n:
        assert time.monotonic() < deadline, f"{name} closed {_count(name)} of {n} times"
        time.sleep(0.001)


def _children(line, parent, name):
    return [e for e in line if e[0] == name and parent[1] <= e[1] and e[2] <= parent[2]]


def test_traced_write_path_nests_on_the_host_plane(rng, tmp_path):
    window = _window()
    gw = IngestGateway(window, start=False)
    gw.submit("/a", rng.pareto(1.0, 20) + 1.0)  # compile the tick's shape untraced
    gw.flush()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with QuantileHTTPServer(TelemetryFacade(window, KeyedAggregator(window.spec)),
                            gateway=gw) as server:
        posts0 = _count("http.ingest")
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            seqs = [_post(server.url, k, (rng.pareto(1.0, 10) + 1.0).tolist())["seq"]
                    for k in ("/a", "/b")]
            gw.flush()
            _await_count("http.ingest", posts0 + 2)
        finally:
            jax.profiler.stop_trace()
    lines = _host_lines(tmp_path)

    ticks = [(line, e) for line in lines for e in line if e[0] == "gateway.tick"]
    assert len(ticks) == 1
    line, tick = ticks[0]
    assert tick[3]["first_seq"] <= min(seqs) and max(seqs) <= tick[3]["last_seq"]
    assert tick[3]["batches"] == 2 and tick[3]["values"] == 20
    (rec,) = _children(line, tick, "window.record_batches")
    (ing,) = _children(line, rec, "engine.ingest")
    assert ing[3]["lanes"] == 20 and str(ing[3]["geom"]) == "32"

    posts = [(line, e) for line in lines for e in line if e[0] == "http.ingest"]
    assert sorted(e[3]["seq"] for _, e in posts) == sorted(seqs)
    for line, post in posts:
        assert post[3]["status"] == 200 and post[3]["values"] == 10
        assert len(_children(line, post, "http.ingest.parse")) == 1
        (sub,) = _children(line, post, "gateway.submit")
        assert sub[3]["outcome"] == "accepted" and sub[3]["seq"] == post[3]["seq"]
        assert len(_children(line, post, "http.reply")) == 1


def test_span_counts_reconcile_with_gateway_counters(rng):
    window = _window()
    gw = IngestGateway(window, start=False)
    with QuantileHTTPServer(TelemetryFacade(window, KeyedAggregator(window.spec)),
                            gateway=gw) as server:
        before = gw.stats()
        posts0 = _count("http.ingest")
        for i in range(3):
            for k in ("/a", "/b", "/c"):
                gw.submit(k, rng.pareto(1.0, 7 + i) + 1.0)
            _post(server.url, "/d", [1.0, 2.0, 3.0])
            gw.flush()
        _await_count("http.ingest", posts0 + 3)
        after = gw.stats()
    spans0, spans1 = before["spans"], after["spans"]

    def d(name):
        zero = {"count": 0}
        return spans1.get(name, zero)["count"] - spans0.get(name, zero)["count"]

    assert d("gateway.tick") == after["ticks"] - before["ticks"] == 3
    assert d("engine.ingest") == after["engine_calls"] - before["engine_calls"] == 3
    assert d("window.record_batches") == 3
    assert d("gateway.submit") == 12
    assert d("http.ingest") == d("http.ingest.parse") == 3
    assert after["batches_taken"] - before["batches_taken"] == 12
    assert after["queue_wait_s"] > before["queue_wait_s"]
    # stats() hands out a copy: the earlier snapshot did not move
    assert spans0 != spans1 and spans0 == before["spans"]
    for t in spans1.values():
        assert 0.0 <= t["self_s"] <= t["total_s"]


def test_rejected_values_counted(rng):
    gw = IngestGateway(_window(), max_queue_values=16, start=False)
    gw.submit("/a", np.ones(10))
    for n in (7, 9):
        with pytest.raises(GatewayOverloaded):
            gw.submit("/a", np.ones(n))
    st = gw.stats()
    assert st["rejected_batches"] == 2 and st["rejected_values"] == 16
    assert st["accepted_values"] == 10


def test_self_time_excludes_children_on_the_same_thread():
    before = totals("test.tracing.")

    def work():
        with span("test.tracing.outer"):
            with span("test.tracing.inner"):
                time.sleep(0.02)
            time.sleep(0.01)

    threads = [threading.Thread(target=work) for _ in range(3)]
    for t in threads:
        t.start()
    work()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    after = totals("test.tracing.")
    outer, inner = after["test.tracing.outer"], after["test.tracing.inner"]
    o0 = before.get("test.tracing.outer", {"count": 0, "total_s": 0.0, "self_s": 0.0})
    i0 = before.get("test.tracing.inner", {"count": 0, "total_s": 0.0, "self_s": 0.0})
    assert outer["count"] - o0["count"] == 4 and inner["count"] - i0["count"] == 4
    d_outer_total = outer["total_s"] - o0["total_s"]
    d_outer_self = outer["self_s"] - o0["self_s"]
    d_inner_total = inner["total_s"] - i0["total_s"]
    assert abs(d_outer_self - (d_outer_total - d_inner_total)) < 1e-6
    assert 4 * 0.01 <= d_outer_self < d_outer_total
    assert abs(inner["self_s"] - i0["self_s"] - d_inner_total) < 1e-9  # a leaf's self is its total


def test_stats_with_spans_is_strict_json(rng):
    window = _window()
    gw = IngestGateway(window, start=False)
    with QuantileHTTPServer(TelemetryFacade(window, KeyedAggregator(window.spec)),
                            gateway=gw) as server:
        posts0, reads0 = _count("http.ingest"), _count("http.read")
        _post(server.url, "/a", [1.0, 2.0])
        gw.flush()
        with urlopen(f"{server.url}/live?q=0.5", timeout=10):
            pass
        _await_count("http.ingest", posts0 + 1)
        _await_count("http.read", reads0 + 1)
        with urlopen(f"{server.url}/stats", timeout=10) as resp:
            payload = _strict_json(resp.read())
    spans = payload["gateway"]["spans"]
    for name in ("http.ingest", "http.ingest.parse", "gateway.submit", "gateway.tick",
                 "window.record_batches", "engine.ingest"):
        assert spans[name]["count"] >= 1, name
    assert set(spans[name]) == {"count", "total_s", "self_s"}
    read = payload["query_planner"]["spans"]
    assert read["http.read"]["count"] >= 1 and read["planner.wait"]["count"] >= 1
    assert read["planner.dispatch"]["count"] >= 1
    assert payload["gateway"]["latency_s"][0] > 0


def _get_json(url):
    with urlopen(url, timeout=30) as resp:
        return _strict_json(resp.read())


def test_ring_spans_nest_count_and_reach_stats(rng, tmp_path):
    window = KeyedWindow(BucketSpec(), capacity=8, num_slices=4, slice_seconds=60.0)
    gw = IngestGateway(window, start=False)
    reads = ("/quantiles?endpoint=/a&slices=2&q=0.5", "/rollup?slices=3&q=0.5,0.9")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with QuantileHTTPServer(TelemetryFacade(window, KeyedAggregator(window.spec)),
                            gateway=gw) as server:
        def step():
            _post(server.url, "/a", (rng.pareto(1.0, 8) + 1.0).tolist())
            gw.flush()
            window.advance_slice()
            for path in reads:
                _get_json(server.url + path)

        step()  # compile every shape untraced
        before, ring0 = gw.stats()["spans"], window.ring_stats()
        reads0 = _count("http.read")
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            step()
            step()
            _await_count("http.read", reads0 + 4)
        finally:
            jax.profiler.stop_trace()
        payload = _get_json(server.url + "/stats")
    after, ring1 = payload["gateway"]["spans"], window.ring_stats()

    def d(name):
        return after[name]["count"] - before[name]["count"]

    assert d("window.ring_seal") == d("window.advance_slice") == 2
    assert d("window.ring_read") == ring1["ring_reads"] - ring0["ring_reads"] == 4
    assert ring1["ring_read_retries"] == ring0["ring_read_retries"] == 0
    assert payload["engine"]["ring"]["ring_reads"] == ring1["ring_reads"]
    assert payload["engine"]["ring"]["ring_read_retries"] == 0

    lines = _host_lines(tmp_path)
    seals = [(line, e) for line in lines for e in line if e[0] == "window.advance_slice"]
    assert len(seals) == 2
    for line, adv in seals:
        (seal,) = _children(line, adv, "window.ring_seal")
        assert seal[3]["merges"] in (0, 1, 2)
    assert sorted(_children(line, adv, "window.ring_seal")[0][3]["sealed"]
                  for line, adv in seals) == [ring1["sealed"] - 1, ring1["sealed"]]
    dispatches = [(line, e) for line in lines for e in line
                  if e[0] == "planner.dispatch" and e[3]["window"] > 0]
    assert len(dispatches) == 4
    for line, disp in dispatches:
        (read,) = _children(line, disp, "window.ring_read")
        args = read[3]
        assert args["window"] == disp[3]["window"] and args["retried"] == 0
        assert args["sealed"] == args["ring_sealed"]


def test_totals_lose_no_update_under_thread_churn():
    """Per-thread tables take no lock: many short threads (ids reused) and
    a tiny switch interval must still count every span exactly once."""
    import sys

    name = "test.tracing.churn"
    n_threads, per_thread = 64, 200
    before = _count(name)

    def work():
        for _ in range(per_thread):
            with span(name):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for _ in range(200):  # a reader sums the tables while they grow
            totals(name)
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert _count(name) - before == n_threads * per_thread
