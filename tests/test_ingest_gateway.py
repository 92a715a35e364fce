"""Ingest gateway: coalescing, backpressure, shedding, deadlines, HTTP.

The write-path acceptance story: batches from many clients coalesce into
one engine ingest per tick, overload degrades to defined responses (429 +
Retry-After under reject, mass-preserving weighted sampling under sample),
expired batches shed with recorded mass, and every path conserves
accounting: ingested mass + shed mass == submitted mass.
"""

import json
import time
from urllib.error import HTTPError
from urllib.request import Request, urlopen

import numpy as np
import pytest

from repro.core.jax_sketch import BucketSpec
from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
from repro.launch.ingest_client import IngestClient, IngestError
from repro.launch.ingest_gateway import GatewayOverloaded, IngestGateway
from repro.telemetry.keyed import KeyedAggregator, KeyedWindow


def make_window(capacity=8):
    return KeyedWindow(BucketSpec(), capacity=capacity)


def _get(url, token=None):
    req = Request(url)
    if token is not None:
        req.add_header("Authorization", f"Bearer {token}")
    with urlopen(req, timeout=10) as resp:
        return json.loads(resp.read())


# --------------------------------------------------------------------- #
# queue semantics (no HTTP, no drain thread: flush() drives ticks)
# --------------------------------------------------------------------- #
def test_coalescing_one_engine_call_per_tick(rng):
    window = make_window()
    gw = IngestGateway(window, start=False)
    for i in range(10):
        gw.submit(f"/ep{i % 3}", rng.pareto(1.0, 50) + 1.0)
    gw.flush()
    st = gw.stats()
    assert st["engine_calls"] == 1  # 10 client batches, ONE ingest
    assert st["ingested_values"] == 500
    assert window.total_mass() == 500.0
    assert st["queue_depth"] == 0


def test_record_batches_matches_record(rng):
    """The coalesced routing is bit-identical to per-batch record()."""
    vals = {k: (rng.pareto(1.0, 200) + 1.0).astype(np.float32) for k in "abc"}
    w1, w2 = make_window(), make_window()
    for k, v in vals.items():
        w1.record(k, v)
    w2.record_batches([(k, v, None) for k, v in vals.items()])
    qs = [0.5, 0.95, 0.99]
    for k in vals:
        np.testing.assert_array_equal(w1.quantiles(k, qs), w2.quantiles(k, qs))
    np.testing.assert_array_equal(
        w1.rollup_quantiles(qs), w2.rollup_quantiles(qs)
    )


def test_reject_policy_raises_with_retry_after(rng):
    gw = IngestGateway(make_window(), max_queue_values=100, start=False)
    gw.submit("/a", rng.pareto(1.0, 100) + 1.0)  # fills the queue exactly
    with pytest.raises(GatewayOverloaded) as err:
        gw.submit("/a", [1.0])
    assert err.value.retry_after_s > 0
    assert gw.stats()["rejected_batches"] == 1
    gw.flush()  # queue drains; admissions resume
    assert gw.submit("/a", [1.0])["status"] == "accepted"
    # accounting: everything admitted eventually lands
    gw.flush()
    st = gw.stats()
    assert st["ingested_values"] == st["accepted_values"] == 101


def test_sample_policy_preserves_mass_and_records_shed(rng):
    gw = IngestGateway(
        make_window(),
        max_queue_values=1000,
        shed_policy="sample",
        sample_stride=4,
        sample_watermark=0.25,
        start=False,
    )
    n = 800  # past the watermark: degrades to stride sampling
    receipt = gw.submit("/a", rng.pareto(1.0, n) + 1.0)
    assert receipt["status"] == "accepted"
    assert receipt["shed"] > 0
    gw.flush()
    st = gw.stats()
    # mass conservation: the weighted survivors carry the full batch mass
    assert gw.window.total_mass() == pytest.approx(n)
    assert st["shed_mass"] == receipt["shed"]
    assert st["sampled_batches"] == 1


def test_sample_policy_full_queue_sheds_whole_batch(rng):
    gw = IngestGateway(
        make_window(), max_queue_values=64, shed_policy="sample", start=False
    )
    gw.submit("/a", np.ones(64))  # watermark passed -> sampled, queue fills
    depth = gw.depth()
    assert 0 < depth <= 64
    gw._depth = gw.max_queue_values  # simulate a completely full queue
    receipt = gw.submit("/a", np.ones(32))
    assert receipt["status"] == "shed" and receipt["shed"] == 32
    gw._depth = depth
    gw.flush()


def test_deadline_expires_stale_batches(rng):
    gw = IngestGateway(make_window(), deadline_s=0.01, start=False)
    gw.submit("/a", np.ones(100))
    gw.submit("/b", np.ones(50), deadline_s=60.0)  # per-request override
    time.sleep(0.05)  # /a's deadline passes while queued
    gw.flush()
    st = gw.stats()
    assert st["expired_batches"] == 1
    assert st["shed_mass"] == 100
    assert st["ingested_values"] == 50
    assert gw.window.total_mass() == 50.0


def test_drain_error_sheds_tick_and_keeps_serving(rng):
    gw = IngestGateway(make_window(), start=False)
    boom = {"armed": True}
    real = gw.window.record_batches

    def flaky(batches):
        if boom.pop("armed", None):
            raise RuntimeError("injected engine failure")
        return real(batches)

    gw.window.record_batches = flaky
    gw.submit("/a", np.ones(10))
    gw.flush()  # failing tick: shed, not raised
    st = gw.stats()
    assert st["drain_errors"] == 1 and st["shed_mass"] == 10
    gw.submit("/a", np.ones(5))
    gw.flush()  # next tick succeeds
    assert gw.stats()["ingested_values"] == 5


def test_drain_error_keeps_message_and_logs_once(rng, caplog):
    """A failing engine tick is never silent: the exception's type and
    message stay in stats(), and only the first traceback is logged."""
    window = make_window()
    gw = IngestGateway(window, start=False)
    assert gw.stats()["last_drain_error"] is None
    charges = [2]

    def failing_tick(path):
        if charges[0]:
            charges[0] -= 1
            raise ValueError(f"Mosaic refused the {path} kernel")

    window.engine.tick_hooks.append(failing_tick)
    with caplog.at_level("ERROR", logger="repro.launch.ingest_gateway"):
        for n in (10, 20):
            gw.submit("/a", np.ones(n))
            gw.flush()
    st = gw.stats()
    assert st["drain_errors"] == 2 and st["shed_mass"] == 30
    assert st["last_drain_error"] == "ValueError: Mosaic refused the ingest kernel"
    logged = [r for r in caplog.records if r.exc_info]
    assert len(logged) == 1 and "drain tick" in logged[0].getMessage()
    json.dumps(st)  # the message rides the JSON /stats payload
    gw.submit("/a", np.ones(5))
    gw.flush()  # the hook is spent: the next tick ingests
    assert gw.stats()["ingested_values"] == 5


def test_background_drain_thread(rng):
    gw = IngestGateway(make_window(), tick_interval_s=0.002)
    gw.submit("/a", rng.pareto(1.0, 100) + 1.0)
    deadline = time.monotonic() + 10.0
    while gw.stats()["ingested_values"] < 100:
        assert time.monotonic() < deadline, "drain thread never ingested"
        time.sleep(0.005)
    lat = gw.latency_quantiles([0.5])
    assert lat[0] > 0
    gw.stop()
    with pytest.raises(RuntimeError):
        gw.submit("/a", [1.0])


class _TimedWindow:
    """A window whose ``record_batches`` takes ``tick_s`` and notes the
    gateway's counters as each tick begins its ingest; ``refill`` queues
    one more batch from inside the tick, so the queue never runs dry."""

    def __init__(self, tick_s=0.0, refill=0):
        self.tick_s, self.refill = tick_s, refill
        self.gw = None
        self.seen = []  # gateway stats at each record_batches call

    def record_batches(self, batches):
        self.seen.append(self.gw.stats())
        if self.refill:
            self.refill -= 1
            self.gw.submit("/a", np.ones(4))
        if self.tick_s:
            time.sleep(self.tick_s)
        return sum(v.size for _, v, _ in batches)


def test_overrunning_ticks_follow_each_other_without_a_wait():
    """A tick three intervals long, with work always queued: the next tick
    begins at once, and the loop adds no wait between them."""
    interval, n = 0.01, 8
    window = _TimedWindow(tick_s=3 * interval, refill=n - 1)
    gw = IngestGateway(window, tick_interval_s=interval)
    window.gw = gw
    gw.submit("/a", np.ones(4))
    deadline = time.monotonic() + 10.0
    while len(window.seen) < n:
        assert time.monotonic() < deadline, "drain thread stopped ticking"
        time.sleep(0.005)
    gw.stop()
    first, last = window.seen[0], window.seen[n - 1]
    assert last["ticks"] - first["ticks"] == n - 1
    assert last["drain_wait_s"] - first["drain_wait_s"] < interval


def test_quick_ticks_are_still_spaced_by_the_interval():
    """An instant tick under a trickle of batches waits out the rest of
    its interval: no more ticks than intervals elapsed, plus one."""
    interval = 0.02
    window = _TimedWindow()
    t_start = time.monotonic()
    gw = IngestGateway(window, tick_interval_s=interval)
    window.gw = gw
    while time.monotonic() - t_start < 0.3:
        gw.submit("/a", np.ones(4))
        time.sleep(0.002)
    st = gw.stats()
    elapsed = time.monotonic() - t_start
    gw.stop()
    assert 1 <= st["ticks"] <= elapsed / interval + 1
    assert st["drain_wait_s"] > 0


def test_drain_wait_counters_in_stats_and_http():
    window = _TimedWindow()
    gw = IngestGateway(window, tick_interval_s=0.002)
    window.gw = gw
    gw.submit("/a", np.ones(4))
    deadline = time.monotonic() + 10.0
    while gw.stats()["ingested_values"] < 4:
        assert time.monotonic() < deadline, "drain thread never ingested"
        time.sleep(0.005)
    st = gw.stats()
    assert isinstance(st["drain_wait_s"], float) and st["drain_wait_s"] > 0
    with QuantileHTTPServer(TelemetryFacade(make_window(), None), gateway=gw) as server:
        got = _get(f"{server.url}/stats")["gateway"]
        assert got["drain_wait_s"] >= st["drain_wait_s"]
    gw.stop()


def test_gateway_validation():
    gw = IngestGateway(make_window(), start=False)
    with pytest.raises(ValueError):
        gw.submit("", [1.0])
    with pytest.raises(ValueError):
        gw.submit("/a", [1.0, 2.0], weights=[1.0])
    assert gw.submit("/a", [])["queued"] == 0
    with pytest.raises(ValueError):
        IngestGateway(make_window(), shed_policy="nope", start=False)


# --------------------------------------------------------------------- #
# over the wire
# --------------------------------------------------------------------- #
def test_http_ingest_end_to_end(rng):
    window = make_window()
    agg = KeyedAggregator(window.spec)
    gw = IngestGateway(window, tick_interval_s=0.002)
    with QuantileHTTPServer(TelemetryFacade(window, agg), gateway=gw) as server:
        client = IngestClient(server.url)
        vals = (rng.pareto(1.0, 400) + 1.0).astype(np.float32)
        receipt = client.ingest("/v1/chat", vals.tolist())
        assert receipt["status"] == "accepted" and receipt["queued"] == 400
        gw.flush()
        live = _get(f"{server.url}/live?q=0.5,0.99")
        got = live["endpoints"]["/v1/chat"]
        want = window.quantiles("/v1/chat", [0.5, 0.99])
        np.testing.assert_allclose(got, want)
        stats = _get(f"{server.url}/stats")
        assert stats["gateway"]["ingested_values"] == 400
        assert stats["server"]["ingest_accepted"] == 1


def test_http_ingest_429_and_client_retry(rng):
    """A full queue 429s with Retry-After; the client backs off, the drain
    catches up, and the retried batch lands — nothing is lost."""
    window = make_window()
    gw = IngestGateway(
        window, max_queue_values=256, tick_interval_s=0.01, start=False
    )
    with QuantileHTTPServer(TelemetryFacade(window, None), gateway=gw) as server:
        client = IngestClient(server.url, max_retries=0)
        client.ingest("/a", [1.0] * 256)
        with pytest.raises(IngestError) as err:
            client.ingest("/a", [1.0] * 10)
        assert isinstance(err.value.cause, HTTPError)
        assert err.value.cause.code == 429
        assert float(err.value.cause.headers["Retry-After"]) > 0

        # a retrying client succeeds once a flusher drains the queue
        import threading

        flusher = threading.Thread(target=gw.flush, daemon=True)
        retry_client = IngestClient(server.url, max_retries=5, base_backoff_s=0.02)
        flusher.start()
        receipt = retry_client.ingest("/a", [2.0] * 10)
        flusher.join()
        assert receipt["status"] == "accepted"
        gw.flush()
        assert window.total_mass() == 266.0
        assert retry_client.stats["throttled"] >= 0  # may win the race outright


def test_http_ingest_payload_validation(rng):
    gw = IngestGateway(make_window(), start=False)
    with QuantileHTTPServer(
        TelemetryFacade(make_window(), None), gateway=gw, max_body_bytes=4096
    ) as server:
        def post(body: bytes, headers=None):
            req = Request(f"{server.url}/ingest", data=body, method="POST")
            req.add_header("Content-Type", "application/json")
            for k, v in (headers or {}).items():
                req.add_header(k, v)
            with urlopen(req, timeout=10) as resp:
                return resp.status

        for bad in (
            b"not json",
            b"[1,2,3]",
            json.dumps({"values": [1.0]}).encode(),  # no key
            json.dumps({"key": "", "values": [1.0]}).encode(),
            json.dumps({"key": "/a", "values": "xs"}).encode(),
            json.dumps({"key": "/a", "values": [1.0], "weights": [1.0, 2.0]}).encode(),
            # malformed *types* must 400 too, not TypeError-crash the handler
            json.dumps({"key": "/a", "values": [1.0], "deadline_ms": [1]}).encode(),
            json.dumps({"key": "/a", "values": [1.0], "deadline_ms": "soon"}).encode(),
            json.dumps({"key": "/a", "values": [{"v": 1.0}]}).encode(),
            json.dumps({"key": "/a", "values": [1.0], "weights": [{"w": 1}]}).encode(),
            json.dumps({"key": "/a", "values": [1.0], "weights": "heavy"}).encode(),
        ):
            with pytest.raises(HTTPError) as err:
                post(bad)
            assert err.value.code == 400, bad
        with pytest.raises(HTTPError) as err:
            post(json.dumps({"key": "/a", "values": [1.0] * 4096}).encode())
        assert err.value.code == 413
        # GET /ingest is not a thing; POST elsewhere 404s
        with pytest.raises(HTTPError) as err:
            post_req = Request(f"{server.url}/nope", data=b"{}", method="POST")
            urlopen(post_req, timeout=10)
        assert err.value.code == 404


def test_stats_json_is_strict_before_first_tick(rng):
    """Pre-first-tick latency quantiles are NaN host-side; /stats must map
    them to null — json.dumps would otherwise emit the non-standard token
    NaN, which strict parsers (browsers, jq) reject."""
    gw = IngestGateway(make_window(), start=False)
    with QuantileHTTPServer(TelemetryFacade(make_window(), None), gateway=gw) as server:
        with urlopen(Request(f"{server.url}/stats"), timeout=10) as resp:
            raw = resp.read()
        assert b"NaN" not in raw
        payload = json.loads(raw, parse_constant=lambda c: (_ for _ in ()).throw(
            AssertionError(f"non-standard JSON constant {c!r} in /stats")
        ))
        assert payload["gateway"]["latency_s"] == [None, None, None]


def test_retry_after_is_integer_seconds(rng):
    """RFC 9110: Retry-After is integer delta-seconds; the sub-second
    advisory rides X-Retry-After-Ms (preferred by IngestClient)."""
    gw = IngestGateway(make_window(), max_queue_values=8, start=False)
    with QuantileHTTPServer(TelemetryFacade(make_window(), None), gateway=gw) as server:
        client = IngestClient(server.url, max_retries=0)
        client.ingest("/a", [1.0] * 8)
        with pytest.raises(IngestError) as err:
            client.ingest("/a", [1.0])
        ra = err.value.cause.headers["Retry-After"]
        assert ra == str(int(ra))  # integer token, no fraction
        assert int(ra) >= 1
        assert float(err.value.cause.headers["X-Retry-After-Ms"]) > 0
        gw.flush()


def test_http_ingest_without_gateway_404s(rng):
    with QuantileHTTPServer(TelemetryFacade(make_window(), None)) as server:
        req = Request(
            f"{server.url}/ingest",
            data=json.dumps({"key": "/a", "values": [1.0]}).encode(),
            method="POST",
        )
        with pytest.raises(HTTPError) as err:
            urlopen(req, timeout=10)
        assert err.value.code == 404


def test_http_ingest_auth_and_rate_limit(rng):
    gw = IngestGateway(make_window(), start=False)
    with QuantileHTTPServer(
        TelemetryFacade(make_window(), None),
        gateway=gw,
        auth_token="s3cret",
        rate_limit=0.0,
        rate_burst=2,
    ) as server:
        noauth = IngestClient(server.url, max_retries=0)
        with pytest.raises(IngestError) as err:
            noauth.ingest("/a", [1.0])
        assert err.value.cause.code == 401
        ok = IngestClient(server.url, auth_token="s3cret", max_retries=0)
        assert ok.ingest("/a", [1.0])["status"] == "accepted"
        with pytest.raises(IngestError) as err:  # bucket exhausted -> 429
            ok.ingest("/a", [1.0])
        assert err.value.cause.code == 429
        gw.flush()
