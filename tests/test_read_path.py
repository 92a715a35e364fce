"""Lock-free read path: snapshots, coalesced dispatches, versioned cache.

The PR-10 tentpole contract, test by test:

* ``KeyedWindow.snapshot()`` hands out an immutable version-stamped view:
  answers off a held snapshot never move, even while donated ingest
  executables consume the live bank's buffers, slices seal, and the
  window resets underneath it;
* windowed queries off a snapshot replay against the seal count captured
  at publish time (``WindowRing.query_args_at``), not the live ring;
* ``version`` bumps at exactly the events that can change a query answer
  — ingest tick (reactive collapse rides the same executable), slice
  seal, reset — and at no other time;
* snapshot publication is cached per version and the writer-side
  ``publish()`` is self-tuning (a no-op until the first reader appears);
* the ``QueryPlanner`` coalescer folds a mixed batch of per-row / rollup
  / windowed requests into one fused dispatch per (shape, window) group
  over the union of requested qs, and every scattered answer is
  bit-exact vs a per-request dispatch against the same snapshot
  (deterministic grid + hypothesis sweep);
* the version-keyed result cache hits at the live version, misses after
  any bump (implicit invalidation), and never serves a stale answer;
* HTTP: every versioned read carries ``ETag: "<version>"``; a matching
  ``If-None-Match`` re-poll is answered 304 with NO body before any
  planner or device work; a stale tag gets a full 200 with the new tag;
* query-path auto-dispatch fallbacks (row axis below the kernel tile)
  warn once per site and count in ``ops.dispatch_stats()``;
* windowed reads run on the live ring slab: with ingest, seals and readers
  in threads at once every answer equals the host ``DDSketch`` merge at
  the version it names, a stale cover makes the planner re-take the
  newest snapshot, and the window holds one slab and a few banks however
  many seals pass under readers.
"""

import gc
import json
import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import sketch_bank as sb
from repro.core.ddsketch import DDSketch
from repro.engine import StaleCover
from repro.kernels import ops
from repro.kernels.ref import BucketSpec
from repro.launch.query_planner import QueryPlanner, QueryResultCache, _Pending
from repro.telemetry.keyed import KeyedWindow

SMALL = BucketSpec(num_buckets=128, offset=-64)
QS = [0.1, 0.5, 0.9]
QPOOL = [0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0]


def _build_window(num_slices=8, steps=5, seed=0):
    """A window with sealed history, live data, and collapsed rows."""
    win = KeyedWindow(SMALL, capacity=8, num_slices=num_slices)
    r = np.random.default_rng(seed)
    for step in range(steps):
        for key in ("a", "b", "c"):
            win.record([key] * 32, r.gamma(2.0, 2.0, 32).astype(np.float32))
        # huge dynamic range: forces reactive uniform collapse on one row
        win.record(["a"] * 2, np.asarray([1e-12, 1e12], np.float32))
        if step < steps - 1:
            win.advance_slice()
    return win


@pytest.fixture(scope="module")
def parity_window():
    """Shared read-only window for the parity sweeps (snapshots make
    concurrent reads safe; no test below mutates it)."""
    return _build_window()


# --------------------------------------------------------------------- #
# snapshot isolation
# --------------------------------------------------------------------- #
def test_snapshot_survives_donated_ingest_seal_and_reset():
    win = _build_window(num_slices=4, steps=2)
    snap = win.snapshot()
    table = np.asarray(snap.row_quantiles(QS)).copy()
    rows = dict(snap.key_to_row)
    mass = snap.total_mass()
    levels = dict(snap.levels())
    # every event class that mutates (and donates) the live state
    win.record(["a"] * 16, np.full(16, 7.0, np.float32))
    win.advance_slice()
    win.reset()
    assert np.array_equal(
        np.asarray(snap.row_quantiles(QS)), table, equal_nan=True
    )
    assert snap.key_to_row == rows
    assert snap.total_mass() == mass
    assert snap.levels() == levels
    # and the snapshot answers match what the engine said pre-mutation
    assert snap.quantiles("b", QS) == list(map(float, table[rows["b"]]))


def test_snapshot_windowed_replay_pinned_to_publish_seal_count():
    win = KeyedWindow(SMALL, capacity=4, num_slices=8)
    win.record(["a"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    win.advance_slice()
    win.record(["a"] * 4, np.asarray([5.0, 6.0, 7.0, 8.0], np.float32))
    win.advance_slice()
    snap = win.snapshot()
    pinned = np.asarray(snap.windowed_row_quantiles([0.5], slices=3)).copy()
    for _ in range(3):
        win.record(["a"] * 4, np.full(4, 100.0, np.float32))
        win.advance_slice()
    # the held snapshot replays the 2-seals-old window, bit for bit
    assert np.array_equal(
        np.asarray(snap.windowed_row_quantiles([0.5], slices=3)),
        pinned,
        equal_nan=True,
    )
    live = np.asarray(win.snapshot().windowed_row_quantiles([0.5], slices=3))
    assert not np.array_equal(live, pinned, equal_nan=True)


def test_version_bumps_on_every_state_change_and_only_those():
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    v0 = win.version
    win.record(["a"], np.asarray([1.0], np.float32))
    assert win.version == v0 + 1
    # reactive collapse is fused into the ingest tick: one bump, and the
    # collapse event is observable
    win.record(["a"] * 2, np.asarray([1e-12, 1e12], np.float32))
    assert win.version == v0 + 2
    assert win.drain_events()
    win.advance_slice()  # ring seal
    assert win.version == v0 + 3
    win.reset()
    assert win.version == v0 + 4
    # reads never bump
    win.record(["a"], np.asarray([2.0], np.float32))
    v = win.version
    win.snapshot().rollup_quantiles(QS)
    win.quantiles("a", QS)
    win.total_mass()
    assert win.version == v


def test_snapshot_reuse_and_self_tuning_publish():
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    win.record(["a"], np.asarray([1.0], np.float32))
    # publish() before any reader: a pure-write workload pays no copies
    win.publish()
    assert win.engine_stats()["read_path"]["snapshot_builds"] == 0
    s1 = win.snapshot()
    assert win.snapshot() is s1  # version unchanged -> cached object
    win.record(["a"], np.asarray([2.0], np.float32))
    win.publish()  # readers exist now: the writer pre-pays the copy
    s2 = win.snapshot()
    assert s2 is not s1 and s2.version == s1.version + 1
    rp = win.engine_stats()["read_path"]
    assert rp["version"] == win.version
    assert rp["snapshot_builds"] == 2


# --------------------------------------------------------------------- #
# coalesced union dispatch: bit-exact scatter
# --------------------------------------------------------------------- #
def _assert_request_exact(req, snap):
    assert req.error is None, req.error
    if req.kind == "rows":
        version, table, rows = req.result
        want = (
            snap.row_quantiles(list(req.qs))
            if req.wslices is None
            else snap.windowed_row_quantiles(list(req.qs), slices=req.wslices)
        )
        assert np.array_equal(
            np.asarray(table), np.asarray(want), equal_nan=True
        )
        assert rows == snap.key_to_row
    else:
        version, vals = req.result
        want = (
            snap.rollup_quantiles(list(req.qs))
            if req.wslices is None
            else snap.windowed_rollup(list(req.qs), slices=req.wslices)
        )
        assert np.array_equal(
            np.asarray(vals), np.asarray(want), equal_nan=True
        )
    assert version == snap.version


def test_coalesced_batch_bit_exact_vs_per_request(parity_window):
    """One mixed coalescer round — per-row and rollup shapes, live and
    windowed, overlapping q sets — scatters answers identical to what a
    per-request dispatch against the same snapshot returns."""
    win = parity_window
    planner = QueryPlanner(win, coalesce_window_s=0.0)
    qs_sets = [(0.5,), (0.1, 0.9), (0.25, 0.5, 0.75), (0.0, 0.5, 0.95, 1.0)]
    batch = [
        _Pending(kind, w, qs)
        for kind in ("rows", "rollup")
        for w in (None, 2, 5)
        for qs in qs_sets
    ]
    planner._execute(batch)
    snap = win.snapshot()
    for req in batch:
        _assert_request_exact(req, snap)
    # one fused dispatch per (kind, window) group, not per request
    assert planner.stats()["dispatches"] == 6
    # the round filled the cache: a re-poll of any member is a pure hit
    v, table, rows = planner.quantile_rows([0.1, 0.9], 2)
    assert planner.cache.stats()["hits"] >= 1
    assert v == snap.version


@settings(max_examples=30, deadline=None)
@given(
    qsets=st.lists(
        st.lists(
            st.sampled_from(QPOOL), min_size=1, max_size=4, unique=True
        ),
        min_size=1,
        max_size=6,
    ),
    wslices=st.sampled_from([None, 1, 2, 3, 5, 8]),
    kind=st.sampled_from(["rows", "rollup"]),
)
def test_coalesced_parity_property(parity_window, qsets, wslices, kind):
    """Any mix of concurrent q sets folded into one union dispatch is
    bit-exact vs per-request reads — across windows and collapse levels
    (the shared window has a reactively-collapsed row)."""
    planner = QueryPlanner(parity_window, coalesce_window_s=0.0)
    batch = [_Pending(kind, wslices, tuple(qs)) for qs in qsets]
    planner._execute(batch)
    snap = parity_window.snapshot()
    for req in batch:
        _assert_request_exact(req, snap)


def test_concurrent_pollers_coalesce_and_agree(parity_window):
    """16 threads with distinct q sets: every answer is exact, nobody
    deadlocks, and the leader/follower accounting adds up."""
    planner = QueryPlanner(parity_window, coalesce_window_s=0.02)
    n = 16
    qs_by_thread = [[QPOOL[i % len(QPOOL)]] for i in range(n)]
    results: list = [None] * n
    errors: list = []
    barrier = threading.Barrier(n)

    def poll(i):
        try:
            barrier.wait()
            results[i] = planner.quantile_rows(qs_by_thread[i])
        except BaseException as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=poll, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = parity_window.snapshot()
    for i, (version, table, rows) in enumerate(results):
        assert version == snap.version
        assert np.array_equal(
            np.asarray(table),
            np.asarray(snap.row_quantiles(qs_by_thread[i])),
            equal_nan=True,
        )
    stats = planner.stats()
    assert stats["requests"] == n
    assert stats["dispatches"] <= stats["leader_rounds"] * 1 + n
    assert stats["dispatches"] >= 1


# --------------------------------------------------------------------- #
# versioned result cache
# --------------------------------------------------------------------- #
def test_cache_hits_at_live_version_and_invalidates_on_bump():
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    win.record(["a"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    planner = QueryPlanner(win, coalesce_window_s=0.0)
    v1, t1, _ = planner.quantile_rows([0.5, 0.9])
    assert planner.cache.stats()["hits"] == 0
    v2, t2, _ = planner.quantile_rows([0.5, 0.9])
    assert v2 == v1 and t2 is t1  # the exact cached object, no dispatch
    assert planner.cache.stats()["hits"] == 1
    dispatches = planner.stats()["dispatches"]

    for bump in (
        lambda: win.record(["a"], np.asarray([9.0], np.float32)),  # ingest
        lambda: win.advance_slice(),  # seal
        lambda: win.reset(),  # reset
    ):
        v_before = win.version
        bump()
        assert win.version == v_before + 1
        v, t, _ = planner.quantile_rows([0.5, 0.9])
        assert v == win.version  # recomputed at the new version, not stale
        new_dispatches = planner.stats()["dispatches"]
        assert new_dispatches == dispatches + 1
        dispatches = new_dispatches


def test_cached_aux_reads_are_version_memoized():
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    win.record(["a"], np.asarray([1.0], np.float32))
    planner = QueryPlanner(win, coalesce_window_s=0.0)
    calls = {"n": 0}

    def compute():
        calls["n"] += 1
        return {"value": calls["n"]}

    v1, a = planner.cached(("report", (0.5,)), compute)
    v2, b = planner.cached(("report", (0.5,)), compute)
    assert v1 == v2 and b is a and calls["n"] == 1
    win.record(["a"], np.asarray([2.0], np.float32))
    v3, c = planner.cached(("report", (0.5,)), compute)
    assert v3 == v1 + 1 and calls["n"] == 2


def test_query_result_cache_lru_eviction():
    cache = QueryResultCache(max_entries=2)
    cache.put(("a",), 1)
    cache.put(("b",), 2)
    assert cache.get(("a",)) == 1  # refreshes recency
    cache.put(("c",), 3)  # evicts ("b",)
    assert cache.get(("b",)) is None
    assert cache.get(("a",)) == 1 and cache.get(("c",)) == 3
    assert cache.stats()["evictions"] == 1
    assert len(cache) == 2
    with pytest.raises(ValueError):
        QueryResultCache(max_entries=0)


def test_planner_for_window_requires_snapshot_surface():
    class Bare:
        pass

    assert QueryPlanner.for_window(Bare()) is None
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    planner = QueryPlanner.for_window(win)
    assert planner is not None
    assert planner.etag() == f'"{win.version}"'
    # windowed param validation surfaces the HTTP 400 contract
    assert planner.resolve_window() is None
    with pytest.raises(ValueError):
        planner.resolve_window(window="zzz")
    with pytest.raises(ValueError):
        planner.resolve_window(slices=0)


# --------------------------------------------------------------------- #
# HTTP: ETag / If-None-Match / 304
# --------------------------------------------------------------------- #
def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


@pytest.fixture
def http_planner():
    from repro.launch.http_api import QuantileHTTPServer, TelemetryFacade
    from repro.telemetry.keyed import KeyedAggregator

    win = KeyedWindow(SMALL, capacity=4, num_slices=4, slice_seconds=60.0)
    tele = TelemetryFacade(win, KeyedAggregator(win.spec))
    assert tele.planner is not None  # auto-built from the window
    with QuantileHTTPServer(tele) as srv:
        yield win, srv, tele


def test_http_etag_roundtrip_304_has_no_body(http_planner):
    win, srv, tele = http_planner
    win.record(["ep"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    code, headers, body = _get(srv.url + "/live?q=0.5")
    assert code == 200
    etag = headers["ETag"]
    assert etag == f'"{win.version}"'
    assert json.loads(body)["endpoints"]["ep"] == [pytest.approx(2.0, 0.02)]
    # matching tag: 304, ETag header, EMPTY body — no planner/device work
    code, headers, body = _get(
        srv.url + "/live?q=0.5", headers={"If-None-Match": etag}
    )
    assert code == 304 and body == b""
    assert headers["ETag"] == etag
    # every versioned read path honors the same contract
    for path in (
        "/quantiles?endpoint=ep&q=0.5",
        "/quantiles?endpoint=ep&slices=2&q=0.5",
        "/rollup?q=0.5",
        "/rollup?slices=2&q=0.5",
        "/report",
    ):
        code, headers, body = _get(
            srv.url + path, headers={"If-None-Match": etag}
        )
        assert (code, body) == (304, b""), path
    code, body_stats = _get(srv.url + "/stats")[::2]
    stats = json.loads(body_stats)
    assert stats["server"]["http_304"] == 6
    assert stats["query_planner"]["version"] == win.version


def test_http_stale_etag_gets_full_200_with_new_tag(http_planner):
    win, srv, _ = http_planner
    win.record(["ep"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    code, headers, _ = _get(srv.url + "/rollup?q=0.5")
    stale = headers["ETag"]
    win.record(["ep"], np.asarray([9.0], np.float32))  # version bump
    code, headers, body = _get(
        srv.url + "/rollup?q=0.5", headers={"If-None-Match": stale}
    )
    assert code == 200
    assert headers["ETag"] == f'"{win.version}"' != stale
    assert json.loads(body)["quantiles"]
    # seals and resets rotate the tag too (any event readers can observe)
    tag = headers["ETag"]
    win.advance_slice()
    code, headers, _ = _get(
        srv.url + "/rollup?q=0.5", headers={"If-None-Match": tag}
    )
    assert code == 200 and headers["ETag"] != tag


def test_http_planner_answers_match_direct_window_reads(http_planner):
    win, srv, _ = http_planner
    win.record(["ep"] * 4, np.asarray([1.0, 2.0, 3.0, 4.0], np.float32))
    win.advance_slice()
    win.record(["ep"] * 2, np.asarray([5.0, 6.0], np.float32))
    snap = win.snapshot()
    code, _, body = _get(srv.url + "/quantiles?endpoint=ep&slices=2&q=0.5,0.9")
    assert code == 200
    got = json.loads(body)["quantiles"]
    want = snap.windowed_quantiles("ep", [0.5, 0.9], slices=2)
    assert got == [pytest.approx(w) for w in want]
    code, _, body = _get(srv.url + "/rollup?q=0.5")
    assert json.loads(body)["quantiles"] == [
        pytest.approx(v) for v in snap.rollup_quantiles([0.5])
    ]
    # error contracts survive the planner path
    assert _get(srv.url + "/quantiles?endpoint=ghost&slices=2")[0] == 404
    assert _get(srv.url + "/quantiles?endpoint=ep&window=zzz")[0] == 400
    assert _get(srv.url + "/rollup?slices=0")[0] == 400


# --------------------------------------------------------------------- #
# windowed reads on the live ring slab
# --------------------------------------------------------------------- #
KEYS = ("a", "b", "c")


class _Log:
    """The writer side of a test window: every state change and the state
    it left, by version, so an answer can be rebuilt on the host."""

    def __init__(self, win, seed=0):
        self.win = win
        self.rng = np.random.default_rng(seed)
        self.lock = threading.Lock()
        self.sealed: list[dict] = []
        self.live = {k: [] for k in KEYS}
        self.at = {win.version: (0, {k: 0 for k in KEYS})}

    def _note(self):
        self.at[self.win.version] = (len(self.sealed),
                                     {k: len(v) for k, v in self.live.items()})

    def ingest(self):
        with self.lock:
            k = KEYS[int(self.rng.integers(len(KEYS)))]
            # inside SMALL's range, so no row collapses
            vals = self.rng.uniform(0.5, 3.0, 16).astype(np.float32)
            self.win.record(k, vals)
            self.live[k].extend(vals.tolist())
            self._note()

    def seal(self):
        with self.lock:
            self.win.advance_slice()
            self.sealed.append({k: list(v) for k, v in self.live.items()})
            self.live = {k: [] for k in KEYS}
            self._note()

    def host(self, version, w, keys):
        """Host ``DDSketch`` merge of the slices a ``w``-slice window read
        at ``version`` covers, over ``keys``."""
        n_sealed, live_len = self.at[version]
        slices = self.sealed[max(0, n_sealed - (w - 1)):n_sealed] if w > 1 else []
        # the live slice then: the slice sealed next, once it was sealed
        live = self.sealed[n_sealed] if n_sealed < len(self.sealed) else self.live
        slices = slices + [{k: live[k][:live_len[k]] for k in keys}]
        out = DDSketch(SMALL.relative_accuracy, max_bins=None, mapping=SMALL.mapping)
        for sl in slices:
            for k in keys:
                sk = DDSketch(SMALL.relative_accuracy, max_bins=None,
                              mapping=SMALL.mapping)
                sk.extend(sl[k])
                out.merge(sk)
        return out


def _assert_host(log, version, w, table, rows):
    for k in KEYS:
        want = log.host(version, w, [k])
        got = table[rows[k]] if k in rows else None
        if want.count == 0:
            assert got is None or np.isnan(got).all(), (version, w, k)
        else:
            assert list(got) == pytest.approx(want.quantiles(QS), rel=1e-5), (version, w, k)


def test_concurrent_ingest_seal_and_windowed_reads_match_host():
    """Ingest, seal and windowed-read threads at once: every answer equals
    the host ``DDSketch`` merge of the slices it covers at the version it
    names, and no read saw a deleted (donated) slab."""
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    log = _Log(win, seed=3)
    planner = QueryPlanner(win, coalesce_window_s=0.001)
    stop = threading.Event()
    answers, errors = {}, []  # one answer per (kind, window, version)

    def writer():
        try:
            for i in range(60):
                log.ingest()
                if i % 5 == 4:
                    log.seal()
        except BaseException as e:  # pragma: no cover - failure detail
            errors.append(e)
        finally:
            stop.set()

    def reader(j):
        try:
            w = (1, 2, 4)[j % 3]
            while not stop.is_set():
                rows, fleet = planner.quantile_rows(QS, w), planner.rollup(QS, w)
                answers[("rows", w, rows[0])] = rows
                answers[("rollup", w, fleet[0])] = fleet
                time.sleep(0.001)
        except BaseException as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(j,)) for j in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert not errors, errors
    assert len(answers) > 6
    for (kind, w, _), res in answers.items():
        if kind == "rows":
            version, table, rows = res
            _assert_host(log, version, w, table, rows)
        else:
            version, vals = res
            want = log.host(version, w, KEYS)
            assert list(vals) == pytest.approx(want.quantiles(QS), rel=1e-5,
                                               nan_ok=True), (version, w)
    assert win.ring_stats()["ring_reads"] > 0


def test_stale_cover_retakes_the_newest_snapshot():
    """A snapshot the ring has sealed past ``S - w + 1`` times refuses the
    read; the planner then answers from the newest snapshot and names its
    version, and the retry is counted."""
    win = KeyedWindow(SMALL, capacity=4, num_slices=4)
    log = _Log(win, seed=4)
    for _ in range(5):
        log.ingest()
        log.seal()
    log.ingest()
    old = win.snapshot()
    log.seal()
    log.seal()  # two seals: a 4-slice cover of ``old`` is overwritten
    log.ingest()
    with pytest.raises(StaleCover):
        old.windowed_row_quantiles(QS, slices=4)
    # a 3-slice window of the same snapshot lasts S - 3 + 1 = 2 seals
    old.windowed_row_quantiles(QS, slices=3)

    class Lagging:
        """The window, but its first snapshot is the stale one."""

        def __init__(self):
            self.stale = [old]

        def __getattr__(self, name):
            return getattr(win, name)

        def snapshot(self):
            return self.stale.pop() if self.stale else win.snapshot()

    planner = QueryPlanner(Lagging(), coalesce_window_s=0.0)
    retries = win.ring_stats()["ring_read_retries"]
    version, table, rows = planner.quantile_rows(QS, 4)
    assert version == win.version != old.version
    _assert_host(log, version, 4, table, rows)
    assert win.ring_stats()["ring_read_retries"] == retries + 1


def _device_bytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def _live_bytes() -> int:
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def test_window_holds_one_slab_and_few_banks_under_readers():
    """After many seals with readers polling between them, the device
    bytes the window holds stay under one slab plus four banks: no slab
    copy is made for readers."""
    base = _live_bytes()
    win = KeyedWindow(SMALL, capacity=63, num_slices=8)
    planner = QueryPlanner(win, coalesce_window_s=0.0)
    rng = np.random.default_rng(9)
    for i in range(20):
        win.record(f"k{i % 7}", (rng.pareto(1.5, 64) + 1.0).astype(np.float32))
        win.publish()
        planner.quantile_rows(QS, 8)
        planner.rollup(QS, 3)
        planner.quantile_rows(QS)
        win.advance_slice()
        win.publish()
        planner.rollup(QS, 8)
    held = _live_bytes() - base
    slab, bank = _device_bytes(win.ring.slab), _device_bytes(win.bank)
    assert slab > 8 * bank  # the bound below tells one slab from two
    assert held <= slab + 4 * bank, (held, slab, bank)
    assert win.ring_stats()["ring_reads"] == 60


# --------------------------------------------------------------------- #
# query-path fallback observability
# --------------------------------------------------------------------- #
def test_query_auto_fallback_warns_once_and_counts(monkeypatch, rng):
    """Row axes below the kernel tile route bank_quantiles and
    bank_range_merge to the XLA ref on TPU — observably: RuntimeWarning
    once per site plus dispatch_stats() counters (the read-path twin of
    the PR-7 tall-bank ingest fix)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_stats()
    spec = BucketSpec(num_buckets=64, offset=-32)
    k = 2  # below the default row_tile=8
    x = jnp.asarray((rng.pareto(1.0, 256) + 1.0).astype(np.float32))
    s = jnp.asarray(rng.integers(0, k, 256).astype(np.int32))
    bank = sb.add(sb.empty(spec, k), x, s, None, spec=spec)
    qs = jnp.asarray([0.5, 0.95], jnp.float32)
    with pytest.warns(RuntimeWarning, match="row_tile"):
        ops.bank_quantiles(
            bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
            qs, spec=spec,
        )
    assert ops.dispatch_stats()["query_fallbacks"]["bank_quantiles"] == 1
    counts = jnp.stack([bank.pos, bank.pos])  # (D=2, R=2, m)
    deltas = jnp.zeros((2, k), jnp.int32)
    with pytest.warns(RuntimeWarning, match="row_tile"):
        ops.bank_range_merge(counts, deltas, spec=spec)
    assert ops.dispatch_stats()["query_fallbacks"]["bank_range_merge"] == 1
    # warn-once: repeats count but stay quiet
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.bank_quantiles(
            bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
            qs, spec=spec,
        )
        ops.bank_range_merge(counts, deltas, spec=spec)
    stats = ops.dispatch_stats()["query_fallbacks"]
    assert stats == {"bank_quantiles": 2, "bank_range_merge": 2}
    # pinning force acknowledges the path: no warning, no count
    ops.reset_dispatch_stats()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.bank_quantiles(
            bank.pos, bank.neg, bank.zero, bank.vmin, bank.vmax, bank.level,
            qs, spec=spec, force="ref",
        )
    assert ops.dispatch_stats()["query_fallbacks"] == {}
    ops.reset_dispatch_stats()
