"""Keyed telemetry: per-metric-key windows backed by one device SketchBank.

This is the paper's multi-tenant setting (one sketch per endpoint / customer
/ host) joined with the agent -> aggregator pipeline of ``telemetry.host``:

* on device, a window is a ``SketchBank`` driven through the
  ``repro.engine`` tier — every ``record`` is **one persistent compiled
  executable call** (add + reactive collapse fused) that **donates** the
  bank, so the hot ingest loop pays neither jit re-dispatch nor a fresh
  K×m allocation per call;
* with ``num_shards > 1`` the bank rows partition over the ``keys`` mesh
  axis (``repro.engine.sharded``): the window stays one logical bank while
  its capacity scales with the mesh, and the host-side key→row map doubles
  as the key→(shard, row) router (rows stripe across shards so load
  balances as keys arrive);
* on the host, ``KeyedAggregator`` keeps one exact, unbounded ``DDSketch``
  per key and merges flushed windows in (Algorithm 4 — mixed collapse
  levels included), so any-horizon rollups per key stay exact-after-merge.

Key -> row assignment is a host-side dict (tracing never sees strings).
Rows are *recycled*: a key idle for ``evict_after`` or more consecutive
whole windows is evicted at the next reset, its row returned to a free
pool, so long-tailed key sets don't permanently exhaust capacity.  If the pool runs
dry mid-window, surplus keys collapse into the reserved ``OVERFLOW_KEY``
row — degrading gracefully while the device state shape stays static for
jit.

Resolution adapts per row (UDDSketch uniform collapse): after each
``record`` the window auto-collapses rows whose clamped mass exceeded
``collapse_threshold`` — fused into the ingest executable — and the
per-row levels *survive* window resets.  Every transition is recorded as a
``CollapseEvent`` (key, old/new level, window index, clamped mass), so
operators can see *when and why* a key's alpha degraded; ``levels()`` /
``alphas()`` report the live resolution, ``drain_events()`` hands the
event log to the serving layer.

Spans (``repro.tracing``): ``window.record_batches`` (its self time is the
row-id and concatenate stage; ``engine.ingest`` is its child),
``window.materialize_events``, ``window.publish`` (a snapshot build, with
its ``engine.snapshot`` bank copy), ``window.advance_slice`` (a seal) ⊃
``window.ring_seal`` (args ``sealed``, ``merges``: the seal and the bank
reset under the ring lock), and ``window.ring_read`` (args ``sealed``,
``ring_sealed``, ``window``, ``retried``: one windowed read's enqueue under
the ring lock).
"""

from __future__ import annotations

import re
import threading
from collections import deque
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import sketch_bank as sbank
from repro.core.ddsketch import DDSketch
from repro.core.jax_sketch import BucketSpec, effective_alpha
from repro.engine import ShardedEngine, StaleCover, WindowRing, make_engine
from repro.tracing import span

__all__ = [
    "OVERFLOW_KEY",
    "BankSnapshot",
    "CollapseEvent",
    "KeyedWindow",
    "KeyedAggregator",
    "parse_duration",
]

OVERFLOW_KEY = "__other__"

_DURATION_UNITS = {"ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0}
# one duration token: a (float) magnitude + optional unit suffix
_DURATION_TOKEN = re.compile(r"([+-]?[0-9.]+(?:e[+-]?[0-9]+)?)(ms|h|m|s)?")


def parse_duration(text) -> float:
    """``"250ms" | "30s" | "5m" | "1h30m" | "90"`` -> seconds.

    The ``?window=`` HTTP parameter grammar.  Compound forms concatenate
    tokens (``"1h30m"`` = 5400 s, ``"1m30.5s"`` works too); a bare number
    is seconds.  Raises ``ValueError`` (the HTTP layer's 400 contract)
    naming the offending token on anything unparseable, negative, or
    zero — ``"0s"`` and ``"-3s"`` are rejected the same way ``"zzz"`` is,
    not silently accepted to confuse the window validator downstream.
    """
    s = str(text).strip().lower()
    if not s:
        raise ValueError("empty duration: use e.g. 250ms, 30s, 5m, 1h30m")
    secs = 0.0
    pos = 0
    while pos < len(s):
        m = _DURATION_TOKEN.match(s, pos)
        if m is None:
            raise ValueError(
                f"unparseable duration {text!r} at {s[pos:]!r}: "
                "use e.g. 250ms, 30s, 5m, 1h30m"
            )
        num, unit = m.group(1), m.group(2)
        try:
            mag = float(num)
        except ValueError:
            raise ValueError(
                f"unparseable duration {text!r}: bad magnitude {num!r}"
            ) from None
        if unit is None and m.end() < len(s):
            # a unit-less token may only be the whole string ("90" = 90 s);
            # inside a compound it means a typo'd unit ("5x30s")
            raise ValueError(
                f"unparseable duration {text!r}: token {num!r} has no unit "
                f"(before {s[m.end():]!r})"
            )
        if mag < 0:
            raise ValueError(
                f"duration must be positive, got token {m.group(0)!r} "
                f"in {text!r}"
            )
        secs += mag * _DURATION_UNITS[unit or "s"]
        pos = m.end()
    if not secs > 0:
        raise ValueError(f"duration must be positive, got {text!r}")
    return secs


class CollapseEvent(NamedTuple):
    """One auto-collapse transition: why a key's guarantee degraded."""

    key: str
    old_level: int
    new_level: int
    window: int  # window index the transition happened in
    clamped_mass: float  # mass that had clamped when the fold fired


class BankSnapshot:
    """An immutable, version-stamped read view of a ``KeyedWindow``.

    Holds a device-side *copy* of the live bank minted by
    ``SketchEngine.snapshot`` — a fresh buffer the writer's donated
    ingest/reset paths can never consume — plus a host copy of the
    key->row map and the ring's seal count taken at the same instant.
    Live-bank reads run **lock-free**: the drain thread keeps donating into
    the live bank while any number of reader threads answer quantiles off
    this view.  Windowed reads take no slab copy: they run against the
    live ring slab (``KeyedWindow._ring_read``), which still holds this
    view's node cover for ``S - w + 1`` seals; past that they raise
    ``StaleCover`` and the caller re-takes the newest snapshot.

    ``version`` stamps the window state the view reflects (exactly one
    bump per ingest tick, slice seal, or window reset — the discrete
    events at which UDDSketch-style results can change), so it doubles as
    the result-cache key and the HTTP ``ETag``.
    """

    __slots__ = (
        "version",
        "spec",
        "engine",
        "bank",
        "key_to_row",
        "ring",
        "sealed",
        "window",
    )

    def __init__(self, *, version, window, bank, key_to_row, sealed):
        self.version = version
        self.window = window
        self.spec = window.spec
        self.engine = window.engine
        self.bank = bank
        self.key_to_row = key_to_row
        self.ring = window.ring
        self.sealed = sealed  # ring seal count at capture (None: no ring)

    # fused device reads ------------------------------------------------- #
    def row_quantiles(self, qs) -> np.ndarray:
        """Raw per-row quantiles ``(K, len(qs))`` — the coalescer's unit."""
        return np.asarray(self.engine.quantiles(self.bank, qs))

    def windowed_row_quantiles(self, qs, *, window=None, slices=None) -> np.ndarray:
        """Raw per-row windowed quantiles ``(K, len(qs))``.

        The node cover comes from ``query_args_at`` evaluated at the
        *captured* seal count and is read from the live slab; raises
        ``StaleCover`` once the ring has sealed past it.
        """
        return self._windowed(self.engine.window_query, qs, window, slices)

    def _windowed(self, read, qs, window, slices) -> np.ndarray:
        w = self.window.resolve_window(window=window, slices=slices)
        nodes, valid = self.ring.query_args_at(self.sealed, w)
        return np.asarray(self.window._ring_read(self, read, w, nodes, valid, qs))

    # keyed views (same contracts as the KeyedWindow methods) ------------ #
    def quantiles(self, key: str, qs) -> list[float]:
        rid = self.key_to_row.get(key)
        if rid is None:
            raise KeyError(f"no values recorded for key {key!r}")
        return [float(v) for v in self.row_quantiles(qs)[rid]]

    def all_quantiles(self, qs) -> dict[str, list[float]]:
        out = self.row_quantiles(qs)
        return {
            k: [float(v) for v in out[rid]]
            for k, rid in self.key_to_row.items()
            if k != OVERFLOW_KEY
        }

    def rollup_quantiles(self, qs) -> list[float]:
        out = np.asarray(self.engine.rollup_quantiles(self.bank, qs))
        return [float(v) for v in out]

    def windowed_quantiles(self, key: str, qs, *, window=None, slices=None):
        rid = self.key_to_row.get(key)
        if rid is None:
            raise KeyError(f"no values recorded for key {key!r}")
        out = self.windowed_row_quantiles(qs, window=window, slices=slices)
        return [float(v) for v in out[rid]]

    def windowed_all_quantiles(self, qs, *, window=None, slices=None):
        out = self.windowed_row_quantiles(qs, window=window, slices=slices)
        return {
            k: [float(v) for v in out[rid]]
            for k, rid in self.key_to_row.items()
            if k != OVERFLOW_KEY
        }

    def windowed_rollup(self, qs, *, window=None, slices=None) -> list[float]:
        out = self._windowed(self.engine.window_rollup, qs, window, slices)
        return [float(v) for v in out]

    def total_mass(self) -> float:
        return float(np.sum(self.engine.host_rows(self.bank.counts)))

    def levels(self) -> dict[str, int]:
        lv = self.engine.host_rows(self.bank.level)
        return {k: int(lv[r]) for k, r in self.key_to_row.items()}


class KeyedWindow:
    """One flush interval of per-key device sketches (a SketchBank + key map).

    ``capacity`` counts usable key rows; row 0 is reserved for
    ``OVERFLOW_KEY`` so an overfull window degrades gracefully instead of
    raising mid-stream.  ``collapse_threshold`` (float mass; None disables)
    controls the post-record auto-collapse: the default 0.0 folds a row as
    soon as *any* mass clamps (over- or underflow), trading up to half the
    row's resolution for covering its true range — raise it if occasional
    out-of-range outliers should be tolerated instead.  ``evict_after`` is
    the idle-window count at which a key's row is reclaimed.

    ``num_shards`` > 1 row-shards the bank over that many devices (the
    ``keys`` mesh axis); rows are handed out striped across shards.
    ``track_collapse_events=False`` drops the ``CollapseEvent`` log
    entirely.  Tracking is sync-free on the hot path: the ingest
    executable's (fired, clamped) outputs park on device and only transfer
    when the events are actually read (or the window resets).

    Thread safety: every bank *mutation* goes through ``self.lock`` (an
    RLock).  The ingest executable *donates* the bank, so two concurrent
    ``record``/``record_batches`` calls — e.g. the ingest gateway's drain
    thread racing a serving loop's flush — could otherwise hand an
    already-deleted buffer to the engine or lose one thread's update.
    Readers (``quantiles``/``total_mass``/...) do NOT contend on that
    lock: they run against the version-stamped ``BankSnapshot`` published
    by ``snapshot()`` — a device-side bank copy the donation cycle can
    never touch — and only take the lock for the brief rebuild when the
    version moved (RCU-style: the lock shrank from covering every query
    dispatch to covering the snapshot pointer swap).  Windowed reads
    enqueue against the live ring slab under a second, short ring lock,
    which a seal's donated enqueue also takes, so the device runs a read
    already enqueued before the seal that follows it.
    ``KeyedAggregator.flush`` holds the lock across its read-then-reset so
    the window swap is atomic too.
    """

    def __init__(
        self,
        spec: BucketSpec,
        capacity: int,
        *,
        collapse_threshold: float | None = 0.0,
        evict_after: int = 1,
        counts_dtype=jnp.float32,
        num_shards: int | None = None,
        track_collapse_events: bool = True,
        max_events: int = 1024,
        num_slices: int | None = None,
        slice_seconds: float | None = None,
    ):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if evict_after < 1:
            raise ValueError("evict_after must be >= 1")
        self.spec = spec
        self.capacity = capacity
        # reentrant: KeyedAggregator.flush holds it while calling reset()
        self.lock = threading.RLock()
        self.collapse_threshold = collapse_threshold
        self.evict_after = evict_after
        self.counts_dtype = counts_dtype
        self.engine = make_engine(
            spec,
            capacity + 1,
            num_shards=num_shards,
            counts_dtype=counts_dtype,
        )
        self.bank = self.engine.new_bank()
        self.key_to_row: dict[str, int] = {OVERFLOW_KEY: 0}
        self._free = self._initial_free_pool()
        self._last_seen: dict[str, int] = {}
        self._window = 0
        self.track_collapse_events = track_collapse_events
        self._events: deque[CollapseEvent] = deque(maxlen=max_events)
        # (fired, clamped, window) device outputs awaiting host transfer:
        # materializing lazily keeps the hot record() loop sync-free
        self._pending: list[tuple] = []
        # host mirror of per-row levels: reactive folds bump exactly one
        # level per fire, so events never need an extra device read
        self._levels = np.zeros(self.engine.num_sketches, np.int64)
        # optional sliding-window ring: the live bank is the head slice,
        # advance_slice() seals it and recycles the bank in place
        self.ring = (
            None if num_slices is None else WindowRing(self.engine, num_slices)
        )
        self.slice_seconds = None if slice_seconds is None else float(slice_seconds)
        # read path: monotone state version (one bump per ingest tick /
        # slice seal / reset) + the published snapshot readers run against
        self._version = 0
        self._snap: BankSnapshot | None = None
        self._snap_builds = 0
        # held while a windowed read or a seal is enqueued (never while
        # the device runs), so a read enqueued first runs on its slab
        self._ring_lock = threading.Lock()
        self._ring_reads = 0
        self._ring_read_retries = 0

    def _initial_free_pool(self) -> list[int]:
        """Usable rows, ordered so ``pop()`` balances load.

        Single-device: hands out 1, 2, ... in order.  Sharded: rows stripe
        round-robin across shards (shard 0 local 1, shard 1 local 0, ...),
        so the first S hot keys land on S different devices — the host-side
        half of the key→(shard, row) routing.
        """
        rows = list(range(1, self.capacity + 1))
        if isinstance(self.engine, ShardedEngine):
            rows.sort(key=lambda r: (self.engine.local_row(r), self.engine.shard_of(r)))
        return rows[::-1]  # pop() takes from the end

    # ------------------------------------------------------------------ #
    def row_id(self, key: str) -> int:
        """Row for ``key``, allocating from the free pool on first sight
        (overflow row if the pool is dry)."""
        rid = self.key_to_row.get(key)
        if rid is None:
            if not self._free:
                return 0  # bank full: collapse into the OVERFLOW_KEY row
            rid = self._free.pop()
            self.key_to_row[key] = rid
        if key != OVERFLOW_KEY:
            self._last_seen[key] = self._window
        return rid

    def shard_of(self, key: str) -> int:
        """Device shard holding ``key``'s row (0 on a single-device bank)."""
        rid = self.key_to_row.get(key)
        if rid is None:
            raise KeyError(f"no values recorded for key {key!r}")
        if isinstance(self.engine, ShardedEngine):
            return self.engine.shard_of(rid)
        return 0

    def process_of(self, key: str) -> int:
        """Process owning ``key``'s shard (0 unless the mesh spans hosts).

        The fleet-routing half of the key→(shard, row) map: on a
        multi-host window every process records the same key stream (the
        SPMD contract keeps the host-side row maps identical), each host's
        devices ingest only the rows they own, and this helper says who
        owns what.
        """
        rid = self.key_to_row.get(key)
        if rid is None:
            raise KeyError(f"no values recorded for key {key!r}")
        if isinstance(self.engine, ShardedEngine):
            return self.engine.process_of(rid)
        return 0

    def record(self, keys, values, weights=None) -> None:
        """Insert ``(key, value)`` pairs; one engine executable per batch.

        ``keys`` is either a sequence of strings (one per value) or a single
        string applied to every value.  The ingest executable donates the
        bank (in-place update) and fuses the reactive collapse: rows whose
        inserts clamped more than ``collapse_threshold`` mass fold once,
        and each fold is logged as a ``CollapseEvent``.
        """
        values = np.asarray(values, np.float32).reshape(-1)
        with self.lock:
            if isinstance(keys, str):
                ids = np.full(values.shape, self.row_id(keys), np.int32)
            else:
                ids = np.fromiter(
                    (self.row_id(k) for k in keys), np.int32, count=len(values)
                )
            self._ingest(values, ids, weights)

    def record_batches(self, batches) -> int:
        """Coalesce ``[(key, values, weights-or-None), ...]`` into ONE
        engine ingest — the queue -> window routing the ingest gateway
        drains through.

        Each batch's key resolves to a row once (not per value), the
        per-batch arrays concatenate into a single mixed ``(values, ids)``
        stream, and the whole tick lands in one donated executable call
        regardless of how many client batches queued up.  Weights pass
        through per batch (the degrade-to-sampling shed policy ingests
        survivors with mass-preserving weights); batches without weights
        get implicit 1s only when some other batch carries weights.
        Returns the number of value lanes ingested.
        """
        vs: list[np.ndarray] = []
        ids: list[np.ndarray] = []
        ws: list[np.ndarray] = []
        any_weighted = any(w is not None for _, _, w in batches)
        with span("window.record_batches"), self.lock:
            for key, values, weights in batches:
                v = np.asarray(values, np.float32).reshape(-1)
                if v.size == 0:
                    continue
                vs.append(v)
                ids.append(np.full(v.size, self.row_id(key), np.int32))
                if any_weighted:
                    ws.append(
                        np.ones(v.size, np.float32)
                        if weights is None
                        else np.asarray(weights, np.float32).reshape(-1)
                    )
            if not vs:
                return 0
            self._ingest(
                np.concatenate(vs),
                np.concatenate(ids),
                np.concatenate(ws) if any_weighted else None,
            )
        return int(sum(v.size for v in vs))

    def _ingest(self, values: np.ndarray, ids: np.ndarray, weights) -> None:
        self.bank, fired, clamped = self.engine.ingest(
            self.bank,
            values,
            ids,
            weights,
            threshold=self.collapse_threshold,
        )
        if fired is not None and self.track_collapse_events:
            # no host sync here: the (K,) outputs park until events are
            # read (or the window resets), so record() stays async
            self._pending.append((fired, clamped, self._window))
            if len(self._pending) >= 256:  # bound the parked device arrays
                self._materialize_events()
        # last: version N must mean "the bank state after N state changes"
        self._version += 1

    def _materialize_events(self) -> None:
        """Transfer parked (fired, clamped) outputs and log the transitions.

        Rows only change hands at ``reset`` (which materializes first), so
        the *current* row->key map is the map that held at record time.
        """
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        with span("window.materialize_events", pending=len(pending)):
            row_key = {r: k for k, r in self.key_to_row.items()}
            # one batched transfer per kind, not one blocking round trip
            # per parked array (a seal reads back hundreds of ticks')
            fired = jax.device_get([f for f, _, _ in pending])
            hot = [i for i, f in enumerate(fired) if f.any()]
            clamped = jax.device_get([pending[i][1] for i in hot])
            for i, cm in zip(hot, clamped):
                f, window = fired[i], pending[i][2]
                for r in np.flatnonzero(f):
                    old = int(self._levels[r])
                    self._levels[r] = old + 1
                    self._events.append(
                        CollapseEvent(
                            key=row_key.get(int(r), OVERFLOW_KEY),
                            old_level=old,
                            new_level=old + 1,
                            window=window,
                            clamped_mass=float(cm[r]),
                        )
                    )

    @property
    def events(self) -> "deque[CollapseEvent]":
        """Collapse-transition log (materializes any parked outputs)."""
        with self.lock:
            self._materialize_events()
        return self._events

    # ------------------------------------------------------------------ #
    # snapshot publication (the lock-free read path)
    # ------------------------------------------------------------------ #
    @property
    def version(self) -> int:
        """Monotone state version: bumps once per ingest tick (reactive
        collapse rides the same executable), slice seal, and reset — the
        only events at which any query answer can change."""
        return self._version

    def _publish_locked(self) -> BankSnapshot:
        snap = self._snap
        if snap is not None and snap.version == self._version:
            return snap
        with span("window.publish"):
            return self._build_snapshot()

    def _build_snapshot(self) -> BankSnapshot:
        snap = BankSnapshot(
            version=self._version,
            window=self,
            bank=self.engine.snapshot(self.bank),
            key_to_row=dict(self.key_to_row),
            sealed=None if self.ring is None else self.ring.sealed,
        )
        self._snap_builds += 1
        self._snap = snap
        return snap

    def snapshot(self) -> BankSnapshot:
        """The current read view (lock-free fast path).

        Returns the published version-stamped ``BankSnapshot``, rebuilding
        under the lock only when the version moved since the last build.
        The fast path is two GIL-atomic attribute reads — readers never
        wait on an in-flight ingest tick, and the writer never waits on
        readers.
        """
        snap = self._snap
        if snap is not None and snap.version == self._version:
            return snap
        with self.lock:
            return self._publish_locked()

    def publish(self) -> int:
        """Refresh the published snapshot; returns the live version.

        The gateway drain loop calls this once per tick after its
        coalesced ingest.  Self-tuning: if no reader ever took a snapshot
        the call is a no-op (a pure-write workload pays zero copy cost);
        once readers poll, each tick pre-pays the device copy so queries
        between ticks are version-matched cache hits.
        """
        if self._snap is not None:
            with self.lock:
                self._publish_locked()
        return self._version

    # ------------------------------------------------------------------ #
    def quantiles(self, key: str, qs) -> list[float]:
        """Window-local per-key quantiles off the published snapshot
        (one fused bank-query executable for all qs, indexed at the key's
        row; lock-free vs concurrent ingest)."""
        return self.snapshot().quantiles(key, qs)

    def all_quantiles(self, qs) -> dict[str, list[float]]:
        """Window-local quantiles for *every* live key in one fused bank
        query — the serving path for per-endpoint dashboards: one compiled
        executable answers len(keys) x len(qs) estimates off one cumsum per
        row (gathered across shards when the bank is sharded), instead of a
        per-key (let alone per-q) query loop."""
        return self.snapshot().all_quantiles(qs)

    def rollup_quantiles(self, qs) -> list[float]:
        """Fleet-view quantiles of the union of *every* row in the window
        (all keys plus the overflow sink) — "p99 across all tenants".

        One compiled engine call: rows align to the bank-max collapse level
        and sum into a single bucket array (Algorithm 4 as a row-axis
        reduction; a psum under a sharded engine), then one Algorithm 2
        query answers every q.  NaN when the window is empty.
        """
        return self.snapshot().rollup_quantiles(qs)

    def total_mass(self) -> float:
        """Total ingested mass across every row (incl. the overflow sink).

        The conservation probe the gateway's accounting tests ride:
        ``ingested mass + recorded shed mass == submitted mass``.
        """
        return self.snapshot().total_mass()

    def keys(self) -> list[str]:
        return [k for k in self.key_to_row if k != OVERFLOW_KEY]

    def levels(self) -> dict[str, int]:
        """Per-key uniform-collapse level (0 = full resolution)."""
        return self.snapshot().levels()

    def alphas(self) -> dict[str, float]:
        """Per-key effective relative-error guarantee at the live level."""
        return {
            k: effective_alpha(self.spec, lv) for k, lv in self.levels().items()
        }

    def drain_events(self) -> list[CollapseEvent]:
        """Hand off (and clear) the collapse-transition log."""
        with self.lock:
            self._materialize_events()
            out = list(self._events)
            self._events.clear()
        return out

    # ------------------------------------------------------------------ #
    # sliding-window ring (num_slices-enabled windows over time slices)
    # ------------------------------------------------------------------ #
    def _ring_read(self, snap: BankSnapshot, read, w: int, nodes, valid, qs):
        """Enqueue ``read`` (``engine.window_query`` / ``window_rollup``) of
        ``snap``'s cover against the live slab; returns the device result.

        Under the ring lock: take the slab, check the cover is intact at
        ``snap.sealed`` (``WindowRing.cover_intact``), enqueue.  A seal
        enqueued later donates the slab only after this read has run.
        Raises ``StaleCover`` when the ring has sealed past the cover.
        """
        ring = self.ring
        with span("window.ring_read", sealed=snap.sealed, window=w) as sp:
            with self._ring_lock:
                intact = ring.cover_intact(snap.sealed, w)
                sp.set(ring_sealed=ring.sealed, retried=int(not intact))
                if not intact:
                    self._ring_read_retries += 1
                    raise StaleCover(
                        f"{w}-slice cover at seal {snap.sealed} overwritten "
                        f"by the ring at seal {ring.sealed}"
                    )
                self._ring_reads += 1
                return read(ring.slab, snap.bank, nodes, valid, True, qs)

    def _require_ring(self) -> WindowRing:
        if self.ring is None:
            raise ValueError(
                "windowed queries need a slice ring: construct the "
                "KeyedWindow with num_slices="
            )
        return self.ring

    def advance_slice(self) -> int:
        """Seal the live slice into the ring and recycle the bank in place.

        The window-advance tick (the ingest gateway calls this on its
        monotonic slice clock): the live bank is copied into the ring's
        head slot (slab donated), then reset through the engine's donated
        path with ``levels=None`` — so per-key collapse levels survive
        slice turnover and the expiring slice's buffers become the new
        head with zero allocation.  Returns the number of merge-tree node
        rebuilds the seal triggered.
        """
        ring = self._require_ring()
        with span("window.advance_slice"), self.lock:
            self._window += 1
            self._materialize_events()
            with span("window.ring_seal") as sp, self._ring_lock:
                merges = ring.seal(self.bank)
                self.bank = self.engine.reset(self.bank)
                sp.set(sealed=ring.sealed, merges=merges)
            self._version += 1
        return merges

    def resolve_window(self, window=None, slices=None) -> int:
        """``?window=5m`` / ``?slices=8`` -> a validated slice count.

        Exactly one of the two must be given.  Durations round *up* to
        whole slices (a 5m window over 60s slices covers 5 slices + the
        live head) and require ``slice_seconds`` to be configured; raises
        ``ValueError`` (the HTTP 400 contract) on unparseable input or
        windows wider than the ring.
        """
        ring = self._require_ring()
        if (window is None) == (slices is None):
            raise ValueError("pass exactly one of window= or slices=")
        if slices is not None:
            try:
                w = int(str(slices))
            except ValueError:
                raise ValueError(
                    f"slices must be an integer, got {slices!r}"
                ) from None
        else:
            secs = parse_duration(window)
            if self.slice_seconds is None:
                raise ValueError(
                    "duration windows need slice_seconds configured; "
                    "use slices= instead"
                )
            w = max(1, int(np.ceil(secs / self.slice_seconds)))
        if w < 1:
            raise ValueError(f"window must cover at least 1 slice, got {w}")
        if w > ring.num_slices:
            raise ValueError(
                f"window of {w} slices exceeds the ring "
                f"({ring.num_slices} slices retained)"
            )
        return w

    def windowed_quantiles(
        self, key: str, qs, *, window=None, slices=None
    ) -> list[float]:
        """Per-key quantiles over the last N slices (live slice included).

        One fused engine dispatch — gather the ring's O(log S) cached
        nodes, level-reconcile, reduce the slice axis, Algorithm 2 — vs
        N-1 host-looped merges.  Runs against the published snapshot's
        bank copy and the live slab, lock-free vs concurrent ingest.
        """
        self._require_ring()
        return self.snapshot().windowed_quantiles(
            key, qs, window=window, slices=slices
        )

    def windowed_all_quantiles(
        self, qs, *, window=None, slices=None
    ) -> dict[str, list[float]]:
        """Windowed quantiles for every live key (one fused dispatch)."""
        self._require_ring()
        return self.snapshot().windowed_all_quantiles(
            qs, window=window, slices=slices
        )

    def windowed_rollup(self, qs, *, window=None, slices=None) -> list[float]:
        """Fleet-view quantiles over the last N slices ("p99 across all
        tenants, last 5 minutes") — stays one psum on a sharded bank."""
        self._require_ring()
        return self.snapshot().windowed_rollup(qs, window=window, slices=slices)

    def ring_stats(self) -> dict | None:
        """Ring occupancy / maintenance metadata, with the windowed reads
        enqueued on the live slab (``ring_reads``) and those refused for a
        stale cover (``ring_read_retries``); None when no ring."""
        if self.ring is None:
            return None
        with self._ring_lock:
            return {
                **self.ring.stats(),
                "ring_reads": self._ring_reads,
                "ring_read_retries": self._ring_read_retries,
            }

    def engine_stats(self) -> dict:
        """Executable-cache + ring + read-path observability (/stats)."""
        with self.lock:
            out = {
                "executable_cache": self.engine.cache_info(),
                "read_path": {
                    "version": self._version,
                    "snapshot_builds": self._snap_builds,
                },
            }
        if self.ring is not None:
            out["ring"] = self.ring_stats()
        return out

    def reset(self) -> None:
        """Start the next window.

        One donated executable zeroes the bank in place.  Keys idle for
        ``evict_after`` or more whole windows are evicted — their rows
        rejoin the free pool at level 0 — while live keys keep both their
        rows *and* their adapted collapse levels, so stable hot keys stay
        stable across windows.
        """
        with self.lock:
            self._window += 1
            self._materialize_events()  # before rows change hands below
            levels = self.engine.host_rows(self.bank.level).copy()
            for key in list(self.key_to_row):
                if key == OVERFLOW_KEY:
                    continue
                if self._window - self._last_seen.get(key, self._window) > self.evict_after:
                    rid = self.key_to_row.pop(key)
                    self._last_seen.pop(key, None)
                    self._free.append(rid)
                    levels[rid] = 0  # fresh tenants start at full resolution
            self._levels = levels.astype(np.int64)
            self.bank = self.engine.reset(self.bank, levels.astype(np.int32))
            self._version += 1


class KeyedAggregator:
    """Host-tier rollups: one exact DDSketch per key, merged across windows.

    Window rows arrive at whatever collapse level they adapted to; the
    host-tier merge aligns mixed levels (collapsing the finer operand), so
    per-key totals stay exact-after-merge and ``alphas()`` reports the
    effective guarantee each rollup currently offers.  Collapse-transition
    events drain from each flushed window into ``events`` so the serving
    layer can report when/why a key degraded.
    """

    def __init__(self, spec: BucketSpec, max_events: int = 4096):
        self.spec = spec
        self.totals: dict[str, DDSketch] = {}
        self.windows_flushed = 0
        self.events: deque[CollapseEvent] = deque(maxlen=max_events)

    def flush(self, window: KeyedWindow) -> None:
        """Merge a device window into the per-key totals and reset it.

        Lossless per row (same bucket geometry at the row's level);
        Algorithm 4 makes the per-key rollup exactly equal to a sketch that
        saw all the data at the coarsest level the key ever reached.

        The bank moves host-side in one pytree transfer (an all_gather per
        leaf when the window spans processes — every flushing host then
        aggregates the same totals, keeping the host tier replicated).

        Holds ``window.lock`` across the read-then-reset so a concurrent
        writer (the ingest gateway's drain thread) can neither donate the
        bank away mid-read nor slip a record between the snapshot and the
        reset (which would silently drop it).
        """
        with window.lock:
            bank_h = window.engine.host_bank(window.bank)
            counts = np.asarray(bank_h.counts)
            for key, rid in window.key_to_row.items():
                if counts[rid] == 0:
                    continue
                host = sbank.to_host(bank_h, window.spec, rid)
                if key in self.totals:
                    self.totals[key].merge(host)
                else:
                    self.totals[key] = host
            self.events.extend(window.drain_events())
            self.windows_flushed += 1
            window.reset()

    def quantiles(self, key: str, qs) -> list[float]:
        return self.totals[key].quantiles(qs)

    def alphas(self) -> dict[str, float]:
        """Per-key effective relative-error guarantee of the rollups."""
        return {k: sk.effective_alpha for k, sk in self.totals.items()}

    def events_for(self, key: str) -> list[CollapseEvent]:
        """Collapse transitions recorded for one key (all flushed windows)."""
        return [e for e in self.events if e.key == key]

    def keys(self) -> list[str]:
        return [k for k in self.totals if k != OVERFLOW_KEY]
