"""SketchEngine: persistent compiled executables + donated in-place ingest.

Everything above the kernels used to pay two taxes on the hot ingest loop:

* **dispatch** — each ``sketch_bank.add`` / ``quantiles`` call re-entered a
  ``jax.jit`` wrapper, re-hashing the ``BucketSpec`` static argument and
  re-checking the trace-cache signature per call;
* **allocation** — every state-in/state-out step produced a *fresh* bank
  (two new ``(K, m)`` buffers per ``record``), so a 4096×2048 bank churned
  ~64 MiB of allocations per ingest call.

``SketchEngine`` removes both.  It owns one AOT-lowered executable per
(path, batch geometry) — built once via ``jit(...).lower(...).compile()``
and then invoked directly, skipping the jit front door entirely — and every
state-in/state-out path (``ingest``, ``collapse_to``, ``reset``, ``merge``)
**donates** the input bank pytree, so XLA updates the K×m buffers in place
instead of allocating a fresh bank per call.

Consequence of donation (the standard jax contract): after
``bank = engine.ingest(bank, ...)`` the *old* bank reference is dead —
rebind, never reuse.  Engine methods are host-side entry points; inside a
``jit``/``shard_map`` trace call the ``sketch_bank`` impls directly.

Batch geometry: executables are shape-specialized, so ``ingest`` pads
ragged batches up to the next power of two (NaN values / id -1 / weight 0
lanes contribute nothing by the kernel contract) — a stream of arbitrary
batch sizes compiles O(log N) executables, not O(#distinct sizes).

The per-spec bucket-value tables live in ``repro.engine.tables`` — one host
construction + one device upload per spec per process, shared by every
executable this engine builds (and by the non-engine query paths).

Argument/output *kinds* annotate each executable's signature so the
row-sharded subclass (``repro.engine.sharded.ShardedEngine``) can reuse
these exact call paths under ``shard_map``:

* ``"bank"``   — the SketchBank pytree (row axis leading on every leaf);
* ``"slab"``   — a WindowRing slab pytree (leading *node* axis, then the
  bank row axis on every leaf — replicated over nodes, sharded over rows);
* ``"rows"``   — a per-row ``(K,)`` array (collapse targets, reset levels);
* ``"batch"``  — a streamed batch axis (values / weights), replicated;
* ``"ids"``    — like batch, but carries *global* row ids the sharded
  engine rebases to shard-local ids;
* ``"scalar"`` — replicated scalars (thresholds, qs).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import jax_sketch
from repro.core import sketch_bank as sbank
from repro.core.sketch_bank import SketchBank
from repro.engine.tables import next_pow2
from repro.kernels import ops
from repro.kernels.ref import MAX_COLLAPSE_LEVEL, BucketSpec, bank_quantiles_ref

__all__ = ["SketchEngine", "shared_engine", "window_merge_bank"]

_MIN_BATCH = 32  # smallest padded ingest batch (executable-count floor)


def _pad_to_bucket(n: int) -> int:
    """Next power-of-two >= n (floored at ``_MIN_BATCH``)."""
    return next_pow2(n, _MIN_BATCH)


@lru_cache(maxsize=None)
def shared_engine(
    spec: BucketSpec,
    num_sketches: int,
    *,
    counts_dtype=jnp.float32,
    method: str | None = None,
) -> "SketchEngine":
    """Process-wide engine registry, one per bank geometry.

    Engines are stateless with respect to their banks, so every caller
    whose rows pad to the same (spec, K, dtype, backend) — the telemetry
    tier, ad-hoc banks, tests — can share one engine and its compiled
    executables instead of re-lowering per caller.  Pair with
    ``tables.padded_row_count`` to round row counts onto the shared grid.
    """
    return SketchEngine(
        spec,
        num_sketches,
        counts_dtype=counts_dtype,
        method=method,
    )


def _zero_where(mask: jnp.ndarray, arr: jnp.ndarray) -> jnp.ndarray:
    """``where(mask, 0, arr)`` without dtype promotion (int counters stay int)."""
    return jnp.where(mask, jnp.zeros_like(arr), arr)


def window_merge_bank(
    slab: SketchBank,
    bank: SketchBank,
    nodes: jnp.ndarray,
    valid: jnp.ndarray,
    live: jnp.ndarray,
    *,
    spec: BucketSpec,
) -> SketchBank:
    """Traced body of a window query: gather + fused range merge -> one bank.

    Gathers the ``nodes`` (shape ``(D,)``, int32, masked by ``valid``
    (D,) float 0/1 — padding entries point anywhere and contribute
    nothing) out of the ring slab, appends the live bank as one more slice
    gated by the ``live`` scalar, reconciles every slice row to the
    range's per-row max collapse level, and reduces the slice axis — the
    pos and neg stores ride ONE ``ops.bank_range_merge`` dispatch as a
    stacked ``(D+1, 2K, m)`` block.  Returns a float32 ``SketchBank``
    holding the merged rows, bit-identical (for integer-valued counts) to
    sequentially ``sketch_bank.merge``-ing the selected slices.

    Shard-safe: every op is row-local (the node axis is replicated per
    shard), so the same body runs under the base jit and under the
    sharded engine's ``shard_map``.

    Two runtime paths behind a ``lax.cond``, decided *before* any count
    data moves (only the tiny ``(D+1, K)`` level gather is unconditional):

    * **steady state** — every live slice row already sits at the range
      max level (no folds anywhere, the common case once collapse has
      settled): the merge is a weighted accumulate of slab slices read
      in place, ONE streaming pass over the node data with no gather
      copy and no concat;
    * **reconciliation** — gather + stack the cover into a
      ``(D+1, 2K, m)`` block and run the fused ``ops.bank_range_merge``
      (dead slices dropped inside the merge via ``valid``, never by a
      mask multiply over the slab).
    """
    f32 = jnp.float32
    k = bank.level.shape[0]
    def take(leaf):
        return jnp.take(leaf, nodes, axis=0)

    def stack(node_leaf, bank_leaf):
        return jnp.concatenate(
            [node_leaf.astype(f32), bank_leaf.astype(f32)[None]], axis=0
        )

    mask = jnp.concatenate(
        [valid.astype(f32).reshape(-1), live.astype(f32).reshape(1)]
    )  # (D+1,)
    alive = mask > 0
    lvl = jnp.concatenate([take(slab.level), bank.level[None]], axis=0)
    target = jnp.max(jnp.where(alive[:, None], lvl, 0), axis=0)  # (K,)
    delta = target[None, :] - lvl  # (D+1, K)
    # dead slices: any delta sign; live ones: >= 0 by construction
    steady = jnp.all(jnp.where(alive[:, None], delta, 0) == 0)

    def steady_merge(_):
        # exact for integer-valued f32 counts in any accumulation order,
        # so node order here matches sequential merges bit-for-bit.  The
        # node loop is unrolled (static, <= 2 log2 S + 1 slices): XLA CPU
        # only parallelizes straight-line fusions, so an unrolled chain of
        # dynamic slices streams ~5x faster than the same loop under fori
        acc_pos = mask[-1] * bank.pos.astype(f32)
        acc_neg = mask[-1] * bank.neg.astype(f32)
        for d in range(nodes.shape[0]):
            p = jax.lax.dynamic_slice_in_dim(slab.pos, nodes[d], 1, axis=0)
            n = jax.lax.dynamic_slice_in_dim(slab.neg, nodes[d], 1, axis=0)
            acc_pos = acc_pos + mask[d] * p[0].astype(f32)
            acc_neg = acc_neg + mask[d] * n[0].astype(f32)
        return acc_pos, acc_neg

    def general_merge(_):
        counts = jnp.concatenate(
            [stack(take(slab.pos), bank.pos), stack(take(slab.neg), bank.neg)],
            axis=1,
        )  # (D+1, 2K, m)
        merged = ops.bank_range_merge(
            counts,
            jnp.concatenate([delta, delta], axis=1),
            spec=spec,
            valid=mask,
        )
        return merged[:k], merged[k:]

    pos, neg = jax.lax.cond(steady, steady_merge, general_merge, 0)

    def msum(node_leaf, bank_leaf):
        return jnp.sum(stack(take(node_leaf), bank_leaf) * mask[:, None], axis=0)

    def mext(node_leaf, bank_leaf, fill, red):
        x = jnp.where(alive[:, None], stack(take(node_leaf), bank_leaf), fill)
        return red(x, axis=0)

    return SketchBank(
        pos=pos,
        neg=neg,
        zero=msum(slab.zero, bank.zero),
        overflow=msum(slab.overflow, bank.overflow),
        underflow=msum(slab.underflow, bank.underflow),
        summ=msum(slab.summ, bank.summ),
        vmin=mext(slab.vmin, bank.vmin, jnp.inf, jnp.min),
        vmax=mext(slab.vmax, bank.vmax, -jnp.inf, jnp.max),
        level=target,
    )


class SketchEngine:
    """Compiled call paths for one bank geometry (spec, K, dtype, method).

    Stateless with respect to the bank: banks are passed in and returned
    (donated) like any jax state, so one engine can drive many banks of the
    same geometry.  ``new_bank()`` mints a fresh one.

    ``method`` pins the insert pipeline exactly as ``sketch_bank.add``
    does (the backend is ``kernels.ops``' automatic choice: the compiled
    Pallas kernels on TPU, the XLA reference elsewhere); ``collapse_threshold`` semantics
    live at the call site (``ingest(..., threshold=)``), not here, so one
    executable serves every threshold value.
    """

    def __init__(
        self,
        spec: BucketSpec,
        num_sketches: int,
        *,
        counts_dtype=jnp.float32,
        method: str | None = None,
    ):
        self.spec = spec
        self.num_sketches = int(num_sketches)
        self.counts_dtype = jax_sketch._counts_dtype(counts_dtype)
        self.method = method
        self._cache: dict[tuple, Any] = {}
        self._hits = 0
        self._misses = 0
        # host-side hooks fired at the top of every state-mutating tick
        # (`ingest`): the gateway's drain loop and the chaos harness use
        # them to observe/perturb ticks (e.g. injected slow-engine sleeps)
        # without wrapping the call path; empty list = zero overhead
        self.tick_hooks: list[Callable[[str], None]] = []

    def _fire_tick_hooks(self, path: str) -> None:
        for hook in self.tick_hooks:
            hook(path)

    # ------------------------------------------------------------------ #
    # executable cache
    # ------------------------------------------------------------------ #
    def _wrap(
        self,
        fn: Callable,
        donate: tuple[int, ...],
        in_kinds: Sequence[str],
        out_kinds: Sequence[str],
    ) -> Callable:
        """Build the jit-able callable; the sharded engine wraps in shard_map."""
        del in_kinds, out_kinds
        return jax.jit(fn, donate_argnums=donate)

    def _compiled(
        self,
        key: tuple,
        build: Callable,
        donate: tuple[int, ...],
        in_kinds: Sequence[str],
        out_kinds: Sequence[str],
        *args,
    ):
        """AOT-lower ``build`` against ``args`` once; reuse forever after.

        ``key`` captures the batch geometry the executable is specialized
        to; ``donate`` lists argument positions whose buffers the
        executable consumes (state-in/state-out paths donate the bank).
        """
        exe = self._cache.get(key)
        if exe is None:
            self._misses += 1
            exe = self._wrap(build, donate, in_kinds, out_kinds).lower(*args).compile()
            self._cache[key] = exe
        else:
            self._hits += 1
        return exe(*args)

    def cache_info(self) -> dict:
        return {
            "executables": len(self._cache),
            "hits": self._hits,
            "misses": self._misses,
        }

    # ------------------------------------------------------------------ #
    # bank lifecycle
    # ------------------------------------------------------------------ #
    def new_bank(self) -> SketchBank:
        """Fresh zero bank in this engine's geometry."""
        return self._place(
            sbank.empty(self.spec, self.num_sketches, counts_dtype=self.counts_dtype)
        )

    def _place(self, bank: SketchBank) -> SketchBank:
        """Hook for subclasses: pin the bank's device placement."""
        return bank

    def _rows(self, arr) -> jnp.ndarray:
        """A ``(K,)`` per-row argument, placed like the bank's row axis."""
        return jnp.asarray(arr)

    def _prep_batch(self, v, s, w, *, block: int | None = None):
        """Pack a host batch for ingest: ``(values, ids, weights, geom)``.

        The base engine pads to the next power-of-two bucket (inert lanes:
        NaN value / id -1 / weight 0) so ragged streams compile O(log N)
        executables; ``geom`` keys the executable cache.  The sharded
        engine overrides this with the shard-routed ``keys``-sharded
        layout (``ShardedEngine.route``).
        """
        del block
        n = v.size
        pad = _pad_to_bucket(max(n, 1)) - n
        if pad:
            v = np.pad(v, (0, pad), constant_values=np.nan)
            s = np.pad(s, (0, pad), constant_values=-1)
            if w is not None:
                w = np.pad(w, (0, pad))
        return (
            jnp.asarray(v),
            jnp.asarray(s),
            None if w is None else jnp.asarray(w),
            v.size,
        )

    # host-side reads ---------------------------------------------------- #
    def host_rows(self, arr) -> np.ndarray:
        """A per-row device array ((K,) or (K, Q)) as a host np array.

        The sharded engine overrides this with a cross-process gather when
        the bank spans hosts; going through this hook keeps every host-side
        consumer (telemetry resets, aggregator flushes) mesh-agnostic.
        """
        return np.asarray(arr)

    def host_bank(self, bank: SketchBank) -> SketchBank:
        """The whole bank pytree as host np arrays (one transfer per leaf)."""
        return jax.tree.map(np.asarray, bank)

    def snapshot(self, state: SketchBank) -> SketchBank:
        """A device-side copy of a bank (or slab) into FRESH buffers.

        The read-path publish step: the returned pytree shares no buffers
        with ``state``, so later donated mutations of the live state
        (``ingest``/``reset``/``seal_slice``) can never invalidate it —
        readers query the snapshot lock-free while writers keep donating.

        One compiled executable per geometry; never donated.  The body is
        ``lax.optimization_barrier`` rather than a bare identity: jax
        passes *unmodified* jit outputs through as the input array itself
        (which a later donation would then consume out from under the
        snapshot), while any real primitive forces XLA to materialize
        fresh, bit-identical output buffers.
        """
        kind = "slab" if state.pos.ndim == 3 else "bank"

        def copy_impl(b: SketchBank) -> SketchBank:
            return jax.lax.optimization_barrier(b)

        return self._compiled(
            ("snapshot", kind),
            copy_impl,
            (),
            (kind,),
            (kind,),
            state,
        )

    def reset(self, bank: SketchBank, levels=None) -> SketchBank:
        """Zero the bank **in place** (donated), keeping or replacing levels.

        The window-reset path: counts/sums/extrema clear, per-row collapse
        levels persist (``levels=None``) or are overwritten (shape ``(K,)``
        int32 — the eviction path resets reclaimed rows to level 0).
        """

        def reset_impl(b: SketchBank, lv: jnp.ndarray) -> SketchBank:
            z = jax.tree.map(jnp.zeros_like, b)
            return z._replace(
                vmin=jnp.full_like(b.vmin, jnp.inf),
                vmax=jnp.full_like(b.vmax, -jnp.inf),
                level=lv,
            )

        # np round-trip: never hand the donated bank's own level buffer
        # back as a second argument (aliased donation is undefined)
        lv = self._rows(
            np.asarray(
                self.host_rows(bank.level) if levels is None else levels, np.int32
            )
        )
        return self._compiled(
            ("reset",),
            reset_impl,
            (0,),
            ("bank", "rows"),
            ("bank",),
            bank,
            lv,
        )

    # ------------------------------------------------------------------ #
    # ingest (donated, fused with the reactive collapse)
    # ------------------------------------------------------------------ #
    def add(
        self,
        bank: SketchBank,
        values,
        sketch_ids,
        weights=None,
        *,
        auto_collapse: bool = False,
        block: int | None = None,
    ) -> SketchBank:
        """Donated ``sketch_bank.add``: the input bank is updated in place."""
        bank, _, _ = self.ingest(
            bank, values, sketch_ids, weights, auto_collapse=auto_collapse,
            block=block,
        )
        return bank

    def ingest(
        self,
        bank: SketchBank,
        values,
        sketch_ids,
        weights=None,
        *,
        threshold: float | None = None,
        auto_collapse: bool = False,
        block: int | None = None,
    ) -> tuple[SketchBank, Any, Any]:
        """One compiled call: add a batch, then reactive-collapse hot rows.

        Returns ``(bank, fired, clamped)``.  With ``threshold`` set (the
        ``KeyedWindow`` post-record collapse), ``fired`` is the ``(K,)``
        bool mask of rows that folded this call and ``clamped`` the mass
        each had clamped before folding — the observability hooks for
        collapse-transition events — computed inside the same executable
        instead of a second dispatch.  ``threshold=None`` skips the
        reactive pass and returns ``(bank, None, None)``.

        The batch is padded to a power-of-two bucket (invalid lanes
        contribute nothing), so ragged streams reuse a handful of
        executables; the bank argument is always donated.  ``block`` pins
        the padded per-shard block size on a sharded engine — the
        multi-host contract when each process feeds only its local lanes
        (see ``ShardedEngine.route``); single-device engines ignore it.
        """
        if self.tick_hooks:
            self._fire_tick_hooks("ingest")
        v = np.asarray(values, np.float32).reshape(-1)
        s = np.asarray(sketch_ids, np.int32).reshape(-1)
        if v.shape != s.shape:
            raise ValueError(f"values {v.shape} vs sketch_ids {s.shape}")
        w = None if weights is None else np.asarray(weights, np.float32).reshape(-1)
        vv, ss, ww, geom = self._prep_batch(v, s, w, block=block)

        reactive = threshold is not None
        key = ("ingest", geom, w is not None, reactive, auto_collapse)

        def ingest_impl(b, vv, ss, ww, thr):
            b = sbank.add_impl(
                b,
                vv,
                ss,
                ww,
                spec=self.spec,
                auto_collapse=auto_collapse,
                method=self.method,
            )
            if not reactive:
                return b
            clamped = (b.overflow + b.underflow).astype(jnp.float32)
            fire = (clamped > thr) & (b.level < MAX_COLLAPSE_LEVEL)
            folded = sbank.collapse(b, fire, spec=self.spec)
            b = folded._replace(
                overflow=_zero_where(fire, b.overflow),
                underflow=_zero_where(fire, b.underflow),
            )
            return b, fire, clamped

        thr = jnp.asarray(0.0 if threshold is None else threshold, jnp.float32)
        out = self._compiled(
            key,
            ingest_impl,
            (0,),
            ("bank", "batch", "ids", "batch", "scalar"),
            ("bank", "rows", "rows") if reactive else ("bank",),
            bank,
            vv,
            ss,
            ww,
            thr,
        )
        if not reactive:
            return out, None, None
        return out

    # ------------------------------------------------------------------ #
    # resolution management (donated)
    # ------------------------------------------------------------------ #
    def collapse_to(self, bank: SketchBank, target) -> SketchBank:
        """Donated ``sketch_bank.collapse_to`` (scalar or ``(K,)`` target)."""
        tgt = self._rows(
            np.broadcast_to(np.asarray(target, np.int32), (self.num_sketches,))
        )

        def collapse_impl(b, t):
            return sbank.collapse_to(b, t, spec=self.spec)

        return self._compiled(
            ("collapse_to",),
            collapse_impl,
            (0,),
            ("bank", "rows"),
            ("bank",),
            bank,
            tgt,
        )

    def auto_collapse(self, bank: SketchBank, threshold: float = 0.0) -> SketchBank:
        """Donated reactive collapse (see ``sketch_bank.auto_collapse``)."""

        def auto_impl(b, thr):
            return sbank.auto_collapse(
                b, spec=self.spec, threshold=thr
            )

        thr = jnp.asarray(threshold, jnp.float32)
        return self._compiled(
            ("auto_collapse",),
            auto_impl,
            (0,),
            ("bank", "scalar"),
            ("bank",),
            bank,
            thr,
        )

    # ------------------------------------------------------------------ #
    # merge (Algorithm 4; donates the left operand)
    # ------------------------------------------------------------------ #
    def merge(self, a: SketchBank, b: SketchBank) -> SketchBank:
        """Donated ``sketch_bank.merge``: ``a``'s buffers take the result."""

        def merge_impl(x, y):
            return sbank.merge(x, y, spec=self.spec)

        return self._compiled(
            ("merge",),
            merge_impl,
            (0,),
            ("bank", "bank"),
            ("bank",),
            a,
            b,
        )

    # ------------------------------------------------------------------ #
    # window-ring slab: stacked per-slice banks + fused range queries
    # ------------------------------------------------------------------ #
    def new_slab(self, num_nodes: int) -> SketchBank:
        """A stacked bank-of-banks: every leaf gains a leading node axis.

        Node 0..S-1 are the ring's sealed-slice leaves and S..2S-2 the
        merge-tree internals (``repro.engine.ring.WindowRing`` owns the
        indexing); the engine only sees one ``(num_nodes, K, ...)`` pytree
        it seals into, merges within, and range-queries — all in place via
        donation, so a ring's memory footprint is exactly one slab.
        """
        bank = sbank.empty(
            self.spec, self.num_sketches, counts_dtype=self.counts_dtype
        )
        slab = jax.tree.map(
            lambda leaf: jnp.array(
                jnp.broadcast_to(leaf[None], (num_nodes, *leaf.shape))
            ),
            bank,
        )
        return self._place_slab(slab)

    def _place_slab(self, slab: SketchBank) -> SketchBank:
        """Hook for subclasses: pin the slab's device placement."""
        return slab

    def seal_slice(self, slab: SketchBank, bank: SketchBank, node) -> SketchBank:
        """Write ``bank`` into slab node ``node`` in place (slab donated).

        The bank itself is *not* consumed — the caller recycles it through
        the donated ``reset`` path (levels surviving), which is what makes
        window advance allocation-free.
        """

        def seal_impl(sl, b, i):
            return jax.tree.map(
                lambda leaf, x: leaf.at[i].set(x.astype(leaf.dtype)), sl, b
            )

        return self._compiled(
            ("slab_seal", slab.level.shape[0]),
            seal_impl,
            (0,),
            ("slab", "bank", "scalar"),
            ("slab",),
            slab,
            bank,
            jnp.asarray(int(node), jnp.int32),
        )

    def merge_node(self, slab: SketchBank, dst, left, right) -> SketchBank:
        """``slab[dst] = merge(slab[left], slab[right])`` in place (donated).

        The merge-tree maintenance step: one Algorithm 4 merge between two
        resident nodes, never leaving the device.
        """

        def node_impl(sl, d, a, b):
            lhs = jax.tree.map(lambda leaf: leaf[a], sl)
            rhs = jax.tree.map(lambda leaf: leaf[b], sl)
            merged = sbank.merge(lhs, rhs, spec=self.spec)
            return jax.tree.map(
                lambda leaf, x: leaf.at[d].set(x.astype(leaf.dtype)), sl, merged
            )

        i32 = jnp.int32
        return self._compiled(
            ("slab_merge_node", slab.level.shape[0]),
            node_impl,
            (0,),
            ("slab", "scalar", "scalar", "scalar"),
            ("slab",),
            slab,
            jnp.asarray(int(dst), i32),
            jnp.asarray(int(left), i32),
            jnp.asarray(int(right), i32),
        )

    def window_query(
        self, slab: SketchBank, bank: SketchBank, nodes, valid, include_live, qs
    ) -> jnp.ndarray:
        """Per-row quantiles over a slice range: ``(K, len(qs))``.

        ``nodes`` / ``valid`` are the ring's padded O(log S) node cover of
        the range (``WindowRing.query_args``); ``include_live`` gates the
        un-sealed head slice.  The whole thing — gather, level
        reconciliation, slice reduction, Algorithm 2 — is ONE executable
        around ONE fused ``bank_range_merge`` dispatch, vs W-1 host-looped
        ``merge`` calls plus a separate query.  Not donated: querying must
        not consume ring or bank.
        """
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        nodes = np.asarray(nodes, np.int32).reshape(-1)
        valid = np.asarray(valid, np.float32).reshape(-1)
        from repro.engine.tables import device_value_table

        def query_impl(sl, b, nd, vm, lv, q, t):
            mb = window_merge_bank(
                sl, b, nd, vm, lv, spec=self.spec
            )
            return ops.bank_quantiles(
                mb.pos,
                mb.neg,
                mb.zero,
                mb.vmin,
                mb.vmax,
                mb.level,
                q,
                spec=self.spec,
                table=t,
            )

        return self._compiled(
            ("window_query", slab.level.shape[0], nodes.size, qf.size),
            query_impl,
            (),
            ("slab", "bank", "scalar", "scalar", "scalar", "scalar", "scalar"),
            ("rowsq",),
            slab,
            bank,
            jnp.asarray(nodes),
            jnp.asarray(valid),
            jnp.asarray(1.0 if include_live else 0.0, jnp.float32),
            jnp.asarray(qf),
            device_value_table(self.spec),
        )

    def window_rollup(
        self, slab: SketchBank, bank: SketchBank, nodes, valid, include_live, qs
    ) -> jnp.ndarray:
        """Quantiles of every row over a slice range, shape ``(len(qs),)``.

        ``rollup_quantiles`` with the window's fused range merge in front:
        merged rows collapse to their max level, sum into one bucket array,
        and answer one Algorithm 2 query.  ``ShardedEngine`` overrides this
        with the psum form.
        """
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        nodes = np.asarray(nodes, np.int32).reshape(-1)
        valid = np.asarray(valid, np.float32).reshape(-1)
        from repro.engine.tables import device_value_table

        def rollup_impl(sl, b, nd, vm, lv, q, t):
            mb = window_merge_bank(
                sl, b, nd, vm, lv, spec=self.spec
            )
            gmax = jnp.max(mb.level)
            mb = sbank.collapse_to(
                mb,
                jnp.broadcast_to(gmax, mb.level.shape),
                spec=self.spec,
            )
            return bank_quantiles_ref(
                mb.pos.sum(0)[None],
                mb.neg.sum(0)[None],
                mb.zero.sum()[None],
                jnp.min(mb.vmin)[None],
                jnp.max(mb.vmax)[None],
                gmax[None],
                q,
                t,
            )[0]

        return self._compiled(
            ("window_rollup", slab.level.shape[0], nodes.size, qf.size),
            rollup_impl,
            (),
            ("slab", "bank", "scalar", "scalar", "scalar", "scalar", "scalar"),
            ("scalar",),
            slab,
            bank,
            jnp.asarray(nodes),
            jnp.asarray(valid),
            jnp.asarray(1.0 if include_live else 0.0, jnp.float32),
            jnp.asarray(qf),
            device_value_table(self.spec),
        )

    # ------------------------------------------------------------------ #
    # queries (not donated: querying must not consume the bank)
    # ------------------------------------------------------------------ #
    def quantiles(self, bank: SketchBank, qs) -> jnp.ndarray:
        """Fused per-row quantiles ``(K, len(qs))``; one executable per Q.

        The per-level value table threads in as an explicit argument (from
        the per-spec cache) so the AOT executable has no closure constants.
        """
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        from repro.engine.tables import device_value_table

        def quantiles_impl(b, q, t):
            return sbank.quantiles_impl(
                b, q, spec=self.spec, table=t
            )

        return self._compiled(
            ("quantiles", qf.size),
            quantiles_impl,
            (),
            ("bank", "scalar", "scalar"),
            ("rowsq",),
            bank,
            jnp.asarray(qf),
            device_value_table(self.spec),
        )

    def quantile(self, bank: SketchBank, q) -> jnp.ndarray:
        """One quantile for every row, shape ``(K,)``."""
        return self.quantiles(bank, [q])[:, 0]

    def rollup_quantiles(self, bank: SketchBank, qs) -> jnp.ndarray:
        """Quantiles of the union of *every* row, shape ``(len(qs),)``.

        The fleet view ("p99 across all tenants/streams"): rows collapse to
        the bank-max level (making the bucket arrays commensurate), sum
        into one bucket array — Algorithm 4 as a reduction over the row
        axis — and answer one Algorithm 2 query.  Exact for integer-weight
        counts (sums reorder).  ``ShardedEngine`` overrides this with the
        psum form; this single-device twin keeps the call path (and the
        HTTP ``/rollup`` consumer) mesh-agnostic.
        """
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        from repro.engine.tables import device_value_table

        def rollup_impl(b: SketchBank, q: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
            gmax = jnp.max(b.level)
            b = sbank.collapse_to(
                b,
                jnp.broadcast_to(gmax, b.level.shape),
                spec=self.spec,
            )
            f32 = jnp.float32
            return bank_quantiles_ref(
                b.pos.astype(f32).sum(0)[None],
                b.neg.astype(f32).sum(0)[None],
                b.zero.astype(f32).sum()[None],
                jnp.min(b.vmin)[None],
                jnp.max(b.vmax)[None],
                gmax[None],
                q,
                t,
            )[0]

        return self._compiled(
            ("rollup", qf.size),
            rollup_impl,
            (),
            ("bank", "scalar", "scalar"),
            ("scalar",),
            bank,
            jnp.asarray(qf),
            device_value_table(self.spec),
        )
