"""Row-sharded sketch banks: one logical bank across a device mesh — or a
multi-host fleet.

The paper's headline property — full mergeability (Algorithm 4: merge is a
per-key sum) — means a bank row-partitioned over a ``keys`` mesh axis is
still *one* bank: every row lives wholly on one shard, per-row operations
(insert, collapse, quantiles) are shard-local, and the only collective in
the whole system is the rollup psum.  That lifts the bank's key capacity
from one device's VMEM to the mesh's — and, once
``launch.distributed.initialize`` joins a fleet, to every host's devices:
the same ``keys`` mesh spans processes and the same engine methods drive
it (the SPMD contract: every participating process makes the same engine
calls with the same shapes).

``ShardedEngine`` subclasses ``SketchEngine`` and reuses its exact call
paths (the same ``sketch_bank`` impls, the same executable cache, the same
donation) — the deltas are the ``shard_map`` wrapper built from each
executable's argument kinds, global→local id rebasing, and the **routed
batch layout**: ``route`` groups a streamed batch into ``num_shards``
equal blocks (block ``p`` = the lanes whose row lives on shard ``p``, in
original relative order, padded with inert lanes) and the blocks shard
over ``keys`` alongside the rows.  Each shard therefore ingests *only its
own lanes* — on a fleet, a host never materializes another host's batch;
ingest is shard-local and the batch is **never replicated across
processes**.  Bit-exactness vs the single-device bank holds because every
row's lanes keep their relative order and per-bucket sums of
integer-weight mass are order-exact.

Cross-host reads gather instead of replicate: per-row query outputs
(``quantiles``, the reactive-collapse masks) ride one ``all_gather`` so
every process sees the full (K, Q) answer, and ``rollup_quantiles`` stays
the one-psum fleet view.  ``host_rows`` / ``host_bank`` are the host-side
twins for the telemetry tier.

``ShardedBank`` is the stateful convenience wrapper (owns the bank pytree,
rebinding it through the donated paths) used by examples and parity tests;
``telemetry.KeyedWindow`` drives the engines directly.
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import sketch_bank as sbank
from repro.core.sketch_bank import SketchBank
from repro.engine.engine import SketchEngine, _pad_to_bucket, window_merge_bank
from repro.engine.tables import device_value_table
from repro.kernels.ref import BucketSpec, bank_quantiles_ref
from repro.launch.mesh import make_keys_mesh
from repro.sharding.rules import (
    BANK_ROW_AXIS,
    bank_pspec,
    bank_sharding,
    batch_pspec,
    slab_pspec,
    slab_sharding,
)

__all__ = ["ShardedEngine", "ShardedBank", "make_engine"]


def make_engine(
    spec: BucketSpec,
    num_sketches: int,
    *,
    num_shards: int | None = None,
    **kwargs,
) -> SketchEngine:
    """Engine factory: single-device for ``num_shards in (None, 1)``, else
    row-sharded over ``num_shards`` devices (the ``keys`` mesh axis)."""
    if num_shards is None or int(num_shards) == 1:
        return SketchEngine(spec, num_sketches, **kwargs)
    return ShardedEngine(spec, num_sketches, num_shards=num_shards, **kwargs)


class ShardedEngine(SketchEngine):
    """``SketchEngine`` whose bank rows partition over the ``keys`` axis.

    ``num_sketches`` is the *logical* row count; internally rows pad up to a
    multiple of the shard count (``num_rows``) so every shard owns an equal
    block of ``rows_per_shard`` rows.  Row ``r`` lives on shard
    ``r // rows_per_shard`` at local row ``r % rows_per_shard`` — the
    host-side key→(shard, row) routing is that one divmod
    (``shard_of`` / ``local_row``); ``process_of`` extends it to the owning
    process when the mesh spans hosts.
    """

    def __init__(
        self,
        spec: BucketSpec,
        num_sketches: int,
        *,
        num_shards: int | None = None,
        mesh=None,
        **kwargs,
    ):
        self.mesh = make_keys_mesh(num_shards) if mesh is None else mesh
        self.num_shards = self.mesh.shape[BANK_ROW_AXIS]
        self._shard_devices = list(self.mesh.devices.flat)
        self.spans_processes = any(
            d.process_index != jax.process_index() for d in self._shard_devices
        )
        logical = int(num_sketches)
        rows = -(-logical // self.num_shards) * self.num_shards
        super().__init__(spec, rows, **kwargs)
        self.num_logical = logical
        self.rows_per_shard = rows // self.num_shards

    # host-side key→(shard, local row, process) routing ----------------- #
    def shard_of(self, row: int) -> int:
        return int(row) // self.rows_per_shard

    def local_row(self, row: int) -> int:
        return int(row) % self.rows_per_shard

    def process_of(self, row: int) -> int:
        """Process index owning ``row``'s shard (0 on a one-host mesh)."""
        return self._shard_devices[self.shard_of(row)].process_index

    def is_local_row(self, row: int) -> bool:
        """True iff ``row``'s shard is addressable from this process."""
        return self.process_of(row) == jax.process_index()

    def local_shards(self) -> list[int]:
        """Shards whose device this process owns (all, on one host)."""
        me = jax.process_index()
        return [
            i for i, d in enumerate(self._shard_devices) if d.process_index == me
        ]

    # batch routing ------------------------------------------------------ #
    def route(self, values, ids, weights=None, *, block: int | None = None):
        """Group a batch by owning shard into the ``keys``-sharded layout.

        Returns ``(values, ids, weights, block)`` where each array has
        shape ``(num_shards * block,)``: slot ``[p*block : (p+1)*block]``
        holds — in original relative order — exactly the lanes whose
        global row id lives on shard ``p``, padded with inert lanes
        (NaN / id -1 / weight 0).  Ids stay *global*; the in-shard rebase
        keeps out-of-range ids inert, so lanes with invalid ids (parked on
        shard 0 here) contribute nothing, same as the unsharded path.

        ``block=None`` sizes the blocks from this batch (power-of-two of
        the largest group).  On a fleet where each process routes only its
        *local* lanes, pass an agreed explicit ``block`` — block size is
        executable geometry, and every process must compile the same
        program (the SPMD contract).
        """
        v = np.asarray(values, np.float32).reshape(-1)
        s = np.asarray(ids, np.int64).reshape(-1)
        w = None if weights is None else np.asarray(weights, np.float32).reshape(-1)
        shard = np.clip(s // self.rows_per_shard, 0, self.num_shards - 1)
        shard[(s < 0) | (s >= self.num_sketches)] = 0
        sizes = np.bincount(shard, minlength=self.num_shards)
        need = int(sizes.max()) if sizes.size else 0
        blk = _pad_to_bucket(max(need, 1))
        if block is not None:
            if need > int(block):
                raise ValueError(
                    f"block={block} < largest shard group ({need} lanes)"
                )
            blk = int(block)
        order = np.argsort(shard, kind="stable")
        grouped = shard[order]
        starts = np.concatenate(([0], np.cumsum(sizes)))[:-1]
        dst = grouped * blk + (np.arange(s.size) - starts[grouped])
        v_out = np.full(self.num_shards * blk, np.nan, np.float32)
        s_out = np.full(self.num_shards * blk, -1, np.int32)
        v_out[dst] = v[order]
        s_out[dst] = s[order].astype(np.int32)
        w_out = None
        if w is not None:
            w_out = np.zeros(self.num_shards * blk, np.float32)
            w_out[dst] = w[order]
        return v_out, s_out, w_out, blk

    def _put_global(self, a: np.ndarray, sh: NamedSharding):
        """Host array -> globally-sharded device array, local blocks only.

        ``make_array_from_callback`` materializes exactly the addressable
        shards — a process never uploads (or cross-checks) the blocks it
        doesn't own, which is the no-replication story of the fleet tier.
        (A plain ``device_put`` of numpy onto a process-spanning sharding
        would also run a cross-process equality collective per call — and
        trip on the NaN fill lanes, since NaN != NaN.)
        """
        if not self.spans_processes:
            return jax.device_put(a, sh)
        return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])

    def _prep_batch(self, v, s, w, *, block: int | None = None):
        """Routed, ``keys``-sharded batch placement (overrides the base pad).

        Lanes routed to a remote shard's slot are simply never uploaded —
        each process materializes its own blocks only.
        """
        v, s, w, blk = self.route(v, s, w, block=block)
        sh = NamedSharding(self.mesh, batch_pspec())
        return (
            self._put_global(v, sh),
            self._put_global(s, sh),
            None if w is None else self._put_global(w, sh),
            blk,
        )

    # placement hooks --------------------------------------------------- #
    def _place(self, bank: SketchBank) -> SketchBank:
        sh = bank_sharding(self.mesh)
        if self.spans_processes:
            # leaves were built process-locally; each process uploads the
            # row blocks it owns from its host copy
            return jax.tree.map(
                lambda x: self._put_global(np.asarray(x), sh), bank
            )
        return jax.device_put(bank, sh)

    def _rows(self, arr) -> jnp.ndarray:
        a = np.asarray(arr)
        if a.shape[0] < self.num_sketches:  # pad logical -> physical rows
            a = np.concatenate([a, np.zeros(self.num_sketches - a.shape[0], a.dtype)])
        return self._put_global(a, NamedSharding(self.mesh, bank_pspec()))

    def _place_slab(self, slab: SketchBank) -> SketchBank:
        sh = slab_sharding(self.mesh)
        if self.spans_processes:
            return jax.tree.map(
                lambda x: self._put_global(np.asarray(x), sh), slab
            )
        return jax.device_put(slab, sh)

    def _wrap(
        self,
        fn: Callable,
        donate: tuple[int, ...],
        in_kinds: Sequence[str],
        out_kinds: Sequence[str],
    ) -> Callable:
        """shard_map the impl over ``keys``, rebasing global ids per shard.

        On a process-spanning mesh, per-row outputs (``rows`` / ``rowsq``:
        quantile tables, reactive-collapse masks) additionally ride one
        tiled ``all_gather`` so every process holds the full answer —
        that is the ``all_quantiles`` gather story: per-row *results*
        (K × Q floats) cross hosts, the ingest batch never does.
        """
        kind_spec = {
            "bank": bank_pspec(),
            "slab": slab_pspec(),
            "rows": bank_pspec(),
            "batch": batch_pspec(),
            "ids": batch_pspec(),
            "scalar": P(),
        }
        gather = self.spans_processes

        def out_spec(kind: str) -> P:
            if gather and kind in ("rows", "rowsq"):
                return P()  # gathered below: replicated on every process
            if kind == "slab":
                return slab_pspec()
            return bank_pspec()

        rows_local = self.rows_per_shard

        def localized(*args):
            args = list(args)
            off = jax.lax.axis_index(BANK_ROW_AXIS) * rows_local
            for i, kind in enumerate(in_kinds):
                if kind == "ids" and args[i] is not None:
                    # global ids -> shard-local; lanes owned elsewhere fall
                    # outside [0, rows_local) and contribute nothing (the
                    # standard invalid-id contract of the kernels)
                    args[i] = args[i] - off
            out = fn(*args)
            if not gather:
                return out
            single = len(out_kinds) == 1
            outs = (out,) if single else tuple(out)
            outs = tuple(
                jax.lax.all_gather(o, BANK_ROW_AXIS, axis=0, tiled=True)
                if kind in ("rows", "rowsq")
                else o
                for kind, o in zip(out_kinds, outs)
            )
            return outs[0] if single else outs

        sm = shard_map(
            localized,
            mesh=self.mesh,
            in_specs=tuple(kind_spec[k] for k in in_kinds),
            out_specs=(
                out_spec(out_kinds[0])
                if len(out_kinds) == 1
                else tuple(out_spec(k) for k in out_kinds)
            ),
        )
        return jax.jit(sm, donate_argnums=donate)

    # ------------------------------------------------------------------ #
    # host-side reads (cross-process gathers on a fleet)
    # ------------------------------------------------------------------ #
    def _gathered(self, tree):
        """One compiled all_gather per (structure, shape) → host np pytree."""
        leaves, treedef = jax.tree.flatten(tree)
        key = ("host_gather", tuple((leaf.shape, str(leaf.dtype)) for leaf in leaves))

        def gather_impl(*ls):
            return tuple(
                jax.lax.all_gather(leaf, BANK_ROW_AXIS, axis=0, tiled=True)
                for leaf in ls
            )

        sm = shard_map(
            gather_impl,
            mesh=self.mesh,
            in_specs=(bank_pspec(),) * len(leaves),
            out_specs=(P(),) * len(leaves),
        )
        exe = self._cache.get(key)
        if exe is None:
            self._misses += 1
            exe = jax.jit(sm).lower(*leaves).compile()
            self._cache[key] = exe
        else:
            self._hits += 1
        return jax.tree.unflatten(treedef, [np.asarray(o) for o in exe(*leaves)])

    def host_rows(self, arr) -> np.ndarray:
        if not self.spans_processes:
            return np.asarray(arr)
        return self._gathered((arr,))[0]

    def host_bank(self, bank: SketchBank) -> SketchBank:
        if not self.spans_processes:
            return jax.tree.map(np.asarray, bank)
        return self._gathered(bank)

    # ------------------------------------------------------------------ #
    # cross-shard rollup: all rows -> one distribution (psum + Algorithm 2)
    # ------------------------------------------------------------------ #
    def rollup_quantiles(self, bank: SketchBank, qs) -> jnp.ndarray:
        """Quantiles of the union of *every* row, shape ``(len(qs),)``.

        The fleet view ("p99 across all tenants"): shard-locally every row
        collapses to the global max level (pmax) and sums into one bucket
        array, then a single psum per store merges the shards — Algorithm 4
        as one collective.  Exact for integer-weight counts (sums reorder).
        On a multi-host mesh this is the *only* cross-host data path of the
        whole ingest→query pipeline, O(m) floats per store per host.
        """
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        spec = self.spec

        def rollup_impl(b: SketchBank, q: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
            gmax = jax.lax.pmax(jnp.max(b.level), BANK_ROW_AXIS)
            b = sbank.collapse_to(
                b,
                jnp.broadcast_to(gmax, b.level.shape),
                spec=spec,
            )
            f32 = jnp.float32
            pos = jax.lax.psum(b.pos.astype(f32).sum(0), BANK_ROW_AXIS)
            neg = jax.lax.psum(b.neg.astype(f32).sum(0), BANK_ROW_AXIS)
            zero = jax.lax.psum(b.zero.astype(f32).sum(), BANK_ROW_AXIS)
            vmin = jax.lax.pmin(jnp.min(b.vmin), BANK_ROW_AXIS)
            vmax = jax.lax.pmax(jnp.max(b.vmax), BANK_ROW_AXIS)
            return bank_quantiles_ref(
                pos[None],
                neg[None],
                zero[None],
                vmin[None],
                vmax[None],
                gmax[None],
                q,
                t,
            )[0]

        sm = shard_map(
            rollup_impl,
            mesh=self.mesh,
            in_specs=(bank_pspec(), P(), P()),
            out_specs=P(),
        )
        table = device_value_table(spec)
        key = ("rollup", qf.size)
        exe = self._cache.get(key)
        if exe is None:
            self._misses += 1
            exe = jax.jit(sm).lower(bank, jnp.asarray(qf), table).compile()
            self._cache[key] = exe
        else:
            self._hits += 1
        return exe(bank, jnp.asarray(qf), table)

    def window_rollup(
        self, slab: SketchBank, bank: SketchBank, nodes, valid, include_live, qs
    ) -> jnp.ndarray:
        """Windowed fleet rollup: fused range merge shard-locally, then the
        same pmax + collapse + psum reduction as ``rollup_quantiles`` —
        the window changes nothing about the collective story (still one
        psum per store)."""
        qf = np.atleast_1d(np.asarray(qs, np.float32))
        nodes = np.asarray(nodes, np.int32).reshape(-1)
        valid = np.asarray(valid, np.float32).reshape(-1)
        spec = self.spec

        def rollup_impl(sl, b, nd, vm, lv, q, t):
            mb = window_merge_bank(
                sl, b, nd, vm, lv, spec=spec
            )
            gmax = jax.lax.pmax(jnp.max(mb.level), BANK_ROW_AXIS)
            mb = sbank.collapse_to(
                mb,
                jnp.broadcast_to(gmax, mb.level.shape),
                spec=spec,
            )
            pos = jax.lax.psum(mb.pos.sum(0), BANK_ROW_AXIS)
            neg = jax.lax.psum(mb.neg.sum(0), BANK_ROW_AXIS)
            zero = jax.lax.psum(mb.zero.sum(), BANK_ROW_AXIS)
            vmin = jax.lax.pmin(jnp.min(mb.vmin), BANK_ROW_AXIS)
            vmax = jax.lax.pmax(jnp.max(mb.vmax), BANK_ROW_AXIS)
            return bank_quantiles_ref(
                pos[None],
                neg[None],
                zero[None],
                vmin[None],
                vmax[None],
                gmax[None],
                q,
                t,
            )[0]

        sm = shard_map(
            rollup_impl,
            mesh=self.mesh,
            in_specs=(slab_pspec(), bank_pspec(), P(), P(), P(), P(), P()),
            out_specs=P(),
        )
        table = device_value_table(spec)
        args = (
            slab,
            bank,
            jnp.asarray(nodes),
            jnp.asarray(valid),
            jnp.asarray(1.0 if include_live else 0.0, jnp.float32),
            jnp.asarray(qf),
            table,
        )
        key = ("window_rollup", slab.level.shape[0], nodes.size, qf.size)
        exe = self._cache.get(key)
        if exe is None:
            self._misses += 1
            exe = jax.jit(sm).lower(*args).compile()
            self._cache[key] = exe
        else:
            self._hits += 1
        return exe(*args)


class ShardedBank:
    """Stateful row-sharded bank: a ``ShardedEngine`` plus its live state.

    The drop-in counterpart of a single-device ``SketchBank`` for callers
    that want object-style usage (examples, parity tests); every mutating
    call rebinds the donated state, so the bank genuinely updates in place
    shard by shard.
    """

    def __init__(
        self,
        spec: BucketSpec,
        num_sketches: int,
        *,
        num_shards: int | None = None,
        counts_dtype=jnp.float32,
        method: str | None = None,
    ):
        self.engine = ShardedEngine(
            spec,
            num_sketches,
            num_shards=num_shards,
            counts_dtype=counts_dtype,
            method=method,
        )
        self.state = self.engine.new_bank()

    @property
    def spec(self) -> BucketSpec:
        return self.engine.spec

    @property
    def num_sketches(self) -> int:
        return self.engine.num_logical

    @property
    def num_shards(self) -> int:
        return self.engine.num_shards

    def add(self, values, sketch_ids, weights=None, *, auto_collapse=False,
            block=None) -> None:
        self.state = self.engine.add(
            self.state, values, sketch_ids, weights, auto_collapse=auto_collapse,
            block=block,
        )

    def auto_collapse(self, threshold: float = 0.0) -> None:
        self.state = self.engine.auto_collapse(self.state, threshold)

    def collapse_to(self, target) -> None:
        self.state = self.engine.collapse_to(self.state, target)

    def reset(self, levels=None) -> None:
        self.state = self.engine.reset(self.state, levels)

    def quantiles(self, qs) -> np.ndarray:
        """Per-row quantiles ``(num_sketches, len(qs))`` (logical rows)."""
        out = self.engine.quantiles(self.state, qs)
        return np.asarray(out)[: self.num_sketches]

    def rollup_quantiles(self, qs) -> np.ndarray:
        """Quantiles of all rows merged (the fleet view), ``(len(qs),)``."""
        return np.asarray(self.engine.rollup_quantiles(self.state, qs))

    @property
    def levels(self) -> np.ndarray:
        return self.engine.host_rows(self.state.level)[: self.num_sketches]

    @property
    def counts(self) -> np.ndarray:
        return self.engine.host_rows(self.state.counts)[: self.num_sketches]
