"""WindowRing: S sealed time slices as one device-resident slab, with a
power-of-two merge-tree cache so any slice range costs O(log S) node reads.

The paper's full-mergeability property (Algorithm 4: merge is a per-bucket
'+') makes sliding-window quantiles natural: keep one bank per time slice
and merge the slices a query covers.  Done naively that is O(W) per query
— W-1 host-looped ``engine.merge`` dispatches.  This tier makes it
O(log S) cached node reads feeding ONE fused device dispatch:

* **Slab** — all ring state lives in one stacked pytree of shape
  ``(2S-1, K, ...)`` per leaf, minted by ``SketchEngine.new_slab``.  Nodes
  ``0..S-1`` are the slice leaves (slot = absolute slice index mod S);
  nodes ``S..2S-2`` hold the merge tree: level-j node slots store
  pre-merged blocks of ``2**j`` consecutive slices.  All mutation is
  donated (``seal_slice`` / ``merge_node``), so the ring's footprint is
  exactly one slab — no per-slice allocations, ever, and no copies for
  readers: a read taken at an earlier seal count runs against the live
  slab for as long as ``cover_intact`` holds (below).

* **Incremental cascade** — sealing absolute slice ``a`` writes leaf
  ``a mod S`` and then, for each level ``j`` with ``(a+1) % 2**j == 0``,
  rebuilds one level-j node from its two level-(j-1) children (built
  earlier in the same cascade, bottom-up) — amortized ~1 extra merge per
  seal, ~2 worst case per level.

* **Freshness by construction** — a level-j slot holds the *latest
  completed* block congruent to it mod ``S/2**j``.  For any canonical
  aligned block of a range inside the retention window ``[t-S, t)`` that
  latest completed block IS the block the decomposition wants, so cached
  lookups never serve stale nodes; ``_built`` bookkeeping asserts it.

* **O(log S) range cover** — ``range_nodes`` greedily takes the largest
  aligned block starting at the range's left edge (the standard segment
  tree decomposition), giving at most ``2*log2(S)`` nodes for any range;
  ``query_args`` pads the cover to the fixed ``max_range_nodes`` length so
  every window size reuses ONE compiled executable per ring.

The ring itself is host-side bookkeeping (a few ints); all data stays on
device.  The live (un-sealed) head slice is the caller's bank — queries
append it as one more masked slice, and ``seal`` hands the bank back to be
recycled through the engine's donated ``reset`` (levels surviving), which
is the donated-slice-recycling leg of the tentpole.
"""

from __future__ import annotations

import numpy as np

from repro.core.sketch_bank import SketchBank
from repro.engine.engine import SketchEngine

__all__ = ["StaleCover", "WindowRing"]


class StaleCover(RuntimeError):
    """A read's node cover was overwritten by seals since its seal count:
    re-take the read at a newer count."""


class WindowRing:
    """Segment-tree ring of ``num_slices`` sealed slices over one engine.

    ``num_slices`` must be a power of two >= 2 (the aligned-block
    decomposition and slot recycling both lean on it).  One ring serves
    one bank geometry; the engine may be single-device or row-sharded
    (the slab shards over the same ``keys`` axis as the bank).
    """

    def __init__(self, engine: SketchEngine, num_slices: int):
        s = int(num_slices)
        if s < 2 or s & (s - 1):
            raise ValueError(
                f"num_slices must be a power of two >= 2, got {num_slices}"
            )
        self.engine = engine
        self.num_slices = s
        self.tree_levels = s.bit_length() - 1  # log2(S)
        # node layout: level j occupies [base[j], base[j] + S >> j)
        self._base = [0]
        for j in range(self.tree_levels):
            self._base.append(self._base[-1] + (s >> j))
        self.num_nodes = self._base[-1] + 1  # 2S - 1
        self.slab: SketchBank = engine.new_slab(self.num_nodes)
        self.sealed = 0  # absolute count of sealed slices (t)
        self.node_merges = 0  # cumulative merge-tree maintenance merges
        # absolute block id currently resident per node slot (-1 = never)
        self._built = np.full(self.num_nodes, -1, np.int64)

    # ------------------------------------------------------------------ #
    # node indexing
    # ------------------------------------------------------------------ #
    def node_index(self, level: int, block: int) -> int:
        """Slab node holding level-``level`` block ``block`` (absolute)."""
        return self._base[level] + block % (self.num_slices >> level)

    @property
    def max_range_nodes(self) -> int:
        """Fixed padded length of every range cover: ``2 * log2(S)``."""
        return max(1, 2 * self.tree_levels)

    # ------------------------------------------------------------------ #
    # sealing + cascade
    # ------------------------------------------------------------------ #
    def seal(self, bank: SketchBank) -> int:
        """Seal ``bank`` as absolute slice ``self.sealed``; returns the
        number of merge-tree node rebuilds this seal triggered.

        The bank is copied into the leaf slot (the slab is donated and
        updated in place); the caller still owns the bank and recycles it
        via ``engine.reset`` — levels survive, so per-key collapse state
        persists across slice turnover.
        """
        t = self.sealed
        leaf = t % self.num_slices
        self.slab = self.engine.seal_slice(self.slab, bank, leaf)
        self._built[leaf] = t
        self.sealed = t + 1
        merges = 0
        for j in range(1, self.tree_levels + 1):
            if self.sealed % (1 << j):
                break
            block = self.sealed // (1 << j) - 1
            left = self.node_index(j - 1, 2 * block)
            right = self.node_index(j - 1, 2 * block + 1)
            # children completed earlier in this bottom-up cascade
            assert self._built[left] == 2 * block, (j, block, self._built[left])
            assert self._built[right] == 2 * block + 1
            dst = self.node_index(j, block)
            self.slab = self.engine.merge_node(self.slab, dst, left, right)
            self._built[dst] = block
            merges += 1
        self.node_merges += merges
        return merges

    # ------------------------------------------------------------------ #
    # range decomposition
    # ------------------------------------------------------------------ #
    def range_nodes_at(self, sealed: int, lo: int, hi: int) -> list[int]:
        """Canonical aligned-block node cover of ``[lo, hi)`` *as of* a
        past ``sealed`` count — pure slot arithmetic, no live bookkeeping.

        The snapshot read path: the slab as it stood when ``self.sealed``
        was ``sealed`` holds exactly the blocks this decomposition names
        (the freshness-by-construction invariant), and the live slab still
        does while ``cover_intact(sealed, w)`` holds.
        """
        if not (max(0, sealed - self.num_slices) <= lo <= hi <= sealed):
            raise ValueError(
                f"range [{lo}, {hi}) outside the retained window "
                f"[{max(0, sealed - self.num_slices)}, {sealed}]"
            )
        out: list[int] = []
        while lo < hi:
            j = 0
            while (
                j < self.tree_levels
                and lo % (1 << (j + 1)) == 0
                and lo + (1 << (j + 1)) <= hi
            ):
                j += 1
            out.append(self.node_index(j, lo >> j))
            lo += 1 << j
        return out

    def range_nodes(self, lo: int, hi: int) -> list[int]:
        """Canonical aligned-block node cover of absolute range ``[lo, hi)``.

        Requires ``max(0, sealed - S) <= lo <= hi <= sealed`` (the
        retention window); at most ``2 * log2(S)`` nodes.
        """
        if not (max(0, self.sealed - self.num_slices) <= lo <= hi <= self.sealed):
            raise ValueError(
                f"range [{lo}, {hi}) outside the retained window "
                f"[{max(0, self.sealed - self.num_slices)}, {self.sealed}]"
            )
        out: list[int] = []
        while lo < hi:
            j = 0
            while (
                j < self.tree_levels
                and lo % (1 << (j + 1)) == 0
                and lo + (1 << (j + 1)) <= hi
            ):
                j += 1
            node = self.node_index(j, lo >> j)
            # freshness by construction: the slot's latest completed block
            # is exactly this one for any in-window aligned block
            assert self._built[node] == lo >> j, (j, lo, self._built[node])
            out.append(node)
            lo += 1 << j
        return out

    def query_args_at(
        self, sealed: int, window_slices: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """``query_args`` evaluated at a captured ``sealed`` count.

        Pure math over the ring's static layout — safe to call without
        holding the writer lock, against ring state that has since moved
        on.  The live slab answers it while ``cover_intact`` holds.
        """
        w = int(window_slices)
        if w < 1:
            raise ValueError(f"window must cover at least 1 slice, got {w}")
        if w > self.num_slices:
            raise ValueError(
                f"window of {w} slices exceeds the ring "
                f"({self.num_slices} slices retained)"
            )
        span = min(w - 1, sealed)  # can't read more than is sealed
        cover = self.range_nodes_at(sealed, sealed - span, sealed)
        dmax = self.max_range_nodes
        nodes = np.zeros(dmax, np.int32)
        valid = np.zeros(dmax, np.float32)
        nodes[: len(cover)] = cover
        valid[: len(cover)] = 1.0
        return nodes, valid

    def cover_intact(self, sealed: int, window_slices: int) -> bool:
        """Whether the live slab still holds every node of the cover
        ``query_args_at(sealed, window_slices)`` names.

        That cover reads sealed slices ``[sealed - (w - 1), sealed)``.  The
        seal at count ``s`` writes leaf ``s mod S``, which held slice
        ``s - S``, and for each level ``j`` with ``(s + 1) % 2**j == 0`` the
        slot that held block ``[s + 1 - 2**j - S, s + 1 - S)``.  So the seals
        ``sealed, ..., sealed + d - 1`` leave the cover untouched whenever
        ``d <= S - w + 1`` (exactly then, where the cover's oldest slice is
        read as a leaf; a level-``j`` block there lasts ``2**j - 1`` seals
        longer).  ``w <= S`` makes one seal always safe.
        """
        return self.sealed - int(sealed) <= self.num_slices - int(window_slices) + 1

    def query_args(self, window_slices: int) -> tuple[np.ndarray, np.ndarray]:
        """Padded ``(nodes, valid)`` arrays covering the last
        ``window_slices - 1`` sealed slices (the window's remaining slice
        is the live bank, appended by the engine).

        Fixed length ``max_range_nodes`` regardless of the window, so one
        compiled ``window_query`` executable serves every window size.
        """
        return self.query_args_at(self.sealed, window_slices)

    # ------------------------------------------------------------------ #
    # queries
    # ------------------------------------------------------------------ #
    def quantiles(
        self, bank: SketchBank, qs, *, window_slices: int, include_live: bool = True
    ):
        """Per-row quantiles over the last ``window_slices`` slices
        (live bank included), shape ``(K, len(qs))`` — one fused dispatch."""
        nodes, valid = self.query_args(window_slices)
        return self.engine.window_query(
            self.slab, bank, nodes, valid, include_live, qs
        )

    def rollup(
        self, bank: SketchBank, qs, *, window_slices: int, include_live: bool = True
    ):
        """All-rows quantiles over the last ``window_slices`` slices,
        shape ``(len(qs),)``."""
        nodes, valid = self.query_args(window_slices)
        return self.engine.window_rollup(
            self.slab, bank, nodes, valid, include_live, qs
        )

    # ------------------------------------------------------------------ #
    # observability
    # ------------------------------------------------------------------ #
    def stats(self) -> dict:
        """Ring occupancy / maintenance metadata (the /stats payload)."""
        return {
            "num_slices": self.num_slices,
            "sealed": self.sealed,
            "slot": self.sealed % self.num_slices,
            "occupancy": min(self.sealed, self.num_slices),
            "node_merges": self.node_merges,
            "max_range_nodes": self.max_range_nodes,
        }
