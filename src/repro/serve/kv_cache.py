"""Paged/packed KV-cache pool for batched decode (DESIGN.md §15).

The engine's per-slot caches are exact but dispatch-wasteful: each
``decode_step`` call streams every weight from HBM, so B=1 calls per
slot cost B weight streams a step.  This module gives the engine a
*paged* layout so all active slots share one call:

* one page pool per cache side (K and V), page-major:
  ``(num_pages + 1, layers, kv_heads, page_size, head_dim)`` — a page
  holds ``page_size`` consecutive cache positions of one slot across
  every layer; the last page is a spare that no slot owns;
* a per-slot page table (position-ordered page ids) plus the slot's
  valid length; pages allocate on demand as the cache grows and return
  to the free list when the slot recycles;
* ``gather`` packs any set of slots — their K/V lengths may differ —
  into one batched cache ``{"layers": {"k": (L, B, Hkv, W, hd), ...},
  "len": (B,)}`` that ``decode_step`` advances in a single call, each row
  at its own position and masked by its own length; ``scatter`` writes
  the updated buffers back through the page tables (allocating the page
  a growth step crosses into).  Rows with fewer pages than the widest
  are padded with the spare page, so padding is read from and written
  to a page no slot owns, and every position past a row's length holds
  finite data (spare-page, stale or zero) that the mask weighs by 0.

Gather→compute→scatter round-trips are value-exact (pages are plain
slices), so the batched path's numerics reduce to ``decode_step``'s own
row independence — pinned by the batched-vs-B=1 parity tests.
``shape_buckets`` (slots of equal K/V length) no longer groups the
engine's dispatches; the CIM simulator (``sim/serve_sim.py``) still
lowers per bucket.

Each copy is one jitted program per pool side with a stable name in the
device trace — ``kv_gather``, ``kv_scatter``, ``kv_write`` — taking the
page ids as an int32 argument, so one program serves every slot set of a
(batch size, page count) shape.  The host spans ``kv.gather``,
``kv.scatter`` and ``kv.write`` (``repro.obs.spans``) cover each call;
``scatter`` and ``admit`` each read the cache length(s) in one
``host_read``.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import Counter
from repro.obs.spans import host_read, span


def shape_buckets(kv_lens: Sequence[int]
                  ) -> List[Tuple[int, Tuple[int, ...]]]:
    """Group slot positions by KV length, order-preserving.

    Returns ``[(kv_len, positions), ...]`` where ``positions`` index into
    ``kv_lens``; buckets appear in order of their first member, members
    keep their relative order.  Slots in one bucket share cache shape
    *and* position counter — the unit the CIM simulator lowers a decode
    step by (``sim/serve_sim.py``); the engine dispatches all slots at
    once.
    """
    order: List[int] = []
    members: Dict[int, List[int]] = {}
    for i, kv in enumerate(kv_lens):
        kv = int(kv)
        if kv < 1:
            raise ValueError(f"kv_lens must be >= 1, got {kv_lens!r}")
        if kv not in members:
            members[kv] = []
            order.append(kv)
        members[kv].append(i)
    return [(kv, tuple(members[kv])) for kv in order]


@functools.partial(jax.jit, static_argnames="width")
def kv_gather(pool, ids, width: int):
    """Pack the pages ``ids`` (B, npg) of one pool side into a dense
    ``(L, B, Hkv, width, hd)`` cache, zero past the pages' end."""
    B, npg = ids.shape
    _, L, Hkv, pg, hd = pool.shape
    pages = jnp.take(pool, ids.reshape(-1), axis=0)
    pages = pages.reshape(B, npg, L, Hkv, pg, hd)
    dense = jnp.moveaxis(pages, 1, 3)          # (B, L, Hkv, npg, pg, hd)
    dense = dense.reshape(B, L, Hkv, npg * pg, hd)
    dense = jnp.moveaxis(dense, 0, 1)          # (L, B, Hkv, S, hd)
    S = npg * pg
    if S < width:
        dense = jnp.pad(dense, ((0, 0), (0, 0), (0, 0), (0, width - S),
                                (0, 0)))
    return dense[:, :, :, :width]


@jax.jit
def kv_scatter(pool, ids, dense):
    """Write the first ``npg`` pages' worth of each row of a dense
    ``(L, B, Hkv, W, hd)`` cache to the pages ``ids`` (B, npg) of one
    pool side; returns the new pool."""
    B, npg = ids.shape
    _, L, Hkv, pg, hd = pool.shape
    S, W = npg * pg, dense.shape[3]
    dense = jnp.moveaxis(dense, 1, 0)          # (B, L, Hkv, W, hd)
    if S > W:
        dense = jnp.pad(dense, ((0, 0), (0, 0), (0, 0), (0, S - W),
                                (0, 0)))
    pages = dense[:, :, :, :S].reshape(B, L, Hkv, npg, pg, hd)
    pages = jnp.moveaxis(pages, 3, 1)          # (B, npg, L, Hkv, pg, hd)
    return pool.at[ids.reshape(-1)].set(
        pages.reshape(B * npg, L, Hkv, pg, hd))


@jax.jit
def kv_write(pool, ids, dense):
    """Page out one B=1 cache leaf ``(L, 1, Hkv, W, hd)`` to the pages
    ``ids`` (npg,) of one pool side: ``kv_scatter`` of a one-slot bucket,
    under a name of its own so admissions and decode steps read apart."""
    return kv_scatter(pool, ids[None], dense)


@dataclasses.dataclass
class _SlotEntry:
    pages: List[int]          # position-ordered page ids
    length: int               # valid cache entries (== cache["len"])


class PagedKVCache:
    """Demand-paged K/V pool for one engine's decode slots.

    Built lazily from the first admitted cache (``from_cache``): the pool
    only supports the plain per-layer ``{"k", "v"}`` cache tree the
    unified transformer uses — families with richer state (SSM / MLA /
    hybrid) stay on the engine's per-slot fallback.  ``syncs``: the
    counter each host read of a cache length increments (the engine's
    ``host_syncs``).
    """

    def __init__(self, *, slots: int, num_layers: int, kv_heads: int,
                 width: int, head_dim: int, dtype,
                 page_size: int = 64,
                 syncs: Optional[Counter] = None) -> None:
        if slots < 1 or width < 1:
            raise ValueError(f"slots ({slots}) and width ({width}) must "
                             "be >= 1")
        self.slots = slots
        self.num_layers = num_layers
        self.kv_heads = kv_heads
        self.width = width                     # per-slot cache positions
        self.head_dim = head_dim
        self.page_size = min(int(page_size), width)
        self.pages_per_slot = -(-width // self.page_size)
        self.num_pages = slots * self.pages_per_slot
        self._spare = self.num_pages       # pads ragged rows; never free
        shape = (self.num_pages + 1, num_layers, kv_heads, self.page_size,
                 head_dim)
        self._k_pool = jnp.zeros(shape, dtype)
        self._v_pool = jnp.zeros(shape, dtype)
        self._free: deque = deque(range(self.num_pages))
        self._table: Dict[int, _SlotEntry] = {}
        self._syncs = syncs

    # ------------------------------------------------------------------
    # Construction / introspection
    # ------------------------------------------------------------------

    @staticmethod
    def supports(cache) -> bool:
        """True iff ``cache`` is the plain stacked-KV tree this pool
        pages (``{"layers": {"k", "v"}, "len"}`` with B == 1 leaves)."""
        try:
            layers = cache["layers"]
        except (TypeError, KeyError):
            return False
        if not isinstance(layers, dict) or set(layers) != {"k", "v"}:
            return False
        k = layers["k"]
        return getattr(k, "ndim", 0) == 5 and k.shape[1] == 1

    @classmethod
    def from_cache(cls, cache, *, slots: int, page_size: int = 64,
                   syncs: Optional[Counter] = None) -> "PagedKVCache":
        """Size a pool from one admitted B=1 cache's leaf shapes."""
        k = cache["layers"]["k"]               # (L, 1, Hkv, W, hd)
        L, _, Hkv, W, hd = k.shape
        return cls(slots=slots, num_layers=L, kv_heads=Hkv, width=W,
                   head_dim=hd, dtype=k.dtype, page_size=page_size,
                   syncs=syncs)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def len_of(self, slot: int) -> int:
        return self._table[slot].length

    def page_table(self, slot: int) -> Tuple[int, ...]:
        return tuple(self._table[slot].pages)

    def _occupied(self, length: int) -> int:
        """Cache positions holding live entries at ``length`` — the ring
        buffer (sliding-window W < max context) caps at the full width
        once wrapped."""
        return min(length, self.width)

    def _pages_for(self, length: int) -> int:
        return -(-self._occupied(length) // self.page_size) if length else 0

    def _alloc(self, entry: _SlotEntry, length: int) -> None:
        need = self._pages_for(length)
        while len(entry.pages) < need:
            if not self._free:
                raise RuntimeError("paged KV pool exhausted (page leak?)")
            entry.pages.append(self._free.popleft())

    # ------------------------------------------------------------------
    # Slot lifecycle
    # ------------------------------------------------------------------

    def admit(self, slot: int, cache) -> None:
        """Page in one freshly prefilled B=1 cache for ``slot``."""
        if slot in self._table:
            raise ValueError(f"slot {slot} already admitted")
        if not self.supports(cache):
            raise ValueError("cache tree is not the plain {'k','v'} "
                             "layout this pool pages")
        with span("kv.write"):
            entry = _SlotEntry(
                pages=[], length=int(host_read(cache["len"], self._syncs)))
            self._alloc(entry, entry.length)
            self._table[slot] = entry
            if entry.pages:
                ids = np.asarray(entry.pages, np.int32)
                self._k_pool = kv_write(self._k_pool, ids,
                                        cache["layers"]["k"])
                self._v_pool = kv_write(self._v_pool, ids,
                                        cache["layers"]["v"])

    def free(self, slot: int) -> None:
        """Recycle a finished slot's pages back to the pool."""
        entry = self._table.pop(slot)
        self._free.extend(entry.pages)

    # ------------------------------------------------------------------
    # Batch gather / scatter
    # ------------------------------------------------------------------

    def _page_ids(self, entries: Sequence[_SlotEntry]) -> np.ndarray:
        """(B, npg) page ids, each row padded to the widest row's page
        count with the spare page."""
        npg = max(len(e.pages) for e in entries)
        return np.asarray([e.pages + [self._spare] * (npg - len(e.pages))
                           for e in entries], np.int32)

    def gather(self, slot_ids: Sequence[int]):
        """Pack slots of any K/V lengths into one batched decode cache.

        Returns ``{"layers": {"k": (L, B, Hkv, W, hd), "v": ...},
        "len": (B,) int32}`` ready for one ``decode_step`` call; each row
        holds its slot's pages, then spare-page data and zeros.
        """
        with span("kv.gather"):
            entries = [self._table[s] for s in slot_ids]
            lens = jnp.asarray([e.length for e in entries], jnp.int32)
            if not any(e.pages for e in entries):
                k = jnp.zeros((self.num_layers, len(entries), self.kv_heads,
                               self.width, self.head_dim),
                              self._k_pool.dtype)
                return {"layers": {"k": k, "v": k}, "len": lens}
            ids = self._page_ids(entries)
            return {"layers": {"k": kv_gather(self._k_pool, ids, self.width),
                               "v": kv_gather(self._v_pool, ids, self.width)},
                    "len": lens}

    def scatter(self, slot_ids: Sequence[int], cache) -> None:
        """Write one advanced batch cache back through the page tables,
        allocating the page each slot's growth step crossed into.

        ``cache["len"]`` is read in one ``host_read``: ``(B,)``, one
        length per row, or a scalar every row shares.  One
        ``kv_scatter`` per pool side for the whole batch — a per-slot
        write-back loop would cost as many dispatches as the batching
        saved.
        """
        with span("kv.scatter"):
            new_lens = np.broadcast_to(
                host_read(cache["len"], self._syncs), (len(slot_ids),))
            entries = [self._table[s] for s in slot_ids]
            for e, n in zip(entries, new_lens.tolist()):
                if n < e.length:
                    raise ValueError("scatter would shrink a slot's cache")
                self._alloc(e, n)
                e.length = n
            if not any(e.pages for e in entries):
                return
            ids = self._page_ids(entries)
            self._k_pool = kv_scatter(self._k_pool, ids,
                                      cache["layers"]["k"])
            self._v_pool = kv_scatter(self._v_pool, ids,
                                      cache["layers"]["v"])
