"""Paged (block) KV cache: fixed-size pages as the unified protection unit.

vLLM-style memory layout for the serving engines: every float KV-cache
leaf gets a page pool ``[repeats, n_pages, page_size, *tail]`` and all
leaves share ONE host-side page table ``[slots, pages_per_slot]`` mapping
each slot's logical pages onto physical pages.  Physical page 0 is the
reserved immutable **zero page** — unallocated table entries point at it,
so a gathered dense cache is exactly zero beyond every slot's write head
(which the causal mask discards; see the decode-parity note below).

The page is also the repo's ABFT unit for serving memory, replacing the
per-slot fingerprints from PR 6 whose scrub unit was the whole slot:

  * **checksum-on-write, page granular** — every mutation re-arms exactly
    the pages it touched: a per-(leaf, page) float64 scalar fingerprint
    (detect + locate) and a per-leaf float64 *elementwise* page sum
    ``esum[r, o, *tail] = sum_p pool[r, p, o, *tail]`` (the erasure row
    that repairs).  The engine's decode writes ONE token per slot per
    step, so the incremental update is always ``+= new`` — the write-once
    invariant (cells are zero until their first and only write between
    free/zero cycles) makes arming O(page) instead of O(cache).
  * **verify-on-read** — `verify()` recomputes page fingerprints and
    returns the tripped (leaf, page) pairs; NaN-poisoned pages (a bit-30
    flip near 1.0) compare as tripped, not silently equal.
  * **erasure repair** — `repair()` rebuilds a page as
    ``esum - sum(other live pages)`` in float64 (single-page fault model,
    the f=1 erasure code of the diskless family applied to serving DRAM).
  * **prefix caching** — full pages of a shared system prompt register in
    an LRU map keyed by the token prefix; a later request mapping the
    same prefix shares the physical pages (refcounted, copy-on-write on
    any attempted write into a shared page).

Freed pages are zeroed on the device and their contribution removed from
the checksums, so allocation is free (a fresh page is already zero and
already consistent) and the pool's free list + live refcounts conserve
the pool exactly — `tests/test_paged_kv.py` drives random
admit/decode/evict/free traces against these invariants.

Decode parity: the dense cache `gather()` materializes differs from the
contiguous engine's only at causally-masked positions (zeros here, prefill
pad garbage there); `_sdpa_dense` masks with ``where(mask, s, NEG_INF)``
before the softmax, so those positions carry exactly zero weight either
way and the paged engine's decode logits are bit-identical
(tests/test_traffic.py golden-parity, clean and drilled).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.chaos.faults import register_surface

__all__ = ["PagedKVCache", "PagedStats"]

register_surface(
    "serve.paged_kv/pages", owner=__name__, protected=True,
    promise="tolerance",
    detector="per-(leaf, page) float64 fingerprints verified on the scrub "
             "cadence; a tripped page is rebuilt from the elementwise "
             "float64 page sum (erasure solve over the live pages)",
    kinds=("dram_kv_cache",),
    note="the page is the unified scrub + DRAM-recovery + erasure-repair "
         "unit for serving memory (PagedServeEngine); single-page fault "
         "model per leaf, like f=1 diskless.  Checksums re-arm at page "
         "granularity on every write — a single-token decode write "
         "dirties exactly one page checksum per leaf")


@dataclasses.dataclass
class PagedStats:
    """Counters for the allocator + checksum machinery (test hooks)."""
    allocs: int = 0
    frees: int = 0
    cow_copies: int = 0
    prefix_hits: int = 0
    prefix_misses: int = 0
    prefix_insertions: int = 0
    prefix_evictions: int = 0
    checksum_rearms: int = 0      # one per (leaf, page) checksum update
    verifies: int = 0
    repairs: int = 0


class PagedKVCache:
    """Engine-agnostic paged pool; see module docstring.

    ``leaf_shapes`` maps a leaf key (the engine uses jax keystr paths) to
    ``(dense_shape, dtype)`` where dense_shape is the contiguous layout
    ``[repeats, slots, max_len, *tail]`` the leaf would occupy.
    """

    def __init__(self, leaf_shapes: Dict[str, Tuple[Sequence[int], object]],
                 *, slots: int, max_len: int, page_size: int,
                 extra_pages: int = 0, max_prefixes: int = 16):
        if max_len % page_size:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"page_size={page_size}")
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        # +1: physical page 0 is the reserved zero page
        self.n_pages = 1 + slots * self.pages_per_slot + extra_pages
        self.max_prefixes = max_prefixes
        self.stats = PagedStats()

        self.pools: Dict[str, jax.Array] = {}
        self._tails: Dict[str, Tuple[int, ...]] = {}
        for key, (shape, dtype) in leaf_shapes.items():
            shape = tuple(shape)
            if len(shape) < 3 or shape[1] != slots or shape[2] != max_len:
                raise ValueError(
                    f"leaf {key!r}: expected [repeats, {slots}, {max_len}, "
                    f"*tail], got {shape}")
            repeats, tail = shape[0], shape[3:]
            self.pools[key] = jnp.zeros(
                (repeats, self.n_pages, page_size) + tail, dtype)
            self._tails[key] = tail

        # ONE table shared by every leaf: logical -> physical page ids
        self.table = np.zeros((slots, self.pages_per_slot), np.int32)
        self.refcount = np.zeros((self.n_pages,), np.int32)
        self.free: List[int] = list(range(self.n_pages - 1, 0, -1))
        # prefix registry: token-tuple -> list of physical pages (LRU);
        # the registry holds its own reference on each page
        self.prefixes: "OrderedDict[tuple, List[int]]" = OrderedDict()

        # armed checksums: fp (per-page float64 scalar, host) + esum
        # (per-leaf elementwise float64 page sum, host)
        self.page_fp: Dict[str, np.ndarray] = {
            key: np.zeros((self.n_pages,), np.float64) for key in self.pools}
        self.esum: Dict[str, np.ndarray] = {
            key: np.zeros((p.shape[0], page_size) + self._tails[key],
                          np.float64) for key, p in self.pools.items()}
        self.last_rearmed: List[Tuple[str, int]] = []

    # -- bookkeeping helpers ---------------------------------------------------
    def page_of(self, slot: int, pos: int) -> int:
        return int(self.table[slot, pos // self.page_size])

    def live_pages(self) -> List[int]:
        return [p for p in range(1, self.n_pages) if self.refcount[p] > 0]

    def n_free(self) -> int:
        return len(self.free)

    def _alloc(self) -> int:
        """Pop a (zeroed, checksum-consistent) free page; when the free
        list is dry, evict unshared prefix-registry entries LRU-first —
        registry references are always droppable, so a pool sized
        ``slots * pages_per_slot`` can always serve every slot."""
        while not self.free and self.prefixes:
            key, pages = self.prefixes.popitem(last=False)
            self.stats.prefix_evictions += 1
            for p in pages:
                self._deref(p)
        if not self.free:
            raise RuntimeError("page pool exhausted (no free or evictable "
                               "pages) — admission control must defer")
        phys = self.free.pop()
        self.refcount[phys] = 1
        self.stats.allocs += 1
        return phys

    def _deref(self, phys: int):
        if phys == 0:
            return  # the zero page is immortal
        self.refcount[phys] -= 1
        if self.refcount[phys] > 0:
            return
        # zero-at-free keeps "free page == zero page contents == zero
        # checksum contribution": allocation needs no work and a corrupted
        # free page is detectable (its fingerprint must stay 0)
        for key, pool in self.pools.items():
            page64 = np.asarray(pool[:, phys], np.float64)
            if np.any(page64):
                self.esum[key] -= page64
                self.pools[key] = pool.at[:, phys].set(0)
            self.page_fp[key][phys] = 0.0
        self.free.append(phys)
        self.stats.frees += 1

    # -- slot lifecycle --------------------------------------------------------
    def lookup_prefix(self, prompt: Sequence[int]) -> List[int]:
        """Physical pages of the longest registered full-page prefix of
        ``prompt`` that leaves at least one suffix token to prefill ([] on
        a miss); counts the hit or miss and refreshes the entry's LRU
        place."""
        plen = len(prompt)
        for k in range((plen - 1) // self.page_size, 0, -1):
            key = tuple(prompt[:k * self.page_size])
            pages = self.prefixes.get(key)
            if pages is not None:
                self.prefixes.move_to_end(key)
                self.stats.prefix_hits += 1
                obs.counter("repro_prefix_hits_total",
                            "prefix-cache page-share hits").inc()
                return pages
        self.stats.prefix_misses += 1
        obs.counter("repro_prefix_misses_total",
                    "prefix-cache lookup misses").inc()
        return []

    def alloc_slot(self, slot: int, need_len: int,
                   prompt: Optional[Sequence[int]] = None,
                   shared: Optional[List[int]] = None) -> int:
        """Map slot ``slot`` for a sequence of up to ``need_len`` tokens:
        shared prefix pages first (``shared``, the pages `lookup_prefix`
        gave the caller, else looked up here when ``prompt`` is given),
        fresh pages for the rest.  Returns the shared prefix length in
        tokens (0 on miss) — the caller prefills ``[shared, plen)`` only."""
        if np.any(self.table[slot]):
            raise RuntimeError(f"slot {slot} still holds pages — free it "
                               "before re-admitting")
        if shared is None:
            shared = self.lookup_prefix(prompt) if prompt is not None else []
        need_len = min(need_len, self.max_len)
        n_logical = -(-need_len // self.page_size)  # ceil
        for i, phys in enumerate(shared[:n_logical]):
            self.table[slot, i] = phys
            self.refcount[phys] += 1
        for i in range(len(shared[:n_logical]), n_logical):
            self.table[slot, i] = self._alloc()
        return len(shared[:n_logical]) * self.page_size

    def free_slot(self, slot: int):
        for i in range(self.pages_per_slot):
            phys = int(self.table[slot, i])
            if phys:
                self.table[slot, i] = 0
                self._deref(phys)

    def register_prefix(self, slot: int, prompt: Sequence[int]):
        """After a slot's prompt is fully prefilled, publish its full pages
        under the token prefix (LRU, capped at ``max_prefixes``)."""
        k = (len(prompt) - 1) // self.page_size
        if k <= 0:
            return
        key = tuple(prompt[:k * self.page_size])
        if key in self.prefixes:
            self.prefixes.move_to_end(key)
            return
        pages = [int(self.table[slot, i]) for i in range(k)]
        if any(p == 0 for p in pages):
            return  # slot not actually filled that far
        for p in pages:
            self.refcount[p] += 1
        self.prefixes[key] = pages
        self.stats.prefix_insertions += 1
        while len(self.prefixes) > self.max_prefixes:
            _, old = self.prefixes.popitem(last=False)
            self.stats.prefix_evictions += 1
            for p in old:
                self._deref(p)

    # -- writes (checksum-on-write, page granular) -----------------------------
    def _writable(self, slot: int, logical: int) -> int:
        """Physical page for a write: allocate on demand, copy-on-write when
        the mapped page is shared (prefix sharing never writes into shared
        pages in normal operation, but the write path stays safe)."""
        phys = int(self.table[slot, logical])
        if phys == 0:
            phys = self._alloc()
            self.table[slot, logical] = phys
            return phys
        if self.refcount[phys] > 1:
            new = self._alloc()
            for key, pool in self.pools.items():
                page = pool[:, phys]
                page64 = np.asarray(page, np.float64)
                self.pools[key] = pool.at[:, new].set(page)
                self.esum[key] += page64
                self.page_fp[key][new] = float(page64.sum())
                self.last_rearmed.append((key, new))
                self.stats.checksum_rearms += 1
            self.table[slot, logical] = new
            self._deref(phys)
            self.stats.cow_copies += 1
            return new
        return phys

    def write(self, key: str, slot: int, start: int, vals):
        """Write ``vals`` ``[repeats, n, *tail]`` at positions
        ``[start, start + n)`` of ``slot``, re-arming exactly the touched
        pages' checksums.  The update is incremental and O(segment):
        ``+= new - old`` (``old`` is zero on the engine's write-once path —
        cells stay zero between free/zero cycles — but a copy-on-write
        overwrite of copied prefix content stays consistent too)."""
        pool = self.pools[key]
        vals = jnp.asarray(vals, pool.dtype)
        n = vals.shape[1]
        ps = self.page_size
        pos = start
        while pos < start + n:
            logical, off = pos // ps, pos % ps
            seg_n = min(ps - off, start + n - pos)
            phys = self._writable(slot, logical)
            seg = vals[:, pos - start:pos - start + seg_n]
            old64 = np.asarray(
                self.pools[key][:, phys, off:off + seg_n], np.float64)
            self.pools[key] = self.pools[key].at[
                :, phys, off:off + seg_n].set(seg)
            seg64 = np.asarray(seg, np.float64)
            self.page_fp[key][phys] += float(seg64.sum() - old64.sum())
            self.esum[key][:, off:off + seg_n] += seg64 - old64
            self.last_rearmed.append((key, phys))
            self.stats.checksum_rearms += 1
            pos += seg_n

    def write_token(self, key: str, slot: int, pos: int, val):
        """One decode token: ``val`` ``[repeats, *tail]`` at ``pos``."""
        self.write(key, slot, pos, jnp.asarray(val)[:, None])

    def begin_mutation(self):
        """Reset the per-mutation re-arm ledger (test hook: asserts a
        single-page write dirties exactly one checksum per leaf)."""
        self.last_rearmed = []

    # -- reads -----------------------------------------------------------------
    def gather(self, key: str) -> jax.Array:
        """Dense ``[repeats, slots, max_len, *tail]`` view of every slot
        (zero beyond each write head — the zero page)."""
        pool = self.pools[key]
        flat = jnp.asarray(self.table.reshape(-1), jnp.int32)
        dense = jnp.take(pool, flat, axis=1)
        r, tail = pool.shape[0], pool.shape[3:]
        return dense.reshape((r, self.slots, self.max_len) + tail)

    def gather_slot(self, key: str, slot: int) -> jax.Array:
        pool = self.pools[key]
        flat = jnp.asarray(self.table[slot], jnp.int32)
        dense = jnp.take(pool, flat, axis=1)
        r, tail = pool.shape[0], pool.shape[3:]
        return dense.reshape((r, 1, self.max_len) + tail)

    # -- verify / repair (the scrub + DRAM-recovery unit) ----------------------
    def arm_all(self):
        """Full recompute of every checksum from the pools (init/reset)."""
        for key, pool in self.pools.items():
            p64 = np.asarray(pool, np.float64)
            self.page_fp[key] = p64.sum(
                axis=tuple(i for i in range(p64.ndim) if i != 1))
            self.esum[key] = p64.sum(axis=1)

    def verify(self) -> List[Tuple[str, int]]:
        """Recompute page fingerprints; returns tripped (leaf, page) pairs.
        Every non-zero physical page is checked — a corrupted FREE page
        (fingerprint must be 0) trips too, protecting zero-at-free."""
        self.stats.verifies += 1
        tripped = []
        for key, pool in self.pools.items():
            p64 = np.asarray(pool, np.float64)
            fp = p64.sum(axis=tuple(i for i in range(p64.ndim) if i != 1))
            armed = self.page_fp[key]
            diff = np.abs(fp - armed)
            # a flip into the NaN pattern poisons the page sum; NaN
            # compares false against any threshold — count it tripped
            diff = np.where(np.isnan(diff), np.inf, diff)
            scale = float(np.max(np.abs(armed))) + 1.0
            for phys in np.nonzero(diff > 1e-6 * scale)[0]:
                if phys:  # page 0 is immutable-zero by construction
                    tripped.append((key, int(phys)))
        return tripped

    def repair(self, key: str, phys: int) -> bool:
        """Erasure solve: rebuild page ``phys`` of leaf ``key`` as
        ``esum - sum(other live pages)`` in float64 (a corrupted free page
        rebuilds to zero: it contributes nothing to esum)."""
        pool = self.pools[key]
        others = [p for p in self.live_pages() if p != phys]
        recon = self.esum[key].copy()
        if others:
            recon -= np.asarray(pool[:, np.asarray(others)],
                                np.float64).sum(axis=1)
        self.pools[key] = pool.at[:, phys].set(
            jnp.asarray(recon.astype(np.asarray(pool).dtype)))
        self.page_fp[key][phys] = float(recon.sum())
        self.last_rearmed.append((key, phys))
        self.stats.checksum_rearms += 1
        self.stats.repairs += 1
        obs.counter("repro_page_repairs_total",
                    "paged-KV erasure page rebuilds").inc()
        return True

    def scrub(self) -> List[Tuple[str, int]]:
        """verify + repair; returns the repaired (leaf, page) pairs."""
        repaired = []
        for key, phys in self.verify():
            if self.repair(key, phys):
                repaired.append((key, phys))
        return repaired

    # -- drills ----------------------------------------------------------------
    def corrupt_page(self, key: str, phys: int, index: int = 0,
                     bit: int = 30):
        """Fault-injection helper: flip one bit of page ``phys`` (float32
        pools; other dtypes get an additive 1e4 delta at ``index``)."""
        pool = self.pools[key]
        page = pool[:, phys]
        if page.dtype == jnp.float32:
            from repro.chaos.faults import flip_bit
            page = flip_bit(page, index, bit)
        else:
            flat = page.reshape(-1)
            page = flat.at[index].add(
                jnp.asarray(1e4, page.dtype)).reshape(page.shape)
        self.pools[key] = pool.at[:, phys].set(page)

    # -- invariants (property-test hooks) --------------------------------------
    def check_invariants(self):
        """Raises AssertionError on any broken pool invariant:
        conservation (free + live partition the pool exactly), refcount
        accounting (table refs + registry refs), no page shared by two
        slots unless it is a registry (prefix) page, zero-page integrity."""
        live = set(self.live_pages())
        free = set(self.free)
        assert not (live & free), f"pages both live and free: {live & free}"
        assert live | free == set(range(1, self.n_pages)), (
            "conservation broken: free + live must partition the pool "
            f"(missing {set(range(1, self.n_pages)) - live - free})")
        refs = np.zeros((self.n_pages,), np.int64)
        for phys in self.table.reshape(-1):
            if phys:
                refs[phys] += 1
        registry_pages = set()
        for pages in self.prefixes.values():
            for p in pages:
                refs[p] += 1
                registry_pages.add(p)
        assert np.array_equal(refs[1:], self.refcount[1:]), (
            f"refcount mismatch: counted {refs[1:].tolist()} "
            f"vs tracked {self.refcount[1:].tolist()}")
        owners: Dict[int, set] = {}
        for s in range(self.slots):
            for phys in self.table[s]:
                if phys:
                    owners.setdefault(int(phys), set()).add(s)
        for phys, ss in owners.items():
            assert len(ss) == 1 or phys in registry_pages, (
                f"page {phys} referenced by slots {sorted(ss)} without a "
                "prefix-registry entry (non-prefix sharing)")
        for key, pool in self.pools.items():
            assert not np.any(np.asarray(pool[:, 0])), \
                f"zero page of {key!r} was written"
            for phys in free:
                assert not np.any(np.asarray(pool[:, phys])), \
                    f"free page {phys} of {key!r} is not zero"

    def checksums_consistent(self, rtol: float = 1e-6) -> bool:
        """True when every armed checksum matches a recompute (every page
        re-armed after each mutation — the property tests' postcondition)."""
        if self.verify():
            return False
        for key, pool in self.pools.items():
            p64 = np.asarray(pool, np.float64)
            if not np.allclose(p64.sum(axis=1), self.esum[key],
                               rtol=rtol, atol=1e-8):
                return False
        return True
