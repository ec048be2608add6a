"""Paged (block) KV cache: fixed-size pages as the unified protection unit.

vLLM-style memory layout for the serving engine: every float KV-cache
leaf gets a page pool ``[n_pages, repeats, page_size, features]`` (the
leaf's tail flattened into features) and all
leaves share ONE host-side page table ``[slots, pages_per_slot]`` mapping
each slot's logical pages onto physical pages.  Physical page 0 is the
reserved immutable **zero page** — unallocated table entries point at it,
so a page read through the table is exactly zero beyond every slot's
write head (which the causal mask discards; see the decode-parity note
below).

The page is also the repo's ABFT unit for serving memory:

  * **checksum-on-write, page granular** — every mutation re-arms exactly
    the pages it touched: a per-(leaf, page) float64 scalar fingerprint
    (detect + locate) and a per-leaf float64 *elementwise* page sum
    ``esum[r, o, f] = sum_p pool[p, r, o, f]`` (the erasure row
    that repairs).  The engine's decode writes ONE token per slot per
    step, so the incremental update is always ``+= new`` — the write-once
    invariant (cells are zero until their first and only write between
    free/zero cycles) makes arming O(page) instead of O(cache).
  * **verify-on-read** — `verify()` recomputes the fingerprints of the
    live pages, read to the host ``SCRUB_PAGES`` at a time, checks on the
    device that every other page is exactly zero, and returns the tripped
    (leaf, page) pairs; NaN-poisoned pages (a bit-30 flip near 1.0)
    compare as tripped, not silently equal.
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

Writes and frees are batched: the page-table work (allocation,
copy-on-write, the re-arm ledger) runs on the host first, then ONE
compiled program (`serve_kv_write`, `serve_kv_zero`) with the pools
donated updates every leaf in place and returns the written and replaced
values, which come home in ONE device-to-host read for the float64
checksum update.  Batches are padded to a fixed size per caller (decode:
slots, prefill: the prompt bucket, free: pages per slot) so that a warmed
engine compiles nothing new; padding entries name an out-of-range page,
which the gathers fill with 0 and the scatters drop.

The layout serves the TPU's tiled memory.  Within a page the layers are
major, so one layer's page ``[page_size, features]`` is one contiguous
tile (16 x 128 in bf16 for qwen2-0.5b), which the paged decode reads
whole, and a page of every layer is one contiguous block, which the
programs above write, zero and read whole.  A write reads the pages it
touches, sets its tokens in them and writes the pages back: a scatter of
single tokens across every layer would make the compiler re-lay the
whole pool (about 1 GB of copies per 0.5 GB pool on a v5e), as would the
unflattened order ``[..., 2, 64]`` with its 64-wide minor dimension.

Decode reads the pools in place: the compiled decode
(`serve.engine.PagedServeEngine`) reads each layer's pages through the
page table (`models.attention.paged_view`), sets the fresh token in that
per-layer view only and returns the new K/V rows, which `write_batch`
puts into the pools.  Decode parity: that view differs from a dense
contiguous cache only at causally-masked positions (zeros here, prefill
pad garbage there); `_sdpa_dense` masks with ``where(mask, s, NEG_INF)``
before the softmax, so those positions carry exactly zero weight either
way and the decode logits equal the dense decode's bit for bit
(tests/test_paged_decode.py; token streams: tests/test_traffic.py,
clean and drilled).  `gather()` builds the same view for every slot and
layer at once: the tests' oracle and the chunked prefill's one-slot
cache.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
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
         "unit for serving memory (PagedServeEngine(scrub_every=N) sets "
         "the verify cadence); single-page fault "
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
    # compiled write programs run, by kind
    write_batches: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"decode": 0, "prefill": 0, "free": 0})


_PAD = np.iinfo(np.int32).max   # index of a padding entry: out of range


def _page_sums(pages: np.ndarray, ex: ThreadPoolExecutor) -> np.ndarray:
    """Float64 sum of each page of a host pool ``[n_pages, ...]``: values
    widened exactly to float32, summed in float64, blocks of pages on the
    executor's threads (NumPy leaves the interpreter lock in its loops)."""
    rows = pages.reshape(len(pages), -1)
    cuts = np.linspace(0, len(rows), 32 + 1).astype(int)    # 32 blocks
    return np.concatenate(list(ex.map(
        lambda a, b: rows[a:b].astype(np.float32, copy=False).sum(
            axis=1, dtype=np.float64), cuts[:-1], cuts[1:])))


# the jitted programs take these names (the trace's "XLA Modules" line
# shows jit_serve_kv_write, jit_serve_kv_zero, jit_serve_kv_gather)
@functools.partial(jax.jit, donate_argnums=0)
def serve_kv_write(pools, srcs, idx, pages):
    """One batch of token writes into every pool, in place, a page at a
    time.

    ``pages`` ``[P]`` are the physical pages the batch writes (padding:
    out of range).  ``idx`` is ``[4, N]`` int32: token i goes from
    ``src[:, idx[0, i], idx[1, i]]`` (a dense ``[repeats, B, len, *tail]``
    source: a prefill's one-slot cache, or the decode's new rows, ``len``
    1) to offset ``idx[3, i]`` of page ``pages[idx[2, i]]``.  Returns the
    pools, the values written and the values they replaced, each ``[N,
    repeats, features]``."""
    src_slot, src_pos, at, off = idx
    new_pools, news, olds = [], [], []
    for pool, src in zip(pools, srcs):
        rows = src.at[:, src_slot, src_pos].get(mode="fill", fill_value=0)
        new = jnp.moveaxis(rows.reshape(rows.shape[:2] + (-1,)), 1, 0)
        new = new.astype(pool.dtype)
        block = pool.at[pages].get(mode="fill", fill_value=0)
        olds.append(block.at[at, :, off].get(mode="fill", fill_value=0))
        block = block.at[at, :, off].set(new, mode="drop")
        new_pools.append(pool.at[pages].set(block, mode="drop"))
        news.append(new)
    return tuple(new_pools), tuple(news), tuple(olds)


@functools.partial(jax.jit, donate_argnums=0)
def serve_kv_zero(pools, phys):
    """Zero pages ``phys`` ``[P]`` of every pool in place; returns the
    pools and the pages' previous contents ``[P, repeats, page,
    features]``."""
    olds = tuple(pool.at[phys].get(mode="fill", fill_value=0)
                 for pool in pools)
    return tuple(pool.at[phys].set(0, mode="drop")
                 for pool in pools), olds


SCRUB_PAGES = 128
"""Pages of every pool that one scrub read brings to the host."""


@jax.jit
def serve_kv_read(pools, idx):
    """Pages ``idx`` ``[SCRUB_PAGES]`` of every pool (padding: out of
    range, read as zero)."""
    return tuple(pool.at[idx].get(mode="fill", fill_value=0)
                 for pool in pools)


@jax.jit
def serve_kv_nonzero(pools):
    """For every page of every pool, whether any of its values is not zero
    (a NaN is not zero)."""
    return tuple(jnp.any(pool != 0, axis=tuple(range(1, pool.ndim)))
                 for pool in pools)


@functools.partial(jax.jit, static_argnums=2)
def serve_kv_gather(pool, table, tail):
    """Dense ``[repeats, B, len, *tail]`` view of the pages ``table``
    ``[B, pages]`` maps."""
    b, n = table.shape
    _, r, ps, _ = pool.shape
    dense = jnp.moveaxis(pool[table.reshape(-1)], 1, 0)
    return dense.reshape((r, b, n * ps) + tail)


class PagedKVCache:
    """Engine-agnostic paged pool; see module docstring.

    ``leaf_shapes`` maps a leaf key (the engine uses jax keystr paths) to
    ``(dense_shape, dtype)`` where dense_shape is the contiguous layout
    ``[repeats, slots, max_len, *tail]`` the leaf would occupy; its pages
    live in ``pools[key]`` ``[n_pages, repeats, page_size, features]``.
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
                (self.n_pages, repeats, page_size, int(np.prod(tail))),
                dtype)
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
            key: np.zeros(p.shape[1:], np.float64)
            for key, p in self.pools.items()}
        self.last_rearmed: List[Tuple[str, int]] = []
        self._bufs: Dict[Tuple[int, ...], Tuple[np.ndarray, ...]] = {}

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
            self._release(pages)
        if not self.free:
            raise RuntimeError("page pool exhausted (no free or evictable "
                               "pages) — admission control must defer")
        phys = self.free.pop()
        self.refcount[phys] = 1
        self.stats.allocs += 1
        return phys

    def _release(self, pages: Sequence[int]):
        """Drop one reference to each of ``pages``; those left unreferenced
        are zeroed in one batch and return to the free list.  Zero-at-free
        keeps "free page == zero page contents == zero checksum
        contribution": allocation needs no work and a corrupted free page
        is detectable (its fingerprint must stay 0).  A freed page's
        contents leave ``esum`` as they stand, corrupted or not."""
        dead = []
        for phys in pages:
            if phys == 0:
                continue  # the zero page is immortal
            self.refcount[phys] -= 1
            if self.refcount[phys] == 0:
                dead.append(phys)
        if not dead:
            return
        n = len(dead)
        idx = np.full(-(-n // self.pages_per_slot) * self.pages_per_slot,
                      _PAD, np.int32)
        idx[:n] = dead
        keys = list(self.pools)
        pools, olds = serve_kv_zero(tuple(self.pools.values()),
                                    jnp.asarray(idx))
        self.pools = dict(zip(keys, pools))
        for key, old in zip(keys, jax.device_get(olds)):
            self.esum[key] -= old[:n].astype(np.float64).sum(axis=0)
            self.page_fp[key][dead] = 0.0
        self.free.extend(dead)
        self.stats.frees += n
        self._count_batch("free")

    def _count_batch(self, kind: str):
        self.stats.write_batches[kind] += 1
        obs.counter("repro_kv_write_batches_total",
                    "compiled paged-KV write programs run").inc(kind=kind)

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
        pages = [int(p) for p in self.table[slot] if p]
        self.table[slot] = 0
        self._release(pages)

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
            self._release(old)

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
                page = pool[phys]
                page64 = np.asarray(page, np.float64)
                self.pools[key] = pool.at[new].set(page)
                self.esum[key] += page64
                self.page_fp[key][new] = float(page64.sum())
                self.last_rearmed.append((key, new))
                self.stats.checksum_rearms += 1
            self.table[slot, logical] = new
            self._release([phys])
            self.stats.cow_copies += 1
            return new
        return phys

    def write_batch(self, srcs: Dict[str, jax.Array],
                    writes: Sequence[Tuple[int, int, int, int, int]], *,
                    kind: str, pad_to: int = 0):
        """Write positions ``[start, start + n)`` of ``slot`` for every
        ``(slot, start, n, src_slot, src_start)`` in ``writes`` and every
        leaf in ``srcs`` (dense ``[repeats, B, len, *tail]`` sources; token
        ``start + i`` comes from ``src[:, src_slot, src_start + i]``), as
        one compiled scatter padded to ``pad_to`` tokens.

        The page-table work runs first, in the order of a per-(leaf,
        segment) loop: allocation on demand, copy-on-write, one re-arm per
        (leaf, page) touched.  The checksum update is incremental,
        ``+= new - old`` in float64, from one device-to-host read (``old``
        is zero on the engine's write-once path — cells stay zero between
        free/zero cycles — but a copy-on-write overwrite of copied prefix
        content stays consistent too)."""
        writes = [w for w in writes if w[2] > 0]
        if not writes:
            return
        ps = self.page_size
        keys = list(srcs)
        if any(srcs[k].shape[1] > 1 for k in keys) and (
                any(w[2] != 1 for w in writes)
                or len({w[3] for w in writes}) < len(writes)):
            raise ValueError("a source of more than one slot gives at most "
                             "one token per slot")
        for key in keys:
            for slot, start, n, _, _ in writes:
                for logical in range(start // ps, -(-(start + n) // ps)):
                    phys = self._writable(slot, logical)
                    self.last_rearmed.append((key, phys))
                    self.stats.checksum_rearms += 1
        slot = np.concatenate([np.full(w[2], w[0]) for w in writes])
        pos = np.concatenate([np.arange(w[1], w[1] + w[2]) for w in writes])
        n = len(pos)
        width = max(n, pad_to)
        idx = np.zeros((4, width), np.int32)
        idx[(0, 2), n:] = _PAD
        idx[0, :n] = np.concatenate([np.full(w[2], w[3]) for w in writes])
        idx[1, :n] = np.concatenate([np.arange(w[4], w[4] + w[2])
                                     for w in writes])
        phys = self.table[slot, pos // ps]
        idx[3, :n] = off = pos % ps
        touched, idx[2, :n] = np.unique(phys, return_inverse=True)
        # one run of tokens spans at most this many pages; a batch of runs
        # (the decode's, one token a slot) no more pages than tokens
        one_run = len(writes) == 1 and all(
            srcs[k].shape[1] == 1 for k in keys)
        pages = np.full(min(width, -(-(width + ps - 1) // ps)) if one_run
                        else width, _PAD, np.int32)
        pages[:len(touched)] = touched
        pools, news, olds = serve_kv_write(
            tuple(self.pools[k] for k in keys),
            tuple(srcs[k] for k in keys), jnp.asarray(idx),
            jnp.asarray(pages))
        self.pools.update(zip(keys, pools))
        # tokens grouped by their offset in the page, in write order
        order = np.argsort(off, kind="stable")
        offs, starts = np.unique(off[order], return_index=True)
        bounds = list(zip(offs, starts, [*starts[1:], n]))
        for key, new, old in zip(keys, *jax.device_get((news, olds))):
            d, grouped, row = self._scratch(n, new.shape[1:])
            np.subtract(new[:n], old[:n], out=d, dtype=np.float64)
            self.page_fp[key] += np.bincount(
                phys, d.reshape(n, -1).sum(axis=1), minlength=self.n_pages)
            np.take(d, order, axis=0, out=grouped)
            esum = self.esum[key]
            for o, a, b in bounds:
                esum[:, o] += grouped[a:b].sum(axis=0, out=row)
        self._count_batch(kind)

    def _scratch(self, n: int, shape: Tuple[int, ...]):
        """Float64 buffers for the checksum update of ``n`` tokens of
        ``shape``: the differences, the same grouped by page offset, and
        one group's sum.  They are kept from write to write: fresh
        temporaries of megabytes cost page faults whose number follows the
        allocator's state, which made a prefill's page write take from 30
        to 80 ms (TPU v5e host)."""
        bufs = self._bufs.get(shape)
        if bufs is None or len(bufs[0]) < n:
            bufs = self._bufs[shape] = (
                np.empty((n,) + shape), np.empty((n,) + shape),
                np.empty(shape))
        return bufs[0][:n], bufs[1][:n], bufs[2]

    def write(self, key: str, slot: int, start: int, vals):
        """Write ``vals`` ``[repeats, n, *tail]`` of leaf ``key`` at
        positions ``[start, start + n)`` of ``slot`` (one batch)."""
        vals = jnp.asarray(vals, self.pools[key].dtype)
        self.write_batch({key: vals[:, None]},
                         [(slot, start, vals.shape[1], 0, 0)], kind="prefill")

    def begin_mutation(self):
        """Reset the per-mutation re-arm ledger (test hook: asserts a
        single-page write dirties exactly one checksum per leaf)."""
        self.last_rearmed = []

    # -- reads -----------------------------------------------------------------
    def gather(self, key: str) -> jax.Array:
        """Dense ``[repeats, slots, max_len, *tail]`` view of every slot
        (zero beyond each write head — the zero page): what the decode
        reads through the table, one layer at a time."""
        return serve_kv_gather(self.pools[key], jnp.asarray(self.table),
                               self._tails[key])

    def gather_slot(self, key: str, slot: int) -> jax.Array:
        return serve_kv_gather(self.pools[key],
                               jnp.asarray(self.table[slot:slot + 1]),
                               self._tails[key])

    # -- verify / repair (the scrub + DRAM-recovery unit) ----------------------
    def _read(self, phys: np.ndarray):
        """Host copies of pages ``phys`` of every pool, ``SCRUB_PAGES`` a
        read: yields ``(pages, {key: [len(pages), ...]})``.  One read runs
        even for no pages (yielding nothing), so that a reset compiles the
        program before any scrub needs it."""
        pools = tuple(self.pools.values())
        for a in range(0, max(len(phys), 1), SCRUB_PAGES):
            part = phys[a:a + SCRUB_PAGES]
            idx = np.full(SCRUB_PAGES, _PAD, np.int32)
            idx[:len(part)] = part
            got = jax.device_get(serve_kv_read(pools, jnp.asarray(idx)))
            if len(part):
                yield part, {key: g[:len(part)]
                             for key, g in zip(self.pools, got)}

    def _nonzero(self) -> Dict[str, np.ndarray]:
        """Per leaf, ``[n_pages]``: does the page hold a value other than
        zero?  Computed on the device; only the flags come home."""
        flags = jax.device_get(serve_kv_nonzero(tuple(self.pools.values())))
        return dict(zip(self.pools, flags))

    def arm_all(self):
        """Full recompute of every checksum from the pools (init/reset):
        every page that is live or not zero is read; the others add
        nothing."""
        nonzero = self._nonzero()
        read = np.nonzero((self.refcount > 0)
                          | np.logical_or.reduce(list(nonzero.values())))[0]
        for key, pool in self.pools.items():
            self.page_fp[key] = np.zeros((self.n_pages,), np.float64)
            self.esum[key] = np.zeros(pool.shape[1:], np.float64)
        for part, pages in self._read(read):
            for key, pg in pages.items():
                p64 = pg.astype(np.float64)
                self.page_fp[key][part] = p64.reshape(len(part), -1).sum(
                    axis=1)
                self.esum[key] += p64.sum(axis=0)

    def verify(self) -> List[Tuple[str, int]]:
        """Recompute page fingerprints; returns tripped (leaf, page) pairs.
        Live pages are read to the host and summed in float64; every other
        page must be zero, which the device checks (zero-at-free), so a
        corrupted FREE page trips too."""
        self.stats.verifies += 1
        tripped = []
        nonzero = self._nonzero()
        live = np.nonzero(self.refcount)[0]
        sums = {key: np.zeros((self.n_pages,), np.float64)
                for key in self.pools}
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
            for part, pages in self._read(live):
                for key, pg in pages.items():
                    sums[key][part] = _page_sums(pg, ex)
        for key, fp in sums.items():
            armed = self.page_fp[key]
            diff = np.abs(fp - armed)
            # a flip into the NaN pattern poisons the page sum; NaN
            # compares false against any threshold — count it tripped
            diff = np.where(np.isnan(diff), np.inf, diff)
            scale = float(np.max(np.abs(armed))) + 1.0
            bad = ((diff > 1e-6 * scale)
                   | (nonzero[key] & (self.refcount == 0)))
            for phys in np.nonzero(bad)[0]:
                if phys:  # page 0 is immutable-zero by construction
                    tripped.append((key, int(phys)))
        return tripped

    def repair(self, key: str, phys: int) -> bool:
        """Erasure solve: rebuild page ``phys`` of leaf ``key`` as
        ``esum - sum(other live pages)`` in float64.  A page that is not
        live contributes nothing to esum: it rebuilds to zero."""
        pool = self.pools[key]
        recon = np.zeros(pool.shape[1:], np.float64)
        if self.refcount[phys]:
            recon += self.esum[key]
            others = np.asarray([p for p in self.live_pages() if p != phys],
                                np.int32)
            for _, pages in self._read(others):
                recon -= pages[key].astype(np.float64).sum(axis=0)
        self.pools[key] = pool.at[phys].set(
            jnp.asarray(recon.astype(pool.dtype)))
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
        page = pool[phys]
        if page.dtype == jnp.float32:
            from repro.chaos.faults import flip_bit
            page = flip_bit(page, index, bit)
        else:
            flat = page.reshape(-1)
            page = flat.at[index].add(
                jnp.asarray(1e4, page.dtype)).reshape(page.shape)
        self.pools[key] = pool.at[phys].set(page)

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
            pages = np.asarray(pool)
            assert not np.any(pages[0]), f"zero page of {key!r} was written"
            dirty = [p for p in sorted(free) if np.any(pages[p])]
            assert not dirty, f"free pages {dirty} of {key!r} are not zero"

    def checksums_consistent(self, rtol: float = 1e-6) -> bool:
        """True when every armed checksum matches a recompute (every page
        re-armed after each mutation — the property tests' postcondition)."""
        if self.verify():
            return False
        for key, pool in self.pools.items():
            p64 = np.asarray(pool, np.float64)
            if not np.allclose(p64.sum(axis=0), self.esum[key],
                               rtol=rtol, atol=1e-8):
                return False
        return True
