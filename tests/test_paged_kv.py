"""Property tests for the paged KV cache (serve.paged_kv).

The allocator + checksum invariants land test-first (PR 8's archetype):

  * conservation — free list + live pages partition the pool exactly
  * no page is referenced by two slots unless it is a prefix-registry page
  * every page checksum is re-armed after each mutation, and a
    single-page write dirties EXACTLY one checksum per leaf (the PR 6
    scrub-unit regression)
  * corrupt -> verify locates the page -> repair rebuilds it exactly

The random-trace drivers below are always-on (seeded numpy); when
hypothesis is installed the same state machine also runs under generated
traces (guarded import — hypothesis is optional in this environment).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.paged_kv import PagedKVCache

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SLOTS, MAX_LEN, PS = 3, 32, 8


def make_kv(extra_pages=0, max_prefixes=4):
    return PagedKVCache(
        {"k": ((2, SLOTS, MAX_LEN, 4), np.float32),
         "v": ((2, SLOTS, MAX_LEN, 4), np.float32)},
        slots=SLOTS, max_len=MAX_LEN, page_size=PS,
        extra_pages=extra_pages, max_prefixes=max_prefixes)


def fill(rs, n, lo=1, hi=8):
    """Integer-valued float payloads: the float64 checksum chain is exact,
    so repair roundtrips bit-for-bit."""
    return rs.randint(lo, hi, size=(2, n, 4)).astype(np.float32)


def ok(kv):
    kv.check_invariants()
    assert kv.checksums_consistent()


# ---------------------------------------------------------------------------
# targeted invariants
# ---------------------------------------------------------------------------


def test_alloc_write_free_conservation(rs):
    kv = make_kv()
    total_free = kv.n_free()
    start = kv.alloc_slot(0, 20)
    assert start == 0 and kv.n_free() == total_free - 3  # ceil(20/8) pages
    kv.write("k", 0, 0, fill(rs, 20))
    kv.write("v", 0, 0, fill(rs, 20))
    ok(kv)
    kv.free_slot(0)
    assert kv.n_free() == total_free
    ok(kv)   # freed pages are zeroed and checksum-consistent again


def test_single_page_write_dirties_exactly_one_checksum(rs):
    """The PR 6 scrub-unit fix: a one-token decode write re-arms one page
    checksum per leaf — not the whole slot, not the whole cache."""
    kv = make_kv()
    kv.alloc_slot(0, 12)
    kv.write("k", 0, 0, fill(rs, 10))
    kv.write("v", 0, 0, fill(rs, 10))
    fp_before = {key: kv.page_fp[key].copy() for key in kv.pools}

    kv.begin_mutation()
    kv.write("k", 0, 10, fill(rs, 1))
    assert len(kv.last_rearmed) == 1, (
        f"single-page write re-armed {kv.last_rearmed}")
    (leaf, phys), = kv.last_rearmed
    assert leaf == "k" and phys == kv.page_of(0, 10)
    # every OTHER page checksum is untouched, including the other leaf's
    for key in kv.pools:
        same = kv.page_fp[key] == fp_before[key]
        if key == leaf:
            assert not same[phys] and same[np.arange(len(same)) != phys].all()
        else:
            assert same.all()
    ok(kv)


def test_write_across_page_boundary_rearms_both_pages(rs):
    kv = make_kv()
    kv.alloc_slot(0, 16)
    kv.begin_mutation()
    kv.write("k", 0, PS - 2, fill(rs, 4))   # straddles pages 0 and 1
    pages = {p for _, p in kv.last_rearmed}
    assert len(kv.last_rearmed) == 2 and len(pages) == 2
    ok(kv)


def test_prefix_sharing_refcounts_and_no_foreign_sharing(rs):
    kv = make_kv()
    prompt = list(range(100, 100 + 2 * PS))     # two full pages + none over
    start = kv.alloc_slot(0, len(prompt) + 4, prompt=prompt)
    assert start == 0 and kv.stats.prefix_misses == 1
    kv.write("k", 0, 0, fill(rs, len(prompt)))
    kv.write("v", 0, 0, fill(rs, len(prompt)))
    kv.register_prefix(0, prompt)
    assert kv.stats.prefix_insertions == 1
    ok(kv)

    # a second slot admitting the same prompt shares the full first page
    # (register keeps (plen-1)//ps pages so a suffix token always remains)
    start1 = kv.alloc_slot(1, len(prompt) + 4, prompt=prompt)
    assert start1 == PS and kv.stats.prefix_hits == 1
    shared = kv.page_of(1, 0)
    assert shared == kv.page_of(0, 0) and kv.refcount[shared] == 3
    ok(kv)   # shared page is registry-backed: not "foreign" sharing

    # both slots retire; the registry still holds its reference
    kv.free_slot(0)
    kv.free_slot(1)
    assert kv.refcount[shared] == 1
    ok(kv)


def test_copy_on_write_unshares(rs):
    kv = make_kv()
    prompt = list(range(2 * PS))
    kv.alloc_slot(0, 2 * PS + 2, prompt=prompt)
    kv.write("k", 0, 0, fill(rs, 2 * PS))
    kv.write("v", 0, 0, fill(rs, 2 * PS))
    kv.register_prefix(0, prompt)
    kv.alloc_slot(1, 2 * PS + 2, prompt=prompt)
    shared = kv.page_of(1, 0)
    before = np.asarray(kv.pools["k"][kv.page_of(0, 0)]).copy()

    kv.write("k", 1, 0, fill(rs, 2))    # write INTO the shared page
    assert kv.stats.cow_copies == 1
    assert kv.page_of(1, 0) != shared, "write must unshare first"
    np.testing.assert_array_equal(
        np.asarray(kv.pools["k"][kv.page_of(0, 0)]), before,
        err_msg="slot 0's view of the shared page changed")
    ok(kv)


def test_corrupt_verify_locates_repair_exact(rs):
    kv = make_kv()
    kv.alloc_slot(0, 24)
    kv.write("k", 0, 0, fill(rs, 24))
    kv.write("v", 0, 0, fill(rs, 24))
    target = kv.page_of(0, PS)          # a middle live page
    golden = np.asarray(kv.pools["k"][target]).copy()

    kv.corrupt_page("k", target, bit=30)
    tripped = kv.verify()
    assert tripped == [("k", target)], (
        f"verify must locate exactly the corrupted page, got {tripped}")
    assert kv.repair("k", target)
    np.testing.assert_array_equal(
        np.asarray(kv.pools["k"][target]), golden,
        err_msg="erasure repair must rebuild the page exactly")
    ok(kv)


def test_corrupted_free_page_detected_and_rebuilt_to_zero(rs):
    kv = make_kv()
    kv.alloc_slot(0, 8)
    kv.write("k", 0, 0, fill(rs, 8))
    free_page = kv.free[0]
    kv.corrupt_page("k", free_page, bit=30)
    assert ("k", free_page) in kv.verify(), \
        "zero-at-free: a corrupted free page must trip"
    kv.repair("k", free_page)
    assert not np.any(np.asarray(kv.pools["k"][free_page]))
    ok(kv)


def test_free_page_off_by_less_than_the_tolerance_trips(rs):
    """A free page must be exactly zero: a value far below the page-sum
    tolerance trips, and the repair writes zeros, not a float64
    remainder."""
    kv = make_kv()
    kv.alloc_slot(0, 8)
    kv.write("k", 0, 0, fill(rs, 8))
    free_page = kv.free[0]
    kv.pools["k"] = kv.pools["k"].at[free_page, 1, 2, 3].set(1e-30)
    assert kv.verify() == [("k", free_page)]
    kv.repair("k", free_page)
    assert not np.any(np.asarray(kv.pools["k"][free_page]))
    ok(kv)


@pytest.mark.parametrize("chunk", [1, 2, 3, 128])
def test_scrub_reads_live_pages_only_in_chunks(rs, monkeypatch, chunk):
    """The scrub brings home the live pages, ``SCRUB_PAGES`` at a time,
    and no free page: a flip in any live page, first chunk or last, is
    located and rebuilt exactly."""
    from repro.serve import paged_kv
    monkeypatch.setattr(paged_kv, "SCRUB_PAGES", chunk)
    reads = []
    read = paged_kv.serve_kv_read
    monkeypatch.setattr(paged_kv, "serve_kv_read", lambda pools, idx: (
        reads.append(np.asarray(idx)), read(pools, idx))[1])
    kv = make_kv()
    kv.alloc_slot(0, 20)
    kv.alloc_slot(2, 9)
    kv.write("k", 0, 0, fill(rs, 20))
    kv.write("v", 2, 0, fill(rs, 9))
    live = kv.live_pages()
    assert len(live) == 5
    reads.clear()
    assert not kv.verify()
    got = np.concatenate(reads)
    assert sorted(got[got < kv.n_pages].tolist()) == live
    assert len(reads) == -(-len(live) // chunk)
    for phys in (live[0], live[-1]):
        golden = np.asarray(kv.pools["v"][phys]).copy()
        kv.corrupt_page("v", phys, index=5, bit=30)
        assert kv.scrub() == [("v", phys)]
        np.testing.assert_array_equal(np.asarray(kv.pools["v"][phys]),
                                      golden)
    ok(kv)
    # nothing live: one read of padding, so a reset compiles the program
    for s in (0, 2):
        kv.free_slot(s)
    reads.clear()
    assert not kv.verify()
    assert len(reads) == 1 and not np.any(reads[0] < kv.n_pages)


def test_nan_poisoned_page_trips(rs):
    kv = make_kv()
    kv.alloc_slot(0, 8)
    kv.write("k", 0, 0, fill(rs, 8))
    phys = kv.page_of(0, 0)
    kv.pools["k"] = kv.pools["k"].at[phys, 0, 0, 0].set(np.nan)
    assert ("k", phys) in kv.verify(), "NaN must not compare as clean"


def test_pool_exhaustion_evicts_lru_prefix_then_raises(rs):
    kv = make_kv(max_prefixes=4)
    # slot 0 publishes a prefix, then retires: the registry alone holds it
    prompt = list(range(PS + 1))
    kv.alloc_slot(0, PS + 1, prompt=prompt)
    kv.write("k", 0, 0, fill(rs, PS + 1))
    kv.write("v", 0, 0, fill(rs, PS + 1))
    kv.register_prefix(0, prompt)
    kv.free_slot(0)
    held = kv.n_free()
    # exhaust the free list: the LRU prefix page must be evicted to serve
    for s in range(SLOTS):
        kv.alloc_slot(s, MAX_LEN)
    assert kv.stats.prefix_evictions == 1 and not kv.prefixes
    assert kv.n_free() == 0 and held == SLOTS * (MAX_LEN // PS) - 1
    ok(kv)
    with pytest.raises(RuntimeError, match="exhausted"):
        kv._alloc()


def test_gather_matches_dense_layout(rs):
    kv = make_kv()
    start = kv.alloc_slot(1, 12)
    vals = fill(rs, 12)
    kv.write("k", 1, start, vals)
    dense = np.asarray(kv.gather("k"))
    assert dense.shape == (2, SLOTS, MAX_LEN, 4)
    np.testing.assert_array_equal(dense[:, 1, :12], vals)
    assert not dense[:, 0].any() and not dense[:, 2].any()
    assert not dense[:, 1, 12:].any()


# ---------------------------------------------------------------------------
# random-trace state machine (always-on, seeded)
# ---------------------------------------------------------------------------


def _drive_trace(ops, rs):
    """Interpret a trace of (op, r1, r2) triples against a live pool and a
    host-side model of slot occupancy, checking every invariant after
    every mutation."""
    kv = make_kv(extra_pages=2)
    slot_pos = {}            # slot -> (write head, prompt, need)
    for op, r1, r2 in ops:
        if op == "admit":
            free = [s for s in range(SLOTS) if s not in slot_pos]
            if not free:
                continue
            s = free[r1 % len(free)]
            plen = 2 + r2 % (MAX_LEN - 6)
            prompt = [101 + (r1 + i) % 7 for i in range(plen)]
            need = min(plen + 4, MAX_LEN)
            start = kv.alloc_slot(s, need, prompt=prompt)
            for key in kv.pools:
                kv.write(key, s, start,
                         fill(rs, plen - start))
            kv.register_prefix(s, prompt)
            slot_pos[s] = plen
        elif op == "decode":
            if not slot_pos:
                continue
            s = sorted(slot_pos)[r1 % len(slot_pos)]
            if slot_pos[s] >= MAX_LEN:
                continue
            kv.begin_mutation()
            for key in kv.pools:
                kv.write(key, s, slot_pos[s], fill(rs, 1))
            # one page checksum per leaf per token — the scrub-unit fix
            assert len(kv.last_rearmed) == len(kv.pools)
            assert len({k for k, _ in kv.last_rearmed}) == len(kv.pools)
            slot_pos[s] += 1
        elif op == "free":
            if not slot_pos:
                continue
            s = sorted(slot_pos)[r1 % len(slot_pos)]
            kv.free_slot(s)
            del slot_pos[s]
        elif op == "corrupt_scrub":
            live = kv.live_pages()
            if not live:
                continue
            phys = live[r1 % len(live)]
            key = sorted(kv.pools)[r2 % len(kv.pools)]
            kv.corrupt_page(key, phys, bit=30)
            assert (key, phys) in [tuple(t) for t in kv.scrub()]
        kv.check_invariants()
        assert kv.checksums_consistent(), f"after op {op}"
    return kv


OPS = ("admit", "decode", "decode", "decode", "free", "corrupt_scrub")


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_random_trace_invariants(seed):
    rs = np.random.RandomState(seed)
    ops = [(OPS[rs.randint(len(OPS))], int(rs.randint(1 << 30)),
            int(rs.randint(1 << 30))) for _ in range(60)]
    kv = _drive_trace(ops, rs)
    # drain: conservation must return every page to the free list except
    # the ones the prefix registry intentionally holds
    for s in range(SLOTS):
        kv.free_slot(s)
    registry_held = len({p for ps in kv.prefixes.values() for p in ps})
    assert kv.n_free() == kv.n_pages - 1 - registry_held
    kv.check_invariants()


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(OPS),
                              st.integers(0, 1 << 30),
                              st.integers(0, 1 << 30)),
                    min_size=1, max_size=40),
           st.integers(0, 2 ** 31 - 1))
    def test_hypothesis_trace_invariants(ops, seed):
        _drive_trace(ops, np.random.RandomState(seed))


# ---------------------------------------------------------------------------
# batched writes against the per-segment eager path
# ---------------------------------------------------------------------------


class EagerKV(PagedKVCache):
    """The reference: every (leaf, page segment) written by its own eager
    scatter with two blocking reads, every freed page zeroed on its own —
    the write path before writes were batched into one program."""

    def write_batch(self, srcs, writes, *, kind, pad_to=0):
        ps = self.page_size
        for key, src in srcs.items():
            for slot, start, n, src_slot, src_start in writes:
                pos = start
                while pos < start + n:
                    logical, off = pos // ps, pos % ps
                    seg_n = min(ps - off, start + n - pos)
                    phys = self._writable(slot, logical)
                    a = src_start + pos - start
                    pool = self.pools[key]
                    seg = src[:, src_slot, a:a + seg_n]
                    seg = seg.reshape(seg.shape[:2] + pool.shape[3:]).astype(
                        pool.dtype)
                    old64 = np.asarray(pool[phys, :, off:off + seg_n],
                                       np.float64)
                    self.pools[key] = pool.at[phys, :, off:off + seg_n].set(
                        seg)
                    seg64 = np.asarray(seg, np.float64)
                    self.page_fp[key][phys] += float(seg64.sum()
                                                     - old64.sum())
                    self.esum[key][:, off:off + seg_n] += seg64 - old64
                    self.last_rearmed.append((key, phys))
                    self.stats.checksum_rearms += 1
                    pos += seg_n

    def _release(self, pages):
        for phys in pages:
            if phys == 0:
                continue
            self.refcount[phys] -= 1
            if self.refcount[phys] > 0:
                continue
            for key, pool in self.pools.items():
                page64 = np.asarray(pool[phys], np.float64)
                if np.any(page64):
                    self.esum[key] -= page64
                    self.pools[key] = pool.at[phys].set(0)
                self.page_fp[key][phys] = 0.0
            self.free.append(phys)
            self.stats.frees += 1


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.itemsize == 2 else np.uint32)


def _assert_same(batched, eager):
    for key in batched.pools:
        np.testing.assert_array_equal(_bits(batched.pools[key]),
                                      _bits(eager.pools[key]), err_msg=key)
        for name in ("page_fp", "esum"):
            got, want = getattr(batched, name)[key], getattr(eager, name)[key]
            scale = float(np.max(np.abs(want), initial=0.0))
            np.testing.assert_allclose(got, want, rtol=1e-12,
                                       atol=1e-12 * scale, err_msg=name)
    assert batched.last_rearmed == eager.last_rearmed
    for f in ("checksum_rearms", "allocs", "frees", "cow_copies",
              "prefix_evictions"):
        assert getattr(batched.stats, f) == getattr(eager.stats, f), f
    np.testing.assert_array_equal(batched.table, eager.table)
    np.testing.assert_array_equal(batched.refcount, eager.refcount)
    assert batched.free == eager.free


EQ_OPS = ("admit", "admit", "decode", "decode", "decode", "cow", "free",
          "corrupt_free")


def _payload(rs, shape, dtype):
    if dtype == np.float32:
        return rs.randint(1, 8, size=shape).astype(np.float32)
    return rs.standard_normal(shape).astype(np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_writes_match_eager_segments(seed, dtype):
    """A random admit / chunked prefill / decode / copy-on-write / free /
    prefix-eviction trace through both paths: pools bit-identical,
    checksums equal to 1e-12, the same re-arm ledger and page table; the
    chunked prefills start mid-page, cross page boundaries and end on
    partial pages."""
    dt = np.float32 if dtype == "float32" else jnp.bfloat16
    shape = (2, SLOTS, MAX_LEN, 4)
    kvs = [cls({"k": (shape, dt), "v": (shape, dt)}, slots=SLOTS,
               max_len=MAX_LEN, page_size=PS, max_prefixes=2)
           for cls in (PagedKVCache, EagerKV)]
    rs = np.random.RandomState(seed)
    heads = {}
    seen = set()

    def both(fn):
        for kv in kvs:
            kv.begin_mutation()
            fn(kv)

    def write(srcs, writes, pad_to):
        srcs = {k: jnp.asarray(v, dt) for k, v in srcs.items()}
        both(lambda kv: kv.write_batch(srcs, writes, kind="prefill",
                                       pad_to=pad_to))

    for _ in range(80):
        op = EQ_OPS[rs.randint(len(EQ_OPS))]
        live = sorted(heads)
        if op == "admit" and len(live) < SLOTS:
            s = min(set(range(SLOTS)) - set(live))
            plen = 2 + rs.randint(MAX_LEN - 6)
            prompt = [101 + (rs.randint(2) + i) % 5 for i in range(plen)]
            need = min(plen + 4, MAX_LEN)
            start = [kv.alloc_slot(s, need, prompt=prompt) for kv in kvs][0]
            src = {k: _payload(rs, (2, 1, MAX_LEN, 4), dt) for k in "kv"}
            while start < plen:
                n = min(1 + rs.randint(2 * PS), plen - start)
                seen.add((start % PS != 0, start // PS != (start + n - 1)
                          // PS, (start + n) % PS != 0))
                write(src, [(s, start, n, 0, start)], pad_to=2 * PS)
                start += n
            both(lambda kv: kv.register_prefix(s, prompt))
            heads[s] = plen
        elif op == "decode" and live:
            src = {k: _payload(rs, (2, SLOTS, MAX_LEN, 4), dt) for k in "kv"}
            writes = [(s, heads[s], 1, s, heads[s]) for s in live
                      if heads[s] < MAX_LEN]
            srcs = {k: jnp.asarray(v, dt) for k, v in src.items()}
            both(lambda kv: kv.write_batch(srcs, writes, kind="decode",
                                           pad_to=SLOTS))
            for s, *_ in writes:
                heads[s] += 1
        elif op == "cow":
            shared = [(s, i) for s in live for i in range(kvs[0].pages_per_slot)
                      if kvs[0].refcount[kvs[0].table[s, i]] > 1]
            if not shared or not kvs[0].n_free():
                continue
            s, i = shared[rs.randint(len(shared))]
            cows = kvs[0].stats.cow_copies
            n = 1 + rs.randint(PS)
            src = {k: _payload(rs, (2, 1, MAX_LEN, 4), dt) for k in "kv"}
            write(src, [(s, i * PS, n, 0, 0)], pad_to=PS)
            assert kvs[0].stats.cow_copies == cows + 1
        elif op in ("free", "corrupt_free") and live:
            s = live[rs.randint(len(live))]
            own = [int(p) for p in kvs[0].table[s]
                   if p and kvs[0].refcount[p] == 1]
            if op == "corrupt_free" and own:
                # a live page corrupted and then freed: zero-at-free takes
                # its contents out of esum as they stand
                phys = own[rs.randint(len(own))]
                seen.add("corrupt_free")
                orig = float(np.asarray(kvs[0].pools["k"][phys, 0, 1, 2]))
                for kv in kvs:
                    kv.pools["k"] = kv.pools["k"].at[phys, 0, 1, 2].add(4)
                delta = float(np.asarray(
                    kvs[0].pools["k"][phys, 0, 1, 2])) - orig
                both(lambda kv: kv.free_slot(s))
                _assert_same(*kvs)
                for kv in kvs:
                    kv.check_invariants()
                    assert not kv.verify()     # the page is zero again
                    gap = (np.asarray(kv.pools["k"], np.float64).sum(axis=0)
                           - kv.esum["k"])
                    want = np.zeros_like(gap)
                    want[0, 1, 2] = delta
                    np.testing.assert_allclose(gap, want, atol=1e-9)
                    kv.arm_all()
            else:
                both(lambda kv: kv.free_slot(s))
            del heads[s]
        else:
            continue
        _assert_same(*kvs)
        for kv in kvs:
            kv.check_invariants()
            assert kv.checksums_consistent(), op
    assert kvs[0].stats.prefix_evictions and kvs[0].stats.cow_copies
    assert (True, True, True) in seen and "corrupt_free" in seen


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_checksum_update_is_the_float64_formula_in_kept_buffers(dtype):
    """The float64 update of a write (``d = new - old``, page sums by
    bincount, ``esum[:, o] += d[off == o].sum(axis=0)``) comes out bit for
    bit on values that are not integers, from buffers that later writes
    of as many tokens or fewer reuse."""
    dt = np.float32 if dtype == "float32" else jnp.bfloat16
    rng = np.random.default_rng(7)
    kv = PagedKVCache({"k": ((2, SLOTS, MAX_LEN, 4), dt)}, slots=SLOTS,
                      max_len=MAX_LEN, page_size=PS)
    fp = np.zeros(kv.n_pages)
    esum = np.zeros((2, PS, 4))

    def write(src, writes, pad_to):
        vals = np.asarray(jnp.asarray(src, dt)).astype(np.float64)
        kv.write_batch({"k": jnp.asarray(src, dt)}, writes, kind="prefill",
                       pad_to=pad_to)
        for slot, start, n, s_slot, s_start in writes:
            pos = np.arange(start, start + n)
            d = np.moveaxis(vals[:, s_slot, s_start:s_start + n], 1, 0)
            phys = kv.table[slot, pos // PS]
            fp[:] += np.bincount(phys, d.reshape(n, -1).sum(axis=1),
                                 minlength=kv.n_pages)
            for o in np.unique(pos % PS):
                esum[:, o] += d[pos % PS == o].sum(axis=0)

    scale = 10.0 ** rng.uniform(-3, 3, (1, 1, 27, 1))
    write(rng.standard_normal((2, 1, 27, 4)) * scale, [(0, 3, 27, 0, 0)], 32)
    bufs = kv._bufs[(2, 4)]
    # one token a slot from a source of every slot, as the decode writes
    write(rng.standard_normal((2, SLOTS, 1, 4)),
          [(s, 30, 1, s, 0) for s in range(SLOTS)], SLOTS)
    assert all(a is b for a, b in zip(kv._bufs[(2, 4)], bufs))
    np.testing.assert_array_equal(kv.page_fp["k"], fp)
    np.testing.assert_array_equal(kv.esum["k"], esum)


def test_write_programs_trace_once_per_padded_shape(rs):
    """Writes of every length within one prefill bucket, decode batches of
    1..slots live slots and frees of any page count reuse one compiled
    program each; one batch is counted per call."""
    from repro.serve.paged_kv import serve_kv_write, serve_kv_zero
    shape = (2, SLOTS, MAX_LEN, 5)      # a tail no other test uses
    kv = PagedKVCache({"k": (shape, np.float32), "v": (shape, np.float32)},
                      slots=SLOTS, max_len=MAX_LEN, page_size=PS)
    writes0, zeros0 = serve_kv_write._cache_size(), serve_kv_zero._cache_size()
    src1 = {k: jnp.asarray(rs.randn(2, 1, MAX_LEN, 5), jnp.float32)
            for k in "kv"}
    srcB = {k: jnp.asarray(rs.randn(2, SLOTS, MAX_LEN, 5), jnp.float32)
            for k in "kv"}
    frees = 0
    for n in range(PS + 1, 2 * PS + 1):            # one bucket: 2 * PS
        for s in range(SLOTS):
            kv.alloc_slot(s, n + SLOTS)
            kv.write_batch(src1, [(s, 0, n, 0, 0)], kind="prefill",
                           pad_to=2 * PS)
        for live in range(1, SLOTS + 1):
            kv.write_batch(srcB, [(s, n + live - 1, 1, s, 0)
                                  for s in range(live)],
                           kind="decode", pad_to=SLOTS)
        for s in range(SLOTS):
            kv.free_slot(s)
            frees += 1
        ok(kv)
    assert serve_kv_write._cache_size() - writes0 == 2
    assert serve_kv_zero._cache_size() - zeros0 == 1
    assert kv.stats.write_batches == {
        "prefill": PS * SLOTS, "decode": PS * SLOTS, "free": frees}


def test_engine_counts_one_write_batch_per_step_prefill_and_retirement():
    """The paged engine runs one write program per decode step, one per
    prefill or chunk and one per retiring request, each with one read."""
    import jax
    from repro import obs
    from repro.configs.base import smoke_config
    from repro.models import transformer as tf
    from repro.serve.engine import PagedServeEngine, Request

    cfg = smoke_config("qwen2-0.5b")
    eng = PagedServeEngine(cfg, tf.init_params(jax.random.PRNGKey(0), cfg),
                           slots=2, max_len=64, page_size=8,
                           chunk_prefill=8, prefix_cache=False)
    obs.reset_all()
    prompts = (5, 20, 9, 30)
    for i, n in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=[(3 * i + j) % 50 + 1
                                          for j in range(n)],
                           max_new_tokens=4))
    assert len(eng.run()) == len(prompts)
    writes = [e for e in obs.events() if e.name == "serve/kv_write"]
    chunks = sum(-(-n // 8) for n in prompts)
    assert len(writes) == chunks
    assert eng.kv.stats.write_batches == {
        "decode": eng.stats.decode_steps, "prefill": chunks,
        "free": len(prompts)}
    c = obs.counter("repro_kv_write_batches_total")
    assert {k: c.value(kind=k) for k in ("decode", "prefill", "free")} == \
        eng.kv.stats.write_batches
    obs.reset_all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_verify_page_sums_equal_float64_recompute(dtype):
    """The scrub's page sums (one read, values widened to float32, float64
    sums on threads) equal a float64 recompute of every page."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.serve.paged_kv import _page_sums
    dt = np.float32 if dtype == "float32" else jnp.bfloat16
    pages = np.asarray(jnp.asarray(
        np.random.RandomState(3).standard_normal((37, PS, 2, 4)), dt))
    with ThreadPoolExecutor(3) as ex:
        got = _page_sums(pages, ex)
    want = pages.astype(np.float64).reshape(37, -1).sum(axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)
