"""The paged engine's decode reads the page pools in place.

Its logits equal, bit for bit, the dense decode over the view that
`PagedKVCache.gather` builds (the oracle): across slots of different
lengths, a shared prefix page that a decode write copies on write, and a
slot that retires and is re-admitted; clean and with a drilled logits
reduction.  The engine keeps no dense copy of a paged leaf, the decode
program returns only the new K/V rows, and a flipped page element is
located and repaired by the scrub with the next decode unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import smoke_config
from repro.models import transformer as tf
from repro.serve.engine import PagedServeEngine, Request
from repro.serve.paged_kv import PagedKVCache

SLOTS, MAX_LEN, PS = 3, 64, 8


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("qwen2-0.5b")
    return cfg, tf.init_params(jax.random.PRNGKey(0), cfg)


def _engine(setup, **kw):
    cfg, params = setup
    return PagedServeEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                            page_size=PS, prefix_cache=True, **kw)


def _dense_view(eng, state, pos):
    """A dense contiguous cache: every paged leaf gathered from the pools
    through the table, index = positions."""
    def leaf(path, x):
        key = jax.tree_util.keystr(path)
        if key in eng.kv.pools:
            return eng.kv.gather(key)
        if getattr(path[-1], "key", None) == "index":
            return jnp.broadcast_to(pos, (x.shape[0],) + pos.shape)
        return state["dense"][key]
    return jax.tree_util.tree_map_with_path(leaf, eng._cache_struct)


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _record(eng, oracle):
    """Wrap both decode programs: each call's logits are checked against
    ``oracle(tokens, pos, dense_cache, inject)`` bit for bit."""
    calls = []

    def wrap(prog, drill):
        def run(params, tokens, pos, state, *inject):
            out = prog(params, tokens, pos, state, *inject)
            want = oracle(tokens, pos, _dense_view(eng, state, pos),
                          tuple(inject) if drill else None)
            np.testing.assert_array_equal(_bits(out[0]), _bits(want))
            calls.append(np.asarray(pos).tolist())
            return out
        return run

    eng._decode = wrap(eng._decode, False)
    eng._decode_drill = wrap(eng._decode_drill, True)
    return calls


def _drive(eng):
    """Admit four requests into three slots (one retires early and its
    slot takes the fourth), and make one decode write land in a page
    that a registered prefix shares, so the write copies it first."""
    lens = (5, 19, 12, 9)
    news = (30, 4, 25, 20)
    for i, (n, m) in enumerate(zip(lens, news)):
        eng.submit(Request(rid=i, prompt=[(7 * i + j) % 97 + 1
                                          for j in range(n)],
                           max_new_tokens=m))
    shared = []

    def share_next_page(e, step):
        if shared:
            return
        pos = np.asarray(e.pos)
        for s, r in enumerate(e.active):
            if r is not None and pos[s] % PS == 0 and pos[s] > 0:
                # register the slot's pages up to and including the one
                # its next decode writes: that page is now shared
                e.kv.register_prefix(s, [10 ** 6 + j
                                         for j in range(pos[s] + PS + 1)])
                shared.append((s, e.kv.page_of(s, int(pos[s]))))
                return

    done = eng.run(on_step=share_next_page)
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    return shared


@pytest.mark.parametrize("mode", ["plain", "drilled"])
def test_paged_decode_logits_equal_dense_decode_over_gather(setup, mode):
    from repro.chaos.faults import SDCInjector, SDCPlan

    cfg, params = setup
    if mode == "plain":
        eng = _engine(setup)
        step = jax.jit(lambda t, p, c: tf.decode_step(
            params, t, p, c, cfg, abft=eng.abft)[0])
        oracle = lambda t, p, c, inject: step(t, p, c)    # noqa: E731
    else:
        eng = _engine(setup, abft_reduce="correct",
                      sdc=SDCInjector(SDCPlan(((6, 0, 1e4), (17, 0, 1e4)))))
        # the dense decode with the same verified unembed
        def dense(t, p, c, inj):
            hidden, _ = tf.decode_step(params, t, p, c, cfg, abft=eng.abft,
                                       return_hidden=True)
            return eng._verified_unembed(params, hidden, inj)[0]
        core = jax.jit(dense)
        oracle = lambda t, p, c, inject: core(t, p, c, inject)  # noqa
    calls = _record(eng, oracle)
    cows = eng.kv.stats.cow_copies
    shared = _drive(eng)
    assert shared and eng.kv.stats.cow_copies > cows, \
        "no decode write copied a shared page"
    assert len(calls) == eng.stats.decode_steps > 20
    assert any(len(set(p)) == SLOTS for p in calls), \
        "slots should decode at different lengths"
    assert len(eng.stats.ttft_s) == 4       # 4 requests through 3 slots
    if mode == "drilled":
        assert len(eng.stats.events) == 2
        assert all(e.detected and e.corrected for e in eng.stats.events)


def test_engine_keeps_no_dense_cache_and_decode_returns_rows(setup):
    eng = _engine(setup, abft_reduce="correct", scrub_every=4)
    eng.warm(prompt_len=8, decode_steps=3)
    paged = set(eng.kv.pools)
    assert paged and not (paged & set(eng.cache))

    def dense_shaped(x):
        return len(x.shape) >= 3 and tuple(x.shape[1:3]) == (SLOTS, MAX_LEN)
    held = [x for v in list(vars(eng).values()) + list(vars(eng.kv).values())
            for x in jax.tree.leaves(v)
            if isinstance(x, jax.Array) and dense_shaped(x)]
    assert not held, [x.shape for x in held]

    low = eng._decode.lower(eng.params, *eng._place())
    _, new, _, _ = low.out_info
    for key, pool in eng.kv.pools.items():
        _, reps, _, feat = pool.shape
        assert new[key].shape[:3] == (reps, SLOTS, 1)
        assert int(np.prod(new[key].shape)) <= SLOTS * reps * feat
    for x in jax.tree.leaves(low.out_info):
        assert not dense_shaped(x), x.shape


def test_scrub_repairs_a_flipped_page_and_the_decode_is_unchanged(setup):
    eng = _engine(setup, scrub_every=0)
    for i, n in enumerate((13, 21)):
        eng.submit(Request(rid=i, prompt=[(5 * i + j) % 61 + 1
                                          for j in range(n)],
                           max_new_tokens=30))
    eng.run(max_steps=6)
    assert sum(r is not None for r in eng.active) == 2
    clean = eng._decode(eng.params, *eng._place())[0]
    key = sorted(eng.kv.pools)[1]
    phys = eng.kv.page_of(1, PS)
    eng.kv.corrupt_page(key, phys, index=37, bit=30)
    assert not np.array_equal(
        _bits(eng._decode(eng.params, *eng._place())[0]), _bits(clean))
    assert eng.kv.scrub() == [(key, phys)]
    np.testing.assert_array_equal(
        _bits(eng._decode(eng.params, *eng._place())[0]), _bits(clean))
    eng.kv.check_invariants()
    assert eng.kv.checksums_consistent()


def test_arm_all_fingerprints_equal_page_major_sums(rs):
    """Per-page float64 sums do not depend on the order of the axes within
    a page: the pools (layers major within the page) arm the fingerprints
    and erasure rows that the previous order, ``[n_pages, page_size,
    repeats, features]``, gives for the same contents."""
    shape = (3, SLOTS, MAX_LEN, 2, 4)
    kv = PagedKVCache({"k": (shape, jnp.bfloat16), "v": (shape, jnp.bfloat16)},
                      slots=SLOTS, max_len=MAX_LEN, page_size=PS)
    for s, n in enumerate((20, 64, 7)):
        kv.alloc_slot(s, n)
        src = {k: jnp.asarray(rs.standard_normal((3, 1, MAX_LEN, 2, 4)),
                              jnp.bfloat16) for k in "kv"}
        kv.write_batch(src, [(s, 0, n, 0, 0)], kind="prefill")
    armed = {k: (kv.page_fp[k].copy(), kv.esum[k].copy()) for k in kv.pools}
    kv.arm_all()
    for key, pool in kv.pools.items():
        before = np.swapaxes(np.asarray(pool, np.float64), 1, 2)
        np.testing.assert_array_equal(
            kv.page_fp[key], before.reshape(kv.n_pages, -1).sum(axis=1))
        np.testing.assert_array_equal(
            np.swapaxes(kv.esum[key], 0, 1), before.sum(axis=0))
        fp, esum = armed[key]
        np.testing.assert_allclose(kv.page_fp[key], fp, rtol=1e-12,
                                   atol=1e-9)
        np.testing.assert_allclose(kv.esum[key], esum, rtol=1e-12,
                                   atol=1e-9)
    assert not kv.verify()
