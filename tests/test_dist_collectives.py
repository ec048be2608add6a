"""dist.collectives: int8 error-feedback all-reduce and the Huang-Abraham
checksum-verified psum (single-bit-flip detect/correct through the wire).

Collectives are exercised with jax.vmap(axis_name=...) — identical manual-
collective semantics to shard_map, one CPU device (the conftest invariant).
The sharded end-to-end path runs in test_distributed's subprocesses.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.collectives import (abft_psum, abft_psum_tree, ef_psum_tree,
                                    ef_wire_bytes)
from repro.dist.sharding import make_mesh
from repro.ft.failures import SDCInjector, SDCPlan, flip_bit

NDP = 4


def _per_shard_tree(rs, ndp=NDP):
    return {
        "w": jnp.asarray(rs.standard_normal((ndp, 8, 16)), jnp.float32),
        "b": jnp.asarray(rs.standard_normal((ndp, 32)), jnp.float32),
    }


# ---------------------------------------------------------------------------
# ef_psum_tree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire", ["psum", "int8"])
def test_ef_psum_matches_pmean_within_int8_tolerance(rs, wire):
    grads = _per_shard_tree(rs)
    res = jax.tree.map(jnp.zeros_like, grads)

    def body(g, r):
        return ef_psum_tree(g, r, ("dp",), NDP, wire=wire)

    out, new_res = jax.vmap(body, axis_name="dp")(grads, res)
    for k in grads:
        ref = np.mean(np.asarray(grads[k]), axis=0)
        got = np.asarray(out[k][0])
        # every shard agrees on the reduced value
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.broadcast_to(got, out[k].shape))
        # int8 quantization: |err| <= sum of per-shard scale/2, i.e. ~1% here
        scale = np.abs(np.asarray(grads[k])).max(
            axis=tuple(range(1, grads[k].ndim))).mean() / 127.0
        assert np.max(np.abs(got - ref)) <= scale, k


@pytest.mark.parametrize("wire", ["psum", "int8"])
def test_ef_residual_feedback_converges(rs, wire):
    """Repeatedly reducing the SAME grads: the running mean of EF outputs
    must converge to the exact mean (the residual re-injects what int8
    dropped), beating the one-shot quantization error."""
    grads = _per_shard_tree(rs)
    res = jax.tree.map(jnp.zeros_like, grads)
    body = jax.vmap(lambda g, r: ef_psum_tree(g, r, ("dp",), NDP, wire=wire),
                    axis_name="dp")
    ref = np.mean(np.asarray(grads["w"]), axis=0)
    outs = []
    first_err = None
    for t in range(20):
        out, res = body(grads, res)
        outs.append(np.asarray(out["w"][0]))
        if first_err is None:
            first_err = np.max(np.abs(outs[0] - ref))
    running = np.mean(outs, axis=0)
    assert np.max(np.abs(running - ref)) < 0.25 * first_err
    # residuals stay bounded (no drift)
    assert float(jnp.max(jnp.abs(res["w"]))) < 1.0


def test_ef_wire_bytes_shows_the_4x():
    """The roofline-table accounting (launch.dryrun wires this into train
    cells): the int8 exchange moves ~4x fewer gradient bytes per device
    than the fp32 ring all-reduce, at any DP extent."""
    params = {"w": jnp.zeros((512, 512)), "b": jnp.zeros((512,))}
    for ndp in (2, 8, 256):
        acct = ef_wire_bytes(params, ndp)
        frac = (ndp - 1) / ndp
        assert acct["grad_elems"] == 512 * 512 + 512
        assert acct["f32_ring_bytes_per_device"] == \
            2 * 4 * acct["grad_elems"] * frac
        assert 3.9 < acct["saving"] <= 4.0, acct
    # degenerate single-device "reduction": nothing on the wire
    assert ef_wire_bytes(params, 1)["f32_ring_bytes_per_device"] == 0.0


# ---------------------------------------------------------------------------
# abft_psum
# ---------------------------------------------------------------------------


def _vpsum(x, **kw):
    return jax.vmap(lambda v: abft_psum(v, ("dp",), **kw), axis_name="dp")(x)


def test_abft_psum_clean_matches_psum(rs):
    x = jnp.asarray(rs.standard_normal((NDP, 6, 7)), jnp.float32)
    y, ok = _vpsum(x, mode="verify")
    assert bool(ok.all())
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(x).sum(0), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shard", [0, 2, NDP - 1])
def test_abft_psum_detects_injected_fault(rs, shard):
    x = jnp.asarray(rs.standard_normal((NDP, 6, 7)), jnp.float32)
    y, ok = _vpsum(x, mode="verify", inject=(shard, 37.5))
    assert not bool(ok.any())                      # every shard sees it
    # and without correction the sum really is wrong
    assert np.max(np.abs(np.asarray(y[0]) - np.asarray(x).sum(0))) > 1.0


def test_abft_psum_corrects_single_bit_flip(rs):
    """The acceptance-criteria case: one bit-flip-sized corruption injected
    into one shard's contribution is located and subtracted — the corrected
    reduction equals the clean psum."""
    x = jnp.asarray(rs.standard_normal((NDP, 6, 7)), jnp.float32)
    # delta the size a flipped exponent bit produces on an O(1) value
    clean = np.asarray(x).sum(0)
    flipped = flip_bit(jnp.asarray(1.0, jnp.float32)[None], 0, bit=29)
    delta = float(flipped[0] - 1.0)
    y, ok = _vpsum(x, mode="correct", inject=(2, delta))
    assert not bool(ok.any())                      # fault was seen...
    np.testing.assert_allclose(np.asarray(y[0]), clean,
                               rtol=1e-4, atol=1e-4)  # ...and repaired
    # all shards agree on the repaired value
    np.testing.assert_allclose(np.asarray(y), np.broadcast_to(clean, y.shape),
                               rtol=1e-4, atol=1e-4)


def test_abft_psum_with_info_locates_the_injected_element(rs):
    """`with_info=True` exposes the located (row, col, flat index) of the
    corrupted element plus the estimated magnitude — the telemetry the
    serving engine's drill records into EngineStats."""
    x = jnp.asarray(rs.standard_normal((NDP, 6, 7)), jnp.float32)
    y, ok, info = _vpsum(x, mode="correct", inject=(1, 1e3), with_info=True)
    assert not bool(ok.any())
    n = 6 * 7
    cdim = 7  # ceil(sqrt(42))
    assert int(info["index"][0]) == n // 2        # inject site is flat n//2
    assert int(info["row"][0]) == (n // 2) // cdim
    assert int(info["col"][0]) == (n // 2) % cdim
    assert bool(info["corrected"].all())
    np.testing.assert_allclose(float(info["magnitude"][0]), 1e3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x).sum(0),
                               rtol=1e-4, atol=1e-4)
    # clean run: nothing located, nothing corrected
    y2, ok2, info2 = _vpsum(x, mode="correct", with_info=True)
    assert bool(ok2.all())
    assert int(info2["index"][0]) == -1
    assert not bool(info2["corrected"].any())


def test_abft_psum_inject_local_matches_inject(rs):
    """`inject_local` (caller-side shard selection, used where axis_index
    cannot lower — see serve.engine) must corrupt/correct exactly like the
    equivalent `inject=(shard, delta)`."""
    x = jnp.asarray(rs.standard_normal((NDP, 6, 7)), jnp.float32)
    deltas = jnp.zeros((NDP,), jnp.float32).at[2].set(500.0)
    y_loc, ok_loc = jax.vmap(
        lambda v, d: abft_psum(v, ("dp",), mode="correct", inject_local=d),
        axis_name="dp")(x, deltas)
    y_ref, ok_ref = _vpsum(x, mode="correct", inject=(2, 500.0))
    assert not bool(ok_loc.any()) and not bool(ok_ref.any())
    np.testing.assert_array_equal(np.asarray(y_loc), np.asarray(y_ref))
    with pytest.raises(ValueError):
        abft_psum(jnp.zeros((8,)), ("dp",), inject=(0, 1.0),
                  inject_local=jnp.float32(1.0))


def test_abft_psum_tree_means_and_flags(rs):
    g = _per_shard_tree(rs)
    body = jax.vmap(functools.partial(
        abft_psum_tree, dp_axes=("dp",), ndp=NDP, mode="correct",
        inject=(1, 100.0)), axis_name="dp")
    out, ok = body(g)
    assert not bool(ok.any())
    for k in g:
        np.testing.assert_allclose(np.asarray(out[k][0]),
                                   np.mean(np.asarray(g[k]), axis=0),
                                   rtol=1e-4, atol=1e-4)


def test_abft_psum_tree_two_events_two_reductions(rs):
    """Multi-collective fault model: two injected events land in two
    DIFFERENT protected reductions of the same step — each leaf's checksums
    see at most one fault, so BOTH are located and corrected."""
    g = _per_shard_tree(rs)           # two leaves ("w", "b"), both eligible
    body = jax.vmap(functools.partial(
        abft_psum_tree, dp_axes=("dp",), ndp=NDP, mode="correct",
        inject=((1, 1e3), (3, -2e3))), axis_name="dp")
    out, ok = body(g)
    assert not bool(ok.any())                       # faults were seen ...
    for k in g:                                     # ... in BOTH reductions
        np.testing.assert_allclose(np.asarray(out[k][0]),
                                   np.mean(np.asarray(g[k]), axis=0),
                                   rtol=1e-4, atol=1e-4)
    # verify-only: the two corruptions remain in their respective leaves
    body_v = jax.vmap(functools.partial(
        abft_psum_tree, dp_axes=("dp",), ndp=NDP, mode="verify",
        inject=((1, 1e3), (3, -2e3))), axis_name="dp")
    out_v, ok_v = body_v(g)
    assert not bool(ok_v.any())
    for k in g:
        assert np.max(np.abs(np.asarray(out_v[k][0])
                             - np.mean(np.asarray(g[k]), axis=0))) > 1.0, k


def test_abft_psum_tree_too_many_events_raises(rs):
    g = {"w": jnp.asarray(rs.standard_normal((NDP, 8, 16)), jnp.float32)}
    with pytest.raises(ValueError):
        jax.vmap(functools.partial(
            abft_psum_tree, dp_axes=("dp",), ndp=NDP, mode="correct",
            inject=((0, 1.0), (1, 2.0))), axis_name="dp")(g)


def test_sdc_injector_check_all_fires_same_step_events():
    """A plan may carry several events for ONE step; `check_all` delivers
    them together (the compiled drill step injects them into different
    reductions), `check` one at a time (legacy single-fault consumers)."""
    plan = SDCPlan(((2, 0, 1e3), (2, 1, -2e3), (4, 2, 5.0)))
    assert plan.events_at(2) == ((0, 1e3), (1, -2e3))
    inj = SDCInjector(plan)
    assert inj.check_all(1) == ()
    assert inj.check_all(2) == ((0, 1e3), (1, -2e3))
    assert inj.check_all(2) == ()                  # fires once
    assert inj.check(4) == (2, 5.0)
    inj2 = SDCInjector(plan)
    assert inj2.check(2) == (0, 1e3)
    assert inj2.check(2) == (1, -2e3)
    assert inj2.check(2) is None


def test_ft_runtime_delivers_multi_event_payload():
    from repro.ft.runtime import FTPolicy, FTRuntime

    rt = FTRuntime(4, FTPolicy(diskless_every=100),
                   sdc_injector=SDCInjector(
                       SDCPlan(((1, 0, 1e3), (1, 2, -4e3)))))
    seen = []
    for i in range(3):
        rt.step(i, {"x": jnp.zeros(())}, lambda s: s,
                run_step_sdc=lambda s, ev: (seen.append(ev), s)[1])
    assert seen == [((0, 1e3), (2, -4e3))]         # both payloads, one step
    assert rt.recoveries["sdc"] == 1


# ---------------------------------------------------------------------------
# end-to-end: the opt-in train-step path + ft.runtime SDC drill
# ---------------------------------------------------------------------------


def _train_pair():
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("qwen2-0.5b")
    shape = ShapeConfig("t", 32, 4, "train")
    dc = DataConfig(cfg.vocab_size, 32, 4)

    def build(**kw):
        opts = StepOptions(remat=False, defer_grad_reduce=True, **kw)
        with jax.set_mesh(mesh):
            fn, in_sh, out_sh = build_train_step(
                cfg, mesh, shape, AdamWConfig(lr=1e-3, total_steps=10), opts)
            jit_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            state = jax.device_put(
                init_state(jax.random.PRNGKey(0), cfg, opts, mesh), in_sh[0])
            batch = jax.device_put(
                {k: jnp.asarray(v) for k, v in
                 synthetic_batch(dc, 0).items()}, in_sh[1])
        return jit_fn, state, batch

    return build


def test_sdc_plan_random_one_event_per_step():
    plan = SDCPlan.random(8, 10, p=4, seed=3)
    steps = [s for (s, _, _) in plan.events]
    assert len(steps) == len(set(steps))
    assert all(1 <= s < 10 for s in steps)


def test_abft_reduce_option_conflicts_raise():
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.train.step import StepOptions, build_train_step

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("qwen2-0.5b")
    shape = ShapeConfig("t", 32, 4, "train")
    for bad in (StepOptions(abft_reduce="correct"),               # no defer
                StepOptions(defer_grad_reduce=True, zero2=True,
                            abft_reduce="correct"),
                StepOptions(defer_grad_reduce=True,
                            grad_compression="int8_ef",
                            abft_reduce="verify"),
                StepOptions(defer_grad_reduce=True,
                            sdc_inject=(0, 1e3))):                # no abft
        with pytest.raises(ValueError):
            build_train_step(cfg, mesh, shape, opts=bad)


@pytest.mark.slow
def test_train_step_abft_reduce_corrects_sdc():
    build = _train_pair()
    clean_fn, state, batch = build(abft_reduce="correct")
    sdc_fn, _, _ = build(abft_reduce="correct", sdc_inject=(0, 1e3))
    s_clean, m_clean = clean_fn(state, batch)
    s_sdc, m_sdc = sdc_fn(state, batch)
    assert float(m_clean["abft_ok"]) == 1.0
    assert float(m_sdc["abft_ok"]) == 0.0          # detected ...
    for a, b in zip(jax.tree.leaves(s_clean["params"]),
                    jax.tree.leaves(s_sdc["params"])):
        np.testing.assert_allclose(                 # ... and corrected
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-4)


@pytest.mark.slow
def test_train_step_two_bit_flips_two_reductions():
    """Bit flips in TWO different gradient reductions of one compiled step:
    both are detected (abft_ok drops) and both corrected — the update
    matches the clean step."""
    build = _train_pair()
    clean_fn, state, batch = build(abft_reduce="correct")
    flipped = flip_bit(jnp.asarray(1.0, jnp.float32)[None], 0, bit=29)
    delta = float(flipped[0] - 1.0)
    sdc_fn, _, _ = build(abft_reduce="correct",
                         sdc_inject=((0, 1e3), (0, delta)))
    s_clean, m_clean = clean_fn(state, batch)
    s_sdc, m_sdc = sdc_fn(state, batch)
    assert float(m_clean["abft_ok"]) == 1.0
    assert float(m_sdc["abft_ok"]) == 0.0          # detected ...
    for a, b in zip(jax.tree.leaves(s_clean["params"]),
                    jax.tree.leaves(s_sdc["params"])):
        np.testing.assert_allclose(                 # ... and corrected
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-4)


@pytest.mark.slow
def test_int8_ef_convergence_1k_steps():
    """ROADMAP "int8-EF compression at scale" smoke: >=1k steps through the
    deferred-reduction + int8_ef path actually CONVERGE — the error-
    feedback residual keeps the quantized gradient unbiased enough that
    the loss falls like the uncompressed path's trend."""
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("qwen2-0.5b")
    steps = 1000
    shape = ShapeConfig("t", 16, 4, "train")
    dc = DataConfig(cfg.vocab_size, 16, 4, seed=11)
    opts = StepOptions(remat=False, defer_grad_reduce=True,
                       grad_compression="int8_ef")
    with jax.set_mesh(mesh):
        fn, in_sh, out_sh = build_train_step(
            cfg, mesh, shape, AdamWConfig(lr=1e-3, total_steps=steps), opts)
        jit_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0,))
        state = jax.device_put(
            init_state(jax.random.PRNGKey(0), cfg, opts, mesh), in_sh[0])
        losses = []
        for i in range(steps):
            batch = jax.device_put(
                {k: jnp.asarray(v) for k, v in
                 synthetic_batch(dc, i).items()}, in_sh[1])
            state, m = jit_fn(state, batch)
            losses.append(float(m["loss"]))
    head = np.mean(losses[:50])
    tail = np.mean(losses[-50:])
    assert np.isfinite(tail)
    assert tail < 0.8 * head, (head, tail)         # genuinely converging
    # the EF residual stays bounded (no drift blow-up over 1k steps)
    assert float(jnp.max(jnp.abs(
        jax.tree.leaves(state["ef_residual"])[0]))) < 10.0


@pytest.mark.slow
def test_ft_runtime_drives_sdc_through_protected_step():
    from repro.ft.runtime import FTPolicy, FTRuntime

    build = _train_pair()
    clean_fn, state, batch = build(abft_reduce="correct")
    sdc_fn, _, _ = build(abft_reduce="correct", sdc_inject=(0, 1e3))
    rt = FTRuntime(4, FTPolicy(diskless_every=100),
                   sdc_injector=SDCInjector(SDCPlan(((1, 0, 1e3),))))
    oks = []
    events = []
    for i in range(3):
        state, m = rt.step(
            i, state, lambda s: clean_fn(s, batch),
            run_step_sdc=lambda s, ev: (events.append(ev), sdc_fn(s, batch))[1])
        oks.append(float(m["abft_ok"]))
    assert events == [(0, 1e3)]                    # payload delivered
    assert rt.recoveries["sdc"] == 1
    assert oks == [1.0, 0.0, 1.0]                  # fired exactly at step 1
    assert np.isfinite(float(m["loss"]))
