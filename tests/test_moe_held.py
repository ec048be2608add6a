"""The held-expert (expert-parallel share) MoE layer, its grouped ABFT
kernels and QK-norm.

The layer of one chip's share routes over every expert and computes the
part of the result its held experts give, dropless; the shares of all the
chips add up to the uncut layer.  The grouped kernels run here in
interpret mode against their jnp twin, forward and backward.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.chaos.faults import ensure_registered, flip_bit
from repro.configs.base import smoke_config
from repro.core.abft_gemm import ABFTConfig, abft_grouped_matmul, encode_weight
from repro.kernels import ops as kops
from repro.models import transformer as tf
from repro.models.moe import (MOE_STATS, MoESpec, balance_loss, moe_apply,
                              moe_held_apply, moe_init)

D, F, E, K = 64, 32, 8, 2
SPEC = MoESpec(d_model=D, d_ff=F, n_experts=E, top_k=K)


def _share(p, offset, held):
    """The weights one chip holds: the whole router, ``held`` experts."""
    return dict(p, gate=p["gate"][offset:offset + held],
                up=p["up"][offset:offset + held],
                down=p["down"][offset:offset + held])


def _dense_reference(p, x):
    """Every expert on every token, weighted by the renormalised top-k."""
    xf = x.reshape(-1, D)
    probs = jax.nn.softmax(xf @ p["router"]["w"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    y = jnp.zeros_like(xf)
    for e in range(E):
        h = jax.nn.silu(xf @ p["gate"][e]) * (xf @ p["up"][e])
        gate = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)
        y = y + gate[:, None] * (h @ p["down"][e])
    return y.reshape(x.shape)


@pytest.fixture(scope="module")
def layer():
    p = moe_init(jax.random.PRNGKey(0), SPEC)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, D))
    return p, x


@pytest.mark.parametrize("abft", [None, ABFTConfig(mode="verify"),
                                  ABFTConfig(mode="verify",
                                             backend="pallas")],
                         ids=["plain", "verify-twin", "verify-pallas"])
def test_shares_sum_to_the_uncut_layer(layer, abft):
    """Four chips holding two experts each: their parts add up to the
    whole layer, which is the dense mixture and the capacity layer's
    (dropless at this size) result; the balance loss is every share's."""
    p, x = layer
    with jax.default_matmul_precision("highest"):
        parts = [moe_held_apply(_share(p, 2 * c, 2), x,
                                SPEC._replace(experts_held=2,
                                              expert_offset=2 * c), abft)
                 for c in range(4)]
        whole, aux_w, st_w = moe_held_apply(
            p, x, SPEC._replace(experts_held=E), abft)
        ref = _dense_reference(p, x)
        cap, aux_c = moe_apply(p, x, SPEC)
    total = sum(y for y, _, _ in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(cap),
                               rtol=1e-4, atol=1e-4)
    for _, aux, _ in parts:
        np.testing.assert_allclose(float(aux), float(aux_w), rtol=1e-6)
    np.testing.assert_allclose(float(aux_w), float(aux_c), rtol=1e-6)
    # every one of the 32 tokens' 2 assignments lands on exactly one share
    assert sum(float(st[0]) for _, _, st in parts) == 2 * 16 * K
    assert float(st_w[0]) == float(st_w[1]) == 2 * 16 * K
    assert all(float(st[4]) == 0 for _, _, st in parts)


def test_dropless_under_forced_imbalance(layer):
    """Every token's first choice is held expert 3: its group takes all 32
    tokens and none is dropped."""
    p, x = layer
    x = jnp.abs(x) + 0.5
    w = p["router"]["w"].at[:, 3].set(1.0)
    p = dict(p, router={"w": w})
    spec = SPEC._replace(experts_held=2, expert_offset=2)
    with jax.default_matmul_precision("highest"):
        y, _, st = moe_held_apply(_share(p, 2, 2), x, spec,
                                  ABFTConfig(mode="verify"))
        probs = jax.nn.softmax(x.reshape(-1, D) @ w, axis=-1)
        assert bool(jnp.all(jnp.argmax(probs, -1) == 3))
        q = dict(p, gate=p["gate"].at[jnp.array([0, 1, 4, 5, 6, 7])].set(0),
                 up=p["up"], down=p["down"])
        ref = _dense_reference(q, x)
    stats = dict(zip(MOE_STATS, np.asarray(st)))
    assert stats["moe_assignments"] == stats["moe_rows_routed"] >= 32
    assert stats["moe_max_group"] == 32
    assert stats["moe_rows_computed"] % kops.GROUPED_BM == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


def test_balance_loss_counts_every_slot():
    """Uniform routing over E experts with k slots: f_e = k / E, P_e = 1/E,
    so the loss is k (the published form), not 1 (the form divided by k)."""
    t = 4 * E
    probs = jnp.full((t, E), 1.0 / E)
    top_e = (jnp.arange(t)[:, None] + jnp.arange(K)[None]) % E
    assert float(balance_loss(probs, top_e, E)) == pytest.approx(K)


# ---------------------------------------------------------------------------
# the grouped kernels
# ---------------------------------------------------------------------------


def _rows(counts, bm, k_dim, seed=0):
    counts = jnp.asarray(counts, jnp.int32)
    m = (int(counts.sum()) // bm + len(counts) + 1) * bm
    lay = kops.grouped_layout(counts, m, bm)
    rng = np.random.default_rng(seed)
    a = np.zeros((m, k_dim), np.float32)
    for e, c in enumerate(np.asarray(counts)):
        s = int(lay.starts[e])
        a[s:s + c] = rng.standard_normal((c, k_dim))
    return lay, jnp.asarray(a), int(lay.n_used[0]) * bm


def test_grouped_layout_pads_each_expert_to_whole_tiles():
    lay = kops.grouped_layout(jnp.array([5, 0, 17, 3]), 64, 8)
    assert lay.padded.tolist() == [8, 0, 24, 8]
    assert lay.starts.tolist() == [0, 8, 8, 32]
    assert lay.sizes.tolist() == [8, 0, 24, 32]
    assert lay.tile_group.tolist() == [0, 2, 2, 2, 3, 3, 3, 3]
    assert lay.n_used.tolist() == [5]


@pytest.mark.parametrize("residual", [True, False])
def test_grouped_kernel_matches_its_twin_forward_and_vjp(residual):
    lay, a, used = _rows([5, 0, 17, 3], 8, 64)
    b = jax.random.normal(jax.random.PRNGKey(2), (4, 64, 70))
    wn = jax.random.normal(jax.random.PRNGKey(3), (70, 2)) if residual \
        else None

    def run(pallas, a, b):
        return kops.grouped_matmul(a, b, lay, wn, pallas=pallas)

    (c1, r1), (c0, r0) = run(True, a, b), run(False, a, b)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c0), rtol=1e-5,
                               atol=1e-5)
    if residual:
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r0),
                                   rtol=1e-4, atol=1e-4)
    else:
        assert r1 is None and r0 is None

    def loss(pallas, a, b):
        return jnp.sum(jnp.sin(run(pallas, a, b)[0]))

    (da1, db1), (da0, db0) = (jax.grad(lambda a, b: loss(p, a, b),
                                       argnums=(0, 1))(a, b)
                              for p in (True, False))
    # the buffer's unused tail is never computed (and never read back)
    np.testing.assert_allclose(np.asarray(da1[:used]),
                               np.asarray(da0[:used]), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db1), np.asarray(db0), rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(db1[1]))) == 0.0    # expert 1 has no rows


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_flipped_expert_product_output_is_detected_and_located(backend):
    """A bit flipped in one output element of a grouped product: the
    per-row verify trips on that row alone, and correct mode puts the
    element back."""
    lay, a, _ = _rows([5, 0, 17, 3], 8, 64, seed=4)
    w = jax.random.normal(jax.random.PRNGKey(5), (4, 64, 48)) * 0.1
    row, col = 11, 7                       # expert 2's rows
    flat = row * (48 + 2) + col

    def flip(y_f):
        return flip_bit(y_f, flat, 27)

    cfg = ABFTConfig(mode="verify", backend=backend)
    w_enc = encode_weight(w, cfg)
    clean, trips0 = abft_grouped_matmul(a, w_enc, lay, cfg)
    bad, trips = abft_grouped_matmul(a, w_enc, lay, cfg, inject=flip)
    assert int(trips0) == 0 and int(trips) == 1
    diff = np.argwhere(np.asarray(bad != clean))
    assert diff.tolist() == [[row, col]]
    fixed, trips_c = abft_grouped_matmul(
        a, w_enc, lay, ABFTConfig(mode="correct", backend=backend),
        inject=flip)
    assert int(trips_c) == 1
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(clean),
                               rtol=1e-5, atol=1e-5)


def test_chaos_drill_expert_product_flip_is_corrected(layer):
    """The models.moe.expert surface: a bit flipped in the gate/up
    product's output inside the layer, corrected before the layer's
    output is formed."""
    reg = ensure_registered()
    assert reg["models.moe.expert"].protected
    assert reg["models.moe.router"].protected
    p, x = layer
    spec = SPEC._replace(experts_held=2, expert_offset=2)
    share = _share(p, 2, 2)
    clean, _, st0 = moe_held_apply(share, x, spec, ABFTConfig(mode="verify"))
    flip = lambda y_f: flip_bit(y_f, 5, 29)  # noqa: E731  row 0, col 5
    bad, _, st1 = moe_held_apply(share, x, spec, ABFTConfig(mode="verify"),
                                 inject=flip)
    fixed, _, st2 = moe_held_apply(share, x, spec,
                                   ABFTConfig(mode="correct"), inject=flip)
    assert float(st0[4]) == 0 and float(st1[4]) == 1 == float(st2[4])
    assert float(jnp.max(jnp.abs(bad - clean))) > 1e-3
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(clean),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the model path
# ---------------------------------------------------------------------------


def test_train_step_returns_and_launcher_publishes_moe_counters():
    from repro import obs
    from repro.launch.train import publish_moe_counters
    from repro.configs.base import ShapeConfig
    from repro.dist.sharding import make_mesh
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state
    cfg = smoke_config("qwen3-moe-30b-a3b").scaled(experts_held=2,
                                                   expert_offset=6)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", 16, 2, "train")
    opts = StepOptions(abft_mode="verify", remat=True, aux_weight=0.001)
    with jax.set_mesh(mesh):
        fn, in_sh, out_sh = build_train_step(cfg, mesh, shape, AdamWConfig(),
                                             opts)
        state = init_state(jax.random.PRNGKey(0), cfg, opts)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        _, m = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)(
            state, {"tokens": toks, "labels": toks})
    assert set(MOE_STATS) <= set(m)
    assert float(m["moe_assignments"]) == float(m["moe_rows_routed"]) > 0
    assert float(m["moe_trips"]) == 0
    before = obs.counter("repro_moe_rows_computed_total").total()
    publish_moe_counters(m, cfg)
    assert obs.counter("repro_moe_assignments_total").value(held="6-7") \
        >= float(m["moe_assignments"])
    assert obs.counter("repro_moe_rows_computed_total").total() - before \
        == float(m["moe_rows_computed"])
    assert obs.counter("repro_abft_trips_total").value(
        surface="moe.expert") == 0


def test_qk_norm_normalises_q_and_k_per_head():
    """With QK-norm on, scaling a head's q or k projection leaves the
    attention output unchanged (the per-head RMSNorm removes it)."""
    cfg = smoke_config("qwen3-moe-30b-a3b")
    assert cfg.qk_norm
    p = tf.init_params(jax.random.PRNGKey(0), cfg)
    attn = p["groups"][0]["b0"]["attn"]
    assert attn["q_norm"]["scale"].shape[-1] == cfg.resolved_head_dim
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                              cfg.vocab_size)
    scaled = jax.tree.map(lambda a: a, p)
    g = scaled["groups"][0]["b0"]["attn"]
    g["wq"] = {"w": g["wq"]["w"] * 3.0}
    g["wk"] = {"w": g["wk"]["w"] * 0.5}
    a = tf.forward(p, toks, cfg)[0]
    b = tf.forward(scaled, toks, cfg)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


# digests of the qwen2 smoke forward's logits and of the loss gradients
# (PRNGKey(0) params, PRNGKey(1) tokens [2, 24]) computed by the program as
# it was before QK-norm and the held-expert layer existed
QWEN2_LOGITS = ("a2932b44cc192eb715dc696d7aa790d069d29b8ac0f811adaf3d3a41ab"
                "96bec6")
QWEN2_GRADS = ("6e84fe51f870a82c75d1ff076ddd2b76ccfd5aae495c5f9d033d37b088c"
               "f2e71")


def test_qk_norm_off_leaves_a_qwen2_smoke_forward_bit_identical():
    cfg = smoke_config("qwen2-0.5b")
    assert not cfg.qk_norm and not cfg.experts_held
    p = tf.init_params(jax.random.PRNGKey(0), cfg)
    assert "q_norm" not in p["groups"][0]["b0"]["attn"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    logits = tf.forward(p, toks, cfg)[0]
    g = jax.grad(lambda p: tf.loss_fn(p, toks, toks, cfg))(p)
    assert hashlib.sha256(np.asarray(logits).tobytes()).hexdigest() == \
        QWEN2_LOGITS
    assert hashlib.sha256(b"".join(
        np.asarray(x).tobytes() for x in jax.tree.leaves(g))).hexdigest() \
        == QWEN2_GRADS
