"""Plain float32 Qwen3-MoE decoder on one chip's share of the experts:
weights from a seed, forward pass and loss.

Written from the published description (Qwen3 Technical Report,
arXiv:2505.09388, and the Hugging Face ``Qwen3MoeForCausalLM`` config keys)
in ``jax.numpy`` alone.  It imports nothing of the program under test; the
benchmark hands the program the weights made here.

Every layer is attention then a routed MoE, each pre-norm (RMSNorm) with a
residual.  Attention is GQA with a per-head RMSNorm over ``head_dim`` on q
and on k before RoPE (QK-norm) and no bias.  The router is a [d, E]
projection; its softmax over all E experts gives the top-k, whose k weights
are renormalised to sum to one (``norm_topk_prob``).  This chip holds the
experts ``[expert_offset, expert_offset + held)``: each held expert is
computed densely over every token and weighted by the routing weight the
token gave it (zero when it did not pick it); the other experts' part of
the result is left out, as on the chip whose share this is.  SwiGLU
experts.  The load-balancing loss, per layer over the batch's tokens, is
``E * sum_e f_e * P_e`` (Switch Transformer, arXiv:2101.03961 eq. 4, as
the Hugging Face Qwen3-MoE code writes it: f_e the assignments to expert
e per token over all k slots, so the f_e sum to k, not divided by k; P_e
the mean router probability); the loss is the mean next-token cross
entropy plus ``router_aux_loss_coef`` times the sum of the layers' terms.

Every matmul runs at ``Precision.HIGHEST``.  Queries are taken in blocks
and each layer, each query block and each expert is rematerialised, so
that a backward pass over two 8k-token rows fits beside the fp32 state.

Departures from the published model, each deliberate:
  * weights are random from the seed, not the released checkpoint;
    norm scales are random too, so a program that drops one fails;
  * the chip's share: ``num_experts`` held experts of the published 128,
    the vocabulary's first ``vocab_size`` rows (embedding and head), and
    ``num_hidden_layers`` of 48 (one pipeline stage);
  * the balance loss sums per-layer terms (the Hugging Face code pools the
    router outputs of all layers into one term);
  * weights are stored in the configuration's dtype and gradients taken
    with respect to them (as `bench.reference.qwen2`'s are), so each
    gradient element is rounded to that dtype: float32 copies of the
    weights and gradients would not fit one chip beside Adam's moments;
  * no dropout, no sliding window (published: none), no padding mask.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.flops_moe import MoEDecoder
from bench.reference.qwen2 import _rms, _rope, key_from_seed

HI = lax.Precision.HIGHEST

# per-layer weight names in the benchmark's layout ([layers, ...] stacked)
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "mlp_norm", "router", "w_gate", "w_up", "w_down")
NORMS = ("final_norm", "attn_norm", "mlp_norm", "q_norm", "k_norm")


def weight_shapes(c: MoEDecoder) -> Dict[str, tuple]:
    q, kv = c.heads * c.head_dim, c.kv_heads * c.head_dim
    L, D, F, H = c.layers, c.d_model, c.moe_dff, c.experts_held
    return {"embed": (c.vocab, D), "lm_head": (D, c.vocab),
            "final_norm": (D,), "attn_norm": (L, D), "wq": (L, D, q),
            "wk": (L, D, kv), "wv": (L, D, kv), "wo": (L, q, D),
            "q_norm": (L, c.head_dim), "k_norm": (L, c.head_dim),
            "mlp_norm": (L, D), "router": (L, D, c.n_experts),
            "w_gate": (L, H, D, F), "w_up": (L, H, D, F),
            "w_down": (L, H, F, D)}


def _init(key, c: MoEDecoder, dtype):
    shapes = weight_shapes(c)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in NORMS:
            w = 1.0 + 0.1 * z
        elif name == "embed":
            w = 0.02 * z
        else:                       # [.., in, out]: unit-variance outputs
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(c: MoEDecoder, dtype_name: str):
    return jax.jit(functools.partial(_init, c=c, dtype=jnp.dtype(dtype_name)))


def init_weights(seed: int, c: MoEDecoder, dtype="bfloat16"):
    """Every weight, on the default device, in one jitted call."""
    return _init_fn(c, jnp.dtype(dtype).name)(key_from_seed(seed))


def _attention(x, lw, c: MoEDecoder, theta, eps, q_block):
    """Causal GQA self-attention of one sequence x [S, D], queries in
    blocks of ``q_block``."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = jnp.dot(x, lw["wq"], precision=HI).reshape(s, c.heads, c.head_dim)
    k = jnp.dot(x, lw["wk"], precision=HI).reshape(s, c.kv_heads,
                                                    c.head_dim)
    v = jnp.dot(x, lw["wv"], precision=HI).reshape(s, c.kv_heads,
                                                    c.head_dim)
    q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
    k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
    rep = c.heads // c.kv_heads          # query head h reads kv head h // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = -(-s // q_block)
    qp = jnp.pad(q, ((0, nb * q_block - s), (0, 0), (0, 0)))

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(qp, i * q_block, q_block)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) \
            * c.head_dim ** -0.5
        sc = jnp.where((qpos[:, None] >= pos[None, :])[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    o = lax.map(block, jnp.arange(nb)).reshape(nb * q_block, -1)[:s]
    return jnp.dot(o, lw["wo"], precision=HI)


def route(h, router, c: MoEDecoder):
    """Routing of tokens h [N, D]: (the router's probabilities [N, E],
    top-k experts [N, k], their renormalised weights [N, k])."""
    probs = jax.nn.softmax(jnp.dot(h, router, precision=HI), axis=-1)
    top_w, top_e = lax.top_k(probs, c.top_k)
    return probs, top_e, top_w / jnp.sum(top_w, axis=-1, keepdims=True)


def balance_loss(probs, top_e, n_experts: int):
    """``E * sum_e f_e * P_e`` over the tokens of probs [N, E] and top_e
    [N, k] (f_e: assignments to e per token, over every slot)."""
    f = jnp.sum(jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32),
                axis=(0, 1)) / probs.shape[0]
    return n_experts * jnp.sum(f * jnp.mean(probs, axis=0))


def _experts(h, lw, c: MoEDecoder):
    """The held experts' weighted part of the MoE output on tokens h
    [N, D], and the layer's balance-loss term."""
    probs, top_e, top_w = route(h, lw["router"], c)
    aux = balance_loss(probs, top_e, c.n_experts)

    @jax.checkpoint
    def one(acc, ew):
        e, wg, wu, wd = ew
        gate = jnp.sum(jnp.where(top_e == c.expert_offset + e, top_w, 0.0),
                       axis=-1)                                   # [N]
        y = jnp.dot(jax.nn.silu(jnp.dot(h, wg, precision=HI))
                    * jnp.dot(h, wu, precision=HI), wd, precision=HI)
        return acc + gate[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (jnp.arange(c.experts_held), lw["w_gate"],
                       lw["w_up"], lw["w_down"]))
    return out, aux


def _layer(x, lw, c: MoEDecoder, theta: float, eps: float, q_block: int):
    """One decoder layer on x [B, S, D]; returns (x, balance-loss term)."""
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    h = _rms(x, lw["attn_norm"], eps)
    x = x + jax.vmap(lambda hh: _attention(hh, lw, c, theta, eps,
                                           q_block))(h)
    b, s, d = x.shape
    h = _rms(x, lw["mlp_norm"], eps).reshape(b * s, d)
    y, aux = _experts(h, lw, c)
    return x + y.reshape(b, s, d), aux


def hidden(w, tokens, c: MoEDecoder, theta: float, eps: float,
           q_block: int = 512):
    """Final-normed hidden states [B, S, D] of a batch, float32, and the
    sum of the layers' balance-loss terms.  Layers run one at a time under
    `jax.checkpoint`; each layer's weights become float32 inside it."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS}
    body = jax.checkpoint(
        lambda xx, lw: _layer(xx, lw, c, theta, eps, q_block))
    x, auxes = lax.scan(body, x, layers)
    return _rms(x, w["final_norm"].astype(jnp.float32), eps), jnp.sum(auxes)


def logits_at(w, tokens, c: MoEDecoder, theta: float, eps: float,
              q_block: int = 512):
    """Logits [B, S, vocab] of a (short) batch."""
    h, _ = hidden(w, tokens, c, theta, eps, q_block)
    return jnp.dot(h, w["lm_head"].astype(jnp.float32), precision=HI)


def loss(w, tokens, labels, c: MoEDecoder, theta: float, eps: float,
         aux_coef: float, chunk: int = 512, q_block: int = 512):
    """Mean next-token cross entropy over a batch [B, S] plus ``aux_coef``
    times the layers' balance-loss terms; the head runs ``chunk``
    positions at a time so that a backward pass never holds the whole
    [B * S, vocab] logits."""
    h, aux = hidden(w, tokens, c, theta, eps, q_block)
    d = h.shape[-1]
    h = h.reshape(-1, d)
    n = h.shape[0]
    nb = -(-n // chunk)
    pad = nb * chunk - n
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, chunk, d)
    lb = jnp.pad(labels.reshape(-1), (0, pad)).reshape(nb, chunk)
    mb = (jnp.arange(nb * chunk) < n).reshape(nb, chunk)
    head = w["lm_head"].astype(jnp.float32)

    @jax.checkpoint
    def part(hc, lc, mc):
        z = jnp.dot(hc, head, precision=HI)
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(mc, nll, 0.0))

    tot = lax.map(lambda a: part(*a), (hb, lb, mb))
    return jnp.sum(tot) / n + aux_coef * aux
