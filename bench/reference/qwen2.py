"""Plain float32 Qwen2 decoder: weights from a seed, forward pass and loss.

Written from the published description (arXiv:2407.10671 and the Hugging
Face ``Qwen2ForCausalLM`` config keys) in ``jax.numpy`` alone.  It imports
nothing of the program under test; the benchmark hands the program the
weights made here, never the other way round.

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 matmul is one
bfloat16 pass otherwise.

Departures from the published model, each deliberate:
  * weights are random from the seed, not the released checkpoint;
  * biases and norm scales are random too (published checkpoints train
    them), so that a program that drops a bias or a norm scale fails;
  * no sliding window (published ``use_sliding_window`` is false), no
    dropout (inference and the benchmark's training both run without);
  * the loss is the plain mean next-token cross entropy over every
    position (no padding mask: the benchmark's batches have none).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from bench.flops import DenseDecoder

HI = lax.Precision.HIGHEST

# per-layer weight names in the benchmark's layout ([layers, ...] stacked)
LAYER_KEYS = ("attn_norm", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "mlp_norm", "w_gate", "w_up", "w_down")


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def weight_shapes(c: DenseDecoder) -> Dict[str, tuple]:
    q, kv = c.heads * c.head_dim, c.kv_heads * c.head_dim
    L, D, F = c.layers, c.d_model, c.d_ff
    return {"embed": (c.vocab, D), "final_norm": (D,),
            "attn_norm": (L, D), "wq": (L, D, q), "bq": (L, q),
            "wk": (L, D, kv), "bk": (L, kv), "wv": (L, D, kv), "bv": (L, kv),
            "wo": (L, q, D), "mlp_norm": (L, D), "w_gate": (L, D, F),
            "w_up": (L, D, F), "w_down": (L, F, D)}


def _init(key, c: DenseDecoder, dtype):
    shapes = weight_shapes(c)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name in ("final_norm", "attn_norm", "mlp_norm"):
            w = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            w = 0.1 * z
        elif name == "embed":
            w = 0.02 * z
        else:                       # [.., in, out]: unit-variance outputs
            w = z * shape[-2] ** -0.5
        out[name] = w.astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _init_fn(c: DenseDecoder, dtype_name: str):
    return jax.jit(functools.partial(_init, c=c, dtype=jnp.dtype(dtype_name)))


def init_weights(seed: int, c: DenseDecoder, dtype="bfloat16"):
    """Every weight, on the default device, in one jitted call."""
    return _init_fn(c, jnp.dtype(dtype).name)(key_from_seed(seed))


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotary embedding on [S, H, hd] in the rotate-half layout."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, c: DenseDecoder, theta: float, eps: float):
    """One decoder layer on x [S, D] (one sequence, positions 0..S-1)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, lw["attn_norm"], eps)
    q = jnp.dot(h, lw["wq"], precision=HI) + lw["bq"]
    k = jnp.dot(h, lw["wk"], precision=HI) + lw["bk"]
    v = jnp.dot(h, lw["wv"], precision=HI) + lw["bv"]
    q = _rope(q.reshape(s, c.heads, c.head_dim), pos, theta)
    k = _rope(k.reshape(s, c.kv_heads, c.head_dim), pos, theta)
    v = v.reshape(s, c.kv_heads, c.head_dim)
    rep = c.heads // c.kv_heads          # query head h reads kv head h // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) \
        * c.head_dim ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(s, -1)
    x = x + jnp.dot(o, lw["wo"], precision=HI)
    h = _rms(x, lw["mlp_norm"], eps)
    g = jnp.dot(h, lw["w_gate"], precision=HI)
    u = jnp.dot(h, lw["w_up"], precision=HI)
    return x + jnp.dot(jax.nn.silu(g) * u, lw["w_down"], precision=HI)


def hidden(w, tokens, c: DenseDecoder, theta: float, eps: float):
    """Final-normed hidden states [S, D] of one sequence, float32.  Layers
    run one at a time under `jax.checkpoint`, so a backward pass holds one
    layer's activations besides the residual stream."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    x = jnp.take(w["embed"], tokens, axis=0)
    layers = {k: w[k] for k in LAYER_KEYS}
    body = jax.checkpoint(
        lambda xx, lw: (_layer(xx, lw, c, theta, eps), None))
    x, _ = lax.scan(body, x, layers)
    return _rms(x, w["final_norm"], eps), w["embed"]


def logits_at(w, tokens, idx, c: DenseDecoder, theta: float, eps: float):
    """Logits [len(idx), vocab] at the positions ``idx`` of one sequence."""
    h, emb = hidden(w, tokens, c, theta, eps)
    return jnp.dot(jnp.take(h, idx, axis=0), emb.T, precision=HI)


def loss(w, tokens, labels, c: DenseDecoder, theta: float, eps: float,
         chunk: int = 512):
    """Mean next-token cross entropy of one sequence; the unembedding runs
    ``chunk`` positions at a time so that a backward pass never holds the
    whole [S, vocab] logits."""
    h, emb = hidden(w, tokens, c, theta, eps)
    s = h.shape[0]
    n = -(-s // chunk)
    pad = n * chunk - s
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, chunk, -1)
    lb = jnp.pad(labels, (0, pad)).reshape(n, chunk)
    mb = (jnp.arange(n * chunk) < s).reshape(n, chunk)

    @jax.checkpoint
    def part(hc, lc, mc):
        z = jnp.dot(hc, emb.T, precision=HI)
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(mc, nll, 0.0))

    tot = lax.map(lambda a: part(*a), (hb, lb, mb))
    return jnp.sum(tot) / s
