"""Attention: GQA/MQA, global + sliding-window, softcap, cross-attn, KV cache.

Three compute paths, one semantic:
  * dense  — masked einsum, for short sequences (smoke tests, whisper frames)
  * flash  — chunked online-softmax lax.scan, O(S) memory, for long train /
             prefill sequences (TPU-friendly: the chunk loop maps onto what a
             Pallas flash kernel would do; XLA fuses the inner chain)
  * decode — single-query einsum over the KV cache (never quadratic)

All paths support GQA (n_kv <= n_heads), causal + window masks and logit
softcapping (gemma2).  Cross-attention reuses the dense path with no mask.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.layers import (linear_apply, linear_init, rmsnorm_apply,
                                 rmsnorm_init, rope, softcap_fn)

NEG_INF = -1e30


class AttnSpec(NamedTuple):
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    softcap: Optional[float] = None
    window: Optional[int] = None     # sliding window (None = global)
    rope_theta: float = 10000.0
    use_rope: bool = True
    kc: int = 512                    # flash KV chunk length
    qk_norm: bool = False            # RMSNorm over head_dim on q, k (Qwen3)
    norm_eps: float = 1e-6


def attn_init(key, s: AttnSpec, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": linear_init(kq, s.d_model, s.n_heads * s.head_dim,
                          bias=s.qkv_bias, dtype=dtype),
        "wk": linear_init(kk, s.d_model, s.n_kv * s.head_dim,
                          bias=s.qkv_bias, dtype=dtype),
        "wv": linear_init(kv, s.d_model, s.n_kv * s.head_dim,
                          bias=s.qkv_bias, dtype=dtype),
        "wo": linear_init(ko, s.n_heads * s.head_dim, s.d_model, dtype=dtype),
    }
    if s.qk_norm:
        p["q_norm"] = rmsnorm_init(s.head_dim, dtype)
        p["k_norm"] = rmsnorm_init(s.head_dim, dtype)
    return p


def _split_heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


def _mask(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """Boolean mask, True = attend.  q_pos: [Sq] -> [Sq, Sk] shared mask;
    q_pos: [B, Sq] (continuous batching: per-slot positions) -> [B, Sq, Sk]."""
    qp = q_pos[..., :, None]
    kp = k_pos[None, :] if q_pos.ndim == 1 else k_pos[None, None, :]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= qp >= kp
    if window is not None:
        # two-sided band: bounding only qp - kp would let a non-causal
        # window attend to arbitrarily-far future keys
        m &= qp - kp < window
        m &= kp - qp < window
    return m


def _sdpa_dense(q, k, v, *, scale, softcap, mask):
    """q: [B,Sq,G,g,D]; k,v: [B,Sk,G,D]; mask [Sq,Sk] or [B,Sq,Sk]."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = softcap_fn(s, softcap)
    m = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o


class _FlashStatic(NamedTuple):
    scale: float
    softcap: Optional[float]
    causal: bool
    window: Optional[int]
    kc: int


def _chunk_kv(k, v, k_pos, kc):
    b, sk, g_kv, d = k.shape
    pad = (-sk) % kc
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-(10 ** 9))
    nk = (sk + pad) // kc
    kb = k.reshape(b, nk, kc, g_kv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, kc, g_kv, d).transpose(1, 0, 2, 3, 4)
    return kb, vb, k_pos.reshape(nk, kc), pad


def _scores(st: _FlashStatic, q32, k_c, q_pos, kp_c):
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q32,
                   k_c.astype(jnp.float32)) * st.scale
    s = softcap_fn(s, st.softcap)
    # an additive [Sq, kc] bias, not a select: a select's mask is kept per
    # chunk when the loop is linearised, broadcast to the scores' shape (4.3
    # GB at 2 x 8k tokens and 32 heads), the bias at its own [Sq, kc]
    msk = _mask(q_pos, kp_c, causal=st.causal, window=st.window)
    return s + jnp.where(msk, 0.0, NEG_INF)[None, None, None], s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(st: _FlashStatic, q, k, v, q_pos, k_pos):
    o, _ = _flash_fwd_impl(st, q, k, v, q_pos, k_pos)
    return o


def _flash_fwd_impl(st, q, k, v, q_pos, k_pos):
    """FlashAttention-2 forward: chunked online softmax over K/V.

    q: [B,Sq,KV,g,D]; k,v: [B,Sk,KV,D].  Returns o: [B,Sq,KV,g,D] and the
    per-row log-sum-exp (the only softmax residual the backward needs).
    """
    b, sq, g_kv, g, d = q.shape
    kb, vb, kpb, _ = _chunk_kv(k, v, k_pos, min(st.kc, k.shape[1]))
    q32 = q.astype(jnp.float32)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        k_c, v_c, kp_c = inp
        s, _ = _scores(st, q32, k_c, q_pos, kp_c)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, g_kv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, g_kv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, g_kv, g, sq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kb, vb, kpb))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(0, 3, 1, 2, 4)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))      # [B,KV,g,Sq]
    return o.astype(q.dtype), lse


def _flash_fwd(st, q, k, v, q_pos, k_pos):
    o, lse = _flash_fwd_impl(st, q, k, v, q_pos, k_pos)
    return o, (q, k, v, o, lse, q_pos, k_pos)


def _flash_bwd(st, res, do):
    """FA-2 backward: recompute scores per chunk; no S x S materialization."""
    q, k, v, o, lse, q_pos, k_pos = res
    b, sq, g_kv, g, d = q.shape
    sk = k.shape[1]
    kc = min(st.kc, sk)
    kb, vb, kpb, pad = _chunk_kv(k, v, k_pos, kc)
    q32 = q.astype(jnp.float32)
    do32 = do.astype(jnp.float32).transpose(0, 2, 3, 1, 4)   # [B,KV,g,Sq,D]
    o32 = o.astype(jnp.float32).transpose(0, 2, 3, 1, 4)
    delta = jnp.sum(do32 * o32, axis=-1)                     # [B,KV,g,Sq]

    def step(dq_acc, inp):
        k_c, v_c, kp_c = inp
        s_masked, s_raw = _scores(st, q32, k_c, q_pos, kp_c)
        p = jnp.exp(s_masked - lse[..., None])               # [B,KV,g,Sq,kc]
        dv_c = jnp.einsum("bhgqk,bhgqd->bkhd", p, do32)
        dp = jnp.einsum("bhgqd,bkhd->bhgqk", do32,
                        v_c.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        if st.softcap:
            # d/dx [cap tanh(x/cap)] = 1 - (capped/cap)^2; guard masked
            # positions (s = -inf, p = 0) against 0 * inf = NaN
            sc = jnp.where(s_masked > NEG_INF / 2, s_masked, 0.0)
            ds = ds * (1.0 - jnp.square(sc / st.softcap))
        ds = ds * st.scale
        dq_acc = dq_acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds, k_c.astype(jnp.float32))
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q32)
        return dq_acc, (dk_c, dv_c)

    dq0 = jnp.zeros((b, sq, g_kv, g, d), jnp.float32)
    dq, (dkb, dvb) = lax.scan(step, dq0, (kb, vb, kpb))
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, g_kv, d)[:, :sk]
    dv = dvb.transpose(1, 0, 2, 3, 4).reshape(b, sk + pad, g_kv, d)[:, :sk]
    zero_pos = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_pos(q_pos), zero_pos(k_pos))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _sdpa_flash(q, k, v, *, scale, softcap, q_pos, k_pos, causal, window,
                kc: int = 512):
    """Chunked online-softmax attention (custom-vjp FA-2)."""
    st = _FlashStatic(scale=scale, softcap=softcap, causal=causal,
                      window=window, kc=kc)
    return _flash(st, q, k, v, q_pos, k_pos)


def attn_apply(
    p,
    x,
    s: AttnSpec,
    *,
    positions: jax.Array,            # [Sq] global positions of the queries
    causal: bool = True,
    cache: Optional[dict] = None,    # {"k","v": [B, Smax, n_kv, D], "index"}
    cross_kv: Optional[jax.Array] = None,  # [B, Skv, d_model] encoder states
    abft=None,
    flash_threshold: int = 1024,
):
    """Returns (y, new_cache).  Modes:
       - train/prefill: cache None -> full self-attention over x
       - prefill w/ cache: cache with index 0, Sq tokens written
       - decode: Sq == 1, reads cache, writes at cache["index"]
       - paged decode: Sq == 1, cache {"k","v": page pools [n_pages,
         layers, page_size, n_kv * D], "layer": this layer's index,
         "table": [B, pages]}; positions [B, 1]; new_cache holds only the
         token's K/V rows [B, 1, n_kv, D]
       - cross: cross_kv set (no cache, no mask)
    """
    b, sq, _ = x.shape
    q = _split_heads(linear_apply(p["wq"], x, abft), s.n_heads, s.head_dim)
    kv_src = cross_kv if cross_kv is not None else x
    k = _split_heads(linear_apply(p["wk"], kv_src, abft), s.n_kv, s.head_dim)
    v = _split_heads(linear_apply(p["wv"], kv_src, abft), s.n_kv, s.head_dim)
    if s.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, s.norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, s.norm_eps)

    if s.use_rope and cross_kv is None:
        pos_b = positions[None] if positions.ndim == 1 else positions
        q = rope(q, pos_b, s.rope_theta)
        k = rope(k, pos_b, s.rope_theta)

    new_cache = None
    paged = cache is not None and "table" in cache
    if paged:
        # paged decode: this layer's pages read through the page table and
        # the fresh token set at each slot's position in that view only;
        # the new K/V rows go back to the caller, which owns the pools
        if sq != 1:
            raise ValueError(f"a paged cache decodes one token, got {sq}")
        new_cache = {"k": k.astype(cache["k"].dtype),
                     "v": v.astype(cache["v"].dtype)}
        k_pos = jnp.arange(cache["table"].shape[1] * cache["k"].shape[2])
        fresh = (k_pos[None, :] == positions)[:, :, None]

        def view(n):
            pages = paged_view(cache[n], cache["layer"], cache["table"])
            return jnp.where(fresh, new_cache[n].reshape(b, 1, -1), pages)
        k, v = view("k"), view("v")
    elif cache is not None:
        idx = cache["index"]
        if jnp.ndim(idx) == 0:
            ck = lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            cv = lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        else:
            # continuous batching: per-slot write positions (sq == 1)
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, idx].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, idx].set(
                v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv, "index": idx + sq}
        k, v = ck, cv
        k_pos = jnp.arange(cache["k"].shape[1])
        # positions beyond the write head are masked out by causality
    else:
        k_pos = positions if cross_kv is None else jnp.arange(k.shape[1])

    g = s.n_heads // s.n_kv
    qh = q.reshape(b, sq, s.n_kv, g, s.head_dim)
    scale = s.head_dim ** -0.5
    use_causal = causal and cross_kv is None
    window = s.window if cross_kv is None else None

    sk = k.shape[1]
    if paged:
        mask = _mask(positions, k_pos, causal=use_causal, window=window)
        # one head at a time on its features of the merged axis: on a TPU
        # no operand then gets a head_dim-wide minor axis, which is padded
        # to 128 lanes and re-laid before the dot
        hd = s.head_dim
        o = jnp.concatenate([_sdpa_dense(
            qh[:, :, i:i + 1], k[:, :, None, i * hd:(i + 1) * hd],
            v[:, :, None, i * hd:(i + 1) * hd], scale=scale,
            softcap=s.softcap, mask=mask) for i in range(s.n_kv)], axis=2)
    elif sq == 1 or sk <= flash_threshold or cross_kv is not None:
        mask = _mask(positions, k_pos, causal=use_causal, window=window)
        o = _sdpa_dense(qh, k, v, scale=scale, softcap=s.softcap, mask=mask)
    else:
        o = _sdpa_flash(qh, k, v, scale=scale, softcap=s.softcap,
                        q_pos=positions, k_pos=k_pos, causal=use_causal,
                        window=window, kc=s.kc)
    o = o.reshape(b, sq, s.n_heads * s.head_dim).astype(x.dtype)
    y = linear_apply(p["wo"], o, abft)
    return y, new_cache


def paged_view(pool, layer, table):
    """``[B, pages * page_size, features]`` view of layer ``layer``'s pages
    that ``table`` ``[B, pages]`` names in a pool ``[n_pages, layers,
    page_size, features]``, read straight from the pool."""
    b, n = table.shape
    return pool[table, layer].reshape(b, n * pool.shape[2], -1)


def make_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
               dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "index": jnp.zeros((), jnp.int32),
    }
