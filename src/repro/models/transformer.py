"""Generic layered LM over period-group layouts (see configs.base.ModelConfig).

One implementation serves all ten assigned architectures:
  * params/caches are stacked per layout group and scanned with lax.scan
    (compact HLO even for 88-layer granite or 61-layer kimi);
  * each pattern element has its own param/cache slot inside the period;
  * mixers: GQA attention (global/local/bidir/cross/dec), mamba, m/sLSTM;
  * FFN: dense SwiGLU/GeGLU or sort-dispatch MoE (EP-shardable);
  * modes: train/prefill forward, single-token decode with typed caches.

ABFT protection (the paper's technique) threads through every projection via
`abft` (core.abft_gemm.ABFTConfig); `None`/mode "off" is the baseline path.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.layers import (
    embed_apply, embed_init, linear_init, mlp_apply, mlp_init, rmsnorm_apply,
    rmsnorm_init, softcap_fn, unembed_apply,
)

# ---------------------------------------------------------------------------
# Specs derived from config
# ---------------------------------------------------------------------------


def _attn_spec(cfg: ModelConfig, kind: str) -> attn.AttnSpec:
    return attn.AttnSpec(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias,
        softcap=cfg.attn_softcap,
        window=cfg.window if kind == "attn_local" else None,
        rope_theta=cfg.rope_theta,
        use_rope=kind not in ("cross",),
        kc=cfg.flash_kc,
        qk_norm=cfg.qk_norm,
        norm_eps=cfg.norm_eps,
    )


def _mamba_spec(cfg: ModelConfig) -> mb.MambaSpec:
    return mb.MambaSpec(cfg.d_model, cfg.d_state, cfg.d_conv, cfg.mamba_expand)


def _xlstm_spec(cfg: ModelConfig) -> xl.XLSTMSpec:
    return xl.XLSTMSpec(cfg.d_model, cfg.n_heads)


def _moe_spec(cfg: ModelConfig) -> moe_mod.MoESpec:
    return moe_mod.MoESpec(cfg.d_model, cfg.moe_dff or cfg.d_ff,
                           cfg.n_experts, cfg.top_k, cfg.capacity_factor,
                           cfg.moe_groups, experts_held=cfg.experts_held,
                           expert_offset=cfg.expert_offset)


def _no_moe_stats():
    return jnp.zeros((len(moe_mod.MOE_STATS),), jnp.float32)


def merge_moe_stats(a, b):
    """Two `moe.MOE_STATS` vectors as one: counts add, the largest group
    is the larger."""
    i = moe_mod.MOE_STATS.index("moe_max_group")
    return jnp.where(jnp.arange(a.shape[0]) == i, jnp.maximum(a, b), a + b)


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig, mixer: str, ffn: str):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dt)}
    if mixer in ("attn", "attn_local", "attn_bidir"):
        p["attn"] = attn.attn_init(keys[0], _attn_spec(cfg, mixer), dt)
    elif mixer == "cross":
        p["attn"] = attn.attn_init(keys[0], _attn_spec(cfg, mixer), dt)
    elif mixer == "dec":
        p["attn"] = attn.attn_init(keys[0], _attn_spec(cfg, "attn"), dt)
        p["cross"] = attn.attn_init(keys[1], _attn_spec(cfg, "cross"), dt)
        p["norm_c"] = rmsnorm_init(cfg.d_model, dt)
    elif mixer == "mamba":
        p["mamba"] = mb.mamba_init(keys[0], _mamba_spec(cfg), dt)
    elif mixer == "mlstm":
        p["mlstm"] = xl.mlstm_init(keys[0], _xlstm_spec(cfg), dt)
    elif mixer == "slstm":
        p["slstm"] = xl.slstm_init(keys[0], _xlstm_spec(cfg), dt)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn == "dense":
        p["norm2"] = rmsnorm_init(cfg.d_model, dt)
        p["mlp"] = mlp_init(keys[2], cfg.d_model, cfg.d_ff, dtype=dt)
    elif ffn == "moe":
        p["norm2"] = rmsnorm_init(cfg.d_model, dt)
        p["moe"] = moe_mod.moe_init(keys[2], _moe_spec(cfg), dt)
    elif ffn != "none":
        raise ValueError(f"unknown ffn {ffn!r}")
    return p


def _block_cache(cfg: ModelConfig, mixer: str, batch: int, max_len: int):
    dt = _dtype(cfg)
    hd = cfg.resolved_head_dim
    if mixer in ("attn", "attn_local"):
        return attn.make_cache(batch, max_len, cfg.n_kv_heads, hd, dt)
    if mixer == "dec":
        c = attn.make_cache(batch, max_len, cfg.n_kv_heads, hd, dt)
        # cross K/V computed once at prefill, reused each decode step
        c["ck"] = jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, hd), dt)
        c["cv"] = jnp.zeros((batch, cfg.n_frames, cfg.n_kv_heads, hd), dt)
        return c
    if mixer == "mamba":
        return mb.mamba_init_state(_mamba_spec(cfg), batch, dt)
    if mixer == "mlstm":
        return xl.mlstm_init_state(_xlstm_spec(cfg), batch)
    if mixer == "slstm":
        return xl.slstm_init_state(_xlstm_spec(cfg), batch)
    return {"_empty": jnp.zeros((batch,), jnp.int8)}  # bidir/cross: stateless


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------


def _block_apply(p, x, cfg: ModelConfig, mixer: str, ffn: str, *,
                 positions, cache=None, cross_src=None, abft=None,
                 invariants: bool = False):
    """Returns (x, new_cache, aux_loss, inv_ok, moe_stats).

    ``invariants=True`` runs each rmsnorm through its second-moment
    construction check (models.layers surface drills); ``inv_ok`` is the
    AND of every check, constant True when checks are off.  ``moe_stats``
    is the held-expert layer's `moe.MOE_STATS` (zeros for other blocks).
    """
    aux = jnp.zeros((), jnp.float32)
    ok = jnp.array(True)
    stats = _no_moe_stats()

    def norm(pn, xx):
        if invariants:
            return rmsnorm_apply(pn, xx, cfg.norm_eps, check=True)
        return rmsnorm_apply(pn, xx, cfg.norm_eps), jnp.array(True)

    h, ok1 = norm(p["norm1"], x)
    ok &= ok1
    new_cache = cache
    if mixer in ("attn", "attn_local", "attn_bidir"):
        spec = _attn_spec(cfg, mixer)
        y, new_cache = attn.attn_apply(
            p["attn"], h, spec, positions=positions,
            causal=(mixer != "attn_bidir"), cache=cache, abft=abft)
    elif mixer == "cross":
        spec = _attn_spec(cfg, mixer)
        y, _ = attn.attn_apply(p["attn"], h, spec, positions=positions,
                               causal=False, cross_kv=cross_src, abft=abft)
    elif mixer == "dec":
        spec = _attn_spec(cfg, "attn")
        y, new_cache = attn.attn_apply(
            p["attn"], h, spec, positions=positions, causal=True,
            cache={k: cache[k] for k in ("k", "v", "index")} if cache else None,
            abft=abft)
        if cache is not None:
            new_cache = {**cache, **new_cache}
        x = x + y
        hc, okc = norm(p["norm_c"], x)
        ok &= okc
        cspec = _attn_spec(cfg, "cross")
        if cross_src is not None:
            yc, _ = attn.attn_apply(p["cross"], hc, cspec, positions=positions,
                                    causal=False, cross_kv=cross_src, abft=abft)
            if cache is not None:  # stash cross K/V for decode
                from repro.models.layers import linear_apply
                k = linear_apply(p["cross"]["wk"], cross_src, abft)
                v = linear_apply(p["cross"]["wv"], cross_src, abft)
                hd = cspec.head_dim
                new_cache["ck"] = k.reshape(k.shape[0], -1, cspec.n_kv, hd).astype(new_cache["ck"].dtype)
                new_cache["cv"] = v.reshape(v.shape[0], -1, cspec.n_kv, hd).astype(new_cache["cv"].dtype)
        else:  # decode: attend over cached cross K/V
            yc = _cross_from_cache(p["cross"], hc, cspec, cache)
        y = yc
    elif mixer == "mamba":
        spec = _mamba_spec(cfg)
        if cache is None:
            y = mb.mamba_apply(p["mamba"], h, spec, abft=abft)
        elif h.shape[1] == 1:
            y, new_cache = mb.mamba_decode_step(p["mamba"], h, cache, spec, abft)
        else:  # prefill: emit the post-sequence state for decode
            y, st = mb.mamba_apply(p["mamba"], h, spec, abft=abft,
                                   return_state=True)
            new_cache = {"h": st["h"], "conv": st["conv"].astype(cache["conv"].dtype)}
    elif mixer == "mlstm":
        spec = _xlstm_spec(cfg)
        if cache is None:
            y = xl.mlstm_apply(p["mlstm"], h, spec, abft=abft)
        elif h.shape[1] == 1:
            y, new_cache = xl.mlstm_decode_step(p["mlstm"], h, cache, spec, abft)
        else:
            y, new_cache = xl.mlstm_apply(p["mlstm"], h, spec, abft=abft,
                                          return_state=True)
    elif mixer == "slstm":
        spec = _xlstm_spec(cfg)
        if cache is None:
            y = xl.slstm_apply(p["slstm"], h, spec, abft=abft)
        elif h.shape[1] == 1:
            y, new_cache = xl.slstm_decode_step(p["slstm"], h, cache, spec, abft)
        else:
            y, new_cache = xl.slstm_apply(p["slstm"], h, spec, abft=abft,
                                          return_state=True)
    else:
        raise ValueError(mixer)
    x = x + y
    if ffn in ("dense", "moe"):
        h2, ok2 = norm(p["norm2"], x)
        ok &= ok2
        if ffn == "dense":
            y2 = mlp_apply(p["mlp"], h2, activation=cfg.activation, abft=abft)
        elif cfg.experts_held:
            y2, aux, stats = moe_mod.moe_held_apply(p["moe"], h2,
                                                    _moe_spec(cfg), abft)
        else:
            y2, aux = moe_mod.moe_apply(p["moe"], h2, _moe_spec(cfg), abft)
        x = x + y2
    return x, new_cache, aux, ok, stats


def _cross_from_cache(p_cross, h, spec, cache):
    """Decode-time cross-attention over cached encoder K/V."""
    from repro.models.layers import linear_apply
    b, sq, _ = h.shape
    q = linear_apply(p_cross["wq"], h).reshape(b, sq, spec.n_heads, spec.head_dim)
    k, v = cache["ck"], cache["cv"]
    g = spec.n_heads // spec.n_kv
    qh = q.reshape(b, sq, spec.n_kv, g, spec.head_dim)
    mask = jnp.ones((sq, k.shape[1]), bool)
    o = attn._sdpa_dense(qh, k, v, scale=spec.head_dim ** -0.5,
                         softcap=spec.softcap, mask=mask)
    o = o.reshape(b, sq, spec.n_heads * spec.head_dim).astype(h.dtype)
    return linear_apply(p_cross["wo"], o)


# ---------------------------------------------------------------------------
# Model init / forward / decode
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 4 + len(cfg.layout))
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(keys[1], cfg.d_model, cfg.vocab_size,
                                        dtype=dt)
    if cfg.n_enc_layers:  # whisper encoder (+ learned positions for frames)
        ek = jax.random.split(keys[2], cfg.n_enc_layers)
        params["encoder"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_block_init(ek[i], cfg, "attn_bidir", "dense")
              for i in range(cfg.n_enc_layers)])
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dt)
    groups = []
    for gi, (pattern, repeats) in enumerate(cfg.layout):
        gkey = jax.random.split(keys[3 + gi], repeats)
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[{f"b{bi}": _block_init(jax.random.fold_in(gkey[r], bi), cfg,
                                     mixer, ffn)
               for bi, (mixer, ffn) in enumerate(pattern)}
              for r in range(repeats)])
        groups.append(stacked)
    params["groups"] = groups
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    groups = []
    for pattern, repeats in cfg.layout:
        slots = {}
        for bi, (mixer, ffn) in enumerate(pattern):
            one = _block_cache(cfg, mixer, batch, max_len)
            slots[f"b{bi}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (repeats,) + x.shape), one)
        groups.append(slots)
    return {"groups": groups}


def _run_groups(params, x, cfg: ModelConfig, *, positions, cache,
                cross_src, abft, remat: bool, x_sharding=None,
                invariants: bool = False):
    """Scan every layout group; returns (x, new_cache, aux_total, inv_ok,
    moe_stats).

    The cache rides in the scan CARRY (indexed by the layer counter), not in
    xs/ys: while-loop carries alias in place, so a decode step updates the
    KV cache without materializing a second stacked copy (xs->ys streaming
    measured ~2.5x the cache size in temps).  A paged block's cache (page
    pools and a page table, `attention.attn_apply`'s paged decode) is only
    read, each layer its own pages by the layer counter: it stays out of
    the carry, and the block's new K/V rows come out as ys.
    """
    new_groups = []
    aux_total = jnp.zeros((), jnp.float32)
    ok_total = jnp.array(True)
    # only a held-expert layer has counters to carry
    stats_total = _no_moe_stats() if cfg.experts_held else None
    for gi, (pattern, repeats) in enumerate(cfg.layout):
        gparams = params["groups"][gi]
        gcache = cache["groups"][gi] if cache is not None else None
        paged = {b: c for b, c in (gcache or {}).items() if "table" in c}
        if gcache is not None:
            gcache = {b: c for b, c in gcache.items() if b not in paged}

        def body(carry, xs, _pattern=pattern):
            xx, aux_acc, ok_acc, st_acc, cstack = carry
            pslice, idx = xs
            rows = {}
            if x_sharding is not None:
                # pin the residual stream so the auto-partitioner doesn't
                # drift to batch-replicated layouts inside the scan
                xx = jax.lax.with_sharding_constraint(xx, x_sharding)
            for bi, (mixer, ffn) in enumerate(_pattern):
                name = f"b{bi}"
                if name in paged:
                    c_in = {**paged[name], "layer": idx}
                elif cstack is not None:
                    c_in = jax.tree.map(
                        lambda c: lax.dynamic_index_in_dim(c, idx, 0,
                                                           keepdims=False),
                        cstack[name])
                else:
                    c_in = None
                xx, c_out, aux, ok_b, st_b = _block_apply(
                    pslice[f"b{bi}"], xx, cfg, mixer, ffn,
                    positions=positions, cache=c_in, cross_src=cross_src,
                    abft=abft, invariants=invariants)
                aux_acc = aux_acc + aux
                ok_acc = ok_acc & ok_b
                if st_acc is not None:
                    st_acc = merge_moe_stats(st_acc, st_b)
                if name in paged:
                    rows[name] = c_out
                elif cstack is not None and c_out is not None:
                    cstack = dict(cstack)
                    cstack[name] = jax.tree.map(
                        lambda full, new: lax.dynamic_update_index_in_dim(
                            full, new.astype(full.dtype), idx, 0),
                        cstack[name], c_out)
            return (xx, aux_acc, ok_acc, st_acc, cstack), rows

        if remat:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if remat == "dots" else
                      jax.checkpoint_policies.nothing_saveable)
            body = jax.checkpoint(body, policy=policy)
        (x, aux_total, ok_total, stats_total, new_gcache), rows = lax.scan(
            body, (x, aux_total, ok_total, stats_total, gcache),
            (gparams, jnp.arange(repeats)))
        new_groups.append(None if new_gcache is None
                          else {**new_gcache, **rows})
    new_cache = {"groups": new_groups} if cache is not None else None
    return x, new_cache, aux_total, ok_total, stats_total


def _encode_frames(params, frames, cfg: ModelConfig):
    """Whisper encoder over precomputed frame embeddings (conv stub)."""
    x = frames

    def body(carry, pslice):
        xx = carry
        xx = _block_apply(pslice, xx, cfg, "attn_bidir", "dense",
                          positions=jnp.arange(x.shape[1]))[0]
        return xx, None

    x, _ = lax.scan(body, x, params["encoder"])
    return rmsnorm_apply(params["enc_norm"], x, cfg.norm_eps)


def forward(params, tokens, cfg: ModelConfig, *, positions=None, cache=None,
            frames=None, img_emb=None, abft=None, remat: bool = False,
            logits_sharding=None, x_sharding=None, return_hidden: bool = False,
            invariants: bool = False, moe_stats: bool = False):
    """Train/prefill forward. tokens: [B,S] -> logits [B,S,V] fp32.

    frames: [B, n_frames, d_model] (whisper stub input);
    img_emb: [B, n_img_tokens, d_model] (vlm stub input).
    return_hidden: skip the unembedding and return the post-final-norm
    hidden state [B,S,D] instead of logits — the serving engine uses this
    to route the final projection through its own checksum-verified
    cross-shard reduction (serve.engine).
    invariants: run the models.layers construction invariants (embedding
    gather checksum column, every rmsnorm second moment) and return a
    4-tuple (..., inv_ok) — StepOptions.invariant_checks surfaces it as
    metrics["inv_ok"].
    moe_stats: append the held-expert layers' `moe.MOE_STATS`, summed
    over the layers (the largest group: the largest of any layer).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    if invariants:
        x, ok_embed = embed_apply(params["embed"], tokens, check=True)
    else:
        x = embed_apply(params["embed"], tokens)
        ok_embed = jnp.array(True)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    cross_src = None
    if cfg.n_enc_layers and frames is not None:
        cross_src = _encode_frames(params, frames, cfg)
    elif img_emb is not None:
        cross_src = img_emb
    x, new_cache, aux, ok_run, stats = _run_groups(
        params, x, cfg, positions=positions, cache=cache,
        cross_src=cross_src, abft=abft, remat=remat, x_sharding=x_sharding,
        invariants=invariants)
    if invariants:
        x, ok_fn = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps,
                                 check=True)
    else:
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        ok_fn = jnp.array(True)
    inv_ok = ok_embed & ok_run & ok_fn
    if moe_stats and stats is None:
        stats = _no_moe_stats()
    extra = ((inv_ok,) if invariants else ()) + ((stats,) if moe_stats
                                                  else ())
    if return_hidden:
        return (x, new_cache, aux) + extra
    head = params.get("lm_head")
    if head is None:
        logits = (x.astype(jnp.float32) @
                  params["embed"]["table"].astype(jnp.float32).T)
        logits = softcap_fn(logits, cfg.final_softcap)
    else:
        logits = unembed_apply(head, x, softcap=cfg.final_softcap, abft=abft)
    if logits_sharding is not None:
        logits = jax.lax.with_sharding_constraint(logits, logits_sharding)
    return (logits, new_cache, aux) + extra


def decode_step(params, token, pos, cache, cfg: ModelConfig, *,
                img_emb=None, abft=None, return_hidden: bool = False):
    """One-token decode. token: [B,1]; pos: scalar (lockstep batch) or
    [B] vector (continuous batching: per-slot positions).
    return_hidden: return the post-final-norm hidden [B,1,D] instead of
    logits [B,V] (the serving engine's verified-unembed path)."""
    if pos.ndim == 0:
        positions = pos[None]          # shared [1]
    else:
        positions = pos[:, None]       # per-slot [B, 1]
    out, new_cache, _ = forward(
        params, token, cfg, positions=positions, cache=cache,
        img_emb=img_emb, abft=abft, return_hidden=return_hidden)
    if return_hidden:
        return out, new_cache          # [B, 1, D]
    return out[:, -1], new_cache


def loss_fn(params, tokens, labels, cfg: ModelConfig, *, frames=None,
            img_emb=None, abft=None, remat: bool = False,
            aux_weight: float = 0.01, logits_sharding=None, x_sharding=None,
            invariants: bool = False, moe_stats: bool = False):
    """Scalar LM loss; with ``invariants`` or ``moe_stats`` returns
    ``(loss, extras)`` (value_and_grad has_aux form), extras holding
    ``inv_ok`` and/or ``moe`` (the `forward` values)."""
    out = forward(params, tokens, cfg, frames=frames,
                  img_emb=img_emb, abft=abft, remat=remat,
                  logits_sharding=logits_sharding,
                  x_sharding=x_sharding, invariants=invariants,
                  moe_stats=moe_stats)
    logits, aux = out[0], out[2]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll) + aux_weight * aux
    if not (invariants or moe_stats):
        return loss
    extras = dict(zip([n for n, on in (("inv_ok", invariants),
                                       ("moe", moe_stats)) if on], out[3:]))
    return loss, extras


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(cfg: ModelConfig, params=None) -> int:
    """N for MODEL_FLOPS: non-embedding params, experts scaled by k/E."""
    if params is None:
        params = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
    total = 0
    embed = params["embed"]["table"].size
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        size = leaf.size
        if "table" in keys:
            continue
        if any(k in ("gate", "up", "down") for k in keys) and "moe" in keys:
            size = int(size * cfg.top_k / max(cfg.n_experts, 1))
        total += size
    return total
