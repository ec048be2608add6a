"""Mixture-of-Experts FFN with grouped sort-based dispatch (EP-shardable).

Dispatch is *grouped* (GShard lineage): tokens are reshaped to
[G, Tg, D] groups; G maps onto the data-parallel mesh axes so every group's
sort/rank/scatter is device-local, and the dispatch buffer
[G, E, C, D] (G sharded over `data`, E over `model`) turns the scatter into
XLA's all-to-all dispatch collective — the same communication structure real
TPU MoE systems use.

Per group (jit-friendly, no [T, E] one-hots):
  1. router top-k -> (expert_id, weight) per token-slot, N = Tg*k assignments
  2. stable argsort by expert id; rank-within-expert = pos - group_start
     (group starts via batched searchsorted — O(E log N), no one-hot)
  3. scatter into the [E, C, D] capacity buffer (overflow drops, Switch-style)
  4. expert einsum [g,E,C,D] x [E,D,F]
  5. gather back by (expert, rank), weighted-combine the k slots.

Capacity C = ceil(Tg*k/E * capacity_factor); small groups (decode) get a
dropless floor C = N so routing never silently changes decode results.

`moe_held_apply` is the expert-parallel layer of one chip's share: it is
told which experts it holds (``experts_held`` from ``expert_offset``),
routes every token over all ``n_experts`` at the published top-k, and
computes its held experts' part of the result, dropless: every assignment
that lands on a held expert gets a row of a static worst-case buffer
(tokens x min(top_k, experts_held) rows, plus each expert's padding to a
whole row tile), rows sorted by expert, and the gate/up and down products
run as grouped ABFT products over that buffer (`core.abft_gemm.
abft_grouped_matmul`, the per-expert encoding ``[W_e, W_e @ w_r]`` and a
verify per row).  Assignments to experts held elsewhere add nothing here;
on one chip the layer runs without its exchange.  Dispatch and combine
are gathers both ways (custom VJPs), never scatters of activations.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.chaos.faults import register_surface
from repro.core.abft_gemm import abft_grouped_matmul, encode_weight
from repro.kernels import ops as kops
from repro.models.layers import linear_apply, linear_init

__all__ = ["MoESpec", "MOE_STATS", "balance_loss", "moe_init", "moe_apply",
           "moe_held_apply"]

HIGHEST = jax.lax.Precision.HIGHEST

register_surface(
    "models.moe.expert", owner=__name__, protected=True,
    promise="tolerance",
    detector="per-expert encoding W_F[e] = [W_e, W_e @ w_r] of the gate/up "
             "and down weights; the grouped kernel's epilogue reduces each "
             "row's residual Y @ w_r - Y_cs from its accumulator (the jnp "
             "twin: the verify einsum) and every row over tolerance trips",
    kinds=(),
    note="moe_held_apply's expert products; trips surface as the train "
         "step's moe_trips metric, correct mode repairs a single element "
         "per product; drilled by tests/test_moe_held.py (a bit flipped in "
         "an expert product's output), not yet a campaign fault kind")
register_surface(
    "models.moe.router", owner=__name__, protected=True,
    promise="tolerance",
    detector="the router projection runs through models.layers."
             "linear_apply, so core.abft_gemm.abft_matmul's checksum "
             "columns and residual test cover it as any dense projection",
    kinds=(),
    note="top-k selection and the softmax after the projection carry no "
         "checksum")

# per-layer statistics moe_held_apply returns, in this order
MOE_STATS = ("moe_assignments", "moe_rows_routed", "moe_rows_computed",
             "moe_max_group", "moe_trips")


class MoESpec(NamedTuple):
    d_model: int
    d_ff: int            # per-expert hidden dim
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    groups: int = 1      # dispatch groups (set to the DP shard count)
    activation: str = "silu"
    experts_held: int = 0    # this chip's experts (0: all, capacity layer)
    expert_offset: int = 0   # the first of them


def moe_init(key, s: MoESpec, dtype=jnp.float32):
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale_in = s.d_model ** -0.5
    scale_out = s.d_ff ** -0.5
    e = s.experts_held or s.n_experts
    return {
        "router": linear_init(kr, s.d_model, s.n_experts, dtype=jnp.float32),
        "gate": (jax.random.normal(kg, (e, s.d_model, s.d_ff))
                 * scale_in).astype(dtype),
        "up": (jax.random.normal(ku, (e, s.d_model, s.d_ff))
               * scale_in).astype(dtype),
        "down": (jax.random.normal(kd, (e, s.d_ff, s.d_model))
                 * scale_out).astype(dtype),
    }


def balance_loss(probs, top_e, n_experts: int):
    """Load-balancing loss, as Switch Transformer (arXiv:2101.03961 eq. 4)
    and the Hugging Face Qwen3-MoE / Mixtral ``load_balancing_loss_func``
    write it: ``n_experts * sum_e f_e * P_e`` with ``f_e`` the assignments
    to expert e per token (over every top-k slot, not divided by k: the
    f_e sum to top_k) and ``P_e`` the mean router probability.  probs:
    [..., T, E]; top_e: [..., T, k]; leading axes (dispatch groups) are
    averaged.  f_e passes no gradient."""
    t = probs.shape[-2]
    p_e = jnp.mean(probs, axis=-2)                                 # [..,E]
    f_e = jnp.sum(jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32),
                  axis=(-3, -2)) / t                               # [..,E]
    return n_experts * jnp.mean(jnp.sum(f_e * p_e, axis=-1))


def moe_apply(p, x, s: MoESpec, abft=None):
    """x: [B, S, D] -> (y: [B, S, D], aux_loss scalar)."""
    b, t, d = x.shape
    n_tok = b * t
    g = s.groups if n_tok % max(s.groups, 1) == 0 else 1
    tg = n_tok // g
    xg = x.reshape(g, tg, d)

    logits = linear_apply(p["router"], xg.astype(jnp.float32))   # [G,Tg,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, s.top_k)                 # [G,Tg,k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    aux = balance_loss(probs, top_e, s.n_experts)

    # ---- grouped sort-based dispatch ---------------------------------------
    n = tg * s.top_k
    flat_e = top_e.reshape(g, n)                                  # [G,N]
    flat_w = top_w.reshape(g, n)
    tok_of = jnp.broadcast_to(
        (jnp.arange(n, dtype=jnp.int32) // s.top_k)[None], (g, n))
    order = jnp.argsort(flat_e, axis=-1, stable=True)             # [G,N]
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    sorted_tok = jnp.take_along_axis(tok_of, order, axis=-1)
    starts = jax.vmap(
        lambda a: jnp.searchsorted(a, jnp.arange(s.n_experts), side="left")
    )(sorted_e)                                                   # [G,E]
    rank = (jnp.arange(n, dtype=jnp.int32)[None]
            - jnp.take_along_axis(starts, sorted_e, axis=-1))     # [G,N]

    if n <= 4096:
        capacity = n  # dropless floor: decode/tiny batches stay exact
    else:
        capacity = max(math.ceil(n / s.n_experts * s.capacity_factor),
                       s.top_k)
    keep = rank < capacity
    safe_rank = jnp.where(keep, rank, capacity - 1)
    gi = jnp.broadcast_to(jnp.arange(g)[:, None], (g, n))
    src = (jnp.take_along_axis(xg, sorted_tok[..., None], axis=1)
           * keep[..., None].astype(x.dtype))                     # [G,N,D]
    buf = jnp.zeros((g, s.n_experts, capacity, d), x.dtype)
    buf = buf.at[gi, sorted_e, safe_rank].add(src)

    # ---- expert compute (E sharded over the EP/model axis) -----------------
    act = jax.nn.silu if s.activation == "silu" else jax.nn.gelu
    gate = jnp.einsum("gecd,edf->gecf", buf, p["gate"].astype(x.dtype))
    up = jnp.einsum("gecd,edf->gecf", buf, p["up"].astype(x.dtype))
    h = act(gate) * up
    out = jnp.einsum("gecf,efd->gecd", h, p["down"].astype(x.dtype))

    # ---- combine ------------------------------------------------------------
    gathered = out[gi, sorted_e, safe_rank] * keep[..., None].astype(x.dtype)
    w_sorted = jnp.take_along_axis(flat_w, order, axis=-1)
    y_sorted = gathered * w_sorted[..., None].astype(x.dtype)
    yg = jnp.zeros((g, tg, d), x.dtype)
    yg = yg.at[gi, sorted_tok].add(y_sorted)
    return yg.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# the held-expert layer (one chip's share, dropless)
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _dispatch(xf, src_tok, pos):
    """rows[r] = xf[src_tok[r]] (0 where src_tok[r] == T: a padding row)."""
    return jnp.take(xf, src_tok, axis=0, mode="fill", fill_value=0)


def _dispatch_fwd(xf, src_tok, pos):
    return _dispatch(xf, src_tok, pos), (src_tok, pos, xf.shape[0])


def _dispatch_bwd(res, d_rows):
    # d xf[t] = sum over t's slots of the row each was given: a gather
    src_tok, pos, _ = res
    d = jnp.zeros(pos.shape[:1] + d_rows.shape[1:], jnp.float32)
    for j in range(pos.shape[1]):
        d = d + jnp.take(d_rows, pos[:, j], axis=0, mode="fill",
                         fill_value=0).astype(jnp.float32)
    return d.astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w, pos, slot):
    """y[t] = sum_j w[t, j] * out[pos[t, j]] (pos == rows: nothing here).
    ``slot[r]`` is the flat (t * k + j) assignment of row r, T * k for a
    padding row."""
    y = jnp.zeros(w.shape[:1] + out.shape[1:], jnp.float32)
    for j in range(w.shape[1]):
        y = y + w[:, j, None] * jnp.take(out, pos[:, j], axis=0, mode="fill",
                                         fill_value=0)
    return y


def _combine_fwd(out, w, pos, slot):
    return _combine(out, w, pos, slot), (out, w, pos, slot)


def _combine_bwd(res, dy):
    out, w, pos, slot = res
    k = w.shape[1]
    w_row = jnp.take(w.reshape(-1), slot, mode="fill", fill_value=0)
    d_out = w_row[:, None] * jnp.take(dy, slot // k, axis=0, mode="fill",
                                      fill_value=0)
    d_w = jnp.stack([jnp.sum(dy * jnp.take(out, pos[:, j], axis=0,
                                           mode="fill", fill_value=0), -1)
                     for j in range(k)], axis=1)
    return d_out.astype(out.dtype), d_w.astype(w.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_held_apply(p, x, s: MoESpec, abft=None, inject=None):
    """x: [B, S, D] -> (y [B, S, D], aux loss, stats [len(MOE_STATS)]).

    Routes over all ``s.n_experts`` (softmax, top-k, the k weights
    renormalised), keeps every assignment to the experts
    ``[expert_offset, expert_offset + experts_held)`` and computes their
    weighted part of the result.  ``p["router"]`` is [D, n_experts];
    ``p["gate"]``/``p["up"]`` [held, D, F], ``p["down"]`` [held, F, D].
    ``inject(y_f) -> y_f`` corrupts the gate/up product's output (chaos
    drill).  stats: `MOE_STATS` (assignments to held experts, buffer rows
    that received one, rows the grouped kernel computed with their tile
    padding, the largest expert's assignments, rows whose verify tripped).
    """
    b, t, d = x.shape
    n_tok = b * t
    held, k, e_all = s.experts_held, s.top_k, s.n_experts
    xf = x.reshape(n_tok, d)
    logits = linear_apply(p["router"], xf.astype(jnp.float32), abft)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [T, E]
    top_w, top_e = jax.lax.top_k(probs, k)                         # [T, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    aux = balance_loss(probs, top_e, e_all)

    # ---- held assignments, sorted by expert, rows padded to whole tiles ----
    n = n_tok * k
    local = top_e.reshape(n) - s.expert_offset
    key = jnp.where((local >= 0) & (local < held), local, held)    # [N]
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    counts = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    bm = kops.GROUPED_BM
    m = -(-(n_tok * min(k, held) + held * (bm - 1)) // bm) * bm
    lay = kops.grouped_layout(counts, m, bm)
    e_s = jnp.minimum(sorted_key, held - 1)
    first = jnp.cumsum(counts) - counts          # sorted position of e's 1st
    dest = jnp.where(sorted_key < held,
                     lay.starts[e_s] + jnp.arange(n) - first[e_s], m)
    slot = jnp.full((m,), n, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")                      # [m]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        dest.astype(jnp.int32)).reshape(n_tok, k)                  # [T, k]
    src_tok = slot // k                                 # T for padding rows

    # ---- grouped expert products ------------------------------------------
    rows = _dispatch(xf, src_tok, pos)                             # [m, D]
    w_gu = jnp.concatenate([p["gate"], p["up"]], axis=-1)         # [H,D,2F]
    # bf16 activations against bf16 weights: every product exact in one
    # MXU pass
    exact = x.dtype == w_gu.dtype == jnp.bfloat16
    if abft is not None and abft.active:
        gu, trips_gu = abft_grouped_matmul(
            rows, encode_weight(w_gu.astype(jnp.float32), abft, HIGHEST),
            lay, abft, exact=exact, inject=inject)
    else:
        gu, _ = kops.grouped_matmul(rows, w_gu, lay, compute_dtype=x.dtype)
        trips_gu = jnp.zeros((), jnp.int32)
    act = jax.nn.silu if s.activation == "silu" else jax.nn.gelu
    h = (act(gu[:, :s.d_ff]) * gu[:, s.d_ff:]).astype(x.dtype)     # [m, F]
    if abft is not None and abft.active:
        out, trips_d = abft_grouped_matmul(
            h, encode_weight(p["down"].astype(jnp.float32), abft, HIGHEST),
            lay, abft, exact=exact)
    else:
        out, _ = kops.grouped_matmul(h, p["down"], lay, compute_dtype=x.dtype)
        trips_d = jnp.zeros((), jnp.int32)
    y = _combine(out, top_w, pos, slot).astype(x.dtype)

    stats = jnp.stack([
        jnp.sum(counts), jnp.sum(src_tok < n_tok), lay.n_used[0] * bm,
        jnp.max(counts), trips_gu + trips_d]).astype(jnp.float32)
    return y.reshape(b, t, d), aux, stats
