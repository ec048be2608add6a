"""Flash-attention forward Pallas kernel with an in-kernel ABFT checksum.

The XLA-level chunked flash (models/attention.py) streams its fp32
accumulator through HBM once per KV chunk — the §Perf roofline shows
prefill cells memory-bound on exactly that traffic.  This kernel is the
TPU-native fix: the (m, l, acc) online-softmax state lives in VMEM scratch
for the whole KV sweep; HBM sees only Q/K/V once and O once.

Grid: (B*H, Sq/bq, Sk/bk), KV-chunk innermost.  Heads (all of them, for
GQA the already-repeated query heads) are folded into the leading BH axis
only — q-groups are NOT folded into the q-tile rows, because positional
masking is computed from the q-tile row index and folded groups would
alias distinct head rows onto the same sequence position.  Causal/window
masking is positional, computed from the grid indices; a window bounds
the distance in BOTH directions, so ``causal=False`` with a window is a
symmetric local-attention band rather than "everything in the future".

Fault tolerance (kernels.flash_attention surface, promise ``tolerance``):
softmax kills Huang-Abraham linearity for the QK^T stage, but the PV
inner product is still a GEMM — so a column checksum on V (vc = Σ_d v)
rides the online-softmax recurrence in VMEM exactly like
``abft_matmul_pallas``'s §4.3 epilogue trick:

    cs  <- cs * corr + p @ vc        (must equal Σ_d acc at all times)
    l2  <- l2 * corr + p @ 1         (MXU-path duplicate of the VPU l)

and the epilogue emits two per-tile residuals with O:

    r_pv = max_rows |Σ_d o − cs/l| / (|cs/l| + 1)   — catches acc flips
    r_l  = max_rows |l2/l − 1|                       — catches l flips
           (post-normalization softmax rows must sum to one)

A flip in ``m`` is self-cancelling in the output (o = acc/l is invariant
to a common exp(-m) factor), so the envelope intentionally does not chase
it.  ``flash_attention_checked`` reads the residuals on the host and
recomputes only the flagged (batch·head, q-tile) tiles against a dense
reference — detect-and-recompute-tile, not full recompute.

Structural accounting (per [B,S,H,D] layer, vs the XLA scan):
    HBM bytes:  kernel ~ 2·B·S·(H+2KV)·D·bytes   (Q,K,V in + O out)
                XLA    ~ kernel + 2·nk·B·H·S·D·4 (acc carry per chunk)
    => the kernel removes the dominant prefill memory-term contribution.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.chaos.faults import register_surface

__all__ = ["flash_attention_pallas", "flash_attention_checked",
           "FlashCheckReport", "FLASH_CHECK_TOL"]

NEG_INF = -1e30
FLASH_CHECK_TOL = 1e-3
# per-tile stats block: one full (8, 128) TPU tile, every row the same
# (r_pv, r_l, 0, ...) lane vector — a (1, 128) block is refused by the TPU
# lowering once there is more than one q-tile
_STATS_TILE = (8, 128)

register_surface(
    "kernels.flash_attention", owner=__name__, protected=True,
    promise="tolerance",
    detector="in-kernel V-column checksum reduced from the VMEM acc "
             "scratch (r_pv epilogue residual) plus the post-"
             "normalization softmax rowsum==1 invariant carried as an "
             "MXU-path duplicate of l (r_l residual); trip triggers "
             "dense recomputation of only the flagged q-tile",
    kinds=("flash_state_flip",),
    note="m flips are self-cancelling in o = acc/l and intentionally "
         "outside the envelope")


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest,
            k_steps: int, bq: int, bk: int, scale: float, causal: bool,
            window, softcap, checksum: bool, inject, pipeline: bool):
    if checksum:
        stats_ref, m_ref, l_ref, acc_ref, cs_ref, l2_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    kk = pl.program_id(2)
    qi = pl.program_id(1)
    bh = pl.program_id(0)
    # pipelined grid: the normalize/residual epilogue gets a dot-free extra
    # step (kk == k_steps) whose K/V block index is clamped to the last KV
    # chunk — Pallas skips the re-fetch (block index unchanged) and instead
    # prefetches the NEXT q-tile's first K/V chunk while the VPU divides,
    # so the epilogue cost is hidden under DMA exactly as in abft_matmul.
    epi_step = k_steps if pipeline else k_steps - 1

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if checksum:
            cs_ref[...] = jnp.zeros_like(cs_ref)
            l2_ref[...] = jnp.zeros_like(l2_ref)

    @pl.when(kk <= k_steps - 1)
    def _recurrence():
        q = q_ref[0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            s_capped = softcap * jnp.tanh(s / softcap)
        else:
            s_capped = s

        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            # two-sided band: without the second bound a non-causal window
            # admitted arbitrarily-far FUTURE keys
            mask &= (q_pos - k_pos) < window
            mask &= (k_pos - q_pos) < window
        sm = jnp.where(mask, s_capped, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sm, axis=-1, keepdims=True))
        # mask p explicitly: on a fully-masked tile m_new stays NEG_INF and
        # exp(s - m_new) = exp(0) = 1 would pollute l/acc (reachable now that
        # a two-sided window can put a fully-masked tile first in kk order)
        p = jnp.where(mask, jnp.exp(sm - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        if checksum:
            vc = jnp.sum(v, axis=-1, keepdims=True)           # [bk, 1]
            cs_ref[...] = cs_ref[...] * corr + jnp.dot(
                p, vc, preferred_element_type=jnp.float32)
            l2_ref[...] = l2_ref[...] * corr + jnp.dot(
                p, jnp.ones((bk, 1), jnp.float32),
                preferred_element_type=jnp.float32)

        if inject is not None:
            inj_qi, inj_kk, delta, target = inject
            hit = ((bh == 0) & (qi == inj_qi) & (kk == inj_kk))

            @pl.when(hit)
            def _inject():
                if target == "l":
                    l_ref[0, 0] = l_ref[0, 0] + delta
                else:
                    acc_ref[0, 0] = acc_ref[0, 0] + delta

    @pl.when(kk == epi_step)
    def _epilogue():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / l_safe
        o_ref[0] = o.astype(o_ref.dtype)
        if checksum:
            live = l_ref[...] > 0.0
            want = cs_ref[...] / l_safe
            r_pv = jnp.where(
                live,
                jnp.abs(jnp.sum(o, axis=-1, keepdims=True) - want) /
                (jnp.abs(want) + 1.0), 0.0)
            r_l = jnp.where(live, jnp.abs(l2_ref[...] / l_safe - 1.0), 0.0)
            lane = jax.lax.broadcasted_iota(jnp.int32, _STATS_TILE, 1)
            stats_ref[0, 0] = jnp.where(
                lane == 0, jnp.max(r_pv),
                jnp.where(lane == 1, jnp.max(r_l), 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "bq", "bk",
                     "interpret", "checksum", "inject", "pipeline"))
def _flash_call(q, k, v, *, scale, causal, window, softcap, bq, bk,
                interpret, checksum, inject, pipeline=True):
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    k_steps = sk // bk
    grid = (bh, sq // bq, k_steps + (1 if pipeline else 0))
    kv_block = (lambda b, i, kk: (b, jnp.minimum(kk, k_steps - 1), 0)) \
        if pipeline else (lambda b, i, kk: (b, kk, 0))
    kernel = functools.partial(
        _kernel, k_steps=k_steps, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, softcap=softcap, checksum=checksum, inject=inject,
        pipeline=pipeline)
    out_specs = pl.BlockSpec((1, bq, d), lambda b, i, kk: (b, i, 0))
    out_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
    scratch = [
        pltpu.VMEM((bq, 1), jnp.float32),    # m
        pltpu.VMEM((bq, 1), jnp.float32),    # l
        pltpu.VMEM((bq, d), jnp.float32),    # acc
    ]
    if checksum:
        out_specs = [out_specs, pl.BlockSpec(
            (1, 1) + _STATS_TILE, lambda b, i, kk: (b, i, 0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct(
            (bh, sq // bq) + _STATS_TILE, jnp.float32)]
        scratch += [
            pltpu.VMEM((bq, 1), jnp.float32),    # cs  (Σ_d acc shadow)
            pltpu.VMEM((bq, 1), jnp.float32),    # l2  (MXU-path l)
        ]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_block),
            pl.BlockSpec((1, bk, d), kv_block),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    if checksum:
        o, stats = out
        return o, stats[:, :, 0, :]     # [BH, Sq/bq, 128] stats rows
    return out


def flash_attention_pallas(
    q: jax.Array,       # [BH, Sq, D]  (batch x heads folded)
    k: jax.Array,       # [BH, Sk, D]
    v: jax.Array,       # [BH, Sk, D]
    *,
    scale: float,
    causal: bool = True,
    window=None,
    softcap=None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
    pipeline: bool = True,
):
    return _flash_call(q, k, v, scale=scale, causal=causal, window=window,
                       softcap=softcap, bq=bq, bk=bk, interpret=interpret,
                       checksum=False, inject=None, pipeline=pipeline)


@dataclasses.dataclass(frozen=True)
class FlashCheckReport:
    ok: bool                              # no residual tripped
    detected: Tuple[Tuple[int, int], ...]  # flagged (bh, q-tile) tiles
    repaired: int                         # tiles recomputed dense
    max_pv_residual: float
    max_rowsum_residual: float


def _dense_tile(q, k, v, q0, scale, causal, window, softcap):
    """Dense oracle for one q-tile (kernel mask semantics, fp32)."""
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T,
                preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qp = q0 + jnp.arange(q.shape[0])[:, None]
    kp = jnp.arange(k.shape[0])[None, :]
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= (qp - kp) < window
        mask &= (kp - qp) < window
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.dot(p, v.astype(jnp.float32)) / jnp.maximum(l, 1e-30)


def flash_attention_checked(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    scale: float,
    causal: bool = True,
    window=None,
    softcap=None,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
    tol: float = FLASH_CHECK_TOL,
    inject: Optional[Tuple[int, int, float, str]] = None,
    pipeline: bool = True,
):
    """Checksummed flash attention: (o, FlashCheckReport).

    Runs the kernel with the cs/l2 checksum recurrence live; any q-tile
    whose epilogue residual exceeds ``tol`` is recomputed against the
    dense per-tile oracle and patched in place.  ``inject`` is the chaos
    drill hook: a static ``(qi, kk, delta, target)`` tuple adds ``delta``
    to the named VMEM scratch ("acc" or "l") of tile (bh=0, qi) at KV
    step kk — corrupting the state mid-sweep exactly like a DRAM/SRAM
    flip would.
    """
    o, stats = _flash_call(
        q, k, v, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, interpret=interpret, checksum=True, inject=inject,
        pipeline=pipeline)
    st = np.asarray(stats)
    # a NaN-contaminated tile must read as tripped, not compare false
    st = np.where(np.isnan(st), np.inf, st)
    r_pv, r_l = st[..., 0], st[..., 1]
    bad = np.argwhere((r_pv > tol) | (r_l > tol))
    detected = tuple((int(b), int(i)) for b, i in bad)
    if detected:
        for b, i in detected:
            fixed = _dense_tile(q[b, i * bq:(i + 1) * bq], k[b], v[b],
                                i * bq, scale, causal, window, softcap)
            o = o.at[b, i * bq:(i + 1) * bq].set(fixed.astype(o.dtype))
    report = FlashCheckReport(
        ok=not detected, detected=detected, repaired=len(detected),
        max_pv_residual=float(r_pv.max()), max_rowsum_residual=float(r_l.max()))
    return o, report
