"""Public jit'd wrappers over the Pallas kernels.

On TPU the kernels run compiled (interpret=False); on CPU (this container)
they run in interpret mode for correctness, with a pure-XLA fallback for
shapes where the tiling would waste too much work.  ``pick_blocks`` plans the
tiling for ANY shape: ragged edges are zero-padded to the chosen MXU-aligned
blocks (zero rows/cols checksum to zero, so padding commutes with the
Huang-Abraham encoding) and the plan is chosen by a bytes-based cost model
over candidate tilings.  ``use_pallas`` is resolved once per call site;
benchmarks exercise both paths.

This module is also where the fused ABFT-GEMM family gets its gradient: the
one-shot dispatcher carries a custom VJP (plain fp32 dots, with the checksum
cotangents folded back through W_m / W_n), so model layers can run the fused
forward inside ``jax.grad``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.chaos.faults import register_surface
from repro.kernels import ref
from repro.kernels.abft_matmul import (STATS_WIDTH, abft_matmul_acc_pallas,
                                       abft_matmul_pallas)
from repro.kernels.checksum_encode import checksum_encode_pallas
from repro.kernels.grouped_matmul import (abft_grouped_matmul_pallas,
                                          abft_grouped_matmul_pallas_dw)

__all__ = [
    "BlockPlan", "GroupedLayout", "abft_matmul", "abft_matmul_acc",
    "acc_state_zeros", "checksum_encode", "grouped_layout", "grouped_matmul", "correct_from_state", "detection_eps",
    "kernel_weights", "mxu_rate", "on_tpu", "pick_blocks",
    "plan_accounting", "rank_blocks", "reduce_state", "tile_checksums",
    "vmem_bytes",
]

KERNEL_F = 2  # checksums per direction: plain sum + one weighted row

# the protection domain this module owns (repro.chaos campaigns drill it):
# the carried (ccol, crow) per-tile state of the accumulate kernel family
register_surface(
    "kernels.ops/acc_state", owner=__name__, protected=True,
    promise="tolerance",
    detector="fused verify/correct prologue of abft_matmul_acc: per-tile "
             "residual of recomputed vs carried dual checksums; "
             "concentration-gated single-element repair by masked "
             "re-computation from the carried plain-sum column checksum",
    kinds=("sdc_collective", "checksum_state_flip"),
    note="a flip in the carried DATA is located and repaired (bit-exact on "
         "integer data); a flip in the carried CHECKSUM state trips only "
         "one residual family, so it is detected but deliberately NOT "
         "repaired (repairing would corrupt healthy data) — refresh via "
         "tile_checksums instead")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_weights(m: int, f: int = KERNEL_F, dtype=jnp.float32) -> jax.Array:
    """[f, m] checkpoint matrix used by the fused kernels (row 0 = sum)."""
    return ref.default_weights(m, f, dtype=dtype)


# ---------------------------------------------------------------------------
# Tiling plan
# ---------------------------------------------------------------------------

_CANDIDATE_BLOCKS = (128, 256, 512)

# Overlap-aware time model (v4-class part): bytes and FLOPs live on
# SEPARATE resources — the HBM stream and the MXU run concurrently under
# the Pallas double-buffered pipeline, so a candidate tiling costs
#   t = max(t_hbm, t_mxu) + exposed_epilogue
# rather than bytes + flop-byte-equivalents.  The MXU rate is dtype-aware:
# fp32 matmul runs as a multi-pass bf16 emulation at ~1/8 of bf16 peak;
# int8 doubles bf16 throughput.  The VPU rate prices the checksum
# epilogue / verify-prologue reductions; with the pipelined kernel grid
# (their own dot-free steps) that work hides under the next tile's A/B
# fetch and only the remainder (``exposed_s``) lands on the critical path.
HBM_BW = 819e9                       # bytes/s
MXU_FLOPS = {                        # dtype name -> FLOP/s
    "float32": 34e12,                # ~275/8: multi-pass bf16 emulation
    "bfloat16": 197e12,
    "int8": 394e12,
}
VPU_FLOPS = 4e12                     # checksum-reduction (epilogue) rate

# Legacy single-score constant (pre-time-model planner): FLOPs per
# HBM-byte-equivalent at the fp32 emulation rate.  Kept for reference and
# external callers; ``pick_blocks`` now scores with the time model above.
MXU_FP32_FLOPS_PER_BYTE = 28.0


def mxu_rate(in_dtype) -> float:
    """Modeled MXU FLOP/s for an A/B input dtype (planner time model)."""
    dt = jnp.dtype(in_dtype)
    if dt.name in MXU_FLOPS:
        return MXU_FLOPS[dt.name]
    if jnp.issubdtype(dt, jnp.integer):
        return MXU_FLOPS["int8"]
    if dt.itemsize == 2:
        return MXU_FLOPS["bfloat16"]
    return MXU_FLOPS["float32"]


def detection_eps(dtype) -> float:
    """Dtype-aware detection epsilon for the ABFT residual tolerances.

    The carried checksums are fp32 functions of the ROUNDED stored values,
    so fp32 eps is the floor for any storage dtype (including integers,
    whose checksums are exact below 2^24); wider-rounding float storage
    (bf16/fp16) contributes its own eps when states are re-derived through
    the storage grid.  The old fp32-only constant silently over-fired on
    bf16 data and was needlessly loose nowhere — this is the single eps
    source for ``kernels`` and the ``core.abft_gemm`` residual check.
    """
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.integer):
        return float(jnp.finfo(jnp.float32).eps)
    return float(max(jnp.finfo(dt).eps, jnp.finfo(jnp.float32).eps))


def _resolve_in_dtype(in_dtype, in_bytes):
    """(dtype, itemsize) from whichever of the two the caller provided."""
    if in_dtype is not None:
        dt = jnp.dtype(in_dtype)
        return dt, dt.itemsize
    size = 4 if in_bytes is None else in_bytes
    dt = {1: jnp.dtype(jnp.int8), 2: jnp.dtype(jnp.bfloat16)}.get(
        size, jnp.dtype(jnp.float32))
    return dt, size


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A padded MXU-aligned tiling for an (m, k, n) matmul.

    ``pm/pk/pn`` are the zero-padded dims (multiples of ``bm/bk/bn``);
    ``cost_bytes`` is the modeled HBM traffic of the tiled GEMM including
    padding waste and the checksum-partial writes.
    """
    m: int
    k: int
    n: int
    bm: int
    bn: int
    bk: int
    pm: int
    pk: int
    pn: int
    cost_bytes: int

    @property
    def grid(self) -> Tuple[int, int, int]:
        return (self.pm // self.bm, self.pn // self.bn, self.pk // self.bk)

    @property
    def exact(self) -> bool:
        return (self.pm, self.pk, self.pn) == (self.m, self.k, self.n)

    @property
    def waste(self) -> float:
        """Relative extra FLOPs spent on padding (0.0 for aligned shapes)."""
        return self.pm * self.pk * self.pn / (self.m * self.k * self.n) - 1.0


def _round_up(x: int, b: int) -> int:
    return -(-x // b) * b


def vmem_bytes(bm: int, bn: int, bk: int, *, in_bytes: int = 4,
               out_bytes: int = 4, f: int = KERNEL_F,
               carry: bool = False) -> int:
    """Modeled VMEM working set of one kernel grid step: double-buffered
    A/B streams, fp32 accumulator, C_in tile (accumulate variant), and the
    weight/checksum tiles.  Shared by ``pick_blocks`` and the benches."""
    return (2 * (bm * bk + bk * bn) * in_bytes
            + bm * bn * 4
            + (bm * bn * out_bytes if carry else 0)
            + 2 * 4 * f * (bm + bn))


def plan_accounting(plan: BlockPlan, *, in_bytes: Optional[int] = None,
                    out_bytes: int = 4, f: int = KERNEL_F,
                    carry: bool = False, in_dtype=None,
                    pipeline: bool = True) -> dict:
    """Structural byte/FLOP accounting + overlap-aware time model.

    The single source of truth for the kernel's modeled cost — used both
    by ``pick_blocks``/``rank_blocks`` to score candidate tilings and by
    ``benchmarks.bench_kernels`` to report it.  Byte terms: A is streamed
    once per n-tile column, B once per m-tile row, C written once
    (read+written once more with a carried state); both fused checksum
    directions add ZERO extra reads (``extra_hbm_rd_col``/``_row``) — only
    the per-tile partial writes (``cs_wr_bytes``) — whereas unfused
    post-GEMM encode einsums would re-read all of C once per direction
    (``unfused_extra_rd``).

    Time terms (seconds): bytes and MXU FLOPs occupy SEPARATE resources, so
    ``t_total_s = max(t_hbm_s, t_mxu_s) + exposed_s`` where ``exposed_s``
    is the part of the VPU checksum epilogue (+ verify prologue with
    ``carry``) NOT hidden under the adjacent tile's operand fetch.  With
    the pipelined kernel grid those stages overlap the next (i, j) tile's
    A/B (+C_in) DMA, so per tile only ``max(0, t_vpu - t_fetch)`` is
    exposed; the serial layout (``pipeline=False``) exposes all of it.
    ``exposed_fraction`` = exposed share of the total VPU epilogue work.
    ``in_dtype`` picks the dtype-aware MXU rate (fp32 emulation / bf16 /
    int8); when only ``in_bytes`` is given the dtype is inferred from the
    itemsize.
    """
    in_dtype, in_bytes = _resolve_in_dtype(in_dtype, in_bytes)
    mt, nt, _ = plan.grid
    gemm_rd = (plan.pm * plan.pk * nt * in_bytes
               + plan.pk * plan.pn * mt * in_bytes)
    gemm_wr = plan.pm * plan.pn * out_bytes
    cs_wr = mt * f * plan.pn * 4 + nt * plan.pm * f * 4
    carry_bytes = 0
    if carry:  # C_in read + carried-state read + stats write
        carry_bytes = (plan.pm * plan.pn * out_bytes + cs_wr
                       + mt * nt * STATS_WIDTH * 4)
    flops = 2 * plan.pm * plan.pk * plan.pn
    cs_flops = 4 * f * plan.pm * plan.pn      # both directions, FMA=2 flops
    total_bytes = gemm_rd + gemm_wr + cs_wr + carry_bytes
    # ---- overlap-aware time model ---------------------------------------
    rate = mxu_rate(in_dtype)
    t_hbm = total_bytes / HBM_BW
    t_mxu = flops / rate
    t_epi = cs_flops / VPU_FLOPS
    # verify prologue (carry): 2 passes x dual checksum recompute per tile
    pro_flops = 8 * f * plan.pm * plan.pn if carry else 0
    t_pro = pro_flops / VPU_FLOPS
    n_tiles = mt * nt
    # operand bytes the pipeline can prefetch for one (i, j) tile while the
    # previous tile's epilogue / this tile's prologue runs on the VPU
    fetch_tile = (plan.pk * (plan.bm + plan.bn) * in_bytes
                  + (plan.bm * plan.bn * out_bytes if carry else 0))
    t_fetch_tile = fetch_tile / HBM_BW
    per_tile_vpu = (t_epi + t_pro) / n_tiles
    if pipeline:
        exposed = max(0.0, per_tile_vpu - t_fetch_tile) * n_tiles
    else:
        exposed = t_epi + t_pro
    t_total = max(t_hbm, t_mxu) + exposed
    vpu_total = t_epi + t_pro
    return dict(
        gemm_bytes=gemm_rd + gemm_wr,
        extra_hbm_rd_col=0,                   # reduced from the VMEM acc
        extra_hbm_rd_row=0,
        cs_wr_bytes=cs_wr,
        carry_bytes=carry_bytes,
        unfused_extra_rd=2 * plan.pm * plan.pn * out_bytes,
        flops=flops,
        cs_flops=cs_flops,
        total_bytes=total_bytes,
        mxu_rate=rate,
        t_hbm_s=t_hbm,
        t_mxu_s=t_mxu,
        t_epilogue_s=t_epi,
        t_prologue_s=t_pro,
        exposed_s=exposed,
        exposed_fraction=exposed / vpu_total if vpu_total else 0.0,
        t_total_s=t_total,
    )


def rank_blocks(
    m: int,
    k: int,
    n: int,
    *,
    vmem_budget: int = 8 * 2**20,
    in_bytes: Optional[int] = None,
    out_bytes: int = 4,
    f: int = KERNEL_F,
    carry: bool = False,
    require_exact: bool = False,
    in_dtype=None,
    pipeline: bool = True,
) -> list:
    """All qualifying MXU-aligned tilings for an (m, k, n) ABFT-GEMM,
    best-first under the overlap-aware time model.

    Candidate (bm, bn, bk) tilings are scored by ``plan_accounting``'s
    ``t_total_s`` — ``max(t_hbm, t_mxu) + exposed_epilogue`` with the
    dtype-aware MXU rate — so the model prices re-streams (HBM term),
    padding waste (MXU term) and un-hidden checksum work (exposed term) in
    one unit.  Ties (e.g. exactly-tileable compute-bound shapes, where
    padded FLOPs are equal across candidates) break toward fewer modeled
    bytes, then bigger tiles.  ``cost_bytes`` on each plan stays the pure
    byte cost (``total_bytes``), so bench accounting is unchanged.
    Tilings whose working set (double-buffered A/B streams, accumulator,
    C_in tile when ``carry``, weight/checksum tiles) exceeds
    ``vmem_budget`` are discarded.  ``require_exact`` restricts the search
    to tilings that divide (m, k, n) with no padding — callers that keep a
    long-lived carried state (the SUMMA local update) need this.

    This ranking is what ``kernels.autotune`` measures: the top-K plans
    here are the measurement candidates, and element 0 is the pure
    cost-model answer (``pick_blocks``).
    """
    in_dtype, in_bytes = _resolve_in_dtype(in_dtype, in_bytes)
    ranked = []
    for bm in _CANDIDATE_BLOCKS:
        for bn in _CANDIDATE_BLOCKS:
            for bk in _CANDIDATE_BLOCKS:
                if vmem_bytes(bm, bn, bk, in_bytes=in_bytes,
                              out_bytes=out_bytes, f=f,
                              carry=carry) > vmem_budget:
                    continue
                pm, pk, pn = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
                if require_exact and (pm, pk, pn) != (m, k, n):
                    continue
                cand = BlockPlan(m=m, k=k, n=n, bm=bm, bn=bn, bk=bk,
                                 pm=pm, pk=pk, pn=pn, cost_bytes=0)
                acct = plan_accounting(cand, in_bytes=in_bytes,
                                       out_bytes=out_bytes, f=f,
                                       carry=carry, in_dtype=in_dtype,
                                       pipeline=pipeline)
                cost = acct["total_bytes"]
                # modeled wall time first; tie-break toward cheaper
                # traffic, then bigger tiles
                key = (acct["t_total_s"], cost, -(bm * bn * bk), -bk)
                ranked.append((key, dataclasses.replace(cand,
                                                        cost_bytes=cost)))
    ranked.sort(key=lambda kp: kp[0])
    return [p for _, p in ranked]


def pick_blocks(
    m: int,
    k: int,
    n: int,
    *,
    vmem_budget: int = 8 * 2**20,
    in_bytes: Optional[int] = None,
    out_bytes: int = 4,
    f: int = KERNEL_F,
    carry: bool = False,
    require_exact: bool = False,
    in_dtype=None,
    pipeline: bool = True,
) -> Optional[BlockPlan]:
    """Best tiling under the cost model — ``rank_blocks(...)[0]``.

    Returns None if no candidate qualifies.  For a MEASURED choice (with
    on-disk persistence) use ``kernels.autotune.best_plan`` / ``autotune``.
    """
    ranked = rank_blocks(m, k, n, vmem_budget=vmem_budget,
                         in_bytes=in_bytes, out_bytes=out_bytes, f=f,
                         carry=carry, require_exact=require_exact,
                         in_dtype=in_dtype, pipeline=pipeline)
    return ranked[0] if ranked else None


def _pad2(x: jax.Array, pr: int, pc: int) -> jax.Array:
    r, c = x.shape
    if (r, c) == (pr, pc):
        return x
    return jnp.pad(x, ((0, pr - r), (0, pc - c)))


def _pad_weights(wm, wn, plan: BlockPlan):
    """Zero-pad W_m: [f, m] -> [f, pm] and W_n: [n, f] -> [pn, f]."""
    f = wm.shape[0]
    return _pad2(wm, f, plan.pm), _pad2(wn, plan.pn, f)


# ---------------------------------------------------------------------------
# One-shot fused matmul (with custom VJP)
# ---------------------------------------------------------------------------


def _run_oneshot(plan: BlockPlan, out_dtype, interpret, a, b, wm, wn):
    a_p = _pad2(a, plan.pm, plan.pk)
    b_p = _pad2(b, plan.pk, plan.pn)
    wm_p, wn_p = _pad_weights(wm, wn, plan)
    c, ccol, crow = abft_matmul_pallas(
        a_p, b_p, wm_p, wn_p, bm=plan.bm, bn=plan.bn, bk=plan.bk,
        out_dtype=out_dtype, interpret=interpret)
    cs_col = jnp.sum(ccol, axis=0)[:, : plan.n]
    cs_row = jnp.sum(crow, axis=0)[: plan.m, :]
    return c[: plan.m, : plan.n], cs_col, cs_row


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_mm(plan, out_dtype, interpret, a, b, wm, wn):
    return _run_oneshot(plan, out_dtype, interpret, a, b, wm, wn)


def _fused_mm_fwd(plan, out_dtype, interpret, a, b, wm, wn):
    return _run_oneshot(plan, out_dtype, interpret, a, b, wm, wn), (a, b, wm, wn)


def _fused_mm_bwd(plan, out_dtype, interpret, res, g):
    a, b, wm, wn = res
    gc, gcol, grow = g
    # fold the checksum cotangents back into the C cotangent:
    #   cs_col = W_m @ C  =>  dC += W_m^T @ g_col
    #   cs_row = C @ W_n  =>  dC += g_row @ W_n^T
    gc32 = (gc.astype(jnp.float32)
            + jnp.dot(wm.astype(jnp.float32).T, gcol)
            + jnp.dot(grow, wn.astype(jnp.float32).T))
    ga = jnp.dot(gc32, b.astype(jnp.float32).T).astype(a.dtype)
    gb = jnp.dot(a.astype(jnp.float32).T, gc32).astype(b.dtype)
    # the encoding weights are fixed constants of the scheme, never trained
    return ga, gb, jnp.zeros_like(wm), jnp.zeros_like(wn)


_fused_mm.defvjp(_fused_mm_fwd, _fused_mm_bwd)


def abft_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    f: int = KERNEL_F,
    wm: Optional[jax.Array] = None,
    wn: Optional[jax.Array] = None,
    out_dtype=None,
    force_pallas: bool = False,
    max_waste: float = 1.0,
    plan: Optional[BlockPlan] = None,
):
    """C = A @ B with fused dual checksums -> (c, cs_col [f,n], cs_row [m,f]).

    Custom weight matrices turn the row direction into arbitrary fused
    epilogue reductions of C (e.g. ``core.abft_gemm`` passes
    ``wn = [w_r; -I]`` so cs_row IS the verification residual, with zero
    extra HBM reads of C).  Differentiable via a custom VJP.
    """
    m, k = a.shape
    n = b.shape[1]
    if out_dtype is None:
        # int8 inputs accumulate exactly in int32 — an int8 output would
        # overflow on the first dot
        out_dtype = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) \
            else a.dtype
    if wm is not None:
        f = wm.shape[0]   # before building the default wn: shapes must agree
    wm = kernel_weights(m, f) if wm is None else wm
    wn = kernel_weights(n, f).T if wn is None else wn
    if wn.shape != (n, f):
        raise ValueError(f"wn shape {wn.shape} != ({n}, {f})")
    if plan is None:
        # layered plan resolution: autotune cache / env override when warm,
        # the pure cost model otherwise (never measures at dispatch time)
        from repro.kernels import autotune  # lazy: autotune imports ops
        plan = autotune.best_plan(m, k, n, in_dtype=a.dtype,
                                  out_dtype=out_dtype, f=f)
    use_pallas = (plan is not None and (on_tpu() or force_pallas)
                  and plan.waste <= max_waste)
    # dispatch runs at trace time, so this counts TRACES (≈ compiles) per
    # backend — the first-trace side of the obs compile/warm split
    obs.counter("repro_kernel_traces_total",
                "kernel dispatcher traces (≈ compiles)").inc(
        op="abft_matmul", backend="pallas" if use_pallas else "ref")
    if use_pallas:
        return _fused_mm(plan, jnp.dtype(out_dtype), not on_tpu(),
                         a, b, wm, wn)
    return ref.abft_matmul_ref(a, b, wm, wn, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# Accumulate variant + carried checksum state
# ---------------------------------------------------------------------------


def acc_state_zeros(plan: BlockPlan, f: int = KERNEL_F):
    """Carried checksum state for C = 0 under ``plan`` (padded layout)."""
    return (
        jnp.zeros((plan.pm // plan.bm, f, plan.pn), jnp.float32),
        jnp.zeros((plan.pn // plan.bn, plan.pm, f), jnp.float32),
    )


def tile_checksums(c: jax.Array, wm: jax.Array, wn: jax.Array,
                   bm: int, bn: int):
    """Per-tile dual checksums of a [pm, pn] array (pm % bm == pn % bn == 0).

    Returns (ccol: [pm/bm, f, pn], crow: [pn/bn, pm, f]) — the carried-state
    layout of ``abft_matmul_acc_pallas``; used to (re)derive a consistent
    state from data, e.g. after a SUMMA failure recovery rebuilt C blocks.
    """
    pm, pn = c.shape
    f = wm.shape[0]
    mt, nt = pm // bm, pn // bn
    c32 = c.astype(jnp.float32)
    wm_t = wm.astype(jnp.float32).reshape(f, mt, bm).transpose(1, 0, 2)
    ccol = jnp.einsum("tfb,tbn->tfn", wm_t, c32.reshape(mt, bm, pn))
    wn_t = wn.astype(jnp.float32).reshape(nt, bn, f)
    crow = jnp.einsum("tmb,tbf->tmf",
                      c32.reshape(pm, nt, bn).transpose(1, 0, 2), wn_t)
    return ccol, crow


def reduce_state(state, m: Optional[int] = None, n: Optional[int] = None):
    """Reduce a per-tile state to full checksums (cs_col [f,n], cs_row [m,f])."""
    ccol, crow = state
    cs_col = jnp.sum(ccol, axis=0)
    cs_row = jnp.sum(crow, axis=0)
    if n is not None:
        cs_col = cs_col[:, :n]
    if m is not None:
        cs_row = cs_row[:m, :]
    return cs_col, cs_row


def correct_from_state(c: jax.Array, state, wm: jax.Array, wn: jax.Array,
                       bm: int, bn: int, *, tol_factor: float = 64.0):
    """jnp twin of the kernel's verify/correct prologue, on a full [pm, pn] C.

    Locates a single corrupted element against the carried per-tile state
    (row via the row-direction residual, column via the column-direction
    residual) and repairs it by masked re-computation from the carried
    plain-sum column checksum.  Used for the post-loop scrub of the fused
    SUMMA path (a flip after the last accumulate has no next kernel call to
    catch it) and as the semantic oracle in tests.
    Returns (fixed, detected: bool scalar, corrected: bool scalar,
    row: int32 scalar, col: int32 scalar) — row/col are the located element
    (-1 when nothing was corrected).
    """
    ccol_c, crow_c = state
    pm, pn = c.shape
    # dtype-aware eps: fp32 floor (carried checksums are fp32 functions of
    # the rounded stored values), widened to the storage grid for bf16/fp16
    # so re-derived states never false-alarm (see detection_eps)
    eps_c = detection_eps(c.dtype)
    c32 = c.astype(jnp.float32)
    scale = jnp.mean(jnp.abs(c32)) + 1e-30
    tol_c = tol_factor * bm * eps_c * scale
    tol_r = tol_factor * bn * eps_c * scale
    detected = jnp.zeros((), bool)
    corrected = jnp.zeros((), bool)
    loc_r = jnp.full((), -1, jnp.int32)
    loc_c = jnp.full((), -1, jnp.int32)
    for it in range(2):
        ccol_now, crow_now = tile_checksums(c32, wm, wn, bm, bn)
        rcc = ccol_now - ccol_c                       # [mt, f, pn]
        rcr = crow_now - crow_c                       # [nt, pm, f]
        acol = jnp.sum(jnp.abs(rcc[:, 0, :]), axis=0)  # [pn]
        arow = jnp.sum(jnp.abs(rcr[:, :, 0]), axis=0)  # [pm]
        cmax, cidx = jnp.max(acol), jnp.argmax(acol)
        rmax, ridx = jnp.max(arow), jnp.argmax(arow)
        c2nd = jnp.max(jnp.where(jnp.arange(pn) == cidx, 0.0, acol))
        r2nd = jnp.max(jnp.where(jnp.arange(pm) == ridx, 0.0, arow))
        single = (
            (cmax > tol_c) & (rmax > tol_r)
            & (c2nd <= jnp.maximum(0.25 * cmax, tol_c))
            & (r2nd <= jnp.maximum(0.25 * rmax, tol_r))
        )
        if it == 0:
            detected = (cmax > tol_c) | (rmax > tol_r)
            corrected = single
            loc_r = jnp.where(single, ridx.astype(jnp.int32), loc_r)
            loc_c = jnp.where(single, cidx.astype(jnp.int32), loc_c)
        # masked re-computation from the carried column checksum of the
        # tile-row holding (ridx, cidx)
        tile_i = ridx // bm
        col = c32[:, cidx]
        col = col.at[ridx].set(0.0)
        seg = lax.dynamic_slice(col, (tile_i * bm,), (bm,))
        w_seg = lax.dynamic_slice(wm.astype(jnp.float32)[0], (tile_i * bm,),
                                  (bm,))
        carried = ccol_c[tile_i, 0, cidx]
        x_new = (carried - jnp.dot(w_seg, seg)) / (wm[0, ridx] + 1e-30)
        c32 = jnp.where(single, c32.at[ridx, cidx].set(x_new), c32)
    if jnp.issubdtype(c.dtype, jnp.integer):
        c32 = jnp.round(c32)   # integer storage: snap the repair to grid
    return c32.astype(c.dtype), detected, corrected, loc_r, loc_c


def _tile_verify_correct(c32, state, wm, wn, bm, bn, *, tol_factor,
                         eps_c: Optional[float] = None):
    """Vectorized-over-tiles twin of the kernel's verify/correct prologue.

    Exactly the math of ``kernels.abft_matmul._verify_correct``, batched
    over the [mt, nt] tile grid: per-tile residuals vs the carried state,
    one concentration-gated repair PER TILE by masked re-computation from
    the carried plain-sum column checksum, two passes.
    Returns (fixed c32 [pm, pn], stats [mt, nt, STATS_WIDTH]).
    """
    ccol, crow = state
    pm, pn = c32.shape
    mt, nt = pm // bm, pn // bn
    f = wm.shape[0]
    eps_c = detection_eps(jnp.float32) if eps_c is None else eps_c
    t = c32.reshape(mt, bm, nt, bn).transpose(0, 2, 1, 3)        # [mt,nt,bm,bn]
    wmt = wm.astype(jnp.float32).reshape(f, mt, bm).transpose(1, 0, 2)
    wnt = wn.astype(jnp.float32).reshape(nt, bn, f)
    ccol_t = ccol.reshape(mt, f, nt, bn).transpose(0, 2, 1, 3)   # [mt,nt,f,bn]
    crow_t = crow.reshape(nt, mt, bm, f).transpose(1, 0, 2, 3)   # [mt,nt,bm,f]
    scale = jnp.mean(jnp.abs(t), axis=(2, 3)) + 1e-30            # [mt,nt]
    tol_c = tol_factor * bm * eps_c * scale
    tol_r = tol_factor * bn * eps_c * scale
    row_i = jnp.arange(bm)
    col_i = jnp.arange(bn)

    def take(arr, idx):
        return jnp.take_along_axis(arr, idx[..., None], axis=-1)[..., 0]

    stats = None
    for it in range(2):
        rc = jnp.einsum("xfb,xybn->xyfn", wmt, t) - ccol_t       # [mt,nt,f,bn]
        rr = jnp.einsum("xybn,ynf->xybf", t, wnt) - crow_t       # [mt,nt,bm,f]
        ac = jnp.abs(rc[:, :, 0, :])                             # [mt,nt,bn]
        ar = jnp.abs(rr[:, :, :, 0])                             # [mt,nt,bm]
        cmax, cidx = ac.max(-1), ac.argmax(-1)                   # [mt,nt]
        rmax, ridx = ar.max(-1), ar.argmax(-1)
        c2 = jnp.where(col_i[None, None, :] == cidx[..., None], 0.0, ac).max(-1)
        r2 = jnp.where(row_i[None, None, :] == ridx[..., None], 0.0, ar).max(-1)
        detected = (cmax > tol_c) | (rmax > tol_r)
        single = ((cmax > tol_c) & (rmax > tol_r)
                  & (c2 <= jnp.maximum(0.25 * cmax, tol_c))
                  & (r2 <= jnp.maximum(0.25 * rmax, tol_r)))
        mask = ((row_i[None, None, :, None] == ridx[..., None, None])
                & (col_i[None, None, None, :] == cidx[..., None, None]))
        masked = jnp.where(mask, 0.0, t)
        s0 = jnp.einsum("xb,xybn->xyn", wmt[:, 0, :], masked)    # [mt,nt,bn]
        num = take(ccol_t[:, :, 0, :], cidx) - take(s0, cidx)
        w0r = take(jnp.broadcast_to(wmt[:, None, 0, :], (mt, nt, bm)), ridx)
        x_new = num / (w0r + 1e-30)
        t = jnp.where(single[..., None, None] & mask,
                      x_new[..., None, None], t)
        if it == 0:
            r_glob = jnp.arange(mt)[:, None] * bm + ridx
            c_glob = jnp.arange(nt)[None, :] * bn + cidx
            stats = jnp.stack([
                detected.astype(jnp.float32),
                single.astype(jnp.float32),
                jnp.where(single, r_glob.astype(jnp.float32), -1.0),
                jnp.where(single, c_glob.astype(jnp.float32), -1.0),
                cmax, rmax, tol_c, scale,
            ], axis=-1)
    return t.transpose(0, 2, 1, 3).reshape(pm, pn), stats


def abft_matmul_acc(
    a: jax.Array,
    b: jax.Array,
    c_in: jax.Array,
    state,
    *,
    plan: BlockPlan,
    wm: Optional[jax.Array] = None,
    wn: Optional[jax.Array] = None,
    verify: bool = True,
    tol_factor: float = 64.0,
    out_dtype=None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    pipeline: bool = True,
):
    """C_out = C_in + A @ B with carried checksum state and fused scrub.

    ``state`` is the (ccol, crow) pair from ``acc_state_zeros`` or a prior
    call under the same ``plan``.  ``backend``: "pallas" runs the fused
    kernel (interpret mode off-TPU), "jnp" the XLA twin (same semantics,
    separate einsums), "auto" picks pallas on TPU.  A/B may be fp32, bf16
    or int8 (int32 accumulation, integer C; repairs snap to the integer
    grid, so the int8 path stays bit-exact); the verify tolerance uses the
    dtype-aware ``detection_eps`` of the C storage dtype.  ``pipeline``
    selects the pipelined kernel grid (dot-free prologue/epilogue steps).
    Returns (c_out [m, n], new_state, stats [mt, nt, STATS_WIDTH]).
    """
    m, n = c_in.shape
    out_dtype = out_dtype or c_in.dtype
    int_data = jnp.issubdtype(a.dtype, jnp.integer)
    eps_c = detection_eps(c_in.dtype)
    f = KERNEL_F if wm is None else wm.shape[0]
    wm = kernel_weights(m, f) if wm is None else wm
    wn = kernel_weights(n, f).T if wn is None else wn
    if wn.shape != (n, f):
        raise ValueError(f"wn shape {wn.shape} != ({n}, {f})")
    wm_p, wn_p = _pad_weights(wm, wn, plan)
    a_p = _pad2(a, plan.pm, plan.pk)
    b_p = _pad2(b, plan.pk, plan.pn)
    c_p = _pad2(c_in, plan.pm, plan.pn)
    ccol_in, crow_in = state
    use_pallas = backend == "pallas" or (backend == "auto" and on_tpu())
    obs.counter("repro_kernel_traces_total",
                "kernel dispatcher traces (≈ compiles)").inc(
        op="abft_matmul_acc", backend="pallas" if use_pallas else "jnp")
    if use_pallas:
        interpret = not on_tpu() if interpret is None else interpret
        c, ccol, crow, stats = abft_matmul_acc_pallas(
            a_p, b_p, c_p, ccol_in, crow_in, wm_p, wn_p,
            bm=plan.bm, bn=plan.bn, bk=plan.bk, verify=verify,
            tol_factor=tol_factor, out_dtype=out_dtype, interpret=interpret,
            eps_c=eps_c, pipeline=pipeline)
        return c[:m, :n], (ccol, crow), stats
    # --- XLA twin: identical semantics, separate (unfused) einsums --------
    c32 = c_p.astype(jnp.float32)
    mt, nt = plan.pm // plan.bm, plan.pn // plan.bn
    if verify:
        c32, stats = _tile_verify_correct(
            c32, state, wm_p, wn_p, plan.bm, plan.bn, tol_factor=tol_factor,
            eps_c=eps_c)
    else:
        stats = jnp.zeros((mt, nt, STATS_WIDTH), jnp.float32)
        stats = stats.at[..., 2:4].set(-1.0)
    if int_data:
        # mirror the kernel's exact int32 accumulation (int values < 2^24
        # are exact in fp32, so the fp32 carrier stays bit-faithful)
        c32 = c32 + jnp.dot(a_p, b_p,
                            preferred_element_type=jnp.int32
                            ).astype(jnp.float32)
    else:
        c32 = c32 + jnp.dot(a_p.astype(jnp.float32),
                            b_p.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
    if jnp.issubdtype(jnp.dtype(out_dtype), jnp.integer):
        c32 = jnp.round(c32)   # repairs may leave eps off the int grid
    c_out = c32.astype(out_dtype)
    new_state = tile_checksums(c_out.astype(jnp.float32), wm_p, wn_p,
                               plan.bm, plan.bn)
    return c_out[:m, :n], new_state, stats


# ---------------------------------------------------------------------------
# Diskless-checkpoint encode
# ---------------------------------------------------------------------------


def checksum_encode(x: jax.Array, a: jax.Array, *, force_pallas: bool = False):
    """Diskless-checkpoint encode: [p,m,n] x [f,p] -> [f,m,n]."""
    p, m, n = x.shape
    ok = m % 128 == 0 and n % 128 == 0
    if ok and (on_tpu() or force_pallas):
        # bound VMEM: p * bm * bn * 4 <= 8 MB
        bm = 128
        bn = 128
        while bn * 2 <= n and n % (bn * 2) == 0 and x.shape[0] * bm * bn * 8 < 2**22:
            bn *= 2
        return checksum_encode_pallas(x, a, bm=bm, bn=bn, interpret=not on_tpu())
    return ref.checksum_encode_ref(x, a)


# ---------------------------------------------------------------------------
# Grouped (per-expert) products
# ---------------------------------------------------------------------------

GROUPED_BM = 256       # row tile: each expert's rows are padded to it
_LANE = 128


class GroupedLayout(NamedTuple):
    """Where each expert's rows sit in a row buffer of whole ``bm`` tiles.

    ``padded``: [G] rows per expert rounded up to the tile; ``starts``:
    [G] their first row; ``sizes``: ``padded`` with the buffer's unused
    tail added to the last expert (ragged-dot group sizes, summing to the
    buffer's rows); ``tile_group``: [m/bm] the expert of each row tile,
    non-decreasing, the tail carrying the last used tile's expert;
    ``n_used``: [1] tiles that hold rows."""
    padded: jax.Array
    starts: jax.Array
    sizes: jax.Array
    tile_group: jax.Array
    n_used: jax.Array


def grouped_layout(counts: jax.Array, m: int,
                   bm: int = GROUPED_BM) -> GroupedLayout:
    """The layout of ``counts`` [G] rows per expert in an [m, .] buffer
    (m a multiple of bm, at least the padded total)."""
    assert m % bm == 0, (m, bm)
    g = counts.shape[0]
    counts = counts.astype(jnp.int32)
    padded = (counts + bm - 1) // bm * bm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    used = ends[-1] // bm
    raw = jnp.searchsorted(ends, jnp.arange(m // bm, dtype=jnp.int32) * bm,
                           side="right").astype(jnp.int32)
    last = raw[jnp.maximum(used - 1, 0)]
    tile_group = jnp.minimum(
        jnp.where(jnp.arange(m // bm) < used, raw, last), g - 1)
    sizes = padded.at[g - 1].add(m - ends[-1])
    return GroupedLayout(padded, starts, sizes, tile_group.astype(jnp.int32),
                         used.reshape(1).astype(jnp.int32))


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` (itself a multiple
    of 128) and is at most ``cap``."""
    units = dim // _LANE
    best = 1
    for d in range(1, units + 1):
        if units % d == 0 and d * _LANE <= cap:
            best = d
    return best * _LANE


def _pad_to(x: jax.Array, shape) -> jax.Array:
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    return jnp.pad(x, pads) if any(p for _, p in pads) else x


def _gmm(layout, a, b, wn, cdt, interpret, out_dtype=jnp.float32,
         exact_cols=0):
    """The forward kernel on lane-padded operands: (c [m, n], resid|None)."""
    m, k = a.shape
    n = b.shape[-1]
    kp, np_ = _round_up(k, _LANE), _round_up(n, _LANE)
    bm = m // layout.tile_group.shape[0]
    wn_p = None if wn is None else _pad_to(wn, (np_, wn.shape[1]))
    c, crow = abft_grouped_matmul_pallas(
        layout.tile_group, layout.n_used, _pad_to(a, (m, kp)),
        _pad_to(b, (b.shape[0], kp, np_)), wn_p, bm=bm,
        bn=_tile(np_, 512), bk=_tile(kp, 2048), compute_dtype=cdt,
        out_dtype=out_dtype, exact_cols=exact_cols, interpret=interpret)
    return c[:, :n], (None if crow is None else jnp.sum(crow, axis=0))


def _gmm_dw(layout, a, g, num_groups, cdt, interpret, exact=False):
    """dW [G, k, n] of the grouped product (experts with no rows: 0)."""
    m, k = a.shape
    n = g.shape[1]
    kp, np_ = _round_up(k, _LANE), _round_up(n, _LANE)
    bm = m // layout.tile_group.shape[0]
    dw = abft_grouped_matmul_pallas_dw(
        layout.tile_group, layout.n_used, _pad_to(a, (m, kp)),
        _pad_to(g, (m, np_)), num_groups=num_groups, bm=bm,
        bk=_tile(kp, 512), bn=_tile(np_, 512), compute_dtype=cdt,
        out_dtype=jnp.float32, exact=exact, interpret=interpret)
    return jnp.where((layout.padded > 0)[:, None, None], dw[:, :k, :n], 0.0)


def _exact_cols(b, wn, exact):
    """B's leading columns exact in bf16: its data columns (not the
    checksum columns ``wn`` reduces) when the caller says so."""
    if not exact:
        return 0
    return b.shape[-1] - (0 if wn is None else wn.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _grouped_mm(cdt, out_dtype, exact, interpret, layout, a, b, wn):
    return _gmm(layout, a, b, wn, cdt, interpret, out_dtype,
                _exact_cols(b, wn, exact))


def _grouped_mm_fwd(cdt, out_dtype, exact, interpret, layout, a, b, wn):
    return (_gmm(layout, a, b, wn, cdt, interpret, out_dtype,
                 _exact_cols(b, wn, exact)), (layout, a, b, wn))


def _grouped_mm_bwd(cdt, out_dtype, exact, interpret, res, g):
    """dA through the same kernel on the transposed weights, dB through
    its ``_dw`` sibling; the residual output carries no gradient (it
    only feeds the verify).  A bf16 cotangent keeps exact operands exact
    (the checksum rows of B^T meet the checksum columns' cotangent, zero
    unless a repair reads them)."""
    layout, a, b, wn = res
    gc = g[0]
    exact = exact and gc.dtype == jnp.bfloat16
    da, _ = _gmm(layout, gc, jnp.swapaxes(b, 1, 2), None, cdt, interpret,
                 out_dtype=a.dtype, exact_cols=a.shape[1] if exact else 0)
    db = _gmm_dw(layout, a, gc, b.shape[0], cdt, interpret, exact)
    zero_layout = jax.tree.map(
        lambda x: np.zeros(x.shape, jax.dtypes.float0), layout)
    return (zero_layout, da, db.astype(b.dtype),
            None if wn is None else jnp.zeros_like(wn))


_grouped_mm.defvjp(_grouped_mm_fwd, _grouped_mm_bwd)


def grouped_matmul(a: jax.Array, b: jax.Array, layout: GroupedLayout,
                   wn: Optional[jax.Array] = None, *,
                   compute_dtype=jnp.float32, out_dtype=jnp.float32,
                   exact: bool = False, pallas: Optional[bool] = None):
    """C = A @ B[expert of each row] over a `GroupedLayout` row buffer.

    a: [m, k] rows sorted by expert (padding rows zero); b: [G, k, n];
    wn: [n, f] or None.  Returns (c [m, n] in ``out_dtype``, the fp32
    product @ wn [m, f] or None).  ``exact``: A and B's data columns hold
    values exact in bf16 (the MXU then takes one pass where it can, the
    same products).  With ``pallas`` (default: on a TPU) the
    grouped Pallas kernels run, forward and backward (custom VJP),
    interpreted off the TPU; otherwise the jnp twin
    (`ref.grouped_matmul_ref`), differentiated by JAX.
    """
    use_pallas = on_tpu() if pallas is None else pallas
    obs.counter("repro_kernel_traces_total",
                "kernel dispatcher traces (≈ compiles)").inc(
        op="abft_grouped_matmul", backend="pallas" if use_pallas else "ref")
    cdt, odt = jnp.dtype(compute_dtype), jnp.dtype(out_dtype)
    if use_pallas:
        return _grouped_mm(cdt, odt, exact, not on_tpu(), layout, a, b, wn)
    c, resid = ref.grouped_matmul_ref(a, b, layout.sizes, wn,
                                      compute_dtype=cdt)
    return c.astype(odt), resid
