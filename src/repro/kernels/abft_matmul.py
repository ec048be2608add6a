"""Fused dual-checksum ABFT matmul Pallas kernel family — the TPU-native
realization of the paper's "hide the O(n^2) checksum under the O(n^3) matmul"
economics, grown into the single local-update primitive of the stack.

The local DGEMM of the paper becomes an MXU-tiled matmul whose Huang-Abraham
checksums in BOTH directions are accumulated by the VPU *in the same pass*,
on data already resident in VMEM:

  * column checksums  CS_col = W_m @ C   (f weighted sum-rows,   [f, n])
  * row checksums     CS_row = C @ W_n   (f weighted sum-cols,   [m, f])

with W_m: [f, m] / W_n: [n, f] checkpoint matrices (row/col 0 is the plain
Huang-Abraham sum; the remaining f-1 weighted rows give location capability).
Neither direction re-reads A, B or C from HBM — the checksums are reduced
from the fp32 accumulator in VMEM during the epilogue, so the only extra HBM
traffic is the (tiny) partial-checksum writes: [m/bm, f, n] + [n/bn, m, f]
fp32, ~0.1% of the GEMM traffic at 2048^3.

Two entry points:

  * ``abft_matmul_pallas``      — one-shot C = A @ B with dual checksums.
  * ``abft_matmul_acc_pallas``  — accumulate step C_out = C_in + A @ B with a
    carried-in per-tile checksum state and a fused verify/correct prologue:
    at the first k-step the kernel recomputes the checksums of the C_in tile
    it has just loaded (needed anyway for the accumulation — zero extra HBM
    reads), compares against the carried state, and on a single-element
    mismatch locates the element (row via the row-direction residual, column
    via the column-direction residual, cross-checked against the f>=2
    weighted components) and repairs it by masked re-computation from the
    carried sum-checksum before accumulating.  This is the per-step rank-kb
    update of ``core.summa._local_summa``: every SUMMA step's checksum
    maintenance and SDC scrub ride the MXU pass instead of separate einsums.

Grid: (m/bm, n/bn, k-steps), k innermost (same C tile revisited across k;
the accumulator lives in VMEM scratch — fp32, or int32 for int8 inputs).
On the last k step the tile is cast to the output dtype and both checksum
partials are computed FROM THE ROUNDED tile, so a clean carried state
verifies bit-exactly on the next accumulate call for any storage dtype.
Each output block is visited by a single contiguous run of grid steps (no
non-monotonic revisits — safe under TPU pipelining).

Pipelined grid (``pipeline=True``, the default): the dual-checksum epilogue
— and, in the accumulate variant, the verify/correct prologue — get their
OWN grid steps instead of sharing one with an MXU dot.  The one-shot grid
becomes (mt, nt, ks+1) with a dot-free epilogue step at kk == ks; the
accumulate grid becomes (mt, nt, ks+2) with a dot-free prologue step at
kk == 0 and the epilogue at kk == ks+1.  The A/B index maps clamp the
k-block (``min``/``clip``), so the extra steps re-reference the block
already resident in VMEM — Pallas skips the DMA for an unchanged block
index and instead prefetches the NEXT (i, j) tile's A/B (and C_in) streams
while the VPU runs the checksum reductions.  That is the double-buffered
overlap the GPU online-FT GEMM literature gets from an explicit epilogue
pipeline stage: the checksum work hides under the adjacent tile's operand
fetch rather than extending the MXU steps.  ``pipeline=False`` keeps the
serial fused layout (epilogue/prologue sharing dot steps) for A/B bench
comparison.

Mixed precision: A/B may be fp32, bf16 or int8.  Float inputs feed the MXU
at their native width (``preferred_element_type=float32`` keeps the
accumulator fp32); int8 inputs accumulate exactly in an int32 scratch.
Checksums are ALWAYS fp32, taken of the rounded stored tile — exact for
integer data below 2^24, so the int8 path detects, locates and repairs
bit-exactly.  ``eps_c`` (detection epsilon) is dtype-aware and supplied by
the ``kernels.ops`` dispatcher via ``detection_eps(storage dtype)``.

Block shapes are MXU-aligned (multiples of 128); ragged shapes are padded by
the ``kernels.ops`` dispatcher (zero rows/cols checksum to zero, so padding
commutes with the encoding).  VMEM budget per grid step:
2*(bm*bk + bk*bn)*in_bytes (double-buffered A/B streams) + bm*bn*4 (fp32
accumulator) + bm*bn*out_bytes (C_in tile, accumulate variant only)
+ 4*f*(bm + bn) (weight + checksum tiles).  Default (512, 512, 512) fp32
=> ~6.3 MB << 16 MB VMEM; (256, 256, 512) => ~2.4 MB.

The verify/correct prologue uses only 2-D iota, reductions and where-masked
updates (no dynamic scatters/gathers), so it lowers on both the TPU Mosaic
backend and the CPU interpreter used on this container.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["abft_matmul_pallas", "abft_matmul_acc_pallas", "STATS_WIDTH"]

# stats vector per C tile (accumulate variant):
#   0: detected (residual over threshold)      1: corrected (single-elt fix)
#   2: global row of the fix                   3: global col of the fix
#   4: residual magnitude (col direction)      5: residual magnitude (row dir)
#   6: detection threshold (col direction)     7: |C_in| scale used for tol
STATS_WIDTH = 8
# In HBM each tile's stats are one (8, 128) block — the TPU lowering wants a
# block's last two dims to be multiples of (8, 128) or the whole array, so a
# (1, 1, STATS_WIDTH) block is refused once the grid has more than one tile.
# Every row holds the STATS_WIDTH values in its first lanes; the wrapper
# slices them back out.
_STATS_TILE = (8, 128)


def _stats_tile(values):
    """Broadcast STATS_WIDTH scalars into a lane-dense (8, 128) tile."""
    lane = lax.broadcasted_iota(jnp.int32, _STATS_TILE, 1)
    tile = jnp.zeros(_STATS_TILE, jnp.float32)
    for s, v in enumerate(values):
        tile = jnp.where(lane == s, v, tile)
    return tile


def _tile_checksums(c32, wm, wn):
    """Dual checksums of one fp32 tile: (W_m @ C [f, bn], C @ W_n [bm, f])."""
    return (
        jnp.dot(wm, c32, preferred_element_type=jnp.float32),
        jnp.dot(c32, wn, preferred_element_type=jnp.float32),
    )


def _verify_correct(cin, wm, wn, ccol_c, crow_c, *, tol_factor, eps_c, bm, bn,
                    i, j):
    """Fused verify/correct on one C_in tile (all operands VMEM-resident).

    Residuals against the carried per-tile checksums locate a single
    corrupted element: row from the row-direction sum residual, column from
    the column-direction sum residual.  The repair recomputes the element
    from the carried column checksum minus the surviving column entries
    (masked re-sum), which avoids the catastrophic cancellation of the naive
    ``x -= residual`` fix for large (exponent-bit) flips.  Two passes: the
    second is a no-op on clean data and mops up any residual left by the
    first.  Returns (fixed_tile, STATS_WIDTH stats scalars).
    """
    row_iota = lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    col_iota = lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    # The carried checksums are fp32 functions of the ROUNDED stored tile, so
    # a clean tile re-verifies with residual exactly 0 in any storage dtype;
    # eps_c (fp32) only needs to absorb re-derived states whose fp32
    # summation order differs (e.g. a jnp state refresh after recovery).
    scale = jnp.mean(jnp.abs(cin)) + 1e-30
    tol_c = tol_factor * bm * eps_c * scale   # col residual sums bm terms
    tol_r = tol_factor * bn * eps_c * scale
    fixed = cin
    stats = None
    for it in range(2):
        rc = jnp.dot(wm, fixed, preferred_element_type=jnp.float32) - ccol_c
        rr = jnp.dot(fixed, wn, preferred_element_type=jnp.float32) - crow_c
        ac = jnp.abs(rc[0:1, :])              # [1, bn] plain-sum col residual
        ar = jnp.abs(rr[:, 0:1])              # [bm, 1] plain-sum row residual
        cmax = jnp.max(ac)
        rmax = jnp.max(ar)
        cidx = jnp.argmax(ac.reshape(-1)).astype(jnp.int32)
        ridx = jnp.argmax(ar.reshape(-1)).astype(jnp.int32)
        col_sel = col_iota[0:1, :] == cidx    # [1, bn]
        row_sel = row_iota[:, 0:1] == ridx    # [bm, 1]
        # concentration gate: a genuine single-element corruption leaves the
        # other columns'/rows' residuals at (near) zero; diffuse residuals
        # (e.g. a stale state after an unrelated rebuild) must not trigger a
        # bogus point fix.
        c2nd = jnp.max(jnp.where(col_sel, 0.0, ac))
        r2nd = jnp.max(jnp.where(row_sel, 0.0, ar))
        detected = (cmax > tol_c) | (rmax > tol_r)
        single = (
            (cmax > tol_c) & (rmax > tol_r)
            & (c2nd <= jnp.maximum(0.25 * cmax, tol_c))
            & (r2nd <= jnp.maximum(0.25 * rmax, tol_r))
        )
        # masked re-computation of the corrupted element from the carried
        # plain-sum column checksum (sum-trick gathers only — TPU-safe)
        mask = (row_iota == ridx) & (col_iota == cidx)
        masked = jnp.where(mask, 0.0, fixed)
        s_others = jnp.dot(wm[0:1, :], masked,
                           preferred_element_type=jnp.float32)   # [1, bn]
        carried = jnp.sum(jnp.where(col_sel, ccol_c[0:1, :], 0.0))
        others = jnp.sum(jnp.where(col_sel, s_others, 0.0))
        wm_sel = lax.broadcasted_iota(jnp.int32, (1, bm), 1) == ridx
        w0r = jnp.sum(jnp.where(wm_sel, wm[0:1, :], 0.0))
        x_new = (carried - others) / (w0r + 1e-30)
        fixed = jnp.where(single & mask, x_new, fixed)
        if it == 0:
            stats = (
                detected.astype(jnp.float32),
                single.astype(jnp.float32),
                jnp.where(single, (i * bm + ridx).astype(jnp.float32), -1.0),
                jnp.where(single, (j * bn + cidx).astype(jnp.float32), -1.0),
                cmax, rmax, tol_c, scale,
            )
    return fixed, stats


def _kernel(*refs, k_steps, carry_in, verify, tol_factor, eps_c, pipeline):
    if carry_in:
        (a_ref, b_ref, wm_ref, wn_ref, cin_ref, ccin_ref, crin_ref,
         c_ref, ccol_ref, crow_ref, stats_ref, acc_ref) = refs
    else:
        (a_ref, b_ref, wm_ref, wn_ref,
         c_ref, ccol_ref, crow_ref, acc_ref) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    bm, bn = acc_ref.shape
    int_acc = jnp.issubdtype(acc_ref.dtype, jnp.integer)
    # pipelined layout: dot-free prologue step (accumulate variant) and
    # dot-free epilogue step; serial layout: dots on every step, epilogue
    # sharing the last one (the pre-pipeline fused form)
    dot_lo = 1 if (pipeline and carry_in) else 0
    dot_hi = dot_lo + k_steps - 1
    epi_step = dot_hi + 1 if pipeline else dot_hi

    def _to_acc(x32):
        # float accumulators hold fp32; the int8 path's int32 accumulator
        # stores rounded integers (true values are integral, so this is
        # exact below 2^24)
        return jnp.round(x32).astype(acc_ref.dtype) if int_acc else x32

    @pl.when(k == 0)
    def _prologue():
        if not carry_in:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            return
        cin = cin_ref[...].astype(jnp.float32)
        if verify:
            fixed, stats = _verify_correct(
                cin, wm_ref[...].astype(jnp.float32),
                wn_ref[...].astype(jnp.float32),
                ccin_ref[0], crin_ref[0],
                tol_factor=tol_factor, eps_c=eps_c,
                bm=bm, bn=bn, i=i, j=j,
            )
            stats_ref[0, 0] = _stats_tile(stats)
            acc_ref[...] = _to_acc(fixed)
        else:
            # -1 location sentinels (slots 2:4), matching the verified path
            stats_ref[0, 0] = _stats_tile((0.0, 0.0, -1.0, -1.0))
            acc_ref[...] = _to_acc(cin)

    @pl.when((k >= dot_lo) & (k <= dot_hi))
    def _dot():
        # native-width MXU feed: bf16 inputs take the bf16 MXU path with an
        # fp32 accumulator; int8 inputs accumulate exactly in int32; fp32
        # is the multi-pass emulation as before
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...],
            preferred_element_type=acc_ref.dtype)

    @pl.when(k == epi_step)
    def _epilogue():
        acc = acc_ref[...]
        c_ref[...] = acc.astype(c_ref.dtype)
        # Checksum the ROUNDED tile so a clean carried state re-verifies
        # bit-exactly next call, for any storage dtype.
        rounded = acc.astype(c_ref.dtype).astype(jnp.float32)
        ccol, crow = _tile_checksums(
            rounded, wm_ref[...].astype(jnp.float32),
            wn_ref[...].astype(jnp.float32))
        ccol_ref[...] = ccol[None]
        crow_ref[...] = crow[None]


def _common_specs(bm, bn, bk, f, k_steps, *, pipeline, carry_in):
    # k-block selection: the serial grid walks blocks directly; the
    # pipelined grid clamps so the extra prologue/epilogue steps re-
    # reference the resident block (no DMA) while Pallas prefetches the
    # next (i, j) tile's streams under the VPU checksum work
    if not pipeline:
        def kblk(kk):
            return kk
    elif carry_in:
        def kblk(kk):
            return jnp.clip(kk - 1, 0, k_steps - 1)
    else:
        def kblk(kk):
            return jnp.minimum(kk, k_steps - 1)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kblk(kk))),   # A
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kblk(kk), j)),   # B
        pl.BlockSpec((f, bm), lambda i, j, kk: (0, i)),     # W_m
        pl.BlockSpec((bn, f), lambda i, j, kk: (j, 0)),     # W_n
    ]
    out_specs = [
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # C
        pl.BlockSpec((1, f, bn), lambda i, j, kk: (i, 0, j)),  # col partials
        pl.BlockSpec((1, bm, f), lambda i, j, kk: (j, i, 0)),  # row partials
    ]
    return in_specs, out_specs


def _acc_dtype(in_dtype):
    """Accumulator dtype for given A/B inputs: int32 for integer (exact),
    fp32 otherwise (bf16 inputs keep an fp32 accumulator)."""
    return jnp.int32 if jnp.issubdtype(jnp.dtype(in_dtype), jnp.integer) \
        else jnp.float32


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype",
                              "pipeline")
)
def abft_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    wm: jax.Array,
    wn: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
    pipeline: bool = True,
):
    """One-shot C = A @ B with fused dual (row + column) checksums.

    a: [m, k], b: [k, n] — fp32, bf16 or int8 (int8 accumulates exactly in
    int32; pass an integer ``out_dtype``); wm: [f, m], wn: [n, f];
    m % bm == k % bk == n % bn == 0 (``kernels.ops`` pads ragged shapes).
    ``pipeline`` gives the checksum epilogue its own grid step so it
    overlaps the next tile's A/B fetch (see module docstring).
    Returns (c: [m, n], ccol: [m/bm, f, n] fp32, crow: [n/bn, m, f] fp32) —
    per-tile checksum partials; summing over axis 0 gives the full W_m @ C
    and C @ W_n (each partial reduction is checksum-sized, negligible next
    to the matmul).
    """
    m, k = a.shape
    k2, n = b.shape
    f = wm.shape[0]
    assert k == k2, (a.shape, b.shape)
    assert wm.shape == (f, m) and wn.shape == (n, f), (wm.shape, wn.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})"
    )
    acc_dtype = _acc_dtype(a.dtype)
    out_dtype = out_dtype or (jnp.int32 if acc_dtype == jnp.int32
                              else a.dtype)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps + (1 if pipeline else 0))
    kernel = functools.partial(
        _kernel, k_steps=k_steps, carry_in=False, verify=False,
        tol_factor=0.0, eps_c=float(jnp.finfo(jnp.float32).eps),
        pipeline=pipeline)
    in_specs, out_specs = _common_specs(bm, bn, bk, f, k_steps,
                                        pipeline=pipeline, carry_in=False)
    c, ccol, crow = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((m // bm, f, n), jnp.float32),
            jax.ShapeDtypeStruct((n // bn, m, f), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(a, b, wm, wn)
    return c, ccol, crow


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "verify", "tol_factor", "interpret",
                     "out_dtype", "eps_c", "pipeline"),
)
def abft_matmul_acc_pallas(
    a: jax.Array,
    b: jax.Array,
    c_in: jax.Array,
    ccol_in: jax.Array,
    crow_in: jax.Array,
    wm: jax.Array,
    wn: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    verify: bool = True,
    tol_factor: float = 64.0,
    out_dtype=None,
    interpret: bool = False,
    eps_c=None,
    pipeline: bool = True,
):
    """Accumulate step C_out = C_in + A @ B with carried checksum state.

    c_in: [m, n]; ccol_in: [m/bm, f, n]; crow_in: [n/bn, m, f] — the state
    produced by a previous ``abft_matmul_pallas`` / ``abft_matmul_acc_pallas``
    call with the same blocks (zeros for C_in = 0).  When ``verify``, each
    C_in tile is checked against the carried state at the first k-step and a
    single corrupted element is repaired in-VMEM before accumulation.
    A/B may be fp32, bf16 or int8 (int32 accumulator, integer C).  ``eps_c``
    is the dtype-aware detection epsilon for the verify tolerance (defaults
    to fp32 eps; ``kernels.ops`` passes ``detection_eps(c_in.dtype)``).
    ``pipeline`` gives the verify prologue and the checksum epilogue their
    own dot-free grid steps (see module docstring).
    Returns (c_out, ccol_out, crow_out, stats: [m/bm, n/bn, STATS_WIDTH]).
    """
    m, k = a.shape
    k2, n = b.shape
    f = wm.shape[0]
    assert k == k2 and c_in.shape == (m, n), (a.shape, b.shape, c_in.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})"
    )
    assert ccol_in.shape == (m // bm, f, n), ccol_in.shape
    assert crow_in.shape == (n // bn, m, f), crow_in.shape
    acc_dtype = _acc_dtype(a.dtype)
    out_dtype = out_dtype or c_in.dtype
    eps_c = float(jnp.finfo(jnp.float32).eps) if eps_c is None else eps_c
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps + (2 if pipeline else 0))
    kernel = functools.partial(
        _kernel, k_steps=k_steps, carry_in=True, verify=verify,
        tol_factor=tol_factor, eps_c=eps_c, pipeline=pipeline)
    in_specs, out_specs = _common_specs(bm, bn, bk, f, k_steps,
                                        pipeline=pipeline, carry_in=True)
    in_specs = in_specs + [
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),       # C_in
        pl.BlockSpec((1, f, bn), lambda i, j, kk: (i, 0, j)),  # carried col
        pl.BlockSpec((1, bm, f), lambda i, j, kk: (j, i, 0)),  # carried row
    ]
    out_specs = out_specs + [
        pl.BlockSpec((1, 1) + _STATS_TILE, lambda i, j, kk: (i, j, 0, 0)),
    ]
    c, ccol, crow, stats = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((m // bm, f, n), jnp.float32),
            jax.ShapeDtypeStruct((n // bn, m, f), jnp.float32),
            jax.ShapeDtypeStruct((m // bm, n // bn) + _STATS_TILE,
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(a, b, wm, wn, c_in, ccol_in, crow_in)
    return c, ccol, crow, stats[:, :, 0, :STATS_WIDTH]
