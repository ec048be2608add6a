"""Grouped (ragged) ABFT matmul Pallas kernels for the expert products.

An expert layer multiplies rows sorted by expert, each group of rows by
its own expert's weights.  The rows of each group are padded to a whole
number of row tiles (``bm``), so every row tile belongs to one expert; a
scalar-prefetched ``tile_group`` array maps row tile -> expert and picks
the weight block the tile's DMA fetches.  Tiles past ``n_used`` (the
static worst-case buffer's unused tail) are skipped and written as zeros.

  * ``abft_grouped_matmul_pallas``    — C = A @ B[g(row)], B encoded per
    expert as ``[W_e, W_e @ w_r]``.  Given ``wn = [w_r; -I]`` the epilogue
    reduces each row's §4.3 residual ``Y @ w_r - Y_cs`` from the fp32 VMEM
    accumulator, as ``abft_matmul_pallas`` does for the dense projections
    (per-n-tile partials ``[n/bn, m, f]``, summed by the caller), so the
    output itself may be stored narrower.  Without
    ``wn`` it is the plain grouped product (the backward's dX pass).
  * ``abft_grouped_matmul_pallas_dw`` — dW[g] = A_g^T @ G_g, the weight
    gradient of the grouped product: row tiles are the reduction axis,
    innermost, and consecutive tiles of one expert accumulate into that
    expert's output block.  Blocks of experts with no rows are never
    visited; the caller zeroes them.

Operands may come in a narrower storage dtype than ``compute_dtype`` (bf16
rows against fp32 weights): each block is cast in VMEM, which is exact for
bf16 -> fp32 and spares HBM a widened copy of the row buffer.  The
accumulator is fp32.  An fp32 ``compute_dtype`` multiplies at
``Precision.HIGHEST`` (at the MXU's default single bf16 pass the residual
of a clean product is of the order of bf16 rounding, and a verify held to
fp32 rounding trips on nearly every row), except where both operands hold
values exact in bf16 (bf16 activations against bf16 weights): one pass
then forms every product exactly, and only the tiles that hold the fp32
checksum columns need the six.  Shapes must be tile multiples
(``kernels.ops`` pads); a block's last two dims are multiples of (8, 128)
or whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["abft_grouped_matmul_pallas", "abft_grouped_matmul_pallas_dw"]


def _precision(cdt, exact: bool = False):
    """fp32 operands in full fp32 (the MXU's default takes one bf16 pass),
    unless their values are ``exact`` in bf16: that one pass then forms
    every product exactly."""
    return lax.Precision.HIGHEST if cdt == jnp.float32 and not exact \
        else None


def _last_used(i, nu):
    """Row tile ``i``, or the last used one for the unused tail: an
    unchanged block index skips the tail's DMA."""
    return jnp.minimum(i, jnp.maximum(nu[0] - 1, 0))


def _gmm_kernel(tg_ref, nu_ref, a_ref, b_ref, *refs, k_steps, cdt,
                residual, exact_tiles):
    if residual:
        wn_ref, c_ref, crow_ref, acc_ref = refs
    else:
        c_ref, acc_ref = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def dot(exact):
        acc_ref[...] += jnp.dot(a_ref[...].astype(cdt),
                                b_ref[0].astype(cdt),
                                preferred_element_type=jnp.float32,
                                precision=_precision(cdt, exact))

    used = i < nu_ref[0]
    pl.when(used & (j < exact_tiles))(lambda: dot(True))
    pl.when(used & (j >= exact_tiles))(lambda: dot(False))

    @pl.when(k == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        c_ref[...] = acc.astype(c_ref.dtype)
        if residual:
            # the product as computed, before it is rounded to the output;
            # in full fp32, or the checksum columns' rounding trips it
            crow_ref[...] = jnp.dot(
                acc, wn_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32,
                precision=lax.Precision.HIGHEST)[None]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "compute_dtype", "out_dtype",
                              "exact_cols", "interpret"))
def abft_grouped_matmul_pallas(tile_group, n_used, a, b, wn=None, *,
                               bm: int, bn: int, bk: int,
                               compute_dtype=jnp.float32,
                               out_dtype=jnp.float32, exact_cols: int = 0,
                               interpret: bool = False):
    """C[r] = A[r] @ B[tile_group[r // bm]] for the first ``n_used`` row
    tiles (the rest are zero).

    tile_group: [m/bm] int32; n_used: [1] int32; a: [m, k]; b: [g, k, n];
    wn: [n, f] residual weights or None.  ``exact_cols``: B's leading
    columns whose values, like A's, are exact in bf16 (bf16 weights, not
    their fp32 checksum columns); their tiles multiply in one MXU pass.
    Returns (c [m, n], crow [n/bn, m, f] fp32 per-n-tile partials of
    ``C @ wn``, or None).
    """
    m, k = a.shape
    g, k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})")
    k_steps = k // bk
    residual = wn is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk, tg, nu: (_last_used(i, nu),
                                                         kk)),
        pl.BlockSpec((1, bk, bn),
                     lambda i, j, kk, tg, nu: (tg[_last_used(i, nu)], kk, j)),
    ]
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, kk, tg, nu: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype)]
    args = [a, b]
    if residual:
        f = wn.shape[1]
        assert wn.shape == (n, f), wn.shape
        in_specs.append(pl.BlockSpec((bn, f),
                                     lambda i, j, kk, tg, nu: (j, 0)))
        out_specs.append(pl.BlockSpec((1, bm, f),
                                      lambda i, j, kk, tg, nu: (j, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n // bn, m, f), jnp.float32))
        args.append(wn)
    kernel = functools.partial(_gmm_kernel, k_steps=k_steps,
                               cdt=jnp.dtype(compute_dtype),
                               residual=residual,
                               exact_tiles=exact_cols // bn)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(m // bm, n // bn, k_steps),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)]),
        out_shape=out_shape,
        interpret=interpret,
    )(tile_group, n_used, *args)
    return (out[0], out[1]) if residual else (out[0], None)


def _dw_kernel(tg_ref, nu_ref, a_ref, g_ref, o_ref, acc_ref, *, m_tiles,
               cdt, exact):
    i = pl.program_id(2)
    grp = tg_ref[i]
    first = (i == 0) | (tg_ref[jnp.maximum(i - 1, 0)] != grp)
    last = (i == m_tiles - 1) | (tg_ref[jnp.minimum(i + 1, m_tiles - 1)]
                                 != grp)

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nu_ref[0])
    def _dot():
        acc_ref[...] += lax.dot_general(
            a_ref[...].astype(cdt), g_ref[...].astype(cdt),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_precision(cdt, exact))

    @pl.when(last)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


@functools.partial(
    jax.jit, static_argnames=("num_groups", "bm", "bk", "bn",
                              "compute_dtype", "out_dtype", "exact",
                              "interpret"))
def abft_grouped_matmul_pallas_dw(tile_group, n_used, a, g, *,
                                  num_groups: int, bm: int, bk: int, bn: int,
                                  compute_dtype=jnp.float32,
                                  out_dtype=jnp.float32, exact: bool = False,
                                  interpret: bool = False):
    """dW[e] = sum over the row tiles of expert e of A_tile^T @ G_tile.

    tile_group must be non-decreasing, the unused tail carrying the last
    used tile's group (``kernels.ops.grouped_layout`` builds it so).  a:
    [m, k]; g: [m, n]; ``exact``: both hold values exact in bf16.  Returns
    [num_groups, k, n]; blocks of experts with no row tile are left
    unwritten.
    """
    m, k = a.shape
    m2, n = g.shape
    assert m == m2, (a.shape, g.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"shape ({m},{k},{n}) not divisible by blocks ({bm},{bk},{bn})")
    m_tiles = m // bm
    kernel = functools.partial(_dw_kernel, m_tiles=m_tiles,
                               cdt=jnp.dtype(compute_dtype), exact=exact)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(k // bk, n // bn, m_tiles),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda kk, j, i, tg, nu: (
                    _last_used(i, nu), kk)),
                pl.BlockSpec((bm, bn), lambda kk, j, i, tg, nu: (
                    _last_used(i, nu), j)),
            ],
            out_specs=pl.BlockSpec((1, bk, bn),
                                   lambda kk, j, i, tg, nu: (tg[i], kk, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        interpret=interpret,
    )(tile_group, n_used, a, g)
