"""Pure-jnp oracles for the Pallas kernels (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.checksum import checkpoint_matrix

__all__ = ["default_weights", "abft_matmul_ref", "checksum_encode_ref",
           "checksum_verify_ref", "grouped_matmul_ref"]

# Seed for the kernel-level checkpoint matrices.  Fixed so that carried
# checksum states are reproducible across calls, processes and the jnp/Pallas
# boundary (row/col 0 is the plain Huang-Abraham sum either way).
_WEIGHT_SEED = 23


def default_weights(m: int, f: int = 2, dtype=jnp.float32) -> jax.Array:
    """The kernel's [f, m] checksum weights (row 0 = plain sum-checksum)."""
    return checkpoint_matrix(f, m, seed=_WEIGHT_SEED, dtype=dtype)


def abft_matmul_ref(a: jax.Array, b: jax.Array, wm=None, wn=None, *,
                    f: int = 2, out_dtype=None):
    """C = A @ B plus its dual weighted checksums (fp32 accumulation).

    wm: [f, m] (default ``default_weights(m, f)``), wn: [n, f] (default
    ``default_weights(n, f).T``).  Returns (c: [m, n] in out_dtype,
    cs_col = wm @ C: [f, n] fp32, cs_row = C @ wn: [m, f] fp32), where the
    checksums are computed from the ROUNDED output — exactly what the fused
    kernel reduces from its VMEM accumulator in the epilogue.
    """
    m, n = a.shape[0], b.shape[1]
    out_dtype = out_dtype or a.dtype
    wm = default_weights(m, f) if wm is None else wm
    wn = default_weights(n, f).T if wn is None else wn
    c32 = jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    c = c32.astype(out_dtype)
    rounded = c.astype(jnp.float32)
    cs_col = jnp.dot(wm.astype(jnp.float32), rounded)
    cs_row = jnp.dot(rounded, wn.astype(jnp.float32))
    return c, cs_col, cs_row


def checksum_encode_ref(x: jax.Array, a: jax.Array):
    """Weighted checksums of stacked shards: [p, m, n] x [f, p] -> [f, m, n]."""
    return jnp.einsum(
        "fp,pmn->fmn", a.astype(jnp.float32), x.astype(jnp.float32)
    ).astype(x.dtype)


def checksum_verify_ref(c: jax.Array, colsum: jax.Array):
    """Max abs residual between colsum(C) and a carried checksum row."""
    rec = jnp.sum(c.astype(jnp.float32), axis=0)
    return jnp.max(jnp.abs(rec - colsum.astype(jnp.float32)))


def grouped_matmul_ref(a: jax.Array, b: jax.Array, sizes: jax.Array,
                       wn=None, *, compute_dtype=jnp.float32):
    """jnp twin of ``abft_grouped_matmul_pallas``: C = ragged_dot(A, B)
    over consecutive row groups of ``sizes`` (summing to A's rows) in fp32
    accumulation, and with ``wn`` the verify einsum ``C @ wn`` [m, f]."""
    c = jax.lax.ragged_dot(a.astype(compute_dtype), b.astype(compute_dtype),
                           sizes, preferred_element_type=jnp.float32)
    if wn is None:
        return c, None
    return c, jnp.dot(c, wn.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
