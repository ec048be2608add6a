"""ABFT-protected matmul / einsum for model layers (Huang-Abraham per layer).

This is the "fault-tolerant BLAS" the paper argues should encapsulate all the
fault tolerance of a dense-LA stack (§1), applied to the matmuls of an LM:

    W_F = [W, W @ w_r]          (f checksum columns; encoded once per step,
                                 after the optimizer update — amortized)
    Y_F = X @ W_F               (checksum columns ride along: +f/n FLOPs)
    verify:  Y_F[..., -f:] =?= Y_F[..., :-f] @ w_r    (O(m n f) vs O(m n k))
    correct: single corrupted element located by (row = argmax residual rows,
             col via a second weighted checksum), fixed by the residual.

Modes (config `ft.mode`):
    off      — plain matmul
    checksum — carry checksums, don't verify (zero sync cost; verify lazily)
    verify   — carry + verify; returns an `ok` flag alongside
    correct  — carry + verify + correct single bit-flips in the output

The element-granular weight matrix here is ``w_r = checkpoint_matrix(f, n).T``
(n = output features), i.e. the paper's encoding at element granularity —
appropriate because a TPU shard failure erases a *slab* of Y, which the SUMMA
path handles; this path targets silent data corruption (bit-flips), where
element granularity maximizes location precision.

Backend: with ``backend="pallas"`` (or "auto" on TPU) the matmul AND the
verification residual run in one fused Pallas kernel (`kernels.ops`): the
kernel's row-checksum epilogue is fed ``W_n = [w_r; -I]`` so it reduces
``Y @ w_r - Y_cs`` — the §4.3 residual — directly from the VMEM-resident
accumulator.  That deletes the separate ``Y @ w_r`` verify einsum and its
full extra HBM read of Y; detection/correction then run on checksum-sized
data.  ``backend="ref"`` (and "auto" off-TPU) keeps the plain XLA path.
This is the fused path behind `models.layers.linear_apply` and the serving
engine's projections.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.checksum import checkpoint_matrix

__all__ = ["ABFTConfig", "encode_weight", "abft_matmul", "verify_output",
           "correct_output"]


# Kernel compute dtypes the layer path accepts.  Checksum ACCUMULATION is
# always fp32 (int8 products route through an int32 GEMM first) — only the
# A/B operand stream narrows, which is what buys MXU rate.
_KERNEL_DTYPES = {
    "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
}


@dataclasses.dataclass(frozen=True)
class ABFTConfig:
    mode: str = "off"          # off | checksum | verify | correct
    f: int = 2                 # number of checksum columns (2 => locate 2D)
    tol_factor: float = 256.0  # residual threshold multiplier
    seed: int = 17
    backend: str = "auto"      # auto | pallas | ref (fused-kernel dispatch)
    in_dtype: str = "fp32"     # fp32 | bf16 | int8 — GEMM operand dtype

    @property
    def active(self) -> bool:
        return self.mode != "off"

    @property
    def compute_dtype(self):
        try:
            return _KERNEL_DTYPES[self.in_dtype]
        except KeyError:
            raise ValueError(
                f"in_dtype={self.in_dtype!r} not in {sorted(_KERNEL_DTYPES)}"
            ) from None


def _detection_eps(cfg: "ABFTConfig") -> float:
    """Residual-test eps for the configured operand dtype.

    fp32 keys on fp32 eps (unchanged).  bf16 operands quantize the encoded
    checksum COLUMNS of ``w_enc`` to bf16, so the clean residual floor is
    ~eps_bf16 * sqrt(n) * |Y| — eps must widen to bf16 or every clean bf16
    matmul false-alarms.  int8 rides the dynamic-quantization path whose
    checksum sums stay fp32-exact-ish (integer products < 2^24 per term),
    so fp32 eps keeps detection sharp.
    """
    dt = cfg.compute_dtype
    eps32 = float(jnp.finfo(jnp.float32).eps)
    if jnp.issubdtype(dt, jnp.floating):
        return max(float(jnp.finfo(dt).eps), eps32)
    return eps32


def _weights(n: int, f: int, seed: int, dtype) -> jax.Array:
    """Element-granularity encoding weights w_r: [n, f] (row 0 = plain sum)."""
    return checkpoint_matrix(f, n, seed=seed).T.astype(dtype)


def encode_weight(w: jax.Array, cfg: ABFTConfig) -> jax.Array:
    """Append f checksum columns to a [k, n] weight matrix -> [k, n + f]."""
    n = w.shape[-1]
    wr = _weights(n, cfg.f, cfg.seed, jnp.float32)
    cs = (w.astype(jnp.float32) @ wr).astype(w.dtype)
    return jnp.concatenate([w, cs], axis=-1)


def _fused_forward(x: jax.Array, w_enc: jax.Array, cfg: ABFTConfig):
    """Fused-kernel forward: (y_f fp32, residual fp32 [..., f]) or None.

    Dispatches through `kernels.ops.abft_matmul` with the row-checksum
    weights set to ``[w_r; -I]``, so the kernel epilogue reduces the §4.3
    verification residual from the VMEM-resident accumulator — no separate
    verify einsum, no extra HBM read of Y.
    """
    from repro.kernels import ops as kops  # lazy: avoids core<->kernels cycle
    from repro.kernels import autotune as ktune

    force = cfg.backend == "pallas"
    if not (force or (cfg.backend == "auto" and kops.on_tpu())):
        return None
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = 1
    for d in lead:
        m *= d
    n_enc = w_enc.shape[-1]
    n = n_enc - cfg.f
    plan = ktune.best_plan(m, k, n_enc, in_dtype=x.dtype,
                           out_dtype=jnp.float32, f=cfg.f)
    if plan is None or (not force and plan.waste > 0.25):
        # padding would cost more than it saves (e.g. decode: m = slots):
        # the XLA reference runs — counted, so a run can see it
        obs.counter("repro_kernel_traces_total",
                    "kernel dispatcher traces (≈ compiles)").inc(
            op="abft_matmul", backend="ref")
        return None
    wr = _weights(n, cfg.f, cfg.seed, jnp.float32)             # [n, f]
    wn_res = jnp.concatenate(
        [wr, -jnp.eye(cfg.f, dtype=jnp.float32)], axis=0)      # [n+f, f]
    wm = kops.kernel_weights(m, cfg.f)
    y_f, _cs_col, res = kops.abft_matmul(
        x.reshape(m, k), w_enc, wm=wm, wn=wn_res,
        out_dtype=jnp.float32, force_pallas=force,
        max_waste=float("inf"), plan=plan)
    return y_f.reshape(*lead, n_enc), res.reshape(*lead, cfg.f)


def _int8_forward(x: jax.Array, w_enc: jax.Array, cfg: ABFTConfig):
    """Dynamically-quantized int8 forward: (y_f fp32, residual fp32).

    Checksum columns of magnitude ~sqrt(n)*127*|w_q| cannot live in int8,
    so the int8 path splits the encoded matrix: the DATA block is
    quantized to int8 and multiplied on the int8 MXU wire (int32
    accumulate, composing with the ``ef_psum_tree`` int8 collective), while
    the checksum product re-encodes in fp32 from the *quantized* weights —
    cs_q = w_q @ w_r, y_cs = x_q @ cs_q — a different association order
    than (x_q @ w_q) @ w_r, so a fault in the main GEMM still breaks the
    consistency relation.  Integer products stay below 2^24 per term, so
    both sides are fp32-exact-ish and detection keeps fp32 eps.
    """
    n = w_enc.shape[-1] - cfg.f
    w = w_enc[..., :n].astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    sx = 127.0 / (jnp.max(jnp.abs(x32)) + 1e-30)
    sw = 127.0 / (jnp.max(jnp.abs(w)) + 1e-30)
    xq = jnp.clip(jnp.round(x32 * sx), -127, 127).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w * sw), -127, 127).astype(jnp.int8)
    yq = jnp.dot(xq, wq, preferred_element_type=jnp.int32).astype(jnp.float32)
    wr = _weights(n, cfg.f, cfg.seed, jnp.float32)          # [n, f]
    cs_q = wq.astype(jnp.float32) @ wr                      # [k, f]
    ycs_q = xq.astype(jnp.float32) @ cs_q                   # [..., f]
    residual_q = yq @ wr - ycs_q
    inv = 1.0 / (sx * sw)
    y_f = jnp.concatenate([yq, ycs_q], axis=-1) * inv
    return y_f, residual_q * inv


def abft_matmul(
    x: jax.Array, w_enc: jax.Array, cfg: ABFTConfig,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Y = X @ W with fault-tolerance per cfg.mode.

    w_enc must be `encode_weight(w, cfg)` when cfg.active, else plain w.
    Returns (y, ok) where ok is None unless mode in {verify, correct}.
    cfg.in_dtype narrows the GEMM operand stream (bf16 casts both
    operands, int8 dynamically quantizes); checksums stay fp32 throughout
    and the residual test widens eps to match (`_detection_eps`).
    """
    if not cfg.active:
        return jnp.dot(x, w_enc, preferred_element_type=jnp.float32).astype(x.dtype), None
    if cfg.in_dtype == "int8":
        y_f, residual = _int8_forward(x, w_enc, cfg)
    else:
        cdt = cfg.compute_dtype
        x_c = x.astype(cdt) if x.dtype != cdt else x
        w_c = w_enc.astype(cdt) if w_enc.dtype != cdt else w_enc
        fused = _fused_forward(x_c, w_c, cfg)
        if fused is None:
            y_f = jnp.dot(x_c, w_c, preferred_element_type=jnp.float32)
            residual = None
        else:
            y_f, residual = fused
    y, y_cs = y_f[..., : -cfg.f], y_f[..., -cfg.f :]
    if cfg.mode == "checksum":
        return y.astype(x.dtype), None
    if residual is None:
        ok, residual = verify_output(y, y_cs, cfg)
    else:
        ok = _residual_ok(y, residual, cfg)
    if cfg.mode == "verify":
        return y.astype(x.dtype), ok
    y = correct_output(y, y_cs, residual, cfg)
    return y.astype(x.dtype), ok


def _residual_ok(y: jax.Array, residual: jax.Array, cfg: ABFTConfig):
    """The §4.3 acceptance test: max |residual| <= tol * n * eps * |Y|.

    eps keys on the configured OPERAND dtype (`_detection_eps`), not on
    y.dtype — y is always the fp32 accumulator on the fused path, so the
    old y.dtype check silently kept fp32 eps for bf16 operands and every
    clean bf16 matmul tripped the detector on checksum-quantization noise.
    """
    n = y.shape[-1]
    eps = _detection_eps(cfg)
    # mean-|.| scale: robust to a single corrupted element (see core.detect)
    scale = jnp.mean(jnp.abs(y.astype(jnp.float32))) + 1e-30
    tol = cfg.tol_factor * n * eps * scale
    return jnp.max(jnp.abs(residual)) <= tol


def verify_output(y: jax.Array, y_cs: jax.Array, cfg: ABFTConfig):
    """Check Y @ w_r == carried checksums, with the paper's residual scaling
    tau ~ tol * n * eps * |Y|  (§4.3 residual checking)."""
    n = y.shape[-1]
    wr = _weights(n, cfg.f, cfg.seed, jnp.float32)
    recomputed = y.astype(jnp.float32) @ wr
    residual = recomputed - y_cs.astype(jnp.float32)   # [..., f]
    return _residual_ok(y, residual, cfg), residual


def correct_output(y, y_cs, residual, cfg: ABFTConfig):
    """Correct a single corrupted element of Y.

    Row: argmax over the leading (flattened) axes of |residual[..., 0]|.
    Column: the ratio residual[r,1]/residual[r,0] equals w_r[col,1]/w_r[col,0]
    for the corrupted column (needs f >= 2); we pick the column whose weight
    ratio matches, then subtract residual[r,0] / w_r[col,0].
    """
    if cfg.f < 2:
        raise ValueError("correct mode needs f >= 2 checksum columns")
    n = y.shape[-1]
    wr = _weights(n, cfg.f, cfg.seed, jnp.float32)      # [n, f]
    y32 = y.astype(jnp.float32)
    flat_y = y32.reshape(-1, n)
    flat_res = residual.reshape(-1, cfg.f)
    r = jnp.argmax(jnp.abs(flat_res[:, 0]))
    ratio = flat_res[r, 1] / (flat_res[r, 0] + 1e-30)
    col = jnp.argmin(jnp.abs(wr[:, 1] / wr[:, 0] - ratio))
    delta = flat_res[r, 0] / wr[col, 0]
    fixed = flat_y.at[r, col].add(-delta)
    # one iterative-refinement pass: the first residual was computed with
    # the (huge) corrupted value in the sum, so it carries |delta|*eps of
    # cancellation error; re-deriving it from the repaired row leaves only
    # O(n eps |y|) error on the corrected element
    flat_cs = y_cs.reshape(-1, cfg.f).astype(jnp.float32)
    res_r = fixed[r] @ wr - flat_cs[r]
    fixed = fixed.at[r, col].add(-res_r[0] / wr[col, 0])
    eps = _detection_eps(cfg)  # dtype-aware: bf16 checksum-quantization
    # noise must not trip a phantom "repair" of a healthy element.
    # mean-|.| scale (as in _residual_ok): a max-|.| scale is inflated by
    # the corrupted element itself, which with the wider bf16 eps pushed
    # the threshold above genuine flip residuals
    scale = jnp.mean(jnp.abs(y32)) + 1e-30
    tol = cfg.tol_factor * n * eps * scale
    use_fixed = jnp.max(jnp.abs(flat_res)) > tol
    out = jnp.where(use_fixed, fixed, flat_y)
    return out.reshape(y.shape)
