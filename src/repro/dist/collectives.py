"""Fault-tolerant / compressed data-parallel reductions.

The paper's core claim is that ABFT encoding *rides the collectives*: the
checksum blocks flow through the same reduction as the data, so detection
and correction cost a lower-order number of extra wire bytes instead of a
second pass.  This module applies that idea to the two hot DP reductions of
LM training:

  * `abft_psum` / `abft_psum_tree` — Huang-Abraham row/column checksums of
    the (2-D-viewed) contribution are packed into the SAME psum as the
    data; after the reduction the checksums of the sum must equal the sum
    of the checksums (linearity), which detects a silent corruption
    injected anywhere in the reduction and locates + corrects a single
    corrupted element.  Extra wire: O(sqrt(n)) per leaf.
  * `ef_psum_tree` — int8 error-feedback quantized gradient all-reduce,
    quantization error carried to the next step as a residual (Seide et
    al. 1-bit SGD generalized to int8).  The wire realization is
    selectable: a psum of the dequantized payload (lowers everywhere), or
    the true compressed exchange (reduce-scatter-shaped int8 all_to_all +
    requantized int8 all-gather, ~4x fewer wire bytes at any DP extent)
    where the toolchain supports those collectives in the surrounding
    region.

All functions run inside a manual-collective region (jax.shard_map over the
DP axes, or jax.vmap with an axis_name in tests) and reduce over `axes`,
a tuple of mesh axis names.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.chaos.faults import register_surface

__all__ = ["ef_psum_tree", "abft_psum", "abft_psum_tree", "ef_wire_bytes"]

# the protection domain this module owns, visible to repro.chaos campaigns:
# checksums riding the reduction see a corruption of the reduction itself —
# they cannot see garbage that was already in the contribution when its
# checksums were taken (that blind spot is the *_at_rest ledger entries)
register_surface(
    "dist.collectives/abft_psum", owner=__name__, protected=True,
    promise="tolerance",
    detector="Huang-Abraham row/column checksums packed into the same psum "
             "(linearity residual); single corrupted element located "
             "exactly, repaired by subtracting the row residual",
    kinds=("sdc_collective",),
    note="repair is a float subtraction of the residual: near-exact "
         "(~ulp(delta)), not bit-exact — the train-side promise is "
         "tolerance; the serving engine's argmax token stream absorbs it "
         "to bit-identity (see serve.engine/logits_reduce)")


def _axis_tuple(axes):
    return tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)


def _linear_axis_index(axes):
    """Row-major linear index of this shard across possibly-multiple axes."""
    idx = jnp.zeros((), jnp.int32)
    for a in _axis_tuple(axes):
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# int8 error-feedback compressed all-reduce
# ---------------------------------------------------------------------------


def ef_psum_tree(grads, residual, dp_axes, ndp: int, *, wire: str = "psum"):
    """int8 error-feedback quantized DP gradient mean.

    Per leaf: add the carried residual, quantize to int8 with a per-shard
    fp32 scale, reduce the dequantized payloads, and keep the quantization
    error as the next step's residual.  Returns ``(mean_grads,
    new_residual)`` matching the `jax.lax.pmean` the uncompressed path uses.

    wire:
      * "psum" (default) — the dequantized values ride a plain psum.  The
        gradient still passes through the int8 bottleneck (EF semantics,
        convergence behavior, residual dynamics all identical) but the
        bytes on the wire stay f32.  It lowers inside a PARTIAL-manual
        shard_map (auto model axis), where an earlier toolchain's SPMD
        partitioner crashed on all_gather/all_to_all (not yet re-tested
        on jax 0.9: ROADMAP Queue 3, item 2).
      * "int8" — true compressed exchange: an all_to_all hands every
        device its 1/ndp segment of all shards' int8 payloads
        (reduce-scatter shape), the segment is dequantized + averaged
        locally, requantized, and all_gathered back.  ~2 x leaf_size int8
        wire bytes per device vs ~2 x leaf_size f32 for a ring all-reduce
        — the real 4x, at any DP extent.  Requires a toolchain where these
        collectives lower in the surrounding region (fully-manual regions,
        or a newer XLA); both quantization errors feed the residual.
    """
    if wire not in ("psum", "int8"):
        raise ValueError(f"unknown wire {wire!r}: expected 'psum' or 'int8'")
    axes = _axis_tuple(dp_axes)

    def quant(x):
        amax = jnp.max(jnp.abs(x))
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
        return q, scale

    def one_psum(g, r):
        x = g.astype(jnp.float32) + r.astype(jnp.float32)
        q, scale = quant(x)
        deq = q.astype(jnp.float32) * scale
        return jax.lax.psum(deq, axes) / ndp, x - deq

    def one_int8(g, r):
        x = g.astype(jnp.float32) + r.astype(jnp.float32)
        flat = x.reshape(-1)
        seg = -(-flat.size // ndp)                  # ceil
        q, scale = quant(jnp.pad(flat, (0, seg * ndp - flat.size)))
        local_err = x - (q.astype(jnp.float32) * scale)[
            : flat.size].reshape(x.shape)
        # reduce-scatter shape: device j ends with chunk j of EVERY
        # shard's int8 payload ([ndp, seg] int8 on the wire)
        chunks = jax.lax.all_to_all(
            q.reshape(ndp, seg), axes, split_axis=0, concat_axis=0,
            tiled=True)
        s_all = jax.lax.all_gather(scale, axes)                  # [ndp] f32
        seg_mean = jnp.sum(
            chunks.astype(jnp.float32) * s_all[:, None], axis=0) / ndp
        # requantize the owned segment and share it ([seg] int8 wire)
        q2, s2 = quant(seg_mean)
        q2_all = jax.lax.all_gather(q2, axes)                    # [ndp, seg]
        s2_all = jax.lax.all_gather(s2, axes)                    # [ndp]
        mean = (q2_all.astype(jnp.float32) * s2_all[:, None]).reshape(
            -1)[: flat.size].reshape(x.shape)
        # feed this device's segment-requant error back through ITS
        # residual (x ndp: the residual is in local-contribution units,
        # the error is in mean units)
        seg_err = jnp.zeros((ndp, seg), jnp.float32).at[
            _linear_axis_index(axes)].set(ndp * (seg_mean - q2.astype(
                jnp.float32) * s2))
        new_r = local_err + seg_err.reshape(-1)[: flat.size].reshape(x.shape)
        return mean, new_r

    one = one_int8 if (wire == "int8" and ndp > 1) else one_psum
    leaves, treedef = jax.tree.flatten(grads)
    r_leaves = treedef.flatten_up_to(residual)
    outs = [one(g, r) for g, r in zip(leaves, r_leaves)]
    mean = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_res = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return mean, new_res


# ---------------------------------------------------------------------------
# Huang-Abraham checksum-verified psum
# ---------------------------------------------------------------------------


def abft_psum(x, axes, *, f: int = 2, mode: str = "correct",
              tol_factor: float = 256.0,
              inject: Optional[Tuple[int, float]] = None,
              inject_local=None, with_info: bool = False):
    """psum(x) over `axes` with checksums riding the same collective.

    The local contribution is viewed as an R x C grid (R*C >= n,
    R ~ C ~ sqrt(n)); its row sums (f >= 1) and column sums (f >= 2) are
    appended and ``[v, rows, cols]`` is reduced in ONE psum — the paper's
    2-D Huang-Abraham scheme applied to the reduction.  By linearity the
    reduced checksums must equal the checksums of the reduced data; a
    residual detects a corruption of the reduction, and the (argmax-row,
    argmax-col) intersection locates a single corrupted element EXACTLY at
    any n (a closed-form/weighted 1-D location cannot resolve columns in
    f32 beyond n ~ 1e7).  Extra wire: R + C ~ 2*sqrt(n) floats.

    mode: "verify" detects only; "correct" (f >= 2) also repairs a single
    fault.  inject: optional ``(shard, delta)`` — adds `delta` to one
    element of shard `shard`'s contribution AFTER its checksums are taken,
    simulating a transient fault on the wire (FT drills / tests).  Both
    components may be traced scalars, so one compiled drill program serves
    every planned (shard, delta).  ``inject_local`` is the same drill with
    the shard selection done by the CALLER: a per-shard additive delta
    (0.0 on unaffected shards): serve.engine pre-scatters the delta into a
    model-axis-sharded vector and passes this shard's slice here, so the
    partial-manual region needs no `lax.axis_index` (see
    `chaos.faults.scatter_delta`).

    Runs inside any manual-collective region over `axes` — fully-manual or
    partial-manual shard_map, or vmap(axis_name=...) in tests; it lowers
    to a single psum.

    Returns ``(y, ok)`` where y = psum(x) (repaired when possible) and ok
    is a scalar bool (True = checksums consistent, no fault seen).  With
    ``with_info=True`` additionally returns a dict of scalars for FT
    telemetry (serve.engine drills): ``row``/``col``/``index`` locate the
    corrupted element in the flattened leaf (-1 = not located),
    ``magnitude`` is the estimated corruption (the row residual), and
    ``corrected`` says whether the repair was applied.
    """
    if mode not in ("verify", "correct"):
        raise ValueError(f"unknown mode {mode!r}: expected 'verify' or "
                         "'correct'")
    if mode == "correct" and f < 2:
        raise ValueError("correct mode needs f >= 2 (row AND column "
                         "checksums locate the fault)")
    if inject is not None and inject_local is not None:
        raise ValueError("pass either inject (shard, delta) or inject_local "
                         "(this shard's delta), not both")
    axes = _axis_tuple(axes)
    shape, dtype = x.shape, x.dtype
    v = x.astype(jnp.float32).reshape(-1)
    n = v.size
    neg1 = jnp.asarray(-1, jnp.int32)
    info = {"row": neg1, "col": neg1, "index": neg1,
            "magnitude": jnp.asarray(0.0, jnp.float32),
            "corrected": jnp.asarray(False)}
    if n < max(f, 2):
        if inject is not None or inject_local is not None:
            raise ValueError(
                f"cannot inject into a {n}-element leaf: too small to "
                f"carry {f} checksums (pick a bigger leaf)")
        y, ok = jax.lax.psum(x, axes), jnp.asarray(True)
        return (y, ok, info) if with_info else (y, ok)
    cdim = int(math.ceil(math.sqrt(n)))
    rdim = -(-n // cdim)
    pad = rdim * cdim - n

    def grid(vec):
        return jnp.pad(vec, (0, pad)).reshape(rdim, cdim)

    v2 = grid(v)
    checks = [v2.sum(axis=1)]                       # row sums [R]
    if f >= 2:
        checks.append(v2.sum(axis=0))               # col sums [C]
    # element updates are iota-masked adds, not scatters: inside a partial-
    # manual region the auto axes may shard the flattened leaf, and the
    # SPMD partitioner misplaces a scatter into it (4x2 mesh: the repair
    # landed one slot over)
    flat = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
    if inject is not None:
        shard, delta = inject
        hit = _linear_axis_index(axes) == shard
        v = v + jnp.where(hit & (flat == n // 2), jnp.float32(delta), 0.0)
    elif inject_local is not None:
        v = v + jnp.where(flat == n // 2, jnp.float32(inject_local), 0.0)
    packed = jnp.concatenate([v] + checks)
    total = jax.lax.psum(packed, axes)
    y = total[:n]
    y2 = grid(y)

    eps = float(jnp.finfo(jnp.float32).eps)
    scale = jnp.mean(jnp.abs(y)) + 1e-30
    row_res = y2.sum(axis=1) - total[n: n + rdim]                  # [R]
    row_bad = jnp.max(jnp.abs(row_res)) > tol_factor * cdim * eps * scale
    ok = ~row_bad
    if f >= 2:
        col_res = y2.sum(axis=0) - total[n + rdim:]                # [C]
        col_bad = jnp.max(jnp.abs(col_res)) > tol_factor * rdim * eps * scale
        ok = ok & ~col_bad
        # single DATA fault: the corrupted element is the intersection of
        # the offending row and column and the row residual IS the delta.
        # A fault on a CHECKSUM element trips only ONE family — repairing
        # then would corrupt healthy data, so require both (the checksum
        # fault stays detect-only: ok is already False).
        rr = jnp.argmax(jnp.abs(row_res))
        cc = jnp.argmax(jnp.abs(col_res))
        idx = jnp.minimum(rr * cdim + cc, n - 1)
        located = row_bad & col_bad
        info["row"] = jnp.where(located, rr.astype(jnp.int32), neg1)
        info["col"] = jnp.where(located, cc.astype(jnp.int32), neg1)
        info["index"] = jnp.where(located, idx.astype(jnp.int32), neg1)
        info["magnitude"] = jnp.where(located, row_res[rr], 0.0)
        if mode == "correct":                                      # f >= 2
            y = y - jnp.where(located & (flat == idx), row_res[rr], 0.0)
            info["corrected"] = located
    y = y.reshape(shape).astype(dtype)
    return (y, ok, info) if with_info else (y, ok)


def _normalize_events(inject):
    """``inject`` may be one (shard, delta) pair or a sequence of them."""
    if inject is None:
        return ()
    if isinstance(inject, (tuple, list)) and len(inject) == 2 \
            and not isinstance(inject[0], (tuple, list)):
        return (tuple(inject),)
    return tuple(tuple(ev) for ev in inject)


def abft_psum_tree(grads, dp_axes, ndp: int, *, mode: str = "verify",
                   f: int = 2, inject=None):
    """Checksum-verified DP gradient mean over a pytree.

    Applies `abft_psum` leaf-wise (one protected collective per leaf, like
    the pmean it replaces) and divides by `ndp` to match `jax.lax.pmean`
    semantics.  `inject` takes one ``(shard, delta)`` event or a SEQUENCE
    of them — the multi-collective fault model: event j corrupts the j-th
    leaf big enough to carry the checksums, so k events land in k
    *different* protected reductions of the same step (tiny leaves skip
    protection entirely, so injecting there would test nothing).  Each
    reduction still carries at most the single fault its own checksums can
    locate and correct exactly.
    Returns ``(mean_grads, all_ok)``.

    Opt-in via ``train.step.StepOptions.abft_reduce`` on the deferred-
    reduction path, which is kept single-device (see
    ``StepOptions.defer_grad_reduce``); tests exercise the vmap collective
    semantics and the single-device SPMD path.
    """
    leaves, treedef = jax.tree.flatten(grads)
    events = _normalize_events(inject)
    inject_for = {}
    if events:
        eligible = [i for i, g in enumerate(leaves) if g.size >= max(f, 2)]
        if len(eligible) < len(events):
            raise ValueError(
                f"{len(events)} injected events need as many leaves large "
                f"enough to carry checksums; only {len(eligible)} qualify")
        inject_for = dict(zip(eligible, events))
    outs, oks = [], []
    for i, g in enumerate(leaves):
        y, ok = abft_psum(g, dp_axes, f=f, mode=mode,
                          inject=inject_for.get(i))
        outs.append(y / ndp)
        oks.append(ok)
    all_ok = jnp.stack(oks).all() if oks else jnp.asarray(True)
    return jax.tree.unflatten(treedef, outs), all_ok


# ---------------------------------------------------------------------------
# wire-byte accounting (roofline inputs — no compilation involved)
# ---------------------------------------------------------------------------


def ef_wire_bytes(param_shapes, ndp: int) -> dict:
    """Per-device gradient-reduction wire bytes: fp32 ring all-reduce vs the
    int8-EF compressed exchange (`ef_psum_tree(wire="int8")`).

    The fp32 baseline is a bandwidth-optimal ring all-reduce: each device
    sends ``2 * S * (ndp-1)/ndp`` bytes for an ``S``-byte fp32 payload
    (reduce-scatter + all-gather phases).  The int8 exchange sends the same
    two phases at 1 byte/element (all_to_all of the quantized shards +
    all_gather of the requantized segments) plus one fp32 scale per leaf
    per phase — the ~4x the ROADMAP's roofline tables want visible.  Used
    by `launch.dryrun` to annotate train cells without compiling the
    int8 path (see `ef_psum_tree`).
    """
    leaves = jax.tree.leaves(param_shapes)
    n_elems = sum(int(math.prod(x.shape)) for x in leaves)
    n_leaves = len(leaves)
    frac = (ndp - 1) / ndp if ndp > 1 else 0.0
    f32 = 2 * 4 * n_elems * frac
    int8 = 2 * 1 * n_elems * frac + 2 * 4 * n_leaves * frac
    return {
        "ndp": ndp,
        "grad_elems": n_elems,
        "f32_ring_bytes_per_device": f32,
        "int8_ef_bytes_per_device": int8,
        "saving": (f32 / int8) if int8 else 1.0,
    }
