"""Distributed ABFT SUMMA matrix-matrix multiplication (paper §2.2, §3, Fig. 1).

The paper's algorithm, mapped to JAX SPMD:

  * The device grid is a (rows=P, cols=P) mesh slice.  The *data* occupies the
    leading (P-f) x (P-f) sub-grid; the last f grid rows hold the checksum
    blocks of A and C (Cc^T A), the last f grid cols hold the checksum blocks
    of B and C (B Cr) — exactly the paper's "(2p-1) of p^2 processes are
    dedicated to fault tolerance" layout (f=1).

  * SUMMA outer-product schedule: at step k, the owner column broadcasts its
    A panel along grid rows and the owner row broadcasts its B panel along
    grid columns (masked-psum broadcast — identical communication volume to
    the paper's ring broadcast), then every device does a local rank-kb
    update.  Because the schedule is outer-product, EVERY intermediate C_k is
    checksum-consistent, which is the paper's key contribution: a failure at
    any step is recoverable without rollback.

  * Failure: `FailureEvent(step, row, col)` erases the A, B and partial-C
    blocks of one device mid-loop.  Recovery (paper §3.3) happens in-line:
    weighted psums along the surviving axis rebuild the lost blocks
    (T_checksum, the MPI_Reduce analogue), then the loop continues.

  * Local update: when the per-device block shapes are MXU-tileable
    (`local_update="auto"` on TPU, or "pallas" to force — interpret mode on
    CPU), the per-step rank-kb update runs through the fused dual-checksum
    Pallas kernel (`kernels.abft_matmul_acc_pallas`): each step's
    Huang-Abraham checksum maintenance rides the MXU pass from the
    VMEM-resident accumulator instead of separate XLA einsums, and the fused
    verify/correct prologue scrubs a silently-corrupted C element at the
    NEXT step's load (plus a post-loop scrub for a last-step flip).  The
    plain-jnp update (`local_update="jnp"`, the default off-TPU for
    non-tileable blocks) is preserved unchanged.

Everything is jit-safe; the failure coordinates are static (recovery is
compiled after failure detection, mirroring FT-MPI's out-of-band restart).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.encoding import EncodingSpec, encode_block_cols, encode_block_rows, make_spec

__all__ = ["FailureEvent", "MultiFailureEvent", "BitflipEvent",
           "abft_summa", "summa", "encode_operands"]


@dataclasses.dataclass(frozen=True)
class FailureEvent:
    """Erase device (row, col)'s blocks after `step` SUMMA steps."""
    step: int
    row: int
    col: int


@dataclasses.dataclass(frozen=True)
class MultiFailureEvent:
    """Erase SEVERAL devices simultaneously after `step` SUMMA steps.

    Recoverable iff, per grid column, at most f devices fail (A/C recover
    along columns via cc) AND, per grid row, at most f fail (B recovers
    along rows via cr) — the in-flight analogue of the paper's f-failure
    condition.
    """
    step: int
    devices: Tuple[Tuple[int, int], ...]

    def check(self, f: int):
        by_col: dict = {}
        by_row: dict = {}
        for (r, c) in self.devices:
            by_col.setdefault(c, []).append(r)
            by_row.setdefault(r, []).append(c)
        if any(len(v) > f for v in by_col.values()):
            raise ValueError(f"more than f={f} failures in one grid column")
        if any(len(v) > f for v in by_row.values()):
            raise ValueError(f"more than f={f} failures in one grid row")
        return by_col, by_row


@dataclasses.dataclass(frozen=True)
class BitflipEvent:
    """Corrupt one element of the partial C on device (row,col) after `step`."""
    step: int
    row: int
    col: int
    delta: float = 1.0e3


def encode_operands(a: jax.Array, b: jax.Array, spec: EncodingSpec):
    """Row-encode A ([M,K] -> [M+f*mb,K]) and col-encode B ([K,N] -> [K,N+f*nb]).

    Checksum granularity is the process grid (one block per device), so the
    encoded matrices gain f full block rows / cols.
    """
    a_enc = encode_block_rows(a, spec.cc)
    b_enc = encode_block_cols(b, spec.cr)
    return a_enc, b_enc


def _solve_static(a: jax.Array, b: jax.Array) -> jax.Array:
    """Solve a @ x = b for a tiny static-k system in pure jnp.

    Runs inside shard_map, where an earlier toolchain could not lower
    jnp.linalg.solve's custom call (not yet re-tested on jax 0.9:
    ROADMAP Queue 3, item 2).  k is the number of simultaneously failed
    lines (<= f, i.e. 1-2 in practice); closed forms for k<=2, unrolled
    Gauss-Jordan with partial pivoting beyond.
    """
    k = a.shape[0]
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if k == 1:
        return b / a[0, 0]
    if k == 2:
        det = a[0, 0] * a[1, 1] - a[0, 1] * a[1, 0]
        return jnp.stack([(a[1, 1] * b[0] - a[0, 1] * b[1]) / det,
                          (a[0, 0] * b[1] - a[1, 0] * b[0]) / det])
    aug = jnp.concatenate([a, b], axis=1)
    for col in range(k):
        piv = jnp.argmax(jnp.abs(aug[col:, col])) + col
        swap = jnp.stack([aug[piv], aug[col]])
        aug = aug.at[jnp.asarray([col, piv])].set(swap)
        aug = aug / jnp.where(jnp.arange(k) == col,
                              aug[col, col], 1.0)[:, None]
        elim = aug - jnp.where(jnp.arange(k) == col, 0.0,
                               aug[:, col])[:, None] * aug[col][None]
        aug = elim
    return aug[:, k:]


def _local_summa(
    a_blk, b_blk, *,
    grid: int,
    row_axis: str,
    col_axis: str,
    spec: Optional[EncodingSpec],
    failure: Optional[FailureEvent],
    bitflip: Optional[BitflipEvent],
    preferred_dtype,
    fused_plan=None,
):
    """Per-device SUMMA body (runs inside shard_map)."""
    from repro.kernels import ops as kops  # lazy: avoids core<->kernels cycle

    my_row = lax.axis_index(row_axis)
    my_col = lax.axis_index(col_axis)
    mb, kb_local = a_blk.shape
    nb = b_blk.shape[1]
    fused = fused_plan is not None
    # The plain (non-FT) SUMMA baseline must not pay the per-step scrub nor
    # be able to rewrite its own accumulator — verify only under an ABFT
    # encoding (spec), where the scrub is the point.
    fused_verify = fused and spec is not None
    if fused:
        wm = kops.kernel_weights(mb)
        wn = kops.kernel_weights(nb).T

    def bcast_panels(a_blk, b_blk, k):
        # Masked-psum broadcast: owner column k sends its A panel along the
        # row; owner row k sends its B panel along the column.  Same volume
        # as the paper's ring broadcast (each link carries one panel).
        a_panel = lax.psum(
            jnp.where(my_col == k, a_blk, jnp.zeros_like(a_blk)), col_axis
        )
        b_panel = lax.psum(
            jnp.where(my_row == k, b_blk, jnp.zeros_like(b_blk)), row_axis
        )
        return a_panel, b_panel

    def step(k, carry):
        a_blk, b_blk, c_blk, state = carry
        a_panel, b_panel = bcast_panels(a_blk, b_blk, k)
        if fused:
            # rank-kb update through the fused dual-checksum kernel: the
            # checksum state is maintained (and C_in scrubbed) in the same
            # MXU pass as the accumulation.
            c_blk, state, _stats = kops.abft_matmul_acc(
                a_panel.astype(preferred_dtype),
                b_panel.astype(preferred_dtype),
                c_blk, state, plan=fused_plan, wm=wm, wn=wn,
                verify=fused_verify, out_dtype=jnp.float32,
                backend="pallas", interpret=not kops.on_tpu(),
            )
        else:
            c_blk = c_blk + jnp.dot(
                a_panel.astype(preferred_dtype),
                b_panel.astype(preferred_dtype),
                preferred_element_type=jnp.float32,
            ).astype(c_blk.dtype)
        return (a_blk, b_blk, c_blk, state)

    c_blk = lax.pcast(jnp.zeros((mb, nb), dtype=jnp.float32),
                      (row_axis, col_axis), to="varying")
    state = ()
    if fused:
        state = jax.tree.map(
            lambda x: lax.pcast(x, (row_axis, col_axis), to="varying"),
            kops.acc_state_zeros(fused_plan))
    carry = (a_blk, b_blk, c_blk, state)

    events = []
    if failure is not None:
        events.append(("fail", failure))
    if bitflip is not None:
        events.append(("flip", bitflip))
    events.sort(key=lambda e: e[1].step)

    k0 = 0
    for kind, ev in events:
        carry = lax.fori_loop(k0, ev.step, step, carry)
        k0 = ev.step
        a_blk, b_blk, c_blk, state = carry
        if kind == "fail":
            assert spec is not None, "failure injection requires an encoding"
            devices = (ev.devices if isinstance(ev, MultiFailureEvent)
                       else ((ev.row, ev.col),))
            by_col: dict = {}
            by_row: dict = {}
            for (r, c) in devices:
                by_col.setdefault(c, []).append(r)
                by_row.setdefault(r, []).append(c)
            # --- the failure: these devices' state is gone ---------------
            hit = jnp.zeros((), bool)
            for (r, c) in devices:
                hit = hit | ((my_row == r) & (my_col == c))
            a_blk = jnp.where(hit, jnp.zeros_like(a_blk), a_blk)
            b_blk = jnp.where(hit, jnp.zeros_like(b_blk), b_blk)
            c_blk = jnp.where(hit, jnp.zeros_like(c_blk), c_blk)
            # --- T_checksum: rebuild from the weighted checksums ---------
            # A and the partial C recover along columns (cc checksums);
            # B recovers along rows (cr) — per line, a joint f-way solve.
            for col, rows in by_col.items():
                a_blk = _recover_line(
                    a_blk, spec.cc, grid, my_row, my_col, tuple(rows), col,
                    line_axis=row_axis, f=spec.f)
                c_blk = _recover_line(
                    c_blk, spec.cc, grid, my_row, my_col, tuple(rows), col,
                    line_axis=row_axis, f=spec.f)
            for row, cols in by_row.items():
                b_blk = _recover_line(
                    b_blk, spec.cr, grid, my_col, my_row, tuple(cols), row,
                    line_axis=col_axis, f=spec.f)
            if fused:
                # the kernel-level checksum state predates the rebuild (the
                # recovered blocks carry fresh rounding) — re-derive it from
                # the recovered C so the next fused step doesn't misread the
                # recovery noise as corruption.
                state = kops.tile_checksums(
                    c_blk.astype(jnp.float32), wm, wn,
                    fused_plan.bm, fused_plan.bn)
            carry = (a_blk, b_blk, c_blk, state)
        else:  # bit-flip: silent corruption of one partial-sum element
            hit = (my_row == ev.row) & (my_col == ev.col)
            c_blk = jnp.where(
                hit, c_blk.at[0, 0].add(jnp.float32(ev.delta)), c_blk
            )
            carry = (a_blk, b_blk, c_blk, state)

    carry = lax.fori_loop(k0, grid, step, carry)
    c_blk = carry[2]
    if fused_verify:
        # post-loop scrub: a flip after the last accumulate has no next
        # kernel call to catch it; the state-vs-C residual repairs it here.
        c_blk = kops.correct_from_state(
            c_blk, carry[3], wm, wn, fused_plan.bm, fused_plan.bn)[0]
    return c_blk


def _recover_line(
    x_blk, weights, grid, my_line, my_perp, fail_lines, fail_perp, *,
    line_axis: str, f: int,
):
    """Rebuild the blocks at (fail_lines x {fail_perp}) from the line's
    checksums — a joint |failed-data| x |failed-data| solve (paper §2.1).

    The line runs along `line_axis` (length `grid` = p_data + f); data
    indices are [0, p_data), checksum j lives at index p_data + j and holds
    sum_i weights[j, i] * x_i.  Every device in the perpendicular slice
    `fail_perp` participates in the psums; other slices psum zeros (no-op).
    Lost checksum blocks are recomputed from the restored data afterwards.
    """
    p_data = grid - f
    w32 = weights.astype(jnp.float32)  # [f, p_data]
    in_slice = my_perp == fail_perp
    is_data = my_line < p_data
    failed_data = tuple(l for l in fail_lines if l < p_data)
    failed_cs = tuple(l for l in fail_lines if l >= p_data)
    is_failed = jnp.zeros((), bool)
    for l in fail_lines:
        is_failed = is_failed | (my_line == l)
    is_failed = is_failed & in_slice

    idx_data = jnp.clip(my_line, 0, p_data - 1)
    w_mine = w32[:, idx_data]                                   # [f]
    x32 = x_blk.astype(jnp.float32)

    if failed_data:
        # rhs_j = y_j - sum_ok w[j,i] x_i  (failed blocks are zeroed, so
        # they contribute nothing to the partial sums)
        contrib_data = -w_mine[:, None, None] * x32[None]       # [f, mb, nb]
        slot = my_line - p_data
        one_hot = (jnp.arange(f) == slot).astype(jnp.float32)
        contrib_cs = one_hot[:, None, None] * x32[None]
        contrib = jnp.where(is_data, contrib_data, contrib_cs)
        contrib = jnp.where(in_slice & ~is_failed, contrib,
                            jnp.zeros_like(contrib))
        rhs = lax.psum(contrib, line_axis)                      # [f, mb, nb]

        k = len(failed_data)
        # use only checksum slots whose devices SURVIVED (a failed checksum
        # device contributes a zeroed y_j — its equation is unusable)
        avail = tuple(j for j in range(f)
                      if (p_data + j) not in fail_lines)[:k]
        assert len(avail) == k, "not enough surviving checksums in line"
        sel = jnp.asarray(avail)
        sub = w32[sel][:, jnp.asarray(failed_data)]             # [k, k]
        sol = _solve_static(
            sub, rhs[sel].reshape(k, -1)).reshape((k,) + x_blk.shape)
        restored = jnp.zeros_like(x32)
        for i, l in enumerate(failed_data):
            restored = jnp.where(my_line == l, sol[i], restored)
        x_blk = jnp.where(is_failed & is_data,
                          restored.astype(x_blk.dtype), x_blk)

    if failed_cs:
        # recompute lost checksum blocks from the (now restored) data
        x32 = x_blk.astype(jnp.float32)
        for l in failed_cs:
            j = l - p_data
            contrib2 = jnp.where(in_slice & is_data, w_mine[j] * x32,
                                 jnp.zeros_like(x32))
            sol = lax.psum(contrib2, line_axis)
            x_blk = jnp.where(is_failed & (my_line == l),
                              sol.astype(x_blk.dtype), x_blk)
    return x_blk


def _resolve_local_update(local_update: str, mb: int, kb: int, nb: int):
    """Map a `local_update` request to a fused BlockPlan (or None for jnp).

    "pallas" demands the fused kernel (raises if the local block shapes are
    not exactly tileable — padding inside the shard_map loop would churn
    copies every step); "auto" fuses on TPU when exactly tileable; "jnp"
    keeps the plain dot.
    """
    from repro.kernels import ops as kops  # lazy: avoids core<->kernels cycle

    if local_update == "jnp":
        return None
    # require_exact: the carried checksum state lives across the whole SUMMA
    # loop, and padding every step would churn copies — search only tilings
    # that divide the local blocks (the cost model may otherwise prefer a
    # padded plan for its fewer HBM re-streams).  best_plan resolves a
    # measured winner (env override / warmed cache) when one exists and
    # falls back to the pure cost model — it never measures inline.
    from repro.kernels import autotune as ktune
    plan = ktune.best_plan(mb, kb, nb, carry=True, require_exact=True)
    if local_update == "pallas":
        if plan is None:
            raise ValueError(
                f"local_update='pallas' needs block-divisible local shapes, "
                f"got ({mb},{kb},{nb})")
        return plan
    if local_update == "auto":
        return plan if plan is not None and kops.on_tpu() else None
    raise ValueError(f"unknown local_update {local_update!r}")


def abft_summa(
    a_enc: jax.Array,
    b_enc: jax.Array,
    mesh: Mesh,
    *,
    axes: Tuple[str, str] = ("rows", "cols"),
    spec: EncodingSpec,
    failure: Optional[FailureEvent] = None,
    bitflip: Optional[BitflipEvent] = None,
    preferred_dtype=jnp.float32,
    local_update: str = "auto",
) -> jax.Array:
    """Fault-tolerant distributed matmul of encoded operands.

    a_enc: [M + f*mb, K] row-encoded; b_enc: [K, N + f*nb] col-encoded.
    Returns the fully-encoded product C_F = [M+f*mb, N+f*nb] (Eq. 1).
    The grid is square: mesh.shape[axes[0]] == mesh.shape[axes[1]].
    `local_update` selects the per-step rank-kb update: "pallas" fuses the
    checksum maintenance + SDC scrub into the Pallas GEMM kernel, "jnp" is
    the plain dot, "auto" fuses on TPU when the local blocks are tileable.
    """
    row_axis, col_axis = axes
    grid = mesh.shape[row_axis]
    if mesh.shape[col_axis] != grid:
        raise ValueError("ABFT SUMMA needs a square grid")
    fused_plan = _resolve_local_update(
        local_update, a_enc.shape[0] // grid, a_enc.shape[1] // grid,
        b_enc.shape[1] // grid)

    body = functools.partial(
        _local_summa,
        grid=grid,
        row_axis=row_axis,
        col_axis=col_axis,
        spec=spec,
        failure=failure,
        bitflip=bitflip,
        preferred_dtype=preferred_dtype,
        fused_plan=fused_plan,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
        out_specs=P(row_axis, col_axis),
        # VMA checking off around the fused Pallas update (pallas_call had
        # no VMA rule on an earlier toolchain; ROADMAP Queue 3, item 2)
        check_vma=fused_plan is None,
    )
    # jit: a shard_map called outside jit runs its body op by op
    return jax.jit(fn)(a_enc, b_enc)


def summa(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    axes: Tuple[str, str] = ("rows", "cols"),
    preferred_dtype=jnp.float32,
    local_update: str = "auto",
) -> jax.Array:
    """Plain (non-FT) SUMMA — the paper's PBLAS PDGEMM baseline."""
    row_axis, col_axis = axes
    grid = mesh.shape[row_axis]
    fused_plan = _resolve_local_update(
        local_update, a.shape[0] // grid, a.shape[1] // grid,
        b.shape[1] // grid)
    body = functools.partial(
        _local_summa,
        grid=grid,
        row_axis=row_axis,
        col_axis=col_axis,
        spec=None,
        failure=None,
        bitflip=None,
        preferred_dtype=preferred_dtype,
        fused_plan=fused_plan,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis, col_axis)),
        out_specs=P(row_axis, col_axis),
        check_vma=fused_plan is None,
    )
    return jax.jit(fn)(a, b)
