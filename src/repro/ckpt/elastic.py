"""Elastic restore: re-shard a checkpoint (or live state) onto a different
mesh, with a placement-diff plan of what actually moves.

The TPU-native answer to FT-MPI's process respawn (DESIGN.md §3): when a pod
(or slice) is lost, training resumes on a smaller mesh — e.g. 2x16x16 ->
1x16x16 — by restoring the latest checkpoint with shardings inferred for the
*new* mesh.  Params/opt-state shardings are mesh-shape-agnostic (param rules
are name-based in `dist.sharding`; opt-state rules come from the optimizer
via `train.step.state_specs`), so the same state tree places onto any mesh
whose axis sizes divide the respective dims; the global batch is re-split
over the surviving DP extent (`data.pipeline.DataPipeline.resplit` —
gradient noise scale changes, sample order and schedule do not).

Three entry points, consumed by `ft.runtime.ElasticRuntime`:

  * `plan_reshard`     — the placement diff: per-leaf bytes, old vs new
                         spec, whether the leaf's PartitionSpec changed
                         (ZeRO dims legitimately differ when the DP extent
                         changes divisibility) — the reshard bill of
                         materials before any bytes move.
  * `reshard_restore`  — disk checkpoint -> survivor mesh (rung 3b:
                         the hardware holding the state is actually gone).
  * `reshard_state`    — LIVE state -> new mesh through host memory
                         (planned re-grow, or a shrink whose state
                         survived via `ckpt.diskless.DisklessCheckpoint
                         .reshard` — rung 3a).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np

from repro.dist import sharding as shd
from repro.train.step import StepOptions, state_specs

__all__ = ["reshard_restore", "reshard_state", "survivor_mesh",
           "plan_reshard", "ReshardPlan", "LeafMove"]


def survivor_mesh(failed_pods: int = 1, total_pods: int = 2, mesh=None):
    """Mesh over the surviving pods (drop the 'pod' axis when one remains).

    With `mesh` given, the survivor shape is derived from it: its leading
    "pod" extent shrinks by `failed_pods`, the other axes are kept — this
    is what the elastic runtime uses, so drills work on any (pod, data,
    model) drill mesh, not just the production 2x16x16.  Without `mesh`,
    the legacy production behavior: 2x16x16 -> 1x16x16 (16x16, no pod
    axis).
    """
    if mesh is not None:
        if "pod" not in mesh.axis_names:
            raise ValueError(f"mesh {dict(mesh.shape)} has no 'pod' axis "
                             "to lose")
        total_pods = mesh.shape["pod"]
        remaining = total_pods - failed_pods
        if remaining <= 0:
            raise ValueError("no survivors")
        rest_axes = tuple(a for a in mesh.axis_names if a != "pod")
        rest_shape = tuple(mesh.shape[a] for a in rest_axes)
        if remaining == 1:
            return shd.make_mesh(rest_shape, rest_axes)
        return shd.make_mesh((remaining,) + rest_shape, ("pod",) + rest_axes)
    from repro.launch.mesh import make_production_mesh
    remaining = total_pods - failed_pods
    if remaining <= 0:
        raise ValueError("no survivors")
    if remaining == 1:
        return make_production_mesh(multi_pod=False)
    return shd.make_mesh((remaining, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# placement-diff planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafMove:
    """One leaf's reshard line item."""
    path: str
    nbytes: int
    spec_from: str
    spec_to: str
    respecced: bool      # PartitionSpec changed (e.g. ZeRO dim moved)


@dataclasses.dataclass(frozen=True)
class ReshardPlan:
    """Placement diff between two mesh generations.

    On a topology change the device set itself changes, so every byte
    lands on new hardware — `bytes_total` is the reshard wire/host bill.
    `bytes_respecced` narrows that to leaves whose PartitionSpec changed
    (a different ZeRO dim, a dim that stopped dividing): those need
    re-LAYOUT, not just re-placement, and are the interesting rows of the
    report."""
    mesh_from: Tuple[Tuple[str, int], ...]
    mesh_to: Tuple[Tuple[str, int], ...]
    leaves: Tuple[LeafMove, ...]

    @property
    def bytes_total(self) -> int:
        return sum(l.nbytes for l in self.leaves)

    @property
    def bytes_respecced(self) -> int:
        return sum(l.nbytes for l in self.leaves if l.respecced)

    @property
    def n_respecced(self) -> int:
        return sum(1 for l in self.leaves if l.respecced)

    def summary(self) -> dict:
        return {
            "mesh_from": dict(self.mesh_from),
            "mesh_to": dict(self.mesh_to),
            "n_leaves": len(self.leaves),
            "n_respecced": self.n_respecced,
            "bytes_total": self.bytes_total,
            "bytes_respecced": self.bytes_respecced,
        }

    def report(self, top: int = 8) -> str:
        """Human-readable placement diff, largest re-specced leaves first."""
        s = self.summary()
        lines = [f"reshard {s['mesh_from']} -> {s['mesh_to']}: "
                 f"{s['n_leaves']} leaves / {s['bytes_total']/2**20:.1f} MiB "
                 f"move; {s['n_respecced']} leaves / "
                 f"{s['bytes_respecced']/2**20:.1f} MiB change spec"]
        resp = sorted((l for l in self.leaves if l.respecced),
                      key=lambda l: -l.nbytes)
        for l in resp[:top]:
            lines.append(f"  {l.path}: {l.nbytes/2**20:.2f} MiB  "
                         f"{l.spec_from} -> {l.spec_to}")
        if len(resp) > top:
            lines.append(f"  ... and {len(resp) - top} more")
        return "\n".join(lines)


def _dtype_bytes(leaf) -> int:
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize \
        if leaf.shape else np.dtype(leaf.dtype).itemsize


def plan_reshard(state_like, old_mesh, new_mesh,
                 opts: Optional[StepOptions] = None, cfg=None) -> ReshardPlan:
    """Diff the state placement between two meshes — the bill of materials
    `ft.runtime.ElasticRuntime` logs (bytes moved per leaf) before a
    shrink/re-grow actually moves anything.

    `state_like`: pytree of ShapeDtypeStructs (or arrays) of the full train
    state; specs for both meshes come from the same mesh-agnostic
    `train.step.state_specs`, so the diff reflects exactly what the restore
    will do."""
    opts = opts or StepOptions()
    specs_old = state_specs(state_like, old_mesh, opts, cfg)
    specs_new = state_specs(state_like, new_mesh, opts, cfg)
    flat_like, _ = jax.tree_util.tree_flatten_with_path(state_like)
    old_leaves = jax.tree.leaves(
        specs_old, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    new_leaves = jax.tree.leaves(
        specs_new, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    moves = []
    for (path, leaf), so, sn in zip(flat_like, old_leaves, new_leaves):
        moves.append(LeafMove(
            path=jax.tree_util.keystr(path),
            nbytes=_dtype_bytes(leaf),
            spec_from=str(so), spec_to=str(sn),
            respecced=tuple(so) != tuple(sn)))
    return ReshardPlan(
        mesh_from=tuple(old_mesh.shape.items()),
        mesh_to=tuple(new_mesh.shape.items()),
        leaves=tuple(moves))


# ---------------------------------------------------------------------------
# the two restore paths
# ---------------------------------------------------------------------------


def reshard_restore(manager, step: int, state_like, new_mesh,
                    opts: Optional[StepOptions] = None, cfg=None):
    """Restore checkpoint `step` placed for `new_mesh` (rung 3b: disk).

    state_like: pytree of ShapeDtypeStructs matching the saved state.
    Returns the restored state, sharded for the surviving mesh.
    """
    opts = opts or StepOptions()
    specs = state_specs(state_like, new_mesh, opts, cfg)
    shardings = shd.to_shardings(specs, new_mesh)
    return manager.restore(step, state_like, sharding_tree=shardings)


def reshard_state(state, new_mesh, opts: Optional[StepOptions] = None,
                  cfg=None):
    """Re-place LIVE state onto `new_mesh` through host memory.

    Used by the planned re-grow (the pod "returns": nothing was lost, no
    rollback — the survivor state simply spreads back over the full mesh)
    and by the rung-3a shrink whose state survived disklessly.  Goes
    device -> host -> device deliberately: the host hop works between any
    two meshes, and it is the honest cost a real pod-to-pod transfer pays
    anyway (it is what the reshard wall-clock in BENCH_PR4.json
    measures)."""
    opts = opts or StepOptions()
    state_like = jax.eval_shape(lambda: state)
    specs = state_specs(state_like, new_mesh, opts, cfg)
    shardings = shd.to_shardings(specs, new_mesh)
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
    return jax.tree.map(jax.device_put, host, shardings)
