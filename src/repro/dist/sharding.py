"""Name-based sharding rules: params, batches, optimizer state, KV caches.

Design constraints (consumed by train/step.py, ckpt/elastic.py, serve):

  * **Mesh-shape-agnostic.**  Rules key on parameter *names* (wq/wo/gate/
    down/table/...) and on divisibility against the given mesh — never on a
    fixed mesh shape.  The same param tree therefore places onto a 1x1 dev
    mesh, the 16x16 pod, or the 2x16x16 multi-pod mesh, which is what lets
    `ckpt.elastic.reshard_restore` re-place a checkpoint on the survivor
    mesh after a pod loss.
  * **Model axis is named "model"; every other axis is data-parallel.**
    Multi-pod meshes add a leading "pod" axis that behaves as extra DP.
  * **Divisibility guards everywhere.**  A dim that the mesh extent does not
    divide stays replicated instead of erroring — smoke configs and odd
    vocab/expert counts must place on any mesh.

Layout conventions assumed (models/transformer.py):
  params are stacked per layout group with a leading `repeats` dim;
  caches are stacked `[repeats, batch, ...]`.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MODEL_AXIS", "make_mesh", "dp_axes", "batch_specs", "infer_param_specs",
    "zero1_spec", "zero_dim", "cache_specs", "to_shardings",
]

MODEL_AXIS = "model"

# Projections whose OUTPUT features (last dim) split over the model axis
# (Megatron column-parallel): QKV and gate/up enter a row-parallel partner.
_COL_PARALLEL = {
    "wq", "wk", "wv",              # attention / mlstm QKV
    "gate", "up",                  # dense + MoE FFN in-projections
    "in_proj", "x_proj",           # mamba
    "wz", "wi", "wf", "wo_gate",   # xlstm gates
    "router",                      # MoE router (over experts)
    "lm_head",
}
# Projections whose INPUT features (second-to-last dim) split over model
# (row-parallel): the matmul's contraction produces the partial-sum psum.
_ROW_PARALLEL = {"wo", "down", "out_proj", "wout"}


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """Every mesh of this codebase: `jax.make_mesh` with all axes `Auto`.

    `jax.make_mesh` defaults to `Explicit` axes, where shardings are part of
    the array types and `with_sharding_constraint` / gathers on a sharded
    operand become type errors.  This code places arrays with explicit
    NamedShardings and lets the partitioner propagate the rest — the
    `Auto` contract."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def dp_axes(mesh: Mesh) -> tuple:
    """All mesh axes that are not the model axis, in mesh order.

    Everything non-"model" is data-parallel by convention (a multi-pod
    mesh's leading "pod" axis included), so this tuple is what gradient
    reductions reduce over and what ZeRO/FSDP shard over, and what
    train.step passes as the *manual* axes of the partial-manual shard_map
    that defers its gradient reduction."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def _axes_extent(mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def _model_extent(mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)


def _dp_entry(mesh):
    """The DP axes as a single PartitionSpec entry."""
    dp = dp_axes(mesh)
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def _entry_extent(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        return _axes_extent(mesh, entry)
    return mesh.shape[entry]


def _path_names(path):
    out = []
    for p in path:
        key = getattr(p, "key", None)
        if key is None:
            key = getattr(p, "name", None)
        if isinstance(key, str):
            out.append(key)
    return out


# ---------------------------------------------------------------------------
# batch
# ---------------------------------------------------------------------------


def batch_specs(mesh: Mesh, global_batch: int) -> tuple:
    """Spec entries for a leading batch dim (length-1 tuple).

    Shards the batch over the greedy prefix of the DP axes whose cumulative
    extent divides `global_batch`; replicates when nothing divides (e.g.
    batch-1 long-context decode).  Consumers: train.step input specs, the
    serving engine's decode-slot batch, and `cache_specs` (which falls back
    to sequence sharding when the batch entry replicates).
    """
    axes = []
    extent = 1
    for a in dp_axes(mesh):
        nxt = extent * mesh.shape[a]
        if global_batch % nxt == 0:
            axes.append(a)
            extent = nxt
    if not axes:
        return (None,)
    return (tuple(axes) if len(axes) > 1 else axes[0],)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def infer_param_specs(param_shapes, mesh: Mesh, cfg: Any = None):
    """PartitionSpec tree for a param tree of ShapeDtypeStructs/arrays.

    Name-based tensor-parallel rules (column/row split over "model"), with
    divisibility guards: a dim the model extent does not divide stays
    replicated instead of erroring, so any param tree places on any mesh
    (the property ckpt.elastic's survivor-mesh restore depends on).  `cfg`
    is accepted for rule refinements that need model metadata; the baseline
    rules are purely name-driven.  Row-parallel placements (wo/down/...)
    make XLA insert the partial-sum all-reduce in auto-sharded code;
    serve.engine instead lifts its final row-parallel projection into an
    explicit shard_map region so that reduction can ride `abft_psum`.
    """
    model = _model_extent(mesh)

    def rule(path, leaf):
        names = _path_names(path)
        ndim = len(leaf.shape)
        dims = [None] * ndim
        if not names or ndim == 0:
            return P()
        leaf_name = names[-1]
        owner = names[-2] if len(names) >= 2 else ""

        if leaf_name == "table":                       # embed [V, D]
            if leaf.shape[0] % model == 0:
                dims[0] = MODEL_AXIS
            return P(*dims)

        # linear params live as {"w": ..., "b": ...} under a named module;
        # MoE expert weights are raw arrays named gate/up/down under "moe".
        if leaf_name in ("w", "b"):
            module = owner
        elif owner == "moe" and leaf_name in ("gate", "up", "down"):
            module = leaf_name
        else:
            return P()                                  # norms, ssm vectors...

        if module in _COL_PARALLEL and leaf.shape[-1] % model == 0:
            dims[-1] = MODEL_AXIS
        elif module in _ROW_PARALLEL and leaf_name == "w" and ndim >= 2 \
                and leaf.shape[-2] % model == 0:
            dims[-2] = MODEL_AXIS
        elif module == "down" and owner == "moe" and ndim >= 2 \
                and leaf.shape[-2] % model == 0:        # moe down [.., dff, D]
            dims[-2] = MODEL_AXIS
        return P(*dims)

    return jax.tree_util.tree_map_with_path(rule, param_shapes)


# ---------------------------------------------------------------------------
# ZeRO sharding (optimizer state / FSDP params)
# ---------------------------------------------------------------------------


def zero_dim(spec, shape, mesh: Mesh) -> Optional[int]:
    """The dim a ZeRO shard/reduce-scatter splits over the DP axes.

    First unsharded dim whose size the full DP extent divides; None when no
    dim qualifies (leaf stays replicated over DP).
    """
    dp = dp_axes(mesh)
    if not dp:
        return None
    ndp = _axes_extent(mesh, dp)
    entries = list(spec) if spec is not None else []
    entries += [None] * (len(shape) - len(entries))
    for d, size in enumerate(shape):
        if entries[d] is None and size > 0 and size % ndp == 0:
            return d
    return None


def zero1_spec(spec, shape, mesh: Mesh):
    """Additionally shard `spec` over the DP axes along its ZeRO dim.

    Identity when no dim divides — the leaf is then DP-replicated, exactly
    like a non-ZeRO setup (correct, just not memory-saving for that leaf).
    """
    d = zero_dim(spec, shape, mesh)
    if d is None:
        return spec if spec is not None else P()
    entries = list(spec) if spec is not None else []
    entries += [None] * (len(shape) - len(entries))
    entries[d] = _dp_entry(mesh)
    return P(*entries)


# ---------------------------------------------------------------------------
# KV / recurrent caches
# ---------------------------------------------------------------------------


def cache_specs(mesh: Mesh, global_batch: int, cfg: Any = None):
    """Rule callable for `jax.tree_util.tree_map_with_path` over a cache tree.

    Cache leaves are `[repeats, batch, ...]`:
      * batch dim shards over DP when divisible;
      * batch-1 attention caches fall back to SEQUENCE sharding of the KV
        length over DP (long-context decode: the cache, not the batch, is
        the big tensor);
      * KV head dim shards over "model" when divisible;
      * per-layer `index` counters replicate.
    """
    model = _model_extent(mesh)
    dp = _dp_entry(mesh)
    ndp = _entry_extent(mesh, dp)
    bentry = batch_specs(mesh, global_batch)[0]

    def rule(path, leaf):
        names = _path_names(path)
        key = names[-1] if names else ""
        if key == "index":
            return P()
        shape = leaf.shape
        ndim = len(shape)
        dims = [None] * ndim
        if ndim >= 2:
            if bentry is not None and shape[1] % _entry_extent(mesh, bentry) == 0:
                dims[1] = bentry
            elif key in ("k", "v") and ndim >= 3 and dp is not None \
                    and shape[2] % ndp == 0:
                dims[2] = dp                      # sequence-sharded KV cache
        if key in ("k", "v", "ck", "cv") and ndim >= 4 \
                and shape[3] % model == 0:
            dims[3] = MODEL_AXIS
        return P(*dims)

    return rule


# ---------------------------------------------------------------------------
# materialization
# ---------------------------------------------------------------------------


def to_shardings(spec_tree, mesh: Mesh):
    """PartitionSpec tree -> NamedSharding tree on `mesh`.

    The bridge from this module's mesh-agnostic specs to the explicit
    NamedShardings that `jax.jit(in_shardings=...)`/`jax.device_put`
    consume.  Every sharding in this codebase is an explicit NamedSharding
    (never ambient-mesh-dependent)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
