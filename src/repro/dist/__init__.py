"""Distribution layer: sharding-rule inference and fault-tolerant collectives.

`dist.sharding` turns a mesh + pytrees of shapes into PartitionSpecs with
*name-based* rules (mesh-shape-agnostic — required by ckpt.elastic's
reshard-restore).  `dist.collectives` provides the reductions that carry the
paper's checksums along the wire: an int8 error-feedback compressed tree
all-reduce and a Huang-Abraham checksum-verified psum (`abft_psum`), which
`train.step` threads through the gradient reduction and `serve.engine`
through the decode path's logits reduction.

Inside PARTIAL-manual shard_map regions everything in this package lowers
to plain psum (or is opt-in where it cannot, e.g.
``ef_psum_tree(wire="int8")``): an earlier toolchain's SPMD partitioner
rejected scan-over-stacked-params, the gather-family collectives and
`lax.axis_index` there, and these limits are not yet re-tested on jax 0.9
(ROADMAP Queue 3, item 2).
"""
from repro.dist import collectives, sharding  # noqa: F401
from repro.dist.collectives import abft_psum, abft_psum_tree, ef_psum_tree
from repro.dist.sharding import (MODEL_AXIS, batch_specs, cache_specs,
                                 dp_axes, infer_param_specs, to_shardings,
                                 zero1_spec, zero_dim)

__all__ = [
    "sharding", "collectives",
    "MODEL_AXIS", "dp_axes", "batch_specs", "infer_param_specs",
    "zero1_spec", "zero_dim", "cache_specs", "to_shardings",
    "ef_psum_tree", "abft_psum", "abft_psum_tree",
]
