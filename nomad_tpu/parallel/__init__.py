"""Multi-chip SPMD scheduling — the node matrix sharded over a device mesh.

SURVEY.md §2.5/§5: the reference's scale axis is nodes×allocs; it *bounds*
per-eval work (shuffle + log₂(n) candidates) and scales via optimistic worker
concurrency. This package inverts that: the (nodes × resource-dims) matrix is
sharded across TPU devices with ``jax.sharding``, every eval scores ALL nodes,
and the cross-device argmax/psum reductions ride ICI.
"""

from .sharding import (
    make_mesh,
    mesh_layout,
    shard_matrix_arrays,
    sharded_fused_place_batch,
)

__all__ = [
    "make_mesh",
    "mesh_layout",
    "shard_matrix_arrays",
    "sharded_fused_place_batch",
]
