"""Distributed execution: device meshes, sharded indexes, SPMD search.

Reference analogs: OperationRouting / AbstractSearchAsyncAction /
SearchPhaseController (SURVEY.md §2.6-§2.7) — redesigned as mesh-sharded
arrays + XLA collectives instead of RPC scatter/gather.
"""

from .mesh import (
    DATA_AXIS,
    SHARD_AXIS,
    fold_factor,
    make_mesh,
    mesh_shape,
    single_device_mesh,
)
from .mesh_executor import MeshExecutor, MeshUnavailable
from .sharded import (
    ShardedIndex,
    ShardedTopK,
    build_mesh_knn_step,
    build_mesh_text_step,
    build_sharded_bm25_step,
    build_sharded_knn_step,
)

__all__ = [
    "DATA_AXIS",
    "SHARD_AXIS",
    "fold_factor",
    "make_mesh",
    "mesh_shape",
    "single_device_mesh",
    "MeshExecutor",
    "MeshUnavailable",
    "ShardedIndex",
    "ShardedTopK",
    "build_mesh_knn_step",
    "build_mesh_text_step",
    "build_sharded_bm25_step",
    "build_sharded_knn_step",
]
