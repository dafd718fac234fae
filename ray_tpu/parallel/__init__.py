"""TPU-native parallelism layer (SURVEY.md §2.4).

The reference scales via actor fleets + NCCL process groups (Ray Train DDP,
`ray.util.collective`); TP/PP/SP/EP exist only through integrations. Here
every strategy is first-class and jax-native: one `Mesh` with axes
(dp, fsdp, pp, tp, sp, ep), `NamedSharding` annotations, and XLA collectives
over ICI — the scaling-book recipe (pick a mesh, annotate shardings, let XLA
insert collectives).
"""

from ray_tpu.parallel.mesh import (
    MeshConfig,
    get_mesh,
    make_mesh,
    mesh_context,
)
from ray_tpu.parallel.sharding import (
    ShardingRules,
    logical_sharding,
    shard_params,
    with_sharding_constraint,
)
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.ulysses import ulysses_attention
from ray_tpu.parallel.pipeline import pipeline_spmd
from ray_tpu.parallel import distributed
from ray_tpu.parallel.distributed import (
    HybridMeshConfig,
    make_hybrid_mesh,
)

__all__ = [
    "MeshConfig",
    "ShardingRules",
    "get_mesh",
    "logical_sharding",
    "make_mesh",
    "mesh_context",
    "pipeline_spmd",
    "ring_attention",
    "shard_params",
    "ulysses_attention",
    "with_sharding_constraint",
]
