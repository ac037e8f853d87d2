"""Parallelism: mesh construction, sharding rules, sequence parallelism."""

from raydp_tpu.parallel.mesh import (
    data_parallel_mesh,
    make_mesh,
    mesh_axis_size,
    multihost_mesh,
)
from raydp_tpu.parallel.partitioner import (
    DataParallelPartitioner,
    NullPartitioner,
    Partitioner,
)
from raydp_tpu.parallel.ring_attention import (
    full_attention,
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
)
from raydp_tpu.parallel.pipeline import pipeline_apply, pipeline_sharded
from raydp_tpu.parallel.sharding import shard_params_by_rules, sharding_rules_fn

__all__ = [
    "DataParallelPartitioner",
    "NullPartitioner",
    "Partitioner",
    "pipeline_apply",
    "pipeline_sharded",
    "data_parallel_mesh",
    "full_attention",
    "make_mesh",
    "mesh_axis_size",
    "multihost_mesh",
    "ring_attention",
    "ring_attention_sharded",
    "shard_params_by_rules",
    "sharding_rules_fn",
    "ulysses_attention",
]
