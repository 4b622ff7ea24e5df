"""Parallelism library: mesh building, sharding presets, and sequence /
pipeline / expert parallelism primitives.

This is the capability layer the reference delegates to NCCL/torch
(SURVEY.md §2.5): here DP/FSDP/TP/PP/SP/EP are first-class, expressed as
GSPMD shardings over a ``jax.sharding.Mesh`` whose axes map onto ICI, with
``shard_map`` + ``ppermute`` ring collectives for the sequence dimension.
"""

from ray_tpu.parallel.mesh import (  # noqa: F401
    MeshConfig,
    build_mesh,
    mesh_shape_for,
)
from ray_tpu.parallel.sharding import (  # noqa: F401
    ShardingRules,
    logical_to_mesh,
    shard_params,
)
from ray_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from ray_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from ray_tpu.parallel.pipeline import pipeline_apply  # noqa: F401
