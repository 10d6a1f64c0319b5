"""TPU-native parallelism layer.

The reference (mwtian/ray) has *no* tensor/pipeline/sequence parallelism
(SURVEY.md §2.4, §5.7) — DP exists as a library (``ray.util.sgd``) over
NCCL (``ray.util.collective``). Here the equivalent capability is built
TPU-first: a named ``jax.sharding.Mesh`` over the ICI torus, GSPMD
sharding rules, and XLA collectives, with ring attention and Ulysses
all-to-all as first-class sequence-parallel schedules.

Axes (by convention, any subset may be size 1):
  dp — data parallel (batch)
  pp — pipeline parallel (layer stages)
  sp — sequence/context parallel (ring attention / Ulysses)
  tp — tensor parallel (MXU-dim sharding; inside each held expert too)
"""

from ray_tpu.parallel.mesh import (  # noqa: F401
    AXES,
    MeshConfig,
    build_mesh,
    default_mesh_shape,
)
from ray_tpu.parallel.collectives import (  # noqa: F401
    all_gather,
    all_to_all,
    axis_index,
    axis_size,
    pmean,
    ppermute_ring,
    psum,
    psum_scatter,
    shard_map,
)
from ray_tpu.parallel.sharding import (  # noqa: F401
    logical_to_mesh,
    transformer_rules,
    with_sharding,
)
from ray_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from ray_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from ray_tpu.parallel.pipeline import pipeline_spmd  # noqa: F401
from ray_tpu.parallel.experts import expert_ffn, route  # noqa: F401
