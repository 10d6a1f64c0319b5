"""Flagship models, TPU-first.

The reference schedules user-supplied torch/TF models; here the model
zoo is part of the framework, built on ``ray_tpu.ops`` kernels and
``ray_tpu.parallel`` shardings so one definition runs single-chip or
over a dp/pp/sp/tp mesh.
"""

from ray_tpu.models.decode import generate  # noqa: F401
from ray_tpu.models.transformer import (  # noqa: F401
    DENSE_168M,
    ParallelConfig,
    TransformerConfig,
    forward,
    init_params,
    init_train_state,
    loss_fn,
    make_train_step,
    param_specs,
)
