"""ray_tpu.models: first-party TPU-native model families.

The reference ships no models of its own (torch wrappers only); here the
model zoo is part of the framework so Train/Serve/RLlib drive real sharded
JAX programs.
"""

from ray_tpu.models.lm import (
    LMTrainContext,
    cross_entropy_loss,
    default_optimizer,
    head_cross_entropy,
)
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    param_axes,
)

__all__ = [
    "LMTrainContext",
    "TransformerConfig",
    "cross_entropy_loss",
    "default_optimizer",
    "forward",
    "head_cross_entropy",
    "init_params",
    "param_axes",
]
