"""Model zoo: TPU-first transformer family.

Pure-functional JAX models: parameters are plain pytrees with a parallel
pytree of logical sharding axes (`ray_tpu.parallel.sharding`), layers are
stacked and scanned (`lax.scan`) so compile time is O(1) in depth, compute
is bfloat16 on the MXU with float32 master params.
"""
from ray_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    init_params,
    param_logical_axes,
    forward,
    loss_fn,
)
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.mamba2_moe import Mamba2MoEConfig
from ray_tpu.models.mla_moe import MLAMoEConfig
from ray_tpu.models import configs
from ray_tpu.models.hf_convert import from_hf

__all__ = [
    "Transformer",
    "TransformerConfig",
    "HybridConfig",
    "Mamba2MoEConfig",
    "MLAMoEConfig",
    "init_params",
    "param_logical_axes",
    "forward",
    "loss_fn",
    "configs",
    "from_hf",
]
