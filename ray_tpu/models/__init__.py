from ray_tpu.models.gpt import (
    GPTConfig,
    forward,
    init_params,
    loss_fn,
    num_params,
    param_logical_axes,
    train_flops_per_token,
)
from ray_tpu.models.glm4_moe_lite import GLM4MoELiteConfig
from ray_tpu.models.lfm2 import LFM2Config
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.olmoe import OLMoEConfig
from ray_tpu.models.resnet import ResNetConfig
from ray_tpu.models.training import (
    TrainState,
    create_train_state,
    default_optimizer,
    make_train_step,
    param_shardings,
    shard_batch,
)

__all__ = [
    "GLM4MoELiteConfig",
    "GPTConfig",
    "LFM2Config",
    "LlamaConfig",
    "OLMoEConfig",
    "ResNetConfig",
    "TrainState",
    "create_train_state",
    "default_optimizer",
    "forward",
    "init_params",
    "loss_fn",
    "make_train_step",
    "num_params",
    "param_logical_axes",
    "param_shardings",
    "shard_batch",
    "train_flops_per_token",
]
