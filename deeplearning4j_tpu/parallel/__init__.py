"""Device-mesh parallelism: DP/TP/SP over XLA collectives.

The reference's distributed training was removed upstream (SURVEY.md §2.5);
this package is the TPU-native replacement designed per the GSPMD recipe:
named mesh → sharding annotations → XLA inserts ICI/DCN collectives.
"""
from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS, DeviceMesh)
from deeplearning4j_tpu.parallel.sharding import (
    ShardingRule, ShardingSpec, ShardingStrategy, data_and_tensor_parallel,
    data_parallel, megatron_data_and_tensor_parallel,
    megatron_tensor_parallel_rules, tensor_parallel_rules,
    transformer_tensor_parallel_rules)
from deeplearning4j_tpu.parallel.trainer import (
    BatchedParallelInference, ParallelInference, ParallelTrainer,
    ensure_sharded, resolve_strategy, shard_model)
from deeplearning4j_tpu.parallel.ring_attention import (
    ring_attention, ulysses_attention)
from deeplearning4j_tpu.parallel.pipeline import (
    pipeline_forward, pipeline_model_train_step, pipeline_train_step,
    place_stage_params, sequential_forward, split_microbatches)
from deeplearning4j_tpu.parallel.moe import (
    EXPERT_AXIS, dropless_topk_ffn, expert_parallel_specs, init_moe_params,
    moe_ffn, moe_train_step, sigmoid_bias_route, switch_gating,
    tiled_grouped_dot, topk_route)
from deeplearning4j_tpu.parallel import collectives, multihost

__all__ = [
    "DeviceMesh", "DATA_AXIS", "MODEL_AXIS", "PIPE_AXIS", "SEQ_AXIS",
    "ShardingRule", "ShardingSpec", "ShardingStrategy", "data_parallel",
    "ensure_sharded", "resolve_strategy", "shard_model",
    "data_and_tensor_parallel", "tensor_parallel_rules",
    "ParallelTrainer", "ParallelInference", "BatchedParallelInference",
    "megatron_data_and_tensor_parallel", "megatron_tensor_parallel_rules",
    "ring_attention",
    "ulysses_attention", "collectives", "multihost",
    "pipeline_forward", "pipeline_train_step", "pipeline_model_train_step",
    "place_stage_params", "sequential_forward", "split_microbatches",
    "transformer_tensor_parallel_rules",
    "EXPERT_AXIS", "moe_ffn", "switch_gating", "init_moe_params",
    "expert_parallel_specs", "moe_train_step", "topk_route",
    "dropless_topk_ffn", "sigmoid_bias_route", "tiled_grouped_dot",
]
