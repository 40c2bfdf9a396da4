"""Pipelined execution runtime: schedule analytics, micro-batched executors,
and the shard_map SPMD stage pipeline (the paper's technique as a
first-class runtime feature)."""

from .schedule import (SimResult, memory_highwater, simulate,
                       simulate_from_breakdown)
from .stage import (VGGStage, split_vgg_params, transformer_stage_fn,
                    vgg_stages_from_cuts)
from .executor import (LinkHooks, SplitLearningExecutor, microbatch_grads,
                       split_batch)
from .spmd import (PipelineConfig, make_pipelined_loss,
                   make_pipelined_train_step, plan_to_pipeline_config,
                   stage_shardings)

__all__ = [
    "SimResult", "memory_highwater", "simulate", "simulate_from_breakdown",
    "VGGStage",
    "split_vgg_params", "transformer_stage_fn", "vgg_stages_from_cuts",
    "LinkHooks",
    "SplitLearningExecutor", "microbatch_grads", "split_batch",
    "PipelineConfig", "make_pipelined_loss", "make_pipelined_train_step",
    "plan_to_pipeline_config", "stage_shardings",
]
