"""Launchers: production meshes, sharding rules, the multi-pod dry-run,
roofline derivation, and train/serve drivers.

NOTE: do not import ``dryrun`` from here — it sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 at import time by
design, and must only be imported as the entry module."""

from .mesh import (make_production_mesh, make_pipeline_mesh, data_axes,
                   mesh_tag)
from .sharding import (ShardingPolicy, param_sharding_tree, batch_sharding,
                       cache_sharding, opt_sharding_tree, replicated)
from .steps import (make_train_step, make_prefill_step, make_decode_step,
                    default_optimizer_name, default_microbatches)

__all__ = ["make_production_mesh", "make_pipeline_mesh", "data_axes",
           "mesh_tag", "ShardingPolicy", "param_sharding_tree",
           "batch_sharding", "cache_sharding", "opt_sharding_tree",
           "replicated", "make_train_step", "make_prefill_step",
           "make_decode_step", "default_optimizer_name",
           "default_microbatches"]
