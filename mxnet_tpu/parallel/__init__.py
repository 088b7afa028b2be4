"""SPMD parallelism over a TPU device mesh.

This package is the TPU-native replacement for the reference's entire
distributed stack (reference: src/kvstore/{comm.h,kvstore_nccl.h,
kvstore_dist.h,kvstore_dist_server.h}, ps-lite, tools/launch.py; SURVEY
§2.3/§5.8). Instead of explicit reduce machinery, parallelism is expressed
as jax.sharding over a Mesh and XLA inserts the ICI/DCN collectives:

- data parallel == batch axis sharded over 'dp' (replaces
  DataParallelExecutorGroup + kvstore local/device/NCCL)
- tensor parallel == weight axes sharded over 'mp' (NEW capability; the
  reference only has by-device model placement via __ctx_group__)
- multi-host == jax.distributed + the same mesh spanning hosts (replaces
  ps-lite dist_sync)
"""
from __future__ import annotations

from .mesh import make_mesh, current_mesh, mesh_scope, device_count
from .spmd import (all_reduce, all_reduce_coalesced, group_all_reduce,
                   SPMDTrainer, shard_batch, replicate, shard_params)
from .ring_attention import ring_attention
from .ulysses import ulysses_attention
from .moe import (moe_ffn, switch_router, top_k_router, expert_ffn,
                  expert_parallel_ffn)
from .pipeline import pipeline_apply
from .checkpoint import (save_sharded, load_sharded, save_trainer,
                         load_trainer)

__all__ = ["moe_ffn", "switch_router", "top_k_router", "expert_ffn",
           "expert_parallel_ffn", "pipeline_apply",
           "save_sharded", "load_sharded", "save_trainer", "load_trainer",
           "make_mesh", "current_mesh", "mesh_scope", "device_count",
           "all_reduce", "all_reduce_coalesced", "group_all_reduce",
           "SPMDTrainer", "shard_batch",
           "replicate", "shard_params", "ring_attention",
           "ulysses_attention"]
