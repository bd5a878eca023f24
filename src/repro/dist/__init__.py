"""Distribution substrate: meshes plans program against.

``repro.dist.sharding`` answers *where tensors live* (batch-axes context,
name-rule parameter specs, divisibility-safe constraint helpers);
``repro.dist.collectives`` answers *what moves on the wire* (agent-grid
averages and their byte accounting).

See docs/sharding.md for the API walkthrough.
"""
from repro.dist.collectives import (agent_axes, average_agents,
                                    average_intra_pod, sync_bytes, tree_bytes)
from repro.dist.sharding import (DEFAULT_BATCH_AXES, batch_axes, batch_spec,
                                 current_batch_axes, dp_param_specs,
                                 filter_spec, named_shardings, param_specs,
                                 shape_of, shard, shard_attn_qkv)

__all__ = [
    "DEFAULT_BATCH_AXES", "agent_axes", "average_agents", "average_intra_pod",
    "batch_axes", "batch_spec", "current_batch_axes", "dp_param_specs",
    "filter_spec", "named_shardings", "param_specs", "shape_of", "shard",
    "shard_attn_qkv", "sync_bytes", "tree_bytes",
]
