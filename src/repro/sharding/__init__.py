from repro.sharding.partitioning import (
    DATA_AXES,
    LOGICAL_RULES,
    logical_to_mesh_spec,
    mesh_data_axes,
    named_sharding,
    shard_tree,
    constrain,
    batch_spec,
)

__all__ = [
    "DATA_AXES",
    "LOGICAL_RULES",
    "logical_to_mesh_spec",
    "mesh_data_axes",
    "named_sharding",
    "shard_tree",
    "constrain",
    "batch_spec",
]
