"""Parallelism layer: meshes, shardings, collective helpers."""

from .collectives import (
    all_gather,
    all_reduce_sum,
    reduce_scatter,
    ring_permute,
    sharded,
    sharded_top_k,
)
from .mesh import (
    BATCH_AXIS,
    DATA_AXIS,
    MODEL_AXIS,
    SERVING_MODES,
    data_sharding,
    device_hbm_bytes,
    make_mesh,
    make_serving_mesh,
    model_sharding,
    pad_to_multiple,
    replicated,
    resolve_serving_mode,
    rows_spec,
    single_device_mesh,
)
from .multihost import (
    from_process_local,
    global_mesh,
    host_shard,
    initialize_distributed,
)

__all__ = [
    "all_gather",
    "all_reduce_sum",
    "reduce_scatter",
    "ring_permute",
    "sharded",
    "sharded_top_k",
    "from_process_local",
    "global_mesh",
    "host_shard",
    "initialize_distributed",
    "BATCH_AXIS",
    "DATA_AXIS",
    "MODEL_AXIS",
    "SERVING_MODES",
    "data_sharding",
    "device_hbm_bytes",
    "make_mesh",
    "make_serving_mesh",
    "model_sharding",
    "pad_to_multiple",
    "replicated",
    "resolve_serving_mode",
    "rows_spec",
    "single_device_mesh",
]
