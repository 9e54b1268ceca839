from ollamamq_tpu.parallel.mesh import (make_mesh, AXIS_DATA, AXIS_EXPERT,
                                        AXIS_TENSOR)
from ollamamq_tpu.parallel.sharding import (
    param_partition_specs,
    kv_cache_spec,
    shard_params,
)

__all__ = [
    "make_mesh", "AXIS_DATA", "AXIS_EXPERT", "AXIS_TENSOR",
    "param_partition_specs", "kv_cache_spec", "shard_params",
]
