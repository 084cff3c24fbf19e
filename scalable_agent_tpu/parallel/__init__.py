from scalable_agent_tpu.parallel.mesh import (
    MeshSpec,
    batch_shards,
    batch_sharding,
    fused_kernels_profitable,
    make_mesh,
    model_parallel_shardings,
    pallas_interpret,
    replicated_sharding,
)
from scalable_agent_tpu.parallel.sequence import (
    from_importance_weights_sharded,
)
from scalable_agent_tpu.parallel.distributed import (
    initialize_distributed,
    is_coordinator,
    local_batch_size,
)
from scalable_agent_tpu.parallel.pipeline import (
    gpipe_spmd,
    pipeline_utilization,
)
