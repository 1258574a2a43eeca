"""Parallelism layer: device mesh, shardings, distributed init, barrier law.

The TPU-native replacement for the reference's DDP stack
(/root/reference/train.py:23-45 `mp.spawn` + NCCL process groups): one
process per host, a `jax.sharding.Mesh` over all devices, GSPMD-partitioned
jit instead of gradient-hook all-reduce. Multi-process lifecycle helpers
(process-group init, the AOT-compile -> coordination-barrier -> execute
law that sidesteps Gloo's 30 s first-execution deadline) live in
`distributed.py` (ISSUE 11).
"""

from .distributed import (
    barrier_synced_compile,
    coordination_barrier,
    init_process_group,
    use_gloo_cpu_collectives,
)
from .mesh import (
    batch_sharding,
    init_distributed,
    fit_data_mesh,
    make_mesh,
    replicated,
    shard_batch,
    under_kernel_mesh,
)

__all__ = [
    "barrier_synced_compile",
    "batch_sharding",
    "coordination_barrier",
    "init_distributed",
    "init_process_group",
    "fit_data_mesh",
    "make_mesh",
    "replicated",
    "shard_batch",
    "under_kernel_mesh",
    "use_gloo_cpu_collectives",
]
