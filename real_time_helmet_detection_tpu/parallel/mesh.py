"""Device mesh construction and sharding specs.

Capability parity with the reference's distributed layer, re-designed for
XLA GSPMD:

* reference `mp.spawn` one-process-per-GPU + NCCL rendezvous
  (/root/reference/train.py:23-45, config.py:44-47) becomes **one process
  per host** + `jax.distributed.initialize` over DCN; all devices of all
  hosts join a single `Mesh`;
* reference `DistributedDataParallel` gradient all-reduce
  (/root/reference/train.py:174-175) becomes GSPMD auto-partitioning of the
  jitted train step: batch arrays are sharded over the `data` mesh axis and
  XLA inserts the gradient `all-reduce` over ICI itself;
* the optional `spatial` mesh axis shards the H dimension of the 512x512
  activation maps — the idiomatic TPU "sequence/context parallel" analogue
  for a CNN (SURVEY.md §2.3): XLA emits halo exchanges for the convolutions
  automatically.

Mesh axes: `("data", "spatial")`. With `spatial=1` this is pure DP, the
reference's only parallelism.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SPATIAL_AXIS = "spatial"


def init_distributed(cfg) -> None:
    """Multi-host rendezvous (≡ reference `dist.init_process_group`,
    /root/reference/train.py:42-45). No-op for single-host runs, and
    idempotent within a process (both train() and evaluate() call it at
    their top, so a driver composing them must not double-rendezvous).
    The config-free core lives in distributed.init_process_group."""
    from .distributed import init_process_group
    # dist_url keeps the reference's tcp://host:port convention.
    init_process_group(cfg.dist_url.replace("tcp://", ""),
                       getattr(cfg, "world_size", 1), cfg.rank)


def make_mesh(num_devices: int = 0, spatial: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build the ("data", "spatial") mesh.

    Args:
      num_devices: how many devices to use; 0 = all visible.
      spatial: size of the spatial-sharding axis (must divide num_devices).
      devices: explicit device list (testing); default `jax.devices()`.
    """
    devs = list(devices if devices is not None else jax.devices())
    if num_devices:
        devs = devs[:num_devices]
    n = len(devs)
    if n % spatial != 0:
        raise ValueError(f"spatial={spatial} must divide device count {n}")
    arr = np.asarray(devs).reshape(n // spatial, spatial)
    return Mesh(arr, (DATA_AXIS, SPATIAL_AXIS))


def fit_data_mesh(batch_size: int, num_devices: int = 0,
                  spatial: int = 1) -> int:
    """Single-host mesh sizing shared by train and eval: clamp the request
    to the VISIBLE device count (make_mesh would silently trim an
    oversized request, then the sharding constraint would crash on the
    first call), then shrink the data axis to the largest size that
    divides `batch_size` (≡ the reference's per-GPU batch split,
    ref train.py:38 — but without its silent truncation). Returns the
    total device count to build the mesh with (data * spatial, >= spatial).
    """
    ndev = len(jax.devices())
    if num_devices:
        ndev = min(num_devices, ndev)
    if ndev < spatial or ndev % spatial:
        raise ValueError(
            "spatial=%d must divide the usable device count %d"
            % (spatial, ndev))
    data = ndev // spatial
    while batch_size % data:
        data -= 1
    return data * spatial


def under_kernel_mesh(fn, mesh: Mesh):
    """`fn` with `mesh` named for the Pallas kernels traced inside it, so
    each chip runs them on its own batch shard (ops/pallas/partition.py).
    Wrap the function a mesh-sharded `jax.jit` is given; attributes the
    step builders hang on their bodies (`sentinel`) carry over."""
    import functools

    from ..ops.pallas.partition import kernel_mesh

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with kernel_mesh(mesh, DATA_AXIS):
            return fn(*args, **kwargs)

    return traced


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params, opt state, scalars)."""
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int, spatial_dim: Optional[int] = None) -> NamedSharding:
    """Sharding for a batch array: dim 0 over `data`, optionally one spatial
    dim over `spatial` (H of NHWC / NSHWC maps)."""
    spec = [None] * ndim
    spec[0] = DATA_AXIS
    if spatial_dim is not None and mesh.shape[SPATIAL_AXIS] > 1:
        spec[spatial_dim] = SPATIAL_AXIS
    return NamedSharding(mesh, P(*spec))


def shard_batch(mesh: Mesh, arrays, spatial_dims=None):
    """Put a pytree of *process-local* host batch arrays onto the mesh with
    batch(+spatial) shardings. `spatial_dims` maps leaf index -> spatial dim
    (or None).

    This is the host->device boundary (≡ reference `.to(device)`,
    /root/reference/train.py:99). Single-host this is a sharded
    `device_put`; multi-host each process contributes its local shard and
    the result is the assembled *global* array (the global batch is
    `num_hosts x local_batch` — the DistributedSampler contract,
    ref train.py:54).
    """
    leaves, treedef = jax.tree.flatten(arrays)
    sd = spatial_dims or [None] * len(leaves)
    multi = jax.process_count() > 1
    out = []
    for x, d in zip(leaves, sd):
        sharding = batch_sharding(mesh, np.ndim(x), d)
        if multi:
            out.append(jax.make_array_from_process_local_data(sharding, x))
        else:
            out.append(jax.device_put(x, sharding))
    return jax.tree.unflatten(treedef, out)
