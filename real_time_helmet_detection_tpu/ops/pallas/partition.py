"""Batch-parallel partitioning of the Pallas kernels under a device mesh.

(The reference has no custom kernels and no mesh: its multi-GPU path is
DDP over whole replicas, ref train.py:23-45.)

A `pallas_call` is an opaque custom call to XLA's SPMD partitioner: inside
a GSPMD-jitted step whose operands are sharded over the batch, jax refuses
to lower it ("Mosaic kernels cannot be automatically partitioned. Please
wrap the call in a shard_map."). Every kernel in this package is
independent per sample — grids walk the batch axis, reductions leave
per-sample partials for XLA to sum (GSPMD adds the all-reduce) — so the
right partitioning is the trivial one: each chip runs the kernel on its
own batch shard. `shard_map` says exactly that.

The mesh is not visible from a traced value (GSPMD shardings live outside
the avals), so the function that owns the jit names it while it is being
traced:

    def step(state, images, ...):
        with kernel_mesh(mesh, "data"):
            ...  # every fused op traced here runs per batch shard

`batch_parallel` is the identity when no mesh is named, the axis has one
device, or the batch does not divide over it (GSPMD then decides, as it
does for any other op).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Sequence

import jax
from jax.sharding import PartitionSpec as P

_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "pallas_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh, axis: str):
    """Name the mesh (and its batch axis) for every fused op traced in
    this scope. A trace-time dynamic scope, like `jax.named_scope`."""
    token = _KERNEL_MESH.set((mesh, axis) if mesh is not None else None)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def batch_parallel(fn: Callable, batched: Sequence[bool]) -> Callable:
    """`fn(*arrays) -> array | tuple of arrays`, every output and every
    `batched[i]` input carrying the batch on dim 0, the rest replicated.
    Under a named mesh, returns `fn` mapped over the batch axis' shards."""
    scope = _KERNEL_MESH.get()
    if scope is None:
        return fn
    mesh, axis = scope
    size = mesh.shape[axis]
    if size == 1:
        return fn

    def mapped(*args):
        n = next(a.shape[0] for a, b in zip(args, batched) if b)
        if n % size:
            return fn(*args)
        in_specs = tuple(P(axis) if b else P() for b in batched)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=P(axis), check_vma=False)(*args)

    return mapped
