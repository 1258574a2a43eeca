"""Grouped matmul over the experts held: rows sorted by expert, one weight
matrix a group, `out[rows of group g] = lhs[rows of group g] @ rhs[g]`.

The reference has no experts (ref hourglass.py is convolutions only); this
kernel is new capability. It is the megablox scheme (jax's shipped
`pallas.ops.tpu.megablox`, whose `make_group_metadata` it reuses): the grid
walks (n tile, visit, k tile), a visit being one (m tile, group) pair that
holds rows, found through scalar-prefetched tables, so an expert with no
rows costs nothing and its weights are never read; a float32 accumulator in
VMEM over the k tiles; the store masked to the group's rows of the tile. The
shipped `gmm` is not called because its Pallas call cannot be named (a trace
shows it as `kernel`) and its default tiles are 128^3; this one carries
`name="expert_gmm"` and tiles sized for the MXU at the decoder's widths.

Rows past the last group (the caller pads to a tile multiple) come back
uninitialised: the caller masks them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILING = (512, 1024, 1024)  # rows, contraction, columns: chip run, PR 29


def _fit(size: int, tile: int) -> int:
    """The largest of tile, tile/2, ... (down to 128) that divides `size`;
    `size` itself if none does (a toy size: one tile)."""
    while tile >= 128:
        if size % tile == 0:
            return tile
        tile //= 2
    return size


def row_tile(m: int, tiling: Tuple[int, int, int] = TILING) -> int:
    return min(tiling[0], m)


def group_metadata(group_sizes, m: int, tm: int):
    """The visit tables for `m` rows in tiles of `tm` (m % tm == 0): shared by
    every grouped matmul over the same sorted rows."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    if m % tm:
        raise ValueError("rows %d are not a multiple of the tile %d" % (m, tm))
    return make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=False)


def _kernel(group_offsets, group_ids, m_tile_ids, lhs, rhs, out, acc, *,
            tm: int, tn: int, tiles_k: int):
    visit, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        g = group_ids[visit]
        row = m_tile_ids[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (row >= group_offsets[g]) & (row < group_offsets[g + 1])
        out[...] = jnp.where(mine, acc[...],
                             out[...].astype(jnp.float32)).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def expert_gmm(lhs, rhs, metadata, tiling: Tuple[int, int, int] = TILING,
               interpret: bool = False):
    """lhs (m, k), rhs (groups, k, n), `metadata` = `group_metadata(sizes, m,
    row_tile(m, tiling))` -> (m, n) in lhs's dtype."""
    (group_offsets, group_ids, m_tile_ids), visits = metadata
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = row_tile(m, tiling), _fit(k, tiling[1]), _fit(n, tiling[2])
    tiles_k = k // tk
    itemsize = jnp.dtype(lhs.dtype).itemsize
    call = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, v, k_i, go, gi, mt:
                             (mt[v], k_i)),
                pl.BlockSpec((None, tk, tn), lambda n_i, v, k_i, go, gi, mt:
                             (gi[v], k_i, n_i)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, v, k_i, go, gi, mt:
                                   (mt[v], n_i)),
            grid=(n // tn, visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=itemsize * (m * k * (n // tn) + m * n
                                       + k * n * group_ids.shape[0])),
        interpret=interpret,
        name="expert_gmm")
    return call(group_offsets, group_ids, m_tile_ids, lhs, rhs)
