"""Causal, mask-restricted attention of one sequence for all heads, fused:
the online softmax of flash attention, so that a score exists only as a tile
in fast memory and no float32 score tensor goes through HBM.

The reference has no attention of any kind (ref hourglass.py is convolutions
only); this kernel is new capability. It computes what
`ops/attention.py:blockwise_attention` computes in its per-head, window-less
form (which states the contract and sends such calls here), reordered: the
grid walks (head tile, visit), a visit being one (q block, key block) pair on
or under the diagonal, key blocks innermost and in order, found through
scalar-prefetched tables (a pair above the diagonal is not in the tables, so
it costs no grid step); float32 running maximum, running sum and output
accumulator in VMEM scratch; float32 scores, `scale` applied in float32, the
operands' dtype into every matrix product, `p` cast to the values' dtype for
the value product, one division by the sum at a q block's last visit.

`length` is an operand (scalar prefetch): a q block that starts at or past it
is visits that do nothing, their index maps held at the last live visit's
blocks so that nothing new is fetched, and its output rows are zeros. A part
of the scores that every head reads from ONE key array (`q_shared` (H, T, ds)
against `k_shared` (T, ds): the latent family's rotary part) is a second
product a tile, its keys fetched once a visit and never copied a head. The
mask (what `select_blocks` chose, as int8 (T, T)) is fetched once a visit for
the whole head tile. Masked entries use the finite `NEG`, so a tile with
nothing allowed gives numbers, not NaN, which the first allowed key's
rescaling wipes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import select

# as ops/attention.py's (which imports this module)
NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
# heads and keys a grid step (the mask tile and the shared keys are fetched
# once for all the heads of a step): chip run, PR 34, `scripts/attn_bench.py`
HEAD_TILE = 8
KEY_BLOCK = 1024

_NT = (((1,), (1,)), ((), ()))  # a (m, d) . b (n, d) -> (m, n)


def visits(total: int, bq: int, bk: int):
    """(q block, key block) of every visit, q blocks in order and each one's
    key blocks in order: those whose first key is not past the q block's last
    row."""
    pairs = [(i, j) for i in range(total // bq)
             for j in range(((i + 1) * bq - 1) // bk + 1)]
    return tuple(np.asarray(col, np.int32) for col in zip(*pairs))


def key_block(total: int, k_block: int = KEY_BLOCK) -> int:
    """The key block a call of `total` rows runs: `k_block` cut to what it
    shares with `total`."""
    return math.gcd(total, k_block)


def visits_run(total: int, bq: int, bk: int) -> np.ndarray:
    """int32 (total // bq + 1,): entry r is the visits that do work for a
    sequence whose first r q blocks are live (a visit of a q block past
    `length` does nothing), counted from `visits`' own tables."""
    q_of, _ = visits(total, bq, bk)
    per_block = np.bincount(q_of, minlength=total // bq)
    return np.concatenate([[0], np.cumsum(per_block)]).astype(np.int32)


def _kernel(length, q_of, k_of, *refs, bq: int, bk: int, scale: float,
            shared: bool, masked: bool):
    refs = list(refs)
    q, k, v = refs[:3]
    qs, ks = refs[3:5] if shared else (None, None)
    mask = refs[3 + 2 * shared] if masked else None
    out, m_s, l_s, acc = refs[-4:]
    visit = pl.program_id(1)
    i, j = q_of[visit], k_of[visit]
    live = i * bq < length[0]
    last = j == ((i + 1) * bq - 1) // bk

    @pl.when(j == 0)
    def _start():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(live)
    def _visit():
        row = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        allowed = col <= row
        if masked:
            allowed &= mask[...].astype(jnp.int32) != 0
        for h in range(q.shape[0]):
            s = lax.dot_general(q[h], k[h], _NT,
                                preferred_element_type=jnp.float32)
            if shared:
                s += lax.dot_general(qs[h], ks[...], _NT,
                                     preferred_element_type=jnp.float32)
            s = jnp.where(allowed, s * scale, NEG)
            m_prev = m_s[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_next)
            alpha = jnp.exp(m_prev - m_next)
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            m_s[h] = m_next
            acc[h] = alpha * acc[h] + jnp.dot(
                p.astype(v.dtype), v[h], preferred_element_type=jnp.float32)

    @pl.when(last & live)
    def _store():
        out[...] = (acc[...] / l_s[...]).astype(out.dtype)

    @pl.when(last & jnp.logical_not(live))
    def _zeros():
        out[...] = jnp.zeros_like(out)


@functools.partial(jax.jit, static_argnames=(
    "q_block", "scale", "k_block", "head_tile", "interpret"))
def attn_fused(q, k, v, length, mask=None, q_shared=None, k_shared=None, *,
               q_block: int, scale: float, k_block: int = KEY_BLOCK,
               head_tile: int = HEAD_TILE,
               interpret: Optional[bool] = None):
    """q (H, T, d), k (H, T, d), v (H, T, dv) -> (H, T, dv) in v's dtype:
    softmax over the keys s <= t of `scale` x (q . k [+ q_shared . k_shared])
    where `mask` (int8 (T, T), optional) is not 0. `length` (int32 scalar):
    the rows of a q block (`q_block` rows, T a multiple of it) that starts at
    or past it are zeros. `k_block` and `head_tile` are cut to what they
    share with T and H (toy sizes). `interpret=None`: compiled on the chip, the Pallas interpreter
    elsewhere (tests)."""
    heads, total, dv = v.shape
    if total % q_block:
        raise ValueError("%d rows are not whole q blocks of %d"
                         % (total, q_block))
    if interpret is None:
        interpret = not select.on_chip()
    bq, bk, hb = q_block, key_block(total, k_block), math.gcd(heads,
                                                              head_tile)
    q_of, k_of = visits(total, bq, bk)
    shared, masked = q_shared is not None, mask is not None

    # a visit of a q block past `length` asks for the last live visit's
    # blocks again: nothing is fetched for it
    def at(visit, n, qo, ko):
        i_live = (n[0] - 1) // bq
        i, j = qo[visit], ko[visit]
        return (jnp.minimum(i, i_live),
                jnp.where(i <= i_live, j, ((i_live + 1) * bq - 1) // bk))

    def q_map(h, visit, n, qo, ko):
        return h, at(visit, n, qo, ko)[0], 0

    def k_map(h, visit, n, qo, ko):
        return h, at(visit, n, qo, ko)[1], 0

    operands = [q, k, v]
    in_specs = [pl.BlockSpec((hb, bq, q.shape[-1]), q_map),
                pl.BlockSpec((hb, bk, k.shape[-1]), k_map),
                pl.BlockSpec((hb, bk, dv), k_map)]
    if shared:
        operands += [q_shared, k_shared]
        in_specs += [pl.BlockSpec((hb, bq, q_shared.shape[-1]), q_map),
                     pl.BlockSpec((bk, k_shared.shape[-1]),
                                  lambda h, visit, n, qo, ko:
                                  (at(visit, n, qo, ko)[1], 0))]
    if masked:
        operands.append(mask)
        in_specs.append(pl.BlockSpec((bq, bk), lambda h, visit, n, qo, ko:
                                     at(visit, n, qo, ko)))
    depth = q.shape[-1] + (q_shared.shape[-1] if shared else 0)
    itemsize = jnp.dtype(v.dtype).itemsize
    call = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=float(scale),
                          shared=shared, masked=masked),
        out_shape=jax.ShapeDtypeStruct((heads, total, dv), v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, in_specs=in_specs,
            out_specs=pl.BlockSpec((hb, bq, dv),
                                   lambda h, visit, n, qo, ko:
                                   (h, qo[visit], 0)),
            grid=(heads // hb, len(q_of)),
            scratch_shapes=[pltpu.VMEM((hb, bq, 1), jnp.float32),
                            pltpu.VMEM((hb, bq, 1), jnp.float32),
                            pltpu.VMEM((hb, bq, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * len(q_of) * bq * bk * (depth + dv),
            transcendentals=heads * len(q_of) * bq * bk,
            bytes_accessed=(itemsize * heads * (
                total * (depth + dv) + len(q_of) * bk * (k.shape[-1] + dv))
                + (heads // hb) * len(q_of) * bq * bk * masked)),
        interpret=interpret,
        name="attn_fused")
    return call(jnp.reshape(length, (1,)).astype(jnp.int32),
                jnp.asarray(q_of), jnp.asarray(k_of), *operands)
