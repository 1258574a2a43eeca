"""`kda_step`: one decode step of the gated delta rule with a decay a
channel (Kimi Delta Attention), every (row, head) of a batch, in place.

The reference has no linear attention (ref hourglass.py is convolutions
only); this kernel is new capability. For each row-head it reads the float32
state S (d_k x d_v) once, and writes it once, aliased to its input:

    S <- Diag(exp g) S;   w = beta (v - S^T k);   S <- S + k w^T;   o = S^T q

(`ops/linear_attention.state_step` is the same arithmetic in XLA, the twin
the program runs where Mosaic does not compile.) The step is bound by the
state's bytes: 2 x d_k x d_v x 4 a row-head, against a few hundred bytes of
vectors, and all of it is float32 elementwise work and sublane sums, so the
MXU's rounding of float32 operands never enters.

The vectors come packed: one (8, d) float32 tile a row-head, rows q, k, g,
v, beta (beta repeated along the row), then zeros. Inside the kernel the
tile is transposed once, so that q, k and g stand as columns (along the
state's d_k sublanes) and v and beta as rows (along its d_v lanes): the
decay, the correction and the write are then broadcasts, and both products
with the state are sums over sublanes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import select

# rows of the packed vector tile
Q, K, G, V, BETA = range(5)
PACK = 8


def head_block(heads: int) -> int:
    """Heads a grid step: 8 (whole sublane tiles of the output) where they
    divide, else all of a row's."""
    return 8 if heads % 8 == 0 else heads


def pack(q, k, g, v, beta):
    """q, k, g (..., d_k), v (..., d_v), beta (...) float32 with d_k == d_v
    -> (..., 8, d) float32: the kernel's vector tile."""
    d = q.shape[-1]
    if v.shape[-1] != d:
        raise ValueError("kda_step needs d_k == d_v, got %d and %d"
                         % (d, v.shape[-1]))
    rows = [q, k, g, v, jnp.broadcast_to(beta[..., None], q.shape)]
    rows += [jnp.zeros_like(q)] * (PACK - len(rows))
    return jnp.stack([r.astype(jnp.float32) for r in rows], axis=-2)


def _kernel(vec_ref, state_ref, o_ref, new_ref, *, heads: int):
    for h in range(heads):
        rows = vec_ref[0, h]                       # (8, d)
        cols = rows.T                              # (d, 8)
        q, k, g = cols[:, Q:Q + 1], cols[:, K:K + 1], cols[:, G:G + 1]
        v, beta = rows[V:V + 1, :], rows[BETA:BETA + 1, :]
        s = state_ref[0, h] * jnp.exp(g)           # the decay, a channel
        w = beta * (v - jnp.sum(s * k, axis=0, keepdims=True))
        s = s + k * w                              # the delta write
        new_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step(state, vectors, interpret: Optional[bool] = None):
    """state (B, H, d, d) float32, vectors (B, H, 8, d) float32 (`pack`) ->
    (o (B, H, d) float32, the state after the step, written over `state`).
    `interpret=None`: compiled on the chip, the Pallas interpreter
    elsewhere."""
    if interpret is None:
        interpret = not select.on_chip()
    batch, heads, d, _ = state.shape
    hb = head_block(heads)
    block = lambda b, j: (b, j, 0, 0)  # noqa: E731
    call = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        out_shape=(jax.ShapeDtypeStruct((batch, heads, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)),
        grid=(batch, heads // hb),
        in_specs=[pl.BlockSpec((1, hb, PACK, d), block),
                  pl.BlockSpec((1, hb, d, d), block)],
        out_specs=(pl.BlockSpec((1, hb, d), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, hb, d, d), block)),
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * batch * heads * d * d,
            transcendentals=batch * heads * d,
            bytes_accessed=4 * batch * heads * (2 * d * d + PACK * d + d)),
        interpret=interpret,
        name="kda_step")
    return call(vectors, state)
