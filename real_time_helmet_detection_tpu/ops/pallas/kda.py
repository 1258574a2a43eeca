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

`kda_prefill`: one prefill pass of rows through the chunked delta rule
(`ops/linear_attention.chunked_prefill` is its XLA twin, the contract and the
arithmetic's statement). The grid walks (row, head block) and, innermost and
in order, the pass's chunks; a block is (1, C, 8 heads, d) of the (R, T, H,
d) arrays as the program holds them (q, k and o; g and v as (R, T, H d),
the layout their products give), so no layout changes on the way in or out,
and a head's (C, d) is read and written with a sublane stride. Each
head's float32 state lives in VMEM scratch from the row's first chunk to
its last and is written out once. A chunk that
starts at or past the pass's longest row (`live`, scalar prefetch) fetches
nothing new and writes zeros. Within a chunk of C, A[r, s] = sum_c k_r[c]
k_s[c] exp(G_r[c] - G_s[c]) (and B, with q_r) is cut at sub-chunks of
`SUB`: for r in sub-chunk i and s before it, with p the last position before
sub-chunk i,

    exp(G_r - G_s) = exp(G_r - G_p) exp(G_p - G_s),   both exponents <= 0,

so those blocks are one product on the MXU of k_r exp(G_r - G_p) against
k_s exp(G_p - G_s); only the SUB x SUB diagonal blocks keep the pairwise
decays (C x SUB x d_k elementwise terms a chunk and head in place of C x C
x d_k). Every decay is a difference with the later position first, so no
exp(+G) appears whatever g is. The cumulative decays are shifted adds on
the VPU, the unit lower triangular system is solved by forward substitution
in VMEM, and the outputs and the state's hand-over are products against the
VMEM state. Every product runs at HIGHEST on float32 operands (what the
chip spends where: PERF.md section 6).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import select

# rows of the packed vector tile
Q, K, G, V, BETA = range(5)
PACK = 8
# positions a diagonal block of the prefill's chunk: one sublane tile
SUB = 8
# heads a loop step of a grid step (the chunk math is traced once for
# them): a pass of 16 x 512 x 32 x 128 took 10.73 / 9.07 / 7.67 ms at
# 1 / 2 / 8 on a TPU v5e
HEAD_GROUP = 8
HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))  # a (m, d) . b (n, d) -> (m, n)


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


def _mm(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
    return lax.dot_general(a, b, dims, precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _cumsum_rows(x):
    """Inclusive cumulative sum down the rows of x (C, d): log2(C) shifted
    adds (a product on the MXU would put its latency at the head of every
    chunk's chain)."""
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _solve(n, w):
    """W with (I + N) W = w, N (C, C) strictly lower triangular: forward
    substitution by columns, each column's update only on the row tiles
    (8 rows) at or below it."""
    size = n.shape[0]
    ws = [w[i:i + 8] for i in range(0, size, 8)]
    ns = [n[i:i + 8] for i in range(0, size, 8)]
    for s in range(size - 1):
        row_s = ws[s // 8][s % 8:s % 8 + 1]             # final from here
        for t in range(s // 8, len(ws)):
            ws[t] = ws[t] - ns[t][:, s:s + 1] * row_s
    return jnp.concatenate(ws)


def _head_chunk(state, q, k, g, v, beta, sub: int):
    """One chunk of one head: state (d_k, d_v); q, k, g (C, d_k); v (C,
    d_v); beta (C, 1). Returns (the state after it, o (C, d_v))."""
    size, dk = k.shape
    parts = size // sub
    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    gam = _cumsum_rows(g)                              # G, cumulative, <= 0
    gam3, k3, q3 = (x.reshape(parts, sub, dk) for x in (gam, k, q))
    # G_p: the cumulative decay at the last position before each row's
    # sub-chunk (0 before the first)
    ends = gam3[:, sub - 1:, :]
    before = jnp.zeros((1, 1, dk), jnp.float32)
    if parts > 1:
        before = jnp.concatenate([before, ends[:-1]], axis=0)
    from_p = jnp.exp(gam3 - before).reshape(size, dk)  # G_r - G_p
    rk, rq = k * from_p, q * from_p
    # the blocks below the diagonal: one product a sub-chunk of rows
    at = lax.broadcasted_iota(jnp.int32, (size, dk), 0)
    a_rows, b_rows = [], []
    for i in range(parts):
        lo, hi = i * sub, (i + 1) * sub
        if i == 0:
            a_rows.append(jnp.zeros((sub, size), jnp.float32))
            b_rows.append(jnp.zeros((sub, size), jnp.float32))
            continue
        left = k * jnp.exp(jnp.where(at < lo, gam[lo - 1:lo] - gam,
                                     -jnp.inf))          # G_p - G_s
        both = _mm(jnp.concatenate([rk[lo:hi], rq[lo:hi]]), left, _NT)
        a_rows.append(both[:sub])
        b_rows.append(both[sub:])
    a = jnp.concatenate(a_rows)
    b = jnp.concatenate(b_rows)
    # the diagonal blocks: pairwise, a column of the sub-chunk at a time
    place = lax.broadcasted_iota(jnp.int32, (parts, sub, dk), 1)
    start = row // sub * sub
    for j in range(sub):
        decay = jnp.exp(jnp.where(place >= j, gam3 - gam3[:, j:j + 1, :],
                                  -jnp.inf))             # G_r - G_s
        ks = decay * k3[:, j:j + 1, :]
        a_j = jnp.sum(ks * k3, axis=-1, keepdims=True).reshape(size, 1)
        b_j = jnp.sum(ks * q3, axis=-1, keepdims=True).reshape(size, 1)
        on = col == start + j
        a = jnp.where(on & (row > col), a_j, a)
        b = jnp.where(on & (row >= col), b_j, b)
    # (I + Diag(beta) A) W = Diag(beta) [V, K_G]
    e0 = jnp.exp(gam)                                    # G_r - G(start)
    w = _solve(beta * a, beta * jnp.concatenate([v, e0 * k], axis=1))
    dv = v.shape[-1]
    through = _mm(jnp.concatenate([w[:, dv:], e0 * q]), state)
    w = w[:, :dv] - through[:size]                       # the writes
    last = gam[size - 1:]
    to_end = k * jnp.exp(last - gam)                     # G_last - G_s
    # B W and (K exp(G_last - G))^T W: one product
    out = _mm(jnp.concatenate([b, jnp.transpose(to_end)]), w)
    keep = jnp.transpose(jnp.broadcast_to(jnp.exp(last), (8, dk)))[:, :1]
    return keep * state + out[size:], through[size:] + out[:size]


def _prefill_kernel(live, q_ref, k_ref, g_ref, v_ref, b_ref, o_ref, s_out,
                    s_scr, *, heads: int, group: int, sub: int):
    n = pl.program_id(2)
    dk, dv = s_scr.shape[1:]

    @pl.when(n == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    @pl.when(n < live[0])
    def _chunk():
        betas = b_ref[0, 0]                              # (C, heads)
        lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)

        def heads_of_step(i, carry):  # traced once: a chunk is long code
            for h in (i * group + m for m in range(group)):
                kc = pl.ds(pl.multiple_of(h * dk, dk), dk)
                vc = pl.ds(pl.multiple_of(h * dv, dv), dv)
                beta = jnp.sum(jnp.where(lane == h, betas, 0.0), axis=1,
                               keepdims=True)
                state, o = _head_chunk(
                    s_scr[h], q_ref[0, :, h, :], k_ref[0, :, h, :],
                    g_ref[0, :, kc], v_ref[0, :, vc], beta, sub)
                s_scr[h] = state
                o_ref[0, :, h, :] = o
            return carry
        lax.fori_loop(0, heads // group, heads_of_step, 0)

    @pl.when(n >= live[0])
    def _past():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n == pl.num_programs(2) - 1)
    def _hand_over():
        s_out[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_prefill(q, k, v, g, beta, live, *, chunk: int,
                interpret: Optional[bool] = None):
    """One pass of rows. q, k, g (R, T, H, d_k), v (R, T, H, d_v), beta (R,
    T, H), float32, T a multiple of `chunk`; `live` int32 scalar: the chunks
    run (those before it), from a zero state. Returns (o (R, T, H, d_v)
    float32, zeros in chunks not run; the state after the last chunk run
    (R, H, d_k, d_v)). `interpret=None`: compiled on the chip, the Pallas
    interpreter elsewhere."""
    if interpret is None:
        interpret = not select.on_chip()
    rows, total, heads, dk = q.shape
    dv = v.shape[-1]
    hb, sub = head_block(heads), math.gcd(chunk, SUB)
    n = total // chunk
    # (R, T, H) -> (R, H / hb, T, hb): a head block's betas as one tile
    beta = beta.reshape(rows, total, heads // hb, hb).transpose(0, 2, 1, 3)

    def fetched(c, live):  # a chunk not run fetches nothing new
        return jnp.minimum(c, jnp.maximum(live[0] - 1, 0))

    def heads_of(width):  # (R, T, H, d): a head's rows read with a stride
        return pl.BlockSpec((1, chunk, hb, width), lambda r, j, c, live: (
            r, fetched(c, live), j, 0))

    def flat(width):  # (R, T, H d): a head's rows a lane tile
        return pl.BlockSpec((1, chunk, hb * width), lambda r, j, c, live: (
            r, fetched(c, live), j))
    call = pl.pallas_call(
        functools.partial(_prefill_kernel, heads=hb,
                          group=math.gcd(hb, HEAD_GROUP), sub=sub),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct((rows, heads, dk, dv), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, heads // hb, n),
            in_specs=[heads_of(dk), heads_of(dk), flat(dk), flat(dv),
                      pl.BlockSpec((1, 1, chunk, hb),
                                   lambda r, j, c, live: (
                                       r, j, fetched(c, live), 0))],
            out_specs=(pl.BlockSpec((1, chunk, hb, dv),
                                    lambda r, j, c, live: (r, c, j, 0)),
                       pl.BlockSpec((1, hb, dk, dv),
                                    lambda r, j, c, live: (r, j, 0, 0))),
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        cost_estimate=pl.CostEstimate(  # a chunk and head: the products
            # below the diagonal blocks, against the state, and B W with
            # the hand-over; C x d_k exps a sub-chunk, a column of a
            # diagonal block, and two more
            flops=2 * rows * heads * n * chunk * (
                2 * (chunk - sub) * dk + 2 * dk * dv + (chunk + dk) * dv),
            transcendentals=rows * heads * n * chunk * dk * (
                chunk // sub + sub + 2),
            bytes_accessed=4 * rows * (total * heads * (3 * dk + 2 * dv + 1)
                                       + heads * dk * dv)),
        interpret=interpret,
        name="kda_prefill")
    # q and k come from a norm a head, g and v from products a row: each
    # is taken as its producer lays it out
    return call(jnp.reshape(live, (1,)).astype(jnp.int32), q, k,
                g.reshape(rows, total, heads * dk),
                v.reshape(rows, total, heads * dv), beta)
