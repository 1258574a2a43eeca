"""Attention for the decoder families: norms and rotary positions (plain theta,
or a table of inverse frequencies with a scale: YaRN), the exact top-k of the
learned indexer, blockwise causal attention over a window or a chosen key set
(prefill; one k/v a head, or one k/v a GROUP of query heads, read once a
group), and one query a row against a latent cache or a k/v cache (decode).

The reference has no attention of any kind (ref hourglass.py is
convolutions only); this module is new capability. Plain XLA, but for
prefill's per-head calls without a window, which are one fused kernel where
Mosaic compiles (`runs_fused`, `ops/pallas/attention.py`): there the key
range grows with the prompt and XLA's float32 scores go through HBM three
times a q block. The selection is a mask over dense causal blocks (skipping
unchosen key blocks is a later step).
Prefill walks a sequence a q block at a time and is told the sequence's real
`length`: a q block that starts at or past it is a branch not taken on the
device (`lax.cond`), so padding at the end of a row costs no scores, no top-k
and no values. A caller without lengths passes the padded length and every
branch is taken.

Conventions shared with benchmark/reference/latent_moe_decoder.py (which
states the equations): float32 inside norms, softmax and the indexer's score
sum, the operands' dtype (bfloat16) into every matrix product; the rotation
pairs dimension i with i + d/2.
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .moe import kernel_compiles
from .pallas import attention as fused

# masked scores: finite, so that a row with nothing allowed (a padded query)
# gives numbers, not NaN
NEG = fused.NEG


def rms_norm(x, w, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, w, b, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rotate_by(x, pos, freq, scale: float = 1.0):
    """Rotary positions from a table. x (..., d), `freq` (d/2,) float32 the
    inverse frequencies (dimension j pairs with j + d/2, angle pos * freq[j]),
    `scale` on cos and sin alike (YaRN's attention factor; 1 multiplies
    nothing). `pos` is shaped like x's leading axes up to where it stops:
    (T,) for x (T, H, d) or (T, d); (B,) for (B, H, d). Computed in float32,
    returned in x's dtype."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None] * freq
    ang = ang.reshape(pos.shape + (1,) * (x.ndim - pos.ndim - 1) + (half,))
    a, b = (x[..., :half].astype(jnp.float32),
            x[..., half:].astype(jnp.float32))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def rotate(x, pos, theta: float):
    """`rotate_by` at the plain frequencies theta^(-2j/d)."""
    half = x.shape[-1] // 2
    return rotate_by(x, pos,
                     theta ** (-jnp.arange(half, dtype=jnp.float32) / half))


def rotate_leading_by(x, pos, freq, scale: float, dims: int):
    """`rotate_by` over the first `dims` of the last axis (all of it: no
    cut), the rest as it is."""
    if dims == x.shape[-1]:
        return rotate_by(x, pos, freq, scale)
    return jnp.concatenate([rotate_by(x[..., :dims], pos, freq, scale),
                            x[..., dims:]], axis=-1)


def rotate_leading(x, pos, theta: float, dims: int):
    """`rotate` over the first `dims` of the last axis, the rest as it is."""
    half = dims // 2
    return rotate_leading_by(
        x, pos, theta ** (-jnp.arange(half, dtype=jnp.float32) / half), 1.0,
        dims)


# ---- the indexer's exact top-k -------------------------------------------------

def _ordered_bits(x):
    """float32 -> uint32 with the same order (-inf lowest)."""
    i = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def top_k_mask(scores, k: int):
    """bool, like `scores`: True where the entry is among the k largest of
    its row (last axis); entries equal to the k-th largest are all kept.
    Exact: the k-th largest is found bit by bit (32 counts over the row), no
    sort and no approximation, so the kept set is the reference's."""
    if k >= scores.shape[-1]:
        return jnp.ones(scores.shape, bool)
    u = _ordered_bits(scores)
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for bit in range(31, -1, -1):
        cand = kth | jnp.uint32(1 << bit)
        enough = jnp.sum(u >= cand, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        kth = jnp.where(enough, cand, kth)
    return u >= kth


def index_scores(qi, ki, w, head_block: Optional[int] = None):
    """I[t, s] = sum_h w[t, h] ReLU(qi[t, h] . ki[s]) / sqrt(heads * dim),
    float32. qi (..., T, H, d), ki (..., S, d), w (..., T, H) float32;
    heads taken `head_block` at a time so that the per-head scores
    (float32, H x T x S) never stand whole."""
    heads, dim = qi.shape[-2], qi.shape[-1]
    step = head_block or heads
    total = None
    for h0 in range(0, heads, step):
        s = jnp.einsum("...thd,...sd->...hts", qi[..., h0:h0 + step, :], ki,
                       preferred_element_type=jnp.float32)
        wh = jnp.swapaxes(w[..., h0:h0 + step], -1, -2)[..., None]
        part = jnp.sum(wh * jnp.maximum(s, 0.0), axis=-3)
        total = part if total is None else total + part
    return total / math.sqrt(heads * dim)


def q_blocks_live(total: int, q_block: int, length) -> list:
    """One predicate a q block [r0, r0 + q_block) of `total` rows: does the
    block hold a real row, r0 < `length` (int32 scalar, >= 1)? These are the
    predicates `select_blocks` and `blockwise_attention` branch on; block 0
    always does (True, no branch)."""
    return [True if r0 == 0 else r0 < length
            for r0 in range(0, total, q_block)]


def _when(live, body, shape, dtype):
    """`body()` where the scalar `live` holds, else zeros (False for bool) of
    its shape, as a branch on the device: under `lax.map` over rows `live` is
    a scalar, so the body not taken is not computed. A Python True (block 0)
    is no branch at all."""
    if live is True:
        return body()
    return lax.cond(live, body, lambda: jnp.zeros(shape, dtype))


def _causal(r0: int, r1: int):
    """bool (r1 - r0, r1): key s <= query t, for the queries [r0, r1)."""
    t = r0 + lax.broadcasted_iota(jnp.int32, (r1 - r0, r1), 0)
    return lax.broadcasted_iota(jnp.int32, (r1 - r0, r1), 1) <= t


def select_blocks(qi, ki, w, topk: int, q_block: int,
                  head_block: Optional[int] = None, *,
                  length) -> List[jax.Array]:
    """The chosen keys of one sequence, a q block at a time: a list of bool
    (q_block, r1), r1 the block's end (keys after it are not causal), True
    where s <= t and I[t, s] is among the `topk` largest of row t. `length`
    (int32 scalar, an operand) is the sequence's real rows: a block that
    starts at or past it is not scored and chooses nothing (all False). A
    block wholly inside the first `topk` keys is the causal mask, a constant,
    whatever the length."""
    total = qi.shape[0]
    out = []
    for r0, live in zip(range(0, total, q_block),
                        q_blocks_live(total, q_block, length)):
        r1 = min(total, r0 + q_block)
        if r1 <= topk:
            out.append(_causal(r0, r1))
            continue

        # the block's queries are cut out here, behind a barrier that keeps
        # the compiler from moving the cut back into the branch: a branch
        # cannot ask its operand's producer for the layout its product wants,
        # so a branch handed the whole of `qi` copies the whole of it (134 MB
        # at 8,192 x 64 x 128, 0.4 ms a block: PERF.md section 6, PR 32)
        rows = lax.optimization_barrier(qi[r0:r1])

        def chosen(r0=r0, r1=r1, rows=rows):
            causal = _causal(r0, r1)
            scores = index_scores(rows, ki[:r1], w[r0:r1], head_block)
            return top_k_mask(jnp.where(causal, scores, -jnp.inf),
                              topk) & causal
        out.append(_when(live, chosen, (r1 - r0, r1), bool))
    return out


def _masked_exp(scores, allowed):
    """float32 scores -> (exp(scores - row max), 0 where not allowed, and its
    row sum): the softmax's two parts, divided after the value product. A row
    with nothing allowed weighs every key alike: numbers, not NaN."""
    s = jnp.where(allowed, scores, NEG)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return p, jnp.sum(p, axis=-1, keepdims=True)


# ---- prefill: blockwise causal attention ------------------------------------------

def chosen_mask(chosen: List[jax.Array], total: int):
    """The list `select_blocks` gives as ONE int8 (T, T) array (keys past a
    block's end are 0): what the fused kernel reads a tile at a time."""
    return jnp.concatenate([jnp.pad(c, ((0, 0), (0, total - c.shape[1])))
                            for c in chosen]).astype(jnp.int8)


def runs_fused(q_ndim: int, total: int, q_block: int,
               window: Optional[int]) -> bool:
    """Does `blockwise_attention` hand this call to the fused kernel
    (`ops/pallas/attention.py`)? The per-head form without a window, in whole
    q blocks, where Mosaic compiles: no window means the key range grows with
    the prompt and the float32 scores of the XLA path leave fast memory (12 B
    a score through HBM: PERF.md section 6, PR 34); under a window (<= 1,024
    keys a q block) they stay there, and the grouped form has met no long
    prompt yet."""
    return (q_ndim == 3 and window is None and total % q_block == 0
            and kernel_compiles())


def fused_visits(total: int, q_block: int, ran):
    """The (q block, key block) visits the fused kernel does work in for a
    sequence of `total` rows whose first `ran` (int32 scalar) q blocks are
    live: its own visit table (`visits_run`), at the key block it takes."""
    table = fused.visits_run(total, q_block, fused.key_block(total))
    return jnp.asarray(table)[ran]


def blockwise_attention(q, k, v, *, q_block: int, scale: float, length,
                        window: Optional[int] = None,
                        chosen: Optional[List[jax.Array]] = None,
                        head_block: Optional[int] = None,
                        shared: Optional[tuple] = None):
    """Causal attention of one sequence. q (H, T, dq), k (H, T, dq), v (H, T,
    dv) -> (H, T, dv); or the grouped form, q (G, R, T, d) against k, v (G, T,
    d) -> (G, R, T, d): the R query heads of a group read the group's one k/v
    head, which is read once a group and never copied a head. `scale`
    multiplies the scores (in float32). A q block
    [r0, r1) reads the keys [lo, r1): lo = r0 - (window - 1) under a window,
    else 0; inside, a key is allowed where s <= t, t - s < window and, with
    `chosen` (the list `select_blocks` gives, same q_block), where it was
    chosen. Softmax in float32 over the block's whole key range (no running
    maximum needed). `length` (int32 scalar, an operand) is the sequence's
    real rows: a block that starts at or past it is not computed and its rows
    are zeros; no real query reads a padded key (s <= t < length), so rows
    below `length` are what the full length gives. The leading axis (heads,
    or k/v groups) `head_block` at a time under `lax.map`, so that the scores
    (float32, heads x q_block x keys) stay small. `shared` (per-head form
    only): a further part of the scores that every head reads from ONE key
    array, (q_s (H, T, ds), k_s (T, ds)): the scores are q . k + q_s . k_s.
    A call that `runs_fused` is one fused kernel instead (same mathematics,
    a running maximum, the scores never in HBM; `head_block` is then the
    kernel's own)."""
    heads, total = q.shape[0], q.shape[-2]
    if runs_fused(q.ndim, total, q_block, window):
        mask = None if chosen is None else chosen_mask(chosen, total)
        return fused.attn_fused(q, k, v, length, mask, *(shared or ()),
                                q_block=q_block, scale=scale)
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[1], k.shape[:-1] + shared[1].shape[-1:])], axis=-1)
    live = q_blocks_live(total, q_block, length)
    qk, pv = (("grqd,gkd->grqk", "grqk,gkd->grqd") if q.ndim == 4 else
              ("hqd,hkd->hqk", "hqk,hkd->hqd"))

    def group(qkv):
        qg, kg, vg = qkv
        outs = []
        for i, r0 in enumerate(range(0, total, q_block)):
            r1 = min(total, r0 + q_block)
            lo = 0 if window is None else max(0, r0 - (window - 1))

            def block(i=i, r0=r0, r1=r1, lo=lo):
                t = r0 + lax.broadcasted_iota(jnp.int32,
                                              (r1 - r0, r1 - lo), 0)
                s = lo + lax.broadcasted_iota(jnp.int32,
                                              (r1 - r0, r1 - lo), 1)
                allowed = s <= t
                if window is not None:
                    allowed &= (t - s) < window
                if chosen is not None:
                    allowed &= chosen[i][:, lo:]
                sc = jnp.einsum(qk, qg[..., r0:r1, :], kg[:, lo:r1],
                                preferred_element_type=jnp.float32)
                p, denom = _masked_exp(sc * scale, allowed)
                o = jnp.einsum(pv, p.astype(vg.dtype), vg[:, lo:r1],
                               preferred_element_type=jnp.float32)
                return (o / denom).astype(vg.dtype)
            outs.append(_when(live[i], block,
                              qg.shape[:-2] + (r1 - r0, vg.shape[-1]),
                              vg.dtype))
        return jnp.concatenate(outs, axis=-2)

    if not head_block or head_block >= heads:
        return group((q, k, v))
    split = lambda x: x.reshape((heads // head_block, head_block)  # noqa: E731
                                + x.shape[1:])
    out = lax.map(group, (split(q), split(k), split(v)))
    return out.reshape((heads,) + out.shape[2:])


# ---- decode: one query a row against a cache ------------------------------------

def latent_cache_attention(q_lat, q_rope, c_kv, k_r, allowed, scale: float):
    """q_lat (B, H, r): the nope part of the query with W_uk absorbed; q_rope
    (B, H, dr); the cache c_kv (B, S, r), k_r (B, S, dr); allowed (B, S)
    bool. Returns the attention-weighted latents (B, H, r), to be taken
    through W_uv by the caller."""
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q_rope, k_r,
                      preferred_element_type=jnp.float32)) * scale
    p, denom = _masked_exp(s, allowed[:, None, :])
    o = jnp.einsum("bhs,bsr->bhr", p.astype(c_kv.dtype), c_kv,
                   preferred_element_type=jnp.float32)
    return (o / denom).astype(c_kv.dtype)


def grouped_cache_attention(q, k, v, allowed, scale: float):
    """One query a row against a k/v cache, a row being one k/v head of one
    sequence: q (N, R, d), the R query heads that read the head; the cache
    k, v (N, S, d); allowed (N, S) bool. Returns (N, R, d): every query head
    of a group reads the group's one k/v head in place (no copy a head).
    Scores and softmax in float32."""
    s = jnp.einsum("nrd,nsd->nrs", q, k,
                   preferred_element_type=jnp.float32) * scale
    p, denom = _masked_exp(s, allowed[:, None, :])
    o = jnp.einsum("nrs,nsd->nrd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (o / denom).astype(v.dtype)


def ring_positions(pos, width: int):
    """The position each slot of a ring of `width` holds once position `pos`
    (B,) has been written at slot pos % width: (B, width) int32, negative
    where the slot has not been written yet."""
    slot = jnp.arange(width, dtype=jnp.int32)[None, :]
    return pos[:, None] - jnp.mod(pos[:, None] - slot, width)
