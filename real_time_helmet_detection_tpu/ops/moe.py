"""The expert layer of the decoder family: sigmoid router with a selection
bias (or none), the choice (over all the experts, or limited to the best few
of the router's groups), the (token, choice) pairs sorted by expert, the
pairs whose expert is not held here dropped, a grouped matmul over the
experts held, the weighted way back to the tokens.

The reference has no experts (ref hourglass.py is convolutions only); this
module is new capability. No token is dropped for capacity: the sorted pairs
are taken `capacity` rows at a time until all held pairs are done. A share
of an expert-parallel layer takes one pass in all but pathological routings
(the capacity is twice the rows an even routing gives it); a layer that
holds every expert (`ep_size` 1) holds every pair, and its passes are
`MAX_PASS_ROWS` at most so that what a pass gathers stays bounded and the
number of passes does not follow the batch's lengths (PERF.md section 6,
PR 33).

The grouped matmul is `ops/pallas/expert_gmm.py` on the TPU (PERF.md section
6, PR 29, has the chip readings behind the choice); elsewhere Mosaic cannot
compile, so `lax.ragged_dot` stands in (`kernel_compiles`, the one place
that decides). `interpret=True` is for tests: the kernel under the Pallas
interpreter on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.experts import ExpertShare
from .pallas import expert_gmm as gmm
from .pallas import select


def kernel_compiles() -> bool:
    return select.on_chip()


def kept_groups(choice, n_group: int, topk_group: int):
    """choice (T, experts) float32, the scores the choice is made on ->
    bool (T, n_group): the `topk_group` groups (consecutive experts,
    experts / n_group each) whose two best scores sum highest, ties to the
    lower group."""
    tokens, experts = choice.shape
    best_two, _ = lax.top_k(choice.reshape(tokens, n_group,
                                           experts // n_group), 2)
    _, kept = lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    return jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], kept].set(True)


def route_groups(hn, w_router, b_select, per_token: int, norm_weights: bool,
                 routed_scale: float, n_group: int = 0, topk_group: int = 0):
    """hn (T, hidden) -> (chosen expert ids (T, per_token) int32, their
    weights (T, per_token) float32, the groups kept (T, n_group) bool or
    None). Scores in float32; the bias (None: a choice on the scores
    themselves) enters the choice only. With `n_group` the choice is limited
    to the experts of the `topk_group` groups `kept_groups` keeps."""
    scores = jax.nn.sigmoid(jnp.dot(hn, w_router,
                                    preferred_element_type=jnp.float32))
    choice = scores if b_select is None else (
        scores + b_select.astype(jnp.float32))
    kept = None
    if n_group:
        with jax.named_scope("group_limit"):
            kept = kept_groups(choice, n_group, topk_group)
            choice = jnp.where(
                jnp.repeat(kept, scores.shape[-1] // n_group, axis=-1),
                choice, -jnp.inf)
    _, idx = lax.top_k(choice, per_token)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_weights:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weights * routed_scale, kept


def route(hn, w_router, b_select, per_token: int, norm_weights: bool,
          routed_scale: float, n_group: int = 0, topk_group: int = 0):
    """`route_groups` without the groups kept: (ids, weights)."""
    return route_groups(hn, w_router, b_select, per_token, norm_weights,
                        routed_scale, n_group, topk_group)[:2]


def swiglu(x, w_gate_up, w_down):
    gate, up = jnp.split(jnp.dot(x, w_gate_up), 2, axis=-1)
    return jnp.dot(jax.nn.silu(gate) * up, w_down)


def grouped_swiglu(rows, w_gate_up, w_down, group_sizes,
                   interpret: bool = False):
    """rows (m, hidden) sorted by group -> (m, hidden): SwiGLU of each row by
    its group's expert. Rows past the groups' total are undefined."""
    if interpret or kernel_compiles():
        tm = gmm.row_tile(rows.shape[0])
        meta = gmm.group_metadata(group_sizes, rows.shape[0], tm)

        def mm(a, b):
            return gmm.expert_gmm(a, b, meta, interpret=interpret)
    else:
        def mm(a, b):
            return lax.ragged_dot(a, b, group_sizes.astype(jnp.int32))
    gate, up = jnp.split(mm(rows, w_gate_up), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w_down)


# the most rows one pass gathers. A 32 x 1,024-slot prefill of a layer that
# holds all its experts sorts up to 262,144 pairs, of which the real ones
# (131,072-262,144 for prompts of 512-1,024 tokens: padding sorts last and is
# not run) take TWO passes whatever the batch's lengths; at 65,536 the same
# batches took 3 or 4 by their lengths and a batch's time followed (2%:
# PERF.md section 6, PR 33). A pass of a 2,048-wide layer then stands as 0.54
# GB of rows in, 0.40 GB between the products and 0.54 GB out, where all the
# pairs at once would stand as twice that. An eighth's share of a 5,120-wide
# layer keeps its own 65,536 (twice its even load)
MAX_PASS_ROWS = 131072


def capacity_rows(pairs: int, share: ExpertShare) -> int:
    """Rows a pass takes: twice what an even routing sends this share, in
    whole row tiles, never more than all the pairs nor than
    `MAX_PASS_ROWS`."""
    tile = gmm.TILING[0]
    whole = -(-pairs // 16) * 16  # bfloat16 rows come 16 to a tile
    if whole <= tile:
        return whole
    even = -(-2 * pairs * share.held // share.n_routed // tile) * tile
    return min(-(-whole // tile) * tile, max(tile, even), MAX_PASS_ROWS)


def routed_experts(hn, idx, weights, token_real, w_gate_up, w_down,
                   share: ExpertShare, interpret: bool = False):
    """The held experts' part of the layer. hn (T, hidden); idx, weights
    (T, k) from `route`; token_real (T,) bool (a padded position takes no
    expert's time). Returns (y (T, hidden) in hn's dtype, local (T, k) int32:
    each pair's index among the experts held, `share.held` where the pair is
    not computed here)."""
    tokens, k = idx.shape
    pairs = tokens * k
    local = idx - share.first
    here = (local >= 0) & (local < share.held) & token_real[:, None]
    local = jnp.where(here, local, share.held)
    flat = local.reshape(pairs)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    offsets = jnp.searchsorted(flat[order], jnp.arange(
        share.held + 1, dtype=jnp.int32)).astype(jnp.int32)
    n_here = offsets[share.held]
    cap = capacity_rows(pairs, share)
    passes = -(-pairs // cap)
    # where each (token, choice) pair stands among the sorted rows
    place = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32)).reshape(tokens, k)
    order = jnp.pad(order, (0, passes * cap - pairs))

    def one_pass(c, out):
        start = c * cap
        rows = lax.dynamic_slice(order, (start,), (cap,))
        valid = start + jnp.arange(cap, dtype=jnp.int32) < n_here
        sizes = (jnp.clip(offsets[1:] - start, 0, cap)
                 - jnp.clip(offsets[:-1] - start, 0, cap))
        y = grouped_swiglu(hn[jnp.where(valid, rows // k, 0)], w_gate_up,
                           w_down, sizes, interpret)
        # back to the tokens by gathers, a choice at a time (a scatter-add
        # of these rows took five times the grouped matmuls: PERF.md
        # section 6, PR 29); rows past the groups are undefined, so a pair
        # is read only where it is held and in this pass
        at = place - start
        mine = here & (at >= 0) & (at < cap)
        for j in range(k):
            got = y[jnp.clip(at[:, j], 0, cap - 1)].astype(jnp.float32)
            out = out + jnp.where(mine[:, j, None],
                                  got * weights[:, j, None], 0.0)
        return out

    out = jnp.zeros(hn.shape, jnp.float32)
    if passes == 1:
        out = one_pass(0, out)
    else:
        out = lax.fori_loop(0, -(-n_here // cap), one_pass, out)
    return out.astype(hn.dtype), local
