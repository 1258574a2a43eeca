"""Operations and bytes the plain latent-attention decoder's algorithm needs,
from the configuration's sizes and what a window's requests were (real prompt
tokens, pairs routed to the experts held, causal keys, expert visits, the q
blocks the fused attention kernel ran): independent of how the program
computes them.

FLOPs (multiply-add = 2) count matrix products only: the latent projections,
W_o, feed-forward, router, shared expert, head, the routed pairs, attention
scores and values over the causal keys (at the unabsorbed sizes: d_n + d_r a
score, d_v a value). Norms, rotations, softmax, the group limit, the top-k
and the sort of pairs count as 0, so a share of the peak here is slightly
under, never over. A position is a token fed through the layers (prompt
tokens, and every new token but the last); the head runs once a new token.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math
from typing import Optional

from ..reference.mla_moe_decoder import param_spec, sizes
from .gqa_moe_decoder import expert_pairs  # noqa: F401  (config-free)

# the fused prefill attention kernel's key block (ops/pallas/attention.py
# `KEY_BLOCK`), cut to what divides the prompt's slots
KEY_BLOCK = 1024


def param_count(cfg: dict) -> int:
    return sum(math.prod(shape) for shape, _ in param_spec(cfg).values())


def pair_flops(cfg: dict) -> int:
    """One (token, held expert) pair through its SwiGLU."""
    z = sizes(cfg)
    return 2 * 3 * z.hidden * z.expert_width


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One expert's weights."""
    z = sizes(cfg)
    return 3 * z.hidden * z.expert_width * itemsize


def attention_flops(cfg: dict) -> int:
    """The projections of one position in one layer: W_dq, W_uq, W_dkv,
    W_ukv, W_o."""
    z = sizes(cfg)
    return 2 * (z.hidden * z.q_rank + z.q_rank * z.heads * (z.nope + z.rope)
                + z.hidden * (z.kv_rank + z.rope)
                + z.kv_rank * z.heads * (z.nope + z.v)
                + z.heads * z.v * z.hidden)


def position_flops(cfg: dict) -> int:
    """Matrix products a position needs in every layer, the routed experts'
    pairs, the attention over keys and the head left out (counted from the
    window)."""
    z = sizes(cfg)
    dense = 2 * 3 * z.hidden * z.dense_width
    sparse = 2 * z.hidden * z.experts + 2 * 3 * z.hidden * z.shared_width
    return (z.layers * attention_flops(cfg) + z.dense_layers * dense
            + (z.layers - z.dense_layers) * sparse)


def key_flops(cfg: dict) -> int:
    """Scores and values of one (query position, key) on one layer."""
    z = sizes(cfg)
    return 2 * z.heads * (z.nope + z.rope + z.v)


def window_flops(cfg: dict, counters: dict) -> float:
    """FLOPs of the requests a window answered, from its `gen.*` counters
    (`gen.keys_causal`: the keys a query may read, every layer summed)."""
    z = sizes(cfg)
    positions = (counters["gen.prompt_tokens"] + counters["gen.new_tokens"]
                 - counters["gen.requests"])
    return float(
        positions * position_flops(cfg)
        + expert_pairs(counters) * pair_flops(cfg)
        + counters["gen.new_tokens"] * 2 * z.hidden * z.vocab
        + counters["gen.keys_causal"] * key_flops(cfg))


def gmm_work(cfg: dict, counters: dict, itemsize: int = 2):
    """(FLOPs, least bytes) of the grouped matmuls of a window: each pair's
    row in and out of both products, and an expert's weights ONCE A VISIT
    (`gen.expert_visits`: for each prefill or step and expert layer, the
    experts that had at least one pair), which is what a grouped matmul that
    reads no unvisited expert and no expert twice would move."""
    z = sizes(cfg)
    pairs = expert_pairs(counters)
    rows = pairs * (2 * z.hidden + 3 * z.expert_width) * itemsize
    weights = counters["gen.expert_visits"] * expert_bytes(cfg, itemsize)
    return float(pairs * pair_flops(cfg)), float(rows + weights)


def expert_slots(cfg: dict, counters: dict) -> int:
    """Experts the window's prefills and steps could have visited: expert
    layers x experts held, a pass."""
    return counters["gen.expert_passes"] * sizes(cfg).held


def fused_attention_flops(cfg: dict, p_max: int, q_block: int,
                          counters: dict) -> Optional[float]:
    """FLOPs of the fused prefill attention kernel's visits that ran in a
    window: a q block of `q_block` rows visits its keys a key block at a
    time, the tile on the diagonal counted as the whole rectangle it
    computes (as the XLA path's blocks are). From `gen.q_blocks_fused` alone
    that is exact where a prompt's slots are ONE q block (every fused block
    is one visit of `p_max` keys); a cell of longer prompts needs the count a
    row, so None there."""
    if p_max > q_block or not counters.get("gen.q_blocks_fused"):
        return None
    keys = math.gcd(p_max, KEY_BLOCK)
    return float(counters["gen.q_blocks_fused"] * key_flops(cfg)
                 * p_max * keys)
