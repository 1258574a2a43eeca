"""Operations and bytes the decoder family's algorithm needs, from the
configuration's sizes and what a window's requests were (real prompt tokens,
kept keys, pairs routed to the experts held): independent of how the program
computes them (absorbed or not, masked dense blocks or gathered keys).

FLOPs (multiply-add = 2) count matrix products only: projections, feed-forward,
router, head, attention scores and values over the keys KEPT, the indexer's
scores over the keys causal. Norms, rotations, softmax, the top-k and the
sort of pairs count as 0, so a share of the peak here is slightly under, never
over. A position is a token fed through the layers (prompt tokens, and every
new token but the last); the head runs once a new token.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

from ..reference.latent_moe_decoder import FULL, moe_layers, sizes


def _attn_params(a, hidden: int) -> int:
    return (hidden * a.q_rank + a.q_rank * a.heads * (a.nope + a.rope)
            + hidden * (a.kv_rank + a.rope)
            + a.kv_rank * a.heads * (a.nope + a.v)
            + hidden * a.heads + a.heads * a.v * hidden)


def pair_flops(cfg: dict) -> int:
    """One (token, held expert) pair through its SwiGLU."""
    z = sizes(cfg)
    return 2 * 3 * z.hidden * z.expert_width


def position_flops(cfg: dict) -> int:
    """Matrix products a position needs in every layer, the routed experts'
    pairs and the attention over keys left out (counted from the window)."""
    z = sizes(cfg)
    total = 0
    for i, kind in enumerate(z.kinds):
        total += 2 * _attn_params(z.full if kind == FULL else z.swa, z.hidden)
        if kind == FULL:
            total += 2 * (z.full.q_rank * z.index_heads * z.index_dim
                          + z.hidden * z.index_dim + z.hidden * z.index_heads)
        if i < z.dense_layers:
            total += 2 * 3 * z.hidden * z.dense_width
        else:
            total += (2 * z.hidden * z.experts
                      + 2 * 3 * z.hidden * z.expert_width * z.shared)
    return total


def expert_pairs(counters: dict) -> int:
    """Pairs routed to the held experts, all of them summed."""
    return sum(v for k, v in counters.items()
               if k.startswith("gen.expert_pairs."))


def window_flops(cfg: dict, counters: dict) -> float:
    """FLOPs of the requests a window answered, from its `gen.*` counters."""
    z = sizes(cfg)
    requests = counters["gen.requests"]
    positions = (counters["gen.prompt_tokens"] + counters["gen.new_tokens"]
                 - requests)
    pairs = expert_pairs(counters)
    full = sum(kind == FULL for kind in z.kinds)
    # keys a sliding layer reads: min(t + 1, window) a position (every
    # request here is longer than the window)
    window_keys = positions * z.window - requests * (
        z.window * (z.window - 1) // 2)
    per_key = lambda a: 2 * a.heads * (a.nope + a.rope + a.v)  # noqa: E731
    return float(
        positions * position_flops(cfg) + pairs * pair_flops(cfg)
        + counters["gen.new_tokens"] * 2 * z.hidden * z.vocab
        + counters["gen.keys_kept"] * per_key(z.full)
        + counters["gen.keys_causal"] * 2 * z.index_heads * z.index_dim
        + (z.layers - full) * window_keys * per_key(z.swa))


def gmm_work(cfg: dict, counters: dict, new_tokens: int, itemsize: int = 2):
    """(FLOPs, least bytes) of the grouped matmuls of a window: each pair's
    row in and out of both products, each held expert's weights once a
    prefill (every expert has rows there) and ONE expert's once a decode
    step and layer (the least a step that has a pair can read: a lower
    bound, so the share is under, never over)."""
    z = sizes(cfg)
    pairs = expert_pairs(counters)
    batches = counters["batches_total"]
    expert = 3 * z.hidden * z.expert_width * itemsize
    rows = pairs * (2 * z.hidden + 3 * z.expert_width) * itemsize
    weights = batches * len(moe_layers(z)) * expert * (
        z.held + max(0, new_tokens - 1))
    return float(pairs * pair_flops(cfg)), float(rows + weights)
