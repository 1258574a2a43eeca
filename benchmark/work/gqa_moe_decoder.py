"""Operations and bytes the grouped-query decoder family's algorithm needs, from
the configuration's sizes and what a window's requests were (real prompt
tokens, pairs routed to the experts held, keys the full layers' queries read,
expert visits): independent of how the program computes them.

FLOPs (multiply-add = 2) count matrix products only: projections, gate,
feed-forward, router, shared expert, head, the routed pairs, attention scores
and values over the keys READ (causal on a full layer, the window's on a
sliding one). Norms, rotations, softmax, the top-k and the sort of pairs
count as 0, so a share of the peak here is slightly under, never over. A
position is a token fed through the layers (prompt tokens, and every new
token but the last); the head runs once a new token.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math

from ..reference.gqa_moe_decoder import FULL, param_spec, sizes


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


def position_flops(cfg: dict) -> int:
    """Matrix products a position needs in every layer, the routed experts'
    pairs, the attention over keys and the head left out (counted from the
    window)."""
    z = sizes(cfg)
    kv = z.groups * z.dim
    total = 0
    for i in range(z.layers):
        q = z.heads[i] * z.dim
        total += 2 * z.hidden * (2 * q + 2 * kv + z.heads[i])
        if z.dense[i]:
            total += 2 * 3 * z.hidden * z.dense_width
        else:
            total += (2 * z.hidden * z.experts
                      + 2 * 3 * z.hidden * z.shared_width)
    return total


def expert_pairs(counters: dict) -> int:
    """Pairs routed to the held experts, all of them summed."""
    return sum(v for k, v in counters.items()
               if k.startswith("gen.expert_pairs."))


def _key_flops(z, full: bool) -> float:
    """Scores and values of one (query position, key) on one layer of the
    kind: 4 x heads x head dim (the kind's layers' mean head count)."""
    heads = [h for h, k in zip(z.heads, z.kinds) if (k == FULL) == full]
    return 4.0 * z.dim * sum(heads) / max(1, len(heads))


def window_flops(cfg: dict, counters: dict) -> float:
    """FLOPs of the requests a window answered, from its `gen.*` counters."""
    z = sizes(cfg)
    requests = counters["gen.requests"]
    positions = (counters["gen.prompt_tokens"] + counters["gen.new_tokens"]
                 - requests)
    sliding = sum(kind != FULL for kind in z.kinds)
    # keys a sliding layer reads: min(t + 1, window) a position (every
    # request here is at least the window long)
    window_keys = positions * z.window - requests * (
        z.window * (z.window - 1) // 2)
    return float(
        positions * position_flops(cfg)
        + expert_pairs(counters) * pair_flops(cfg)
        + counters["gen.new_tokens"] * 2 * z.hidden * z.vocab
        + counters["gen.keys_causal"] * _key_flops(z, True)
        + sliding * window_keys * _key_flops(z, False))


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

