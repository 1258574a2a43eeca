"""Operations and bytes the linear-hybrid decoder's algorithm needs, from the
configuration's sizes and what a window's requests were (real prompt
tokens, pairs routed to the experts held, the latent layer's causal keys,
the linear layers' prefill chunks and decode state steps, expert visits):
independent of how the program computes them, but for the chunk's size,
which is the program's choice (`ops/linear_attention.py` CHUNK).

FLOPs (multiply-add = 2) count matrix products only: every layer's
projections (KDA: W_q, W_k, W_v, W_f, W_g, W_beta, W_o; MLA: W_q, W_dkv,
W_ukv, W_o, the gate), feed-forward, router, shared expert, head, the routed
pairs, the latent layer's scores and values over the causal keys, and the
delta rule's products: in a chunk of C positions a head, the pairwise
decayed products A and B (2 C^2 d), the triangular solve (C^2 d), B W (C^2
d) and the three products with a d x d state (3 C d^2); a decode step, S^T
k, k w^T and S^T q (3 d^2 a head). The convolution, norms, decays, gates'
sigmoids, the top-k and the sort of pairs count as 0. The chunks are those
a row's own tokens fill (`gen.linear_chunks_live`), not those the program
ran (`gen.linear_chunks_run`): a pass of rows runs to its longest row, and
a chunk past a row's own length is work the row did not need (about a
fifth of the chunks at lengths uniform in 256-512).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math

from ..reference.hybrid_moe_decoder import FULL, param_spec, sizes
from .gqa_moe_decoder import expert_pairs  # noqa: F401  (config-free)

CHUNK = 32  # positions a chunk of the program's linear prefill


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


def latent_flops(cfg: dict) -> int:
    """The MLA layer's projections of one position."""
    z = sizes(cfg)
    return 2 * (z.hidden * z.heads * (z.nope + z.rope)
                + z.hidden * (z.kv_rank + z.rope)
                + z.kv_rank * z.heads * (z.nope + z.v)
                + z.heads * z.v * z.hidden + z.hidden * z.heads)


def linear_flops(cfg: dict) -> int:
    """A KDA layer's projections of one position."""
    z = sizes(cfg)
    width = z.lin_heads * z.head_dim
    return 2 * (6 * z.hidden * width + z.hidden * z.lin_heads)


def position_flops(cfg: dict) -> int:
    """Matrix products a position needs in every layer, the routed pairs,
    the keys, the delta rule's chunks and steps and the head left out."""
    z = sizes(cfg)
    attn = sum(latent_flops(cfg) if kind == FULL else linear_flops(cfg)
               for kind in z.kinds)
    dense = 2 * 3 * z.hidden * z.dense_width
    sparse = 2 * z.hidden * z.experts + 2 * 3 * z.hidden * z.shared_width
    return (attn + z.dense_layers * dense
            + (z.layers - z.dense_layers) * sparse)


def key_flops(cfg: dict) -> int:
    """Scores and values of one (query position, key) on the MLA layer."""
    z = sizes(cfg)
    return 2 * z.heads * (z.nope + z.rope + z.v)


def chunk_flops(cfg: dict, chunk: int = CHUNK) -> int:
    """One chunk of the delta rule's prefill in one layer, every head."""
    z = sizes(cfg)
    d = z.head_dim
    return 2 * z.lin_heads * (4 * chunk * chunk * d + 3 * chunk * d * d)


def step_flops(cfg: dict) -> int:
    """One decode step of one row through one layer's state, every head."""
    z = sizes(cfg)
    return 2 * z.lin_heads * 3 * z.head_dim ** 2


def kda_step_bytes(cfg: dict) -> int:
    """The least bytes of one row's step through one layer's state (a
    `kda_step` visit, every head): the float32 state read and written, q, k,
    g and v in, beta in, o out."""
    z = sizes(cfg)
    d = z.head_dim
    return z.lin_heads * 4 * (2 * d * d + 5 * d + 1)


def window_flops(cfg: dict, counters: dict) -> float:
    """FLOPs of the requests a window answered, from its `gen.*` counters."""
    z = sizes(cfg)
    positions = (counters["gen.prompt_tokens"] + counters["gen.new_tokens"]
                 - counters["gen.requests"])
    return float(
        positions * position_flops(cfg)
        + expert_pairs(counters) * pair_flops(cfg)
        + counters["gen.new_tokens"] * 2 * z.hidden * z.vocab
        + counters["gen.keys_causal"] * key_flops(cfg)
        + counters["gen.linear_chunks_live"] * chunk_flops(cfg)
        + counters["gen.linear_state_steps"] * step_flops(cfg))


def gmm_work(cfg: dict, counters: dict, itemsize: int = 2):
    """(FLOPs, least bytes) of the grouped matmuls of a window: each pair's
    row in and out of both products, and an expert's weights once a visit
    (`gen.expert_visits`)."""
    z = sizes(cfg)
    pairs = expert_pairs(counters)
    rows = pairs * (2 * z.hidden + 3 * z.expert_width) * itemsize
    weights = counters["gen.expert_visits"] * expert_bytes(cfg, itemsize)
    return float(pairs * pair_flops(cfg)), float(rows + weights)
