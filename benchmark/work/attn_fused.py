"""Operations of the fused prefill attention kernel (`attn_fused`,
ops/pallas/attention.py) in the latent families: FLOPs of one visit, a (q
block, key block) pair that did work, from the configuration's sizes. How many
visits a window's rows ran is the program's count (`gen.attn_fused_visits`:
the kernel's own visit table at each row's live q blocks), so a share of the
peak from it needs no assumption about the prompts' lengths.

A visit multiplies a q block of `q_block` rows by a key block of its keys for
scores (`nope + rope` deep) and values (`v` deep) in every head: 2 x heads x
(nope + rope + v) x q_block x key_block (multiply-add = 2). The tile on the
diagonal is counted as the whole rectangle it computes, as the XLA path's
blocks are; softmax and scaling count as 0. So the share is slightly under,
never over, what the kernel's products did.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math

from .mla_moe_decoder import KEY_BLOCK


def key_block(slots: int) -> int:
    """The kernel's key block, cut to what divides the prompt's slots
    (ops/pallas/attention.py `key_block`)."""
    return math.gcd(int(slots), KEY_BLOCK)


def visit_flops(cfg: dict, slots: int, q_block: int) -> int:
    """One visit of the full (window-less) layers' attention, the layers the
    kernel runs: `num_attention_heads` heads of `qk_nope_head_dim` +
    `qk_rope_head_dim` score dims and `v_head_dim` value dims."""
    heads = int(cfg["num_attention_heads"])
    width = (int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
             + int(cfg["v_head_dim"]))
    return 2 * heads * width * int(q_block) * key_block(slots)
