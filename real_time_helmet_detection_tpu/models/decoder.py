"""The decoder families: ONE body (embedding, layer loop, leading dense
SwiGLU layers, sigmoid-routed experts with a shared expert as one share of an
expert-parallel deployment or held whole, counts, head; `prefill` and `step`)
in which each layer's KIND selects its attention within the family
(`ATTENTIONS[family][kind]`, `attention_of`), so that one model may mix two:

`latent_moe_decoder`  latent attention; decode with W_uk / W_uv absorbed into
    the query and the output. What the source's mapping holds decides the
    rest: `layer_types` with sliding layers (a second size set `swa_*` behind
    `sliding_window_size`; absent: every layer full), `index_topk` (a learned
    sparse indexer on full layers; absent: every causal key), an
    `attention_gate_type` (a head-wise output gate; absent: W_o alone),
    `apply_mla_qkv_lora_rescale` (the latents times sqrt(hidden / rank)),
    `rope_scaling` of type yarn (a frequency table, the whole score times
    m^2), `n_group` / `topk_group` (the router's choice limited by groups),
    `topk_method` (`noaux_tc`: a selection bias; else none).
`gqa_moe_decoder`     grouped-query attention: 8 k/v heads shared by a
    number of query heads that differs BY LAYER, rotary positions from a table
    (YaRN on half the head on full layers, plain theta on the whole head on
    sliding ones), the same head-wise gate.
`hybrid_moe_decoder`  `layer_group_size` n: layer i is latent attention
    (`full_attention`) where (i + 1) % n == 0, KDA linear attention
    (`linear_attention`) otherwise. The latent kind reads `q_lora_rank` null
    as one W_q (no query latent) and `gated_attention_proj_granularity_type`
    as the head-wise gate; the linear kind is the gated delta rule with a
    decay a channel on a float32 state a head (`ops/linear_attention.py`,
    the `kda_step` kernel in decode). Router: selection bias and group limit.
    A setting whose other value this program does not compute (a SwiGLU
    clamp in a held layer, a low-rank W_f, ...) is refused by its key.

An attention is three things: the flax module that declares its parameters,
`prefill_row(p, kind, spec, x, length, slots, faults) -> (out, cache entry,
counts)` and `step(p, kind, spec, x, pos, entry, faults) -> (out, entry,
counts)`; the linear kind's prefill takes a pass of rows together
(`Attention.rows`: 16 where the batch allows). The passes are a stopgap: a
pass runs to its longest row, which costs the benchmark's hybrid cell about
a tenth of its rate, and holds a batch's time to a level whatever its rows'
lengths (ROADMAP R12 (i)).

The reference has no language model (ref hourglass.py is the only network);
this module is new capability. The equations are stated once a family, in
benchmark/reference/latent_moe_decoder.py,
benchmark/reference/gqa_moe_decoder.py and
benchmark/reference/hybrid_moe_decoder.py (the plain references the tests
and the benchmark hold this program to); this file is how the program computes
them: bfloat16 parameters and activations, float32 in norms, softmax, router
scores and the indexer's score sum; prefill a sequence at a time under
`lax.map` (so that one row's q, k, v and scores are what stands in memory),
blockwise over queries, the linear layers a pass of rows at a time; decode
one token a row against three kinds of cache.

The flax modules declare parameters and hold no arithmetic of their own: a
module reads its arrays, then calls the pure functions below (a flax module
cannot be entered under `lax.map`).

Cache, one entry a layer (`cache["layers"][i]`):
  full layer     at absolute positions (S = prompt slots + reserved): latent
                 c_kv (B, S, kv_rank), k_r (B, S, rope) and, behind an
                 indexer, k_i (B, S, index dim); grouped-query k, v (B, S,
                 kv heads, head dim), rotated before they are stored;
  sliding layer  a ring, position p at slot p % window: latent c_kv (B,
                 window, kv_rank), k_r (B, window, rope); grouped-query k, v
                 (B, window, kv heads, head dim);
  linear layer   a state that does not grow with positions: `state` (B,
                 heads, head dim, head dim) float32 after the row's last
                 token, `conv` (B, taps - 1, 3 heads x head dim): the row's
                 last real inputs to the short convolution. Prefill writes
                 both at each row's own length (padding writes nothing);
                 each step reads and rewrites them.
`cache["counts"]`, per row: pairs routed to each held expert by expert layer
(padding excluded); expert visits (for each prefill or step and expert layer,
the experts that had at least one pair, credited to the lowest row that
routed there, so that rows add up to the batch's count); under a router with
groups, the (position, expert layer) slots and those of them where a group
this share holds was among the groups kept; the q blocks of
prefill attention that ran and that the padded row holds, attention layers
summed; and what one attention counts (`ATTENTION_COUNTS`; zeros for the
other): keys the indexer kept and keys causal, full layers summed; cache
slots the decode steps read and real keys among them, by layer kind; the
linear layers' state read-modify-writes (steps x layers) and the chunks of
their prefill that ran for the row (its pass's: the pass runs to its longest
row), that the row's own tokens fill, and that the padded row holds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Mapping, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention as att
from ..ops import linear_attention as la
from ..ops import moe
from ..parallel.experts import ExpertShare, expert_share

FULL, SLIDING = "full_attention", "sliding_attention"
LINEAR = "linear_attention"
FAMILY = "latent_moe_decoder"
GQA_FAMILY = "gqa_moe_decoder"
HYBRID_FAMILY = "hybrid_moe_decoder"
# per-row counts an attention may give (int32 (B,)); the body adds the layers
# up and a count the layer's attention does not give stays zeros
ATTENTION_COUNTS = ("keys_kept", "keys_causal", "q_blocks_run",
                    "q_blocks_fused", "attn_fused_visits", "slots_full",
                    "keys_full", "slots_window", "keys_window",
                    "linear_state_steps", "linear_chunks_run",
                    "linear_chunks_live", "linear_chunks_total")


@dataclasses.dataclass(frozen=True)
class AttnSizes:
    """One layer kind of the latent family. `inv_freq` (rope/2,): the rotary
    table where the source scales its positions (None: plain `theta`),
    `rope_scale` on cos and sin; `scale` multiplies every score (nope and
    rope parts alike); `rescale`: the latents times sqrt(hidden / rank)
    after their norms; `gate`: the head-wise output gate."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    scale: float
    rescale: bool
    gate: bool
    inv_freq: Optional[Tuple[float, ...]] = None
    rope_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class LinearSizes:
    """The linear-attention kind (KDA): heads of `head_dim` keys and values,
    the short convolution's taps, the decay's lower bound (g in (lower,
    0))."""
    heads: int
    head_dim: int
    conv: int
    lower: float


@dataclasses.dataclass(frozen=True)
class GroupedSizes:
    """One layer kind of the grouped-query family: k/v heads and the head's
    size, and the rotary table over the head's leading `rot` dims (`inv_freq`
    (rot/2,) made from `theta`, `rope_scale` on cos and sin)."""
    kv_heads: int
    head_dim: int
    theta: float
    rot: int
    inv_freq: Tuple[float, ...]
    rope_scale: float


def rotary_table(params: Mapping, head_dim: int) -> Tuple[int, tuple, float]:
    """(rot, inverse frequencies (rot/2,), scale) of one entry of the
    source's `rope_parameters`: `default` is theta^(-2j/rot); `yarn` blends
    each frequency with itself / factor along a ramp between the dimensions
    that turn beta_fast and beta_slow times over the original length, and
    scales cos and sin by `attention_factor` (0.1 ln factor + 1 where the
    source leaves it out). Python floats: the table does not depend on the
    sequence's length."""
    rot = int(round(head_dim * float(params.get("partial_rotary_factor", 1))))
    theta = float(params["rope_theta"])
    kind = params.get("rope_type", "default")
    if kind == "default":
        return rot, tuple(theta ** (-2.0 * j / rot)
                          for j in range(rot // 2)), 1.0
    if kind != "yarn":
        raise ValueError("rope_type %r is not default | yarn" % (kind,))
    factor = float(params["factor"])
    scale = float(params.get("attention_factor")
                  or 0.1 * math.log(factor) + 1.0)
    return rot, yarn_frequencies(theta, rot, params), scale


def yarn_frequencies(theta: float, rot: int, params: Mapping) -> tuple:
    """theta^(-2j/rot), each blended with itself / `factor` along a ramp
    between the dimensions that turn `beta_fast` and `beta_slow` times over
    `original_max_position_embeddings`."""
    factor = float(params["factor"])
    span = float(params["original_max_position_embeddings"])

    def turns_at(n):  # the dimension that turns n times over `span`
        return rot * math.log(span / (2 * math.pi * n)) / (
            2 * math.log(theta))
    low = max(math.floor(turns_at(float(params["beta_fast"]))), 0)
    high = min(math.ceil(turns_at(float(params["beta_slow"]))), rot - 1)
    ramp = [min(1.0, max(0.0, (j - low) / max(high - low, 1e-3)))
            for j in range(rot // 2)]
    return tuple(theta ** (-2.0 * j / rot) * ((1 - r) + r / factor)
                 for j, r in enumerate(ramp))


def latent_rotary(scaling: Optional[Mapping], theta: float, rope: int):
    """(inv_freq or None, scale on cos and sin, multiplier of the score) of
    a latent attention's `rope_scaling` (the source's own form: `type`,
    `factor`, `mscale`, `mscale_all_dim`). None: plain theta, nothing
    scaled. yarn: the blended table; cos and sin times m(mscale) /
    m(mscale_all_dim), the WHOLE score times m(mscale_all_dim)^2, with
    m(x) = 0.1 x ln factor + 1."""
    if not scaling:
        return None, 1.0, 1.0
    if scaling.get("type") != "yarn":
        raise ValueError("rope_scaling type %r is not yarn"
                         % (scaling.get("type"),))

    def m(x):
        factor = float(scaling["factor"])
        return 0.1 * float(x) * math.log(factor) + 1.0 if factor > 1 else 1.0
    all_dim = scaling.get("mscale_all_dim", 0)
    return (yarn_frequencies(theta, rope, scaling),
            m(scaling.get("mscale", 1)) / m(all_dim),
            m(all_dim) ** 2 if all_dim else 1.0)


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """The sizes, from the source's own keys (`Config.decoder`: the model's
    `config.json` keys as they stand, plus `ep_size` / `ep_rank`, and the
    source's count of routed experts / `vocab_size` counting what is held
    HERE). `full` / `swa`: the attention's sizes by layer kind, an
    `AttnSizes` or a `GroupedSizes` by the family; `linear`: the
    linear-attention kind's `LinearSizes` (None where no layer has it)."""
    family: str
    hidden: int
    vocab: int
    kinds: Tuple[str, ...]
    heads: Tuple[int, ...]   # query heads, by layer
    dense_layers: int
    dense_width: int
    expert_width: int
    shared_width: int
    share: ExpertShare
    per_token: int
    norm_weights: bool
    routed_scale: float
    eps: float
    window: int
    full: object
    swa: object
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0    # 0: no indexer, a full layer reads every causal key
    n_group: int = 0       # 0: the router's choice knows no groups
    topk_group: int = 0
    select_bias: bool = True
    # how the program walks a prompt (not the model's): query rows a block,
    # heads a pass of the attention and of the indexer
    q_block: int = 512
    head_block: int = 32
    linear: object = None
    linear_chunk: int = la.CHUNK  # positions a chunk of the linear prefill

    @classmethod
    def from_mapping(cls, d: Mapping, family: str = FAMILY) -> "DecoderSpec":
        if family not in _SPEC_KEYS:
            raise ValueError("no decoder of family %r (have: %s)"
                             % (family, ", ".join(sorted(_SPEC_KEYS))))
        layers = int(d["num_hidden_layers"])
        kinds = _layer_kinds(d, layers)
        allowed = sorted(ATTENTIONS[family])
        if len(kinds) != layers or set(kinds) - set(allowed):
            raise ValueError("layer_types must name %d layers as %s, got %r"
                             % (layers, " or ".join(allowed), kinds))
        return cls(
            family=family, hidden=int(d["hidden_size"]),
            vocab=int(d["vocab_size"]), kinds=kinds,
            dense_width=int(d["intermediate_size"]),
            expert_width=int(d["moe_intermediate_size"]),
            per_token=int(d["num_experts_per_tok"]),
            eps=float(d["rms_norm_eps"]),
            q_block=int(d.get("attn_q_block", 512)),
            head_block=int(d.get("attn_head_block", 32)),
            linear_chunk=int(d.get("linear_chunk", la.CHUNK)),
            **_SPEC_KEYS[family](d, kinds))

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def full_layers(self) -> int:
        return sum(kind == FULL for kind in self.kinds)

    @property
    def linear_layers(self) -> int:
        return sum(kind == LINEAR for kind in self.kinds)

    def attn(self, kind: str):
        return {FULL: self.full, SLIDING: self.swa, LINEAR: self.linear}[kind]


def _layer_kinds(d: Mapping, layers: int) -> tuple:
    """The source's `layer_types`; else, where it states `layer_group_size`
    n, layer i is full attention when (i + 1) % n == 0 and linear attention
    otherwise (the linear-hybrid sources' rule); else one kind of layer."""
    if d.get("layer_types"):
        return tuple(d["layer_types"])[:layers]
    group = int(d.get("layer_group_size") or 0)
    if group:
        return tuple(FULL if (i + 1) % group == 0 else LINEAR
                     for i in range(layers))
    return (FULL,) * layers


def _share(d: Mapping, held_key: str, n_group: int = 0) -> ExpertShare:
    ep = int(d.get("ep_size", 1))
    return expert_share(ep, int(d.get("ep_rank", 0)), int(d[held_key]) * ep,
                        n_group)


def _latent_sizes(d: Mapping, prefix: str, gate: bool) -> AttnSizes:
    """A latent attention's sizes from the source's keys (`prefix`: swa_ for
    the sliding kind); a null `q_lora_rank` is one W_q, no query latent."""
    nope, rope = (int(d[prefix + "qk_nope_head_dim"]),
                  int(d[prefix + "qk_rope_head_dim"]))
    theta = float(d[prefix + "rope_theta"])
    freq, rope_scale, score = latent_rotary(
        d.get(prefix + "rope_scaling"), theta, rope)
    return AttnSizes(
        int(d[prefix + "num_attention_heads"]),
        int(d[prefix + "q_lora_rank"] or 0), int(d[prefix + "kv_lora_rank"]),
        nope, rope, int(d[prefix + "v_head_dim"]), theta,
        scale=score / math.sqrt(nope + rope),
        rescale=bool(d.get("apply_mla_qkv_lora_rescale")),
        gate=gate, inv_freq=freq, rope_scale=rope_scale)


def _groups(d: Mapping) -> Tuple[int, int]:
    n_group = int(d.get("n_group") or 0)
    topk_group = int(d.get("topk_group") or 0)
    if n_group and not 0 < topk_group <= n_group:
        raise ValueError("topk_group %d is not 1..n_group %d"
                         % (topk_group, n_group))
    return n_group, topk_group


def _latent_keys(d: Mapping, kinds) -> dict:
    """The latent family reads the source's keys as they stand: a key that
    is absent (or null) selects the absent part (module docstring)."""
    def sizes(prefix):
        gate = d.get(prefix + "attention_gate_type")
        if gate not in (None, "headwise"):
            raise ValueError("%sattention_gate_type %r is not headwise"
                             % (prefix, gate))
        return _latent_sizes(d, prefix, gate is not None)
    full = sizes("")
    swa = sizes("swa_") if SLIDING in kinds else None
    n_group, topk_group = _groups(d)
    index_topk = int(d.get("index_topk") or 0)
    return dict(
        heads=tuple((full if k == FULL else swa).heads for k in kinds),
        dense_layers=int(d["first_k_dense_replace"]),
        shared_width=int(d["moe_intermediate_size"])
        * int(d["n_shared_experts"]),
        share=_share(d, "n_routed_experts", n_group),
        norm_weights=bool(d["norm_topk_prob"]),
        routed_scale=float(d["routed_scaling_factor"]),
        window=int(d["sliding_window_size"]) if swa else 0,
        index_heads=int(d["index_n_heads"]) if index_topk else 0,
        index_dim=int(d["index_head_dim"]) if index_topk else 0,
        index_topk=index_topk, n_group=n_group, topk_group=topk_group,
        select_bias=d.get("topk_method") == "noaux_tc", full=full, swa=swa)


def _grouped_keys(d: Mapping, kinds) -> dict:
    groups, dim = int(d["num_key_value_heads"]), int(d["head_dim"])
    heads = tuple(int(h) for h in d["num_attention_heads_per_layer"])[
        :len(kinds)]
    if len(heads) != len(kinds) or any(h % groups for h in heads):
        raise ValueError("num_attention_heads_per_layer must give %d layers "
                         "a multiple of %d k/v heads, got %r"
                         % (len(kinds), groups, heads))
    mlp = tuple(d["mlp_layer_types"])[:len(kinds)]
    dense = sum(kind == "dense" for kind in mlp)
    if mlp != ("dense",) * dense + ("sparse",) * (len(kinds) - dense):
        raise ValueError("mlp_layer_types must name %d layers, the dense ones "
                         "first, got %r" % (len(kinds), mlp))
    if not d.get("gating"):
        raise ValueError("family %s has the head-wise output gate: `gating` "
                         "must be true" % GQA_FAMILY)
    rope = d["rope_parameters"]
    return dict(
        heads=heads, dense_layers=dense,
        shared_width=int(d["shared_expert_intermediate_size"]),
        share=_share(d, "num_experts"), norm_weights=True,
        routed_scale=float(d["moe_routed_scaling_factor"]),
        window=int(d["sliding_window"]),
        **{name: GroupedSizes(groups, dim, float(rope[kind]["rope_theta"]),
                              *rotary_table(rope[kind], dim))
           for name, kind in (("full", FULL), ("swa", SLIDING))})


# switches of a linear-hybrid source whose other values would change the
# function this program computes: a value not listed is refused by name
_HYBRID_SWITCHES = {
    "hidden_act": ("silu",), "scoring_func": ("sigmoid",),
    "kda_safe_gate": (True,), "linear_silu": (True,), "group_norm_size": (1,),
    "use_kda_lora": (False, None), "no_kda_lora": (True, None),
    "use_mla_nope": (False, None), "use_nGPT": (False, None),
    "value_norm": (False, None), "up_proj_norm": (False, None),
    "use_qkv_bias": (False, None), "use_bias": (False, None),
    "scale_router_input": (False, None),
    "gated_attention_proj_granularity_type": ("head_wise", None)}


def _hybrid_keys(d: Mapping, kinds) -> dict:
    """A linear-hybrid source (`layer_group_size`): KDA linear attention
    beside latent attention whose query has no latent (`q_lora_rank` null)
    and whose output the head-wise gate scales, sigmoid-routed experts with
    a selection bias and group-limited choice. A SwiGLU clamp in a layer
    held here is refused: its convention is not settled."""
    for key, allowed in _HYBRID_SWITCHES.items():
        if d.get(key) not in allowed:
            raise ValueError("%s %r is not %s" % (key, d.get(key), " or ".join(
                repr(v) for v in allowed)))
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        held = list(d.get(key) or ())[:len(kinds)]
        if any(held):
            raise ValueError("%s: a SwiGLU clamp in a held layer (%r) has no "
                             "settled convention" % (key, held))
    full = _latent_sizes(d, "", d.get("gated_attention_proj_granularity_type")
                         is not None)
    linear = LinearSizes(
        int(d.get("num_kv_heads_for_linear_attn") or d["num_attention_heads"]),
        int(d["head_dim"]), int(d["short_conv_kernel_size"]),
        float(d["kda_lower_bound"]))
    n_group, topk_group = _groups(d)
    return dict(
        heads=tuple(full.heads if k == FULL else linear.heads for k in kinds),
        dense_layers=int(d["first_k_dense_replace"]),
        shared_width=int(d["moe_shared_expert_intermediate_size"])
        * int(d["num_shared_experts"]),
        share=_share(d, "num_experts", n_group),
        norm_weights=bool(d["norm_topk_prob"]),
        routed_scale=float(d["routed_scaling_factor"]), window=0,
        n_group=n_group, topk_group=topk_group,
        select_bias=d.get("topk_method") == "noaux_tc",
        full=full, swa=None, linear=linear)


# what each family's source calls its sizes (ROADMAP D11/D13: the source's
# keys as they stand)
_SPEC_KEYS = {FAMILY: _latent_keys, GQA_FAMILY: _grouped_keys,
              HYBRID_FAMILY: _hybrid_keys}


_INIT = nn.initializers.normal(0.02)
_scale_init = nn.initializers.ones


# ---- the arithmetic (pure functions of the arrays) -------------------------------

def _latent_norm(c, w, rank: int, a: AttnSizes, spec: DecoderSpec, faults):
    """RMSNorm of a latent; where the source states the rank rescale, its
    scales times sqrt(hidden / rank)."""
    w = w.astype(jnp.float32)
    if a.rescale or "rank_rescale_kept" in faults:
        w = w * math.sqrt(spec.hidden / rank)
    return att.rms_norm(c, w, spec.eps)


def _rotary(a: AttnSizes, x, pos, faults=frozenset(), dims=None):
    """The kind's rotary positions over the first `dims` of x's last axis
    (all of it: None): plain theta, or the source's table."""
    dims = dims or x.shape[-1]
    if a.inv_freq is None or "yarn_dropped" in faults:
        return att.rotate_leading(x, pos, a.theta, dims)
    return att.rotate_leading_by(x, pos, jnp.asarray(a.inv_freq, jnp.float32),
                                 a.rope_scale, dims)


def _score_scale(a: AttnSizes, faults) -> float:
    if "score_scale_plain" in faults:
        return 1.0 / math.sqrt(a.nope + a.rope)
    return a.scale


def _queries(p, a: AttnSizes, spec: DecoderSpec, xn, faults=frozenset()):
    """xn (..., hidden) -> (c_q (..., q_rank) or None where the query has no
    latent, q (..., heads, nope + rope), the rope part not rotated yet)."""
    if not a.q_rank:
        q = jnp.dot(xn, p["w_q"])
        return None, q.reshape(q.shape[:-1] + (a.heads, a.nope + a.rope))
    c_q = _latent_norm(jnp.dot(xn, p["w_dq"]), p["q_norm"], a.q_rank, a,
                       spec, faults)
    q = jnp.dot(c_q, p["w_uq"])
    return c_q, q.reshape(q.shape[:-1] + (a.heads, a.nope + a.rope))


def _latents(p, a: AttnSizes, spec: DecoderSpec, xn, pos,
             faults=frozenset()):
    """xn (T, hidden) at positions pos (T,) -> (c_kv (T, kv_rank), k_r (T,
    rope) rotated): what a token leaves in the cache."""
    ckr = jnp.dot(xn, p["w_dkv"])
    c_kv = _latent_norm(ckr[..., :a.kv_rank], p["kv_norm"], a.kv_rank, a,
                        spec, faults)
    return c_kv, _rotary(a, ckr[..., a.kv_rank:], pos, faults)


def _index_parts(pi, spec: DecoderSpec, xn, c_q, pos):
    """(qi (T, heads, dim), ki (T, dim), w (T, heads) float32)."""
    a = spec.full
    qi = jnp.dot(c_q, pi["w_q"])
    qi = qi.reshape(qi.shape[:-1] + (spec.index_heads, spec.index_dim))
    qi = _rotary(a, qi, pos, dims=a.rope)
    ki = att.layer_norm(jnp.dot(xn, pi["w_k"]), pi["k_norm_scale"],
                        pi["k_norm_bias"], spec.eps)
    ki = _rotary(a, ki, pos, dims=a.rope)
    w = jnp.dot(xn, pi["w_w"], preferred_element_type=jnp.float32)
    return qi, ki, w


def _gated_output(p, xn, o):
    """o (..., heads, v) -> (..., hidden): the head-wise sigmoid gate from the
    layer's normed input, then W_o."""
    gate = jax.nn.sigmoid(jnp.dot(xn, p["w_g"],
                                  preferred_element_type=jnp.float32))
    o = o * gate[..., None].astype(o.dtype)
    return jnp.dot(o.reshape(o.shape[:-2] + (-1,)), p["w_o"])


def _latent_output(p, a: AttnSizes, xn, o, faults):
    """o (..., heads, v) -> (..., hidden): W_o, behind the head-wise gate
    where the source has one."""
    if a.gate:
        return _gated_output(p, xn, o)
    if "gate_kept" in faults:
        # a program that gates where the source has no gate: there is no
        # W_g to read, so the first `heads` columns of W_dq stand in
        return _gated_output(dict(p, w_g=p["w_dq"][:, :a.heads]), xn, o)
    return jnp.dot(o.reshape(o.shape[:-2] + (-1,)), p["w_o"])


def attention_prefill_row(p, kind: str, spec: DecoderSpec, x, length,
                          slots: int, faults=frozenset()):
    """One sequence. x (P, hidden) raw (this normalises), `length` (int32
    scalar) its real rows: the indexer and the attention leave out every q
    block that starts at or past it (`ops/attention.py`; the output's rows
    there are the gate's and W_o's of zeros), the projections run over all P.
    Returns (attention output (P, hidden), the row's cache entry, keys kept
    over the real rows (0 for a sliding layer), q blocks that ran: the
    predicates the branches took, summed)."""
    a = spec.attn(kind)
    total = x.shape[0]
    pos = jnp.arange(total, dtype=jnp.int32)
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    c_q, q = _queries(p, a, spec, xn, faults)
    q_nope, q_rope = q[..., :a.nope], _rotary(a, q[..., a.nope:], pos, faults)
    c_kv, k_r = _latents(p, a, spec, xn, pos, faults)
    kv = jnp.dot(c_kv, p["w_ukv"]).reshape(total, a.heads, a.nope + a.v)
    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))  # noqa: E731
    kept = jnp.zeros((), jnp.int32)
    chosen, window = None, None
    if kind == FULL:
        pad = ((0, slots - total), (0, 0))
        entry = {"c_kv": jnp.pad(c_kv, pad), "k_r": jnp.pad(k_r, pad)}
        # without an indexer every causal key is kept
        kept = length * (length + 1) // 2
        if spec.index_topk:
            with jax.named_scope("indexer"):
                qi, ki, w = _index_parts(p["indexer"], spec, xn, c_q, pos)
                if "no_indexer" not in faults:
                    chosen = att.select_blocks(qi, ki, w, spec.index_topk,
                                               spec.q_block, spec.head_block,
                                               length=length)
                    real = pos < length
                    kept = jnp.zeros((), jnp.int32)
                    for i, block in enumerate(chosen):
                        r0 = i * spec.q_block
                        kept += jnp.sum(
                            block & real[r0:r0 + block.shape[0], None],
                            dtype=jnp.int32)
            entry["k_i"] = jnp.pad(ki, pad)
    else:
        window = spec.window + ("window_off_by_one" in faults)
        held = att.ring_positions(length[None] - 1, spec.window)[0]
        take = lambda t: jnp.where(  # noqa: E731
            held[:, None] >= 0, t[jnp.clip(held, 0, total - 1)], 0)
        entry = {"c_kv": take(c_kv), "k_r": take(k_r)}
    how = dict(q_block=spec.q_block, window=window, chosen=chosen,
               length=length, head_block=spec.head_block,
               scale=_score_scale(a, faults))
    if att.runs_fused(3, total, spec.q_block, window):
        # the rotary part apart: its keys are ONE array for every head, which
        # the kernel fetches once a tile and the XLA path copies a head
        o = att.blockwise_attention(
            heads_first(q_nope), heads_first(kv[..., :a.nope]),
            heads_first(kv[..., a.nope:]),
            shared=(heads_first(q_rope), k_r), **how)
    else:
        k = jnp.concatenate([kv[..., :a.nope], jnp.broadcast_to(
            k_r[:, None, :], (total, a.heads, a.rope))], axis=-1)
        o = att.blockwise_attention(
            heads_first(jnp.concatenate([q_nope, q_rope], axis=-1)),
            heads_first(k), heads_first(kv[..., a.nope:]), **how)
    ran = sum(jnp.asarray(live, jnp.int32)
              for live in att.q_blocks_live(total, spec.q_block, length))
    return _latent_output(p, a, xn, heads_first(o), faults), entry, kept, ran


def attention_step(p, kind: str, spec: DecoderSpec, x, pos, entry,
                   faults=frozenset()):
    """One token a row. x (B, hidden) raw, pos (B,) its position, `entry` the
    layer's cache. Returns (attention output (B, hidden), the cache with the
    token written, keys kept (B,) (0 for a sliding layer))."""
    a = spec.attn(kind)
    rows = jnp.arange(x.shape[0])
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    c_q, q = _queries(p, a, spec, xn, faults)
    q_rope = _rotary(a, q[..., a.nope:], pos, faults)
    c_kv, k_r = _latents(p, a, spec, xn, pos, faults)
    w_ukv = p["w_ukv"].reshape(a.kv_rank, a.heads, a.nope + a.v)
    with jax.named_scope("absorb_q"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q[..., :a.nope],
                           w_ukv[..., :a.nope])
    kept = jnp.zeros(x.shape[:1], jnp.int32)
    if kind == FULL:
        slots = entry["c_kv"].shape[1]
        slot = pos
        if "stale_cache_row" in faults:  # every 7th position's write is lost
            slot = jnp.where(pos % 7 == 3, jnp.minimum(pos + 1, slots - 1),
                             pos)
        with jax.named_scope("cache_write"):
            new = {"c_kv": entry["c_kv"].at[rows, slot].set(c_kv),
                   "k_r": entry["k_r"].at[rows, slot].set(k_r)}
        allowed = jnp.arange(slots, dtype=jnp.int32)[None, :] <= pos[:, None]
        if spec.index_topk:
            qi, ki, w = _index_parts(p["indexer"], spec, xn, c_q, pos)
            with jax.named_scope("cache_write"):
                new["k_i"] = entry["k_i"].at[rows, pos].set(ki)
            if "no_indexer" not in faults:
                with jax.named_scope("indexer"):
                    scores = att.index_scores(qi[:, None], new["k_i"],
                                              w[:, None])[:, 0]
                    allowed &= att.top_k_mask(
                        jnp.where(allowed, scores, -jnp.inf), spec.index_topk)
        kept = jnp.sum(allowed, axis=-1, dtype=jnp.int32)
    else:
        slot = jnp.mod(pos, spec.window)
        if "stale_ring_row" in faults:  # the write of every 7th slot is lost
            slot = jnp.where(slot % 7 == 3, (slot + 1) % spec.window, slot)
        with jax.named_scope("cache_write"):
            new = {"c_kv": entry["c_kv"].at[rows, slot].set(c_kv),
                   "k_r": entry["k_r"].at[rows, slot].set(k_r)}
        allowed = att.ring_positions(pos, spec.window) >= 0
    o_lat = att.latent_cache_attention(
        q_lat, q_rope, new["c_kv"], new["k_r"], allowed,
        _score_scale(a, faults))
    with jax.named_scope("absorb_o"):
        o = jnp.einsum("bhr,rhd->bhd", o_lat, w_ukv[..., a.nope:])
    return _latent_output(p, a, xn, o, faults), new, kept


def _latent_prefill_row(p, kind, spec, x, length, slots, faults=frozenset()):
    out, entry, kept, ran = attention_prefill_row(p, kind, spec, x, length,
                                                  slots, faults)
    causal = length * (length + 1) // 2 * (kind == FULL)
    # the q blocks the fused kernel ran, all of a layer's or none, and the
    # (q block, key block) visits it did work in for them
    fused = att.runs_fused(3, x.shape[0], spec.q_block,
                           None if kind == FULL else spec.window)
    visits = (att.fused_visits(x.shape[0], spec.q_block, ran) if fused
              else jnp.zeros_like(ran))
    return out, entry, {"keys_kept": kept, "keys_causal": causal,
                        "q_blocks_run": ran, "q_blocks_fused": ran * fused,
                        "attn_fused_visits": visits}


def _latent_step(p, kind, spec, x, pos, entry, faults=frozenset()):
    out, entry, kept = attention_step(p, kind, spec, x, pos, entry, faults)
    counts = {"keys_kept": kept, "keys_causal": (pos + 1) * (kind == FULL)}
    if kind == FULL and not spec.index_topk:
        # a full layer without an indexer reads its whole cache and may
        # attend the causal keys: the cache's live share is theirs over the
        # slots (behind an indexer the keys read are `keys_kept`'s business)
        counts.update(
            slots_full=jnp.full(pos.shape, entry["c_kv"].shape[1], jnp.int32),
            keys_full=pos + 1)
    return out, entry, counts


# ---- the grouped-query attention -------------------------------------------------

def _grouped_qkv(p, g: GroupedSizes, spec: DecoderSpec, x, pos, faults):
    """x (..., hidden) raw at positions `pos` -> (xn, q (..., G, R, d), k, v
    (..., G, d)), q and k rotated over their leading `rot` dims. Query head h
    belongs to group h // R."""
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    lead = x.shape[:-1]
    q = jnp.dot(xn, p["w_q"])
    ratio = q.shape[-1] // (g.kv_heads * g.head_dim)
    if "kv_group_misassigned" in faults:  # head h reads group h % G
        q = jnp.swapaxes(q.reshape(lead + (ratio, g.kv_heads, g.head_dim)),
                         -3, -2)
    else:
        q = q.reshape(lead + (g.kv_heads, ratio, g.head_dim))
    k = jnp.dot(xn, p["w_k"]).reshape(lead + (g.kv_heads, g.head_dim))
    v = jnp.dot(xn, p["w_v"]).reshape(lead + (g.kv_heads, g.head_dim))
    rot, freq, scale = g.rot, g.inv_freq, g.rope_scale
    plain = lambda r: tuple(g.theta ** (-2.0 * j / r)  # noqa: E731
                            for j in range(r // 2))
    if "full_rope_whole_head" in faults and rot < g.head_dim:
        rot, freq = g.head_dim, plain(g.head_dim)  # the partial factor lost
    if "yarn_dropped" in faults and scale != 1.0:  # plain theta, no scale
        freq, scale = plain(rot), 1.0
    with jax.named_scope("rope"):
        freq = jnp.asarray(freq, jnp.float32)
        q = att.rotate_leading_by(q, pos, freq, scale, rot)
        k = att.rotate_leading_by(k, pos, freq, scale, rot)
    return xn, q, k, v


def _grouped_output(p, xn, o, faults):
    """o (..., G, R, d) -> (..., hidden): heads back in their order, the
    head-wise gate, W_o."""
    if "kv_group_misassigned" in faults:
        o = jnp.swapaxes(o, -3, -2)
    o = o.reshape(o.shape[:-3] + (-1, o.shape[-1]))
    with jax.named_scope("gate"):
        if "no_gate" in faults:
            return jnp.dot(o.reshape(o.shape[:-2] + (-1,)), p["w_o"])
        return _gated_output(p, xn, o)


def grouped_prefill_row(p, kind: str, spec: DecoderSpec, x, length,
                        slots: int, faults=frozenset()):
    """One sequence. x (P, hidden) raw, `length` its real rows. Returns
    (attention output (P, hidden), the row's cache entry k, v (G, slots or
    window, d), counts)."""
    g = spec.attn(kind)
    total = x.shape[0]
    pos = jnp.arange(total, dtype=jnp.int32)
    xn, q, k, v = _grouped_qkv(p, g, spec, x, pos, faults)
    k, v = jnp.transpose(k, (1, 0, 2)), jnp.transpose(v, (1, 0, 2))
    with jax.named_scope("kv_write"):
        if kind == FULL:
            window = None
            pad = ((0, 0), (0, slots - total), (0, 0))
            entry = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
        else:
            window = spec.window + ("window_off_by_one" in faults)
            held = att.ring_positions(length[None] - 1, spec.window)[0]
            take = lambda t: jnp.where(  # noqa: E731
                held[:, None] >= 0, t[:, jnp.clip(held, 0, total - 1)], 0)
            entry = {"k": take(k), "v": take(v)}
    ratio = q.shape[-2]
    # k/v groups a pass: about `head_block` query heads' scores at a time
    step = max(1, spec.head_block // ratio)
    while g.kv_heads % step:
        step -= 1
    o = att.blockwise_attention(
        jnp.transpose(q, (1, 2, 0, 3)), k, v, q_block=spec.q_block,
        window=window, length=length, head_block=step,
        scale=1.0 / math.sqrt(g.head_dim))
    ran = sum(jnp.asarray(live, jnp.int32)
              for live in att.q_blocks_live(total, spec.q_block, length))
    return (_grouped_output(p, xn, jnp.transpose(o, (2, 0, 1, 3)), faults),
            entry, {"q_blocks_run": ran, "keys_causal":
                    length * (length + 1) // 2 * (kind == FULL)})


def grouped_step(p, kind: str, spec: DecoderSpec, x, pos, entry,
                 faults=frozenset()):
    """One token a row. x (B, hidden) raw, pos (B,) its position, `entry` the
    layer's k/v cache. Returns (attention output (B, hidden), the cache with
    the token written, counts: the slots this step read and the real keys
    among them; on a full layer those are the causal keys)."""
    g = spec.attn(kind)
    batch, groups = x.shape[0], g.kv_heads
    xn, q, k, v = _grouped_qkv(p, g, spec, x, pos, faults)
    slots = entry["k"].shape[2]
    # the cache as (B x G, S, d) while it is written and read: one row of d
    # a (row, group), at a slot; the write's index dims lead and the
    # products have one batch dim, so the compiler keeps ONE layout for
    # both (as (B, G, S, d) it wrote in one layout, read in another and
    # copied every ring whole every step: PERF.md section 6, PR 33)
    flat = lambda t: t.reshape((batch * groups,) + t.shape[2:])  # noqa: E731
    with jax.named_scope("kv_write"):
        if kind == FULL:
            slot, tag = pos, "full"
        else:
            slot, tag = jnp.mod(pos, spec.window), "window"
            if "stale_ring_row" in faults:  # every 7th slot's write is lost
                slot = jnp.where(slot % 7 == 3, (slot + 1) % spec.window, slot)
        at = jnp.arange(batch * groups), jnp.repeat(slot, groups)
        cache_k = flat(entry["k"]).at[at].set(flat(k))
        cache_v = flat(entry["v"]).at[at].set(flat(v))
    if kind == FULL:
        allowed = jnp.arange(slots, dtype=jnp.int32)[None, :] <= pos[:, None]
    else:
        allowed = att.ring_positions(pos, spec.window) >= 0
    o = att.grouped_cache_attention(
        flat(q), cache_k, cache_v, jnp.repeat(allowed, groups, axis=0),
        1.0 / math.sqrt(g.head_dim)).reshape(q.shape)
    entry = {"k": cache_k.reshape(entry["k"].shape),
             "v": cache_v.reshape(entry["v"].shape)}
    keys = jnp.sum(allowed, axis=-1, dtype=jnp.int32)
    return _grouped_output(p, xn, o, faults), entry, {
        "slots_" + tag: jnp.full(pos.shape, slots, jnp.int32),
        "keys_" + tag: keys, "keys_causal": keys * (kind == FULL)}


# ---- the linear attention (KDA) -------------------------------------------------

def _linear_inputs(p, xn):
    """xn (..., hidden) -> what the short convolution takes: the q, k and v
    projections side by side (..., 3 H d), in xn's dtype."""
    return jnp.concatenate([jnp.dot(xn, p["w_q"]), jnp.dot(xn, p["w_k"]),
                            jnp.dot(xn, p["w_v"])], axis=-1)


def _linear_qkv(l: LinearSizes, c, faults):
    """c (..., 3 H d) float32 after the convolution -> q (scaled by d^-1/2),
    k (..., H, d) normalised a head, v (..., H, d)."""
    c = jax.nn.silu(c)
    lead, width = c.shape[:-1], l.heads * l.head_dim
    q, k, v = (c[..., i * width:(i + 1) * width].reshape(
        lead + (l.heads, l.head_dim)) for i in range(3))
    if "no_l2norm" not in faults:
        q, k = la.l2_normalize(q), la.l2_normalize(k)
    return q * l.head_dim ** -0.5, k, v


def _linear_gates(p, l: LinearSizes, xn, faults):
    """xn (..., hidden) -> (g (..., H, d) float32 in (lower, 0): the decay
    of each channel in log space, beta (..., H) float32: the write's
    strength)."""
    f = (jnp.dot(xn, p["w_f"], preferred_element_type=jnp.float32)
         + p["dt_bias"].astype(jnp.float32))
    f = f.reshape(f.shape[:-1] + (l.heads, l.head_dim))
    g = l.lower * jax.nn.sigmoid(
        jnp.exp(p["a_log"].astype(jnp.float32))[:, None] * f)
    beta = jax.nn.sigmoid(jnp.dot(xn, p["w_beta"],
                                  preferred_element_type=jnp.float32))
    if "decay_dropped" in faults:
        g = jnp.zeros_like(g)
    if "beta_one" in faults:
        beta = jnp.ones_like(beta)
    return g, beta


def _linear_output(p, spec: DecoderSpec, xn, o, faults):
    """o (..., H, d) float32 -> (..., hidden): RMSNorm a head (one scale
    shared by the heads), the full-rank sigmoid gate, W_o."""
    o = att.rms_norm(o, p["o_norm"], spec.eps)
    if "out_gate_dropped" not in faults:
        gate = jax.nn.sigmoid(jnp.dot(xn, p["w_g"],
                                      preferred_element_type=jnp.float32))
        o = o * gate.reshape(o.shape)
    return jnp.dot(o.reshape(o.shape[:-2] + (-1,)).astype(xn.dtype),
                   p["w_o"])


def _state_kept(state, faults):
    if "state_bf16" in faults:  # a state held in bfloat16 between tokens
        # (reduce_precision: a cast there and back the compiler may drop)
        return lax.reduce_precision(state, 8, 7)
    return state


def linear_prefill_rows(p, kind: str, spec: DecoderSpec, x, lengths,
                        slots: int, faults=frozenset()):
    """A pass of R sequences (`Attention.rows`). x (R, P, hidden) raw,
    `lengths` (R,) their real rows. Returns (attention output (R, P,
    hidden), the rows' cache entries: `state` (R, H, d, d) float32 after
    each row's last real token, `conv` (R, K-1, 3 H d) its last K-1 real
    inputs to the convolution; counts (R,): the chunks of the delta rule the
    pass ran (its longest row's), that the row's own tokens fill, and that a
    padded row holds). A padded position writes nothing: beta and g are 0
    there."""
    l = spec.linear
    total = x.shape[1]
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    with jax.named_scope("conv"):
        u = _linear_inputs(p, xn)
        c = (u.astype(jnp.float32) if "conv_dropped" in faults
             else la.causal_conv(u, p["conv"]))
        q, k, v = _linear_qkv(l, c, faults)
        tail = la.conv_tail(u, lengths, l.conv)
    with jax.named_scope("gate"):
        g, beta = _linear_gates(p, l, xn, faults)
        if "padding_updates_state" not in faults:
            real = (jnp.arange(total, dtype=jnp.int32)[None, :]
                    < lengths[:, None])
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
    with jax.named_scope("scan"):
        o, state, ran = la.chunked_prefill(q, k, v, g, beta, lengths,
                                           spec.linear_chunk)
    with jax.named_scope("out_norm"):
        out = _linear_output(p, spec, xn, o, faults)
    return out, {"state": _state_kept(state, faults), "conv": tail}, {
        "linear_chunks_run": ran,
        "linear_chunks_live": -(-lengths // spec.linear_chunk),
        "linear_chunks_total": jnp.full(lengths.shape,
                                        -(-total // spec.linear_chunk),
                                        jnp.int32)}


def linear_step(p, kind: str, spec: DecoderSpec, x, pos, entry,
                faults=frozenset()):
    """One token a row. x (B, hidden) raw, `entry` the layer's state and
    convolution tail. Returns (attention output (B, hidden), the entry after
    the token, counts: one state read-modify-write a row)."""
    l = spec.linear
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    with jax.named_scope("conv"):
        u = _linear_inputs(p, xn)
        if "conv_dropped" in faults:
            c, tail = u.astype(jnp.float32), entry["conv"]
        else:
            c, tail = la.conv_step(u, p["conv"], entry["conv"])
        q, k, v = _linear_qkv(l, c, faults)
    with jax.named_scope("gate"):
        g, beta = _linear_gates(p, l, xn, faults)
    state = entry["state"]
    if "state_reset_at_decode" in faults:  # every step from an empty state
        state = jnp.zeros_like(state)
    with jax.named_scope("state"):
        o, state = la.state_step(state, q, k, v, g, beta)
    with jax.named_scope("out_norm"):
        out = _linear_output(p, spec, xn, o, faults)
    return out, {"state": _state_kept(state, faults), "conv": tail}, {
        "linear_state_steps": jnp.ones(pos.shape, jnp.int32)}


def expert_layer_groups(p, spec: DecoderSpec, hn, token_real,
                        faults=frozenset()):
    """hn (T, hidden) normed -> (y (T, hidden): the held experts' part plus
    the shared expert, local (T, k): each pair's held expert or `held`, hit
    (T,) bool: under a router with groups, the real tokens for which a group
    this share holds was among the groups kept; None without groups)."""
    with jax.named_scope("router"):
        bias = p.get("b_select")
        if bias is not None and "no_select_bias" in faults:
            bias = bias * 0.0
        scale = 1.0 if "no_routed_scale" in faults else spec.routed_scale
        groups = 0 if "no_group_limit" in faults else spec.n_group
        idx, weights, kept = moe.route_groups(
            hn, p["w_router"], bias, spec.per_token, spec.norm_weights, scale,
            groups, spec.topk_group)
    with jax.named_scope("experts"):
        y, local = moe.routed_experts(hn, idx, weights, token_real,
                                      p["w_gate_up"], p["w_down"], spec.share)
    if spec.shared_width and "no_shared" not in faults:
        with jax.named_scope("shared_expert"):
            y = y + moe.swiglu(hn, p["shared_gate_up"], p["shared_down"])
    hit = None
    if kept is not None:
        mine = spec.share.groups()
        hit = jnp.any(kept[:, mine.start:mine.stop], axis=-1) & token_real
    return y, local, hit


def expert_layer(p, spec: DecoderSpec, hn, token_real, faults=frozenset()):
    """`expert_layer_groups` without the group hits: (y, local)."""
    return expert_layer_groups(p, spec, hn, token_real, faults)[:2]


def _pairs_by_expert(local, real, held: int):
    """local (B, T, k), real (B, T) -> (B, held) int32: pairs of real tokens
    routed to each held expert."""
    hit = (local[..., None] == jnp.arange(held, dtype=jnp.int32)) \
        & real[..., None, None]
    return jnp.sum(hit, axis=(1, 2), dtype=jnp.int32)


def _visits_by_row(pairs):
    """pairs (B, held) of one pass over an expert layer -> (B,) int32: the
    experts that had at least one pair, each credited to the lowest row that
    routed there (so the rows add up to the experts the batch visited)."""
    hit = pairs > 0
    first = hit & (jnp.cumsum(hit, axis=0, dtype=jnp.int32) == 1)
    return jnp.sum(first, axis=1, dtype=jnp.int32)


# ---- the modules --------------------------------------------------------------------

class Indexer(nn.Module):
    spec: DecoderSpec
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, d = self.spec, self.dtype
        return {
            "w_q": self.param("w_q", _INIT, (s.full.q_rank,
                                             s.index_heads * s.index_dim), d),
            "w_k": self.param("w_k", _INIT, (s.hidden, s.index_dim), d),
            "k_norm_scale": self.param("k_norm_scale", _scale_init,
                                       (s.index_dim,), d),
            "k_norm_bias": self.param("k_norm_bias", nn.initializers.zeros,
                                      (s.index_dim,), d),
            "w_w": self.param("w_w", _INIT, (s.hidden, s.index_heads), d)}


class LatentAttention(nn.Module):
    """The latent attentions: the layer's kind picks the size set and the
    window; the spec (what the source's mapping held) whether there is an
    indexer or a gate. `__call__` returns the arrays; the arithmetic is
    `attention_prefill_row` / `attention_step`."""
    spec: DecoderSpec
    index: int
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def kind(self) -> str:
        return self.spec.kinds[self.index]

    @nn.compact
    def __call__(self):
        s, a, d = self.spec, self.spec.attn(self.kind), self.dtype
        q_width = a.heads * (a.nope + a.rope)
        if a.q_rank:
            p = {"w_dq": self.param("w_dq", _INIT, (s.hidden, a.q_rank), d),
                 "q_norm": self.param("q_norm", _scale_init, (a.q_rank,), d),
                 "w_uq": self.param("w_uq", _INIT, (a.q_rank, q_width), d)}
        else:  # no query latent: one W_q
            p = {"w_q": self.param("w_q", _INIT, (s.hidden, q_width), d)}
        p.update({
            "w_dkv": self.param("w_dkv", _INIT,
                                (s.hidden, a.kv_rank + a.rope), d),
            "kv_norm": self.param("kv_norm", _scale_init, (a.kv_rank,), d),
            "w_ukv": self.param("w_ukv", _INIT,
                                (a.kv_rank, a.heads * (a.nope + a.v)), d),
            "w_o": self.param("w_o", _INIT, (a.heads * a.v, s.hidden), d)})
        if a.gate:
            p["w_g"] = self.param("w_g", _INIT, (s.hidden, a.heads), d)
        if self.kind == FULL and s.index_topk:
            p["indexer"] = Indexer(s, d, name="indexer")()
        return p


class GroupedAttention(nn.Module):
    """Grouped-query attention of layer `index`: its own count of query
    heads over the kind's k/v heads. The arithmetic is `grouped_prefill_row`
    / `grouped_step`."""
    spec: DecoderSpec
    index: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, d, heads = self.spec, self.dtype, self.spec.heads[self.index]
        g = s.attn(s.kinds[self.index])
        kv = g.kv_heads * g.head_dim
        return {
            "w_q": self.param("w_q", _INIT, (s.hidden, heads * g.head_dim), d),
            "w_k": self.param("w_k", _INIT, (s.hidden, kv), d),
            "w_v": self.param("w_v", _INIT, (s.hidden, kv), d),
            "w_g": self.param("w_g", _INIT, (s.hidden, heads), d),
            "w_o": self.param("w_o", _INIT, (heads * g.head_dim, s.hidden), d)}


class LinearAttention(nn.Module):
    """The linear-attention kind (KDA) of layer `index`: W_q, W_k, W_v, the
    decay's W_f (full rank), A_log (a head) and dt_bias (a channel), the
    short convolution's taps, W_beta, the output's norm, gate and W_o. The
    arithmetic is `linear_prefill_rows` / `linear_step`."""
    spec: DecoderSpec
    index: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, d, l = self.spec, self.dtype, self.spec.linear
        width = l.heads * l.head_dim
        p = {name: self.param(name, _INIT, (s.hidden, width), d)
             for name in ("w_q", "w_k", "w_v", "w_f", "w_g")}
        p.update(
            w_beta=self.param("w_beta", _INIT, (s.hidden, l.heads), d),
            a_log=self.param("a_log", nn.initializers.zeros, (l.heads,),
                             jnp.float32),
            dt_bias=self.param("dt_bias", nn.initializers.zeros, (width,),
                               jnp.float32),
            conv=self.param("conv", _INIT, (l.conv, 3 * width), d),
            o_norm=self.param("o_norm", _scale_init, (l.head_dim,), d),
            w_o=self.param("w_o", _INIT, (width, s.hidden), d))
        return p


class Attention(NamedTuple):
    """What a layer's kind selects (module docstring). `rows`: the rows a
    prefill pass takes together; with 1, `prefill_row` takes one row (x (P,
    hidden), a scalar length), else a leading axis of as many rows as divide
    both the batch and `rows`."""
    module: Callable
    prefill_row: Callable
    step: Callable
    rows: int = 1


_LATENT = Attention(LatentAttention, _latent_prefill_row, _latent_step)
_GROUPED = Attention(GroupedAttention, grouped_prefill_row, grouped_step)
# the attention of each layer kind, by family
ATTENTIONS = {
    FAMILY: {FULL: _LATENT, SLIDING: _LATENT},
    GQA_FAMILY: {FULL: _GROUPED, SLIDING: _GROUPED},
    HYBRID_FAMILY: {FULL: _LATENT, LINEAR: Attention(
        LinearAttention, linear_prefill_rows, linear_step, la.ROWS)}}


def attention_of(spec: DecoderSpec, kind: str) -> Attention:
    return ATTENTIONS[spec.family][kind]


class DenseFFN(nn.Module):
    spec: DecoderSpec
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, d = self.spec, self.dtype
        return {"w_gate_up": self.param("w_gate_up", _INIT,
                                        (s.hidden, 2 * s.dense_width), d),
                "w_down": self.param("w_down", _INIT,
                                     (s.dense_width, s.hidden), d)}


class ExpertLayer(nn.Module):
    spec: DecoderSpec
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, d = self.spec, self.dtype
        f, fs, held = s.expert_width, s.shared_width, s.share.held
        p = {
            "w_router": self.param("w_router", _INIT,
                                   (s.hidden, s.share.n_routed), d),
            "w_gate_up": self.param("w_gate_up", _INIT,
                                    (held, s.hidden, 2 * f), d),
            "w_down": self.param("w_down", _INIT, (held, f, s.hidden), d),
            "shared_gate_up": self.param("shared_gate_up", _INIT,
                                         (s.hidden, 2 * fs), d),
            "shared_down": self.param("shared_down", _INIT,
                                      (fs, s.hidden), d)}
        if s.select_bias:
            p["b_select"] = self.param("b_select", nn.initializers.zeros,
                                       (s.share.n_routed,), jnp.float32)
        return p


class DecoderLayer(nn.Module):
    spec: DecoderSpec
    index: int
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def kind(self) -> str:
        return self.spec.kinds[self.index]

    @property
    def dense(self) -> bool:
        return self.index < self.spec.dense_layers

    @nn.compact
    def __call__(self):
        s, d = self.spec, self.dtype
        attention = attention_of(s, self.kind).module
        p = {"attn": dict(attention(s, self.index, d, name="attn")(),
                          attn_norm=self.param("attn_norm", _scale_init,
                                               (s.hidden,), d)),
             "ffn_norm": self.param("ffn_norm", _scale_init, (s.hidden,), d)}
        if self.dense:
            p["ffn"] = DenseFFN(s, d, name="ffn")()
        else:
            p["moe"] = ExpertLayer(s, d, name="moe")()
        return p


def _attn_scope(kind: str) -> str:
    return {FULL: "attn_full", SLIDING: "attn_window",
            LINEAR: "attn_linear"}[kind]


def _feed_forward(p, spec: DecoderSpec, dense: bool, x, real, faults):
    """x (B, T, hidden) after attention -> (x out, local (B, T, k) or None,
    group hits (B, T) bool or None)."""
    hn = att.rms_norm(x, p["ffn_norm"], spec.eps)
    if dense:
        ffn = lambda h: moe.swiglu(h, p["ffn"]["w_gate_up"],  # noqa: E731
                                   p["ffn"]["w_down"])
        with jax.named_scope("dense_ffn"):
            # prefill a row at a time: the gate and up projections of a
            # whole batch of prompts would stand as gigabytes
            y = lax.map(ffn, hn) if x.shape[1] > 1 else ffn(hn)
        return x + y, None, None
    flat = hn.reshape(-1, spec.hidden)
    y, local, hit = expert_layer_groups(p["moe"], spec, flat,
                                        real.reshape(-1), faults)
    return (x + y.reshape(x.shape), local.reshape(x.shape[:2] + (-1,)),
            None if hit is None else hit.reshape(x.shape[:2]))


class MoEDecoder(nn.Module):
    """The one body of both families. `prefill` and `step`; `__call__` (what
    `init` runs) is a prefill of the tokens given, all real."""
    spec: DecoderSpec
    dtype: jnp.dtype = jnp.bfloat16
    faults: frozenset = frozenset()  # tests only: a planted fault by name

    def setup(self):
        s, d = self.spec, self.dtype
        self.embed = self.param("embed", _INIT, (s.vocab, s.hidden), d)
        self.final_norm = self.param("final_norm", _scale_init,
                                     (s.hidden,), d)
        self.lm_head = self.param("lm_head", _INIT, (s.hidden, s.vocab), d)
        # flax names the entries of a list after the attribute: layer_0 ...
        self.layer = [DecoderLayer(s, i, d) for i in range(s.layers)]

    def __call__(self, tokens):
        lengths = jnp.full(tokens.shape[:1], tokens.shape[1], jnp.int32)
        return self.prefill(tokens, lengths)[0]

    def _logits(self, h):
        with jax.named_scope("lm_head"):
            return jnp.dot(att.rms_norm(h, self.final_norm, self.spec.eps),
                           self.lm_head, preferred_element_type=jnp.float32)

    def _layers(self, x, real, attend, counts):
        """The layer loop of `prefill` and `step` alike. x (B, T, hidden) or
        (B, hidden); `attend(i, p, kind, x) -> (out, cache entry, the
        attention's counts)`. Returns (x, the cache entries, `counts` with
        this pass added)."""
        s = self.spec
        entries, pairs, visits, hits, slots = [], [], 0, 0, 0
        tally = {name: counts[name] for name in ATTENTION_COUNTS}
        for i, block in enumerate(self.layer):
            p, kind = block(), s.kinds[i]
            with jax.named_scope(_attn_scope(kind)):
                out, entry, got = attend(i, p, kind, x)
            x = x + out
            entries.append(entry)
            for name, value in got.items():
                tally[name] = tally[name] + value
            wide = x if x.ndim == 3 else x[:, None]
            wide, local, hit = _feed_forward(p, s, i < s.dense_layers, wide,
                                             real, self.faults)
            x = wide if x.ndim == 3 else wide[:, 0]
            if local is not None:
                pairs.append(_pairs_by_expert(local, real, s.share.held))
                visits = visits + _visits_by_row(pairs[-1])
            if hit is not None:
                hits = hits + jnp.sum(hit, axis=1, dtype=jnp.int32)
                slots = slots + jnp.sum(real, axis=1, dtype=jnp.int32)
        tally["expert_tokens"] = counts["expert_tokens"] + (
            jnp.stack(pairs, axis=1) if pairs else 0)
        tally["expert_visits"] = counts["expert_visits"] + visits
        tally["group_hits"] = counts["group_hits"] + hits
        tally["group_slots"] = counts["group_slots"] + slots
        tally["q_blocks_total"] = counts["q_blocks_total"]
        return x, tuple(entries), tally

    def _no_counts(self, rows: int) -> dict:
        s = self.spec
        zeros = jnp.zeros((rows,), jnp.int32)
        return dict({name: zeros for name in ATTENTION_COUNTS
                     + ("expert_visits", "q_blocks_total", "group_hits",
                        "group_slots")},
                    expert_tokens=jnp.zeros(
                        (rows, s.expert_layers, s.share.held), jnp.int32))

    def prefill(self, tokens, lengths, reserve: int = 0):
        """tokens int32 (B, P), lengths int32 (B,) -> (float32 logits (B,
        vocab) at each row's last real token, cache with `reserve` more
        slots than P in the full layers)."""
        s = self.spec
        rows, total = tokens.shape
        slots = total + reserve
        lengths = jnp.clip(lengths, 1, total)
        real = jnp.arange(total, dtype=jnp.int32)[None, :] < lengths[:, None]

        def attend(i, p, kind, x):
            attention = attention_of(s, kind)
            run = lambda xl: attention.prefill_row(  # noqa: E731
                p["attn"], kind, s, xl[0], xl[1], slots, self.faults)
            if attention.rows == 1:
                return lax.map(run, (x, lengths))
            together = math.gcd(rows, attention.rows)
            passes = lax.map(run, (
                x.reshape((rows // together, together) + x.shape[1:]),
                lengths.reshape(rows // together, together)))
            return jax.tree.map(
                lambda a: a.reshape((rows,) + a.shape[2:]), passes)
        with jax.named_scope("prefill"):
            with jax.named_scope("embed"):
                x = self.embed[tokens]
            x, entries, counts = self._layers(x, real, attend,
                                              self._no_counts(rows))
            last = x[jnp.arange(rows), lengths - 1]
            logits = self._logits(last)
        counts["q_blocks_total"] = jnp.full(
            (rows,), (s.layers - s.linear_layers) * -(-total // s.q_block),
            jnp.int32)
        return logits, {"layers": entries, "counts": counts}

    def step(self, token, positions, cache):
        """token int32 (B,) at `positions` (B,) -> (float32 logits (B,
        vocab), the cache with the token written and counted)."""
        s = self.spec
        real = jnp.ones(token.shape + (1,), bool)

        def attend(i, p, kind, x):
            return attention_of(s, kind).step(
                p["attn"], kind, s, x, positions, cache["layers"][i],
                self.faults)
        with jax.named_scope("decode"):
            with jax.named_scope("embed"):
                x = self.embed[token]
            x, entries, counts = self._layers(x, real, attend,
                                              cache["counts"])
            logits = self._logits(x)
        return logits, {"layers": entries, "counts": counts}


LatentMoEDecoder = MoEDecoder  # the name the first family's callers know


def build_decoder(cfg, dtype: Optional[jnp.dtype] = None) -> MoEDecoder:
    """The decoder `cfg.decoder` describes (`models.build_model` dispatches
    here on `cfg.family`)."""
    if not getattr(cfg, "decoder", None):
        raise ValueError("family %r needs `decoder`: the source's config keys"
                         % cfg.family)
    return MoEDecoder(DecoderSpec.from_mapping(cfg.decoder, cfg.family),
                      dtype or jnp.bfloat16)
