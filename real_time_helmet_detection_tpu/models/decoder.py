"""The second family: a decoder of latent-attention layers (full layers
behind a learned sparse indexer, sliding layers behind a window, a head-wise
output gate on both), a leading dense SwiGLU layer and sigmoid-routed experts
with a shared expert, as ONE SHARE of an expert-parallel deployment.

The reference has no language model (ref hourglass.py is the only network);
this module is new capability. The equations are stated once, in
benchmark/reference/latent_moe_decoder.py (the plain reference the tests and
the benchmark hold this program to); this file is how the program computes
them: bfloat16 parameters and activations, float32 in norms, softmax, router
scores and the indexer's score sum; prefill a sequence at a time under
`lax.map` (so that one row's q, k, v and scores are what stands in memory),
blockwise over queries; decode one token a row against two kinds of cache,
with W_uk / W_uv absorbed into the query and the output.

The flax modules declare parameters and hold no arithmetic of their own: a
module reads its arrays, then calls the pure functions below (a flax module
cannot be entered under `lax.map`).

Cache, one entry a layer (`cache["layers"][i]`):
  full layer     c_kv (B, S, kv_rank), k_r (B, S, rope), k_i (B, S, index dim)
                 at absolute positions (S = prompt slots + reserved);
  sliding layer  c_kv (B, window, kv_rank), k_r (B, window, rope): a ring,
                 position p at slot p % window.
`cache["counts"]`: per row, pairs routed to each held expert by expert layer
(padding excluded), keys the indexer kept and keys causal, full layers summed,
and the q blocks of prefill attention that ran and that the padded row holds,
attention layers summed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention as att
from ..ops import moe
from ..parallel.experts import ExpertShare, expert_share

FULL, SLIDING = "full_attention", "sliding_attention"
FAMILY = "latent_moe_decoder"


@dataclasses.dataclass(frozen=True)
class AttnSizes:
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    """The sizes, from the source's own keys (`Config.decoder`: the model's
    `config.json` keys as they stand, plus `ep_size` / `ep_rank`, and
    `n_routed_experts` / `vocab_size` counting what is held HERE)."""
    hidden: int
    vocab: int
    kinds: Tuple[str, ...]
    dense_layers: int
    dense_width: int
    expert_width: int
    shared: int
    share: ExpertShare
    per_token: int
    norm_weights: bool
    routed_scale: float
    eps: float
    window: int
    index_heads: int
    index_dim: int
    index_topk: int
    full: AttnSizes
    swa: AttnSizes
    # how the program walks a prompt (not the model's): query rows a block,
    # heads a pass of the attention and of the indexer
    q_block: int = 512
    head_block: int = 32

    @classmethod
    def from_mapping(cls, d: Mapping) -> "DecoderSpec":
        def sizes(prefix, heads):
            return AttnSizes(
                int(d[heads]), int(d[prefix + "q_lora_rank"]),
                int(d[prefix + "kv_lora_rank"]),
                int(d[prefix + "qk_nope_head_dim"]),
                int(d[prefix + "qk_rope_head_dim"]),
                int(d[prefix + "v_head_dim"]), float(d[prefix + "rope_theta"]))
        layers = int(d["num_hidden_layers"])
        kinds = tuple(d["layer_types"])[:layers]
        if len(kinds) != layers or set(kinds) - {FULL, SLIDING}:
            raise ValueError("layer_types must name %d layers as %s or %s, "
                             "got %r" % (layers, FULL, SLIDING, kinds))
        ep = int(d.get("ep_size", 1))
        held = int(d["n_routed_experts"])
        return cls(
            hidden=int(d["hidden_size"]), vocab=int(d["vocab_size"]),
            kinds=kinds, dense_layers=int(d["first_k_dense_replace"]),
            dense_width=int(d["intermediate_size"]),
            expert_width=int(d["moe_intermediate_size"]),
            shared=int(d["n_shared_experts"]),
            share=expert_share(ep, int(d.get("ep_rank", 0)), held * ep),
            per_token=int(d["num_experts_per_tok"]),
            norm_weights=bool(d["norm_topk_prob"]),
            routed_scale=float(d["routed_scaling_factor"]),
            eps=float(d["rms_norm_eps"]),
            window=int(d["sliding_window_size"]),
            index_heads=int(d["index_n_heads"]),
            index_dim=int(d["index_head_dim"]),
            index_topk=int(d["index_topk"]),
            full=sizes("", "num_attention_heads"),
            swa=sizes("swa_", "swa_num_attention_heads"),
            q_block=int(d.get("attn_q_block", 512)),
            head_block=int(d.get("attn_head_block", 32)))

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def full_layers(self) -> int:
        return sum(kind == FULL for kind in self.kinds)

    def attn(self, kind: str) -> AttnSizes:
        return self.full if kind == FULL else self.swa


_INIT = nn.initializers.normal(0.02)
_scale_init = nn.initializers.ones


# ---- the arithmetic (pure functions of the arrays) -------------------------------

def _queries(p, a: AttnSizes, spec: DecoderSpec, xn):
    """xn (..., hidden) -> (c_q (..., q_rank), q (..., heads, nope + rope),
    the rope part not rotated yet)."""
    c_q = att.rms_norm(
        jnp.dot(xn, p["w_dq"]),
        p["q_norm"].astype(jnp.float32) * math.sqrt(spec.hidden / a.q_rank),
        spec.eps)
    q = jnp.dot(c_q, p["w_uq"])
    return c_q, q.reshape(q.shape[:-1] + (a.heads, a.nope + a.rope))


def _latents(p, a: AttnSizes, spec: DecoderSpec, xn, pos):
    """xn (T, hidden) at positions pos (T,) -> (c_kv (T, kv_rank), k_r (T,
    rope) rotated): what a token leaves in the cache."""
    ckr = jnp.dot(xn, p["w_dkv"])
    c_kv = att.rms_norm(
        ckr[..., :a.kv_rank],
        p["kv_norm"].astype(jnp.float32) * math.sqrt(spec.hidden / a.kv_rank),
        spec.eps)
    return c_kv, att.rotate(ckr[..., a.kv_rank:], pos, a.theta)


def _index_parts(pi, spec: DecoderSpec, xn, c_q, pos):
    """(qi (T, heads, dim), ki (T, dim), w (T, heads) float32)."""
    rope, theta = spec.full.rope, spec.full.theta
    qi = jnp.dot(c_q, pi["w_q"])
    qi = qi.reshape(qi.shape[:-1] + (spec.index_heads, spec.index_dim))
    qi = att.rotate_leading(qi, pos, theta, rope)
    ki = att.layer_norm(jnp.dot(xn, pi["w_k"]), pi["k_norm_scale"],
                        pi["k_norm_bias"], spec.eps)
    ki = att.rotate_leading(ki, pos, theta, rope)
    w = jnp.dot(xn, pi["w_w"], preferred_element_type=jnp.float32)
    return qi, ki, w


def _gated_output(p, xn, o):
    """o (..., heads, v) -> (..., hidden): the head-wise sigmoid gate from the
    layer's normed input, then W_o."""
    gate = jax.nn.sigmoid(jnp.dot(xn, p["w_g"],
                                  preferred_element_type=jnp.float32))
    o = o * gate[..., None].astype(o.dtype)
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
    c_q, q = _queries(p, a, spec, xn)
    q = jnp.concatenate([q[..., :a.nope],
                         att.rotate(q[..., a.nope:], pos, a.theta)], axis=-1)
    c_kv, k_r = _latents(p, a, spec, xn, pos)
    kv = jnp.dot(c_kv, p["w_ukv"]).reshape(total, a.heads, a.nope + a.v)
    k = jnp.concatenate([kv[..., :a.nope], jnp.broadcast_to(
        k_r[:, None, :], (total, a.heads, a.rope))], axis=-1)
    heads_first = lambda t: jnp.transpose(t, (1, 0, 2))  # noqa: E731
    kept = jnp.zeros((), jnp.int32)
    chosen, window, entry = None, None, {}
    if kind == FULL:
        with jax.named_scope("indexer"):
            qi, ki, w = _index_parts(p["indexer"], spec, xn, c_q, pos)
            if "no_indexer" not in faults:
                chosen = att.select_blocks(qi, ki, w, spec.index_topk,
                                           spec.q_block, spec.head_block,
                                           length=length)
                real = pos < length
                for i, block in enumerate(chosen):
                    r0 = i * spec.q_block
                    kept += jnp.sum(block & real[r0:r0 + block.shape[0], None],
                                    dtype=jnp.int32)
            else:
                kept = length * (length + 1) // 2
        pad = ((0, slots - total), (0, 0))
        entry = {"c_kv": jnp.pad(c_kv, pad), "k_r": jnp.pad(k_r, pad),
                 "k_i": jnp.pad(ki, pad)}
    else:
        window = spec.window + ("window_off_by_one" in faults)
        held = att.ring_positions(length[None] - 1, spec.window)[0]
        take = lambda t: jnp.where(  # noqa: E731
            held[:, None] >= 0, t[jnp.clip(held, 0, total - 1)], 0)
        entry = {"c_kv": take(c_kv), "k_r": take(k_r)}
    o = att.blockwise_attention(
        heads_first(q), heads_first(k), heads_first(kv[..., a.nope:]),
        q_block=spec.q_block, window=window, chosen=chosen, length=length,
        head_block=spec.head_block, scale=1.0 / math.sqrt(a.nope + a.rope))
    ran = sum(jnp.asarray(live, jnp.int32)
              for live in att.q_blocks_live(total, spec.q_block, length))
    return _gated_output(p, xn, heads_first(o)), entry, kept, ran


def attention_step(p, kind: str, spec: DecoderSpec, x, pos, entry,
                   faults=frozenset()):
    """One token a row. x (B, hidden) raw, pos (B,) its position, `entry` the
    layer's cache. Returns (attention output (B, hidden), the cache with the
    token written, keys kept (B,) (0 for a sliding layer))."""
    a = spec.attn(kind)
    rows = jnp.arange(x.shape[0])
    xn = att.rms_norm(x, p["attn_norm"], spec.eps)
    c_q, q = _queries(p, a, spec, xn)
    q_rope = att.rotate(q[..., a.nope:], pos, a.theta)
    c_kv, k_r = _latents(p, a, spec, xn, pos)
    w_ukv = p["w_ukv"].reshape(a.kv_rank, a.heads, a.nope + a.v)
    q_lat = jnp.einsum("bhd,rhd->bhr", q[..., :a.nope], w_ukv[..., :a.nope])
    kept = jnp.zeros(x.shape[:1], jnp.int32)
    if kind == FULL:
        qi, ki, w = _index_parts(p["indexer"], spec, xn, c_q, pos)
        entry = {"c_kv": entry["c_kv"].at[rows, pos].set(c_kv),
                 "k_r": entry["k_r"].at[rows, pos].set(k_r),
                 "k_i": entry["k_i"].at[rows, pos].set(ki)}
        slots = entry["c_kv"].shape[1]
        allowed = jnp.arange(slots, dtype=jnp.int32)[None, :] <= pos[:, None]
        if "no_indexer" not in faults:
            with jax.named_scope("indexer"):
                scores = att.index_scores(qi[:, None], entry["k_i"],
                                          w[:, None])[:, 0]
                allowed &= att.top_k_mask(
                    jnp.where(allowed, scores, -jnp.inf), spec.index_topk)
        kept = jnp.sum(allowed, axis=-1, dtype=jnp.int32)
    else:
        slot = jnp.mod(pos, spec.window)
        if "stale_ring_row" in faults:  # the write of every 7th slot is lost
            slot = jnp.where(slot % 7 == 3, (slot + 1) % spec.window, slot)
        entry = {"c_kv": entry["c_kv"].at[rows, slot].set(c_kv),
                 "k_r": entry["k_r"].at[rows, slot].set(k_r)}
        allowed = att.ring_positions(pos, spec.window) >= 0
    o_lat = att.latent_cache_attention(
        q_lat, q_rope, entry["c_kv"], entry["k_r"], allowed,
        1.0 / math.sqrt(a.nope + a.rope))
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_ukv[..., a.nope:])
    return _gated_output(p, xn, o), entry, kept


def expert_layer(p, spec: DecoderSpec, hn, token_real, faults=frozenset()):
    """hn (T, hidden) normed -> (y (T, hidden): the held experts' part plus
    the shared expert, local (T, k): each pair's held expert or `held`)."""
    with jax.named_scope("router"):
        bias = p["b_select"] * (0.0 if "no_select_bias" in faults else 1.0)
        idx, weights = moe.route(hn, p["w_router"], bias, spec.per_token,
                                 spec.norm_weights, spec.routed_scale)
    with jax.named_scope("experts"):
        y, local = moe.routed_experts(hn, idx, weights, token_real,
                                      p["w_gate_up"], p["w_down"], spec.share)
    if spec.shared and "no_shared" not in faults:
        with jax.named_scope("shared_expert"):
            y = y + moe.swiglu(hn, p["shared_gate_up"], p["shared_down"])
    return y, local


def _pairs_by_expert(local, real, held: int):
    """local (B, T, k), real (B, T) -> (B, held) int32: pairs of real tokens
    routed to each held expert."""
    hit = (local[..., None] == jnp.arange(held, dtype=jnp.int32)) \
        & real[..., None, None]
    return jnp.sum(hit, axis=(1, 2), dtype=jnp.int32)


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
    """Both latent attentions: `kind` picks the size set, the window or the
    indexer. `__call__` returns the arrays; the arithmetic is
    `attention_prefill_row` / `attention_step`."""
    spec: DecoderSpec
    kind: str
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        s, a, d = self.spec, self.spec.attn(self.kind), self.dtype
        p = {
            "w_dq": self.param("w_dq", _INIT, (s.hidden, a.q_rank), d),
            "q_norm": self.param("q_norm", _scale_init, (a.q_rank,), d),
            "w_uq": self.param("w_uq", _INIT,
                               (a.q_rank, a.heads * (a.nope + a.rope)), d),
            "w_dkv": self.param("w_dkv", _INIT,
                                (s.hidden, a.kv_rank + a.rope), d),
            "kv_norm": self.param("kv_norm", _scale_init, (a.kv_rank,), d),
            "w_ukv": self.param("w_ukv", _INIT,
                                (a.kv_rank, a.heads * (a.nope + a.v)), d),
            "w_g": self.param("w_g", _INIT, (s.hidden, a.heads), d),
            "w_o": self.param("w_o", _INIT, (a.heads * a.v, s.hidden), d)}
        if self.kind == FULL:
            p["indexer"] = Indexer(s, d, name="indexer")()
        return p


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
        f, fs, held = s.expert_width, s.expert_width * s.shared, s.share.held
        return {
            "w_router": self.param("w_router", _INIT,
                                   (s.hidden, s.share.n_routed), d),
            "b_select": self.param("b_select", nn.initializers.zeros,
                                   (s.share.n_routed,), jnp.float32),
            "w_gate_up": self.param("w_gate_up", _INIT,
                                    (held, s.hidden, 2 * f), d),
            "w_down": self.param("w_down", _INIT, (held, f, s.hidden), d),
            "shared_gate_up": self.param("shared_gate_up", _INIT,
                                         (s.hidden, 2 * fs), d),
            "shared_down": self.param("shared_down", _INIT,
                                      (fs, s.hidden), d)}


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
        p = {"attn": dict(LatentAttention(s, self.kind, d, name="attn")(),
                          attn_norm=self.param("attn_norm", _scale_init,
                                               (s.hidden,), d)),
             "ffn_norm": self.param("ffn_norm", _scale_init, (s.hidden,), d)}
        if self.dense:
            p["ffn"] = DenseFFN(s, d, name="ffn")()
        else:
            p["moe"] = ExpertLayer(s, d, name="moe")()
        return p


def _attn_scope(kind: str) -> str:
    return "attn_full" if kind == FULL else "attn_window"


def _feed_forward(p, spec: DecoderSpec, dense: bool, x, real, faults):
    """x (B, T, hidden) after attention -> (x out, local (B, T, k) or None)."""
    hn = att.rms_norm(x, p["ffn_norm"], spec.eps)
    if dense:
        ffn = lambda h: moe.swiglu(h, p["ffn"]["w_gate_up"],  # noqa: E731
                                   p["ffn"]["w_down"])
        with jax.named_scope("dense_ffn"):
            # prefill a row at a time: the gate and up projections of a
            # whole batch of prompts would stand as gigabytes
            y = lax.map(ffn, hn) if x.shape[1] > 1 else ffn(hn)
        return x + y, None
    flat = hn.reshape(-1, spec.hidden)
    y, local = expert_layer(p["moe"], spec, flat, real.reshape(-1), faults)
    return x + y.reshape(x.shape), local.reshape(x.shape[:2] + (-1,))


class LatentMoEDecoder(nn.Module):
    """`prefill` and `step`; `__call__` (what `init` runs) is a prefill of
    the tokens given, all real."""
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

    def prefill(self, tokens, lengths, reserve: int = 0):
        """tokens int32 (B, P), lengths int32 (B,) -> (float32 logits (B,
        vocab) at each row's last real token, cache with `reserve` more
        slots than P in the full layers)."""
        s = self.spec
        rows, total = tokens.shape
        slots = total + reserve
        lengths = jnp.clip(lengths, 1, total)
        real = jnp.arange(total, dtype=jnp.int32)[None, :] < lengths[:, None]
        params = [block() for block in self.layer]
        entries, pairs = [], []
        kept = jnp.zeros((rows,), jnp.int32)
        ran = jnp.zeros((rows,), jnp.int32)
        with jax.named_scope("prefill"):
            with jax.named_scope("embed"):
                x = self.embed[tokens]
            for i, p in enumerate(params):
                kind = s.kinds[i]
                with jax.named_scope(_attn_scope(kind)):
                    out, entry, k, r = lax.map(
                        lambda xl, p=p, kind=kind: attention_prefill_row(
                            p["attn"], kind, s, xl[0], xl[1], slots,
                            self.faults), (x, lengths))
                x = x + out
                entries.append(entry)
                kept += k
                ran += r
                x, local = _feed_forward(p, s, i < s.dense_layers, x, real,
                                         self.faults)
                if local is not None:
                    pairs.append(_pairs_by_expert(local, real, s.share.held))
            last = x[jnp.arange(rows), lengths - 1]
            logits = self._logits(last)
        counts = {"expert_tokens": jnp.stack(pairs, axis=1) if pairs else
                  jnp.zeros((rows, 0, s.share.held), jnp.int32),
                  "keys_kept": kept,
                  "keys_causal": s.full_layers * lengths * (lengths + 1) // 2,
                  "q_blocks_run": ran,
                  "q_blocks_total": jnp.full(
                      (rows,), s.layers * -(-total // s.q_block), jnp.int32)}
        return logits, {"layers": tuple(entries), "counts": counts}

    def step(self, token, positions, cache):
        """token int32 (B,) at `positions` (B,) -> (float32 logits (B,
        vocab), the cache with the token written and counted)."""
        s = self.spec
        params = [block() for block in self.layer]
        entries, pairs = [], []
        counts = cache["counts"]
        kept = counts["keys_kept"]
        real = jnp.ones(token.shape + (1,), bool)
        with jax.named_scope("decode"):
            with jax.named_scope("embed"):
                x = self.embed[token]
            for i, p in enumerate(params):
                kind = s.kinds[i]
                with jax.named_scope(_attn_scope(kind)):
                    out, entry, k = attention_step(
                        p["attn"], kind, s, x, positions, cache["layers"][i],
                        self.faults)
                x = x + out
                entries.append(entry)
                kept = kept + k
                x, local = _feed_forward(p, s, i < s.dense_layers,
                                         x[:, None], real, self.faults)
                x = x[:, 0]
                if local is not None:
                    pairs.append(_pairs_by_expert(local, real, s.share.held))
            logits = self._logits(x)
        counts = {"expert_tokens": counts["expert_tokens"]
                  + (jnp.stack(pairs, axis=1) if pairs else 0),
                  "keys_kept": kept,
                  "keys_causal": counts["keys_causal"]
                  + s.full_layers * (positions + 1),
                  "q_blocks_run": counts["q_blocks_run"],
                  "q_blocks_total": counts["q_blocks_total"]}
        return logits, {"layers": tuple(entries), "counts": counts}


def build_decoder(cfg, dtype: Optional[jnp.dtype] = None) -> LatentMoEDecoder:
    """The decoder `cfg.decoder` describes (`models.build_model` dispatches
    here on `cfg.family`)."""
    if not getattr(cfg, "decoder", None):
        raise ValueError("family %r needs `decoder`: the source's config keys"
                         % FAMILY)
    return LatentMoEDecoder(DecoderSpec.from_mapping(cfg.decoder),
                            dtype or jnp.bfloat16)
