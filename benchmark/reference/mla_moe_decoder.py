"""The plain reference of a PLAIN latent-attention decoder: latent attention
(MLA) on every layer with no indexer, no window, no output gate and no rank
rescale, YaRN on the rotary part, a leading dense SwiGLU layer, and
sigmoid-routed experts whose choice is limited by routing groups, plus one
shared expert; one chip's share of an expert-parallel deployment (a share
holds whole routing groups, or a whole fraction of one).

jax.numpy, float32, `precision=HIGHEST`, no flax, no kernel, no cache: the
full forward pass, each sequence on its own (attention a sequence at a time;
the position-wise feed-forward takes the sequences' rows together, so that an
expert is drawn once). It reads the configuration's `fields` (the source's own
keys) and nothing of the program.

The equations (x: the residual stream, one row a position t; no biases; H =
num_attention_heads, r_q = q_lora_rank, r = kv_lora_rank, d_n / d_r / d_v =
qk_nope_head_dim / qk_rope_head_dim / v_head_dim):

    xn = RMSNorm(x; w, eps).
    c_q = RMSNorm(xn W_dq; w_q) in R^r_q (no rank rescale);
    q = c_q W_uq -> H x (d_n + d_r), split q_n | q_r.
    [c | k_r] = xn W_dkv in R^(r + d_r);  c_kv = RMSNorm(c; w_kv);
    [k_n | v] = c_kv W_ukv -> H x (d_n + d_v).  k_r is ONE vector a token,
    shared by every head.
    Rotary on q_r and k_r: dimension j pairs with j + d_r/2, angle t * f_j;
      rope_scaling null: f_j = theta^(-2j/d_r), nothing scaled;
      rope_scaling yarn (factor s, original length L0, beta_fast, beta_slow,
      mscale, mscale_all_dim): c(n) = d_r ln(L0 / (2 pi n)) / (2 ln theta);
      low = floor(c(beta_fast)), high = ceil(c(beta_slow));
      ramp_j = clip((j - low) / (high - low), 0, 1);
      f_j = theta^(-2j/d_r) ((1 - ramp_j) + ramp_j / s);
      m(x) = 0.1 x ln s + 1; cos and sin times m(mscale) / m(mscale_all_dim);
      EVERY score (nope and rope parts alike) times m(mscale_all_dim)^2.
    score[t, u] = (q_n . k_n + q_r . k_r) (d_n + d_r)^(-1/2) m^2, allowed
    u <= t; o_h = softmax . v;  x <- x + concat_h(o_h) W_o.
    (A decode step through the cache computes the same numbers with W_ukv
    absorbed: q_n W_uk against the cached c_kv, the weighted latents through
    W_uv. That is the program's business; this file has no cache.)
    hn = RMSNorm(x). Layer < first_k_dense_replace: SwiGLU of
    intermediate_size. Else: s = sigmoid(hn W_r) over all n_routed experts.
    Groups g = 0 .. n_group - 1 of n_routed / n_group consecutive experts;
    G_g = the sum of the two largest s in group g; the topk_group groups of
    largest G_g are kept (ties to the lower index); the num_experts_per_tok
    largest s among the kept groups' experts are chosen; weights = chosen s /
    their sum (norm_topk_prob) x routed_scaling_factor, on the experts'
    outputs; y = sum_{e chosen and held here} w_e SwiGLU_e(hn)
    + SwiGLU_shared(hn) (moe_intermediate_size x n_shared_experts).
    x <- x + y.  logits = RMSNorm(x) W_head over the vocabulary rows held.

DEPARTURE RISKS: readings the source's config does not settle (the
configuration's file lists each under `assumed`): `topk_method` "none" read
as NO selection bias (the choice is on the scores themselves) WITH the group
limit that `n_group` / `topk_group` state, a group's score the sum of its two
best; the rotary pairing (j with j + d_r/2); `rope_scaling` in the family's
convention as above. A model whose code reads any of these otherwise computes
another function than this file.

Parameters: `param_spec` lists them under the program's checkpoint paths;
`Drawn` draws each from the seed where it is used, layer by layer and expert
by expert (5.6 B parameters are 22 GB in float32: never whole);
`program_tree` draws the same values as the program's tree (bfloat16). The
draw, the precision controls (`quant`: f32 | bf16 | fp8) and the tree helpers
are the first latent family's reference's own (`latent_moe_decoder.py`),
reused.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import math
import types
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import latent_moe_decoder as base
from .latent_moe_decoder import (HIGHEST, check_tree,  # noqa: F401
                                 flatten_tree, rms_norm, token_gaps)


# ---- sizes -------------------------------------------------------------------

def sizes(cfg: dict) -> types.SimpleNamespace:
    """The configuration's `fields` under short names. `n_routed_experts` is
    the number held here; the router's width is that times `ep_size`."""
    held, ep = int(cfg["n_routed_experts"]), int(cfg.get("ep_size", 1))
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    first = int(cfg.get("ep_rank", 0)) * held
    n_group = int(cfg.get("n_group") or 0)
    z = types.SimpleNamespace(
        hidden=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        shared_width=int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        held=held, first_expert=first, experts=held * ep,
        per_token=int(cfg["num_experts_per_tok"]),
        norm_weights=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_group=n_group, topk_group=int(cfg.get("topk_group") or 0),
        eps=float(cfg["rms_norm_eps"]),
        heads=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=nope, rope=rope, v=int(cfg["v_head_dim"]),
        theta=float(cfg["rope_theta"]), scaling=cfg.get("rope_scaling"))
    # the routing groups this share holds experts of
    if n_group:
        size = z.experts // n_group
        z.my_groups = list(range(first // size,
                                 (first + held - 1) // size + 1))
    return z


def rotary(z) -> Tuple[np.ndarray, float, float]:
    """(f (d_r/2,), the scale on cos and sin, the multiplier of every score)
    as the docstring states them."""
    j = np.arange(z.rope // 2, dtype=np.float64)
    f = z.theta ** (-2.0 * j / z.rope)
    sc = z.scaling
    if not sc:
        return f, 1.0, 1.0
    if sc.get("type") != "yarn":
        raise ValueError("rope_scaling type %r" % (sc.get("type"),))
    s, span = float(sc["factor"]), float(
        sc["original_max_position_embeddings"])
    c = lambda n: z.rope * math.log(span / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(z.theta))
    low = max(math.floor(c(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(c(float(sc["beta_slow"]))), z.rope - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)

    def m(x):
        return 0.1 * float(x) * math.log(s) + 1.0 if s > 1 else 1.0
    all_dim = sc.get("mscale_all_dim", 0)
    return (f * ((1 - ramp) + ramp / s), m(sc.get("mscale", 1)) / m(all_dim),
            m(all_dim) ** 2 if all_dim else 1.0)


def score_scale(z) -> float:
    return rotary(z)[2] / math.sqrt(z.nope + z.rope)


# ---- parameters --------------------------------------------------------------

def param_spec(cfg: dict) -> Dict[str, Tuple[tuple, str]]:
    """{path: (shape, kind)}; the kinds are the first latent reference's
    (`matrix` N(0, 0.02), `scale` 1 + N(0, 0.02), `experts`: leading axis the
    experts held, each drawn under its id in the whole model, so that every
    share draws the same expert). No gate, no indexer, no selection bias."""
    z = sizes(cfg)
    d, spec = z.hidden, {}
    spec["embed"] = ((z.vocab, d), "matrix")
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, z.vocab), "matrix")
    for i in range(z.layers):
        p = "layer_%d/" % i
        spec[p + "attn_norm"] = ((d,), "scale")
        spec[p + "ffn_norm"] = ((d,), "scale")
        for name, shape, k in (
                ("w_dq", (d, z.q_rank), "matrix"),
                ("q_norm", (z.q_rank,), "scale"),
                ("w_uq", (z.q_rank, z.heads * (z.nope + z.rope)), "matrix"),
                ("w_dkv", (d, z.kv_rank + z.rope), "matrix"),
                ("kv_norm", (z.kv_rank,), "scale"),
                ("w_ukv", (z.kv_rank, z.heads * (z.nope + z.v)), "matrix"),
                ("w_o", (z.heads * z.v, d), "matrix")):
            spec[p + "attn/" + name] = (shape, k)
        if i < z.dense_layers:
            spec[p + "ffn/w_gate_up"] = ((d, 2 * z.dense_width), "matrix")
            spec[p + "ffn/w_down"] = ((z.dense_width, d), "matrix")
        else:
            f, fs = z.expert_width, z.shared_width
            spec[p + "moe/w_router"] = ((d, z.experts), "matrix")
            spec[p + "moe/w_gate_up"] = ((z.held, d, 2 * f), "experts")
            spec[p + "moe/w_down"] = ((z.held, f, d), "experts")
            spec[p + "moe/shared_gate_up"] = ((d, 2 * fs), "matrix")
            spec[p + "moe/shared_down"] = ((fs, d), "matrix")
    return spec


class Drawn(base.Drawn):
    """The first latent reference's draw over this family's list."""

    def __init__(self, cfg: dict, seed: int):
        self.spec = param_spec(cfg)
        self.index = {p: i for i, p in enumerate(sorted(self.spec))}
        self.key = base.seed_key(seed)


class Held(base.Held):
    def __init__(self, cfg: dict, flat: dict):
        self.flat, self.first = flat, sizes(cfg).first_expert


def program_tree(cfg: dict, seed: int) -> dict:
    """{'params': nested} of the program: every leaf as `Drawn` gives it, in
    the program's type (bfloat16), one jitted draw a leaf."""
    drawn, z = Drawn(cfg, seed), sizes(cfg)
    ids = jnp.arange(z.first_expert, z.first_expert + z.held)
    tree: dict = {}
    for path, (shape, kind) in drawn.spec.items():
        key = drawn._leaf_key(path)
        leaf = (base._program_experts(key, ids, tuple(shape[1:]))
                if kind == "experts" else
                base._program_leaf(key, tuple(shape), kind))
        node = tree
        *parents, name = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[name] = leaf
    return {"params": tree}


# ---- arithmetic ----------------------------------------------------------------

def rotate(x, pos, f, m: float):
    """x (n, ..., d) at positions `pos` (n,): dimension j with j + d/2, angle
    pos * f[j], cos and sin times m."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(f, jnp.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    cos, sin = m * jnp.cos(ang), m * jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class Reference:
    """The forward pass, a jitted function a layer (compiled once, used for
    every sequence of the same padded length). `weights`: a `Drawn` or a
    `Held`."""

    def __init__(self, cfg: dict, weights, quant: str = "f32"):
        self.z, self.w = sizes(cfg), weights
        q = base.quantizer(quant)
        self.q = q if q is not None else (lambda x: x)
        self._jitted: dict = {}

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def attention(self, i, x):
        """x (n, hidden) -> the layer's attention output (n, hidden)."""
        z, w = self.z, self.w
        p = "layer_%d/attn/" % i
        n = x.shape[0]
        pos = jnp.arange(n)
        f, m, _ = rotary(z)
        xn = rms_norm(x, w.get("layer_%d/attn_norm" % i), z.eps)
        c_q = rms_norm(self.mm(xn, w.get(p + "w_dq")), w.get(p + "q_norm"),
                       z.eps)
        q = self.mm(c_q, w.get(p + "w_uq")).reshape(n, z.heads,
                                                    z.nope + z.rope)
        q_n, q_r = q[..., :z.nope], rotate(q[..., z.nope:], pos, f, m)
        ckr = self.mm(xn, w.get(p + "w_dkv"))
        c_kv = rms_norm(ckr[:, :z.kv_rank], w.get(p + "kv_norm"), z.eps)
        k_r = rotate(ckr[:, z.kv_rank:], pos, f, m)
        kv = self.mm(c_kv, w.get(p + "w_ukv")).reshape(n, z.heads,
                                                       z.nope + z.v)
        k_n, v = kv[..., :z.nope], kv[..., z.nope:]
        allowed = pos[None, :] <= pos[:, None]
        scale = score_scale(z)

        def head(h):
            sc = (self.mm(q_n[:, h], k_n[:, h].T)
                  + self.mm(q_r[:, h], k_r.T)) * scale
            prob = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
            return self.mm(prob, v[:, h])
        out = lax.map(head, jnp.arange(z.heads))            # (heads, n, v)
        out = jnp.transpose(out, (1, 0, 2)).reshape(n, z.heads * z.v)
        return self.mm(out, w.get(p + "w_o"))

    def swiglu(self, x, w_gate_up, w_down):
        g, u = jnp.split(self.mm(x, w_gate_up), 2, axis=-1)
        return self.mm(jax.nn.silu(g) * u, w_down)

    def kept_groups(self, s):
        """s (n, experts) -> bool (n, n_group): the topk_group groups whose
        two best scores sum highest, ties to the lower index."""
        z = self.z
        by_group = s.reshape(s.shape[0], z.n_group, -1)
        score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
        best = jnp.argsort(-score, axis=-1, stable=True)[:, :z.topk_group]
        return jnp.zeros(score.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], best].set(True)

    def route(self, i, hn):
        """(weights (n, experts) with zeros off the choice, chosen bool (n,
        experts), kept (n, n_group) bool: every group where the router knows
        none)."""
        z, w = self.z, self.w
        s = jax.nn.sigmoid(self.mm(hn, w.get("layer_%d/moe/w_router" % i)))
        kept = jnp.ones((s.shape[0], max(z.n_group, 1)), bool)
        if z.n_group:
            kept = self.kept_groups(s)
        open_to = jnp.repeat(kept, z.experts // kept.shape[1], axis=-1)
        _, idx = lax.top_k(jnp.where(open_to, s, -jnp.inf), z.per_token)
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], idx].set(True)
        wt = jnp.where(chosen, s, 0.0)
        if z.norm_weights:
            wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
        return wt * z.routed_scale, chosen, kept

    def experts(self, i, hn):
        """(the held experts' part, the shared expert's part, chosen (n,
        experts), kept (n, groups)): each chosen and held expert's SwiGLU
        over the rows, weighted (zero off the choice), an expert at a
        time."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        wt, chosen, kept = self.route(i, hn)

        def one(total, e):
            y = self.swiglu(hn, w.expert(p + "w_gate_up", e),
                            w.expert(p + "w_down", e))
            return total + wt[:, e, None] * y, None
        routed, _ = lax.scan(one, jnp.zeros_like(hn), jnp.arange(
            z.first_expert, z.first_expert + z.held))
        shared = jnp.zeros_like(hn)
        if z.shared_width:
            shared = self.swiglu(hn, w.get(p + "shared_gate_up"),
                                 w.get(p + "shared_down"))
        return routed, shared, chosen, kept

    def layer(self, i, x):
        """x (sequences, n, hidden) -> (x out, chosen (sequences, n, held)
        bool: the held experts each row chose, hit (sequences, n) bool: a
        group this share holds was among the groups the row kept; all False
        on a dense layer or without groups). Attention a sequence at a time;
        the feed-forward is position-wise, so the sequences' rows go through
        it together and each expert is drawn once for all of them."""
        z = self.z
        x = x + lax.map(lambda one: self.attention(i, one), x)
        hn = rms_norm(x, self.w.get("layer_%d/ffn_norm" % i), z.eps)
        rows = hn.reshape(-1, z.hidden)
        if i < z.dense_layers:
            p = "layer_%d/ffn/" % i
            y = self.swiglu(rows, self.w.get(p + "w_gate_up"),
                            self.w.get(p + "w_down"))
            return (x + y.reshape(x.shape),
                    jnp.zeros(x.shape[:2] + (z.held,), bool),
                    jnp.zeros(x.shape[:2], bool))
        routed, shared, chosen, kept = self.experts(i, rows)
        held = chosen[:, z.first_expert:z.first_expert + z.held]
        hit = jnp.zeros(rows.shape[:1], bool)
        if z.n_group:
            hit = jnp.any(kept[:, jnp.asarray(z.my_groups)], axis=-1)
        return (x + (routed + shared).reshape(x.shape),
                held.reshape(x.shape[:2] + (z.held,)),
                hit.reshape(x.shape[:2]))

    def _jit(self, name, fn):
        if name not in self._jitted:
            self._jitted[name] = jax.jit(fn)
        return self._jitted[name]

    def forward(self, tokens, n_real, at) -> list:
        """tokens int32 (sequences, n), each sequence on its own (rows from
        its `n_real` on are padding: causal, so they change nothing before
        them); `at` (sequences, m): the positions whose logits are wanted.
        Returns, a sequence, {'logits' (m, vocab), 'chosen' (expert layers,
        n_real, held) bool: each real row's held experts, 'expert_pairs'
        (expert layers, held): their count, 'group_hits': the (real row,
        expert layer) slots where a group held here was kept} as numpy."""
        z, w = self.z, self.w
        tokens, at = jnp.asarray(tokens, jnp.int32), jnp.asarray(at)
        x = self._jit("embed", lambda t: w.get("embed")[t])(tokens)
        chosen, hits = [], []
        for i in range(z.layers):
            x, c, h = self._jit("layer_%d" % i,
                                lambda x, i=i: self.layer(i, x))(x)
            if i >= z.dense_layers:
                chosen.append(c)
                hits.append(h)
        logits = self._jit("head", lambda x, at: self.mm(
            rms_norm(jnp.take_along_axis(x, at[..., None], axis=1),
                     w.get("final_norm"), z.eps), w.get("lm_head")))(x, at)
        logits, chosen, hits = jax.device_get(
            (logits, jnp.stack(chosen, axis=1), jnp.stack(hits, axis=1)))
        out = []
        for row, picks, hit, n in zip(logits, chosen, hits, n_real):
            picks = picks[:, :int(n)]
            out.append({"logits": row, "chosen": picks,
                        "expert_pairs": picks.sum(axis=1, dtype=np.int64),
                        "group_hits": int(hit[:, :int(n)].sum())})
        return out
