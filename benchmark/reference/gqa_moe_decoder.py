"""The plain reference of the third family: a decoder of grouped-query
attention layers (8 key/value heads shared by a number of query heads that
differs BY LAYER; full layers and layers behind a sliding window; two rotary
schemes in one model; a head-wise output gate), a leading dense SwiGLU layer
and sigmoid-routed experts with a shared expert, every expert held here
(`ep_size` 1) or one share of them.

jax.numpy, float32, `precision=HIGHEST`, no flax, no kernel, no cache: the full
forward pass, each sequence on its own (attention a sequence at a time; the
position-wise feed-forward takes the sequences' rows together, so that an
expert is drawn once). It reads the configuration's `fields` (the source's own
keys) and nothing of the program.

The equations (x: the residual stream, one row a position t; layer i of kind
kappa_i = layer_types[i], H_i = num_attention_heads_per_layer[i] query heads,
G = num_key_value_heads, d = head_dim, rho_i = H_i / G; no biases):

    xn = RMSNorm(x; w, eps);  q = xn W_q -> H_i x d;  k = xn W_k -> G x d;
    v = xn W_v -> G x d.
    Rotary on the first r dims of q and k (r = d * partial_rotary_factor of
    the kind's `rope_parameters`), the rest as they are; dimension j pairs
    with j + r/2; angle = t * f_j; cos and sin times m.
      rope_type default: f_j = theta^(-2j/r), m = 1.
      rope_type yarn (theta, factor s, original length L0, beta_fast,
      beta_slow): c(n) = r ln(L0 / (2 pi n)) / (2 ln theta);
      low = floor(c(beta_fast)), high = ceil(c(beta_slow));
      ramp_j = clip((j - low) / (high - low), 0, 1);
      f_j = theta^(-2j/r) ((1 - ramp_j) + ramp_j / s);  m = attention_factor.
      The frequencies do not depend on the sequence's length.
    Query head h reads k/v head floor(h / rho_i). Scores q_t . k_u / sqrt(d);
    allowed u <= t, and on a sliding layer t - u < sliding_window (the window
    counts the current token). o_h = softmax . v.
    gamma = sigmoid(xn W_g) -> H_i;  o_h <- gamma_h o_h;  x <- x + concat(o) W_o.
    hn = RMSNorm(x). mlp_layer_types[i] dense: SwiGLU of intermediate_size.
    sparse: scores s = sigmoid(hn W_r) over all the experts; the
    num_experts_per_tok largest of s + b are chosen (b: the selection bias, in
    the choice only); weights = chosen s / their sum x moe_routed_scaling_factor,
    on the experts' outputs; y = sum_{e chosen and held here} w_e SwiGLU_e(hn)
    + SwiGLU_shared(hn) (shared_expert_intermediate_size, no gate).
    x <- x + y.  logits = RMSNorm(x) W_head.

DEPARTURE RISKS: readings the source's config does not settle (the
configuration's file lists each under `assumed`): `gating: true` read as the
head-wise sigmoid gate from the layer's normed input before W_o; the router's
scoring read as sigmoid with a selection bias and normalised top-k weights
(the config gives the factor 2.5 and no scoring function); no norm on q or k
and no gate on the shared expert (the config names neither); the window
counts the current token; the rotary pairing (j with j + r/2). A model whose
code reads any of these otherwise computes another function than this file.

Parameters: `param_spec` lists them under the program's checkpoint paths;
`Drawn` draws each from the seed where it is used, layer by layer and expert
by expert (3.87 B parameters are 15.5 GB in float32: never whole);
`program_tree` draws the same values as the program's tree (bfloat16). The
draw, the precision controls (`quant`: f32 | bf16 | fp8) and the tree helpers
are the latent family's reference's own (`latent_moe_decoder.py`), reused.

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
from .latent_moe_decoder import (FULL, HIGHEST, SLIDING,  # noqa: F401
                                 check_tree, flatten_tree, rms_norm,
                                 token_gaps)


# ---- sizes -------------------------------------------------------------------

def sizes(cfg: dict) -> types.SimpleNamespace:
    """The configuration's `fields` under short names. `num_experts` is the
    number held here; the router's width is that times `ep_size`."""
    layers = int(cfg["num_hidden_layers"])
    held, ep = int(cfg["num_experts"]), int(cfg.get("ep_size", 1))
    return types.SimpleNamespace(
        hidden=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]),
        layers=layers, kinds=list(cfg["layer_types"])[:layers],
        heads=[int(h) for h in cfg["num_attention_heads_per_layer"]][:layers],
        groups=int(cfg["num_key_value_heads"]), dim=int(cfg["head_dim"]),
        dense=[m == "dense" for m in cfg["mlp_layer_types"]][:layers],
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        shared_width=int(cfg["shared_expert_intermediate_size"]),
        held=held, first_expert=int(cfg.get("ep_rank", 0)) * held,
        experts=held * ep, per_token=int(cfg["num_experts_per_tok"]),
        routed_scale=float(cfg["moe_routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), window=int(cfg["sliding_window"]),
        rope=cfg["rope_parameters"])


def rotary_table(params: dict, dim: int) -> Tuple[int, np.ndarray, float]:
    """(r, f (r/2,), m) of one kind's `rope_parameters`, as the docstring
    states them."""
    r = int(round(dim * float(params.get("partial_rotary_factor", 1))))
    theta = float(params["rope_theta"])
    j = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / r)
    if params.get("rope_type", "default") == "default":
        return r, f, 1.0
    if params["rope_type"] != "yarn":
        raise ValueError("rope_type %r" % (params["rope_type"],))
    s, span = float(params["factor"]), float(
        params["original_max_position_embeddings"])
    c = lambda n: r * math.log(span / (2 * math.pi * n)) / (  # noqa: E731
        2 * math.log(theta))
    low = max(math.floor(c(float(params["beta_fast"]))), 0)
    high = min(math.ceil(c(float(params["beta_slow"]))), r - 1)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    m = params.get("attention_factor") or 0.1 * math.log(s) + 1.0
    return r, f * ((1 - ramp) + ramp / s), float(m)


# ---- parameters --------------------------------------------------------------

def param_spec(cfg: dict) -> Dict[str, Tuple[tuple, str]]:
    """{path: (shape, kind)}; the kinds are the latent reference's (`matrix`
    N(0, 0.02), `scale` 1 + N(0, 0.02), `select` float32 N(0, 0.004),
    `experts`: leading axis the experts held, each drawn under its id in the
    whole model, so that every share draws the same expert)."""
    z = sizes(cfg)
    d, spec = z.hidden, {}
    spec["embed"] = ((z.vocab, d), "matrix")
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, z.vocab), "matrix")
    kv = z.groups * z.dim
    for i in range(z.layers):
        p = "layer_%d/" % i
        spec[p + "attn_norm"] = ((d,), "scale")
        spec[p + "ffn_norm"] = ((d,), "scale")
        for name, shape in (("w_q", (d, z.heads[i] * z.dim)),
                            ("w_k", (d, kv)), ("w_v", (d, kv)),
                            ("w_g", (d, z.heads[i])),
                            ("w_o", (z.heads[i] * z.dim, d))):
            spec[p + "attn/" + name] = (shape, "matrix")
        if z.dense[i]:
            spec[p + "ffn/w_gate_up"] = ((d, 2 * z.dense_width), "matrix")
            spec[p + "ffn/w_down"] = ((z.dense_width, d), "matrix")
        else:
            f, fs = z.expert_width, z.shared_width
            spec[p + "moe/w_router"] = ((d, z.experts), "matrix")
            spec[p + "moe/b_select"] = ((z.experts,), "select")
            spec[p + "moe/w_gate_up"] = ((z.held, d, 2 * f), "experts")
            spec[p + "moe/w_down"] = ((z.held, f, d), "experts")
            spec[p + "moe/shared_gate_up"] = ((d, 2 * fs), "matrix")
            spec[p + "moe/shared_down"] = ((fs, d), "matrix")
    return spec


class Drawn(base.Drawn):
    """The latent reference's draw over this family's list."""

    def __init__(self, cfg: dict, seed: int):
        self.spec = param_spec(cfg)
        self.index = {p: i for i, p in enumerate(sorted(self.spec))}
        self.key = base.seed_key(seed)


class Held(base.Held):
    def __init__(self, cfg: dict, flat: dict):
        self.flat, self.first = flat, sizes(cfg).first_expert


def program_tree(cfg: dict, seed: int) -> dict:
    """{'params': nested} of the program: every leaf as `Drawn` gives it, in
    the program's types (bfloat16; the selection bias float32), one jitted
    draw a leaf."""
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

def rotate(x, pos, r: int, f, m: float):
    """x (n, heads, d) at positions `pos` (n,): the first r dims rotated,
    dimension j with j + r/2, angle pos * f[j], cos and sin times m."""
    half = r // 2
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(
        f, jnp.float32)
    cos, sin = m * jnp.cos(ang), m * jnp.sin(ang)
    a, b = x[..., :half], x[..., half:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


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
        kind, heads, p = z.kinds[i], z.heads[i], "layer_%d/attn/" % i
        n, ratio = x.shape[0], z.heads[i] // z.groups
        pos = jnp.arange(n)
        xn = rms_norm(x, w.get("layer_%d/attn_norm" % i), z.eps)
        r, f, m = rotary_table(z.rope[kind], z.dim)
        q = rotate(self.mm(xn, w.get(p + "w_q")).reshape(n, heads, z.dim),
                   pos, r, f, m)
        k = rotate(self.mm(xn, w.get(p + "w_k")).reshape(n, z.groups, z.dim),
                   pos, r, f, m)
        v = self.mm(xn, w.get(p + "w_v")).reshape(n, z.groups, z.dim)
        t, s = pos[:, None], pos[None, :]
        allowed = s <= t
        if kind == SLIDING:
            allowed &= (t - s) < z.window

        def head(h):
            g = h // ratio
            sc = self.mm(q[:, h], k[:, g].T) / math.sqrt(z.dim)
            prob = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
            return self.mm(prob, v[:, g])
        out = lax.map(head, jnp.arange(heads))            # (heads, n, d)
        gate = jax.nn.sigmoid(self.mm(xn, w.get(p + "w_g")))  # (n, heads)
        out = jnp.transpose(out, (1, 0, 2)) * gate[..., None]
        return self.mm(out.reshape(n, heads * z.dim), w.get(p + "w_o"))

    def swiglu(self, x, w_gate_up, w_down):
        g, u = jnp.split(self.mm(x, w_gate_up), 2, axis=-1)
        return self.mm(jax.nn.silu(g) * u, w_down)

    def route(self, i, hn):
        """(weights (n, experts) with zeros off the choice, chosen bool)."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        s = jax.nn.sigmoid(self.mm(hn, w.get(p + "w_router")))
        _, idx = lax.top_k(s + w.get(p + "b_select"), z.per_token)
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], idx].set(True)
        wt = jnp.where(chosen, s, 0.0)
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
        return wt * z.routed_scale, chosen

    def experts(self, i, hn):
        """(the held experts' part, the shared expert's part, chosen (n,
        experts)): each chosen and held expert's SwiGLU over the rows,
        weighted (zero off the choice), an expert at a time."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        wt, chosen = self.route(i, hn)

        def one(total, e):
            y = self.swiglu(hn, w.expert(p + "w_gate_up", e),
                            w.expert(p + "w_down", e))
            return total + wt[:, e, None] * y, None
        routed, _ = lax.scan(one, jnp.zeros_like(hn), jnp.arange(
            z.first_expert, z.first_expert + z.held))
        shared = self.swiglu(hn, w.get(p + "shared_gate_up"),
                             w.get(p + "shared_down"))
        return routed, shared, chosen

    def layer(self, i, x):
        """x (sequences, n, hidden) -> (x out, chosen (sequences, n, held)
        bool: the held experts each row chose, all False on a dense layer).
        Attention a sequence at a time; the feed-forward is position-wise,
        so the sequences' rows go through it together and each expert is
        drawn once for all of them (the draw, not the arithmetic, is most of
        this reference's time on the chip: PERF.md section 6, PR 33)."""
        z = self.z
        x = x + lax.map(lambda one: self.attention(i, one), x)
        hn = rms_norm(x, self.w.get("layer_%d/ffn_norm" % i), z.eps)
        rows = hn.reshape(-1, z.hidden)
        if z.dense[i]:
            p = "layer_%d/ffn/" % i
            y = self.swiglu(rows, self.w.get(p + "w_gate_up"),
                            self.w.get(p + "w_down"))
            return x + y.reshape(x.shape), jnp.zeros(
                x.shape[:2] + (z.held,), bool)
        routed, shared, chosen = self.experts(i, rows)
        held = chosen[:, z.first_expert:z.first_expert + z.held]
        return (x + (routed + shared).reshape(x.shape),
                held.reshape(x.shape[:2] + (z.held,)))

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
        (expert layers, held): their count} as numpy."""
        z, w = self.z, self.w
        tokens, at = jnp.asarray(tokens, jnp.int32), jnp.asarray(at)
        x = self._jit("embed", lambda t: w.get("embed")[t])(tokens)
        chosen = []
        for i in range(z.layers):
            x, c = self._jit("layer_%d" % i,
                             lambda x, i=i: self.layer(i, x))(x)
            if not z.dense[i]:
                chosen.append(c)
        logits = self._jit("head", lambda x, at: self.mm(
            rms_norm(jnp.take_along_axis(x, at[..., None], axis=1),
                     w.get("final_norm"), z.eps), w.get("lm_head")))(x, at)
        logits, chosen = jax.device_get((logits, jnp.stack(chosen, axis=1)))
        out = []
        for row, picks, n in zip(logits, chosen, n_real):
            picks = picks[:, :int(n)]
            out.append({"logits": row, "chosen": picks,
                        "expert_pairs": picks.sum(axis=1, dtype=np.int64)})
        return out
