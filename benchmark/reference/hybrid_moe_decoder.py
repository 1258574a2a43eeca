"""The plain reference of a LINEAR-HYBRID decoder: KDA linear attention (a
gated delta rule with a decay a channel) on most layers, latent attention
(MLA) on every `layer_group_size`-th, leading dense SwiGLU layers, and
sigmoid-routed experts (a selection bias, a choice limited by routing
groups) plus one shared expert; one chip's share of an expert-parallel
deployment (a share holds whole routing groups).

jax.numpy, float32, `precision=HIGHEST`, no flax, no kernel, no cache, no
chunks: the full forward pass, KDA as its token-by-token recurrence, MLA
unabsorbed over every causal key. It reads the configuration's `fields` (the
source's own keys) and nothing of the program.

The equations (x: the residual stream, one row a position t; no biases; eps
= rms_norm_eps):

    layer i is MLA where (i + 1) % layer_group_size == 0, else KDA.
    xn = RMSNorm(x; w_attn).
    KDA (H heads of d = head_dim keys and values, K = short_conv_kernel_size):
      u_t = [xn W_q | xn W_k | xn W_v] (3 H d);
      c_t = SiLU(sum_{j<K} w_conv[j] u_{t-K+1+j})  (u before position 0 is 0);
      q, k, v = c_t split a head; q <- q / sqrt(|q|^2 + 1e-6) / sqrt(d),
      k <- k / sqrt(|k|^2 + 1e-6);
      g_t = lower * sigmoid(exp(A_log_h) (xn W_f + dt_bias))  (H x d, <= 0);
      beta_t = sigmoid(xn W_beta)  (H);
      S_t = (I - beta k k^T) Diag(exp g_t) S_{t-1} + beta k v^T  (S_0 = 0),
      o_t = S_t^T q  (a head, float32 throughout);
      out = [RMSNorm_d(o; w_o_norm) * sigmoid(xn W_g)] W_o.
    MLA (no query latent: q = xn W_q -> H x (d_n + d_r));
      [c | k_r] = xn W_dkv; c_kv = RMSNorm(c; w_kv); [k_n | v] = c_kv W_ukv;
      rotary on q_r and k_r (one k_r a token, shared by the heads):
      dimension j with j + d_r/2, angle t theta^(-2j/d_r);
      score = (q_n . k_n + q_r . k_r) / sqrt(d_n + d_r), u <= t; o_h =
      softmax . v; out = concat_h(o_h sigmoid(xn . w_g,h)) W_o.
    x <- x + out;  hn = RMSNorm(x; w_ffn).
    Layer < first_k_dense_replace: SwiGLU of intermediate_size. Else s =
    sigmoid(hn W_r) over all experts, b the selection bias; groups of
    experts / n_group consecutive experts, a group's score the sum of its
    two largest s + b; the topk_group best groups kept (ties to the lower
    index); the num_experts_per_tok largest s + b among their experts are
    chosen; weights = chosen s / their sum x routed_scaling_factor;
    y = sum_{e chosen and held here} w_e SwiGLU_e(hn) + SwiGLU_shared(hn)
    (moe_shared_expert_intermediate_size x num_shared_experts).
    x <- x + y.  logits = RMSNorm(x) W_head over the vocabulary rows held.

DEPARTURE RISKS (the configuration's file lists each under `assumed`): the
layer pattern from `layer_group_size`; the decay's form from
`kda_safe_gate` / `kda_lower_bound`; the L2 norm's epsilon; the rotary
pairing (j with j + d_r/2, where the source's `rope_interleave` pairs 2j with
2j + 1: the same map up to a permutation of rope columns); `use_qk_norm` read
as the norm on c_kv. A model whose code reads any of these otherwise computes
another function than this file.

Parameters: `param_spec` lists them under the program's checkpoint paths;
`Drawn` draws each from the seed where it is used (the first latent
reference's draw, with three kinds of this family's own: `alog`, `dtbias`,
`conv`); `program_tree` draws the same values as the program's tree.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import functools
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
from .mla_moe_decoder import rotate

FULL, LINEAR = "full_attention", "linear_attention"
L2_EPS = 1e-6


# ---- sizes -------------------------------------------------------------------

def sizes(cfg: dict) -> types.SimpleNamespace:
    """The configuration's `fields` under short names. `num_experts` is the
    number held here; the router's width is that times `ep_size`."""
    held, ep = int(cfg["num_experts"]), int(cfg.get("ep_size", 1))
    layers, group = int(cfg["num_hidden_layers"]), int(cfg["layer_group_size"])
    first = int(cfg.get("ep_rank", 0)) * held
    n_group = int(cfg.get("n_group") or 0)
    z = types.SimpleNamespace(
        hidden=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]),
        layers=layers,
        kinds=[FULL if (i + 1) % group == 0 else LINEAR
               for i in range(layers)],
        dense_layers=int(cfg["first_k_dense_replace"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        shared_width=int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg["num_shared_experts"]),
        held=held, first_expert=first, experts=held * ep,
        per_token=int(cfg["num_experts_per_tok"]),
        norm_weights=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        n_group=n_group, topk_group=int(cfg.get("topk_group") or 0),
        eps=float(cfg["rms_norm_eps"]),
        heads=int(cfg["num_attention_heads"]),
        kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        v=int(cfg["v_head_dim"]), theta=float(cfg["rope_theta"]),
        lin_heads=int(cfg.get("num_kv_heads_for_linear_attn")
                      or cfg["num_attention_heads"]),
        head_dim=int(cfg["head_dim"]), taps=int(cfg["short_conv_kernel_size"]),
        lower=float(cfg["kda_lower_bound"]))
    if n_group:
        size = z.experts // n_group
        z.my_groups = list(range(first // size,
                                 (first + held - 1) // size + 1))
    return z


# ---- parameters --------------------------------------------------------------

def param_spec(cfg: dict) -> Dict[str, Tuple[tuple, str]]:
    """{path: (shape, kind)}; kinds of the first latent reference (`matrix`,
    `scale`, `select`, `experts`) and three of this family's: `alog` (log
    U(1, 16), float32), `dtbias` (the inverse softplus of a log-uniform dt in
    [0.001, 0.1], float32), `conv` (U(-1/sqrt(taps), 1/sqrt(taps)))."""
    z = sizes(cfg)
    d, spec = z.hidden, {}
    spec["embed"] = ((z.vocab, d), "matrix")
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, z.vocab), "matrix")
    width = z.lin_heads * z.head_dim
    for i, kind in enumerate(z.kinds):
        p = "layer_%d/" % i
        spec[p + "attn_norm"] = ((d,), "scale")
        spec[p + "ffn_norm"] = ((d,), "scale")
        if kind == FULL:
            parts = (("w_q", (d, z.heads * (z.nope + z.rope)), "matrix"),
                     ("w_dkv", (d, z.kv_rank + z.rope), "matrix"),
                     ("kv_norm", (z.kv_rank,), "scale"),
                     ("w_ukv", (z.kv_rank, z.heads * (z.nope + z.v)),
                      "matrix"),
                     ("w_g", (d, z.heads), "matrix"),
                     ("w_o", (z.heads * z.v, d), "matrix"))
        else:
            parts = tuple((name, (d, width), "matrix")
                          for name in ("w_q", "w_k", "w_v", "w_f", "w_g")) + (
                ("w_beta", (d, z.lin_heads), "matrix"),
                ("a_log", (z.lin_heads,), "alog"),
                ("dt_bias", (width,), "dtbias"),
                ("conv", (z.taps, 3 * width), "conv"),
                ("o_norm", (z.head_dim,), "scale"),
                ("w_o", (width, d), "matrix"))
        for name, shape, k in parts:
            spec[p + "attn/" + name] = (shape, k)
        if i < z.dense_layers:
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


def _draw(key, shape, kind):
    if kind == "alog":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dtbias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "conv":
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(jnp.bfloat16)
    return base._draw(key, shape, kind)


class Drawn(base.Drawn):
    """The first latent reference's draw over this family's list. `shift`
    (traced inside a layer's program) is added to a leaf's index: a layer
    drawn under another layer's paths (`Reference.twin`) draws its own
    leaves."""

    shift = 0

    def __init__(self, cfg: dict, seed: int):
        self.spec = param_spec(cfg)
        self.index = {p: i for i, p in enumerate(sorted(self.spec))}
        self.key = base.seed_key(seed)

    def _leaf_key(self, path):
        return jax.random.fold_in(self.key, self.index[path] + self.shift)

    def get(self, path, dtype=jnp.float32):
        shape, kind = self.spec[path]
        if kind == "experts":
            raise ValueError("%s is drawn an expert at a time" % path)
        return _draw(self._leaf_key(path), shape, kind).astype(dtype)


class Held(base.Held):
    def __init__(self, cfg: dict, flat: dict):
        self.flat, self.first = flat, sizes(cfg).first_expert


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _program_leaf(key, shape, kind):
    return _draw(key, shape, kind)


def program_tree(cfg: dict, seed: int) -> dict:
    """{'params': nested} of the program: every leaf as `Drawn` gives it, in
    the program's types (bfloat16; A_log, dt_bias and the selection bias
    float32), one jitted draw a leaf."""
    drawn, z = Drawn(cfg, seed), sizes(cfg)
    ids = jnp.arange(z.first_expert, z.first_expert + z.held)
    tree: dict = {}
    for path, (shape, kind) in drawn.spec.items():
        key = drawn._leaf_key(path)
        leaf = (base._program_experts(key, ids, tuple(shape[1:]))
                if kind == "experts" else
                _program_leaf(key, tuple(shape), kind))
        node = tree
        *parents, name = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[name] = leaf
    return {"params": tree}


# ---- arithmetic ----------------------------------------------------------------

def l2_normalize(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class Reference:
    """The forward pass, a jitted function a form of layer (`twin`).
    `weights`: a `Drawn` or a `Held`. `quant` rounds both operands of every
    product with a weight (the first latent reference's f32 | bf16 | fp8);
    the recurrence stays float32."""

    def __init__(self, cfg: dict, weights, quant: str = "f32"):
        self.z, self.w = sizes(cfg), weights
        q = base.quantizer(quant)
        self.q = q if q is not None else (lambda x: x)
        self._jitted: dict = {}

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def kda(self, i, xn):
        """xn (seqs, n, hidden) -> the KDA layer's output (seqs, n, hidden),
        the recurrence a token at a time over every sequence at once."""
        z, w = self.z, self.w
        p = "layer_%d/attn/" % i
        seqs, n, _ = xn.shape
        h, d = z.lin_heads, z.head_dim
        u = jnp.concatenate([self.mm(xn, w.get(p + name))
                             for name in ("w_q", "w_k", "w_v")], axis=-1)
        taps = w.get(p + "conv")
        padded = jnp.pad(u, ((0, 0), (z.taps - 1, 0), (0, 0)))
        c = jax.nn.silu(sum(taps[j] * padded[:, j:j + n]
                            for j in range(z.taps)))
        q, k, v = (c[..., j * h * d:(j + 1) * h * d].reshape(seqs, n, h, d)
                   for j in range(3))
        q, k = l2_normalize(q) / math.sqrt(d), l2_normalize(k)
        f = (self.mm(xn, w.get(p + "w_f")) + w.get(p + "dt_bias")).reshape(
            seqs, n, h, d)
        g = z.lower * jax.nn.sigmoid(jnp.exp(w.get(p + "a_log"))[:, None] * f)
        beta = jax.nn.sigmoid(self.mm(xn, w.get(p + "w_beta")))[..., None,
                                                                None]
        eye = jnp.eye(d, dtype=jnp.float32)

        def token(s, t):
            qt, kt, vt, gt, bt = t   # (seqs, h, d) ... (seqs, h, 1, 1)
            kk = kt[..., :, None] * kt[..., None, :]
            s = jnp.matmul(eye - bt * kk, jnp.exp(gt)[..., None] * s,
                           precision=HIGHEST) + bt * (
                kt[..., :, None] * vt[..., None, :])
            return s, jnp.einsum("shkv,shk->shv", s, qt, precision=HIGHEST)
        first = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        _, o = lax.scan(token, jnp.zeros((seqs, h, d, d), jnp.float32),
                        tuple(first(a) for a in (q, k, v, g, beta)))
        o = rms_norm(jnp.moveaxis(o, 0, 1), w.get(p + "o_norm"), z.eps)
        o = o * jax.nn.sigmoid(self.mm(xn, w.get(p + "w_g"))).reshape(o.shape)
        return self.mm(o.reshape(seqs, n, h * d), w.get(p + "w_o"))

    def mla(self, i, xn):
        """xn (n, hidden) of one sequence -> the MLA layer's output."""
        z, w = self.z, self.w
        p = "layer_%d/attn/" % i
        n = xn.shape[0]
        pos = jnp.arange(n)
        f = z.theta ** (-2.0 * np.arange(z.rope // 2) / z.rope)
        q = self.mm(xn, w.get(p + "w_q")).reshape(n, z.heads, z.nope + z.rope)
        q_n, q_r = q[..., :z.nope], rotate(q[..., z.nope:], pos, f, 1.0)
        ckr = self.mm(xn, w.get(p + "w_dkv"))
        c_kv = rms_norm(ckr[:, :z.kv_rank], w.get(p + "kv_norm"), z.eps)
        k_r = rotate(ckr[:, z.kv_rank:], pos, f, 1.0)
        kv = self.mm(c_kv, w.get(p + "w_ukv")).reshape(n, z.heads,
                                                       z.nope + z.v)
        k_n, v = kv[..., :z.nope], kv[..., z.nope:]
        allowed = pos[None, :] <= pos[:, None]
        scale = 1.0 / math.sqrt(z.nope + z.rope)

        def head(hh):
            sc = (self.mm(q_n[:, hh], k_n[:, hh].T)
                  + self.mm(q_r[:, hh], k_r.T)) * scale
            prob = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
            return self.mm(prob, v[:, hh])
        out = jnp.transpose(lax.map(head, jnp.arange(z.heads)), (1, 0, 2))
        gate = jax.nn.sigmoid(self.mm(xn, w.get(p + "w_g")))
        out = (out * gate[..., None]).reshape(n, z.heads * z.v)
        return self.mm(out, w.get(p + "w_o"))

    def swiglu(self, x, w_gate_up, w_down):
        g, u = jnp.split(self.mm(x, w_gate_up), 2, axis=-1)
        return self.mm(jax.nn.silu(g) * u, w_down)

    def route(self, i, hn):
        """(weights (n, experts) with zeros off the choice, chosen bool (n,
        experts), kept (n, n_group) bool)."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        s = jax.nn.sigmoid(self.mm(hn, w.get(p + "w_router")))
        choice = s + w.get(p + "b_select")
        kept = jnp.ones((s.shape[0], max(z.n_group, 1)), bool)
        if z.n_group:
            by_group = choice.reshape(s.shape[0], z.n_group, -1)
            score = jnp.sum(jnp.sort(by_group, axis=-1)[..., -2:], axis=-1)
            best = jnp.argsort(-score, axis=-1, stable=True)[:, :z.topk_group]
            kept = jnp.zeros(score.shape, bool).at[
                jnp.arange(s.shape[0])[:, None], best].set(True)
        open_to = jnp.repeat(kept, z.experts // kept.shape[1], axis=-1)
        _, idx = lax.top_k(jnp.where(open_to, choice, -jnp.inf), z.per_token)
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], idx].set(True)
        wt = jnp.where(chosen, s, 0.0)
        if z.norm_weights:
            wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
        return wt * z.routed_scale, chosen, kept

    def experts(self, i, hn):
        """(the held experts' part, the shared expert's part, chosen (n,
        experts), kept (n, groups)), an expert at a time."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        wt, chosen, kept = self.route(i, hn)

        def one(total, e):
            y = self.swiglu(hn, w.expert(p + "w_gate_up", e),
                            w.expert(p + "w_down", e))
            return total + wt[:, e, None] * y, None
        routed, _ = lax.scan(one, jnp.zeros_like(hn), jnp.arange(
            z.first_expert, z.first_expert + z.held))
        shared = self.swiglu(hn, w.get(p + "shared_gate_up"),
                             w.get(p + "shared_down"))
        return routed, shared, chosen, kept

    def layer(self, i, x):
        """x (sequences, n, hidden) -> (x out, chosen (sequences, n, held),
        hit (sequences, n)), as the plain latent reference's `layer`."""
        z = self.z
        xn = rms_norm(x, self.w.get("layer_%d/attn_norm" % i), z.eps)
        if z.kinds[i] == FULL:
            x = x + lax.map(lambda one: self.mla(i, one), xn)
        else:
            x = x + self.kda(i, xn)
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

    def twin(self, i):
        """(j, shift): the first layer whose program is layer i's (the same
        kind, dense or not) and what layer i adds to its leaves' indices.
        Each layer's leaves are one run of the sorted paths, in the same
        order for layers of one form, so layer i runs j's program over its own
        draws: one compile a form, not a layer. `Held` weights are constants
        of their layer's program: each layer its own."""
        z, w = self.z, self.w
        if not isinstance(w, Drawn):
            return i, 0
        form = lambda k: (z.kinds[k], k < z.dense_layers)  # noqa: E731
        j = next(k for k in range(z.layers) if form(k) == form(i))
        mine, its = ("layer_%d/" % i, "layer_%d/" % j)
        shift = {w.index[p] - w.index[its + p[len(mine):]]
                 for p in w.index if p.startswith(mine)}
        assert len(shift) == 1, (i, j, shift)
        return j, shift.pop()

    def _jit(self, name, fn, shift: int = 0):
        """One program a name, over a `Drawn`'s key and `shift` as ARGUMENTS:
        closed over, the key makes each seed's draws constants of a program of
        its own, compiled anew every seed (about 2 min at the published
        sizes)."""
        if name not in self._jitted:
            def keyed(key, shift, *args):
                if key is None:  # `Held` weights: nothing is drawn
                    return fn(*args)
                saved, self.w.key, self.w.shift = (
                    (self.w.key, self.w.shift), key, shift)
                try:
                    return fn(*args)
                finally:
                    self.w.key, self.w.shift = saved
            self._jitted[name] = jax.jit(keyed)
        return functools.partial(self._jitted[name],
                                 getattr(self.w, "key", None), shift)

    def _embed(self):
        return self._jit("embed", lambda t: self.w.get("embed")[t])

    def _layer(self, i):
        j, shift = self.twin(i)
        return self._jit("layer_%d" % j, lambda x: self.layer(j, x), shift)

    def _head(self):
        return self._jit("head", lambda x, at: self.mm(
            rms_norm(jnp.take_along_axis(x, at[..., None], axis=1),
                     self.w.get("final_norm"), self.z.eps),
            self.w.get("lm_head")))

    def compile(self, sequences: int, n: int, m: int) -> None:
        """Compile every program `forward` runs over (sequences, n) tokens
        and m positions a sequence, from shapes alone: what the persistent
        compile cache stores, a later `forward` of any seed finds there."""
        x = jax.ShapeDtypeStruct((sequences, n, self.z.hidden), jnp.float32)
        calls = [(self._embed(), (jax.ShapeDtypeStruct((sequences, n),
                                                       jnp.int32),)),
                 (self._head(), (x, jax.ShapeDtypeStruct((sequences, m),
                                                         jnp.int32)))]
        forms = {self.twin(i)[0]: i for i in range(self.z.layers)}
        calls += [(self._layer(i), (x,)) for i in forms.values()]
        for call, shapes in calls:
            call.func.lower(*call.args, *shapes).compile()

    def forward(self, tokens, n_real, at) -> list:
        """tokens int32 (sequences, n) (rows from a sequence's `n_real` on
        are padding: causal, so they change nothing before them); `at`
        (sequences, m): the positions whose logits are wanted. Returns, a
        sequence, {'logits' (m, vocab), 'chosen', 'expert_pairs' (expert
        layers, held), 'group_hits'} as numpy, as the plain latent
        reference's `forward`."""
        z = self.z
        tokens, at = jnp.asarray(tokens, jnp.int32), jnp.asarray(at)
        x = self._embed()(tokens)
        chosen, hits = [], []
        for i in range(z.layers):
            x, c, h = self._layer(i)(x)
            if i >= z.dense_layers:
                chosen.append(c)
                hits.append(h)
        logits = self._head()(x, at)
        logits, chosen, hits = jax.device_get(
            (logits, jnp.stack(chosen, axis=1), jnp.stack(hits, axis=1)))
        out = []
        for row, picks, hit, n in zip(logits, chosen, hits, n_real):
            picks = picks[:, :int(n)]
            out.append({"logits": row, "chosen": picks,
                        "expert_pairs": picks.sum(axis=1, dtype=np.int64),
                        "group_hits": int(hit[:, :int(n)].sum())})
        return out
