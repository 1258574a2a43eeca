"""The plain reference of the second family: a decoder whose layers mix latent
attention behind a learned sparse indexer (`full_attention`), a second latent
attention behind a sliding window (`sliding_attention`), a dense SwiGLU layer
and sigmoid-routed experts with a shared expert, as one chip's share of an
expert-parallel deployment.

jax.numpy, float32, `precision=HIGHEST`, no flax, no kernel, no cache, no
batching: the full forward pass over ONE sequence. It reads the configuration's
`fields` (the source's own keys) and nothing of the program.

The equations (x: the residual stream, one row a position t):

    x~ = RMSNorm(x) before attention and before the feed-forward; residual
    adds after each.
    latent attention (sizes of the layer's kind, `swa_*` for sliding):
      c_q = RMSNorm(x~ W_dq) * sqrt(hidden / q_rank)
      q_h = c_q W_uq -> heads x [nope | rope], rope part rotated
      [c_kv | k_r] = x~ W_dkv;  c_kv <- RMSNorm(c_kv) * sqrt(hidden / kv_rank)
      k_r rotated, shared by all heads; [k_nope | v]_h = c_kv W_ukv
      a_h = softmax_{s in A_t}(q_h . [k_nope_h | k_r][s] / sqrt(nope + rope)) v_h
      g = sigmoid(x~ W_g) (a gate a head); out = concat_h(g_h a_h) W_o
    A_t, sliding layer: s in t-(window-1) .. t (the window counts the token).
    A_t, full layer: the `index_topk` keys s <= t of largest I[t, s] (all of
    them while there are no more than that), with
      qI = c_q W_Iq -> index heads x index dim, first rope dims rotated
      kI = LayerNorm(x~ W_Ik), first rope dims rotated;  w = x~ W_Iw
      I[t, s] = sum_h w[t, h] ReLU(qI[t, h] . kI[s]) / sqrt(heads * dim)
    expert layer: s = sigmoid(h~ W_r); the `num_experts_per_tok` largest of
    s + b are chosen (b: the selection bias, for the choice only); weights
    s_e / sum_chosen s (x routed_scaling_factor);
      y = sum_{e chosen and held here} w_e SwiGLU_e(h~) + SwiGLU_shared(h~)
    layer < first_k_dense_replace: SwiGLU of `intermediate_size`.
    out: RMSNorm, untied head over the vocabulary rows held here.

Departures and conventions (the configuration's file lists them as `assumed`):
the two sqrt(hidden / rank) scalings, the gate's form, the indexer's form and
scale are conventions of sibling models, the source's config gives switches
only; the rotation pairs dimension i with i + d/2; LayerNorm and RMSNorm share
`rms_norm_eps`; no group-limited routing (the config gives no groups); what
the experts held elsewhere would add is left out (the chip's share).

Parameters: `param_spec` lists them under the program's checkpoint paths (a
file format, not code); `Drawn` draws each from the seed where it is used,
layer by layer and expert by expert, so that float32 copies of a few billion
parameters never stand together; `program_tree` draws the same values as the
program's tree (bfloat16, the precision the source states). Every value is
bfloat16-representable: the reference computes in float32 on the very
numbers the program holds.

`quant` rounds both operands of every matrix product: "f32" (the reference),
"bf16", "fp8" (e4m3, each tensor scaled to 240: the control).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import functools
import math
import types
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
SIGMA = 0.02
SELECT_SIGMA = 0.004
FULL, SLIDING = "full_attention", "sliding_attention"


# ---- sizes -------------------------------------------------------------------

def sizes(cfg: dict) -> types.SimpleNamespace:
    """The configuration's `fields` under short names. `n_routed_experts` is
    the number held here; the router's width is that times `ep_size`."""
    def attn(prefix, heads):
        return types.SimpleNamespace(
            heads=int(cfg[heads]), q_rank=int(cfg[prefix + "q_lora_rank"]),
            kv_rank=int(cfg[prefix + "kv_lora_rank"]),
            nope=int(cfg[prefix + "qk_nope_head_dim"]),
            rope=int(cfg[prefix + "qk_rope_head_dim"]),
            v=int(cfg[prefix + "v_head_dim"]),
            theta=float(cfg[prefix + "rope_theta"]))
    held, ep = int(cfg["n_routed_experts"]), int(cfg.get("ep_size", 1))
    rank = int(cfg.get("ep_rank", 0))
    return types.SimpleNamespace(
        hidden=int(cfg["hidden_size"]), vocab=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        kinds=list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])],
        dense_layers=int(cfg["first_k_dense_replace"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["n_shared_experts"]), held=held,
        first_expert=rank * held, experts=held * ep,
        per_token=int(cfg["num_experts_per_tok"]),
        norm_weights=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), window=int(cfg["sliding_window_size"]),
        index_heads=int(cfg["index_n_heads"]),
        index_dim=int(cfg["index_head_dim"]), index_topk=int(cfg["index_topk"]),
        full=attn("", "num_attention_heads"),
        swa=attn("swa_", "swa_num_attention_heads"))


def moe_layers(z) -> list:
    return [i for i in range(z.layers) if i >= z.dense_layers]


# ---- parameters --------------------------------------------------------------

def param_spec(cfg: dict) -> Dict[str, Tuple[tuple, str]]:
    """{path: (shape, kind)}. Kinds: `matrix` and `bias` N(0, 0.02), `scale`
    1 + N(0, 0.02), `select` (the router's selection bias, float32, N(0,
    0.004)) and `experts` (leading axis: the experts held, each drawn under
    its id in the whole model, so that every share draws the same expert).

    The bias is a fifth of the spread of the scores it decides between: the
    8 chosen of 256 sigmoid scores lie within 0.019 of each other at the
    published sizes (the sigmoid is flat up there), so 0.004 moves one pair
    in twenty and an expert's share of the pairs by an eighth. At 0.02 it
    moved one pair in five and an expert's share by half, the 32 experts a
    share holds did not average that out, and the share's load followed the
    seed (sd 5.5%, and the rate with it: PERF.md section 6, PR 29), where a
    deployment's bias is the very thing that levels the load."""
    z = sizes(cfg)
    d, spec = z.hidden, {}
    spec["embed"] = ((z.vocab, d), "matrix")
    spec["final_norm"] = ((d,), "scale")
    spec["lm_head"] = ((d, z.vocab), "matrix")
    for i, kind in enumerate(z.kinds):
        a = z.full if kind == FULL else z.swa
        p = "layer_%d/" % i
        spec[p + "attn_norm"] = ((d,), "scale")
        spec[p + "ffn_norm"] = ((d,), "scale")
        for name, shape, k in (
                ("w_dq", (d, a.q_rank), "matrix"),
                ("q_norm", (a.q_rank,), "scale"),
                ("w_uq", (a.q_rank, a.heads * (a.nope + a.rope)), "matrix"),
                ("w_dkv", (d, a.kv_rank + a.rope), "matrix"),
                ("kv_norm", (a.kv_rank,), "scale"),
                ("w_ukv", (a.kv_rank, a.heads * (a.nope + a.v)), "matrix"),
                ("w_g", (d, a.heads), "matrix"),
                ("w_o", (a.heads * a.v, d), "matrix")):
            spec[p + "attn/" + name] = (shape, k)
        if kind == FULL:
            hi, di = z.index_heads, z.index_dim
            for name, shape, k in (
                    ("w_q", (a.q_rank, hi * di), "matrix"),
                    ("w_k", (d, di), "matrix"),
                    ("k_norm_scale", (di,), "scale"),
                    ("k_norm_bias", (di,), "bias"),
                    ("w_w", (d, hi), "matrix")):
                spec[p + "attn/indexer/" + name] = (shape, k)
        if i < z.dense_layers:
            spec[p + "ffn/w_gate_up"] = ((d, 2 * z.dense_width), "matrix")
            spec[p + "ffn/w_down"] = ((z.dense_width, d), "matrix")
        else:
            f, fs = z.expert_width, z.expert_width * z.shared
            spec[p + "moe/w_router"] = ((d, z.experts), "matrix")
            spec[p + "moe/b_select"] = ((z.experts,), "select")
            spec[p + "moe/w_gate_up"] = ((z.held, d, 2 * f), "experts")
            spec[p + "moe/w_down"] = ((z.held, f, d), "experts")
            spec[p + "moe/shared_gate_up"] = ((d, 2 * fs), "matrix")
            spec[p + "moe/shared_down"] = ((fs, d), "matrix")
    return spec


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, shape, kind):
    if kind == "select":
        return SELECT_SIGMA * jax.random.normal(key, shape, jnp.float32)
    n = SIGMA * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + n) if kind == "scale" else n).astype(jnp.bfloat16)


class Drawn:
    """The parameters from the seed, each drawn where it is asked for (inside
    the caller's jit): `get(path)` one array, `expert(path, e)` one expert's
    slice by its id in the whole model (`e` may be traced). float32 values
    of the bfloat16 numbers the program holds."""

    def __init__(self, cfg: dict, seed: int):
        self.spec = param_spec(cfg)
        self.index = {p: i for i, p in enumerate(sorted(self.spec))}
        self.key = seed_key(seed)

    def _leaf_key(self, path):
        return jax.random.fold_in(self.key, self.index[path])

    def get(self, path, dtype=jnp.float32):
        shape, kind = self.spec[path]
        if kind == "experts":
            raise ValueError("%s is drawn an expert at a time" % path)
        return _draw(self._leaf_key(path), shape, kind).astype(dtype)

    def expert(self, path, e, dtype=jnp.float32):
        shape, _ = self.spec[path]
        return _draw(jax.random.fold_in(self._leaf_key(path), e), shape[1:],
                     "matrix").astype(dtype)


class Held:
    """The same interface over a tree the program holds ({path: array}, as
    `flatten_tree` gives it): what the CPU tests hand the reference."""

    def __init__(self, cfg: dict, flat: dict):
        self.flat, self.first = flat, sizes(cfg).first_expert

    def get(self, path, dtype=jnp.float32):
        return jnp.asarray(self.flat[path]).astype(dtype)

    def expert(self, path, e, dtype=jnp.float32):
        return jnp.asarray(self.flat[path])[e - self.first].astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "kind"))
def _program_leaf(key, shape, kind):
    return _draw(key, shape, kind)


@functools.partial(jax.jit, static_argnames=("shape",))
def _program_experts(key, ids, shape):
    return jax.vmap(lambda e: _draw(jax.random.fold_in(key, e), shape,
                                    "matrix"))(ids)


def program_tree(cfg: dict, seed: int) -> dict:
    """{'params': nested} of the program: every leaf as `Drawn` gives it, in
    the program's types (bfloat16; the selection bias float32). One jitted
    draw a leaf (compiled once a shape), so that no float32 copy outlives
    its cast."""
    drawn, z = Drawn(cfg, seed), sizes(cfg)
    ids = jnp.arange(z.first_expert, z.first_expert + z.held)
    tree: dict = {}
    for path, (shape, kind) in drawn.spec.items():
        key = drawn._leaf_key(path)
        leaf = (_program_experts(key, ids, tuple(shape[1:]))
                if kind == "experts" else
                _program_leaf(key, tuple(shape), kind))
        node = tree
        *parents, name = path.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[name] = leaf
    return {"params": tree}


def flatten_tree(tree) -> Dict[str, jax.Array]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in flat}


def check_tree(program_shapes, spec) -> None:
    """Raise unless the program's parameters (shapes, as `jax.eval_shape` of
    its init gives them) are exactly the reference's list."""
    got = {p: tuple(leaf.shape) for p, leaf in
           flatten_tree(program_shapes["params"]).items()}
    want = {p: tuple(s) for p, (s, _) in spec.items()}
    if got != want:
        raise ValueError(
            "program and reference disagree on the parameters: program only "
            "%r, reference only %r, shapes differ %r" % (
                sorted(set(got) - set(want))[:5],
                sorted(set(want) - set(got))[:5],
                [p for p in got if p in want and got[p] != want[p]][:5]))


# ---- arithmetic ----------------------------------------------------------------

def quantizer(mode: str) -> Optional[Callable]:
    if mode == "f32":
        return None
    # lax.reduce_precision, not a cast there and back: the compiler is
    # allowed to keep excess precision and drops such a pair of casts
    if mode == "bf16":
        return lambda x: lax.reduce_precision(x, 8, 7)
    if mode == "fp8":
        def q(x):
            s = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return lax.reduce_precision(x * s, 4, 3) / s
        return q
    raise ValueError("quant must be f32 | bf16 | fp8, got %r" % (mode,))


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * w + b


def rotate(x, pos, theta):
    """x[..., d] at positions `pos` (leading axis): dimension i paired with
    i + d/2, angle pos * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


class Reference:
    """The forward pass, a jitted function a layer (compiled once, used for
    every sequence of the same padded length). `weights`: a `Drawn` or a
    `Held`."""

    def __init__(self, cfg: dict, weights, quant: str = "f32"):
        self.z, self.w = sizes(cfg), weights
        q = quantizer(quant)
        self.q = q if q is not None else (lambda x: x)
        self._jitted: dict = {}

    def mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    # -- attention ---------------------------------------------------------------

    def index_scores(self, p, xn, c_q, pos):
        """I[t, s], float32 (n, n), every pair (the caller masks)."""
        z, w = self.z, self.w
        n, rope = xn.shape[0], z.full.rope
        qi = self.mm(c_q, w.get(p + "w_q")).reshape(n, z.index_heads,
                                                    z.index_dim)
        qi = jnp.concatenate([rotate(qi[..., :rope], pos, z.full.theta),
                              qi[..., rope:]], axis=-1)
        ki = layer_norm(self.mm(xn, w.get(p + "w_k")),
                        w.get(p + "k_norm_scale"), w.get(p + "k_norm_bias"),
                        z.eps)
        ki = jnp.concatenate([rotate(ki[:, :rope], pos, z.full.theta),
                              ki[:, rope:]], axis=-1)
        wt = self.mm(xn, w.get(p + "w_w"))

        def head(total, h):
            s = self.mm(qi[:, h], ki.T)
            return total + wt[:, h, None] * jnp.maximum(s, 0.0), None
        total, _ = lax.scan(head, jnp.zeros((n, n), jnp.float32),
                            jnp.arange(z.index_heads))
        return total / math.sqrt(z.index_heads * z.index_dim)

    def attention(self, i, x):
        """x (n, hidden) -> (the layer's attention output, allowed (n, n))."""
        z, w = self.z, self.w
        kind = z.kinds[i]
        a = z.full if kind == FULL else z.swa
        p = "layer_%d/attn/" % i
        n = x.shape[0]
        pos = jnp.arange(n)
        xn = rms_norm(x, w.get("layer_%d/attn_norm" % i), z.eps)
        c_q = rms_norm(self.mm(xn, w.get(p + "w_dq")), w.get(p + "q_norm"),
                       z.eps) * math.sqrt(z.hidden / a.q_rank)
        q = self.mm(c_q, w.get(p + "w_uq")).reshape(n, a.heads,
                                                    a.nope + a.rope)
        q_nope, q_rope = q[..., :a.nope], rotate(q[..., a.nope:], pos,
                                                 a.theta)
        ckr = self.mm(xn, w.get(p + "w_dkv"))
        c_kv = rms_norm(ckr[:, :a.kv_rank], w.get(p + "kv_norm"),
                        z.eps) * math.sqrt(z.hidden / a.kv_rank)
        k_r = rotate(ckr[:, a.kv_rank:], pos, a.theta)
        kv = self.mm(c_kv, w.get(p + "w_ukv")).reshape(n, a.heads,
                                                       a.nope + a.v)
        k_nope, v = kv[..., :a.nope], kv[..., a.nope:]
        t, s = pos[:, None], pos[None, :]
        allowed = s <= t
        if kind == SLIDING:
            allowed &= (t - s) < z.window
        else:
            scores = jnp.where(allowed, self.index_scores(
                p + "indexer/", xn, c_q, pos), -jnp.inf)
            kth = lax.top_k(scores, min(z.index_topk, n))[0][:, -1:]
            allowed &= scores >= kth

        def head(h):
            sc = (self.mm(q_nope[:, h], k_nope[:, h].T)
                  + self.mm(q_rope[:, h], k_r.T)) / math.sqrt(a.nope + a.rope)
            prob = jax.nn.softmax(jnp.where(allowed, sc, -jnp.inf), axis=-1)
            return self.mm(prob, v[:, h])
        out = lax.map(head, jnp.arange(a.heads))          # (heads, n, v)
        gate = jax.nn.sigmoid(self.mm(xn, w.get(p + "w_g")))  # (n, heads)
        out = jnp.transpose(out, (1, 0, 2)) * gate[..., None]
        return self.mm(out.reshape(n, a.heads * a.v), w.get(p + "w_o")), \
            allowed

    # -- feed-forward --------------------------------------------------------------

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
        if z.norm_weights:
            wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
        return wt * z.routed_scale, chosen

    def experts(self, i, hn):
        """(the held experts' part, the shared expert's part, chosen)."""
        z, w = self.z, self.w
        p = "layer_%d/moe/" % i
        wt, chosen = self.route(i, hn)

        def one(total, e):
            y = self.swiglu(hn, w.expert(p + "w_gate_up", e),
                            w.expert(p + "w_down", e))
            return total + wt[:, e, None] * y, None
        routed, _ = lax.scan(one, jnp.zeros_like(hn), jnp.arange(
            z.first_expert, z.first_expert + z.held))
        shared = jnp.zeros_like(hn)
        if z.shared:
            shared = self.swiglu(hn, w.get(p + "shared_gate_up"),
                                 w.get(p + "shared_down"))
        return routed, shared, chosen

    # -- the layer, the whole ----------------------------------------------------

    def layer(self, i, x, n_real):
        """x (n, hidden), the first `n_real` rows real -> (x out, pairs routed
        to each held expert (held,), keys kept, keys causal), the counts
        over the real rows."""
        z = self.z
        real = jnp.arange(x.shape[0]) < n_real
        att, allowed = self.attention(i, x)
        x = x + att
        kept = causal = jnp.zeros((), jnp.int32)
        if z.kinds[i] == FULL:
            kept = jnp.sum(allowed & real[:, None], dtype=jnp.int32)
            causal = n_real * (n_real + 1) // 2
        hn = rms_norm(x, self.w.get("layer_%d/ffn_norm" % i), z.eps)
        pairs = jnp.zeros((z.held,), jnp.int32)
        if i < z.dense_layers:
            p = "layer_%d/ffn/" % i
            y = self.swiglu(hn, self.w.get(p + "w_gate_up"),
                            self.w.get(p + "w_down"))
        else:
            routed, shared, chosen = self.experts(i, hn)
            y = routed + shared
            held = chosen[:, z.first_expert:z.first_expert + z.held]
            pairs = jnp.sum(held & real[:, None], axis=0, dtype=jnp.int32)
        return x + y, pairs, kept, causal

    def _jit(self, name, fn):
        if name not in self._jitted:
            self._jitted[name] = jax.jit(fn)
        return self._jitted[name]

    def forward(self, tokens, n_real: int, at) -> dict:
        """tokens int32 (n,) (rows from `n_real` on are padding: causal, so
        they change nothing before them); `at`: the positions whose logits
        are wanted. Returns {'logits' (len(at), vocab), 'expert_pairs'
        (expert layers, held), 'keys_kept', 'keys_causal'} as numpy."""
        z, w = self.z, self.w
        tokens, at = jnp.asarray(tokens, jnp.int32), jnp.asarray(at)
        n_real = jnp.asarray(n_real, jnp.int32)
        x = self._jit("embed", lambda t: w.get("embed")[t])(tokens)
        pairs, kept, causal = [], 0, 0
        for i in range(z.layers):
            x, p, k, c = self._jit(
                "layer_%d" % i, lambda x, n, i=i: self.layer(i, x, n))(
                    x, n_real)
            if i >= z.dense_layers:
                pairs.append(p)
            kept, causal = kept + k, causal + c
        logits = self._jit("head", lambda x, at: self.mm(
            rms_norm(x[at], w.get("final_norm"), z.eps),
            w.get("lm_head")))(x, at)
        return jax.device_get({
            "logits": logits, "expert_pairs": jnp.stack(pairs),
            "keys_kept": kept, "keys_causal": causal})


def token_gaps(logits: np.ndarray, served_tokens) -> np.ndarray:
    """For each position: the reference's best logit minus its logit of the
    served token, in units of the standard deviation of that position's
    logits (0 where the served token is the reference's own choice)."""
    logits = np.asarray(logits, np.float64)
    picked = logits[np.arange(len(logits)), np.asarray(served_tokens)]
    return (logits.max(axis=-1) - picked) / logits.std(axis=-1)
