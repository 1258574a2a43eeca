"""Linear attention for the decoder families: the gated delta rule with a
decay a channel (Kimi Delta Attention, KDA) and its causal short convolution.

The reference has no attention of any kind (ref hourglass.py is
convolutions only); this module is new capability. A head holds a float32
state S (d_k x d_v) in place of keys; a token t writes it as

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t,

with g_t <= 0 the log decay of each of the d_k channels and beta_t in (0, 1)
the write's strength (benchmark/reference/hybrid_moe_decoder.py states the
whole layer and runs this recurrence token by token).

Prefill (`chunked_prefill`) takes ROWS = 16 rows together and walks them
CHUNK = 32 positions at a time. Within a chunk the writes w_r = beta_r (v_r -
S~_r^T k_r) of all its positions solve one unit lower triangular system (the
WY / UT form):

    (I + Diag(beta) A) W = Diag(beta) (V - K_G S_0),
    A[r, s] = sum_c k_r[c] k_s[c] exp(G_r[c] - G_s[c])   (s < r),

with G the chunk's cumulative log decay and S_0 the state the chunk starts
from; the outputs are O = Q_G S_0 + B W (B as A, with q_r, s <= r), and the
state is handed to the next chunk once. Every decay is applied as a
difference of cumulative log decays with the later position first
(G_r - G_s, G_r - "the chunk's start", G_last - G_s): never exp(+G), which
at g down to -5 a token overflows float32 within a chunk of 18. The pairwise
differences make A and B elementwise work of C x C x d_k a chunk and head;
32 keeps that a small share of a prefill and the chunks a pass walks in turn
at 16 for 512 slots. A chunk that starts at or past the longest row's
length is a branch not taken on the device (`lax.cond`, as
`ops/attention.py`'s q blocks); a chunk some row of the pass still holds
runs for every row of it, and a position past a row's length writes nothing
(beta = g = 0 there: the caller's business), so the state a row hands to
decode is its real tokens' alone. Rows a pass: one chunk of one row is
small work (32 heads of 32 x 32), so a row at a time the chunks are as many
dependent steps as the batch's rows hold chunks; 16 rows a pass cut those
16-fold, and a pass's chunks follow its longest row, so a batch's prefill
no longer follows the sum of its rows' lengths.

Decode (`state_step`) is one token a row: `ops/pallas/kda.py`'s `kda_step`
where Mosaic compiles, its XLA twin elsewhere (`select.on_chip`).
Products inside the chunk math run at `HIGHEST`: the state is float32, and
the MXU's default would round its operands to bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import kda
from .pallas import select

CHUNK = 32
ROWS = 16  # rows a prefill pass takes together
HIGHEST = lax.Precision.HIGHEST
L2_EPS = 1e-6


# ---- the short convolution --------------------------------------------------------

def causal_conv(u, w):
    """u (..., T, C) -> (..., T, C) float32: out[t] = sum_j w[j] u[t - (K-1)
    + j], zeros before u[0]."""
    taps, total = w.shape[0], u.shape[-2]
    full = jnp.pad(u, ((0, 0),) * (u.ndim - 2) + ((taps - 1, 0), (0, 0))
                   ).astype(jnp.float32)
    w = w.astype(jnp.float32)
    return sum(w[j] * full[..., j:j + total, :] for j in range(taps))


def conv_tail(u, lengths, taps: int):
    """The last `taps - 1` real inputs of each row of u (R, T, C), `lengths`
    (R,) (zeros before position 0): what decode's first step convolves
    with."""
    at = lengths[:, None] - (taps - 1) + jnp.arange(taps - 1, dtype=jnp.int32)
    got = jnp.take_along_axis(u, jnp.clip(at, 0, u.shape[1] - 1)[..., None],
                              axis=1)
    return jnp.where((at >= 0)[..., None], got, jnp.zeros((), u.dtype))


def conv_step(u, w, tail):
    """u (B, C) one input a row, tail (B, K-1, C) -> (out (B, C) float32, the
    tail with u written)."""
    full = jnp.concatenate([tail, u[:, None]], axis=1)
    out = jnp.einsum("bjc,jc->bc", full.astype(jnp.float32),
                     w.astype(jnp.float32))
    return out, full[:, 1:]


def l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


# ---- prefill: the chunked delta rule ---------------------------------------------

def _chunk(state, q, k, v, g, beta):
    """One chunk of every head. state (H, d_k, d_v); q, k, g (H, C, d_k); v
    (H, C, d_v); beta (H, C). Returns (the state after it, o (H, C, d_v))."""
    mm = lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)  # noqa: E731
    size = q.shape[1]
    gam = jnp.cumsum(g, axis=1)                        # (H, C, d_k), <= 0
    r = jnp.arange(size)
    on_or_below = r[:, None] >= r[None, :]
    # exp(G_r - G_s) for s <= r (the later position first), 0 above
    decay = jnp.exp(jnp.where(on_or_below[None, :, :, None],
                              gam[:, :, None, :] - gam[:, None, :, :],
                              -jnp.inf))
    a = jnp.sum(decay * k[:, :, None, :] * k[:, None, :, :], axis=-1)
    b = jnp.sum(decay * q[:, :, None, :] * k[:, None, :, :], axis=-1)
    a = jnp.where(r[:, None] > r[None, :], a, 0.0)
    system = jnp.eye(size, dtype=jnp.float32) + beta[:, :, None] * a
    from_start = jnp.exp(gam)                          # G_r - G(start)
    rhs = beta[:, :, None] * jnp.concatenate([v, from_start * k], axis=-1)
    solved = lax.linalg.triangular_solve(system, rhs, left_side=True,
                                         lower=True, unit_diagonal=True)
    u, wk = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    w = u - mm("hck,hkv->hcv", wk, state)              # the writes
    o = mm("hck,hkv->hcv", from_start * q, state) + mm("hcs,hsv->hcv", b, w)
    to_end = jnp.exp(gam[:, -1:, :] - gam)             # G_last - G_s
    state = (jnp.exp(gam[:, -1, :])[:, :, None] * state
             + mm("hck,hcv->hkv", to_end * k, w))
    return state, o


def chunked_prefill(q, k, v, g, beta, lengths, chunk: int = CHUNK):
    """One pass of rows. q, k, g (R, T, H, d_k), v (R, T, H, d_v), beta (R,
    T, H), all float32 (q and k normalised, q scaled; beta = g = 0 at padded
    positions); `lengths` int32 (R,): chunks that start at or past the
    longest are not run. Returns (o (R, T, H, d_v) float32 (zeros in chunks
    not run), the state after each row's last real token (R, H, d_k, d_v),
    from zeros, the chunks run int32 (R,): the pass's, every row alike)."""
    rows, total, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -total % chunk
    n = (total + pad) // chunk
    longest = jnp.max(lengths)

    def blocks(x):  # (R, T, H, d) -> (n, R, H, C, d)
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((rows, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)
    each = jax.vmap(_chunk)

    def step(s, xs):
        i, qc, kc, vc, gc, bc = xs
        return lax.cond(i * chunk < longest,
                        lambda s: each(s, qc, kc, vc, gc, bc),
                        lambda s: (s, jnp.zeros((rows, heads, chunk, dv),
                                                jnp.float32)), s)
    state, o = lax.scan(step, jnp.zeros((rows, heads, dk, dv), jnp.float32),
                        (jnp.arange(n), blocks(q), blocks(k), blocks(v),
                         blocks(g), blocks(beta)))
    # (n, R, H, C, d) -> (R, T, H, d)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        rows, n * chunk, heads, dv)[:, :total]
    return o, state, jnp.full((rows,), -(-longest // chunk), jnp.int32)


# ---- decode: one token a row -------------------------------------------------------

def xla_state_step(state, q, k, v, g, beta):
    """`kda_step`'s twin. state (B, H, d_k, d_v); q, k, g (B, H, d_k); v (B,
    H, d_v); beta (B, H) -> (o (B, H, d_v), the state after the token)."""
    state = state * jnp.exp(g)[..., None]
    w = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
    state = state + k[..., None] * w[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def state_step(state, q, k, v, g, beta, interpret: bool = False):
    """One token a row through its state: the kernel where Mosaic compiles
    (or under the interpreter: tests), the XLA twin elsewhere."""
    if interpret or select.on_chip():
        return kda.kda_step(state, kda.pack(q, k, g, v, beta),
                            interpret=interpret or None)
    return xla_state_step(state, q, k, v, g, beta)
