"""Fused sigmoid + focal + masked-L1 detection-loss Pallas TPU kernel.

The train step's loss (ops/loss.py: CenterNet focal + two mask-normalized
L1s over the raw stack output, ref /root/reference/loss.py:18-69) is a pure
bandwidth problem: the XLA path materializes the post-sigmoid heatmap and
several more heatmap-sized elementwise temporaries per stack (power/log
terms, neg weights, masked diffs) in the forward, saves residuals for
autodiff, and re-reads them in the backward. Here the whole per-stack
reduction fuses into ONE VMEM-resident Pallas pass each way:

* grid (B, S): one program per (sample, stack); the kernel emits only a
  (4, W) tile of per-column partial sums per program (focal pos/neg,
  offset-L1, size-L1; XLA sums the W columns) — the heatmap-sized
  intermediates never touch HBM;
* a `jax.custom_vjp` pairs it with a one-pass backward kernel that
  RECOMPUTES the forward terms from the same inputs and writes d(out)
  directly — no residuals beyond the already-materialized inputs;
* inputs stay in their native channels-last layout, read via FREE bitcast
  reshapes `(.., H, W, K) -> (.., H, W*K)` so the VPU sees full
  (sublane, lane) = (H, W*K) tiles. Individual channels are extracted
  in-VMEM by 0/1 selection-matrix matmuls built from iota
  (`x_c = x @ P_c`, `P_c[l, j] = [l == j*K + c]`) — exact in fp32 at
  HIGHEST matmul precision, and no layout-change traffic (an earlier
  transpose-based wrapper moved more HBM bytes than the XLA loss it
  replaced — counted by scripts/roofline.py's model);
* total HBM traffic: read the five input maps once per pass + write d(out)
  once, vs the XLA path's ~2.6x of that (scripts/roofline.py
  --ab-loss-kernel records the counted delta per platform).

Reduction semantics match `ops/loss.py` exactly (per-sample sums, batch
mean, global positive-count normalization); parity is pinned to the XLA
reference in fp32 and bf16 by tests/test_pallas_loss.py under interpret
mode. Off-TPU the kernel auto-selects interpret mode, like
`ops/pallas/peak.py`; production selection is `--loss-kernel` (config.py),
gated on the real backend exactly as the fused peak kernel is.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import select
from .partition import batch_parallel

_EPS = 1e-7  # matches ops/loss.py focal_loss eps


def _dabs(d: jax.Array) -> jax.Array:
    """d|x|/dx as sign(x). Ties: jax's lax.abs JVP yields 1.0 at exactly 0
    where sign gives 0 — the only positions where a zero diff can carry
    gradient are positives with pred bit-equal to gt (measure-zero for
    real predictions; masked positions are zeroed by the mask factor)."""
    return jnp.sign(d)


def _pow(x: jax.Array, e: float) -> jax.Array:
    """x ** e for a static exponent: repeated multiplication when `e` is
    integral (the focal defaults 2 and 4 — exact, and no transcendental
    for Mosaic to lower), exp(e * log x) otherwise (x >= 0 here; 0 ** e
    is exp(-inf) = 0 for the e > 0 the loss uses)."""
    if float(e).is_integer():
        return jax.lax.integer_pow(x, int(e))
    return jnp.exp(e * jnp.log(x))


def _select_mat(w: int, k: int, c: int, transpose: bool = False
                ) -> jax.Array:
    """0/1 channel-selection matrix: P (w*k, w) with P[l, j] = [l == j*k+c]
    — `flat @ P` gathers channel c of a (.., w, k)-flattened row onto w
    lanes; the transpose scatters it back. Built from iota in-kernel
    (registers/VMEM only, never HBM)."""
    shape = (w, w * k) if transpose else (w * k, w)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    eq = (rows == cols * k + c) if not transpose else (cols == rows * k + c)
    return eq.astype(jnp.float32)


def _select_dot(a: jax.Array, sel: jax.Array) -> jax.Array:
    """`a @ sel` for a 0/1 selection matrix, exact in fp32: each output
    element is one product, but only at HIGHEST precision — the MXU's
    default rounds f32 operands to bf16, which would quantize the logits
    the loss reads."""
    return jnp.dot(a, sel, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _gather_c(flat: jax.Array, k: int, c: int) -> jax.Array:
    """(h, w*k) -> channel c as (h, w) via the selection matmul."""
    return _select_dot(flat, _select_mat(flat.shape[-1] // k, k, c))


def _scatter_c(d: jax.Array, k: int, c: int) -> jax.Array:
    """(h, w) channel-c cotangent -> (h, w*k) flattened layout."""
    return _select_dot(d, _select_mat(d.shape[-1], k, c, transpose=True))


def _rowsum(t: jax.Array) -> jax.Array:
    """(h, w) -> (1, w): the kernel leaves the lane reduction to XLA, so
    nothing scalar leaves the vector unit."""
    return jnp.sum(t, axis=0, keepdims=True)


def _fwd_kernel(out_ref, heat_ref, off_ref, wh_ref, mask_ref, sums_ref, *,
                num_cls: int, alpha: float, beta: float, normalized: bool):
    """One (sample, stack): channels-last flattened maps -> a (4, W) tile
    of per-column partial sums, rows (focal pos, focal neg, offset-L1,
    size-L1).

    pos/neg are the focal-loss positive/negative log terms summed over
    (H, C) (pre-negation, pre-normalization — the tiny XLA epilogue in
    `fused_detection_loss` sums the W columns and applies batch mean and
    num_pos); the L1 rows are the masked-L1 sums over (H, 2)."""
    c = num_cls
    k = c + 4
    x = out_ref[...].astype(jnp.float32)      # (H, W*K) raw logits
    gh = heat_ref[...].astype(jnp.float32)    # (H, W*C)
    go = off_ref[...].astype(jnp.float32)     # (H, W*2)
    gw = wh_ref[...].astype(jnp.float32)      # (H, W*2)
    m = mask_ref[...].astype(jnp.float32)     # (H, W)
    zero = jnp.zeros((1, m.shape[-1]), jnp.float32)
    pos, neg, offl, whl = zero, zero, zero, zero
    for ch in range(c):
        p = jax.nn.sigmoid(_gather_c(x, k, ch))
        g = _gather_c(gh, c, ch)
        pos += _rowsum(jnp.log(p + _EPS) * _pow(1.0 - p, alpha) * m)
        neg += _rowsum(jnp.log(1.0 - p + _EPS) * _pow(p, alpha)
                       * _pow(1.0 - g, beta) * (1.0 - m))
    for j in range(2):
        po = _gather_c(x, k, c + j)
        pw = _gather_c(x, k, c + 2 + j)
        if normalized:
            po = jax.nn.sigmoid(po)
            pw = jax.nn.sigmoid(pw)
        offl += _rowsum(jnp.abs(po * m - _gather_c(go, 2, j) * m))
        whl += _rowsum(jnp.abs(pw * m - _gather_c(gw, 2, j) * m))
    sums_ref[0:1, :] = pos
    sums_ref[1:2, :] = neg
    sums_ref[2:3, :] = offl
    sums_ref[3:4, :] = whl


def _bwd_kernel(cot_ref, out_ref, heat_ref, off_ref, wh_ref, mask_ref,
                dout_ref, *, num_cls: int, alpha: float, beta: float,
                normalized: bool):
    """One pass: recompute forward terms, write d(out) for one (b, s).

    Cotangents arrive as four scalars per (sample, stack) in SMEM — the
    epilogue's mean/normalize factors folded in by XLA autodiff outside
    the kernel. The per-channel (H, W) cotangents scatter back into the
    flattened channels-last layout through the transposed selection
    matmuls."""
    c = num_cls
    k = c + 4
    i, j = pl.program_id(0), pl.program_id(1)
    gp, gn, gof, gwh = (cot_ref[i, 4 * j + q] for q in range(4))
    x = out_ref[...].astype(jnp.float32)
    gh = heat_ref[...].astype(jnp.float32)
    go = off_ref[...].astype(jnp.float32)
    gw = wh_ref[...].astype(jnp.float32)
    m = mask_ref[...].astype(jnp.float32)
    dout = jnp.zeros(x.shape, jnp.float32)
    for ch in range(c):
        p = jax.nn.sigmoid(_gather_c(x, k, ch))
        g = _gather_c(gh, c, ch)
        # d(pos)/dp and d(neg)/dp of the focal log terms (pre-negation)
        dpos = (_pow(1.0 - p, alpha) / (p + _EPS)
                - alpha * _pow(1.0 - p, alpha - 1.0)
                * jnp.log(p + _EPS)) * m
        dneg = (-_pow(p, alpha) / (1.0 - p + _EPS)
                + alpha * _pow(p, alpha - 1.0)
                * jnp.log(1.0 - p + _EPS)) \
            * _pow(1.0 - g, beta) * (1.0 - m)
        d = (gp * dpos + gn * dneg) * p * (1.0 - p)
        dout += _scatter_c(d, k, ch)
    for q in range(2):
        po = _gather_c(x, k, c + q)
        pw = _gather_c(x, k, c + 2 + q)
        if normalized:
            so = jax.nn.sigmoid(po)
            sw = jax.nn.sigmoid(pw)
            d_o = gof * _dabs(so * m - _gather_c(go, 2, q) * m) * m \
                * so * (1.0 - so)
            d_w = gwh * _dabs(sw * m - _gather_c(gw, 2, q) * m) * m \
                * sw * (1.0 - sw)
        else:
            d_o = gof * _dabs(po * m - _gather_c(go, 2, q) * m) * m
            d_w = gwh * _dabs(pw * m - _gather_c(gw, 2, q) * m) * m
        dout += _scatter_c(d_o, k, c + q)
        dout += _scatter_c(d_w, k, c + 2 + q)
    dout_ref[...] = dout


@functools.lru_cache(maxsize=None)
def _make_loss_sums(num_cls: int, alpha: float, beta: float,
                    normalized: bool, interpret: bool):
    """custom_vjp'd (out_f, heat_f, off_f, wh_f, mask2) -> (B, S, 4) sums.

    All static knobs are baked per cache entry so the custom_vjp function
    itself takes ARRAYS ONLY (no nondiff plumbing). Inputs are the
    bitcast-flattened channels-last maps built by
    `fused_stack_loss_sums`. The batch leads every operand and result, so
    under a device mesh each chip runs its own samples
    (`batch_parallel`)."""
    kw = dict(num_cls=num_cls, alpha=alpha, beta=beta,
              normalized=normalized)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))

    def map_specs(s, h, w, wk):
        # grid = (B, S): i walks samples, j walks stacks
        per_sample = lambda i, j: (i, 0, 0)  # noqa: E731
        return [
            pl.BlockSpec((None, None, h, wk), lambda i, j: (i, j, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, h, w * num_cls), per_sample,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, h, w * 2), per_sample,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, h, w * 2), per_sample,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, h, w), per_sample,
                         memory_space=pltpu.VMEM),
        ]

    def fwd_call(out_f, heat_f, off_f, wh_f, mask2):
        b, s, h, wk = out_f.shape
        w = mask2.shape[-1]
        tiles = pl.pallas_call(
            functools.partial(_fwd_kernel, **kw),
            grid=(b, s),
            in_specs=map_specs(s, h, w, wk),
            out_specs=pl.BlockSpec((None, None, 4, w),
                                   lambda i, j: (i, j, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b, s, 4, w), jnp.float32),
            compiler_params=params,
            interpret=interpret,
            name="detection_loss_fwd",
        )(out_f, heat_f, off_f, wh_f, mask2)
        return jnp.sum(tiles, axis=-1)

    def bwd_call(cot, out_f, heat_f, off_f, wh_f, mask2):
        b, s, h, wk = out_f.shape
        w = mask2.shape[-1]
        return pl.pallas_call(
            functools.partial(_bwd_kernel, **kw),
            grid=(b, s),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + map_specs(s, h, w, wk),
            out_specs=pl.BlockSpec((None, None, h, wk),
                                   lambda i, j: (i, j, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b, s, h, wk), jnp.float32),
            compiler_params=params,
            interpret=interpret,
            name="detection_loss_bwd",
        )(cot, out_f, heat_f, off_f, wh_f, mask2)

    every = [True] * 5

    @jax.custom_vjp
    def loss_sums(*maps):
        return batch_parallel(fwd_call, every)(*maps)

    def loss_sums_fwd(*maps):
        return batch_parallel(fwd_call, every)(*maps), maps

    def loss_sums_bwd(res, cotangent):
        out_f, heat_f, off_f, wh_f, mask2 = res
        b, s = out_f.shape[:2]
        cot = cotangent.astype(jnp.float32).reshape(b, s * 4)
        dout = batch_parallel(bwd_call, [True] + every)(cot, *res)
        # gt/mask are labels — their cotangents are dead code at every call
        # site (nothing differentiates w.r.t. targets); zeros are DCE'd.
        return (dout.astype(out_f.dtype), jnp.zeros_like(heat_f),
                jnp.zeros_like(off_f), jnp.zeros_like(wh_f),
                jnp.zeros_like(mask2))

    loss_sums.defvjp(loss_sums_fwd, loss_sums_bwd)
    return loss_sums


def fused_stack_loss_sums(out: jax.Array, gt_heat: jax.Array,
                          gt_off: jax.Array, gt_wh: jax.Array,
                          mask: jax.Array, *, focal_alpha: float = 2.0,
                          focal_beta: float = 4.0, normalized: bool = False,
                          interpret: bool | None = None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]:
    """Per-(stack, sample) loss partial sums from the RAW stack output.

    out: (B, S, H, W, C+4) raw logits (pre-sigmoid, as the model emits);
    gt_heat (B, H, W, C), gt_off/gt_wh (B, H, W, 2), mask (B, H, W, 1).
    Returns (pos, neg, off_l1, wh_l1), each (S, B) float32 — the sums of
    `ops/loss.py`'s focal log terms and masked L1s before batch mean and
    positive-count normalization. Differentiable w.r.t. `out` only.
    """
    if interpret is None:
        interpret = not select.on_chip()
    num_cls = gt_heat.shape[-1]
    b, s, h, w, k = out.shape
    # free reshapes only: merging the two minor dims of a channels-last
    # row-major array is a bitcast
    out_f = out.reshape(b, s, h, w * k)
    heat_f = gt_heat.reshape(b, h, w * num_cls)
    off_f = gt_off.reshape(b, h, w * 2)
    wh_f = gt_wh.reshape(b, h, w * 2)
    mask2 = mask.reshape(b, h, w).astype(jnp.float32)
    fn = _make_loss_sums(int(num_cls), float(focal_alpha),
                         float(focal_beta), bool(normalized),
                         bool(interpret))
    sums = fn(out_f, heat_f, off_f, wh_f, mask2)      # (B, S, 4)
    return tuple(sums[..., q].T for q in range(4))


def fused_detection_loss(out: jax.Array, gt_heat: jax.Array,
                         gt_off: jax.Array, gt_wh: jax.Array,
                         mask: jax.Array, *, hm_weight: float = 1.0,
                         offset_weight: float = 1.0,
                         size_weight: float = 0.1,
                         focal_alpha: float = 2.0, focal_beta: float = 4.0,
                         normalized_coord: bool = False,
                         interpret: bool | None = None
                         ) -> Dict[str, jax.Array]:
    """Deep-supervision detection loss over ALL stacks, fused.

    Drop-in equal to summing `ops.loss.detection_loss` over the per-stack
    split predictions (train.loss_fn's XLA path): returns the same
    {'hm', 'offset', 'size', 'total'} scalars, summed over stacks, with
    the reference reductions (per-sample sum, batch mean, global
    positive-count normalization).
    """
    pos, neg, off, wh = fused_stack_loss_sums(
        out, gt_heat, gt_off, gt_wh, mask, focal_alpha=focal_alpha,
        focal_beta=focal_beta, normalized=normalized_coord,
        interpret=interpret)
    num_pos = jnp.clip(jnp.sum(mask.astype(jnp.float32)), 1.0, 1e30)
    hm = -(jnp.mean(pos, axis=1) + jnp.mean(neg, axis=1)) / num_pos  # (S,)
    off_l = jnp.mean(off, axis=1) / num_pos
    size_l = jnp.mean(wh, axis=1) / num_pos
    hm_t, off_t, size_t = jnp.sum(hm), jnp.sum(off_l), jnp.sum(size_l)
    total = hm_t * hm_weight + off_t * offset_weight + size_t * size_weight
    return {"hm": hm_t, "offset": off_t, "size": size_t, "total": total}
