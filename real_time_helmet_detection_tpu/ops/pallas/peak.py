"""Fused sigmoid + 3x3 peak-test Pallas TPU kernel.

The eval hot path's "NMS kernel" (SURVEY.md §2 #8): the reference computes
`sigmoid` then `MaxPool2d(3, stride=1, pad=1)` then an equality test then a
zero-fill (/root/reference/transform.py:76-79, evaluate.py:139) — four
HBM-bound elementwise/window passes in PyTorch. Here they fuse into ONE
VMEM-resident Pallas kernel:

* one grid step per class channel; the (H, W) map lives in VMEM
  (128x128 fp32 at 512-input = 64 KB, far under the ~16 MB budget);
* the 3x3 window max is built from 2 shifted row-maxes of a horizontal
  3-max (separable decomposition: 4 `jnp.maximum`s on the VPU instead of a
  9-tap window);
* the peak test runs on the *sigmoid* values, exactly as the production XLA
  path does (sigmoid first, then the window-max equality). Testing on raw
  logits would be mathematically equivalent but not float32-identical:
  sigmoid saturates, so distinct large logits can round to the same sigmoid
  value and the tie-counting `==` test then admits *more* peaks — the two
  paths must agree bit-for-bit for cross-platform reproducibility.

`fused_peak_scores` falls back to Pallas interpret mode off-TPU so the same
code path is testable on the CPU mesh (tests/test_pallas.py checks exact
agreement with the XLA reference implementation `peak_scores_reference`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import select

_NEG = -1e30  # python scalar: a jnp constant would be captured by the kernel


def peak_scores_reference(logits: jax.Array, pool_size: int = 3) -> jax.Array:
    """XLA reference: masked sigmoid peak scores.

    logits: (H, W, C) raw heatmap logits. Returns (H, W, C) where local
    maxima of the *sigmoid* map (pool_size x pool_size, ties count) carry
    their sigmoid score and all else is 0 — bit-identical to the production
    decode path (`jnp.where(peak_mask(sigmoid(x)), sigmoid(x), 0)`).
    """
    from ..decode import peak_mask
    heat = jax.nn.sigmoid(logits)
    return jnp.where(peak_mask(heat, pool_size), heat, 0.0)


def _shifted_max(x: jax.Array, axis: int, p: int) -> jax.Array:
    """(2p+1)-tap running max along `axis` with edge padding of -inf —
    2p VPU `maximum`s instead of a (2p+1)-tap reduce_window."""
    out = x
    for s in range(1, p + 1):
        pad = jnp.full(tuple(s if a == axis else d
                             for a, d in enumerate(x.shape)), _NEG)
        fwd = jnp.concatenate(
            [pad, jax.lax.slice_in_dim(x, 0, x.shape[axis] - s, axis=axis)],
            axis=axis)
        bwd = jnp.concatenate(
            [jax.lax.slice_in_dim(x, s, x.shape[axis], axis=axis), pad],
            axis=axis)
        out = jnp.maximum(out, jnp.maximum(fwd, bwd))
    return out


def _peak_kernel(x_ref, out_ref, *, p: int):
    """One class channel: (1, H, W) logits block -> masked sigmoid scores.

    The (2p+1)^2 window max is built separably: a horizontal (2p+1)-max
    followed by a vertical (2p+1)-max of it — 4p VPU `maximum`s on
    VMEM-resident data instead of a (2p+1)^2-tap window."""
    x = jax.nn.sigmoid(x_ref[0])  # (H, W); peak test in sigmoid space
    pooled = _shifted_max(_shifted_max(x, 1, p), 0, p)
    out_ref[0] = jnp.where(pooled == x, x, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret", "pool_size"))
def _fused_chw(logits_chw: jax.Array, interpret: bool = False,
               pool_size: int = 3) -> jax.Array:
    c, h, w = logits_chw.shape
    return pl.pallas_call(
        functools.partial(_peak_kernel, p=(pool_size - 1) // 2),
        grid=(c,),
        in_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((c, h, w), jnp.float32),
        interpret=interpret,
        name="peak_scores",  # the device event's name: what a trace keys on
    )(logits_chw.astype(jnp.float32))


def fused_peak_scores(logits: jax.Array, interpret: bool | None = None,
                      pool_size: int = 3) -> jax.Array:
    """Pallas-fused peak scores, channels-last in/out.

    logits: (H, W, C) raw heatmap logits -> (H, W, C) masked sigmoid scores.
    `interpret=None` auto-selects interpret mode off-TPU (testability).
    `pool_size` is the (odd) peak-test window; the separable-max kernel
    generalizes to any size (ref transform.py:76-79 parses `--pool-size`
    but hard-codes 3; here the flag is honored end to end).
    """
    if pool_size % 2 != 1 or pool_size < 1:
        raise ValueError("pool_size must be odd and >= 1, got %d" % pool_size)
    if interpret is None:
        interpret = not select.on_chip()
    chw = jnp.transpose(logits, (2, 0, 1))
    return jnp.transpose(_fused_chw(chw, interpret=interpret,
                                    pool_size=pool_size), (1, 2, 0))
