"""Heatmap -> boxes decoding, fully jit-able with static shapes.

Capability parity with the reference decoder (/root/reference/transform.py:73-110
`hm2box`): 3x3 max-pool peak test, top-k over the flat (C, H, W) scores,
offset/size gather, un-normalization, box reconstruction, confidence
thresholding. The top-k is the reference's flat one in values, indices and
tie order, selected in two levels (`top_k_exact`) so that the device never
sorts the whole map.

TPU-first differences:
  * channels-last `(H, W, C)` inputs;
  * **fixed output shapes**: always returns `topk` boxes plus a validity mask
    (`score >= conf_th`) instead of boolean-filtering to a data-dependent
    length — the mask is applied downstream (NMS is masked too, and the
    final txt writer filters host-side);
  * the peak test + top-k is the designated fusion target for a Pallas TPU
    kernel (planned: `ops/pallas/`); this module is the XLA path it will be
    benchmarked against.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Detections(NamedTuple):
    """Fixed-size decoded detections for one image."""
    boxes: jax.Array   # (topk, 4) xyxy at image scale
    classes: jax.Array  # (topk,) int32
    scores: jax.Array  # (topk,) float32
    valid: jax.Array   # (topk,) bool — score >= conf_th


class CascadeDetections(NamedTuple):
    """`Detections` plus the scalar cascade-escalation confidence.

    Same leaves as `Detections` with one extra per-image float32 scalar
    (batched: `(B,)`), so the serving engine's generic per-row fetch split
    transports it with zero extra D2H — the confidence rides the one
    box-block `device_get` (docs/ARCHITECTURE.md "Cascade serving").
    """
    boxes: jax.Array    # (topk, 4) xyxy at image scale
    classes: jax.Array  # (topk,) int32
    scores: jax.Array   # (topk,) float32
    valid: jax.Array    # (topk,) bool — score >= conf_th
    confidence: jax.Array  # () float32 — cascade escalation confidence

    def detections(self) -> Detections:
        """The plain `Detections` view (drops the cascade scalar)."""
        return Detections(boxes=self.boxes, classes=self.classes,
                          scores=self.scores, valid=self.valid)


# How deep the peak-margin looks: margin = top1 - (MARGIN_K-th best valid
# score). Fixed (not a flag) so every calibrated threshold artifact refers to
# the same signal definition.
MARGIN_K = 8


def confidence_summary(scores: jax.Array, valid: jax.Array,
                       margin_k: int = MARGIN_K) -> jax.Array:
    """Scalar cascade confidence for one image's masked detections.

    Combines the three signals from the fixed-shape `Detections` block
    (masks, never boolean filtering):

      top1   = best valid score (0 when the image has no valid detection);
      margin = top1 minus the `margin_k`-th best valid score — small when
               many near-tied peaks compete (cluttered / ambiguous scene);
      frac   = valid-detection count / topk — busy scenes are the ones the
               edge tier is most likely to get wrong.

    confidence = top1 + margin - frac, a strictly monotone blend in each
    signal; the absolute scale is irrelevant because the escalation
    threshold is calibrated against this exact definition
    (`quality_matrix --cascade`). Escalate when confidence < threshold.
    """
    masked = jnp.where(valid, scores, 0.0)
    k = min(int(margin_k), masked.shape[-1])
    top = jax.lax.top_k(masked, k)[0]
    top1 = top[..., 0]
    margin = top1 - top[..., k - 1]
    frac = jnp.mean(valid.astype(jnp.float32), axis=-1)
    return (top1 + margin - frac).astype(jnp.float32)


def peak_mask(heatmap: jax.Array, pool_size: int = 3) -> jax.Array:
    """pool_size x pool_size max-pool equality peak test
    (ref transform.py:76-79; the reference parses `--pool-size` but
    hard-codes 3 — here the flag actually works, SURVEY.md §5 dead flags).

    heatmap: (..., H, W, C) channels-last, any number of leading batch dims.
    Returns bool mask of local maxima (ties with the neighborhood max count
    as peaks, matching `==`).
    """
    lead = heatmap.ndim - 3
    p = (pool_size - 1) // 2
    pooled = jax.lax.reduce_window(
        heatmap, -jnp.inf, jax.lax.max,
        window_dimensions=(1,) * lead + (pool_size, pool_size, 1),
        window_strides=(1,) * (lead + 3),
        padding=((0, 0),) * lead + ((p, p), (p, p), (0, 0)))
    return pooled == heatmap


def chunk_length(n: int, k: int) -> int:
    """Chunk length of `top_k_exact`'s two levels, from the shape alone.

    The levels sort `n / L + k * L` keys, least near `L = sqrt(n / k)`: the
    largest power of two not above it that divides `n`. 0 (the direct call)
    where that is under 4: the two levels would sort more than half of `n`.
    """
    if n < 16 * k:
        return 0
    chunk = 1 << ((n // k).bit_length() - 1) // 2
    while n % chunk:
        chunk //= 2
    return chunk if chunk >= 4 else 0


def top_k_exact(flat: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """`jax.lax.top_k(flat, k)` of a 1-D array, values and indices, from two
    small selections instead of one sort of every key (which is what
    `lax.top_k` is on the TPU).

    The `k` chunks of `chunk_length` contiguous scores with the largest
    maxima are picked, put back in index order, and the top-k taken among
    their `k * L` scores. Exact, ties included: `top_k` orders by (value
    descending, index ascending); were a top-k element's chunk not picked,
    the `k` chunks before it by (maximum descending, chunk ascending) would
    each hold an element at or above it with a lower index where equal, `k`
    elements before it. Candidates stand in index order, so the second
    stable `top_k` breaks ties as the first would. Shapes too small for two
    levels (`chunk_length` 0: the toy maps) take the direct call.
    """
    n = flat.shape[0]
    chunk = chunk_length(n, k)
    if not chunk:
        return jax.lax.top_k(flat, k)
    chunks = flat.reshape(n // chunk, chunk)
    picked = jnp.sort(jax.lax.top_k(chunks.max(axis=1), k)[1])
    scores, at = jax.lax.top_k(chunks[picked].reshape(-1), k)
    return scores, picked[at // chunk] * chunk + at % chunk


@partial(jax.jit, static_argnames=("scale_factor", "topk", "normalized"))
def decode_peak_scores(peaks: jax.Array, offset: jax.Array, wh: jax.Array,
                       scale_factor: int = 4, topk: int = 100,
                       conf_th: float = 0.3, normalized: bool = False) -> Detections:
    """Decode pre-masked peak scores into top-k boxes.

    `peaks` is the (H, W, C) map where non-peak cells are already zeroed
    (e.g. the output of the fused Pallas kernel `ops.pallas.fused_peak_scores`
    or the XLA peak test in `decode_heatmap`). Remaining steps: top-k of the
    flat class-major scores, gather, un-normalize, box reconstruction (ref
    transform.py:81-110). The top-k is `top_k_exact`: the `topk` chunks with
    the largest maxima, then the top-k among their scores, which is
    `lax.top_k`'s answer in values, indices and tie order (zeros among
    fewer than `topk` peaks are ties like any other) without a sort of the
    whole map; maps under 16 scores a kept one take `lax.top_k` itself.
    """
    height, width, num_cls = peaks.shape

    # Flatten class-major (C, H, W) to match the reference's index layout
    # (class = idx // (H*W)), keeping tie-break ordering identical.
    flat = peaks.transpose(2, 0, 1).reshape(-1)
    scores, indices = top_k_exact(flat, topk)

    clss = indices // (height * width)
    inds = indices % (height * width)
    yinds = inds // width
    xinds = inds % width

    xoffs = offset[yinds, xinds, 0]
    yoffs = offset[yinds, xinds, 1]
    xsizs = wh[yinds, xinds, 0]
    ysizs = wh[yinds, xinds, 1]

    if normalized:
        xoffs = xoffs * scale_factor
        yoffs = yoffs * scale_factor
        xsizs = xsizs * width
        ysizs = ysizs * height

    xf = xinds.astype(jnp.float32) + xoffs
    yf = yinds.astype(jnp.float32) + yoffs
    sf = float(scale_factor)
    boxes = jnp.stack([
        (xf - xsizs / 2) * sf,
        (yf - ysizs / 2) * sf,
        (xf + xsizs / 2) * sf,
        (yf + ysizs / 2) * sf,
    ], axis=1)

    valid = scores >= conf_th
    return Detections(boxes=boxes, classes=clss.astype(jnp.int32),
                      scores=scores, valid=valid)


@partial(jax.jit, static_argnames=("scale_factor", "topk", "normalized",
                                   "pool_size"))
def decode_heatmap(heatmap: jax.Array, offset: jax.Array, wh: jax.Array,
                   scale_factor: int = 4, topk: int = 100,
                   conf_th: float = 0.3, normalized: bool = False,
                   pool_size: int = 3) -> Detections:
    """Decode one image's maps into top-k boxes.

    Args:
      heatmap: (H, W, C) post-sigmoid class heatmap.
      offset: (H, W, 2) center offsets (x, y).
      wh: (H, W, 2) box sizes (w, h).
      scale_factor: map -> image upsample factor.
      topk: number of peaks to keep (static).
      conf_th: confidence threshold, applied as the `valid` mask.
      normalized: if True, un-normalize offsets (*scale_factor) and sizes
        (*map width/height) as in the reference.
      pool_size: peak-test window (static).

    Returns a `Detections` with static shapes.
    """
    peaks = jnp.where(peak_mask(heatmap, pool_size), heatmap, 0.0)
    return decode_peak_scores(peaks, offset, wh, scale_factor=scale_factor,
                              topk=topk, conf_th=conf_th,
                              normalized=normalized)
