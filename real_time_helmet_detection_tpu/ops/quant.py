"""Inference compression: BN folding + post-training int8 quantization.

The reference has no inference-compression path of any kind (it serves the
fp32 training graph through TorchScript, ref /root/reference/export.py:55);
this module is the precision half of the "as fast as the hardware allows"
north star: the v5e's int8 MXU path has 2x the bf16 peak (394 TOPS vs
197 TFLOPS), and PR 2's roofline table proved the predict step is owned by
the convolutions — numeric compression of exactly those convs is the
largest remaining single-chip lever.

Three stages, all pure pytree/jnp math (jit-able, CPU-provable):

* `fold_batchnorm(params, batch_stats)` — algebraic BN fold. Every
  BatchNorm in this architecture sits directly after a conv inside a
  `Convolution` block (models/hourglass.py), so
      y = g * (conv(x) + b - mu) / sqrt(v + eps) + beta
  folds exactly into
      kernel' = kernel * (g / sqrt(v + eps))   [broadcast on out-channel]
      bias'   = (b - mu) * (g / sqrt(v + eps)) + beta
  producing the param pytree of the `fold_bn=True` model twin (same
  `Conv_0` names, BatchNorm entries gone). Fold-then-predict is allclose
  to the training graph (tests/test_quant.py pins fp32 atol 1e-4) and
  removes ALL BatchNorm work from the predict program — the prerequisite
  for weight quantization (the fold must happen BEFORE scales are
  computed, or the folded multiplier would silently rescale the
  quantization grid).

* `quantize_weights(kernel)` — per-output-channel symmetric int8:
  scale_c = absmax over (kh, kw, cin) / 127, q = round(k / scale_c) in
  [-127, 127]. Per-channel (not per-tensor) because the folded BN
  multipliers spread channel magnitudes over orders of magnitude; the
  round-off is bounded by scale_c/2 per channel (tested).

* activation calibration — `calibrate_scales` runs a jitted instrumented
  forward (the `quant_mode="calibrate"` model twin) over N calibration
  batches; each conv records the abs-max (or an upper percentile) of its
  INPUT into the `quant` collection, so one batch costs ONE dispatch and
  fetches only per-layer scalars (a histogram fetch per layer would be
  a D2H per layer per batch). The host
  max-reduces across batches and the result is the scales pytree the
  `quant_mode="int8"` model consumes, persisted as an atomic artifact
  (`save_scales`, sha256-hashed so export metadata can pin the exact
  calibration run).

The quantized conv itself lives in models/hourglass.py (`QuantConv`):
int8 x int8 `lax.conv_general_dilated` with
`preferred_element_type=int32`, then a bf16 rescale `(s_a * s_w)` + bias.
At TRAIN time the same algebra powers `--fwd-dtype int8` (ISSUE 20):
`make_ste_conv` below runs an eligible conv's forward on the int8 MXU
path with a PER-STEP in-jit abs-max scale refresh and differentiates the
float conv twin through a straight-through estimator — no persisted
scale state, no calibration pass (decision tables:
docs/ARCHITECTURE.md "Step compression" / "Inference compression").
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
from typing import Any, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5  # models/hourglass.py Convolution's nn.BatchNorm epsilon

# floors keeping the int8 grids well-defined on degenerate inputs (an
# all-zero calibration batch, a dead channel): a zero scale would divide
# by zero inside the jitted program
_SCALE_FLOOR = 1e-8


# ---------------------------------------------------------------------------
# BN folding


def _is_mapping(x) -> bool:
    return isinstance(x, dict) or hasattr(x, "items") and not hasattr(x, "shape")


def fold_batchnorm(params, batch_stats, eps: float = BN_EPS):
    """Fold every BatchNorm into its preceding conv's kernel/bias.

    `params`/`batch_stats` are the checkpoint pytrees of the training
    model; returns the params pytree of the `fold_bn=True` inference twin
    (BatchNorm subtrees dropped, every folded `Conv_0` gains a bias).
    Pure jnp tree math: call it eagerly for tests or INSIDE the jitted
    predict program (the production path — the fold costs O(params) FLOPs
    once per dispatch and keeps the artifact contract "same checkpoint
    pytree in").

    Only the `Conv_0`+`BatchNorm_0` sibling pattern of this
    architecture's `Convolution` block is folded; a BatchNorm without a
    conv sibling fails loudly rather than silently keeping
    un-normalized activations.
    """
    def fold(p: Dict, s) -> Dict:
        s = s if _is_mapping(s) else {}
        out = {}
        if "BatchNorm_0" in p:
            if "Conv_0" not in p:
                raise ValueError(
                    "BatchNorm_0 without a Conv_0 sibling: fold_batchnorm "
                    "only understands the Convolution block layout "
                    "(models/hourglass.py); keys: %r" % sorted(p))
            bn = p["BatchNorm_0"]
            st = s.get("BatchNorm_0", {})
            if "mean" not in st or "var" not in st:
                raise ValueError(
                    "batch_stats missing mean/var for a BatchNorm_0 "
                    "(keys: %r) — pass the checkpoint's batch_stats "
                    "collection" % sorted(st))
            kernel = jnp.asarray(p["Conv_0"]["kernel"])
            conv_bias = jnp.asarray(p["Conv_0"].get(
                "bias", jnp.zeros((kernel.shape[-1],), kernel.dtype)))
            gamma = jnp.asarray(bn.get(
                "scale", jnp.ones((kernel.shape[-1],), kernel.dtype)))
            beta = jnp.asarray(bn.get(
                "bias", jnp.zeros((kernel.shape[-1],), kernel.dtype)))
            inv = gamma * jax.lax.rsqrt(jnp.asarray(st["var"],
                                                    jnp.float32) + eps)
            inv = inv.astype(kernel.dtype)
            out["Conv_0"] = {
                "kernel": kernel * inv,  # broadcast on the HWIO out axis
                "bias": (conv_bias - jnp.asarray(st["mean"],
                                                 kernel.dtype)) * inv + beta,
            }
        for key, val in p.items():
            if key in ("BatchNorm_0",) or key in out:
                continue
            out[key] = fold(val, s.get(key)) if _is_mapping(val) else val
        return out

    return fold(_plain_dict(params), _plain_dict(batch_stats))


def _plain_dict(tree):
    """FrozenDict-tolerant deep copy to plain dicts (leaves untouched)."""
    if _is_mapping(tree):
        return {k: _plain_dict(v) for k, v in tree.items()}
    return tree


# ---------------------------------------------------------------------------
# weight quantization


def quantize_weights(kernel: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 quantization of an HWIO kernel.

    Returns `(q int8 (kh, kw, cin, cout), scale float32 (cout,))` with
    `q * scale ~= kernel`, `|q| <= 127` and per-channel round-off bounded
    by `scale/2` (tests pin the bound). Pure jnp — runs inside the jitted
    predict program so the artifact contract stays "checkpoint pytree +
    scales pytree in, nothing else".
    """
    kernel = jnp.asarray(kernel, jnp.float32)
    absmax = jnp.max(jnp.abs(kernel), axis=tuple(range(kernel.ndim - 1)))
    scale = jnp.maximum(absmax, _SCALE_FLOOR) / 127.0
    q = jnp.clip(jnp.round(kernel / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_activations(x: jax.Array, absmax: jax.Array) -> Tuple[jax.Array,
                                                                   jax.Array]:
    """Symmetric per-tensor int8 activation quantization against a
    calibrated clip range. Returns `(q int8, scale float32 scalar)` with
    `q * scale ~= clip(x, -absmax, absmax)`."""
    scale = jnp.maximum(jnp.asarray(absmax, jnp.float32), _SCALE_FLOOR) \
        / 127.0
    q = jnp.clip(jnp.round(jnp.asarray(x, jnp.float32) / scale),
                 -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# int8-forward training (--fwd-dtype int8, ISSUE 20)


@functools.lru_cache(maxsize=None)
def make_ste_conv(stride: int, padding: int, groups: int):
    """custom_vjp'd `(x, kernel) -> conv(x, kernel)` whose FORWARD runs
    int8 x int8 -> int32 on the MXU and whose BACKWARD differentiates the
    float conv twin (a straight-through estimator through the
    quantize/dequantize round trip).

    Forward: the activation clip range is the batch's own abs-max,
    recomputed IN-JIT every step (the "per-step scale refresh") — unlike
    the inference path there is no calibration artifact and no persisted
    scale state, so the train state trees, buffer donation and the D2H
    budget are byte-identical to the bf16 program. Weights quantize
    per-output-channel from the compute-dtype kernel each step
    (`quantize_weights`), activations per-tensor (`quantize_activations`);
    the rescale `acc * (s_a * s_w)` lands back in the compute dtype.

    Backward: `jax.vjp` of the float `lax.conv_general_dilated` with the
    SAME geometry — the STE treats round/clip as identity, so gradients
    are exactly the bf16 twin's. The float forward primal is dead code
    in both passes (the int8 path produces the primal; the conv VJP's
    residuals are the already-saved inputs) and XLA removes it.

    Static geometry baked per cache entry so the SAME function object is
    reused across traces (retrace-stable, graftlint layer 1).
    """
    dn = ("NHWC", "HWIO", "NHWC")
    pad = ((padding, padding), (padding, padding))

    def float_conv(x, kernel):
        return jax.lax.conv_general_dilated(
            x, kernel, (stride, stride), pad, dimension_numbers=dn,
            feature_group_count=groups)

    def int8_fwd(x, kernel):
        absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
        xq, a_scale = quantize_activations(x, absmax)
        wq, w_scale = quantize_weights(kernel)
        acc = jax.lax.conv_general_dilated(
            xq, wq, (stride, stride), pad, dimension_numbers=dn,
            preferred_element_type=jnp.int32,
            feature_group_count=groups)
        return acc.astype(x.dtype) * (a_scale * w_scale).astype(x.dtype)

    @jax.custom_vjp
    def ste_conv(x, kernel):
        return int8_fwd(x, kernel)

    def ste_fwd(x, kernel):
        # residuals are the ALREADY-materialized inputs — exactly what
        # the float conv's VJP needs, nothing extra crosses HBM
        return int8_fwd(x, kernel), (x, kernel)

    def ste_bwd(res, g):
        x, kernel = res
        _, vjp = jax.vjp(float_conv, x, kernel)
        return vjp(g)

    ste_conv.defvjp(ste_fwd, ste_bwd)
    return ste_conv


# ---------------------------------------------------------------------------
# activation-scale calibration


def make_quant_model(cfg, dtype=None, mode: str = "int8",
                     calib_percentile: float = 100.0):
    """The BN-folded model twin in a quantization mode ("calibrate" |
    "int8"); see models/hourglass.py for the mode semantics. The twin
    consumes `fold_batchnorm` params plus (in int8 mode) the scales
    pytree as the `quant` collection."""
    from ..models import build_model
    return build_model(cfg, dtype=dtype, fold_bn=True, quant_mode=mode,
                       calib_percentile=calib_percentile)


def make_calib_step(cfg, dtype=None, normalize: Optional[str] = None,
                    percentile: float = 100.0):
    """The un-jitted instrumented calibration step
    `(params, batch_stats, images, agg) -> quant stats pytree`.

    Exposed separately from `calibrate_scales` so the transfer audit
    (analysis/transfer_audit.py) can measure the max-combine program's
    device<->host surface abstractly: its whole output — the per-layer
    scalar pytree — IS the calibration pass's single D2H budget.
    """
    cmodel = make_quant_model(cfg, dtype=dtype, mode="calibrate",
                              calib_percentile=percentile)
    if normalize is not None:
        from ..utils import normalizer_stats
        mean, std = (jnp.asarray(s) for s in normalizer_stats(normalize))

    def calib_step(params, batch_stats, images, agg):
        if normalize is not None:
            images = (images.astype(jnp.float32) / 255.0 - mean) / std
        folded = fold_batchnorm(params, batch_stats)
        _, mut = cmodel.apply({"params": folded}, images, train=False,
                              mutable=["quant"])
        stats = mut["quant"]
        # agg=None is a static (empty-pytree) arg: the first batch traces
        # its own program, every later batch hits the max-combine trace
        if agg is None:
            return stats
        return jax.tree.map(jnp.maximum, agg, stats)

    return calib_step


def calibrate_scales(cfg, variables, batches: Iterable,
                     dtype=None, normalize: Optional[str] = None,
                     percentile: float = 100.0) -> Dict:
    """Run the instrumented forward over calibration batches; return the
    activation-scales pytree (the `quant` collection).

    `batches` yields (B, H, W, 3) arrays — normalized float32, or raw
    uint8/[0,255] pixels when `normalize` names a stats set (the same
    raw-wire contract as make_predict_fn). Each batch is ONE jitted
    dispatch; the running max-reduce across batches rides INSIDE the
    jitted step (the device-held `agg` carry), so the only D2H of the
    whole pass is the final per-layer-scalar fetch — no per-batch
    device_get. `percentile` < 100
    clips to that upper percentile of |x| instead of the abs-max
    (outlier-robust); the running reduce still max-combines the
    per-batch percentiles (conservative).
    """
    calib_step = jax.jit(make_calib_step(cfg, dtype=dtype,
                                         normalize=normalize,
                                         percentile=percentile))
    agg = None
    for images in batches:
        agg = calib_step(variables["params"], variables["batch_stats"],
                         jnp.asarray(images), agg)
    if agg is None:
        raise ValueError("calibrate_scales: no calibration batches given")
    agg = jax.device_get(agg)  # the pass's single D2H: per-layer scalars
    return jax.tree.map(
        lambda x: np.maximum(np.asarray(x, np.float32), _SCALE_FLOOR), agg)


# ---------------------------------------------------------------------------
# scales artifact (atomic, hashable — export metadata pins the hash)

SCALES_FORMAT = "quant-scales-v1"


def _scales_to_nested(scales) -> Dict:
    return jax.tree.map(lambda x: float(np.asarray(x)),
                        _plain_dict(scales))


def scales_hash(scales) -> str:
    """sha256 of the canonical JSON encoding — the identity export
    metadata records so a served artifact is traceable to its
    calibration run."""
    text = json.dumps(_scales_to_nested(scales), sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def save_scales(path: str, scales, meta: Optional[Dict] = None) -> str:
    """Persist the scales pytree atomically (tmp + os.replace, like every
    artifact — the export/eval paths trust any file they find here).
    Returns the sha256 hash of the scales content."""
    from ..utils import save_json
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    digest = scales_hash(scales)
    save_json(path, {"format": SCALES_FORMAT, "sha256": digest,
                     **(meta or {}), "scales": _scales_to_nested(scales)},
              indent=1, sort_keys=True)
    return digest


def load_scales(path: str) -> Dict:
    """Load a `save_scales` artifact back into a float32 pytree."""
    with open(path) as f:
        rec = json.load(f)
    if rec.get("format") != SCALES_FORMAT:
        raise ValueError("%s is not a %s artifact (format=%r)"
                         % (path, SCALES_FORMAT, rec.get("format")))
    return jax.tree.map(np.float32, rec["scales"])


def synthetic_calibration_batches(batch: int, imsize: int, n: int = 2,
                                  raw: bool = False, seed: int = 0):
    """Deterministic synthetic calibration inputs for contexts with no
    real data at hand (bench, export smoke, trace audit). Raw mode
    yields uint8 pixels (the raw-wire contract); else normalized-ish
    float32."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        if raw:
            yield rng.integers(0, 256, (batch, imsize, imsize, 3),
                               dtype=np.uint8)
        else:
            yield rng.standard_normal(
                (batch, imsize, imsize, 3)).astype(np.float32)
