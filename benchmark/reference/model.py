"""Plain reference of the detector: `jax.numpy`, float32, matmul precision
HIGHEST, no Pallas, no flax, no engine. Imports nothing of the program.

Written from SURVEY.md section "ops/model" and PAPER.md (ref hourglass.py:94-237,
loss.py:18-69, transform.py:73-110, evaluate.py:114-243): a stacked hourglass
(stem -> per stack [hourglass(4) -> neck -> head], 1x1 merges between stacks),
CenterNet focal + masked-L1 loss summed over the stacks, Adam, and the decode
(sigmoid, 3x3 peak test, class-major top-k, box rebuild) with hard or Gaussian
soft NMS.

One walker (`Net`) serves three uses, so they cannot drift apart:

* `param_spec(cfg)` runs it on shapes only and records every parameter it
  asks for: path, shape and kind. The harness draws the weights from the seed
  against this list (benchmark/weights.py) and hands the same arrays to the
  program, after checking that the program's tree has the same paths.
* `forward(...)` computes with those weights.
* `benchmark/work/count.py` reads the convolutions and BatchNorm tails it
  records to count operations and bytes.

Parameter paths follow the program's checkpoint layout (`PreLayer_0/...`), which
is a file format, not code. Departures from the published description: none
known; the residual block's second conv reuses the block's stride as the
reference does (always 1 here).

`quant` selects the precision of every convolution's two operands, with a
straight-through gradient: "f32" (the reference), "bf16" (operands rounded to
bfloat16: the arithmetic the configuration states, used to plant faults at the
stated precision) and "fp8" (e4m3: 4 exponent and 3 mantissa bits, each
tensor scaled so that its largest magnitude is e4m3's 240: the control, the
nearest floating precision below bfloat16).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


def _ste(x, rounded):
    return x + lax.stop_gradient(rounded - x)


def quantizer(mode: str) -> Optional[Callable]:
    if mode == "f32":
        return None
    # lax.reduce_precision, not a cast there and back: the compiler is
    # allowed to keep excess precision and drops such a pair of casts
    if mode == "bf16":
        return lambda x: _ste(x, lax.reduce_precision(x, 8, 7))
    if mode == "fp8":
        def q(x):
            s = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return _ste(x, lax.reduce_precision(x * s, 4, 3) / s)
        return q
    raise ValueError("quant must be f32 | bf16 | fp8, got %r" % (mode,))


class Net:
    """The walker. `get(path, shape, kind)` returns one parameter array.
    `train` selects batch statistics (True) or the running ones (False).
    `convs` / `tails`, when lists, record each convolution and BatchNorm tail
    with its shapes (for benchmark/work); `stats`, when a dict, collects
    each BatchNorm's batch mean and variance under its running-statistics
    path."""

    def __init__(self, cfg: dict, get: Callable, train: bool,
                 quant: str = "f32", remat: bool = False,
                 convs: Optional[list] = None, tails: Optional[list] = None,
                 stats: Optional[dict] = None):
        if cfg.get("variant", "residual") != "residual":
            raise NotImplementedError("the reference covers the residual "
                                      "variant only")
        for key, want in (("increase_ch", 0), ("activation", "ReLU"),
                          ("pool", "Max"), ("neck_activation", "ReLU"),
                          ("neck_pool", "None")):
            if cfg.get(key, want) != want:
                raise NotImplementedError("reference: %s=%r not covered"
                                          % (key, cfg[key]))
        self.cfg, self.get, self.train = cfg, get, train
        self.q = quantizer(quant)
        self.remat = remat
        self.convs, self.tails, self.stats = convs, tails, stats

    # -- primitives ---------------------------------------------------------

    def conv(self, path, x, cout, k, stride=1, bias=False,
             bias_kind="bias"):
        cin = x.shape[-1]
        w = self.get(path + "/Conv_0/kernel", (k, k, cin, cout), "kernel")
        if self.q is not None:
            x, w = self.q(x), self.q(w)
        p = (k - 1) // 2
        y = lax.conv_general_dilated(
            x, w, (stride, stride), ((p, p), (p, p)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        if self.convs is not None:
            self.convs.append(dict(path=path, k=k, cin=cin, cout=cout,
                                   out_hw=(y.shape[1], y.shape[2]),
                                   first=path == "PreLayer_0/Convolution_0"))
        if bias:
            y = y + self.get(path + "/Conv_0/bias", (cout,), bias_kind)
        return y

    def bn(self, path, x, act: bool, skip=None):
        c = x.shape[-1]
        gamma = self.get(path + "/BatchNorm_0/scale", (c,), "bn_scale")
        beta = self.get(path + "/BatchNorm_0/bias", (c,), "bn_bias")
        if self.train:
            mean = jnp.mean(x, axis=(0, 1, 2))
            var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
            if self.stats is not None:
                self.stats[path + "/BatchNorm_0/mean"] = mean
                self.stats[path + "/BatchNorm_0/var"] = var
        else:
            mean = self.get(path + "/BatchNorm_0/mean", (c,), "bn_mean")
            var = self.get(path + "/BatchNorm_0/var", (c,), "bn_var")
        y = (x - mean) * lax.rsqrt(var + BN_EPS) * gamma + beta
        if skip is not None:
            y = y + skip
        if self.tails is not None:
            self.tails.append(dict(path=path, shape=tuple(x.shape[1:]),
                                   add=skip is not None, act=act))
        return jax.nn.relu(y) if act else y

    def residual(self, path, x, cout):
        def block(x):
            y = self.bn(path + "/Convolution_0",
                        self.conv(path + "/Convolution_0", x, cout, 3), True)
            y = self.conv(path + "/Convolution_1", y, cout, 3)
            skip = x
            if x.shape[-1] != cout:
                skip = self.bn(path + "/Convolution_2",
                               self.conv(path + "/Convolution_2", x, cout, 1),
                               False)
            return self.bn(path + "/Convolution_1", y, True, skip=skip)
        # the float32 reference of a 32-image step keeps only block inputs
        # for the backward pass, so that it fits beside nothing else
        return jax.checkpoint(block)(x) if self.remat else block(x)

    @staticmethod
    def pool2(x):
        b, h, w, c = x.shape
        return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))

    @staticmethod
    def up2(x):
        return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)

    def hourglass(self, path, x, depth, ch):
        up1 = self.residual(path + "/Residual_0", x, ch)
        low = self.residual(path + "/Residual_1", self.pool2(x), ch)
        if depth > 1:
            low = self.hourglass(path + "/Hourglass_0", low, depth - 1, ch)
            last = "/Residual_2"
        else:
            low = self.residual(path + "/Residual_2", low, ch)
            last = "/Residual_3"
        low = self.residual(path + last, low, ch)
        return up1 + self.up2(low)

    # -- the network --------------------------------------------------------

    def __call__(self, images):
        cfg = self.cfg
        ch, stacks = int(cfg["hourglass_inch"]), int(cfg["num_stack"])
        out_ch = int(cfg["num_cls"]) + 4
        stem = int(cfg.get("stem_width", 0)) or 128
        p = "PreLayer_0"

        def stem_conv(x):
            return self.bn(p + "/Convolution_0",
                           self.conv(p + "/Convolution_0", x, 64, 7, stride=2,
                                     bias=True), True)
        x = (jax.checkpoint(stem_conv) if self.remat else stem_conv)(images)
        x = self.residual(p + "/Residual_0", x, stem)
        x = self.pool2(x)
        x = self.residual(p + "/Residual_1", x, stem)
        x = self.residual(p + "/Residual_2", x, ch)
        preds = []
        for i in range(stacks):
            hg = self.hourglass("Hourglass_%d" % i, x, 4, ch)
            n = "Neck_%d" % i
            feat = self.bn(n + "/Convolution_0",
                           self.conv(n + "/Convolution_0", hg, ch, 1,
                                     bias=True), True)
            feat = self.residual(n + "/Residual_0", feat, ch)
            pred = self.conv("Head_%d/Convolution_0" % i, feat, out_ch, 1,
                             bias=True, bias_kind="head_bias")
            preds.append(pred)
            if i < stacks - 1:
                x = (x + self.conv("Convolution_%d" % (2 * i), feat, ch, 1,
                                   bias=True)
                     + self.conv("Convolution_%d" % (2 * i + 1), pred, ch, 1,
                                 bias=True))
        return jnp.stack(preds, axis=1)


def _walk_shapes(cfg: dict, imsize: int, train: bool, batch: int = 1):
    spec, convs, tails = {}, [], []

    def get(path, shape, kind):
        spec[path] = (tuple(shape), kind)
        return jnp.zeros(shape, jnp.float32)

    net = Net(cfg, get, train=train, convs=convs, tails=tails)
    jax.eval_shape(net, jax.ShapeDtypeStruct((batch, imsize, imsize, 3),
                                             jnp.float32))
    return spec, convs, tails


def param_spec(cfg: dict) -> Dict[str, tuple]:
    """{path: (shape, kind)} of every array the network reads, the running
    statistics included (kinds: kernel, bias, head_bias, bn_scale, bn_bias,
    bn_mean, bn_var)."""
    return _walk_shapes(cfg, 64, train=False)[0]


def layer_shapes(cfg: dict, imsize: int):
    """(convs, tails) of one image, for benchmark/work."""
    _, convs, tails = _walk_shapes(cfg, imsize, train=False)
    return convs, tails


def is_state(kind: str) -> bool:
    """Running statistics are state, not trained parameters."""
    return kind in ("bn_mean", "bn_var")


def forward(cfg: dict, weights: Dict[str, jax.Array], images, train: bool,
            quant: str = "f32", remat: bool = False):
    """(B, S, H/4, W/4, num_cls + 4) raw outputs."""
    return Net(cfg, lambda path, shape, kind: weights[path], train, quant,
               remat)(images)


def batch_statistics(cfg: dict, weights: Dict[str, jax.Array], images):
    """{running-statistics path: array}: every BatchNorm's mean and variance
    over `images` (normalized float32), in train mode. What a trained
    checkpoint's running statistics are: the network's own activations'
    moments. With drawn statistics instead, thirteen residual blocks deep,
    the eval-mode logits reached +-50 and every score sat at 0 or 1."""
    stats: Dict[str, jax.Array] = {}
    Net(cfg, lambda path, shape, kind: weights[path], True, stats=stats)(images)
    return stats


# ---- loss, gradients, Adam (ref loss.py:18-69, train.py:99-139) -----------

def detection_loss(cfg: dict, out, heat, off, wh, mask):
    num_cls = int(cfg["num_cls"])
    alpha, beta = cfg.get("focal_alpha", 2.0), cfg.get("focal_beta", 4.0)
    num_pos = jnp.clip(jnp.sum(mask), 1.0, 1e30)

    def per_batch(x):
        return jnp.mean(jnp.sum(x, axis=(1, 2, 3)))

    total = 0.0
    for s in range(out.shape[1]):
        o = out[:, s]
        p = jax.nn.sigmoid(o[..., :num_cls])
        pos = jnp.log(p + 1e-7) * (1.0 - p) ** alpha * mask
        neg = (jnp.log(1.0 - p + 1e-7) * p ** alpha * (1.0 - heat) ** beta
               * (1.0 - mask))
        hm = -(per_batch(pos) + per_batch(neg)) / num_pos
        l_off = per_batch(jnp.abs(o[..., num_cls:num_cls + 2] * mask
                                  - off * mask)) / num_pos
        l_wh = per_batch(jnp.abs(o[..., num_cls + 2:num_cls + 4] * mask
                                 - wh * mask)) / num_pos
        total = total + (cfg.get("hm_weight", 1.0) * hm
                         + cfg.get("offset_weight", 1.0) * l_off
                         + cfg.get("size_weight", 0.1) * l_wh)
    return total


def train_steps(cfg: dict, weights: Dict[str, jax.Array], batches, spec,
                quant: str = "f32", rows: Optional[slice] = None):
    """Follow `len(batches)` optimizer steps from `weights`. Returns the loss
    of each step, the first step's gradient and the parameters' change after
    the last, each {path: array} over the trained parameters.

    `rows` plants the fault "part of the batch left out, the mean taken over
    the rest". Adam as published (b1 0.9, b2 0.999, eps 1e-8 outside the
    root, bias-corrected), constant learning rate `cfg['lr']`."""
    lr, b1, b2, eps = float(cfg["lr"]), 0.9, 0.999, 1e-8
    trained = [k for k, (_, kind) in spec.items() if not is_state(kind)]
    params = {k: weights[k] for k in trained}

    def loss_of(params, batch):
        if rows is not None:
            batch = tuple(a[rows] for a in batch)
        image, heat, off, wh, mask = batch
        out = forward(cfg, params, image, train=True, quant=quant, remat=True)
        return detection_loss(cfg, out, heat, off, wh, mask)

    @jax.jit
    def step(params, mu, nu, t, batch):
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
            params, mu, nu)
        return params, mu, nu, loss, grads

    zeros = jax.tree.map(jnp.zeros_like, params)
    mu, nu, start = zeros, zeros, params
    losses, first_grads = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, grads = step(params, mu, nu,
                                           jnp.float32(i + 1), batch)
        losses.append(loss)
        if first_grads is None:
            first_grads = grads
    change = jax.tree.map(lambda a, b: a - b, params, start)
    return losses, first_grads, change


# ---- decode and NMS (ref transform.py:73-110, evaluate.py:155-243) --------

def normalize_pixels(images):
    x = images.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)


def dense_maps(cfg: dict, weights, images_u8, quant: str = "f32"):
    """What the reference says at every cell of every stack, for a block of
    raw uint8 images: `score` (B, S, H, W, C) post-sigmoid, `nbr_max` the
    3x3 neighbourhood maximum of it (a peak has score == nbr_max), `boxes`
    (B, S, H, W, 4) xyxy in pixels, and `kth` (B, S) the top-k-th peak
    score."""
    num_cls, topk = int(cfg["num_cls"]), int(cfg.get("topk", 100))
    sf = float(cfg.get("scale_factor", 4))
    out = forward(cfg, weights, normalize_pixels(images_u8), train=False,
                  quant=quant)
    score = jax.nn.sigmoid(out[..., :num_cls])
    nbr = lax.reduce_window(score, -jnp.inf, lax.max, (1, 1, 3, 3, 1),
                            (1, 1, 1, 1, 1),
                            ((0, 0), (0, 0), (1, 1), (1, 1), (0, 0)))
    h, w = out.shape[2], out.shape[3]
    xs = jnp.arange(w, dtype=jnp.float32)[None, :] + out[..., num_cls]
    ys = jnp.arange(h, dtype=jnp.float32)[:, None] + out[..., num_cls + 1]
    bw, bh = out[..., num_cls + 2], out[..., num_cls + 3]
    boxes = jnp.stack([xs - bw / 2, ys - bh / 2, xs + bw / 2, ys + bh / 2],
                      axis=-1) * sf
    peaks = jnp.where(score == nbr, score, 0.0)
    flat = peaks.reshape(peaks.shape[0], peaks.shape[1], -1)
    kth = lax.top_k(flat, topk)[0][..., -1]
    return dict(score=score, nbr_max=nbr, boxes=boxes, kth=kth)


def _iou(boxes: np.ndarray, plus_one: bool) -> np.ndarray:
    e = 1.0 if plus_one else 0.0
    x1, y1, x2, y2 = (boxes[:, i] for i in range(4))
    area = (x2 - x1 + e) * (y2 - y1 + e)
    w = np.maximum(0.0, np.minimum(x2[:, None], x2[None]) -
                   np.maximum(x1[:, None], x1[None]) + e)
    h = np.maximum(0.0, np.minimum(y2[:, None], y2[None]) -
                   np.maximum(y1[:, None], y1[None]) + e)
    inter = w * h
    with np.errstate(divide="ignore", invalid="ignore"):
        return inter / (area[:, None] + area[None] - inter)


def hard_nms(boxes: np.ndarray, scores: np.ndarray, iou_th: float):
    """Greedy NMS (torchvision semantics: IoU strictly above the threshold
    suppresses). Returns (keep mask, scores unchanged)."""
    order = np.argsort(-scores, kind="stable")
    iou = _iou(boxes[order].astype(np.float64), plus_one=False)
    keep = np.ones(len(order), bool)
    for i in range(len(order)):
        if keep[i]:
            keep[i + 1:] &= ~(iou[i, i + 1:] > iou_th)
    out = np.zeros(len(order), bool)
    out[order] = keep
    return out, scores


def soft_nms(boxes: np.ndarray, scores: np.ndarray, sigma: float = 0.5,
             score_th: float = 0.0):
    """Gaussian Soft-NMS (ref evaluate.py:184-243): repeatedly take the best
    unprocessed box and decay the others by exp(-iou^2 / sigma), with the
    reference's inclusive-pixel IoU."""
    iou = _iou(boxes.astype(np.float64), plus_one=True)
    s = scores.astype(np.float64).copy()
    todo = np.ones(len(s), bool)
    for _ in range(len(s)):
        i = int(np.argmax(np.where(todo, s, -np.inf)))
        todo[i] = False
        s[todo] *= np.exp(-(iou[i, todo] ** 2) / sigma)
    return s > score_th, s.astype(np.float32)


def num_params(cfg: dict) -> int:
    return sum(math.prod(shape) for shape, kind in param_spec(cfg).values()
               if not is_state(kind))
