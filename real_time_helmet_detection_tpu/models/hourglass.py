"""Stacked-hourglass CenterNet backbone in flax.linen, NHWC, TPU-first.

Capability parity with the reference model zoo (/root/reference/hourglass.py):
`Mish`:6, `Activation`:14, `SPP`:46, `Pool`:68, `Convolution`:94,
`Residual`:111, recursive `Hourglass`:130, `PreLayer`:159, `Neck`:176,
`Head`:189, `StackedHourglass`:198 — re-designed rather than translated:

* **NHWC layout** end to end (TPU conv native layout; reference is NCHW);
* shape law: `(B, num_stack, H/4, W/4, num_cls + 4)` — the reference's
  `(B, S, C+4, H/4, W/4)` with channels moved last;
* a `dtype` policy attribute on every block for bf16 compute with fp32
  params/batch-stats (the TPU-native replacement for CUDA AMP + GradScaler:
  bf16 needs no loss scaling);
* explicit symmetric `(k-1)//2` padding to preserve the reference's exact
  spatial geometry (XLA `SAME` pads asymmetrically for stride-2 convs);
* nearest 2x upsampling as a pure `jnp.repeat` (exact, fusable).

BatchNorm uses per-replica batch statistics under data parallelism, matching
DDP's default (SURVEY.md §7 hard parts); pass `bn_axis_name` to opt into
cross-replica sync-BN, a capability the reference lacks.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

from ..ops.pallas.epilogue import (FUSED_EPILOGUE_ACTIVATIONS, fused_bn_act,
                                   fused_bn_act_train)
from ..ops.pallas.select import kernel_plan
from ..ops.quant import (make_ste_conv, quantize_activations,
                         quantize_weights)

Dtype = Any

# quantization modes of the inference-only model twin (ops/quant.py):
# "off" = the ordinary float graph; "calibrate" = float graph that records
# each quantized conv's input abs-max/percentile into the `quant`
# collection; "int8" = int8 conv bodies consuming the calibrated scales.
QUANT_MODES = ("off", "calibrate", "int8")

# conv epilogue implementations (--epilogue): "xla" = the nn.BatchNorm +
# Activation composition, "fused" = the one-pass BN-normalize+activation
# epilogue (ops/pallas/epilogue.py) where eligible.
EPILOGUE_MODES = ("xla", "fused")

# residual-block TAIL implementations (--block-fuse): "xla" = per-conv
# epilogue + XLA skip-add + Activation, "fused" = BN + skip-add + closing
# activation collapsed into the same custom_vjp pass family, the skip as
# its fourth operand, where eligible.
BLOCK_FUSE_MODES = ("xla", "fused")

# train-time forward conv compute dtypes (--fwd-dtype; ISSUE 20): "bf16"
# = the --amp baseline; "int8" = eligible convs run their TRAIN forward
# on the int8 MXU path with a straight-through-estimator backward
# (ops/quant.make_ste_conv). ONE vocabulary with config.py's validation.
FWD_DTYPES = ("bf16", "int8")

# residual-block variants (ISSUE 13; Lighter Stacked Hourglass, arxiv
# 2107.13643): the `variant` axis of the latency-tier model family. ONE
# vocabulary shared with config.py (MODEL_VARIANTS there — stdlib-only;
# tests pin the two tuples equal). Every variant is built from the SAME
# `Convolution` block, so BN folding (ops/quant.fold_batchnorm), int8 PTQ
# (QuantConv) and the fused BN+activation epilogue (FusedBNAct) apply to
# every tier for free — the BN tree keeps the Conv_0+BatchNorm_0 sibling
# shape throughout.
VARIANTS = ("residual", "depthwise", "ghost")


def mish(x: jax.Array) -> jax.Array:
    """x * tanh(softplus(x)) (ref hourglass.py:6-11)."""
    return x * jnp.tanh(jax.nn.softplus(x))


class Activation(nn.Module):
    """Activation factory (ref hourglass.py:14-43).

    Supported: ReLU | LReLU | PReLU | Linear | Mish | Sigmoid | CELU.
    """
    activation: str = "ReLU"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        name = self.activation
        if name == "ReLU":
            return nn.relu(x)
        if name == "LReLU":
            return nn.leaky_relu(x, negative_slope=0.01)
        if name == "PReLU":
            # torch's nn.PReLU initializes the slope at 0.25; flax defaults
            # to 0.01, which would silently change training dynamics.
            return nn.PReLU(negative_slope_init=0.25)(x)
        if name == "Linear":
            return x
        if name == "Mish":
            return mish(x)
        if name == "Sigmoid":
            return nn.sigmoid(x)
        if name == "CELU":
            return nn.celu(x)
        raise NotImplementedError("Not expected activation: %s" % name)


def _max_pool_same(x: jax.Array, k: int) -> jax.Array:
    """k x k stride-1 max pool with symmetric (k-1)//2 padding."""
    p = (k - 1) // 2
    return nn.max_pool(x, (k, k), strides=(1, 1), padding=((p, p), (p, p)))


class SPP(nn.Module):
    """YOLOv4-style spatial pyramid pooling (ref hourglass.py:46-65):
    1x1 channel-halving conv -> parallel stride-1 max pools k in
    {5, 9, 13} -> concat -> 1x1 conv back to `ch`. Keeps resolution."""
    ch: int = 128
    kernel_sizes: Sequence[int] = (5, 9, 13)
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        half = self.ch // 2
        x = nn.Conv(half, (1, 1), use_bias=False, dtype=self.dtype)(x)
        pooled = [x] + [_max_pool_same(x, k) for k in self.kernel_sizes]
        y = jnp.concatenate(pooled, axis=-1)
        return nn.Conv(self.ch, (1, 1), use_bias=False, dtype=self.dtype)(y)


class Pool(nn.Module):
    """Downsample factory (ref hourglass.py:68-91): Max | Avg | Conv | SPP |
    None. Note (as in the reference): SPP keeps resolution; 'None' is
    identity."""
    channel: int
    pool: str = "Max"
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        name = self.pool
        if name == "Max":
            return nn.max_pool(x, (2, 2), strides=(2, 2))
        if name == "Avg":
            return nn.avg_pool(x, (2, 2), strides=(2, 2))
        if name == "Conv":
            return nn.Conv(self.channel, (2, 2), strides=(2, 2), padding="VALID",
                           dtype=self.dtype)(x)
        if name == "SPP":
            return SPP(self.channel, dtype=self.dtype)(x)
        if name == "None":
            return x
        raise NotImplementedError("Not expected pool: %s" % name)


class StemConv(nn.Module):
    """7x7 stride-2 conv with an optional space-to-depth formulation.

    The stem contracts over only kh*kw*3 = 147 input values per output —
    the 3-channel axis starves the MXU's 128-wide contraction lanes. The
    s2d path computes the SAME sums as a 4x4 stride-1 conv over the 2x2
    space-to-depth input (12 channels): kernel padded 7->8 top-left and
    regrouped so output(i,j) = sum W8[2a+p, 2b+q, c] * x[2(i+a-2)+p,
    2(j+b-2)+q, c] — bit-equal arithmetic, different loop order (the
    MLPerf ResNet trick, re-derived for this geometry). Param tree is
    IDENTICAL to nn.Conv ('kernel' (7,7,C,F) + 'bias'), so checkpoints
    are interchangeable across --stem-s2d on/off.
    """
    features: int
    s2d: bool = False
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        c = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, c, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        dt = self.dtype or x.dtype
        x = x.astype(dt)
        k = kernel.astype(dt)
        dn = ("NHWC", "HWIO", "NHWC")
        # the s2d regrouping needs even H and W; odd sizes (legal for the
        # direct conv) silently take the direct path rather than dying in
        # an opaque reshape error mid-trace
        if not self.s2d or x.shape[1] % 2 or x.shape[2] % 2:
            y = jax.lax.conv_general_dilated(
                x, k, (2, 2), ((3, 3), (3, 3)), dimension_numbers=dn)
        else:
            b, h, w, _ = x.shape
            xs = x.reshape(b, h // 2, 2, w // 2, 2, c)
            xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2,
                                                        4 * c)
            k8 = jnp.pad(k, ((1, 0), (1, 0), (0, 0), (0, 0)))
            ks = k8.reshape(4, 2, 4, 2, c, self.features)
            ks = ks.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                        self.features)
            y = jax.lax.conv_general_dilated(
                xs, ks, (1, 1), ((2, 1), (2, 1)), dimension_numbers=dn)
        return y + bias.astype(dt)


class QuantConv(nn.Module):
    """Post-training-quantized conv body for the inference twin
    (ops/quant.py; the reference serves fp32 through TorchScript and has
    no quantized path, ref export.py:55).

    Param tree is IDENTICAL to `nn.Conv(use_bias=True)` ('kernel' HWIO +
    'bias'), so the BN-folded checkpoint pytree drops straight in under
    the same `Conv_0` name. Two modes:

    * `calibrate` — float conv, plus the input's abs-max (or upper
      `calib_percentile` of |x|) recorded into the `quant` collection as
      `act_scale`: ONE scalar per conv per dispatch, so a calibration
      batch fetches only per-layer scalars.
    * `int8` — symmetric per-tensor activation + per-output-channel
      weight quantization, int8 x int8 `lax.conv_general_dilated` with
      `preferred_element_type=int32` (the v5e's 394 TOPS int8 MXU path,
      2x bf16 peak), then one fused rescale `acc * (s_a * s_w)` + bias in
      the compute dtype (bf16 under --amp). Weights quantize INSIDE the
      program from the folded fp32 kernel — the artifact contract stays
      "checkpoint pytree + scales pytree in".
    """
    features: int
    kernel_size: int = 3
    stride: int = 1
    padding: int = 1
    groups: int = 1     # feature_group_count (depthwise/ghost variants)
    mode: str = "int8"  # "calibrate" | "int8"
    calib_percentile: float = 100.0
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        k = self.kernel_size
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (k, k, x.shape[-1] // self.groups,
                             self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        dt = self.dtype or x.dtype
        dn = ("NHWC", "HWIO", "NHWC")
        pad = ((self.padding, self.padding), (self.padding, self.padding))
        if self.mode == "calibrate":
            ax = jnp.abs(x.astype(jnp.float32))
            stat = (jnp.max(ax) if self.calib_percentile >= 100.0
                    else jnp.percentile(ax, self.calib_percentile))
            running = self.variable("quant", "act_scale",
                                    lambda: jnp.zeros((), jnp.float32))
            running.value = jnp.maximum(running.value, stat)
            y = jax.lax.conv_general_dilated(
                x.astype(dt), kernel.astype(dt),
                (self.stride, self.stride), pad, dimension_numbers=dn,
                feature_group_count=self.groups)
        elif self.mode == "int8":
            # the calibrated clip range MUST be provided (the scales
            # pytree as the `quant` collection): a missing entry fails
            # flax's immutable-collection check loudly
            clip_range = self.variable(
                "quant", "act_scale",
                lambda: jnp.ones((), jnp.float32)).value
            xq, a_scale = quantize_activations(x, clip_range)
            wq, w_scale = quantize_weights(kernel)
            acc = jax.lax.conv_general_dilated(
                xq, wq, (self.stride, self.stride), pad,
                dimension_numbers=dn, preferred_element_type=jnp.int32,
                feature_group_count=self.groups)
            y = acc.astype(dt) * (a_scale * w_scale).astype(dt)
        else:
            raise NotImplementedError("Not expected quant mode: %s"
                                      % self.mode)
        return y + bias.astype(dt)


class STEConv(nn.Module):
    """Int8-forward TRAIN conv body (`--fwd-dtype int8`, ISSUE 20).

    Param tree is IDENTICAL to `nn.Conv(use_bias=False)` ('kernel' HWIO,
    same lecun-normal init at the same "Conv_0" path), so the SAME
    checkpoint trains under either forward dtype and eval/predict bind
    the float path unchanged — the StemConv/QuantConv tree-compat law.

    The forward runs `ops/quant.make_ste_conv`: int8 x int8 -> int32 on
    the MXU (the v5e's 394-TOPS path, 2x bf16 peak) with a per-step
    in-jit abs-max activation scale and per-output-channel weight scales,
    and a straight-through-estimator backward through the float conv
    twin — gradients are exactly the bf16 program's. No scale state is
    persisted anywhere (contrast QuantConv's calibrated `quant`
    collection): trees, donation and the D2H budget are untouched."""
    features: int
    kernel_size: int = 3
    stride: int = 1
    padding: int = 1
    groups: int = 1
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        k = self.kernel_size
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (k, k, x.shape[-1] // self.groups,
                             self.features))
        dt = self.dtype or x.dtype
        fn = make_ste_conv(self.stride, self.padding, self.groups)
        return fn(x.astype(dt), kernel.astype(dt))


class FusedBNAct(nn.Module):
    """BatchNorm (+ skip-add) + activation with the normalize(+add)
    +activation chain collapsed into ONE pointwise pass
    (ops/pallas/epilogue.py; `--epilogue fused`, and `--block-fuse fused`
    for a residual block's tail, which passes the block's other branch as
    `skip`): a Pallas custom_vjp family in the train step, a plain
    expression that XLA fuses into the conv at eval.

    Param and batch_stats trees are IDENTICAL to
    `nn.BatchNorm(momentum=0.9, epsilon=1e-5)` and the block instantiates
    it under the same "BatchNorm_0" name, so checkpoints interchange
    across every --epilogue / --block-fuse mode and
    `ops.quant.fold_batchnorm` folds this block exactly as it folds
    nn.BatchNorm (regression-tested). Batch moments are of the BN input x
    ALONE — the skip never enters the statistics, exactly as in the
    unfused composition.

    The statistics stay in XLA (they are reductions, computed in f32 with
    flax's formulas: mean, E[x^2]-E[x]^2 clamped at 0, and the same
    momentum running update); only the pointwise tail leaves it:
    `eff_scale = gamma * rsqrt(var + eps)`, `eff_bias = beta - mean *
    eff_scale` — the PR 5 BN-fold algebra (ops/quant.py) applied at
    train time to the batch statistics (`fused_bn_act_train`, whose
    custom_vjp recomputes the backward instead of saving post-BN
    residuals) and at eval time to the running statistics
    (`fused_bn_act`: constants per channel, so no kernel and no
    custom_vjp — selected by `train`, nothing else)."""
    activation: str = "Mish"
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 skip: Optional[jax.Array] = None) -> jax.Array:
        feat = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((feat,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((feat,), jnp.float32))
        scale = self.param("scale", nn.initializers.ones_init(), (feat,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(), (feat,),
                          jnp.float32)
        if train:
            # moments + normalize (+ add) + activation + the ANALYTIC BN
            # backward, with the skip's pass-through gradient, all live
            # inside ONE custom_vjp (ops/pallas/epilogue.py) — XLA never
            # autodiffs through the statistics, so no f32 activation
            # copies, no materialized sum and no backward-through-stats
            # chains exist in the program. The returned batch moments
            # feed ONLY the running buffers, stop_gradient'd exactly as
            # flax BatchNorm treats them (the custom_vjp drops their zero
            # cotangents).
            out, mean, var = fused_bn_act_train(
                x, scale, bias, skip, eps=self.epsilon,
                activation=self.activation)
            if not self.is_initializing():
                m = self.momentum
                mean = jax.lax.stop_gradient(mean)
                var = jax.lax.stop_gradient(var)
                ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
                ra_var.value = m * ra_var.value + (1.0 - m) * var
            return out
        # eval: running statistics fold into the per-channel affine (the
        # PR 5 fold algebra); the tail is a plain pointwise expression
        # that XLA fuses into the conv that produced x
        eff_scale = scale * jax.lax.rsqrt(ra_var.value + self.epsilon)
        eff_bias = bias - ra_mean.value * eff_scale
        return fused_bn_act(x, eff_scale, eff_bias, skip,
                            activation=self.activation)


class Convolution(nn.Module):
    """Conv -> optional BN -> activation (ref hourglass.py:94-108), with the
    reference's symmetric (k-1)//2 padding.

    Inference-compression attributes (ops/quant.py): `fold_bn` consumes
    the BN-folded param pytree — the conv gains a bias, the BatchNorm
    module disappears; `quant_mode` swaps the conv body for `QuantConv`
    on the folded convs (`self.bn` and `quantize`; the stem and every
    bn-less conv — head, inter-stack merges — stay in the float dtype:
    the first/last-layer rule, and their contractions are not where the
    roofline says the time is).

    `epilogue="fused"` swaps the nn.BatchNorm + Activation tail for the
    one-pass `FusedBNAct` where ELIGIBLE: the conv has a BN that
    is not being folded away, the activation has a recomputable closed
    form (Mish/ReLU/Linear — FUSED_EPILOGUE_ACTIVATIONS), and BN is
    per-replica (cross-replica sync-BN keeps the XLA path: its stats
    collective belongs to XLA). Ineligible combinations silently keep the
    xla path — the decision table lives in docs/ARCHITECTURE.md "Step
    compression".

    A non-None `skip` (`--block-fuse fused`, passed ONLY by `Residual`
    on its tail conv) extends that tail through the skip-add:
    `FusedBNAct` computes BN + add + activation in one pass family with
    the skip's pass-through gradient, whatever `epilogue` says.
    Eligibility is the caller's job; this block only enforces the
    contract.

    `fwd_dtype="int8"` (ISSUE 20) swaps the TRAIN-mode conv body for
    `STEConv` (int8 MXU forward, straight-through float backward) where
    eligible: BN'd, bias-free, unquantized, unfolded — the stem
    (quantize=False) and the bn-less heads/merges keep the float body
    (the first/last-layer rule, shared with `quant_mode`). Eval always
    binds the float body over the same params."""
    out_ch: int
    kernel_size: int = 3
    stride: int = 1
    use_bias: bool = True
    bn: bool = False
    activation: str = "ReLU"
    groups: int = 1         # feature_group_count: 1 = dense (the
    # reference's convs); out_ch = groups = input channels is a depthwise
    # conv — the Lighter-Hourglass variants (ISSUE 13) are built from
    # exactly this knob, so the BN/quant/epilogue machinery sees one block
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    stem_s2d: bool = False  # use the space-to-depth stem formulation
    fold_bn: bool = False   # consume BN-folded params (inference only)
    quant_mode: str = "off"  # off | calibrate | int8 (see QUANT_MODES)
    calib_percentile: float = 100.0
    quantize: bool = True   # eligibility: PreLayer's stem opts out
    epilogue: str = "xla"   # xla | fused (see EPILOGUE_MODES)
    fwd_dtype: str = "bf16"  # bf16 | int8 (see FWD_DTYPES): train-time
    # forward conv compute dtype; "int8" swaps eligible train-mode conv
    # bodies for STEConv

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 skip: Optional[jax.Array] = None) -> jax.Array:
        k, p = self.kernel_size, (self.kernel_size - 1) // 2
        fold = self.bn and self.fold_bn
        quant_active = self.quant_mode != "off" and self.quantize and self.bn
        if quant_active and not fold:
            raise ValueError(
                "quant_mode=%r requires fold_bn: BN must be folded into "
                "the conv before its weights are quantized (ops/quant.py)"
                % self.quant_mode)
        if skip is not None and (
                fold or not self.bn or self.bn_axis_name is not None
                or self.activation not in FUSED_EPILOGUE_ACTIVATIONS):
            raise ValueError(
                "block-fused tail requires an unfolded per-replica BN "
                "and an activation in %s — the caller (Residual) gates "
                "eligibility" % (FUSED_EPILOGUE_ACTIVATIONS,))
        ste_active = (self.fwd_dtype == "int8" and train and self.bn
                      and not fold and self.quant_mode == "off"
                      and self.quantize and not self.use_bias)
        if self.stem_s2d and k == 7 and self.stride == 2 and self.use_bias:
            # name matches the nn.Conv auto-name so the param tree (and
            # every checkpoint) is identical whichever path computes it
            x = StemConv(self.out_ch, s2d=True, dtype=self.dtype,
                         name="Conv_0")(x)
        elif quant_active:
            x = QuantConv(self.out_ch, kernel_size=k, stride=self.stride,
                          padding=p, groups=self.groups,
                          mode=self.quant_mode,
                          calib_percentile=self.calib_percentile,
                          dtype=self.dtype, name="Conv_0")(x)
        elif ste_active:
            x = STEConv(self.out_ch, kernel_size=k, stride=self.stride,
                        padding=p, groups=self.groups,
                        dtype=self.dtype, name="Conv_0")(x)
        else:
            x = nn.Conv(self.out_ch, (k, k),
                        strides=(self.stride, self.stride),
                        padding=((p, p), (p, p)),
                        feature_group_count=self.groups,
                        use_bias=self.use_bias or fold,
                        dtype=self.dtype)(x)
        if self.bn and not self.fold_bn:
            if skip is not None or (
                    self.epilogue == "fused" and self.bn_axis_name is None
                    and self.activation in FUSED_EPILOGUE_ACTIVATIONS):
                # same "BatchNorm_0" name as the nn.BatchNorm auto-name:
                # the param tree (and every checkpoint) is identical
                # whichever tail computes it
                return FusedBNAct(activation=self.activation,
                                  dtype=self.dtype,
                                  name="BatchNorm_0")(x, train, skip=skip)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype,
                             axis_name=self.bn_axis_name)(x)
        return Activation(self.activation)(x)


class GhostModule(nn.Module):
    """Ghost module (Lighter Stacked Hourglass arxiv 2107.13643 §3 /
    GhostNet): a 1x1 "primary" conv produces out_ch/2 intrinsic features,
    a CHEAP depthwise kxk conv generates the other out_ch/2 "ghost"
    features from them, concat — ~half the dense conv's FLOPs at the same
    output width. Both halves are ordinary `Convolution` blocks (BN+act),
    so fold/int8/epilogue machinery applies unchanged."""
    out_ch: int
    kernel_size: int = 3
    stride: int = 1
    activation: str = "ReLU"
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    fold_bn: bool = False
    quant_mode: str = "off"
    calib_percentile: float = 100.0
    epilogue: str = "xla"
    fwd_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        if self.out_ch % 2:
            raise ValueError(
                "ghost variant needs an even channel width (half primary "
                "+ half ghost features), got out_ch=%d" % self.out_ch)
        half = self.out_ch // 2
        kw = dict(dtype=self.dtype, bn_axis_name=self.bn_axis_name,
                  fold_bn=self.fold_bn, quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, fwd_dtype=self.fwd_dtype)
        primary = Convolution(half, 1, self.stride, use_bias=False,
                              bn=True, activation=self.activation,
                              **kw)(x, train)
        ghost = Convolution(half, self.kernel_size, 1, use_bias=False,
                            bn=True, activation=self.activation,
                            groups=half, **kw)(primary, train)
        return jnp.concatenate([primary, ghost], axis=-1)


class Residual(nn.Module):
    """Residual block, `variant`-selectable (ISSUE 13):

    * "residual"  — two 3x3 BN convs (second linear) + 1x1 BN skip on
      channel change, post-add activation (ref hourglass.py:111-127; the
      flagship block, bit-identical to the pre-tier program);
    * "depthwise" — each dense 3x3 becomes depthwise 3x3 + pointwise 1x1
      (both BN'd; the Lighter-Hourglass separable block) — ~(1/C + 1/9)
      of the dense conv's FLOPs;
    * "ghost"     — each dense 3x3 becomes a `GhostModule`.

    Skip path and post-add activation are identical across variants, so
    the block's I/O contract (and the surrounding Hourglass geometry)
    never changes.

    `block_fuse="fused"` collapses the block TAIL — the last conv's BN,
    the skip-add and the post-add activation — into one custom_vjp pass
    family (`FusedBNAct` with a skip) where ELIGIBLE: residual/depthwise
    variants (ghost's tail is a concat of two separately-normalized
    GhostModule halves — there is no single BN feeding the add), no
    quantization/folding, per-replica BN, post-add activation in
    FUSED_EPILOGUE_ACTIVATIONS. Ineligible blocks silently keep the xla
    tail. Children are named explicitly, with the names flax's per-class
    auto-numbering would give them in call order (body, then skip): flax
    derives param RNGs and tree keys from the module PATH, so the trees
    (values included) are identical whichever tail runs and checkpoints
    interchange (tested)."""
    out_ch: int
    kernel_size: int = 3
    stride: int = 1
    activation: str = "ReLU"
    variant: str = "residual"
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    fold_bn: bool = False
    quant_mode: str = "off"
    calib_percentile: float = 100.0
    epilogue: str = "xla"
    block_fuse: str = "xla"  # xla | fused (see BLOCK_FUSE_MODES)
    fwd_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        kw = dict(dtype=self.dtype, bn_axis_name=self.bn_axis_name,
                  fold_bn=self.fold_bn, quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, fwd_dtype=self.fwd_dtype)
        fuse_tail = (self.block_fuse == "fused"
                     and self.variant in ("residual", "depthwise")
                     and self.quant_mode == "off" and not self.fold_bn
                     and self.bn_axis_name is None
                     and self.activation in FUSED_EPILOGUE_ACTIVATIONS)
        # the fused tail conv carries the POST-ADD activation (unfused it
        # is Linear and the activation sits after the add)
        act = self.activation
        tail_act = act if fuse_tail else "Linear"
        conv = functools.partial(Convolution, use_bias=False, bn=True, **kw)
        k = self.kernel_size
        if self.variant == "depthwise":
            in_ch = x.shape[-1]
            y = conv(in_ch, k, self.stride, activation=act, groups=in_ch,
                     name="Convolution_0")(x, train)
            y = conv(self.out_ch, 1, 1, activation=act,
                     name="Convolution_1")(y, train)
            y = conv(self.out_ch, k, 1, activation=act, groups=self.out_ch,
                     name="Convolution_2")(y, train)
            tail = conv(self.out_ch, 1, 1, activation=tail_act,
                        name="Convolution_3")
            skip_name = "Convolution_4"
        elif self.variant == "ghost":
            y = GhostModule(self.out_ch, k, self.stride, activation=act,
                            name="GhostModule_0", **kw)(x, train)
            tail = GhostModule(self.out_ch, k, 1, activation="Linear",
                               name="GhostModule_1", **kw)
            skip_name = "Convolution_0"
        elif self.variant == "residual":
            y = conv(self.out_ch, k, self.stride, activation=act,
                     name="Convolution_0")(x, train)
            tail = conv(self.out_ch, k, self.stride, activation=tail_act,
                        name="Convolution_1")
            skip_name = "Convolution_2"
        else:
            raise NotImplementedError("Not expected variant: %s"
                                      % self.variant)
        # the skip branch is computed BEFORE the tail conv is applied, so
        # that it can feed the fused pass
        if x.shape[-1] != self.out_ch:
            x = conv(self.out_ch, 1, self.stride, activation="Linear",
                     name=skip_name)(x, train)
        if fuse_tail:
            return tail(y, train, skip=x)
        return Activation(act)(tail(y, train) + x)


def _upsample_nearest_2x(x: jax.Array) -> jax.Array:
    return jnp.repeat(jnp.repeat(x, 2, axis=-3), 2, axis=-2)


class Hourglass(nn.Module):
    """Recursive U-module of depth `num_layer` (ref hourglass.py:130-156):
    residual skip + [pool -> residual(+increase_ch) -> recurse/bottom ->
    residual(back down) -> nearest-2x up], summed."""
    num_layer: int
    in_ch: int
    increase_ch: int = 0
    activation: str = "ReLU"
    pool: str = "Max"
    variant: str = "residual"
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    fold_bn: bool = False
    quant_mode: str = "off"
    calib_percentile: float = 100.0
    epilogue: str = "xla"
    block_fuse: str = "xla"
    fwd_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        kw = dict(activation=self.activation, variant=self.variant,
                  dtype=self.dtype,
                  bn_axis_name=self.bn_axis_name, fold_bn=self.fold_bn,
                  quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, block_fuse=self.block_fuse,
                  fwd_dtype=self.fwd_dtype)
        mid_ch = self.in_ch + self.increase_ch

        up1 = Residual(self.in_ch, **kw)(x, train)
        low = Pool(self.in_ch, self.pool, dtype=self.dtype)(x)
        low = Residual(mid_ch, **kw)(low, train)
        if self.num_layer > 1:
            low = Hourglass(self.num_layer - 1, mid_ch, self.increase_ch,
                            self.activation, self.pool, self.variant,
                            self.dtype,
                            self.bn_axis_name, self.fold_bn,
                            self.quant_mode, self.calib_percentile,
                            self.epilogue, self.block_fuse,
                            self.fwd_dtype)(low, train)
        else:
            low = Residual(mid_ch, **kw)(low, train)
        low = Residual(self.in_ch, **kw)(low, train)
        if self.pool in ("SPP", "None"):
            # resolution was never reduced; no upsample (matches the
            # reference geometry where Pool is non-downsampling)
            up2 = low
        else:
            up2 = _upsample_nearest_2x(low)
        return up1 + up2


class PreLayer(nn.Module):
    """Stem: fixed 4x downsample (ref hourglass.py:159-173):
    7x7 s2 conv(64, BN) -> Residual(mid) -> Pool(2x) -> Residual(mid) ->
    Residual(out)."""
    mid_ch: int = 128
    out_ch: int = 128
    activation: str = "ReLU"
    pool: str = "Max"
    variant: str = "residual"
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    stem_s2d: bool = False
    fold_bn: bool = False
    quant_mode: str = "off"
    calib_percentile: float = 100.0
    epilogue: str = "xla"
    block_fuse: str = "xla"
    fwd_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        kw = dict(dtype=self.dtype, bn_axis_name=self.bn_axis_name,
                  fold_bn=self.fold_bn, quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, fwd_dtype=self.fwd_dtype)
        # block_fuse is a Residual-level knob (the block TAIL); the plain
        # Convolution blocks never see it
        rkw = dict(kw, block_fuse=self.block_fuse)
        # the stem conv contracts over only 3 input channels and is the
        # first layer: it stays in the float dtype (quantize=False) and is
        # NEVER a variant block (its 147-value contraction is already
        # minimal) — folding its BN still applies
        x = Convolution(64, 7, 2, use_bias=True, bn=True,
                        activation=self.activation,
                        stem_s2d=self.stem_s2d, quantize=False,
                        **kw)(x, train)
        x = Residual(self.mid_ch, variant=self.variant, **rkw)(x, train)
        x = Pool(self.mid_ch, self.pool, dtype=self.dtype)(x)
        x = Residual(self.mid_ch, variant=self.variant, **rkw)(x, train)
        x = Residual(self.out_ch, variant=self.variant, **rkw)(x, train)
        return x


class Neck(nn.Module):
    """Feature neck (ref hourglass.py:176-186): optional Pool (None | SPP) ->
    1x1 BN conv -> Residual."""
    ch: int = 128
    activation: str = "ReLU"
    pool: str = "None"
    variant: str = "residual"
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    fold_bn: bool = False
    quant_mode: str = "off"
    calib_percentile: float = 100.0
    epilogue: str = "xla"
    block_fuse: str = "xla"
    fwd_dtype: str = "bf16"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        kw = dict(dtype=self.dtype, bn_axis_name=self.bn_axis_name,
                  fold_bn=self.fold_bn, quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, fwd_dtype=self.fwd_dtype)
        x = Pool(self.ch, self.pool, dtype=self.dtype)(x)
        x = Convolution(self.ch, 1, bn=True, activation=self.activation,
                        **kw)(x, train)
        x = Residual(self.ch, variant=self.variant,
                     block_fuse=self.block_fuse, **kw)(x, train)
        return x


class Head(nn.Module):
    """Prediction head: single 1x1 linear conv (ref hourglass.py:189-195)."""
    out_ch: int
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        return Convolution(self.out_ch, 1, 1, use_bias=True, bn=False,
                           activation="Linear", dtype=self.dtype)(x)


class StackedHourglass(nn.Module):
    """Full detector (ref hourglass.py:198-237).

    forward: PreLayer -> per stack [Hourglass -> Neck -> Head], keeping every
    stack's prediction for deep supervision; between stacks
    `x = x + merge_feature(feature) + merge_prediction(prediction)`.

    Returns `(B, num_stack, H/4, W/4, out_ch)` float32 logits (raw — sigmoid
    is applied by the loss/decode callers, as in the reference).
    """
    num_stack: int = 1
    in_ch: int = 128
    out_ch: int = 6  # num_cls + 4
    increase_ch: int = 0
    activation: str = "ReLU"
    pool: str = "Max"
    neck_activation: str = "ReLU"
    neck_pool: str = "None"
    variant: str = "residual"  # residual-block variant (VARIANTS; the
    # latency-tier axis, ISSUE 13) — every Residual in stem/hourglass/neck
    # builds this block type; stem conv and heads are variant-invariant
    stem_width: int = 0  # PreLayer mid width; 0 = the reference's fixed
    # 128 (every pre-tier checkpoint). Tier presets set it to the model
    # width: a 64-wide tier with a 128-wide stem would put most of its
    # full-resolution bytes in the stem (ISSUE 13).
    dtype: Optional[Dtype] = None
    bn_axis_name: Optional[str] = None
    remat: Any = False  # "none"/False | "stacks"/True: rematerialize each
    # Hourglass stack in backward. "full" is handled OUTSIDE the module
    # (train.loss_fn wraps the whole apply in jax.checkpoint, covering the
    # stem/neck/head too) — the module then stays plain so the recompute
    # isn't doubly nested.
    stem_s2d: bool = False  # MXU-friendly space-to-depth stem conv
    fold_bn: bool = False   # inference twin: BN folded into the convs
    # (consumes ops/quant.fold_batchnorm params; training stays BN'd)
    quant_mode: str = "off"  # off | calibrate | int8 (see QUANT_MODES)
    calib_percentile: float = 100.0
    epilogue: str = "xla"   # conv BN+activation tail: "xla" (the pre-PR
    # nn.BatchNorm + Activation composition) | "fused" (one-pass
    # ops/pallas/epilogue.py kernel where eligible; see Convolution)
    block_fuse: str = "xla"  # residual-block tail: "xla" (per-conv
    # epilogue + XLA add + Activation) | "fused" (BN + skip-add +
    # activation in one ops/pallas/epilogue.py pass family where
    # eligible; see Residual)
    fwd_dtype: str = "bf16"  # train-time forward conv compute dtype:
    # "bf16" | "int8" (STEConv where eligible; see Convolution). ISSUE 20.

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        kw = dict(variant=self.variant, dtype=self.dtype,
                  bn_axis_name=self.bn_axis_name,
                  fold_bn=self.fold_bn, quant_mode=self.quant_mode,
                  calib_percentile=self.calib_percentile,
                  epilogue=self.epilogue, block_fuse=self.block_fuse,
                  fwd_dtype=self.fwd_dtype)
        if self.dtype is not None:
            x = x.astype(self.dtype)
        x = PreLayer(mid_ch=self.stem_width or 128, out_ch=self.in_ch,
                     activation=self.activation,
                     pool=self.pool, stem_s2d=self.stem_s2d, **kw)(x, train)

        # --remat stacks trades FLOPs for HBM: each stack's activations are
        # recomputed during backward instead of stored — the lever that
        # fits num_stack=4 @ 768^2 batches in memory (BASELINE config #4);
        # numerically identical (tested). The explicit name keeps the param
        # tree identical to the plain model, so checkpoints are
        # interchangeable across every --remat policy.
        HG = (nn.remat(Hourglass, static_argnums=(2,))
              if self.remat in (True, "stacks") else Hourglass)

        predictions = []
        for i in range(self.num_stack):
            hg = HG(num_layer=4, in_ch=self.in_ch,
                    increase_ch=self.increase_ch,
                    activation=self.activation, pool=self.pool,
                    name=f"Hourglass_{i}", **kw)(x, train)
            feature = Neck(self.in_ch, self.neck_activation, self.neck_pool,
                           **kw)(hg, train)
            prediction = Head(self.out_ch, dtype=self.dtype)(feature)
            predictions.append(prediction)
            if i < self.num_stack - 1:
                x = (x
                     + Convolution(self.in_ch, 1, 1, use_bias=True, bn=False,
                                   activation="Linear", dtype=self.dtype)(feature)
                     + Convolution(self.in_ch, 1, 1, use_bias=True, bn=False,
                                   activation="Linear", dtype=self.dtype)(prediction))

        return jnp.stack(predictions, axis=1).astype(jnp.float32)


def build_model(args_or_cfg, dtype: Optional[Dtype] = None,
                bn_axis_name: Optional[str] = None, fold_bn: bool = False,
                quant_mode: str = "off",
                calib_percentile: float = 100.0) -> StackedHourglass:
    """Construct the detector from a config namespace with the reference's
    flag names (ref train.py:164-172 `load_network`).

    `fold_bn`/`quant_mode` build the inference-compression twin
    (ops/quant.py): same architecture, BN folded into the convs and —
    in `calibrate`/`int8` modes — the quantization machinery in place of
    the folded conv bodies. Training models never set these."""
    c = args_or_cfg
    plan = kernel_plan(c)
    if quant_mode not in QUANT_MODES:
        raise ValueError("quant_mode must be one of %s, got %r"
                         % (QUANT_MODES, quant_mode))
    if quant_mode != "off" and not fold_bn:
        raise ValueError("quant_mode=%r requires fold_bn=True (BN folds "
                         "before quantization)" % quant_mode)
    variant = getattr(c, "variant", "residual")
    if variant not in VARIANTS:
        raise ValueError("variant must be one of %s, got %r"
                         % (VARIANTS, variant))
    return StackedHourglass(
        num_stack=c.num_stack,
        in_ch=c.hourglass_inch,
        out_ch=c.num_cls + 4,
        increase_ch=c.increase_ch,
        variant=variant,
        stem_width=getattr(c, "stem_width", 0),
        activation=c.activation,
        pool=c.pool,
        neck_activation=c.neck_activation,
        neck_pool=c.neck_pool,
        dtype=dtype,
        bn_axis_name=bn_axis_name,
        remat=getattr(c, "remat", False),
        stem_s2d=getattr(c, "stem_s2d", False),
        fold_bn=fold_bn,
        quant_mode=quant_mode,
        calib_percentile=calib_percentile,
        epilogue=plan["epilogue"],
        block_fuse=plan["block_fuse"],
        fwd_dtype=getattr(c, "fwd_dtype", "bf16"),
    )
