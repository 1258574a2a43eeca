"""BatchNorm (+ skip-add) + activation tail of every conv: Pallas kernels
for training, a plain expression at eval.

Every conv in this architecture is followed by `BatchNorm -> activation`
(models/hourglass.py `Convolution`, ref /root/reference/hourglass.py:94-108
`Convolution`: conv -> BN -> act), and every residual block ends with
`BatchNorm -> (+ skip) -> activation` (`Residual`, ref :111-131). Both
modes fold the statistics into a per-channel affine, `eff_scale = gamma *
rsqrt(var + eps)` and `eff_bias = beta - mean * eff_scale` (the BN-fold
algebra of ops/quant.fold_batchnorm), and compute `act(x * eff_scale +
eff_bias [+ skip])` in f32. The skip is an optional operand of one family,
in both modes: it never enters the statistics (BatchNorm sees only the
conv's output, as in the unfused composition), it shifts the
pre-activation, and its gradient is the pass-through `ds = dz`.

**Eval** (`fused_bn_act`): the running statistics make `eff_scale` /
`eff_bias` constants, so the tail is a pointwise epilogue of the conv
that produced x. It is a plain `jax.numpy` expression, never a
`pallas_call` or a `custom_vjp`: XLA fuses it into the convolution's
output, and no separate pass over HBM is made. A Pallas custom call there
was a fusion barrier — the conv wrote x, the kernel read it and wrote it
again, and XLA put whole-activation layout copies between the conv's
layout and the kernel's (N, H*W, C) tiles (PERF.md section 6, PR 26).

**Train** (`fused_bn_act_train`): the batch statistics are a reduction
over x, a real barrier, so the chain is one `jax.custom_vjp` family:

* the forward computes batch moments in f32, then `act(x * eff_scale +
  eff_bias [+ skip])` reading x (and the skip) once and writing the
  activation once — all f32 math lives in VMEM/registers, no
  materialized converts, no saved residuals;
* the backward is the ANALYTIC BatchNorm+activation gradient
  (`_make_fused_train`), recomputing the forward terms from the same
  inputs (the ops/pallas/loss.py pattern): one pass for the per-channel
  sums, one for d(x) (and d(skip));
* layout: `(N, H, W, C) -> (N, H*W, C)`; rows block over the sublane
  axis, channels sit on the 128-wide lane axis — C=128 (the flagship
  width) fills v5e tiles exactly. (The reshape is free in row-major
  terms; on the chip XLA may still copy between a conv's layout and the
  kernel's — measured at the stem's 256² tails, PERF.md section 7.)

Off-TPU, `interpret=None` (the production default) selects a pure-jnp
custom_vjp twin of the train family built from the SAME math helpers
instead of Pallas interpret mode: identical semantics and identical
recompute structure, so CPU tests run fast. Pass interpret=True to force
the Pallas kernels in interpret mode (the parity tests do).

Selection is `--epilogue` / `--block-fuse {auto,fused,xla}` (config.py;
auto = fused on the chip only, ops/pallas/select.py); eligibility lives in
models/hourglass.py: `Convolution` decides the per-conv tail, `Residual`
whether its last conv's tail takes the skip (docs/ARCHITECTURE.md "Step
compression"). Parity vs the XLA composition is pinned in fp32 and bf16 by
tests/test_bn_tail.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .partition import batch_parallel
from . import select

# Activations the fused epilogue supports. Everything on this list has a
# cheap closed-form derivative recomputable from the pre-activation value
# alone; the exotic activations (PReLU carries a param, CELU/Sigmoid are
# not used after BN in this architecture) stay on the XLA path.
FUSED_EPILOGUE_ACTIVATIONS = ("Mish", "ReLU", "Linear")

_BLOCK_ELEMS_CAP = 1024 * 128  # elements per row block: 512 KB in f32, so
# the widest kernel (dx pass with a skip: three inputs, two outputs,
# double-buffered, plus the Mish temporaries) stays well inside v5e's 16
# MiB scoped VMEM at any channel width — the row count shrinks as
# `increase_ch` widens C

X, VEC, PART = "x", "vec", "part"  # operand kinds of `_rows_call`


def _act_fwd(z: jax.Array, act: str) -> jax.Array:
    """act(z) in f32 (ref hourglass.py:6-43 Mish/ReLU/Linear)."""
    if act == "Mish":
        return z * jnp.tanh(jax.nn.softplus(z))
    if act == "ReLU":
        return jnp.maximum(z, 0.0)
    if act == "Linear":
        return z
    raise NotImplementedError("fused epilogue: unsupported activation %r"
                              % act)


def _act_grad(z: jax.Array, act: str) -> jax.Array:
    """d act(z)/dz, recomputed from z (no saved residuals)."""
    if act == "Mish":
        t = jnp.tanh(jax.nn.softplus(z))
        return t + z * (1.0 - t * t) * jax.nn.sigmoid(z)
    if act == "ReLU":
        # ties-at-zero: subgradient 0, matching jnp.maximum's JVP at the
        # measure-zero z == 0 (max picks the second arg's tangent there)
        return (z > 0.0).astype(z.dtype)
    if act == "Linear":
        return jnp.ones_like(z)
    raise NotImplementedError("fused epilogue: unsupported activation %r"
                              % act)


def _row_block(rows: int, c: int) -> int:
    """Rows per block: the largest divisor of `rows` that is a multiple of
    16 (the bf16 sublane tile; f32 needs 8) within the element cap. Mosaic
    accepts a second-minor block dim only when it is tile-aligned or the
    whole dim, so when `rows` has no such divisor (25 rows at the bottom
    of a 320-px hourglass) the block is all of `rows`."""
    cap = max(16, _BLOCK_ELEMS_CAP // c)
    for r in range(min(rows, cap) // 16 * 16, 0, -16):
        if rows % r == 0:
            return r
    if rows * c > 4 * _BLOCK_ELEMS_CAP:
        raise ValueError(
            "fused BN kernels: %d rows x %d channels has no 16-aligned "
            "row block and is too large for one block; use the xla "
            "epilogue for this shape" % (rows, c))
    return rows


@functools.lru_cache(maxsize=None)
def _make_fused_train(act: str, eps: float, use_pallas: bool,
                      interpret: bool, has_skip: bool):
    """custom_vjp'd train-mode tail over (x3 (N, R, C), gamma (1, C) f32,
    beta (1, C) f32[, s3 (N, R, C)]) -> (out, mean (C,), var (C,)); the
    skip is the fourth operand when `has_skip`.

    Forward: batch moments of x ALONE in f32 (two-pass variance —
    E[(x-mean)^2] fuses into the reduction read, no materialized f32 copy
    or x^2; the skip never enters the statistics), then the one-pass
    `act(x*a + b [+ s])` with the fold algebra's a/b.

    Backward: the ANALYTIC BatchNorm+activation gradient, not XLA
    autodiff — the whole backward-through-statistics chain collapses to
    two per-channel sums S1 = sum(dz), S2 = sum(dz*x) plus ONE pointwise
    pass `dx = a*dz - k2*x - k1` with per-channel constants:

        z  = a*(x - mean) + beta [+ s],  a = gamma*rsqrt(var + eps)
        dz = g * act'(z)
        ds = dz                                  (pass-through)
        dgamma = rsqrt(var+eps) * (S2 - mean*S1),  dbeta = S1
        k2 = a*(S2 - mean*S1) / ((var+eps)*N),  k1 = a*S1/N - k2*mean
        dx = a*dz - k2*x - k1

    The skip shifts z but is affine in both operands, so the statistics
    terms are untouched by it. The (mean, var) outputs exist ONLY to feed
    the running-statistics buffers (the module stop_gradients them), so
    their cotangents are structurally zero and the backward drops them —
    exactly flax BatchNorm's semantics (running stats never carry
    gradient)."""
    tag = "bn_add_act" if has_skip else "bn_act"  # the kernels' `name=`s:
    # benchmark/metrics_lib.is_bn_tail_kernel selects trace events by them

    def _colsum(m2):
        """Per-channel sum of a (rows, C) array, f32-accumulated, reading
        the operand directly (no materialized f32 copy)."""
        return jnp.sum(m2, axis=0, dtype=jnp.float32)

    def _inner_cols(m2, n2):
        """Per-channel inner product sum_r m[r,c]*n[r,c] as the DIAGONAL
        of a Gram dot. XLA:CPU materializes elementwise reduction
        operands (a full-size m*n buffer feeding the reduce — measured as
        the bitcast_multiply/subtract_multiply rows of the r09
        single-block study); a dot reads both operands straight from
        their buffers and writes only (C, C). The off-diagonal compute is
        wasted FLOPs (C x the useful work) on an otherwise idle unit —
        this is the CPU TWIN only; the Pallas kernels accumulate these
        sums in-register with zero extra traffic or FLOPs."""
        gram = jax.lax.dot_general(m2, n2, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        return jnp.diagonal(gram)

    def moments(xf2, count):
        mean = _colsum(xf2) / count
        var = jnp.maximum(_inner_cols(xf2, xf2) / count
                          - jnp.square(mean), 0.0)
        return mean, var

    def coeffs(gamma2, beta2, mean, var):
        a = gamma2 * jax.lax.rsqrt(var + eps)  # (1, C) f32
        return a, beta2 - mean * a

    # The twin computes in f32 END TO END (one shared f32 view of x per
    # direction — the same single cast copy the XLA baseline's stats
    # path materializes): injecting bf16 points mid-chain (a bf16 dz, a
    # bf16 dot operand) makes XLA:CPU materialize a convert PAIR around
    # each one, which is exactly the traffic being removed (measured: it
    # doubled the flagship convert class). On TPU none of this exists —
    # the kernels read bf16 and keep f32 in registers.
    def jnp_preact(xf, a, b, skip):
        z = xf * a + b
        return z + skip[0].astype(jnp.float32) if skip else z

    def jnp_fwd(x3, gamma2, beta2, *skip):
        n, rows, c = x3.shape
        xf = x3.astype(jnp.float32)
        mean, var = moments(xf.reshape(n * rows, c), n * rows)
        a, b = coeffs(gamma2, beta2, mean, var)
        out = _act_fwd(jnp_preact(xf, a, b, skip), act)
        return out.astype(x3.dtype), mean, var

    def jnp_bwd_math(x3, gamma2, beta2, skip, mean, var, g):
        n, rows, c = x3.shape
        count = n * rows
        r2 = 1.0 / (var + eps)                     # (C,) f32
        a = gamma2 * jnp.sqrt(r2)                  # (1, C)
        b = beta2 - mean * a
        xf = x3.astype(jnp.float32)
        # dz materializes ONCE (consumers: the two channel sums, the dx
        # pass and the dskip cast); everything else recomputes from xf
        dz = g.astype(jnp.float32) * _act_grad(
            jnp_preact(xf, a, b, skip), act)
        dz2 = dz.reshape(count, c)
        xf2 = xf.reshape(count, c)
        s1 = _colsum(dz2)                          # (C,)
        s2 = _inner_cols(dz2, xf2)
        ctr = s2 - mean * s1
        dgamma = (jnp.sqrt(r2) * ctr).reshape(1, -1)
        dbeta = s1.reshape(1, -1)
        k2 = a * ctr * r2 / count
        k1 = a * s1 / count - k2 * mean
        dx = (a * dz - k2 * xf - k1).astype(x3.dtype)
        return (dx, dgamma, dbeta) + tuple(dz.astype(s3.dtype)
                                           for s3 in skip)

    def pallas_fwd(x3, gamma2, beta2, *skip):
        n, rows, _ = x3.shape
        s, ss = _rows_call(
            _stats_kernel, "bn_stats", [(X, x3)],
            [(PART, jnp.float32), (PART, jnp.float32)], interpret)
        count = float(n * rows)
        mean = _total(s) / count
        var = jnp.maximum(_total(ss) / count - jnp.square(mean), 0.0)
        a, b = coeffs(gamma2, beta2, mean, var)
        out = _rows_call(
            functools.partial(_fwd_kernel, act=act, has_skip=has_skip),
            tag + "_fwd",
            [(X, x3), (VEC, a), (VEC, b)] + [(X, s3) for s3 in skip],
            [(X, x3.dtype)], interpret)
        return out, mean, var

    def pallas_bwd(x3, gamma2, beta2, skip, mean, var, g):
        n, rows, _ = x3.shape
        count = float(n * rows)
        r2 = 1.0 / (var + eps)
        a = gamma2 * jnp.sqrt(r2)
        b = beta2 - mean * a
        recompute = [(X, x3), (VEC, a), (VEC, b)] + [(X, s3) for s3 in skip]
        # pass 1: recompute dz from (x[, skip], g), emit S1/S2 partials
        # only — dz itself never touches HBM
        s1_p, s2_p = _rows_call(
            functools.partial(_bwd_sums_kernel, act=act, has_skip=has_skip),
            tag + "_bwd_sums", recompute + [(X, g)],
            [(PART, jnp.float32), (PART, jnp.float32)], interpret)
        s1 = _total(s1_p)
        s2 = _total(s2_p)
        ctr = s2 - mean * s1
        dgamma = (jnp.sqrt(r2) * ctr).reshape(1, -1)
        dbeta = s1.reshape(1, -1)
        k2 = (a * ctr * r2 / count).astype(jnp.float32)
        k1 = a * s1.reshape(1, -1) / count - k2 * mean
        # pass 2: recompute dz again, write dx (and dskip) in one pass
        grads = _rows_call(
            functools.partial(_bwd_dx_kernel, act=act, has_skip=has_skip),
            tag + "_bwd_dx",
            recompute + [(X, g), (VEC, k1), (VEC, k2)],
            [(X, x3.dtype)] + [(X, s3.dtype) for s3 in skip], interpret)
        dx, *ds = grads if has_skip else (grads,)
        return (dx, dgamma, dbeta, *ds)

    fwd_impl = pallas_fwd if use_pallas else jnp_fwd
    bwd_impl = pallas_bwd if use_pallas else jnp_bwd_math

    @jax.custom_vjp
    def fused(x3, gamma2, beta2, *skip):
        return fwd_impl(x3, gamma2, beta2, *skip)

    def fused_fwd(x3, gamma2, beta2, *skip):
        out, mean, var = fwd_impl(x3, gamma2, beta2, *skip)
        return (out, mean, var), (x3, gamma2, beta2, skip, mean, var)

    def fused_bwd(res, cots):
        g, _g_mean, _g_var = cots  # statistics outputs: buffers only,
        # stop_gradient'd by the module — their cotangents are zero
        return bwd_impl(*res, g)

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


def _rows_call(kernel, name: str, ins, outs, interpret: bool):
    """Run `kernel` over the (sample, row-block) grid of (N, R, C) arrays.

    `ins` is a list of (kind, array): X = an activation-shaped (N, R, C)
    operand, blocked (row-block, C) per program; VEC = a per-channel
    (1, C) f32 vector, whole in every program. `outs` is a list of
    (kind, dtype): X as above, PART = one (1, C) f32 partial per program,
    returned as (N, row-blocks, 1, C) for XLA to sum — the singleton keeps
    the block's last two dims equal to the array's, which Mosaic requires
    of any block that is not (8, 128)-aligned. Both grid axes are
    independent ("parallel"), and so is the batch across chips
    (`batch_parallel`)."""
    kinds = [k for k, _ in ins]

    def call(*operands):
        n, rows, c = operands[kinds.index(X)].shape
        r = _row_block(rows, c)
        nb = rows // r
        specs = {
            X: pl.BlockSpec((None, r, c), lambda i, j: (i, j, 0),
                            memory_space=pltpu.VMEM),
            VEC: pl.BlockSpec((1, c), lambda i, j: (0, 0),
                              memory_space=pltpu.VMEM),
            PART: pl.BlockSpec((None, None, 1, c),
                               lambda i, j: (i, j, 0, 0),
                               memory_space=pltpu.VMEM),
        }
        shapes = {X: (n, rows, c), PART: (n, nb, 1, c)}
        return pl.pallas_call(
            kernel,
            grid=(n, nb),
            in_specs=[specs[k] for k in kinds],
            out_specs=tuple(specs[k] for k, _ in outs),
            out_shape=tuple(jax.ShapeDtypeStruct(shapes[k], dt)
                            for k, dt in outs),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name=name,
        )(*operands)

    out = batch_parallel(call, [k == X for k in kinds])(*(a for _, a in ins))
    return out if len(outs) > 1 else out[0]


def _total(part):
    """(N, row-blocks, 1, C) kernel partials -> (C,) sum."""
    return jnp.sum(part, axis=(0, 1, 2))


def _block_colsum(m):
    """Per-channel (1, C) sum over the rows of a (R, C) block."""
    return jnp.sum(m, axis=0, keepdims=True)


def _stats_kernel(x_ref, s_ref, ss_ref):
    x = x_ref[...].astype(jnp.float32)
    s_ref[...] = _block_colsum(x)
    ss_ref[...] = _block_colsum(x * x)


def _preact(x, a, b_ref, s_ref):
    """z = x*a + b (+ skip) in f32: the pre-activation every kernel
    recomputes. x (R, C) f32; a, b (1, C) broadcast over rows."""
    z = x * a + b_ref[...]
    if s_ref is not None:
        z = z + s_ref[...].astype(jnp.float32)
    return z


def _fwd_kernel(x_ref, a_ref, b_ref, *refs, act: str, has_skip: bool):
    s_ref, o_ref = refs if has_skip else (None, *refs)
    x = x_ref[...].astype(jnp.float32)        # (R, C)
    z = _preact(x, a_ref[...], b_ref, s_ref)  # (1, C) broadcasts over rows
    o_ref[...] = _act_fwd(z, act).astype(o_ref.dtype)


def _bwd_sums_kernel(x_ref, a_ref, b_ref, *refs, act: str, has_skip: bool):
    s_ref, g_ref, s1_ref, s2_ref = refs if has_skip else (None, *refs)
    x = x_ref[...].astype(jnp.float32)
    z = _preact(x, a_ref[...], b_ref, s_ref)
    dz = g_ref[...].astype(jnp.float32) * _act_grad(z, act)
    s1_ref[...] = _block_colsum(dz)
    s2_ref[...] = _block_colsum(dz * x)


def _bwd_dx_kernel(x_ref, a_ref, b_ref, *refs, act: str, has_skip: bool):
    s_ref, g_ref, k1_ref, k2_ref, dx_ref, ds_ref = (
        refs if has_skip else (None, *refs, None))
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...]
    z = _preact(x, a, b_ref, s_ref)
    dz = g_ref[...].astype(jnp.float32) * _act_grad(z, act)
    dx_ref[...] = (a * dz - k2_ref[...] * x
                   - k1_ref[...]).astype(dx_ref.dtype)
    if ds_ref is not None:
        ds_ref[...] = dz.astype(ds_ref.dtype)


def fused_bn_act_train(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                       skip: jax.Array | None = None, *,
                       eps: float = 1e-5, activation: str = "Mish",
                       interpret: bool | None = None):
    """Train-mode fused BatchNorm (+ skip-add) + activation: batch moments
    of x, normalize, add and activation in fused passes with the ANALYTIC
    BN backward (see `_make_fused_train`). Returns `(out, mean, var)`;
    mean/var are the BATCH statistics of x for the caller's
    running-average update and must be consumed under `stop_gradient`
    (the backward treats their cotangents as structurally zero, exactly
    like flax BatchNorm's buffers).

    Differentiable w.r.t. x, gamma, beta and, when given, skip (the
    residual block's other branch, same shape as x). interpret=None
    (production): the Pallas kernels on the chip, the pure-jnp custom_vjp
    twin elsewhere (same math, same recompute structure — see module
    docstring). interpret=True/False forces the Pallas path in that mode
    (tests pin kernel parity with interpret=True)."""
    if activation not in FUSED_EPILOGUE_ACTIVATIONS:
        raise NotImplementedError(
            "fused epilogue supports %s, got %r"
            % (FUSED_EPILOGUE_ACTIVATIONS, activation))
    c = x.shape[-1]
    if gamma.shape != (c,) or beta.shape != (c,):
        raise ValueError("gamma/beta must be (%d,), got %s/%s"
                         % (c, gamma.shape, beta.shape))
    if skip is not None and skip.shape != x.shape:
        raise ValueError("skip must match the BN input shape %s, got %s"
                         % (x.shape, skip.shape))
    use_pallas, interp = ((select.on_chip(), False) if interpret is None
                          else (True, bool(interpret)))
    # (N, H, W, C) -> (N, H*W, C) merges adjacent row-major dims; whether
    # the chip copies between the conv's layout and this one is XLA's
    # choice (PERF.md section 7)
    lead = x.shape[0] if x.ndim >= 3 else 1
    rows = x.size // (lead * c)
    x3 = x.reshape(lead, rows, c)
    skip3 = () if skip is None else (skip.reshape(lead, rows, c),)
    fn = _make_fused_train(str(activation), float(eps), use_pallas, interp,
                           skip is not None)
    out, mean, var = fn(x3, gamma.astype(jnp.float32).reshape(1, c),
                        beta.astype(jnp.float32).reshape(1, c), *skip3)
    return out.reshape(x.shape), mean, var


def fused_bn_act(x: jax.Array, eff_scale: jax.Array, eff_bias: jax.Array,
                 skip: jax.Array | None = None, *,
                 activation: str = "Mish") -> jax.Array:
    """Eval-mode BN tail `act(x * eff_scale + eff_bias [+ skip])`, f32
    inside, x's dtype out — the arithmetic of `_fwd_kernel`, as a plain
    expression that XLA fuses into the convolution producing x (see
    module docstring).

    x: (..., C) conv output; eff_scale/eff_bias: (C,) — the BN-fold
    algebra's per-channel affine from the running statistics; skip: the
    residual block's other branch, same shape as x. Plain autodiff."""
    z = x.astype(jnp.float32) * eff_scale + eff_bias
    if skip is not None:
        z = z + skip.astype(jnp.float32)
    return _act_fwd(z, activation).astype(x.dtype)
