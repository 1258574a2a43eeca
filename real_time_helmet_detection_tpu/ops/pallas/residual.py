"""Fused residual-block tail for training: BatchNorm + skip-add +
activation (Pallas).

Every Residual block in this architecture ends with the same three-step
tail (models/hourglass.py `Residual`, ref /root/reference/hourglass.py:
111-131 `Residual`: body conv -> BN -> (+ skip) -> act): the body's last
conv feeds a BatchNorm, the skip branch is ADDED, and Mish closes the
block. The ISSUE-7 epilogue (ops/pallas/epilogue.py) already fused
BN+act per conv, but in the train step the block tail still pays the
skip-add round trip: XLA materializes the normalized tensor, re-reads it
with the skip for the add, and re-reads the sum for the activation —
with f32<->bf16 converts between each under `--amp`.

At eval the tail has no reduction and is `epilogue.fused_bn_act` with a
`skip`: a plain expression XLA fuses into the conv. In the train step
the whole tail collapses into ONE pass family per direction:

* batch moments are of the BN INPUT y alone — the skip never enters the
  statistics (identical to the unfused composition, where BatchNorm sees
  only the body conv's output);
* forward kernel: `act(y * a + b + skip)` reading (y, skip) once and
  writing the activation once, with the fold algebra's per-channel
  `a = gamma*rsqrt(var+eps)`, `b = beta - mean*a`;
* the `jax.custom_vjp` backward extends the epilogue's ANALYTIC BN
  gradient *through* the add: with `z = a*y + b + s` and
  `dz = g*act'(z)`, the skip's gradient is the pass-through `ds = dz`
  and (dy, dgamma, dbeta) keep the exact S1/S2 channel-sum formulas
  (S1 = sum(dz), S2 = sum(dz*y)) — the add contributes no new
  statistics terms because it is affine in both operands;
* layout is the epilogue's: (N, H, W, C) -> (N, H*W, C), row blocks on
  the sublane axis, channels on the 128-wide lane axis.

Off-TPU, `interpret=None` (the production default) selects a pure-jnp
custom_vjp twin computing f32 end to end with the same Gram-dot
reduction idiom as the epilogue twin — identical semantics and recompute
structure, honest under scripts/roofline.py's counting model (which
replaces the twin's rows by `site_kernel_bytes` analytically, exactly
like the epilogue's). Pass interpret=True to force Pallas interpret mode
(parity tests only).

Selection is `--block-fuse {auto,fused,xla}` (config.py), auto = fused
on TPU only; eligibility rules live in models/hourglass.py `Residual`
(docs/ARCHITECTURE.md "Step compression"). Parity vs the unfused
composition is pinned in fp32 and bf16 by tests/test_block_fuse.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .epilogue import (FUSED_EPILOGUE_ACTIVATIONS, PART, VEC, X, _act_fwd,
                       _act_grad, _block_colsum, _resolve_pallas,
                       _rows_call, _stats_kernel, _total)

__all__ = ["FUSED_EPILOGUE_ACTIVATIONS", "fused_bn_add_act_train",
           "reset_site_registry", "traced_sites", "site_kernel_bytes"]

# Trace-time call-site registry, separate from the epilogue's so
# scripts/roofline.py can substitute each kernel family at its own
# transfer count. Host-side append only — the traced program (and the
# graftlint retrace signature) is unaffected.
_TRACE_SITES: list = []


def reset_site_registry() -> None:
    _TRACE_SITES.clear()


def traced_sites() -> list:
    """[(n_elements, itemsize_bytes), ...] of every train-mode fused
    block-tail call traced since the last reset."""
    return list(_TRACE_SITES)


def site_kernel_bytes(elems: int, itemsize: int) -> float:
    """Operand+result HBM bytes of the REAL kernel sequence for one
    train-mode fused block-tail site (the roofline counting rule; C-sized
    vectors/partials negligible): stats pass reads y; fwd pass reads
    (y, skip), writes out; backward sums pass reads (y, skip, g);
    backward dx pass reads (y, skip, g), writes (dy, dskip) -> 12
    activation-sized transfers."""
    return 12.0 * elems * itemsize


def _fwd_add_kernel(x_ref, a_ref, b_ref, s_ref, o_ref, *, act: str):
    x = x_ref[...].astype(jnp.float32)        # (R, C)
    z = x * a_ref[...] + b_ref[...] + s_ref[...].astype(jnp.float32)
    o_ref[...] = _act_fwd(z, act).astype(o_ref.dtype)


def _bwd_add_sums_kernel(x_ref, a_ref, b_ref, s_ref, g_ref, s1_ref,
                         s2_ref, *, act: str):
    x = x_ref[...].astype(jnp.float32)
    z = x * a_ref[...] + b_ref[...] + s_ref[...].astype(jnp.float32)
    dz = g_ref[...].astype(jnp.float32) * _act_grad(z, act)
    s1_ref[...] = _block_colsum(dz)
    s2_ref[...] = _block_colsum(dz * x)


def _bwd_add_dx_kernel(x_ref, a_ref, b_ref, s_ref, g_ref, k1_ref, k2_ref,
                       dx_ref, ds_ref, *, act: str):
    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...]
    z = x * a + b_ref[...] + s_ref[...].astype(jnp.float32)
    dz = g_ref[...].astype(jnp.float32) * _act_grad(z, act)
    dx_ref[...] = (a * dz - k2_ref[...] * x
                   - k1_ref[...]).astype(dx_ref.dtype)
    ds_ref[...] = dz.astype(ds_ref.dtype)


def _colsum(m2):
    """Per-channel sum of a (rows, C) array, f32-accumulated, reading the
    operand directly (no materialized f32 copy)."""
    return jnp.sum(m2, axis=0, dtype=jnp.float32)


def _inner_cols(m2, n2):
    """Per-channel inner product as the diagonal of a Gram dot — the
    epilogue twin's XLA:CPU idiom (a dot reads operands straight from
    their buffers; an elementwise reduce materializes the product). CPU
    twin only; the Pallas kernels accumulate in-register."""
    gram = jax.lax.dot_general(m2, n2, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return jnp.diagonal(gram)


@functools.lru_cache(maxsize=None)
def _make_fused_add_train(act: str, eps: float, use_pallas: bool,
                          interpret: bool):
    """custom_vjp'd train tail (y3 (N, R, C), gamma (1, C) f32,
    beta (1, C) f32, s3 (N, R, C)) -> (out, mean (C,), var (C,)).

    Forward: batch moments of y ALONE (the skip never enters the
    statistics — identical to the unfused BatchNorm), then the one-pass
    `act(y*a + b + s)` with the fold algebra's a/b.

    Backward: the epilogue's analytic BatchNorm gradient extended
    through the add. With z = a*(y - mean) + beta + s:

        dz = g * act'(z)
        ds = dz                                  (pass-through)
        dgamma = rsqrt(var+eps) * (S2 - mean*S1),  dbeta = S1
        k2 = a*(S2 - mean*S1) / ((var+eps)*N),  k1 = a*S1/N - k2*mean
        dy = a*dz - k2*y - k1

    with S1 = sum(dz), S2 = sum(dz*y) — the skip shifts z but is affine,
    so the statistics terms are untouched. (mean, var) feed ONLY the
    running-statistics buffers (the module stop_gradients them); the
    backward drops their zero cotangents."""

    def moments(xf2, count):
        mean = _colsum(xf2) / count
        var = jnp.maximum(_inner_cols(xf2, xf2) / count
                          - jnp.square(mean), 0.0)
        return mean, var

    def coeffs(gamma2, beta2, mean, var):
        a = gamma2 * jax.lax.rsqrt(var + eps)  # (1, C) f32
        return a, beta2 - mean * a

    # Twin computes f32 END TO END (the epilogue twin's rationale: bf16
    # points mid-chain make XLA:CPU materialize convert pairs — the very
    # traffic being removed). On TPU the kernels read bf16 and keep f32
    # in registers.
    def jnp_fwd(x3, gamma2, beta2, s3):
        n, rows, c = x3.shape
        xf = x3.astype(jnp.float32)
        mean, var = moments(xf.reshape(n * rows, c), n * rows)
        a, b = coeffs(gamma2, beta2, mean, var)
        out = _act_fwd(xf * a + b + s3.astype(jnp.float32), act)
        return out.astype(x3.dtype), mean, var

    def jnp_bwd_math(x3, gamma2, beta2, s3, mean, var, g):
        n, rows, c = x3.shape
        count = n * rows
        r2 = 1.0 / (var + eps)                     # (C,) f32
        a = gamma2 * jnp.sqrt(r2)                  # (1, C)
        b = beta2 - mean * a
        xf = x3.astype(jnp.float32)
        # dz materializes ONCE (consumers: the two channel sums, the dy
        # pass and the dskip cast); everything else recomputes from xf
        dz = g.astype(jnp.float32) * _act_grad(
            xf * a + b + s3.astype(jnp.float32), act)
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
        ds = dz.astype(s3.dtype)
        return dx, dgamma, dbeta, ds

    def pallas_fwd(x3, gamma2, beta2, s3):
        n, rows, _ = x3.shape
        s, ss = _rows_call(
            _stats_kernel, "bn_stats", [(X, x3)],
            [(PART, jnp.float32), (PART, jnp.float32)], interpret)
        count = float(n * rows)
        mean = _total(s) / count
        var = jnp.maximum(_total(ss) / count - jnp.square(mean), 0.0)
        a, b = coeffs(gamma2, beta2, mean, var)
        out = _rows_call(
            functools.partial(_fwd_add_kernel, act=act), "bn_add_act_fwd",
            [(X, x3), (VEC, a), (VEC, b), (X, s3)], [(X, x3.dtype)],
            interpret)
        return out, mean, var

    def pallas_bwd(x3, gamma2, beta2, s3, mean, var, g):
        n, rows, _ = x3.shape
        count = float(n * rows)
        r2 = 1.0 / (var + eps)
        a = gamma2 * jnp.sqrt(r2)
        b = beta2 - mean * a
        # pass 1: recompute dz from (y, skip, g), emit S1/S2 partials —
        # dz itself never touches HBM
        s1_p, s2_p = _rows_call(
            functools.partial(_bwd_add_sums_kernel, act=act),
            "bn_add_act_bwd_sums",
            [(X, x3), (VEC, a), (VEC, b), (X, s3), (X, g)],
            [(PART, jnp.float32), (PART, jnp.float32)], interpret)
        s1 = _total(s1_p)
        s2 = _total(s2_p)
        ctr = s2 - mean * s1
        dgamma = (jnp.sqrt(r2) * ctr).reshape(1, -1)
        dbeta = s1.reshape(1, -1)
        k2 = (a * ctr * r2 / count).astype(jnp.float32)
        k1 = a * s1.reshape(1, -1) / count - k2 * mean
        # pass 2: recompute dz again, write (dy, dskip) in one pass
        dx, ds = _rows_call(
            functools.partial(_bwd_add_dx_kernel, act=act),
            "bn_add_act_bwd_dx",
            [(X, x3), (VEC, a), (VEC, b), (X, s3), (X, g), (VEC, k1),
             (VEC, k2)],
            [(X, x3.dtype), (X, s3.dtype)], interpret)
        return dx, dgamma, dbeta, ds

    fwd_impl = pallas_fwd if use_pallas else jnp_fwd

    @jax.custom_vjp
    def fused(x3, gamma2, beta2, s3):
        return fwd_impl(x3, gamma2, beta2, s3)

    def fused_fwd(x3, gamma2, beta2, s3):
        out, mean, var = fwd_impl(x3, gamma2, beta2, s3)
        return (out, mean, var), (x3, gamma2, beta2, s3, mean, var)

    def fused_bwd(res, cots):
        x3, gamma2, beta2, s3, mean, var = res
        g, _g_mean, _g_var = cots  # statistics outputs: buffers only,
        # stop_gradient'd by the module — their cotangents are zero
        if use_pallas:
            return pallas_bwd(x3, gamma2, beta2, s3, mean, var, g)
        return jnp_bwd_math(x3, gamma2, beta2, s3, mean, var, g)

    fused.defvjp(fused_fwd, fused_bwd)
    return fused


def _prep(x, skip, gamma, beta):
    c = x.shape[-1]
    if gamma.shape != (c,) or beta.shape != (c,):
        raise ValueError("per-channel vectors must be (%d,), got %s/%s"
                         % (c, gamma.shape, beta.shape))
    if skip.shape != x.shape:
        raise ValueError("skip must match the BN input shape %s, got %s"
                         % (x.shape, skip.shape))
    # (N, H, W, C) -> (N, H*W, C) merges adjacent row-major dims; whether
    # the chip copies between the conv's layout and this one is XLA's
    # choice (PERF.md section 7)
    lead = x.shape[0] if x.ndim >= 3 else 1
    rows = x.size // (lead * c)
    return (x.reshape(lead, rows, c), skip.reshape(lead, rows, c),
            gamma.astype(jnp.float32).reshape(1, c),
            beta.astype(jnp.float32).reshape(1, c))


def fused_bn_add_act_train(x: jax.Array, gamma: jax.Array,
                           beta: jax.Array, skip: jax.Array, *,
                           eps: float = 1e-5, activation: str = "Mish",
                           interpret: bool | None = None):
    """Train-mode fused block tail: batch moments of x, normalize,
    skip-add and activation in fused passes with the analytic backward
    extended through the add (see `_make_fused_add_train`). Returns
    `(out, mean, var)`; mean/var are the BATCH statistics of x for the
    caller's running-average update and must be consumed under
    `stop_gradient`.

    Differentiable w.r.t. x, gamma, beta AND skip. `interpret` semantics
    match `epilogue.fused_bn_act_train`."""
    if activation not in FUSED_EPILOGUE_ACTIVATIONS:
        raise NotImplementedError(
            "fused block tail supports %s, got %r"
            % (FUSED_EPILOGUE_ACTIVATIONS, activation))
    use_pallas, interp = _resolve_pallas(interpret)
    x3, s3, g2, b2 = _prep(x, skip, gamma, beta)
    _TRACE_SITES.append((int(x.size), int(jnp.dtype(x.dtype).itemsize)))
    fn = _make_fused_add_train(str(activation), float(eps), use_pallas,
                               interp)
    out, mean, var = fn(x3, g2, b2, s3)
    return out.reshape(x.shape), mean, var
