"""Fused BN+activation epilogue tests (ISSUE 7 tentpole prong 2).

Three layers of parity, mirroring the fused-loss suite
(tests/test_pallas_loss.py):

* tail level — the eval tail (`FusedBNAct` at `train=False`: the plain
  `fused_bn_act` expression XLA fuses into the conv, PR 26) against the
  `nn.BatchNorm(use_running_average=True)` -> `Activation` chain on the
  SAME variables, forward AND grads (w.r.t. x, scale, bias), fp32 and
  bf16, every supported activation; and that an eval-mode model holds no
  `pallas_call` and no `custom_vjp_call` while the train-mode model keeps
  every one it had;
* model level — `--epilogue fused` vs `--epilogue xla` on the full
  hourglass: identical param/stat trees (checkpoints interchange),
  allclose logits/grads/batch-stats at fp32 and bf16;
* int8-path regression — `ops.quant.fold_batchnorm` still folds the
  (tree-identical) FusedBNAct block, so the PR 5 quantization path is
  untouched by the epilogue refactor.
"""

import collections

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.models.hourglass import (
    Activation, FusedBNAct, resolve_epilogue)
from real_time_helmet_detection_tpu.ops.pallas import epilogue, residual
from real_time_helmet_detection_tpu.ops.pallas.epilogue import (
    FUSED_EPILOGUE_ACTIVATIONS, fused_bn_act)

IMSIZE = 64


def tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=2)
    base.update(kw)
    return Config(**base)


def bn_variables(rng, c=16):
    """One BatchNorm's variables with non-trivial running statistics; the
    tree `nn.BatchNorm`, `FusedBNAct` and `FusedBNAddAct` all share."""
    f32 = lambda a: jnp.asarray(a.astype(np.float32))  # noqa: E731
    return {"params": {"scale": f32(rng.standard_normal(c) * 0.5 + 1),
                       "bias": f32(rng.standard_normal(c))},
            "batch_stats": {"mean": f32(rng.standard_normal(c) * 0.3),
                            "var": f32(rng.uniform(0.5, 2.0, c))}}


def xla_eval_tail(variables, x, act, dt, skip=None):
    """The `--epilogue xla` / `--block-fuse xla` eval chain of
    `Convolution` / `Residual`: nn.BatchNorm on running statistics,
    (+ skip), Activation."""
    y = nn.BatchNorm(use_running_average=True, momentum=0.9, epsilon=1e-5,
                     dtype=dt).apply(variables, x)
    if skip is not None:
        y = y + skip
    return Activation(act).apply({}, y)


def assert_tail_parity(ref, tail, operands, names, dt):
    """Forward and sum-of-squares grads of `tail` against `ref` over
    `operands`. fp32 tolerance is op-reordering ULPs (the fold algebra
    reassociates the normalize); bf16 is the format's quantum — the XLA
    chain rounds to bf16 after the normalize and after the add, the tail
    once at its end."""
    ftol = 1e-5 if dt == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(ref(*operands), np.float32),
                               np.asarray(tail(*operands), np.float32),
                               atol=ftol, rtol=ftol)

    def loss_of(fn):
        return lambda *ops: jnp.sum(fn(*ops).astype(jnp.float32) ** 2)

    argnums = tuple(range(len(operands)))
    g_ref = jax.tree.leaves(jax.grad(loss_of(ref), argnums)(*operands))
    g_tail = jax.tree.leaves(jax.grad(loss_of(tail), argnums)(*operands))
    gtol = 1e-4 if dt == jnp.float32 else 1.5e-1
    assert len(g_ref) == len(g_tail) == len(names)
    for r, t, name in zip(g_ref, g_tail, names):
        np.testing.assert_allclose(
            np.asarray(r, np.float32), np.asarray(t, np.float32),
            rtol=gtol, atol=gtol, err_msg="%s vs ref" % name)


@pytest.mark.parametrize("act", FUSED_EPILOGUE_ACTIVATIONS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_kernel_fwd_grad_parity(act, dt):
    """The eval BN+act tail (no kernel since PR 26: `FusedBNAct` at
    train=False is the plain `fused_bn_act` expression) vs the
    nn.BatchNorm -> Activation chain on the same variables: forward +
    grads w.r.t. (x, bias, scale)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)) * 2, dt)
    variables = bn_variables(rng)
    stats = {"batch_stats": variables["batch_stats"]}
    module = FusedBNAct(activation=act, dtype=dt)
    assert_tail_parity(
        lambda x, p: xla_eval_tail({"params": p, **stats}, x, act, dt),
        lambda x, p: module.apply({"params": p, **stats}, x, train=False),
        (x, variables["params"]), ("x", "bias", "scale"), dt)


def test_kernel_rejects_unsupported_activation():
    x = jnp.zeros((1, 4, 4, 8))
    with pytest.raises(NotImplementedError):
        fused_bn_act(x, jnp.ones(8), jnp.zeros(8), activation="CELU")


def count_primitives(jaxpr, acc=None):
    """Counter of primitive names over a jaxpr and every jaxpr nested in
    its equations' parameters."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count_primitives(sub, acc)
    return acc


# (custom_vjp_call, pallas_call) in the train-mode network, counted on the
# parent of PR 26: one custom_vjp a BN tail, two kernels (stats, fwd) in it
@pytest.mark.parametrize("fields,train_counts", [
    (dict(num_stack=1, hourglass_inch=128), (37, 74)),   # the flagship
    (dict(num_stack=2, hourglass_inch=16), (67, 134)),   # two-stack toy
], ids=["flagship", "two-stack"])
def test_eval_has_no_kernel_train_keeps_every_one(monkeypatch, fields,
                                                  train_counts):
    """Selection is by `train` alone: with both levers `fused` and the
    kernels selected as on the chip, the jaxpr of an eval-mode apply
    holds no `pallas_call` and no `custom_vjp_call` (XLA gets plain
    pointwise tails it fuses into the convs), and the train-mode jaxpr
    holds exactly what it held before."""
    on_chip = lambda interpret: (True, False)  # noqa: E731
    monkeypatch.setattr(epilogue, "_resolve_pallas", on_chip)
    monkeypatch.setattr(residual, "_resolve_pallas", on_chip)
    model = build_model(tiny_cfg(epilogue="fused", block_fuse="fused",
                                 **fields), dtype=jnp.bfloat16)
    x = jnp.zeros((2, IMSIZE, IMSIZE, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), x, train=False))
    found = {}
    for train in (False, True):
        jaxpr = jax.make_jaxpr(lambda v, x: model.apply(
            v, x, train=train, mutable=["batch_stats"]))(variables, x)
        prims = count_primitives(jaxpr.jaxpr)
        found[train] = (prims["custom_vjp_call"], prims["pallas_call"])
    assert found[False] == (0, 0)
    assert found[True] == train_counts


def test_resolve_epilogue_auto_is_xla_off_tpu():
    assert resolve_epilogue(tiny_cfg(epilogue="auto")) == "xla"
    assert resolve_epilogue(tiny_cfg(epilogue="fused")) == "fused"
    assert resolve_epilogue(tiny_cfg(epilogue="xla")) == "xla"


# Train-mode comparisons of the WHOLE network need a batch whose deepest
# BatchNorm is well-conditioned: at IMSIZE 64 the hourglass bottom is a 1x1
# map, so batch 2 normalizes every channel there over TWO samples and
# amplifies last-bit differences by up to rsqrt(eps) ~ 316x (the note in
# tests/test_block_fuse.py has the measured gaps: 0.035 max |diff| on the
# ReLU logits at batch 2, 1.3e-4 at batch 8, 1.4e-5 relative L2 on the
# chip at full width — chip_smoke.py, PR 21).
TRAIN_BATCH = 8


def _init_pair(act="Mish", dtype=None, batch=2):
    cfg_x = tiny_cfg(epilogue="xla", activation=act)
    cfg_f = tiny_cfg(epilogue="fused", activation=act)
    mx, mf = build_model(cfg_x, dtype=dtype), build_model(cfg_f, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, IMSIZE, IMSIZE, 3)).astype(np.float32))
    variables = jax.jit(mx.init, static_argnames=("train",))(
        jax.random.key(0), x, train=False)
    return mx, mf, variables, x, cfg_x, cfg_f


@pytest.mark.parametrize("act", ["Mish", "ReLU"])
def test_model_tree_identical_and_logits_allclose(act):
    """Checkpoints must interchange across --epilogue modes: identical
    param/stat trees, and the SAME variables produce allclose logits in
    both eval and train mode (fp32 atol 1e-4 — the fold algebra
    reassociates the normalize)."""
    mx, mf, variables, x, _, _ = _init_pair(act, batch=TRAIN_BATCH)
    vf = jax.jit(mf.init, static_argnames=("train",))(
        jax.random.key(0), x, train=False)
    assert jax.tree.structure(variables) == jax.tree.structure(vf)

    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    np.testing.assert_allclose(ox, of, atol=1e-4, rtol=1e-4)

    oxt, mutx = mx.apply(variables, x, train=True, mutable=["batch_stats"])
    oft, mutf = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    # train mode: per-layer moment reassociation (~1e-7 rel on var) gets
    # amplified by every downstream renormalization — ~1e-4 on the logits
    # at fp32 through the full stack at a well-conditioned batch
    np.testing.assert_allclose(np.asarray(oxt), np.asarray(oft),
                               atol=2e-3, rtol=2e-3)
    # the running-stat streams must track each other (same moment
    # definitions; the Gram-dot E[x^2] reassociation shows up at ~1e-5
    # abs, which is ~1e-2 RELATIVE on near-zero variance channels)
    for a, b in zip(jax.tree.leaves(mutx["batch_stats"]),
                    jax.tree.leaves(mutf["batch_stats"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=2e-5)


@pytest.mark.slow  # 12 s at r15 --durations: gradient-equality pin
# (numerics hygiene, not robustness) — re-tiered (ISSUE 13 satellite)
def test_train_step_grads_allclose_fp32():
    """value_and_grad of the production loss through both epilogues at
    fp32: the recompute backward must match XLA autodiff."""
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.train import loss_fn
    mx, mf, variables, _, cfg_x, cfg_f = _init_pair("Mish")
    arrs = tuple(jnp.asarray(a)
                 for a in synthetic_target_batch(2, IMSIZE, seed=2))
    params, bstats = variables["params"], variables["batch_stats"]
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    (lx, _), gx = grad_fn(params, bstats, mx, *arrs, cfg_x)
    (lf, _), gf = grad_fn(params, bstats, mf, *arrs, cfg_f)
    np.testing.assert_allclose(float(lx), float(lf), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=1e-4)


def test_model_bf16_allclose():
    """bf16 (--amp) parity: per-layer bf16 rounding points differ between
    the epilogues, and BN renormalization amplifies the drift through the
    stack — the honest bound on a deep bf16 net is quanta-accumulation
    scale (observed ~0.4 max on logits of magnitude ~5), with the strict
    parity pinned at fp32 (above) and at kernel level."""
    mx, mf, variables, x, _, _ = _init_pair("Mish", dtype=jnp.bfloat16)
    ox = np.asarray(mx.apply(variables, x, train=False), np.float32)
    of = np.asarray(mf.apply(variables, x, train=False), np.float32)
    np.testing.assert_allclose(ox, of, atol=1.0, rtol=0.1)
    # mean drift ~1% of the logit scale (std ~4.4): bf16-quanta noise,
    # not a systematic shift
    assert float(np.mean(np.abs(ox - of))) < 0.1 * float(np.std(ox))


def test_ineligible_activation_keeps_xla_path_bitwise():
    """CELU is not fusable (no recompute form shipped): epilogue=fused
    must silently keep the XLA tail — bit-identical output."""
    mx, mf, variables, x, _, _ = _init_pair("CELU")
    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    assert np.array_equal(ox, of)


def test_fold_batchnorm_survives_epilogue_refactor():
    """int8-path regression (PR 5): fold_batchnorm over a fused-epilogue
    model's variables produces the fold_bn twin whose logits match the
    epilogue model's eval forward — the quantization entry contract is
    untouched by the refactor."""
    from real_time_helmet_detection_tpu.ops.quant import fold_batchnorm
    _, mf, variables, x, _, cfg_f = _init_pair("Mish")
    # advance the running stats once so the fold sees non-init statistics
    _, mut = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    variables = {"params": variables["params"],
                 "batch_stats": mut["batch_stats"]}
    folded = fold_batchnorm(variables["params"], variables["batch_stats"])
    mfold = build_model(cfg_f, fold_bn=True)
    o_fused = np.asarray(mf.apply(variables, x, train=False))
    o_fold = np.asarray(mfold.apply({"params": folded}, x, train=False))
    np.testing.assert_allclose(o_fused, o_fold, atol=1e-4, rtol=1e-4)


def test_predict_runs_with_fused_epilogue():
    """The eval surface: make_predict_fn over a fused-epilogue model
    (the graftlint trace-audit entry) produces the same detections as
    the xla-epilogue predict on the same variables."""
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    mx, mf, variables, x, cfg_x, cfg_f = _init_pair("Mish")
    px = make_predict_fn(mx, tiny_cfg(topk=16, epilogue="xla"))
    pf = make_predict_fn(mf, tiny_cfg(topk=16, epilogue="fused"))
    dx = px(variables, x)
    df = pf(variables, x)
    np.testing.assert_allclose(np.asarray(dx.scores),
                               np.asarray(df.scores), atol=1e-4)
    assert np.mean(np.asarray(dx.valid) == np.asarray(df.valid)) > 0.99
