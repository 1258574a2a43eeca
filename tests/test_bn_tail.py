"""BatchNorm-tail tests: the fused BN (+ skip-add) + activation family
(ops/pallas/epilogue.py), its module (`FusedBNAct`), the two levers that
select it (`--epilogue` a conv, `--block-fuse` a residual block's tail) and
the one place that resolves them (ops/pallas/select.py).

Three layers of parity, mirroring the fused-loss suite
(tests/test_pallas_loss.py), each with and without the skip:

* kernel level — `fused_bn_act_train` (jnp twin AND Pallas interpret)
  against the plain XLA chain BN(x) -> [+skip] -> act, forward AND grads
  (w.r.t. x, scale, bias and the skip's pass-through), fp32 and bf16; the
  eval tail (`FusedBNAct` at `train=False`: the plain `fused_bn_act`
  expression XLA fuses into the conv) against nn.BatchNorm -> [+skip] ->
  Activation on the same variables; and that an eval-mode model holds no
  `pallas_call` and no `custom_vjp_call` while the train-mode model keeps
  every one, under the `name=`s the benchmark reads;
* model level — each lever `fused` vs `xla` on the full hourglass, for
  every eligible variant: identical param/stat trees (checkpoints
  interchange), allclose logits/grads/batch-stats; the ghost variant and
  non-fusable activations are INELIGIBLE and must keep the xla tail
  bit-exactly;
* downstream regression — `ops.quant.fold_batchnorm` still folds the
  (tree-identical) FusedBNAct block, and the 8-device-mesh train step
  matches single-device, so the quantization path and the data-parallel
  plane are untouched by the fusion.
"""

import collections

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.models.hourglass import (Activation,
                                                             FusedBNAct)
from real_time_helmet_detection_tpu.ops.pallas import select
from real_time_helmet_detection_tpu.ops.pallas.epilogue import (
    FUSED_EPILOGUE_ACTIVATIONS, _act_fwd, fused_bn_act, fused_bn_act_train)

IMSIZE = 64
EPS = 1e-5
SKIP = pytest.mark.parametrize("skip", [False, True],
                               ids=["no_skip", "skip"])
LEVER = pytest.mark.parametrize("lever", ["epilogue", "block_fuse"])


def tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=2)
    base.update(kw)
    return Config(**base)


def bn_variables(rng, c=16):
    """One BatchNorm's variables with non-trivial running statistics; the
    tree `nn.BatchNorm` and `FusedBNAct` share."""
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


def _ref_train_chain(x, gamma, beta, *skip, act):
    """The unfused composition: BatchNorm with batch moments of x ALONE
    (biased variance, flax's normalizer), then +skip, then act — what
    nn.BatchNorm -> [add ->] Activation computes in train mode."""
    xf = x.astype(jnp.float32)
    c = x.shape[-1]
    xr = xf.reshape(-1, c)
    mean = jnp.mean(xr, axis=0)
    var = jnp.maximum(jnp.mean(jnp.square(xr), axis=0)
                      - jnp.square(mean), 0.0)
    a = gamma * jax.lax.rsqrt(var + EPS)
    z = xf * a + (beta - mean * a)
    for s in skip:
        z = z + s.astype(jnp.float32)
    return _act_fwd(z, act).astype(x.dtype), mean, var


def _rand_args(dt, skip, seed=0):
    """(x, gamma, beta[, skip]) of one (2, 8, 8, 16) tail."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)) * 2, dt)
    s = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), dt)
    gamma = jnp.asarray(
        (rng.standard_normal(16) * 0.5 + 1).astype(np.float32))
    beta = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    return (x, gamma, beta, s) if skip else (x, gamma, beta)


@SKIP
@pytest.mark.parametrize("act", FUSED_EPILOGUE_ACTIVATIONS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_train_kernel_fwd_grad_parity(act, dt, skip):
    """fused_bn_act_train (jnp twin AND Pallas interpret) vs the XLA
    chain: forward, batch moments, AND grads w.r.t. (x, gamma, beta[,
    skip]) — the analytic backward (S1/S2 formulas + pass-through dskip)
    must match full autodiff through the moments."""
    args = _rand_args(dt, skip)
    argnums = tuple(range(len(args)))

    def loss_of(fn):
        return lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) ** 2)

    ref = lambda *a: _ref_train_chain(*a, act=act)  # noqa: E731
    fused = lambda *a: fused_bn_act_train(*a, activation=act)  # noqa: E731
    pallas = lambda *a: fused_bn_act_train(  # noqa: E731
        *a, activation=act, interpret=True)

    ftol = 1e-5 if dt == jnp.float32 else 3e-2
    o_ref, m_ref, v_ref = ref(*args)
    o_f, m_f, v_f = fused(*args)
    o_p, m_p, v_p = pallas(*args)
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_f, np.float32),
                               atol=ftol, rtol=ftol)
    np.testing.assert_allclose(np.asarray(o_f, np.float32),
                               np.asarray(o_p, np.float32),
                               rtol=1e-5, atol=1e-5)
    # the statistics feed the running buffers: same moment definitions
    np.testing.assert_allclose(np.asarray(m_ref), np.asarray(m_f),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v_ref), np.asarray(v_f),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_f), np.asarray(m_p),
                               rtol=1e-5, atol=1e-6)

    g_ref = jax.grad(loss_of(ref), argnums)(*args)
    g_f = jax.grad(loss_of(fused), argnums)(*args)
    g_p = jax.grad(loss_of(pallas), argnums)(*args)
    gtol = 1e-4 if dt == jnp.float32 else 1.5e-1
    # pallas-vs-jnp: identical math, but the bf16 output-boundary cast
    # can round an element to the neighboring ulp (~0.8% rel)
    ptol = 1e-4 if dt == jnp.float32 else 1e-2
    for r, f, p, name in zip(g_ref, g_f, g_p,
                             ("x", "gamma", "beta", "skip")):
        np.testing.assert_allclose(
            np.asarray(r, np.float32), np.asarray(f, np.float32),
            rtol=gtol, atol=gtol, err_msg="%s vs ref" % name)
        np.testing.assert_allclose(
            np.asarray(f, np.float32), np.asarray(p, np.float32),
            rtol=ptol, atol=ptol, err_msg="%s pallas vs jnp" % name)


@SKIP
@pytest.mark.parametrize("act", FUSED_EPILOGUE_ACTIVATIONS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_eval_tail_fwd_grad_parity(act, dt, skip):
    """The eval tail (no kernel: `FusedBNAct` at train=False is the plain
    `fused_bn_act` expression, with the skip when given) vs nn.BatchNorm
    -> [+skip] -> Activation on the same variables: forward + grads
    w.r.t. (x, bias, scale[, skip])."""
    x, _, _, *s = _rand_args(dt, skip, seed=1)
    variables = bn_variables(np.random.default_rng(1))
    stats = {"batch_stats": variables["batch_stats"]}
    module = FusedBNAct(activation=act, dtype=dt)
    assert_tail_parity(
        lambda x, p, *s: xla_eval_tail({"params": p, **stats}, x, act, dt,
                                       *s),
        lambda x, p, *s: module.apply({"params": p, **stats}, x,
                                      False, *s),
        (x, variables["params"], *s),
        ("x", "bias", "scale", "skip")[:3 + skip], dt)


@SKIP
def test_kernel_rejects_unsupported_activation_and_shapes(skip):
    x = jnp.zeros((1, 4, 4, 8))
    s = (x,) if skip else ()
    with pytest.raises(NotImplementedError):
        fused_bn_act(x, jnp.ones(8), jnp.zeros(8), *s, activation="CELU")
    with pytest.raises(NotImplementedError):
        fused_bn_act_train(x, jnp.ones(8), jnp.zeros(8), *s,
                           activation="CELU")
    with pytest.raises(ValueError, match="gamma/beta"):
        fused_bn_act_train(x, jnp.ones(4), jnp.zeros(8), *s)
    if skip:
        with pytest.raises(ValueError, match="skip"):
            fused_bn_act_train(x, jnp.ones(8), jnp.zeros(8),
                               jnp.zeros((1, 4, 4, 4)))


def count_primitives(jaxpr, acc=None):
    """Counter of primitive names — and, under ("pallas_call", name), of
    the kernels' `name=`s — over a jaxpr and every jaxpr nested in its
    equations' parameters."""
    acc = collections.Counter() if acc is None else acc
    for eqn in jaxpr.eqns:
        acc[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            acc["pallas_call", eqn.params["name"]] += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    count_primitives(sub, acc)
    return acc


# (custom_vjp_call, pallas_call) in the train-mode network and (tails
# without a skip, tails with one), counted on the parent of PR 31: one
# custom_vjp a BN tail, two kernels (stats, fwd) in it, two more (sums,
# dx) in its backward. benchmark/metrics_lib.is_bn_tail_kernel selects
# the trace events of `bn_tail_roofline.train` by these seven names.
@pytest.mark.parametrize("fields,train_counts,tails", [
    (dict(num_stack=1, hourglass_inch=128), (37, 74), (20, 17)),  # flagship
    (dict(num_stack=2, hourglass_inch=16), (67, 134), (36, 31)),
], ids=["flagship", "two-stack"])
def test_eval_has_no_kernel_train_keeps_every_one(monkeypatch, fields,
                                                  train_counts, tails):
    """Selection is by `train` alone: with both levers `fused` and the
    kernels selected as on the chip, the jaxpr of an eval-mode apply
    holds no `pallas_call` and no `custom_vjp_call` (XLA gets plain
    pointwise tails it fuses into the convs), and the train-mode jaxpr
    holds exactly what it held before, forward and backward, by name."""
    monkeypatch.setattr(select, "on_chip", lambda: True)
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

    def loss(params, batch_stats):
        out, _ = model.apply({"params": params, "batch_stats": batch_stats},
                             x, train=True, mutable=["batch_stats"])
        return jnp.sum(out.astype(jnp.float32))

    prims = count_primitives(jax.make_jaxpr(jax.grad(loss))(
        variables["params"], variables["batch_stats"]).jaxpr)
    plain, added = tails
    assert {k[1]: n for k, n in prims.items() if k[0] == "pallas_call"} == {
        "bn_stats": plain + added,
        "bn_act_fwd": plain, "bn_act_bwd_sums": plain,
        "bn_act_bwd_dx": plain,
        "bn_add_act_fwd": added, "bn_add_act_bwd_sums": added,
        "bn_add_act_bwd_dx": added}


@pytest.mark.parametrize("chip", [False, True], ids=["off_chip", "on_chip"])
@pytest.mark.parametrize("key,field,value", [
    (key, field, value)
    for key, field, values in (
        ("loss", "loss_kernel", ("auto", "fused", "xla")),
        ("epilogue", "epilogue", ("auto", "fused", "xla")),
        ("block_fuse", "block_fuse", ("auto", "fused", "xla")),
        ("peak", "use_pallas", (True, False)))
    for value in values])
def test_kernel_plan(monkeypatch, key, field, value, chip):
    """`auto` (and `use_pallas=True`) is the kernel on the chip and XLA
    off it; `fused` / `xla` (and `use_pallas=False`) are what they say on
    any backend; the other three answers stay at their defaults'."""
    monkeypatch.setattr(select, "on_chip", lambda: chip)
    auto = "fused" if chip else "xla"
    want = dict.fromkeys(("loss", "epilogue", "block_fuse", "peak"), auto)
    want[key] = {"auto": auto, True: auto, False: "xla"}.get(value, value)
    assert select.kernel_plan(tiny_cfg(**{field: value})) == want


def test_kernel_plan_reads_the_backend():
    assert not select.on_chip()  # the suite runs on the CPU
    assert select.choose("auto") == "xla"


# Train-mode comparisons of the WHOLE network need a batch whose deepest
# BatchNorm is well-conditioned. At IMSIZE 64 the bottom of the hourglass
# is a 1x1 map, so at batch 2 every channel there is normalized over TWO
# samples: (x - mean) * rsqrt(var + eps) is then +-1 unless the two values
# nearly coincide, where it amplifies their last-bit difference by up to
# rsqrt(eps) ~ 316x — a chaotic comparison of any two reassociations of
# the same math. Measured on CPU (jax 0.9.0, PR 21), fused vs xla tails,
# fp32 train-mode logits: batch 2 -> max |diff| 0.19 (ReLU) / 0.022
# (Mish); batch 8 -> 8e-5 / 6e-5. On the chip at full width (b16, 512^2,
# w128, fp32 at HIGHEST matmul precision) chip_smoke.py's model parity
# measured a relative L2 gap of 1.4e-5 (4 x TPU v5 lite, PR 21).
TRAIN_BATCH = 8


def _init_pair(lever, variant="residual", act="Mish", dtype=None, batch=2):
    """The same architecture with `lever` at xla and at fused (the other
    lever at its default: xla off the chip), and the xla model's
    variables."""
    cfg_x = tiny_cfg(variant=variant, activation=act, **{lever: "xla"})
    cfg_f = tiny_cfg(variant=variant, activation=act, **{lever: "fused"})
    mx, mf = build_model(cfg_x, dtype=dtype), build_model(cfg_f, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, IMSIZE, IMSIZE, 3)).astype(np.float32))
    variables = jax.jit(mx.init, static_argnames=("train",))(
        jax.random.key(0), x, train=False)
    return mx, mf, variables, x, cfg_x, cfg_f


@pytest.mark.parametrize("lever,variant,act", [
    ("epilogue", "residual", "Mish"), ("epilogue", "residual", "ReLU"),
    ("block_fuse", "residual", "Mish"), ("block_fuse", "depthwise", "Mish")])
def test_model_tree_identical_and_checkpoints_interchange(lever, variant,
                                                          act):
    """Checkpoints must interchange across --epilogue / --block-fuse
    modes: the children carry the same names whichever tail runs, so the
    trees are identical INCLUDING leaf values (flax derives param RNGs
    from the module path), and the SAME variables produce allclose logits
    under either tail, in eval and in train mode."""
    mx, mf, variables, x, _, _ = _init_pair(lever, variant, act,
                                            batch=TRAIN_BATCH)
    vf = jax.jit(mf.init, static_argnames=("train",))(
        jax.random.key(0), x, train=False)
    assert jax.tree.structure(variables) == jax.tree.structure(vf)
    for a, b in zip(jax.tree.leaves(variables), jax.tree.leaves(vf)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # eval: the fused eval pass and the unfused chain share the fold
    # algebra at f32 — parity is reassociation-tight
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
# (numerics hygiene, not robustness)
def test_train_step_grads_allclose_fp32():
    """value_and_grad of the production loss through both epilogues at
    fp32: the recompute backward must match XLA autodiff."""
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.train import loss_fn
    mx, mf, variables, _, cfg_x, cfg_f = _init_pair("epilogue")
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


@pytest.mark.parametrize("variant", ["residual", "depthwise"])
def test_model_train_grads_agree(variant):
    """Sum-of-squares grads through the full train-mode stack, fused vs
    xla block tails at fp32. The analytic backward reassociates the
    per-channel sums, and BN renormalization amplifies that through the
    stack — the honest bound is relative to the tree-wide scale, with the
    strict per-element parity pinned at kernel level above. Run at
    TRAIN_BATCH: see its note for what batch 2 does to this comparison."""
    mx, mf, variables, x, _, _ = _init_pair("block_fuse", variant,
                                            batch=TRAIN_BATCH)

    def loss(m):
        def f(params):
            out, _ = m.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return f

    gx = jax.grad(loss(mx))(variables["params"])
    gf = jax.grad(loss(mf))(variables["params"])
    glob = max(float(np.max(np.abs(np.asarray(leaf, np.float32))))
               for leaf in jax.tree.leaves(gx))
    # observed worst: 2.2e-3·glob residual, 1.5e-2·glob depthwise; BN
    # renormalization leaves near-cancelled leaves (max ~1e-5·glob)
    # whose own scale is meaningless — normalize tree-wide
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gf)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert float(np.max(np.abs(a - b))) <= 5e-2 * glob


def test_model_bf16_allclose():
    """bf16 (--amp) parity: per-layer bf16 rounding points differ between
    the epilogues, and BN renormalization amplifies the drift through the
    stack — the honest bound on a deep bf16 net is quanta-accumulation
    scale (observed ~0.4 max on logits of magnitude ~5), with the strict
    parity pinned at fp32 (above) and at kernel level."""
    mx, mf, variables, x, _, _ = _init_pair("epilogue", dtype=jnp.bfloat16)
    ox = np.asarray(mx.apply(variables, x, train=False), np.float32)
    of = np.asarray(mf.apply(variables, x, train=False), np.float32)
    np.testing.assert_allclose(ox, of, atol=1.0, rtol=0.1)
    # mean drift ~1% of the logit scale (std ~4.4): bf16-quanta noise,
    # not a systematic shift
    assert float(np.mean(np.abs(ox - of))) < 0.1 * float(np.std(ox))


def test_ghost_variant_is_ineligible_and_bitwise_unchanged():
    """The ghost block's tail is a concat of two separately-normalized
    halves — no single BN feeds the add, so block_fuse=fused must
    silently keep the exact xla program (bit-identical outputs)."""
    mx, mf, variables, x, _, _ = _init_pair("block_fuse", "ghost")
    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    assert np.array_equal(ox, of)
    oxt, _ = mx.apply(variables, x, train=True, mutable=["batch_stats"])
    oft, _ = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    assert np.array_equal(np.asarray(oxt), np.asarray(oft))


@LEVER
def test_ineligible_activation_keeps_xla_path_bitwise(lever):
    """CELU is not fusable (no recompute form shipped): either lever at
    fused must silently keep the XLA tail — bit-identical output."""
    mx, mf, variables, x, _, _ = _init_pair(lever, act="CELU")
    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    assert np.array_equal(ox, of)


@LEVER
def test_fold_batchnorm_survives_fused_tail(lever):
    """int8-path regression: fold_batchnorm over a fused-tail model's
    variables produces the fold_bn twin whose logits match the fused
    model's eval forward — FusedBNAct keeps the exact Conv_0/BatchNorm_0
    sibling pattern the fold walks, with or without a skip."""
    from real_time_helmet_detection_tpu.ops.quant import fold_batchnorm
    _, mf, variables, x, _, cfg_f = _init_pair(lever)
    # advance the running stats once so the fold sees non-init statistics
    _, mut = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    variables = {"params": variables["params"],
                 "batch_stats": mut["batch_stats"]}
    folded = fold_batchnorm(variables["params"], variables["batch_stats"])
    mfold = build_model(cfg_f, fold_bn=True)
    o_fused = np.asarray(mf.apply(variables, x, train=False))
    o_fold = np.asarray(mfold.apply({"params": folded}, x, train=False))
    np.testing.assert_allclose(o_fused, o_fold, atol=1e-4, rtol=1e-4)


@LEVER
def test_predict_runs_with_fused_tail(lever):
    """The eval surface: make_predict_fn over a fused-tail model (the
    graftlint trace-audit entries) produces the same detections as the
    xla predict on the same variables."""
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    mx, mf, variables, x, _, _ = _init_pair(lever)
    px = make_predict_fn(mx, tiny_cfg(topk=16, **{lever: "xla"}))
    pf = make_predict_fn(mf, tiny_cfg(topk=16, **{lever: "fused"}))
    dx = px(variables, x)
    df = pf(variables, x)
    np.testing.assert_allclose(np.asarray(dx.scores),
                               np.asarray(df.scores), atol=1e-4)
    assert np.mean(np.asarray(dx.valid) == np.asarray(df.valid)) > 0.99


def test_block_fuse_mesh8_matches_single_device():
    """The data-parallel plane: one fused train step on the 8-device mesh
    equals the 1-device step (same global batch) — the jnp twin's
    reductions partition under GSPMD like the unfused BN's."""
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.parallel import (make_mesh,
                                                         shard_batch)
    from real_time_helmet_detection_tpu.train import (create_train_state,
                                                      make_train_step)
    cfg = tiny_cfg(block_fuse="fused", batch_size=8, lr=1e-3,
                   loss_kernel="xla")
    model = build_model(cfg)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    batch_np = synthetic_target_batch(8, IMSIZE, seed=9)
    results = []
    for ndev in (1, 8):
        mesh = make_mesh(ndev)
        step = make_train_step(model, tx, cfg, mesh)
        st = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
        batch = shard_batch(mesh, batch_np, spatial_dims=[1] * 5)
        st, losses = step(st, *batch)
        results.append((jax.device_get(losses),
                        jax.device_get(jax.tree.leaves(st.params)[0])))
    (l1, p1), (l8, p8) = results
    assert l1["total"] == pytest.approx(l8["total"], rel=1e-3)
    np.testing.assert_allclose(p1, p8, rtol=1e-3, atol=1e-5)


def test_scanned_step_donation_ok():
    """The fused scanned step keeps the full aliasing surface — the
    trace-audit donation rule bench.py reports as donation_ok."""
    from real_time_helmet_detection_tpu.analysis.trace_audit import \
        donation_ok
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.train import (
        create_train_state, make_scanned_train_fn, make_train_step_body)
    cfg = tiny_cfg(block_fuse="fused", batch_size=4, loss_kernel="xla")
    model = build_model(cfg)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    body = make_train_step_body(model, tx, cfg)
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        4, IMSIZE, seed=1))
    train_n = make_scanned_train_fn(body, 2)
    assert donation_ok(train_n, (0,), (state, *arrs))
