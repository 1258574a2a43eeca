"""--block-fuse tests (ISSUE 20 tentpole prong 1).

Three layers of parity, mirroring the fused-epilogue suite
(tests/test_epilogue.py):

* kernel level — `fused_bn_add_act_train` (jnp twin AND Pallas
  interpret) against the plain XLA chain BN(x) -> +skip -> act, forward
  AND grads (w.r.t. x, scale, bias AND the skip's pass-through), fp32
  and bf16; the eval tail (`FusedBNAddAct` at `train=False`: a plain
  expression XLA fuses into the conv, PR 26) against nn.BatchNorm ->
  +skip -> Activation on the same variables;
* model level — `--block-fuse fused` vs `xla` on the full hourglass
  for BOTH eligible variants (residual, depthwise): identical
  param/stat trees (checkpoints interchange), allclose logits/grads;
  the ghost variant and non-fusable activations are INELIGIBLE and must
  keep the xla tail bit-exactly;
* downstream regression — `ops.quant.fold_batchnorm` still folds the
  (tree-identical) FusedBNAddAct tail, and the 8-device-mesh train step
  matches single-device, so the PR 5 quantization path and the
  data-parallel plane are untouched by the fusion.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.models.hourglass import (
    FusedBNAddAct, resolve_block_fuse)
from real_time_helmet_detection_tpu.ops.pallas.epilogue import (
    FUSED_EPILOGUE_ACTIVATIONS, _act_fwd, fused_bn_act)
from real_time_helmet_detection_tpu.ops.pallas.residual import (
    fused_bn_add_act_train)
from test_epilogue import assert_tail_parity, bn_variables, xla_eval_tail

IMSIZE = 64
EPS = 1e-5


def tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=2)
    base.update(kw)
    return Config(**base)


def _ref_train_chain(x, gamma, beta, skip, act):
    """The unfused composition: BatchNorm with batch moments of x ALONE
    (biased variance, flax's normalizer), then +skip, then act — what
    nn.BatchNorm -> add -> Activation computes in train mode."""
    xf = x.astype(jnp.float32)
    c = x.shape[-1]
    xr = xf.reshape(-1, c)
    mean = jnp.mean(xr, axis=0)
    var = jnp.maximum(jnp.mean(jnp.square(xr), axis=0)
                      - jnp.square(mean), 0.0)
    a = gamma * jax.lax.rsqrt(var + EPS)
    b = beta - mean * a
    z = xf * a + b + skip.astype(jnp.float32)
    return _act_fwd(z, act).astype(x.dtype), mean, var


def _rand_args(dt, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 16)) * 2, dt)
    skip = jnp.asarray(rng.standard_normal((2, 8, 8, 16)), dt)
    gamma = jnp.asarray(
        (rng.standard_normal(16) * 0.5 + 1).astype(np.float32))
    beta = jnp.asarray(rng.standard_normal(16).astype(np.float32))
    return x, gamma, beta, skip


@pytest.mark.parametrize("act", FUSED_EPILOGUE_ACTIVATIONS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_train_kernel_fwd_grad_parity(act, dt):
    """fused_bn_add_act_train (jnp twin AND Pallas interpret) vs the XLA
    chain: forward, batch moments, AND grads w.r.t. (x, gamma, beta,
    skip) — the analytic backward (S1/S2 formulas + pass-through dskip)
    must match full autodiff through the moments."""
    x, gamma, beta, skip = _rand_args(dt)

    def loss_of(fn):
        return lambda x, g, b, s: jnp.sum(
            fn(x, g, b, s)[0].astype(jnp.float32) ** 2)

    ref = lambda x, g, b, s: _ref_train_chain(x, g, b, s, act)  # noqa: E731
    fused = lambda x, g, b, s: fused_bn_add_act_train(  # noqa: E731
        x, g, b, s, activation=act)
    pallas = lambda x, g, b, s: fused_bn_add_act_train(  # noqa: E731
        x, g, b, s, activation=act, interpret=True)

    ftol = 1e-5 if dt == jnp.float32 else 3e-2
    o_ref, m_ref, v_ref = ref(x, gamma, beta, skip)
    o_f, m_f, v_f = fused(x, gamma, beta, skip)
    o_p, m_p, v_p = pallas(x, gamma, beta, skip)
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

    g_ref = jax.grad(loss_of(ref), argnums=(0, 1, 2, 3))(
        x, gamma, beta, skip)
    g_f = jax.grad(loss_of(fused), argnums=(0, 1, 2, 3))(
        x, gamma, beta, skip)
    g_p = jax.grad(loss_of(pallas), argnums=(0, 1, 2, 3))(
        x, gamma, beta, skip)
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


@pytest.mark.parametrize("act", FUSED_EPILOGUE_ACTIVATIONS)
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_eval_kernel_fwd_grad_parity(act, dt):
    """The eval block tail (no kernel since PR 26: `FusedBNAddAct` at
    train=False is the plain `fused_bn_act` expression with a skip) vs
    nn.BatchNorm -> +skip -> Activation on the same variables: forward +
    grads w.r.t. (x, bias, scale, skip)."""
    x, _, _, skip = _rand_args(dt, seed=1)
    variables = bn_variables(np.random.default_rng(1))
    stats = {"batch_stats": variables["batch_stats"]}
    module = FusedBNAddAct(activation=act, dtype=dt)
    assert_tail_parity(
        lambda x, p, s: xla_eval_tail({"params": p, **stats}, x, act, dt,
                                      skip=s),
        lambda x, p, s: module.apply({"params": p, **stats}, x, s,
                                     train=False),
        (x, variables["params"], skip), ("x", "bias", "scale", "skip"), dt)


def test_kernel_rejects_unsupported_activation_and_shapes():
    x = jnp.zeros((1, 4, 4, 8))
    with pytest.raises(NotImplementedError):
        fused_bn_act(x, jnp.ones(8), jnp.zeros(8), x, activation="CELU")
    with pytest.raises(ValueError, match="skip"):
        fused_bn_add_act_train(x, jnp.ones(8), jnp.zeros(8),
                               jnp.zeros((1, 4, 4, 4)))


def test_resolve_block_fuse_auto_is_xla_off_tpu():
    assert resolve_block_fuse(tiny_cfg(block_fuse="auto")) == "xla"
    assert resolve_block_fuse(tiny_cfg(block_fuse="fused")) == "fused"
    assert resolve_block_fuse(tiny_cfg(block_fuse="xla")) == "xla"


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


def _init_pair(variant="residual", act="Mish", dtype=None, batch=2):
    cfg_x = tiny_cfg(block_fuse="xla", variant=variant, activation=act)
    cfg_f = tiny_cfg(block_fuse="fused", variant=variant, activation=act)
    mx, mf = build_model(cfg_x, dtype=dtype), build_model(cfg_f, dtype=dtype)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, IMSIZE, IMSIZE, 3)).astype(np.float32))
    variables = jax.jit(mx.init, static_argnames=("train",))(
        jax.random.key(0), x, train=False)
    return mx, mf, variables, x, cfg_x, cfg_f


@pytest.mark.parametrize("variant", ["residual", "depthwise"])
def test_model_tree_identical_and_checkpoints_interchange(variant):
    """Checkpoints must interchange across --block-fuse modes: the fused
    branch's explicit child names reproduce the unfused auto-names, so
    the trees are identical INCLUDING leaf values (flax derives param
    RNGs from the module path), and the SAME variables produce allclose
    logits under either tail."""
    mx, mf, variables, x, _, _ = _init_pair(variant, batch=TRAIN_BATCH)
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
    # train mode: per-layer moment reassociation, amplified by downstream
    # renormalization — ~1e-4 at a well-conditioned batch (TRAIN_BATCH)
    np.testing.assert_allclose(np.asarray(oxt), np.asarray(oft),
                               atol=2e-3, rtol=2e-3)
    for a, b in zip(jax.tree.leaves(mutx["batch_stats"]),
                    jax.tree.leaves(mutf["batch_stats"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-2, atol=2e-5)


@pytest.mark.parametrize("variant", ["residual", "depthwise"])
def test_model_train_grads_agree(variant):
    """Sum-of-squares grads through the full train-mode stack, fused vs
    xla tails at fp32. The analytic backward reassociates the per-channel
    sums, and BN renormalization amplifies that through the stack — the
    honest bound is relative to the tree-wide scale, with the strict
    per-element parity pinned at kernel level above. Run at TRAIN_BATCH:
    see its note for what batch 2 does to this comparison."""
    mx, mf, variables, x, _, _ = _init_pair(variant, batch=TRAIN_BATCH)

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


def test_ghost_variant_is_ineligible_and_bitwise_unchanged():
    """The ghost block's tail is a concat of two separately-normalized
    halves — no single BN feeds the add, so block_fuse=fused must
    silently keep the exact xla program (bit-identical outputs)."""
    mx, mf, variables, x, _, _ = _init_pair("ghost")
    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    assert np.array_equal(ox, of)
    oxt, _ = mx.apply(variables, x, train=True, mutable=["batch_stats"])
    oft, _ = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    assert np.array_equal(np.asarray(oxt), np.asarray(oft))


def test_ineligible_activation_keeps_xla_path_bitwise():
    """CELU has no fused recompute form: block_fuse=fused must keep the
    verbatim pre-PR tail — bit-identical output."""
    mx, mf, variables, x, _, _ = _init_pair("residual", act="CELU")
    ox = np.asarray(mx.apply(variables, x, train=False))
    of = np.asarray(mf.apply(variables, x, train=False))
    assert np.array_equal(ox, of)


def test_fold_batchnorm_survives_block_fuse():
    """int8-path regression (PR 5): fold_batchnorm over a block-fused
    model's variables produces the fold_bn twin whose logits match the
    fused model's eval forward — FusedBNAddAct keeps the exact
    Conv_0/BatchNorm_0 sibling pattern the fold walks."""
    from real_time_helmet_detection_tpu.ops.quant import fold_batchnorm
    _, mf, variables, x, _, cfg_f = _init_pair("residual")
    _, mut = mf.apply(variables, x, train=True, mutable=["batch_stats"])
    variables = {"params": variables["params"],
                 "batch_stats": mut["batch_stats"]}
    folded = fold_batchnorm(variables["params"], variables["batch_stats"])
    mfold = build_model(cfg_f, fold_bn=True)
    o_fused = np.asarray(mf.apply(variables, x, train=False))
    o_fold = np.asarray(mfold.apply({"params": folded}, x, train=False))
    np.testing.assert_allclose(o_fused, o_fold, atol=1e-4, rtol=1e-4)


def test_predict_runs_with_block_fuse():
    """The eval surface: make_predict_fn over a block-fused model (the
    graftlint trace-audit entry predict_block_fused) produces the same
    detections as the xla predict on the same variables."""
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    mx, mf, variables, x, _, _ = _init_pair("residual")
    px = make_predict_fn(mx, tiny_cfg(topk=16, block_fuse="xla"))
    pf = make_predict_fn(mf, tiny_cfg(topk=16, block_fuse="fused"))
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
