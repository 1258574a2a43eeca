"""Trace-level jit-hygiene audit (graftlint layer 1) — CPU-only, no chip.

Abstractly traces the public entry points (scanned train step, predict /
eval chain, export path — the same programs bench.py times and the C++
runner executes) via `jax.make_jaxpr` / `jit(...).lower()` and inspects
the jaxpr + StableHLO for the mistake classes that cost real campaigns
(CLAUDE.md; the reference has no compile-model to audit — its eval loops
eagerly per batch item, ref /root/reference/evaluate.py:66-97):

* `trace/dynamic-shape`    — dynamic dims in the lowered StableHLO
                             (violates the fixed-shapes/masks law that
                             keeps eval recompile-free)
* `trace/trace-failure`    — the entry point no longer traces at all
                             (how boolean filtering manifests: jax raises
                             NonConcreteBooleanIndexError at trace time)
* `trace/f64`              — float64/complex128 avals: a silent x64 leak
                             doubles every buffer and falls off the TPU
                             fast path
* `trace/host-callback`    — callback/infeed primitives inside a hot
                             path: each invocation is a host round trip
                             per step
* `trace/donation`         — a donated argument with no matching output
                             aval: XLA cannot alias it, the copy stays,
                             and the chip log grows a "Some donated
                             buffers were not usable" warning mid-run —
                             caught here at trace time instead
* `trace/retrace-unstable` — tracing the same entry twice (and across the
                             tpu_sweep-representative config grid) yields
                             different trace signatures: trace-time
                             nondeterminism (clock/RNG/dict-order in
                             closures) makes EVERY jit call a potential
                             recompile

All audits run on tiny-shape CPU models: `jax.eval_shape` / `.lower()`
never execute device code, so a full audit costs seconds and zero TPU
contact.
"""

from __future__ import annotations

import hashlib
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import Finding

# substrings of the primitive names through which a traced program calls
# back into the host (jax 0.9: debug_callback / pure_callback / io_callback,
# debug_print — its own primitive since jax.debug.print stopped lowering
# through debug_callback — and the feeds)
_CALLBACK_PRIMS = ("callback", "debug_print", "infeed", "outfeed",
                   "host_local_array_to_global_array")
_BAD_DTYPES = ("float64", "complex128")


# ---------------------------------------------------------------------------
# primitives


def trace_signature(fn: Callable, args: Sequence) -> str:
    """sha256 of the canonicalized jaxpr text: stable across retraces of
    a deterministic trace (jaxpr var names are assigned canonically), and
    a different program -> a different hash. Constants participate — a
    trace-time `random()` constant is exactly the hazard to catch."""
    import jax
    # a FRESH wrapper per call: jax caches traces on function identity,
    # so retracing the same object would be vacuously stable — the hazard
    # being checked is a REBUILT entry (new epoch / new process / re-JIT
    # after clear_caches) tracing to a different program
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    # printed object addresses (custom_jvp thunks etc.) are process noise,
    # not program content — mask them or every custom_vjp'd model would
    # read as unstable
    text = re.sub(r" at 0x[0-9a-f]+", " at 0xX", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()


def _walk_jaxprs(jaxpr):
    """The jaxpr plus every sub-jaxpr closed over by its equations
    (scan/while/cond bodies, custom_vjp branches, pjit callees...)."""
    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        seen.append(j)
        for eqn in j.eqns:
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(cand, "jaxpr", cand)
                    if hasattr(inner, "eqns"):
                        stack.append(inner)
    return seen


def jaxpr_findings(fn: Callable, args: Sequence, entry: str) -> List[Finding]:
    """f64 avals + host-callback primitives, recursively through every
    closed-over sub-jaxpr."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    out: List[Finding] = []
    f64_hit = False
    cb_seen = set()
    for j in _walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            prim = eqn.primitive.name
            if any(tok in prim for tok in _CALLBACK_PRIMS) \
                    and prim not in cb_seen:
                cb_seen.add(prim)
                out.append(Finding(
                    rule="trace/host-callback", path="<%s>" % entry,
                    context=entry,
                    message="primitive %r in the traced program: every "
                            "invocation is a host round trip inside the "
                            "hot path" % prim))
            if not f64_hit:
                for v in tuple(eqn.outvars) + tuple(eqn.invars):
                    dt = getattr(getattr(v, "aval", None), "dtype", None)
                    if dt is not None and str(dt) in _BAD_DTYPES:
                        f64_hit = True
                        out.append(Finding(
                            rule="trace/f64", path="<%s>" % entry,
                            context=entry,
                            message="%s aval in the traced program "
                                    "(primitive %r): silent wide-dtype "
                                    "promotion — pin dtypes; x64 must "
                                    "stay off" % (dt, prim)))
                        break
    return out


def stablehlo_findings(fn: Callable, args: Sequence, entry: str,
                       donate_argnums: Tuple[int, ...] = ()) -> List[Finding]:
    """Lower (never compile/execute) and scan the StableHLO text for
    dynamic dims. f64 leaks are caught at the jaxpr level; the text scan
    here is only for shapes, where the jaxpr can't see what lowering
    decided."""
    import jax
    text = jax.jit(fn, donate_argnums=donate_argnums).lower(
        *args).as_text()
    out = []
    if "tensor<?" in text or "x?x" in text:
        out.append(Finding(
            rule="trace/dynamic-shape", path="<%s>" % entry, context=entry,
            message="dynamic dimension in lowered StableHLO: violates the "
                    "fixed-shapes/masks convention (every retrace with a "
                    "new shape is a fresh XLA compile)"))
    return out


def donation_mismatches(fn: Callable, donate_argnums: Sequence[int],
                        args: Sequence) -> List[str]:
    """Donated input leaves with no same-(shape, dtype) output leaf to
    alias. Aval matching is the lintable approximation of XLA's
    usability rule (layout/sharding also participate on-device); an aval
    mismatch here is ALWAYS a real donation failure."""
    import jax

    out_shape = jax.eval_shape(fn, *args)
    out_leaves = jax.tree.leaves(out_shape)
    pool: Dict[Tuple, int] = {}
    for leaf in out_leaves:
        key = (tuple(leaf.shape), str(leaf.dtype))
        pool[key] = pool.get(key, 0) + 1
    missing = []
    for i in donate_argnums:
        for leaf in jax.tree.leaves(jax.eval_shape(lambda x: x, args[i])):
            key = (tuple(leaf.shape), str(leaf.dtype))
            if pool.get(key, 0) > 0:
                pool[key] -= 1
            else:
                missing.append("arg %d leaf %s%s" % (i, key[1],
                                                     list(key[0])))
    return missing


def donation_ok(fn: Callable, donate_argnums: Sequence[int],
                args: Sequence) -> bool:
    """True when every donated buffer has an aliasing target — the
    `donation_ok` field bench.py's ONE JSON line reports."""
    try:
        return not donation_mismatches(fn, donate_argnums, args)
    except Exception:  # noqa: BLE001 — an unanalyzable fn is not "ok"
        return False


def donation_findings(fn: Callable, donate_argnums: Sequence[int],
                      args: Sequence, entry: str) -> List[Finding]:
    missing = donation_mismatches(fn, donate_argnums, args)
    if not missing:
        return []
    return [Finding(
        rule="trace/donation", path="<%s>" % entry, context=entry,
        message="donated buffers with no matching output aval (the copy "
                "cannot be elided; 'Some donated buffers were not "
                "usable' at run time): %s" % "; ".join(missing[:4]))]


def retrace_findings(fn: Callable, args: Sequence, entry: str) -> List[Finding]:
    sig_a = trace_signature(fn, args)
    sig_b = trace_signature(fn, args)
    if sig_a == sig_b:
        return []
    return [Finding(
        rule="trace/retrace-unstable", path="<%s>" % entry, context=entry,
        message="two traces of the same entry with identical avals "
                "produced different jaxprs: trace-time nondeterminism "
                "(clock/RNG/dict order) — every jit call may recompile")]


def audit_entry(fn: Callable, args: Sequence, entry: str,
                donate_argnums: Tuple[int, ...] = (),
                lower: bool = True) -> List[Finding]:
    """All trace rules over one entry point. A trace failure IS a finding
    (boolean filtering / concretization errors surface here), never an
    audit crash."""
    try:
        out = jaxpr_findings(fn, args, entry)
        out += retrace_findings(fn, args, entry)
        if donate_argnums:
            out += donation_findings(fn, donate_argnums, args, entry)
        if lower:
            out += stablehlo_findings(fn, args, entry, donate_argnums)
        return out
    except Exception as e:  # noqa: BLE001 — the failure is the finding
        return [Finding(
            rule="trace/trace-failure", path="<%s>" % entry, context=entry,
            message="entry point failed to trace (%s: %s) — boolean "
                    "filtering / shape dynamism / a broken entry point"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200]))]


# ---------------------------------------------------------------------------
# the repo's entry points, tiny-shape CPU editions

# The remat policies of tpu_sweep's CPU-representative step_grid (its
# `grid` when not on_tpu, scripts/tpu_sweep.py `step_grid` section). The
# loss kernel is pinned to "xla" here: the fused Pallas kernel off-TPU
# runs in interpret mode, whose trace drags in interpreter internals that
# are not what ships to the chip.
STEP_GRID_REMAT = ("none", "stacks", "full")
_TINY = dict(num_stack=1, hourglass_inch=16, num_cls=2, imsize=64)
_BATCH = 2

# The tier variants audited end to end (ISSUE 13): the SMALLEST tier
# architecture (edge: depthwise blocks, 1 stack, narrow) and the LARGEST
# (quality: residual blocks, 2 stacks) — tiny-width twins of
# config.TIER_PRESETS' shapes. Each gets a train-step + predict entry so
# the whole tier family obeys the dynamic-shape/f64/donation/retrace
# rules, not just the flagship graph.
TIER_AUDIT = (
    ("edge", dict(variant="ghost", num_stack=1, hourglass_inch=8,
                  stem_width=8)),
    # depthwise ships as a first-class variant even though no current
    # preset selects it (the chip arch_grid may) — its trace surface is
    # audited like the presets' (no lowering: jaxpr rules only)
    ("depthwise-variant", dict(variant="depthwise", num_stack=1,
                               hourglass_inch=8, stem_width=8)),
    ("quality", dict(variant="residual", num_stack=2,
                     hourglass_inch=16, stem_width=16)),
)


def _tiny_train_parts(remat: str = "none", param_policy: str = "fp32",
                      arch: Optional[dict] = None,
                      block_fuse: str = "auto", fwd_dtype: str = "bf16"):
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..data import synthetic_target_batch
    from ..models import build_model
    from ..optim import build_optimizer
    from ..train import (create_train_state, make_scanned_train_fn,
                         make_train_step_body)

    # bf16-compute requires the bf16 compute policy (config.py validates)
    tiny = dict(_TINY, **(arch or {}))
    cfg = Config(batch_size=_BATCH, remat=remat, loss_kernel="xla",
                 amp=param_policy == "bf16-compute",
                 param_policy=param_policy, block_fuse=block_fuse,
                 fwd_dtype=fwd_dtype, **tiny)
    model = build_model(cfg, dtype=jnp.bfloat16 if cfg.amp else None)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0),
                               _TINY["imsize"], tx)
    body = make_train_step_body(model, tx, cfg)
    train_n = make_scanned_train_fn(body, 2)
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        _BATCH, _TINY["imsize"], pos_rate=0.05))
    return train_n, (state,) + arrs


def _tiny_predict_parts(normalize: Optional[str] = None,
                        epilogue: str = "auto",
                        arch: Optional[dict] = None,
                        cascade_summary: bool = False,
                        block_fuse: str = "auto"):
    import jax
    import numpy as np

    from ..config import Config
    from ..models import build_model
    from ..predict import make_predict_fn
    from ..train import init_variables

    cfg = Config(topk=16, conf_th=0.0, nms_th=0.5, epilogue=epilogue,
                 block_fuse=block_fuse,
                 **dict(_TINY, **(arch or {})))
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         _TINY["imsize"])
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize=normalize,
                              cascade_summary=cascade_summary)
    if normalize:
        images = np.zeros((_BATCH, _TINY["imsize"], _TINY["imsize"], 3),
                          np.uint8)
    else:
        images = np.zeros((_BATCH, _TINY["imsize"], _TINY["imsize"], 3),
                          np.float32)
    return predict, variables, images


def _tiny_predict_int8_parts():
    """The quantized predict entry (ISSUE 5): BN-folded int8 twin over
    the SAME tiny checkpoint pytree, scales from a 2-batch synthetic
    calibration pass — the exact program `--infer-dtype int8`
    eval/export/bench run, at audit shapes."""
    import jax
    import numpy as np

    from ..config import Config
    from ..models import build_model
    from ..ops.quant import calibrate_scales, synthetic_calibration_batches
    from ..predict import make_predict_fn
    from ..train import init_variables

    cfg = Config(topk=16, conf_th=0.0, nms_th=0.5, infer_dtype="int8",
                 **_TINY)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         _TINY["imsize"])
    variables = {"params": params, "batch_stats": batch_stats}
    scales = calibrate_scales(
        cfg, variables,
        synthetic_calibration_batches(_BATCH, _TINY["imsize"], n=2))
    predict = make_predict_fn(model, cfg, quant_scales=scales)
    images = np.zeros((_BATCH, _TINY["imsize"], _TINY["imsize"], 3),
                      np.float32)
    return predict, variables, images


# The serve bucket set audited per bucket (ISSUE 8): tiny-shape stand-ins
# for serving.resolve_buckets' default — every bucket the engine
# AOT-compiles is its own entry point (the whole set must obey the
# dynamic-shape/f64/donation rules, not just the eval batch shape).
SERVE_BUCKETS_AUDIT = (1, 2, 4)


def _tiny_serve_parts(bucket: int):
    """One serve bucket's program at audit shapes: the raw-uint8 wire
    predict (the engine's ingress contract) at batch size `bucket` —
    exactly what `ServingEngine.__init__` lowers per bucket."""
    import numpy as np

    predict, variables, _ = _tiny_predict_parts(normalize="imagenet")
    images = np.zeros((bucket, _TINY["imsize"], _TINY["imsize"], 3),
                      np.uint8)
    return predict, variables, images


def _predict_chain(predict, n: int = 2):
    """bench.py's donating predict-chain contract (make_predict_chain):
    images donated, final carry returned as the aliasing target."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def prog(variables, images):
        def body(imgs, _):
            det = predict(variables, imgs)
            eps = (jnp.tanh(jnp.sum(det.scores)) * 1e-12).astype(imgs.dtype)
            return imgs + eps, ()
        final, _ = lax.scan(body, images, None, length=n)
        return final, jnp.sum(final[0, 0, 0])
    return prog


def audit_repo_entry_points(lower: bool = True) -> List[Finding]:
    """Trace-audit every public entry point at tiny CPU shapes.

    Entries mirror the production surfaces: the scanned train step
    (bench.py/scaling.py's timed program) across the tpu_sweep
    step-grid remat policies AND under --param-policy bf16-compute (the
    fp32-master state restructure, ISSUE 7), under --block-fuse fused
    and --fwd-dtype int8 (the residual-tail custom_vjp pass and the STE
    int8 forward, ISSUE 20), the jitted predict fn
    (eval), its --epilogue fused twin (the plain eval tail XLA fuses
    into the conv), its --block-fuse fused twin, the donating predict chain
    (bench), the quantized int8
    predict + its donating chain (--infer-dtype int8, ops/quant.py — the
    program tpu_sweep's int8 section times), the raw-uint8-wire predict
    (eval driver / export --export-raw-input), and the export fn (the
    C++ runner's artifact)."""
    findings: List[Finding] = []
    grid_sigs: Dict[str, str] = {}

    for remat in STEP_GRID_REMAT:
        entry = "train_step_scanned[remat=%s]" % remat
        try:
            train_n, targs = _tiny_train_parts(remat)
        except Exception as e:  # noqa: BLE001
            findings.append(Finding(
                rule="trace/trace-failure", path="<%s>" % entry,
                context=entry,
                message="entry construction failed: %s: %s"
                        % (type(e).__name__,
                           (str(e).splitlines() or ["?"])[0][:200])))
            continue
        # lower only the default policy: remat variants share the same
        # shape surface and the StableHLO scan is the slow part
        findings += audit_entry(train_n, targs, entry,
                                donate_argnums=(0,),
                                lower=lower and remat == "none")
        try:
            grid_sigs[entry] = trace_signature(train_n, targs)
        except Exception:  # noqa: BLE001 — already reported above
            pass

    # distinct static configs must trace to distinct programs; a collision
    # means a policy knob silently did nothing (the inverse hazard of
    # retrace instability, same census)
    by_sig: Dict[str, List[str]] = {}
    for entry, sig in grid_sigs.items():
        by_sig.setdefault(sig, []).append(entry)
    for sig, entries in by_sig.items():
        if len(entries) > 1 and "remat=none" not in " ".join(entries):
            findings.append(Finding(
                rule="trace/retrace-unstable", path="<step_grid>",
                context="step_grid",
                message="distinct remat policies traced to the SAME "
                        "program (%s): the policy knob is dead"
                        % ", ".join(sorted(entries))))

    try:
        # the bf16-param-policy scanned step (--param-policy bf16-compute,
        # ISSUE 7): the fp32-master optimizer restructures both the state
        # pytree and the update tail, so its donation/f64/dynamic-shape
        # surface is audited separately from the fp32 grid above
        entry = "train_step_scanned[param=bf16-compute]"
        train_n, targs = _tiny_train_parts("none", "bf16-compute")
        findings += audit_entry(train_n, targs, entry,
                                donate_argnums=(0,), lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure",
            path="<train_step_scanned[param=bf16-compute]>",
            context="train_step_scanned[param=bf16-compute]",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    for tier, arch in TIER_AUDIT:
        # the tier family (ISSUE 13): smallest + largest tier variants,
        # train step AND predict — a depthwise/ghost block that traced
        # dynamically, leaked f64 or broke the scan's donation contract
        # would ship in every tier checkpoint
        entry = "train_step_scanned[tier=%s]" % tier
        try:
            train_n, targs = _tiny_train_parts("none", arch=arch)
            findings += audit_entry(train_n, targs, entry,
                                    donate_argnums=(0,),
                                    lower=lower and tier == "edge")
        except Exception as e:  # noqa: BLE001
            findings.append(Finding(
                rule="trace/trace-failure", path="<%s>" % entry,
                context=entry,
                message="entry construction failed: %s: %s"
                        % (type(e).__name__,
                           (str(e).splitlines() or ["?"])[0][:200])))
        entry = "predict[tier=%s]" % tier
        try:
            predict_t, variables_t, images_t = _tiny_predict_parts(
                arch=arch)
            findings += audit_entry(
                lambda v, im, _p=predict_t: _p(v, im),
                (variables_t, images_t), entry,
                lower=lower and tier == "edge")
        except Exception as e:  # noqa: BLE001
            findings.append(Finding(
                rule="trace/trace-failure", path="<%s>" % entry,
                context=entry,
                message="entry construction failed: %s: %s"
                        % (type(e).__name__,
                           (str(e).splitlines() or ["?"])[0][:200])))

    try:
        predict, variables, images = _tiny_predict_parts()
        findings += audit_entry(
            lambda v, im: predict(v, im), (variables, images), "predict",
            lower=lower)
        chain = _predict_chain(predict)
        findings += audit_entry(chain, (variables, images),
                                "predict_chain", donate_argnums=(1,),
                                lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<predict>", context="predict",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the fused-epilogue predict (--epilogue fused, ISSUE 7): the
        # fold-algebra eval tail (a plain expression, no kernel) replaces
        # every BN+activation tail — its trace must stay as clean as the
        # plain predict
        predict_e, variables_e, images_e = _tiny_predict_parts(
            epilogue="fused")
        findings += audit_entry(
            lambda v, im: predict_e(v, im), (variables_e, images_e),
            "predict_epilogue_fused", lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<predict_epilogue_fused>",
            context="predict_epilogue_fused",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the block-fused scanned step (--block-fuse fused, ISSUE 20):
        # the residual tail's one-pass BN+add+act custom_vjp replaces the
        # unfused chain in every eligible block — its scan must keep the
        # exact donation/f64/dynamic-shape surface of the plain step
        # (off-TPU this audits the jnp recompute twin, the same program
        # roofline counts)
        entry = "train_step_scanned[block-fuse]"
        train_n, targs = _tiny_train_parts(block_fuse="fused")
        findings += audit_entry(train_n, targs, entry,
                                donate_argnums=(0,), lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure",
            path="<train_step_scanned[block-fuse]>",
            context="train_step_scanned[block-fuse]",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the int8-forward scanned step (--fwd-dtype int8, ISSUE 20): the
        # STE conv quantizes per step IN-JIT (absmax ride-along, no
        # persisted scale state) — a host-side scale refresh or a fresh
        # un-donated buffer here would leak a D2H per step into the train
        # loop, exactly what this audit exists to catch
        entry = "train_step_scanned[fwd=int8]"
        train_n, targs = _tiny_train_parts(fwd_dtype="int8")
        findings += audit_entry(train_n, targs, entry,
                                donate_argnums=(0,), lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure",
            path="<train_step_scanned[fwd=int8]>",
            context="train_step_scanned[fwd=int8]",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the block-fused predict (ISSUE 20): the eval-mode tail folds
        # running stats into eff-scale/bias before the add+act — same
        # cleanliness bar as predict_epilogue_fused
        predict_b, variables_b, images_b = _tiny_predict_parts(
            block_fuse="fused")
        findings += audit_entry(
            lambda v, im: predict_b(v, im), (variables_b, images_b),
            "predict_block_fused", lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<predict_block_fused>",
            context="predict_block_fused",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the cascade-summary predict (ISSUE 16): the edge tier's serving
        # program with the in-jit confidence summary riding the detection
        # block (ops/decode.confidence_summary over the fixed-shape
        # masked Detections — the FleetRouter's escalation signal). Its
        # trace must stay exactly as clean as the plain edge predict:
        # dynamic shapes, f64 leaks or retrace instability here would
        # recompile on the cascade hot path
        casc_arch = dict(TIER_AUDIT[0][1])
        predict_c, variables_c, images_c = _tiny_predict_parts(
            arch=casc_arch, cascade_summary=True)
        findings += audit_entry(
            lambda v, im: predict_c(v, im), (variables_c, images_c),
            "predict_cascade_summary[tier=edge]", lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure",
            path="<predict_cascade_summary[tier=edge]>",
            context="predict_cascade_summary[tier=edge]",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the streaming programs (ISSUE 17): the in-jit per-tile delta
        # summary (ops/delta.tile_delta_summary — one cast + one
        # reduce_window over a uint8 frame pair, the (T,) f32 leaf
        # serving/streams.py gates tiles on) dispatches once per frame
        # on EVERY stream, so dynamic shapes, f64 leaks or retrace
        # instability here would recompile on the streaming hot path;
        # the tile predict the gated submits ride is the raw-uint8
        # serve-bucket wire, pinned under its stream name so the
        # surface stays audited even if the serve set changes
        import numpy as np

        from ..ops.delta import tile_delta_summary
        g = 2
        frame = np.zeros((g * _TINY["imsize"], g * _TINY["imsize"], 3),
                         np.uint8)
        findings += audit_entry(
            lambda p, c: tile_delta_summary(p, c, grid=g),
            (frame, frame), "stream_delta_summary[grid=%d]" % g,
            lower=lower)
        predict_st, variables_st, images_st = _tiny_serve_parts(2)
        findings += audit_entry(
            lambda v, im, _p=predict_st: _p(v, im),
            (variables_st, images_st), "stream_tile_predict[b=2]",
            lower=False)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure",
            path="<stream_delta_summary>",
            context="stream_delta_summary",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the quantized predict (--infer-dtype int8, ops/quant.py): the
        # BN fold + weight quantization run inside the program, so the
        # int8 entry has its own trace surface to keep honest — plus the
        # donating bench chain over it (the exact program tpu_sweep's
        # int8 section times)
        predict_q, variables_q, images_q = _tiny_predict_int8_parts()
        findings += audit_entry(
            lambda v, im: predict_q(v, im), (variables_q, images_q),
            "predict_int8", lower=lower)
        chain_q = _predict_chain(predict_q)
        findings += audit_entry(chain_q, (variables_q, images_q),
                                "predict_int8_chain", donate_argnums=(1,),
                                lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<predict_int8>",
            context="predict_int8",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        # the serving engine's bucket set (ISSUE 8): every bucket is a
        # separately-compiled production program — audit each one (the
        # raw-uint8 serve wire), not just the eval batch shape
        for b in SERVE_BUCKETS_AUDIT:
            entry = "serve_predict[b=%d]" % b
            predict_s, variables_s, images_s = _tiny_serve_parts(b)
            findings += audit_entry(
                lambda v, im, _p=predict_s: _p(v, im),
                (variables_s, images_s), entry,
                lower=lower and b == SERVE_BUCKETS_AUDIT[0])
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<serve_predict>",
            context="serve_predict",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    try:
        predict_raw, variables_r, images_u8 = _tiny_predict_parts(
            normalize="imagenet")
        findings += audit_entry(
            lambda v, im: predict_raw(v, im), (variables_r, images_u8),
            "predict_raw_wire", lower=lower)

        from ..config import Config
        from ..export import build_export_fn
        from ..models import build_model
        ecfg = Config(topk=16, **_TINY)
        emodel = build_model(ecfg)
        efn = build_export_fn(emodel, variables_r, ecfg,
                              normalize="imagenet")
        findings += audit_entry(efn, (images_u8,), "export_predict",
                                lower=lower)
    except Exception as e:  # noqa: BLE001
        findings.append(Finding(
            rule="trace/trace-failure", path="<export_predict>",
            context="export_predict",
            message="entry construction failed: %s: %s"
                    % (type(e).__name__,
                       (str(e).splitlines() or ["?"])[0][:200])))

    return findings
