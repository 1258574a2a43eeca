"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main path once, through the entry points a user calls
(main.py: `get_config` -> `train(cfg)`, then `get_config` -> `evaluate(cfg)`
/ `demo(cfg)` over `ServingEngine`), at the full width of the flagship
configuration (`--num-stack 1 --hourglass-inch 128 --imsize 512
--batch-size 16 --amp`, residual variant, every step lever at `auto`) with
depth cut to 8 train steps and seeded synthetic data, and checks what comes
out by the repo's own means: finite losses, a complete checkpoint, finite
detections for every request the engine admitted, and — outside any timed
window — every Pallas kernel family against its XLA composition at the
shapes that step uses.

    python chip_smoke.py

Exits non-zero, printing no result line, when JAX finds no TPU (and, run
alone without the rest of the repo, at its first import). One process,
no child that needs the device. The first failed stage ends the run. On
success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Wall times and compile counts printed per stage are smoke output — how
long bring-up took on this machine — not a benchmark. (The reference has
no smoke test; its entry point, ref main.py:9-17, is what this drives.)
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Size:
    """What one smoke run drives. The default is the flagship; the CPU test
    (tests/test_chip_smoke.py) passes a toy with the kernels forced on."""
    imsize: int = 512
    width: int = 128
    batch: int = 16
    steps_per_epoch: int = 4
    epochs: int = 2
    num_test: int = 8
    amp: bool = True
    extra_flags: tuple = ()      # appended to every get_config argv
    interpret: bool = False      # parity stage: Pallas interpret mode (CPU)

    @property
    def arch_flags(self) -> list:
        flags = ["--num-stack", "1", "--hourglass-inch", str(self.width),
                 "--imsize", str(self.imsize)]
        return flags + (["--amp"] if self.amp else []) \
            + list(self.extra_flags)


def say(msg: str) -> None:
    print("[chip_smoke] %s" % msg, flush=True)


# ---------------------------------------------------------------------------
# stages


def stage_device() -> dict:
    """Refuse to run off the chip; say what the chip and the install are."""
    from importlib.metadata import version

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit("chip_smoke: no TPU (jax.devices()[0].platform = "
                         "%r); this script only runs on the chip"
                         % dev["platform"])
    say("platform=%(platform)s device_kind=%(kind)s device_count=%(count)d"
        % dev)
    say("jax=%s jaxlib=%s libtpu=%s"
        % tuple(version(p) for p in ("jax", "jaxlib", "libtpu")))
    return dev


def stage_data(root: str, size: Size) -> None:
    """Seeded VOC-layout dataset: no network, no committed binaries."""
    from real_time_helmet_detection_tpu.data import make_synthetic_voc
    make_synthetic_voc(root, num_train=size.batch * size.steps_per_epoch,
                       num_test=size.num_test,
                       imsize=(size.imsize, size.imsize), seed=0)


def stage_train(root: str, save: str, size: Size) -> str:
    """`get_config(argv)` -> `train(cfg)`; returns the checkpoint path."""
    from real_time_helmet_detection_tpu.config import get_config
    from real_time_helmet_detection_tpu.ops.pallas.select import kernel_plan
    from real_time_helmet_detection_tpu.train import (find_latest_checkpoint,
                                                      train)
    cfg = get_config(["--train-flag", "--data", root,
                      "--batch-size", str(size.batch),
                      "--end-epoch", str(size.epochs),
                      "--print-interval", "1", "--no-summary",
                      "--save-path", save] + size.arch_flags)
    say("paths: loss_kernel=%(loss)s epilogue=%(epilogue)s "
        "block_fuse=%(block_fuse)s peak=%(peak)s" % kernel_plan(cfg))
    train(cfg)
    ckpt = find_latest_checkpoint(save)
    if ckpt is None or not ckpt.endswith("check_point_%d" % size.epochs):
        raise AssertionError("no complete check_point_%d under %s (found "
                             "%r)" % (size.epochs, save, ckpt))
    with open(os.path.join(ckpt, "loss_log.json")) as f:
        totals = json.load(f)["total"]
    want = size.steps_per_epoch * size.epochs
    if len(totals) != want or not all(math.isfinite(t) for t in totals):
        raise AssertionError("expected %d finite logged losses, got %r"
                             % (want, totals))
    say("train: %d steps, losses %s, checkpoint %s"
        % (len(totals), " ".join("%.4g" % t for t in totals),
           os.path.basename(ckpt)))
    return ckpt


class _EngineLedger:
    """Deltas of the ServingEngine's own counters (the process-wide
    metrics registry) over one stage: did it complete what it admitted?"""
    NAMES = ("submitted", "completed", "failed_batches", "retried",
             "shed_queue_full", "shed_deadline")

    def __init__(self):
        from real_time_helmet_detection_tpu.obs.metrics import \
            default_registry
        self._c = {n: default_registry().counter("serve." + n)
                   for n in self.NAMES}
        self._t0 = {n: c.value for n, c in self._c.items()}

    def check(self, expected: int, what: str) -> None:
        d = {n: c.value - self._t0[n] for n, c in self._c.items()}
        if d["submitted"] != expected or d["completed"] != expected \
                or any(d[n] for n in self.NAMES[2:]):
            raise AssertionError("%s: engine admitted/completed/failed "
                                 "counters %r, expected %d clean requests"
                                 % (what, d, expected))
        say("%s: engine completed %d of %d admitted requests, 0 retried, "
            "0 shed" % (what, d["completed"], d["submitted"]))


def _assert_finite(what: str, **arrays) -> None:
    import numpy as np
    for name, a in arrays.items():
        if not np.all(np.isfinite(np.asarray(a, np.float64))):
            raise AssertionError("%s: non-finite %s" % (what, name))


def stage_serve(root: str, save: str, out: str, size: Size) -> None:
    """`get_config(argv)` -> `evaluate(cfg)`: the test split through
    ServingEngine, from the checkpoint the train stage wrote."""
    import pickle

    from real_time_helmet_detection_tpu.config import get_config
    from real_time_helmet_detection_tpu.evaluate import evaluate
    bucket = str(size.num_test)
    cfg = get_config(["--data", root, "--model-load", save,
                      "--batch-size", bucket, "--serve-buckets", bucket,
                      "--save-path", out] + size.arch_flags)
    ledger = _EngineLedger()
    metrics = evaluate(cfg)
    ledger.check(size.num_test, "evaluate")
    with open(os.path.join(out, "prediction_results.pickle"), "rb") as f:
        results = pickle.load(f)  # written by evaluate() just above
    if len(results) != size.num_test:
        raise AssertionError("evaluate answered %d of %d images"
                             % (len(results), size.num_test))
    for image_id, r in results.items():
        _assert_finite("evaluate %s" % image_id, box=r["box"],
                       score=r["score"])
    _assert_finite("evaluate", map=metrics["map"])
    say("evaluate: %d images answered, %d boxes, mAP %.4f (8 steps from "
        "random weights: a number, not a quality claim)"
        % (len(results), sum(len(r["box"]) for r in results.values()),
           metrics["map"]))


def stage_demo(root: str, save: str, out: str, size: Size) -> None:
    """`get_config(argv)` -> `demo(cfg)`: one JPEG through bucket 1."""
    from real_time_helmet_detection_tpu.config import get_config
    from real_time_helmet_detection_tpu.evaluate import demo
    jpegs = os.path.join(root, "JPEGImages")
    image = os.path.join(jpegs, sorted(os.listdir(jpegs))[-1])
    cfg = get_config(["--data", image, "--model-load", save,
                      "--save-path", out] + size.arch_flags)
    ledger = _EngineLedger()
    det = demo(cfg)
    ledger.check(1, "demo")
    _assert_finite("demo", boxes=det["boxes"], scores=det["scores"])
    if not os.path.exists(os.path.join(out, "image.png")):
        raise AssertionError("demo wrote no overlay")
    say("demo: %s -> %d boxes" % (os.path.basename(image),
                                  len(det["boxes"])))


# ---- kernel parity (outside any timed window) ------------------------------

# Stated tolerances on the relative L2 error ||kernel - xla|| / ||xla|| of
# every compared tensor. L2, not max: a ReLU gradient is discontinuous, so
# among 10^7 elements a last-bit difference in z flips a handful of masks
# and moves those elements by a whole |g| — a max-norm would report that
# as an error of order one. The XLA side always computes in f32 (bf16
# inputs upcast, result cast back) because that is the math the kernels
# keep in registers; for bf16 tensors the bound is then the one final
# rounding, 2^-9 / sqrt(3) ~ 1e-3 per element.
TOL = {"float32": 2e-4, "bfloat16": 5e-3}


def _rel_err(got, ref):
    """||got - ref|| / ||ref||, reduced on the device: only the scalar
    crosses to the host (the compared tensors run to 10^8 elements)."""
    import jax.numpy as jnp
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    return jnp.linalg.norm((got - ref).ravel()) \
        / (jnp.linalg.norm(ref.ravel()) + 1e-30)


def _check(name: str, got, ref, tol: float) -> None:
    """`got`/`ref`: dicts of same-named device arrays."""
    import jax
    errs = {k: float(e) for k, e in jax.jit(
        lambda g, r: {k: _rel_err(g[k], r[k]) for k in r})(got, ref).items()}
    say("parity %-46s %s (tol %.0e)"
        % (name, " ".join("%s=%.2e" % kv for kv in errs.items()), tol))
    bad = {k: e for k, e in errs.items() if not e <= tol}
    if bad:
        raise AssertionError("parity %s outside tolerance %g: %r"
                             % (name, tol, bad))


def _xla_bn_act(x, gamma, beta, act, skip=None):
    """The model's own XLA composition (models/hourglass.py `Convolution`
    / `Residual` tails) — flax BatchNorm on batch statistics, optional
    skip-add, Activation — computed in f32 and cast back to x's dtype."""
    import flax.linen as nn
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.models.hourglass import Activation
    bn = nn.BatchNorm(use_running_average=False, momentum=0.9,
                      epsilon=1e-5, dtype=jnp.float32)
    variables = {"params": {"scale": gamma, "bias": beta},
                 "batch_stats": {"mean": jnp.zeros_like(gamma),
                                 "var": jnp.ones_like(gamma)}}
    y, _ = bn.apply(variables, x.astype(jnp.float32),
                    mutable=["batch_stats"])
    if skip is not None:
        y = y + skip.astype(jnp.float32)
    return Activation(act).apply({}, y).astype(x.dtype)


def parity_bn_kernels(shape, dtype, act: str, interpret: bool) -> None:
    """The BN-tail family without and with a skip, train mode, forward
    and gradient."""
    import jax
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.ops.pallas.epilogue import \
        fused_bn_act_train
    c = shape[-1]
    kx, ks, kg, kv = jax.random.split(jax.random.key(c + shape[1]), 4)
    x, skip, g = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
                  for k in (kx, ks, kg))             # g: upstream cotangent
    gamma = jax.random.uniform(kv, (c,), jnp.float32, 0.5, 1.5)
    beta = 0.1 * jax.random.normal(kv, (c,), jnp.float32)
    names = ("out", "dx", "dgamma", "dbeta", "dskip")

    def via(fn, n_args):
        def run(*args):
            def loss(*args):
                out = fn(*args)
                return jnp.sum(out.astype(jnp.float32)
                               * g.astype(jnp.float32)), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=tuple(range(n_args)), has_aux=True)(*args)
            return dict(zip(names, (out,) + grads))
        return jax.jit(run)(*(x, gamma, beta, skip)[:n_args])

    tol = TOL[jnp.dtype(dtype).name]
    tag = "%s %s %s" % (act, "x".join(map(str, shape)),
                        jnp.dtype(dtype).name)
    _check("epilogue " + tag,
           via(lambda x, ga, be: fused_bn_act_train(
               x, ga, be, activation=act, interpret=interpret)[0], 3),
           via(lambda x, ga, be: _xla_bn_act(x, ga, be, act), 3), tol)
    _check("residual " + tag,
           via(lambda x, ga, be, s: fused_bn_act_train(
               x, ga, be, s, activation=act, interpret=interpret)[0], 4),
           via(lambda x, ga, be, s: _xla_bn_act(x, ga, be, act, skip=s), 4),
           tol)


def parity_loss(batch: int, fmap: int, interpret: bool) -> None:
    """fused detection loss vs ops/loss.py, every component and d(out)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.ops.loss import \
        stacked_detection_loss
    from real_time_helmet_detection_tpu.ops.pallas.loss import \
        fused_detection_loss
    _, heat, off, wh, mask = (jnp.asarray(a) for a in synthetic_target_batch(
        batch, fmap * 4, pos_rate=0.01, seed=3))
    out = jnp.asarray(np.random.default_rng(4).standard_normal(
        (batch, 1, fmap, fmap, 6)).astype(np.float32) * 2.0)

    def via(fn):
        def run(o):
            (_, parts), d = jax.value_and_grad(
                lambda o: (lambda t: (t["total"], t))(fn(o)),
                has_aux=True)(o)
            return dict(parts, dout=d)
        return jax.jit(run)(out)

    _check("loss %dx1x%dx%dx6 float32" % (batch, fmap, fmap),
           via(lambda o: fused_detection_loss(
               o, heat, off, wh, mask, interpret=interpret)),
           via(lambda o: stacked_detection_loss(
               o, heat, off, wh, mask, num_cls=2)), TOL["float32"])


def parity_peak(batch: int, fmap: int, interpret: bool) -> None:
    """fused sigmoid+peak vs `peak_scores_reference`: bit-identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.ops.pallas.peak import (
        fused_peak_scores, peak_scores_reference)
    logits = jnp.asarray(np.random.default_rng(5).standard_normal(
        (batch, fmap, fmap, 2)).astype(np.float32) * 4.0)
    got = jax.jit(jax.vmap(lambda x: fused_peak_scores(
        x, interpret=interpret)))(logits)
    ref = jax.jit(jax.vmap(peak_scores_reference))(logits)
    same = bool(np.array_equal(np.asarray(got), np.asarray(ref)))
    say("parity peak %dx%dx%dx2 float32: bit-identical=%s"
        % (batch, fmap, fmap, same))
    if not same:
        raise AssertionError("fused peak kernel differs from the XLA path")


def parity_model(size: Size) -> None:
    """The pair the chip default depends on: train-mode logits of the
    whole network, every BN tail fused vs every BN tail xla, same fp32
    weights and batch. What tests/test_bn_tail.py pins at toy size, here at the smoke's full width — at HIGHEST matmul
    precision, so that the convolutions between the tails are the same
    f32 function on both sides (the MXU's default rounds f32 operands to
    bf16, which turns the tails' last-bit differences into rounding flips
    the next forty layers amplify)."""
    import jax
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.train import init_variables
    base = dict(num_stack=1, hourglass_inch=size.width, num_cls=2)
    fused = build_model(Config(epilogue="fused", block_fuse="fused", **base))
    xla = build_model(Config(epilogue="xla", block_fuse="xla", **base))
    params, stats = init_variables(xla, jax.random.key(0), size.imsize)
    images = jax.random.normal(
        jax.random.key(6), (size.batch, size.imsize, size.imsize, 3),
        jnp.float32)

    def logits(model):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p, s, im: model.apply(
                {"params": p, "batch_stats": s}, im, train=True,
                mutable=["batch_stats"])[0])(params, stats, images)

    _check("model train-mode logits %dx%dx%dx3 w%d float32"
           % (size.batch, size.imsize, size.imsize, size.width),
           {"logits": logits(fused)}, {"logits": logits(xla)}, 1e-3)


def stage_parity(size: Size) -> None:
    """Each Pallas family against its XLA composition at the shapes the
    smoke's train step and predict use: stem output, hourglass top and
    bottom, the loss/peak feature map."""
    import jax.numpy as jnp
    b, top = size.batch, size.imsize // 4
    dt = jnp.bfloat16 if size.amp else jnp.float32
    parity_peak(b, top, size.interpret)
    parity_loss(b, top, size.interpret)
    for shape, dtype, act in (
            ((b, 2 * top, 2 * top, 64), dt, "ReLU"),           # stem
            ((b, top, top, size.width), dt, "ReLU"),           # hourglass top
            ((b, top // 16, top // 16, size.width), dt, "ReLU"),  # bottom
            ((b, top, top, size.width), dt, "Linear"),
            ((b, top, top, size.width), jnp.float32, "ReLU")):
        parity_bn_kernels(shape, dtype, act, size.interpret)
    if not size.interpret:
        parity_model(size)


def stage_placement(size: Size) -> None:
    """Where the work sits: the mesh train() builds, memory in use on every
    device after the run, and — on more than one chip — what the
    partitioner did with the Pallas calls in the flagship forward."""
    import re

    import jax
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.parallel import (
        batch_sharding, fit_data_mesh, make_mesh, replicated,
        under_kernel_mesh)
    mesh = make_mesh(fit_data_mesh(size.batch))
    say("mesh.shape=%s" % dict(mesh.shape))
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say("device %d: bytes_in_use=%s peak_bytes_in_use=%s"
            % (d.id, stats.get("bytes_in_use"),
               stats.get("peak_bytes_in_use")))
        if d.platform == "tpu" and not stats.get("peak_bytes_in_use"):
            # (the CPU backend of the toy-size test reports no stats)
            raise AssertionError("device %d never held memory: the run "
                                 "sat on a subset of the chips" % d.id)
    if mesh.size == 1:
        return
    cfg = Config(num_stack=1, hourglass_inch=size.width, num_cls=2)
    model = build_model(cfg, dtype=jnp.bfloat16 if size.amp else None)
    images = jax.ShapeDtypeStruct(
        (size.batch, size.imsize, size.imsize, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(images.shape),
                           train=False))
    forward = jax.jit(
        under_kernel_mesh(lambda v, im: model.apply(v, im, train=False),
                          mesh),
        in_shardings=(replicated(mesh), batch_sharding(mesh, 4)))
    compiled = forward.lower(variables, images).compile()
    hlo = compiled.as_text()
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    first = re.search(r"%(\S+) = (\w+\[[\d,]*\])\S* custom-call\(",
                      kernels[0]) if kernels else None
    say("flagship forward on the mesh: output sharding %s; %d Pallas "
        "custom-calls in the per-chip program, the first %s -> %s; "
        "all-gather ops %d"
        % (compiled.output_shardings, len(kernels),
           *(first.groups() if first else (None, None)),
           hlo.count(" all-gather(")))
    if not kernels and jax.default_backend() == "tpu":
        raise AssertionError("no Pallas custom-call in the compiled "
                             "forward: the fused paths did not run")
    say("GSPMD cannot partition a Mosaic call (jax: 'Mosaic kernels cannot "
        "be automatically partitioned'); ops/pallas/partition.py runs each "
        "under shard_map over the data axis, so every chip sees its own "
        "%d of %d samples" % (size.batch // mesh.shape["data"], size.batch))


# ---------------------------------------------------------------------------


def run(size: Size, workdir: str, counter) -> None:
    """Every stage after the device check, timed; the first failure ends
    the run (nothing here catches)."""
    root, save = os.path.join(workdir, "voc"), os.path.join(workdir, "w")
    out = os.path.join(workdir, "out")
    stages = (
        ("data", lambda: stage_data(root, size)),
        ("train", lambda: stage_train(root, save, size)),
        ("serve", lambda: stage_serve(root, save, out, size)),
        ("demo", lambda: stage_demo(root, save, out, size)),
        ("placement", lambda: stage_placement(size)),
        ("parity", lambda: stage_parity(size)),
    )
    for name, fn in stages:
        t0, n0, h0, c0 = (time.time(), counter.count, counter.cache_hits,
                          counter.total_s)
        fn()
        say("stage=%s wall_s=%.1f backend_compiles=%d (persistent-cache "
            "hits %d) compile_s=%.1f  [smoke output, not a benchmark]"
            % (name, time.time() - t0, counter.count - n0,
               counter.cache_hits - h0, counter.total_s - c0))


def main() -> None:
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    t0 = time.time()
    say("compile cache: %s" % use_compile_cache())
    dev = stage_device()
    counter = install_recompile_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke.") as workdir:
        run(Size(), workdir, counter)
    say("all stages passed: wall_s=%.1f backend_compiles=%d (persistent-"
        "cache hits %d) compile_s=%.1f  [smoke output, not a benchmark]"
        % (time.time() - t0, counter.count, counter.cache_hits,
           counter.total_s))
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
