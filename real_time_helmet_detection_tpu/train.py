"""Training runtime: train state, jitted sharded train step, epoch loop,
checkpoint/resume.

Capability parity with the reference training runtime
(/root/reference/train.py), re-designed TPU-first:

* `distributed_device_train` + `mp.spawn` + NCCL process groups
  (ref train.py:23-45) become a single jitted train step partitioned over a
  `jax.sharding.Mesh` — XLA GSPMD inserts the gradient all-reduce over ICI;
  multi-host joins via `parallel.init_distributed` (DCN);
* AMP autocast + GradScaler (ref train.py:96-97, 128-132) become a bf16
  compute dtype on the model — bf16 matches fp32 dynamic range, so no loss
  scaling is needed (an optional-parity scaler would be dead weight);
* per-stack deep-supervision loss (ref train.py:104-120): split the
  (B, S, H/4, W/4, C+4) output per stack, sigmoid the heatmap (+ offset/size
  when `--normalized-coord`), sum `detection_loss` over stacks;
* gradient accumulation every `--sub-divisions` steps (ref train.py:124-139)
  via `optax.MultiSteps` inside the jitted step;
* per-epoch checkpoint of model/optimizer/loss-log/epoch on host 0
  (ref train.py:76-82) via orbax + a JSON loss-log sidecar; resume restores
  everything (ref train.py:190-199);
* segment timing with `AverageMeter`s over data/step (ref train.py:92-140)
  and the rank-0 heatmap-blend snapshot every `--print-interval` iterations
  (ref train.py:154-158).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from .config import Config, save_config
from .data import BatchLoader, load_dataset
from .models import build_model
from .ops.loss import (LossLog, split_stack_predictions,
                       stacked_detection_loss)
from .ops.pallas.select import kernel_plan
from .optim import build_optimizer
from .parallel import (batch_sharding, init_distributed, make_mesh,
                       replicated, shard_batch, under_kernel_mesh)
# HangWatchdog and the transient-error classifier live in runtime/ (the
# job supervisor shares them); re-exported here so existing imports
# (`from ...train import HangWatchdog`) keep working.
from .runtime.errors import (InjectedBackendError,  # noqa: F401
                             TrainingDivergenceError,
                             is_transient_backend_error)
from .runtime.heartbeat import HEARTBEAT_ENV, HangWatchdog  # noqa: F401
from .utils import AverageMeter, blend_heatmap, save_json, timestamp


class TrainState(struct.PyTreeNode):
    """Pure-pytree training state (checkpointable as-is).

    `ema_params` (populated when `--ema-decay` > 0, else None) is an
    exponential moving average of `params`, updated inside the jitted
    step; `--ema-eval` evaluates with it. A capability the reference
    lacks. Whether EMA helps depends on the decay-vs-training-budget
    match — measured both ways on the same 2400-step 256^2 setup:
    decay 0.998 (window reaching back across the final LR drop) scored
    -3.2 mAP, decay 0.99 (window inside the final-LR phase) +0.45
    (builders' r04 calibration runs). Opt-in lever: pick decay so the
    averaging window fits inside the final-LR phase.
    """
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    ema_params: Any = None


# split_stack_predictions moved to ops/loss.py (shared with both loss
# implementations); re-exported above for compatibility.


def init_variables(model, rng: jax.Array, imsize: int):
    """Initialize (params, batch_stats) — no optimizer. The init is jitted:
    eager init would compile and dispatch every conv as its own program."""
    dummy = jnp.zeros((1, imsize, imsize, 3), jnp.float32)
    variables = jax.jit(model.init, static_argnames=("train",))(
        rng, dummy, train=False)
    return variables["params"], variables.get("batch_stats", {})


def resolve_param_policy(cfg: Config) -> str:
    """'fp32' | 'bf16-compute' (no auto mode — the policy is a numerics
    decision, not a backend one; config.py validates the vocabulary and
    the --amp / --sub-divisions requirements)."""
    return getattr(cfg, "param_policy", "fp32")


def create_train_state(model, cfg: Config, rng: jax.Array, imsize: int,
                       tx) -> TrainState:
    """Initialize params/batch-stats/optimizer (≡ ref train.py:164-187
    `load_network` fresh path).

    `--param-policy bf16-compute` (ISSUE 7): the optimizer state seeds
    its fp32 MASTER from the full-precision init (optim.with_fp32_master
    — no mantissa lost), and the TrainState carries the ONCE-cast bf16
    compute copy; the per-step use-site recasts of the fp32 policy
    disappear from the program. The fp32 path is textually the pre-PR
    code (bit-identity pinned by tests/test_param_policy.py)."""
    params, batch_stats = init_variables(model, rng, imsize)
    if resolve_param_policy(cfg) == "bf16-compute":
        opt_state = tx.init(params)  # master = the fp32 init, exactly
        params = jax.jit(lambda p: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, p))(params)
    else:
        opt_state = tx.init(params)
    # EMA starts as a DISTINCT copy of params (one jitted call): aliasing
    # the same buffers would make the donating train step donate them twice
    ema = (jax.jit(lambda p: jax.tree.map(jnp.copy, p))(params)
           if cfg.ema_decay > 0 else None)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      batch_stats=batch_stats, opt_state=opt_state,
                      ema_params=ema)


class Distiller:
    """Teacher half of `--distill` (ISSUE 13): the flagship checkpoint's
    forward pass, run INSIDE the student's jitted step under
    `stop_gradient`, plus the soft-target loss mixing its last stack's
    heatmap/offset/size into the student's deep-supervision loss.

    Design constraints, each load-bearing:

    * the teacher variables are CLOSED OVER (trace-time constants), so
      every step body/runner/scan signature — and therefore the donation
      and sharding contracts — is byte-identical to the non-distill
      program; `--distill` off traces the exact pre-PR step (bit-identity
      pinned by tests/test_tiers.py);
    * fixed shapes: teacher and student share imsize/scale_factor/num_cls,
      so the soft targets are the student's own (B, H/4, W/4, C+4) map —
      no dynamic anything, composes with --grad-accum's micro-batch scan
      and --sentinel's skip-select unchanged;
    * the soft-loss scalars join the step's losses dict and ride the SAME
      deferred loss fetch as every other component (zero extra D2H — the
      --telemetry contract);
    * soft losses reuse the hard loss's own normalizations (focal-style
      num_pos for the heatmap MSE, `normed_l1_loss` for offset/size) so
      `--distill-alpha` weighs comparable magnitudes.
    """

    def __init__(self, model, params, batch_stats, alpha: float,
                 num_cls: int, normalized_coord: bool):
        self.model = model
        self.params = params
        self.batch_stats = batch_stats
        self.alpha = float(alpha)
        self.num_cls = int(num_cls)
        self.normalized = bool(normalized_coord)

    def soft_targets(self, images):
        """Teacher last-stack soft targets (heat, offset, size), all under
        stop_gradient — the backward never touches the teacher graph."""
        out = self.model.apply(
            {"params": self.params, "batch_stats": self.batch_stats},
            images, train=False)
        return split_stack_predictions(
            jax.lax.stop_gradient(out[:, -1]), self.num_cls,
            self.normalized)

    def soft_losses(self, student_out, images, mask, cfg: Config):
        """Per-student-stack soft loss vs the teacher's last stack."""
        from .ops.loss import normed_l1_loss
        t_heat, t_off, t_size = self.soft_targets(images)
        t_heat = t_heat.astype(jnp.float32)
        num_pos = jnp.clip(jnp.sum(mask), 1.0, 1e30)
        hm = jnp.float32(0.0)
        off = jnp.float32(0.0)
        size = jnp.float32(0.0)
        for s in range(student_out.shape[1]):
            s_heat, s_off, s_size = split_stack_predictions(
                student_out[:, s], self.num_cls, self.normalized)
            # heatmap: dense MSE on the sigmoid maps, focal-normalized
            # (sum over HWC, batch mean, / global positive count) so it
            # lives on the hard focal loss's scale
            d = jnp.square(s_heat.astype(jnp.float32) - t_heat)
            hm = hm + jnp.sum(d, axis=(1, 2, 3)).mean() / num_pos
            # offset/size: the hard loss's own masked-L1 against teacher
            # regressions (only GT centers carry signal in these maps)
            off = off + normed_l1_loss(s_off, t_off, mask)
            size = size + normed_l1_loss(s_size, t_size, mask)
        total = (hm * cfg.hm_weight + off * cfg.offset_weight
                 + size * cfg.size_weight)
        return {"hm": hm, "offset": off, "size": size, "total": total}


def make_distiller(cfg: Config) -> Optional[Distiller]:
    """Build the `--distill` teacher from its checkpoint, or None.

    Teacher ARCHITECTURE comes from the checkpoint dir's argument.json
    snapshot (the eval-restore path, config.update_config_for_eval), so a
    flagship stack2 teacher distills into an edge-tier student without
    any teacher flags on the student's command line."""
    path = getattr(cfg, "distill", None)
    if not path:
        return None
    import dataclasses
    from .config import load_config, update_config_for_eval
    path = resolve_model_load(path)
    tcfg = cfg
    snap = os.path.join(os.path.dirname(os.path.abspath(path)),
                        "argument.json")
    if os.path.exists(snap):
        tcfg = update_config_for_eval(cfg, load_config(snap))
    else:
        print("%s: --distill %s has no argument.json; assuming the "
              "student's own architecture" % (timestamp(), path),
              flush=True)
    tmodel = build_model(tcfg, dtype=jnp.bfloat16 if cfg.amp else None)
    imsize = cfg.imsize or cfg.multiscale[1]
    p_tmpl, bs_tmpl = init_variables(tmodel, jax.random.key(0), imsize)
    params, batch_stats = restore_variables(path, p_tmpl, bs_tmpl)
    print("%s: --distill teacher %s (variant=%s stacks=%d width=%d, "
          "alpha=%g)" % (timestamp(), path,
                         getattr(tcfg, "variant", "residual"),
                         tcfg.num_stack, tcfg.hourglass_inch,
                         cfg.distill_alpha), flush=True)
    return Distiller(tmodel, params, batch_stats, cfg.distill_alpha,
                     cfg.num_cls, cfg.normalized_coord)


def loss_fn(params, batch_stats, model, images, gt_heat, gt_off, gt_wh, mask,
            cfg: Config, distill: Optional[Distiller] = None):
    """Forward + deep-supervision loss over all stacks (ref train.py:99-120).

    Two step-compression levers hook in here (both numerically pinned by
    tests): `--remat full` wraps the WHOLE forward in
    `jax.checkpoint(nothing_saveable)` — backward recomputes every
    activation (stem/neck/head included, beyond what the in-model
    per-stack nn.remat covers) so batch 32/64 @512^2 fits HBM; and
    `--loss-kernel` picks the XLA loss composition or the one-pass Pallas
    fused kernel (ops/pallas/loss.py).

    `distill` (ISSUE 13): the teacher's soft-target loss joins the hard
    loss at weight `--distill-alpha`; the teacher forward runs under
    stop_gradient OUTSIDE any remat wrapper (it has no backward to
    recompute, so rematerializing it would only re-run a gradient-free
    forward)."""
    def apply_fn(p, bs, im):
        return model.apply({"params": p, "batch_stats": bs}, im,
                           train=True, mutable=["batch_stats"])

    if getattr(cfg, "remat", "none") == "full":
        apply_fn = jax.checkpoint(
            apply_fn, policy=jax.checkpoint_policies.nothing_saveable)
    out, mutated = apply_fn(params, batch_stats, images)
    kw = dict(hm_weight=cfg.hm_weight, offset_weight=cfg.offset_weight,
              size_weight=cfg.size_weight, focal_alpha=cfg.focal_alpha,
              focal_beta=cfg.focal_beta)
    # the scope names the layer in the compiled program's metadata
    # (obs/hlo_scopes.py). It also keeps the loss kernels' device events
    # under their own `name=`: XLA names an instruction after the LAST
    # element of its op_name, and jax writes the transform it traces under
    # around the outermost scope beneath it — with no scope here that was
    # the kernel's own (`jvp(detection_loss_fwd)` -> `jvp_detection_loss_
    # fwd_`), with it `jvp(loss)/detection_loss_fwd`, as the BN tails get
    # `jvp(StackedHourglass)/.../bn_act_fwd` from flax's module scopes.
    with jax.named_scope("loss"):
        if kernel_plan(cfg)["loss"] == "fused":
            from .ops.pallas import fused_detection_loss
            totals = fused_detection_loss(
                out, gt_heat, gt_off, gt_wh, mask,
                normalized_coord=cfg.normalized_coord, **kw)
        else:
            totals = stacked_detection_loss(
                out, gt_heat, gt_off, gt_wh, mask, num_cls=cfg.num_cls,
                normalized_coord=cfg.normalized_coord, **kw)
        if distill is not None:
            soft = distill.soft_losses(out, images, mask, cfg)
            totals["distill"] = soft["total"]
            totals["total"] = (totals["total"]
                               + distill.alpha * soft["total"])
    return totals["total"], (mutated.get("batch_stats", batch_stats), totals)


def _maybe_telemetry(cfg: Config, losses, grads, old_params,
                     new_state: TrainState):
    """Attach the in-jit telemetry scalars (grad/update/param global norms,
    obs/telemetry.py) to the step's losses dict when `--telemetry` is on.

    Off (the default) this is an identity at TRACE time — the compiled
    step is the exact pre-telemetry program and the loss is bit-identical
    (pinned by tests/test_obs.py). On, the scalars ride the SAME fetch as
    the loss scalars (the deferred flush / the scanned ring): zero extra
    D2H."""
    if not getattr(cfg, "telemetry", False):
        return losses
    from .obs.telemetry import telemetry_scalars
    out = dict(losses)
    out.update(telemetry_scalars(grads, old_params, new_state.params))
    return out


def _optimizer_update(state: TrainState, tx, cfg: Config, grads,
                      batch_stats) -> TrainState:
    """Shared update tail of every train-step body: optimizer step + EMA
    stream (when --ema-decay is on) + step counter. One implementation so
    the host, device-augment and cached input paths cannot drift."""
    from .optim import MasterOptimizer
    with jax.named_scope("optimizer"):
        if isinstance(tx, MasterOptimizer):
            # --param-policy bf16-compute: the wrapper returns the new bf16
            # params directly (params := bf16(updated fp32 master) — the
            # cast fuses into the Adam pass; see optim.with_fp32_master)
            params, opt_state = tx.update(grads, state.opt_state,
                                          state.params)
        else:
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        ema = state.ema_params
        if cfg.ema_decay > 0 and ema is not None:
            d = cfg.ema_decay
            ema = jax.tree.map(lambda e, p: d * e + (1.0 - d) * p, ema,
                               params)
    return state.replace(step=state.step + 1, params=params,
                         batch_stats=batch_stats, opt_state=opt_state,
                         ema_params=ema)


def _sentinel_update(cfg: Config, state: TrainState, tx, grads, batch_stats,
                     losses, loss_scale):
    """The sentinel step tail (ISSUE 9; only traced when cfg.sentinel):
    in-jit NaN/Inf + grad-spike check, SKIP-STEP on a tripped batch — the
    whole TrainState (params, optimizer moments, batch stats, EMA stream,
    step counter) keeps its pre-step value via one fixed-shape select, so
    a poison batch can never contaminate optimizer state — and the
    sentinel scalars join the losses dict that rides the existing
    deferred loss fetch (zero extra D2H; the --telemetry contract)."""
    import optax
    gn = optax.global_norm(grads).astype(jnp.float32)
    bad = jnp.logical_or(jnp.logical_not(jnp.isfinite(losses["total"])),
                         jnp.logical_not(jnp.isfinite(gn)))
    if cfg.sentinel_spike > 0:
        bad = jnp.logical_or(bad, gn > cfg.sentinel_spike)
    new_state = _optimizer_update(state, tx, cfg, grads, batch_stats)
    # XLA select: the NaN branch's values are never propagated, and every
    # old-state buffer has a same-aval output to alias under donation
    out_state = jax.tree.map(lambda o, n: jnp.where(bad, o, n), state,
                             new_state)
    out_losses = dict(_maybe_telemetry(cfg, losses, grads, state.params,
                                       out_state))
    out_losses["sentinel_bad"] = bad.astype(jnp.float32)
    out_losses["sentinel_grad_norm"] = gn
    out_losses["sentinel_scale"] = jnp.asarray(loss_scale, jnp.float32)
    return out_state, out_losses


def _make_accum_step_body(model, tx, cfg: Config, distill=None):
    """`--grad-accum k` train-step body (ISSUE 11): the global batch is
    split into `k` equal micro-batches scanned INSIDE the jitted step —
    per-micro fwd+bwd with gradients accumulated in fp32 (a bf16
    accumulator would lose k-1 rounding steps; this is why the policy
    composes with `--param-policy bf16-compute`, whose grads are bf16),
    then ONE optimizer update on the SUMMED micro-gradients — the
    reference's accumulate-without-dividing convention (ref
    train.py:128-136), deliberately identical to what `--sub-divisions`
    feeds the optimizer (optax.MultiSteps' mean pre-scaled by k), so the
    two accumulation paths and their composition share one effective-LR
    convention (equivalence pinned by tests). Activation memory is that
    of a batch/k step; the effective batch — and, under GSPMD data
    parallelism, the cross-replica gradient all-reduce — is per UPDATE
    (the FireCaffe communication/batch tradeoff, PAPERS.md). BatchNorm
    statistics thread sequentially through the scan carry, exactly as k
    consecutive steps would update them. The losses dict reports the
    micro-batch MEAN, so one poisoned micro-batch makes the step's total
    non-finite and the sentinel (`--sentinel`) skips the WHOLE
    accumulated update — a partial window can never contaminate the
    optimizer."""
    k = int(cfg.grad_accum)

    def accum(params, batch_stats, arrays, loss_scale=None):
        def split(a):
            return a.reshape((k, a.shape[0] // k) + tuple(a.shape[1:]))

        micro = tuple(split(a) for a in arrays)
        acc0 = jax.tree.map(lambda p: jnp.zeros(jnp.shape(p), jnp.float32),
                            params)

        def body(carry, xs):
            bs, acc = carry
            images, gt_heat, gt_off, gt_wh, mask = xs

            def lf(p, b):
                total, aux = loss_fn(p, b, model, images, gt_heat, gt_off,
                                     gt_wh, mask, cfg, distill=distill)
                if loss_scale is not None:
                    total = total * loss_scale
                return total, aux

            (_, (bs, losses)), grads = jax.value_and_grad(
                lf, has_aux=True)(params, bs)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc,
                               grads)
            return (bs, acc), losses

        (batch_stats, acc), stacked = jax.lax.scan(
            body, (batch_stats, acc0), micro)
        # report the readable per-micro MEAN loss; feed the optimizer the
        # SUM of micro-grads (unscaled — see the docstring's convention)
        losses = jax.tree.map(lambda x: jnp.mean(x, axis=0), stacked)
        if loss_scale is None:
            grads = acc
        else:
            grads = jax.tree.map(lambda a: a / loss_scale, acc)
        return grads, batch_stats, losses

    if not getattr(cfg, "sentinel", False):
        def step(state: TrainState, images, gt_heat, gt_off, gt_wh, mask):
            grads, batch_stats, losses = accum(
                state.params, state.batch_stats,
                (images, gt_heat, gt_off, gt_wh, mask))
            new_state = _optimizer_update(state, tx, cfg, grads, batch_stats)
            return new_state, _maybe_telemetry(cfg, losses, grads,
                                               state.params, new_state)

        step.sentinel = False
        return step

    def step(state: TrainState, images, gt_heat, gt_off, gt_wh, mask,
             loss_scale):
        grads, batch_stats, losses = accum(
            state.params, state.batch_stats,
            (images, gt_heat, gt_off, gt_wh, mask), loss_scale=loss_scale)
        return _sentinel_update(cfg, state, tx, grads, batch_stats, losses,
                                loss_scale)

    step.sentinel = True
    return step


def make_train_step_body(model, tx, cfg: Config, distill=None):
    """The un-jitted train-step body: fwd + bwd + optimizer update.

    Exposed separately from `make_train_step` so callers that need the step
    *inside* another XLA program (bench.py scans N steps in one dispatch to
    time steady-state compute without per-dispatch overhead) can reuse the
    exact production step.

    `--grad-accum k` (ISSUE 11) routes to `_make_accum_step_body` (same
    signature — an in-jit micro-batch scan with ONE optimizer update);
    `--grad-accum 1` (the default) keeps the exact pre-PR body below.

    `--sentinel` (ISSUE 9) grows the signature by one trailing f32
    `loss_scale` argument (the host-side backoff lever; the loss is scaled
    before backward and the grads unscaled after, guarding the bf16
    backward against overflow) and routes the update through
    `_sentinel_update`'s skip-step select. Sentinel OFF keeps the exact
    pre-PR body (bit-identity pinned by tests/test_sentinel.py); the
    built step carries `step.sentinel` so wrappers (scan, runners) adapt."""
    if getattr(cfg, "grad_accum", 1) > 1:
        return _make_accum_step_body(model, tx, cfg, distill=distill)
    if not getattr(cfg, "sentinel", False):
        def step(state: TrainState, images, gt_heat, gt_off, gt_wh, mask):
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (_, (batch_stats, losses)), grads = grad_fn(
                state.params, state.batch_stats, model, images, gt_heat,
                gt_off, gt_wh, mask, cfg, distill)
            new_state = _optimizer_update(state, tx, cfg, grads, batch_stats)
            return new_state, _maybe_telemetry(cfg, losses, grads,
                                               state.params, new_state)

        step.sentinel = False
        return step

    def step(state: TrainState, images, gt_heat, gt_off, gt_wh, mask,
             loss_scale):
        def scaled_loss(params, batch_stats):
            total, aux = loss_fn(params, batch_stats, model, images,
                                 gt_heat, gt_off, gt_wh, mask, cfg,
                                 distill)
            return total * loss_scale, aux

        grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)
        (_, (batch_stats, losses)), grads = grad_fn(state.params,
                                                    state.batch_stats)
        grads = jax.tree.map(lambda g: g / loss_scale, grads)
        return _sentinel_update(cfg, state, tx, grads, batch_stats, losses,
                                loss_scale)

    step.sentinel = True
    return step


def make_scanned_train_fn(body, n: int, telemetry: bool = False,
                          ring_capacity: int = 64, sentinel: bool = False):
    """`n` sequential train steps inside ONE XLA program (`lax.scan` over a
    `make_train_step_body` step), returning (final TrainState, last total
    loss).

    The single timing harness both bench.py and scaling.py jit: dispatching
    one program keeps per-call dispatch overhead out of the measurement
    (whether a per-step `block_until_ready` loop agrees with it on a local
    chip is ROADMAP S1's question).

    The FULL final state is returned (not just its step counter) so that
    jitting with `donate_argnums=(0,)` actually works: every donated input
    buffer has a same-aval/same-sharding output to alias, the copy is
    elided, and XLA emits no "Some donated buffers were not usable"
    warning. Callers must time by fetching ONLY the scalar loss
    (`compiled(...)[1]`) — fetching the state would drag the whole model
    through the (slow) D2H transport and into the measurement.

    `telemetry=True` (flight recorder, ISSUE 6; requires a body built from
    a `--telemetry` cfg) additionally threads a FIXED-SHAPE telemetry ring
    (obs/telemetry.py) through the scan carry: per-step loss components +
    grad/update/param norms land in a (ring_capacity, K) f32 buffer that
    returns NEXT TO the loss scalar — out[1] becomes (last_total, ring),
    fetched in the SAME single D2H (a few KiB; decode on host with
    `ring_to_host`). Telemetry off keeps the exact pre-PR signature and
    program.

    `sentinel=True` (ISSUE 9; requires a `--sentinel` body, which takes a
    trailing loss_scale arg — the scan pins it at 1.0) accumulates the
    in-jit skip count through the carry instead: out[1] becomes
    (last_total, skipped_steps int32), same single D2H — how bench.py
    puts `skipped_steps` on its ONE JSON line. Mutually exclusive with
    telemetry (the combined carry has no consumer; pick one)."""
    if sentinel and telemetry:
        raise ValueError("make_scanned_train_fn: telemetry and sentinel "
                         "rings are mutually exclusive — pick one")
    if sentinel:
        if not getattr(body, "sentinel", False):
            raise ValueError(
                "make_scanned_train_fn(sentinel=True) needs a step body "
                "built with cfg.sentinel=True")

        def train_n(state, images, heat, off, wh, mask):
            def sbody(carry, _):
                st, skipped = carry
                st, losses = body(st, images, heat, off, wh, mask,
                                  jnp.float32(1.0))
                skipped = skipped + losses["sentinel_bad"].astype(jnp.int32)
                return (st, skipped), losses["total"]
            carry0 = (state, jnp.zeros((), jnp.int32))
            (st, skipped), totals = jax.lax.scan(sbody, carry0, None,
                                                 length=n)
            return st, (totals[-1], skipped)
        return train_n
    if not telemetry:
        def train_n(state, images, heat, off, wh, mask):
            def sbody(st, _):
                st, losses = body(st, images, heat, off, wh, mask)
                return st, losses["total"]
            st, totals = jax.lax.scan(sbody, state, None, length=n)
            return st, totals[-1]
        return train_n

    from .obs.telemetry import SCAN_TELEMETRY_KEYS, ring_init, ring_push

    def train_n(state, images, heat, off, wh, mask):
        def sbody(carry, _):
            st, ring = carry
            st, losses = body(st, images, heat, off, wh, mask)
            missing = [k for k in SCAN_TELEMETRY_KEYS if k not in losses]
            if missing:
                raise ValueError(
                    "make_scanned_train_fn(telemetry=True) needs a step "
                    "body built with cfg.telemetry=True; losses dict is "
                    "missing %s" % missing)
            ring = ring_push(ring, [losses[k] for k in SCAN_TELEMETRY_KEYS])
            return (st, ring), losses["total"]
        carry0 = (state, ring_init(ring_capacity))
        (st, ring), totals = jax.lax.scan(sbody, carry0, None, length=n)
        return st, (totals[-1], ring)

    train_n.telemetry_keys = SCAN_TELEMETRY_KEYS
    return train_n


def make_state_accum_flush(cfg: Config, steps_per_epoch: int):
    """TrainState-level epoch-end accumulation flush, or None when
    --sub-divisions is 1.

    Parity: the reference steps the optimizer at the LAST iteration of
    every epoch even mid-accumulation-window (ref train.py:124-139);
    optax.MultiSteps would otherwise carry the partial window into the
    next epoch. The EMA stream advances with the flushed update exactly as
    with any other optimizer step."""
    from .optim import make_accum_flush
    flush = make_accum_flush(cfg, steps_per_epoch)
    if flush is None:
        return None

    @jax.jit
    def run(state: TrainState) -> TrainState:
        # EMA decays ONLY when the flush actually applied an update
        # (mini_step > 0): an effective decay of 1.0 makes the EMA branch
        # an identity, so run() is intrinsically no-op-safe even if a
        # caller ever dispatches it with an empty accumulation window
        # (r3 advisor finding — previously only train()'s host-side
        # mini_step check prevented a spurious EMA step).
        applied = state.opt_state.mini_step > 0
        params, opt_state = flush(state.params, state.opt_state)
        ema = state.ema_params
        if cfg.ema_decay > 0 and ema is not None:
            d = jnp.where(applied, cfg.ema_decay, 1.0)
            ema = jax.tree.map(
                lambda e, p: (d * e + (1.0 - d) * p).astype(e.dtype), ema,
                params)
        return state.replace(params=params, opt_state=opt_state,
                             ema_params=ema)

    return run


def make_train_step(model, tx, cfg: Config, mesh, distill=None):
    """Build the jitted, mesh-partitioned train step.

    Batch arrays are sharded (data[, spatial]); state is replicated. The
    gradient all-reduce the reference gets from DDP hooks
    (ref train.py:174-175) falls out of GSPMD partitioning here.
    """
    step = make_train_step_body(model, tx, cfg, distill=distill)
    repl = replicated(mesh)
    # Shardings: state fully replicated; image NHWC and target maps shard
    # (data on B, spatial on H). The sentinel body's trailing loss_scale
    # scalar replicates.
    img_sh = batch_sharding(mesh, 4, spatial_dim=1)
    map_sh = batch_sharding(mesh, 4, spatial_dim=1)
    in_sh = (repl, img_sh, map_sh, map_sh, map_sh, map_sh)
    if getattr(step, "sentinel", False):
        in_sh = in_sh + (repl,)
    return jax.jit(
        under_kernel_mesh(step, mesh),
        in_shardings=in_sh,
        out_shardings=(repl, repl),
        donate_argnums=(0,))


def make_device_step_body(model, tx, cfg: Config, target: int,
                          distill=None):
    """Un-jitted fused-input step: on-device augmentation, GT encoding and
    normalization followed by fwd/bwd/update. Shared by the streaming
    (`make_device_train_step`) and HBM-cached (`make_cached_device_train_
    step`) input paths."""
    from .data.augment_device import augment_encode_batch
    from .utils import normalizer_stats

    mean, std = normalizer_stats(cfg.pretrained)
    mean = jnp.asarray(mean)
    std = jnp.asarray(std)

    def prep(key, step_idx, images, boxes, labels, valid):
        # per-step randomness derived INSIDE the program: the host passes
        # the constant base key + a scalar step index instead of folding on
        # the host (which would dispatch an extra device op per step)
        key = jax.random.fold_in(key, step_idx)
        img, heat, off, wh, mask, _, _ = augment_encode_batch(
            key, images.astype(jnp.float32), boxes, labels, valid,
            target=target,
            scale_factor=cfg.scale_factor, num_cls=cfg.num_cls,
            normalized=cfg.normalized_coord,
            crop_percent=tuple(cfg.crop_percent),
            color_multiply=tuple(cfg.color_multiply),
            translate_percent=cfg.translate_percent,
            affine_scale=tuple(cfg.affine_scale))
        with jax.named_scope("normalize"):
            img = (img / 255.0 - mean) / std
        return img, heat, off, wh, mask

    if not getattr(cfg, "sentinel", False):
        def step(state: TrainState, key, step_idx, images, boxes, labels,
                 valid):
            img, heat, off, wh, mask = prep(key, step_idx, images, boxes,
                                            labels, valid)
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (_, (batch_stats, losses)), grads = grad_fn(
                state.params, state.batch_stats, model, img, heat, off, wh,
                mask, cfg, distill)
            new_state = _optimizer_update(state, tx, cfg, grads, batch_stats)
            return new_state, _maybe_telemetry(cfg, losses, grads,
                                               state.params, new_state)

        step.sentinel = False
        return step

    def step(state: TrainState, key, step_idx, images, boxes, labels,
             valid, loss_scale):
        img, heat, off, wh, mask = prep(key, step_idx, images, boxes,
                                        labels, valid)

        def scaled_loss(params, batch_stats):
            total, aux = loss_fn(params, batch_stats, model, img, heat,
                                 off, wh, mask, cfg, distill)
            return total * loss_scale, aux

        grad_fn = jax.value_and_grad(scaled_loss, has_aux=True)
        (_, (batch_stats, losses)), grads = grad_fn(state.params,
                                                    state.batch_stats)
        grads = jax.tree.map(lambda g: g / loss_scale, grads)
        return _sentinel_update(cfg, state, tx, grads, batch_stats, losses,
                                loss_scale)

    step.sentinel = True
    return step


def make_device_train_step(model, tx, cfg: Config, mesh, target: int,
                           distill=None):
    """Train step with the input pipeline fused in: on-device augmentation,
    GT encoding and normalization followed by fwd/bwd/update — ONE XLA
    program per multiscale bucket. The host only decodes JPEGs and resizes
    to the canvas (data/augment_device.py; ≡ imgaug + box2hm + normalize of
    ref data.py:93-125 moved onto the accelerator)."""
    step = make_device_step_body(model, tx, cfg, target, distill=distill)
    repl = replicated(mesh)
    img_sh = batch_sharding(mesh, 4)     # gather-based warp: no spatial shard
    box_sh = batch_sharding(mesh, 3)
    lab_sh = batch_sharding(mesh, 2)
    in_sh = (repl, repl, repl, img_sh, box_sh, lab_sh, lab_sh)
    if getattr(step, "sentinel", False):
        in_sh = in_sh + (repl,)
    return jax.jit(under_kernel_mesh(step, mesh), in_shardings=in_sh,
                   out_shardings=(repl, repl), donate_argnums=(0,))


def make_cached_device_train_step(model, tx, cfg: Config, mesh, target: int,
                                  cache, distill=None):
    """Fused step over the HBM-resident dataset (`--cache-device`): the
    host sends only a `(B,)` int32 index vector per step; the batch is
    gathered from the replicated device cache, then augmented/encoded/
    trained exactly as the streaming path (same `make_device_step_body`).

    Steady-state host->device traffic: B*4 bytes instead of the
    ~B*canvas^2*3 raw pixels of the streaming path — the input pipeline
    cannot be the bottleneck at any batch size."""
    body = make_device_step_body(model, tx, cfg, target, distill=distill)
    sentinel = getattr(body, "sentinel", False)

    def step(state: TrainState, key, step_idx, images_all, boxes_all,
             labels_all, valid_all, idx, *scale):
        gather = lambda a: jnp.take(a, idx, axis=0)  # noqa: E731
        return body(state, key, step_idx, gather(images_all),
                    gather(boxes_all), gather(labels_all),
                    gather(valid_all), *scale)

    repl = replicated(mesh)
    idx_sh = batch_sharding(mesh, 1)
    in_sh = (repl, repl, repl, repl, repl, repl, repl, idx_sh)
    if sentinel:
        in_sh = in_sh + (repl,)
    jitted = jax.jit(under_kernel_mesh(step, mesh), in_shardings=in_sh,
                     out_shardings=(repl, repl), donate_argnums=(0,))

    def run(state, key, step_idx, idx, *scale):
        return jitted(state, key, step_idx, cache.images, cache.boxes,
                      cache.labels, cache.valid, idx, *scale)

    run.sentinel = sentinel
    return run


def _checkpoint_path(save_path: str, epoch: int) -> str:
    """The on-disk naming contract (≡ ref `check_point_{epoch+1}.pth`)."""
    return os.path.abspath(os.path.join(save_path,
                                        f"check_point_{epoch + 1}"))


def _write_loss_log(path: str, log_state: dict) -> None:
    # atomic: a kill mid-write must leave either no sidecar (handled by
    # _read_loss_log) or a complete one — never a truncated JSON
    save_json(os.path.join(path, "loss_log.json"), log_state)


def _checkpoint_item(epoch: int, state: TrainState) -> dict:
    # plain nested dicts: restorable without reconstructing TrainState /
    # optimizer pytree types first (see _restore_raw). ema_params rides
    # along only when EMA is on, so the on-disk format is unchanged
    # otherwise.
    st = {"step": state.step, "params": state.params,
          "batch_stats": state.batch_stats, "opt_state": state.opt_state}
    if state.ema_params is not None:
        st["ema_params"] = state.ema_params
    return {"state": st, "epoch": epoch}


def save_checkpoint(save_path: str, epoch: int, state: TrainState,
                    loss_log: LossLog) -> str:
    """Per-epoch full-state checkpoint (≡ ref train.py:76-82
    `check_point_{epoch+1}.pth`)."""
    import orbax.checkpoint as ocp
    path = _checkpoint_path(save_path, epoch)
    ckpt = ocp.StandardCheckpointer()
    ckpt.save(path, jax.device_get(_checkpoint_item(epoch, state)),
              force=True)
    ckpt.wait_until_finished()
    _write_loss_log(path, loss_log.state_dict())
    return path


class CheckpointWriter:
    """Checkpoint writer with an optional async mode (`--async-ckpt`).

    Sync mode = `save_checkpoint` (blocking D2H + write each epoch, the
    reference's behavior). Async mode hands orbax the DEVICE arrays and
    returns immediately — the device->host fetch and file write overlap
    the next epoch's training (a full-state fetch is seconds-to-minutes on
    slow transports); the previous save is awaited before starting the
    next, and `finalize()` awaits the last one at the end of training.
    """

    def __init__(self, async_save: bool = False):
        import orbax.checkpoint as ocp
        self.async_save = async_save
        self._ckpt = (ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
                      if async_save else None)
        # orbax writes the checkpoint dir atomically (tmp + rename), so the
        # loss-log sidecar can only be placed inside once the save has
        # finished — deferred until the next wait point
        self._pending_sidecars: list = []

    def _write_sidecars(self) -> None:
        for path, log_state in self._pending_sidecars:
            _write_loss_log(path, log_state)
        self._pending_sidecars.clear()

    def save(self, save_path: str, epoch: int, state: TrainState,
             loss_log: LossLog) -> str:
        if not self.async_save:
            return save_checkpoint(save_path, epoch, state, loss_log)
        import orbax.checkpoint as ocp
        path = _checkpoint_path(save_path, epoch)
        self._ckpt.wait_until_finished()  # at most one save in flight
        self._write_sidecars()
        # Device-side snapshot: the training loop DONATES the state into
        # the next step, which would invalidate the buffers orbax is still
        # streaming to host. ONE jitted program (not a per-leaf eager map:
        # each eager op is its own dispatch, and the state has hundreds
        # of leaves). Note the snapshot
        # transiently doubles the state's HBM footprint until the D2H
        # completes (see config.py --async-ckpt comment).
        item = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(
            _checkpoint_item(epoch, state))
        self._ckpt.save(path, args=ocp.args.StandardSave(item), force=True)
        self._pending_sidecars.append((path, loss_log.state_dict()))
        return path

    def finalize(self) -> None:
        if self._ckpt is not None:
            self._ckpt.wait_until_finished()
            self._write_sidecars()


_CKPT_RE = re.compile(r"^check_point_(\d+)$")
# orbax finalizes a save by writing the checkpoint metadata after the
# atomic tmp-dir rename; a dir missing these markers (or still carrying
# the ".orbax-checkpoint-tmp" name, excluded by the regex above) is a
# save that was killed mid-flight (--async-ckpt) and must never be picked
_CKPT_COMMIT_MARKERS = ("_CHECKPOINT_METADATA", "commit_success.txt")


def checkpoint_complete(path: str) -> bool:
    """Is this directory a FINALIZED checkpoint (safe to restore)?"""
    if not os.path.isdir(path):
        return False
    try:
        entries = set(os.listdir(path))
    except OSError:
        return False
    return any(m in entries for m in _CKPT_COMMIT_MARKERS)


def find_latest_checkpoint(save_path: str) -> Optional[str]:
    """Newest COMPLETE `check_point_N` under `save_path`, or None.

    Skips incomplete/corrupt dirs: an async save killed mid-write leaves
    either an orbax tmp-named dir (name excluded) or a dir without the
    commit marker (content excluded) — neither may poison the
    newest-checkpoint pick that a resume or the runner-drive export
    makes (ISSUE 3 satellite)."""
    try:
        entries = os.listdir(save_path)
    except OSError:
        return None
    numbered = []
    for name in entries:
        m = _CKPT_RE.match(name)
        if m:
            numbered.append((int(m.group(1)), name))
    for _, name in sorted(numbered, reverse=True):
        path = os.path.join(save_path, name)
        if checkpoint_complete(path):
            return path
        print("%s: skipping incomplete/corrupt checkpoint %s"
              % (timestamp(), path), flush=True)
    return None


def resolve_model_load(path: str) -> str:
    """Accept either a checkpoint dir or a SAVE dir in --model-load: a
    save dir (contains check_point_N children, is not itself one)
    resolves to its newest complete checkpoint. Unresolvable inputs are
    returned unchanged so the restore's own error names the real path."""
    if not path or not os.path.isdir(path):
        return path
    if _CKPT_RE.match(os.path.basename(os.path.normpath(path))) \
            or checkpoint_complete(path):
        return path
    latest = find_latest_checkpoint(path)
    if latest:
        print("%s: --model-load %s is a save dir; using its newest "
              "complete checkpoint %s" % (timestamp(), path, latest),
              flush=True)
        return latest
    return path


def _restore_raw(path: str) -> dict:
    """Structure-free orbax restore: returns the checkpoint as nested dicts.

    Restoring without a target means the caller never has to reconstruct the
    exact optimizer pytree first — eval can load a checkpoint trained with
    any --optim/--sub-divisions combination."""
    import orbax.checkpoint as ocp
    return ocp.StandardCheckpointer().restore(os.path.abspath(path))


def _read_loss_log(path: str) -> LossLog:
    log_path = os.path.join(path, "loss_log.json")
    if os.path.exists(log_path):
        with open(log_path) as f:
            return LossLog(json.load(f))
    # possible with --async-ckpt: a kill between the background save
    # completing and the next sidecar flush leaves a valid checkpoint with
    # no loss history — resume proceeds, history restarts
    print("%s: warning: %s has no loss_log.json; resuming with an empty "
          "loss history" % (timestamp(), path), flush=True)
    return LossLog()


def load_checkpoint(path: str, state: TrainState):
    """Restore (state, epoch, loss_log) from a checkpoint dir for training
    resume (≡ ref train.py:190-199). `state` supplies the pytree structure;
    the optimizer configuration must match the one the checkpoint was
    trained with.

    The restore is *targeted*: orbax gets an abstract pytree built from the
    live TrainState, so namedtuple optimizer states (e.g.
    optax.MultiStepsState, whose field order differs from the alphabetical
    key order a structure-free restore returns) are rebuilt field-by-field
    rather than by flat leaf order.
    """
    import orbax.checkpoint as ocp
    apath = os.path.abspath(path)
    if not os.path.isdir(apath):
        raise FileNotFoundError("checkpoint directory not found: %s" % apath)
    # Abstract target from array AVALS, never buffers: `state` may hold
    # DONATED (deleted) arrays when restoring inside the --auto-resume
    # handler after a mid-step failure — shape/dtype metadata survives
    # deletion, a device_get would raise (or hang on a wedged backend).
    def _abstract(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x  # python scalars (epoch) restore by example

    def _attempt(with_ema: bool):
        item = _checkpoint_item(0, state)
        if with_ema:
            item["state"].setdefault("ema_params", state.params)  # avals
        else:
            item["state"].pop("ema_params", None)
        return ocp.StandardCheckpointer().restore(
            apath, jax.tree.map(_abstract, item))

    # The checkpoint may disagree with this run about EMA (resuming a
    # pre-EMA checkpoint with --ema-decay, or an EMA checkpoint without):
    # try the run's shape first, then the opposite, and reconcile below.
    want_ema = state.ema_params is not None
    disk_ema = want_ema
    try:
        raw_ckpt = _attempt(want_ema)
    except FileNotFoundError:
        raise
    except Exception as e:
        try:
            raw_ckpt = _attempt(not want_ema)
            disk_ema = not want_ema
        except Exception:
            raise ValueError(
                "Checkpoint at %s does not match the current model/"
                "optimizer configuration (--optim/--sub-divisions/"
                "--param-policy/architecture): %s" % (path, e)) from e
    restored = raw_ckpt["state"]
    if want_ema and not disk_ema:
        # enabling EMA mid-run: seed the stream from the restored weights —
        # as a DISTINCT copy (aliased buffers would be donated twice by the
        # donating train step)
        print("%s: checkpoint has no EMA stream; seeding EMA from the "
              "restored params" % timestamp(), flush=True)
        ema = jax.jit(lambda p: jax.tree.map(jnp.copy, p))(
            restored["params"])
    elif disk_ema and not want_ema:
        print("%s: checkpoint has an EMA stream but --ema-decay is off; "
              "dropping it" % timestamp(), flush=True)
        ema = None
    else:
        ema = restored.get("ema_params")
    st = TrainState(
        step=jnp.asarray(restored["step"]),
        params=restored["params"],
        batch_stats=restored["batch_stats"],
        opt_state=restored["opt_state"],
        ema_params=ema)
    if jax.default_backend() == "cpu":
        # XLA:CPU only: the restored state feeds straight into the
        # DONATING train step, and donating orbax-restored buffers
        # (tensorstore-backed host allocations XLA:CPU's allocator does
        # not own) corrupts the glibc heap — reproduced at HEAD as the
        # slow-tier test_auto_resume SIGABRT/SIGSEGV in the first
        # post-recovery loss fetch ("malloc_consolidate(): invalid chunk
        # size" when run outside pytest's capture); one jitted deep copy
        # into XLA-owned buffers fixes the full e2e run. TPU restores are
        # PJRT-allocated (donation is the normal, on-chip-proven path)
        # and skip the copy — it would transiently double the state's
        # HBM footprint.
        st = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(st)
    return st, int(raw_ckpt["epoch"]), _read_loss_log(path)


def restore_variables(path: str, params_template, batch_stats_template,
                      prefer_ema: bool = False):
    """Eval-time weight restore: (params, batch_stats), no optimizer
    (≡ ref train.py:191-193 when not training). Works regardless of the
    optimizer the checkpoint was trained with; the templates supply the
    pytree structure only. `prefer_ema` (--ema-eval) loads the EMA
    weights when the checkpoint has them (error if it doesn't — silently
    evaluating raw weights would misattribute the score)."""
    restored = _restore_raw(path)["state"]
    weight_key = "params"
    if prefer_ema:
        if "ema_params" not in restored:
            raise ValueError(
                "--ema-eval: checkpoint %s has no EMA weights (trained "
                "without --ema-decay)" % path)
        weight_key = "ema_params"
    params = jax.tree.unflatten(jax.tree.structure(params_template),
                                jax.tree.leaves(restored[weight_key]))
    batch_stats = jax.tree.unflatten(
        jax.tree.structure(batch_stats_template),
        jax.tree.leaves(restored["batch_stats"]))
    return params, batch_stats


def restore_params_only(path: str, state: TrainState) -> TrainState:
    """`restore_variables` for TrainState holders."""
    params, batch_stats = restore_variables(path, state.params,
                                            state.batch_stats)
    return state.replace(params=params, batch_stats=batch_stats)


def make_snapshot_fn(model, cfg: Config, mesh):
    """Jitted first-stack sigmoid heatmap for the training-log blends
    (≡ ref train.py:154-158's prediction snapshots). Partitioned like the
    train step it reads its weights from: state replicated, the (host)
    batch sharded over `data`."""
    def snapshot(params, batch_stats, images):
        out = model.apply({"params": params, "batch_stats": batch_stats},
                          images, train=False)
        return jax.nn.sigmoid(out[:, 0, ..., :cfg.num_cls])

    repl = replicated(mesh)
    return jax.jit(under_kernel_mesh(snapshot, mesh),
                   in_shardings=(repl, repl, batch_sharding(mesh, 4)),
                   out_shardings=repl)


def make_step_runner(cfg: Config, mesh, model, tx, cache=None,
                     sentinel_scale=None, distill=None, tracer=None):
    """Build `runner(state, batch, step_idx) -> (state, losses)` for the
    configured input path.

    `sentinel_scale` (`--sentinel` runs): a zero-arg callable returning
    the current loss scale (SentinelMonitor.scale_value — the host-side
    backoff lever); the runner forwards it as the jitted step's trailing
    f32 argument each call. A scalar H2D rides the dispatch args — no
    extra round trip, no recompile (same aval every call).

    Host path: targets encoded in collate; runner shards the 5 arrays and
    calls the plain train step. Device path (`--device-augment`): runner
    shards raw canvases + padded boxes and calls the fused
    augment+encode+train step, one jit cache entry per multiscale bucket.
    Cached path (`--cache-device`): `batch` is a host index vector; the
    fused step gathers the batch from the HBM-resident `cache`.

    The streaming runners expose `runner.stage(batch) -> device arrays`
    (the sharded H2D transfer alone) and accept a `data.StagedBatch` in
    place of the host batch — the `--device-prefetch` hook: train_epoch
    wraps the loader in a `DevicePrefetcher` that calls `stage` up to N
    batches ahead, so the H2D copy overlaps the previous step's compute.
    The cached path has no stage (its per-step wire is a B-int32 vector).
    """
    from .data import StagedBatch
    from .obs.spans import default_tracer
    from .obs.telemetry import install_compile_listener

    # flight recorder (obs/spans.py): `h2d` around every sharded transfer
    # and `dispatch` around every call of the jitted step, into the
    # process's ring whoever drives the runner (and into `tracer`'s span
    # log when train() hands one in); a compile that lands in a step shows
    # as a `compile` span beside it
    if tracer is None:
        tracer = default_tracer()
    install_compile_listener()

    sentinel = bool(getattr(cfg, "sentinel", False))
    scale_of = sentinel_scale if sentinel_scale is not None else (lambda: 1.0)

    def scale_args():
        # () when the sentinel is off: the call (and the traced program)
        # is exactly the pre-PR one
        return (np.float32(scale_of()),) if sentinel else ()

    if not cfg.device_augment:
        step = make_train_step(model, tx, cfg, mesh, distill=distill)

        def stage(batch):
            with tracer.span("h2d"):
                return shard_batch(
                    mesh, (batch.image, batch.heatmap, batch.offset,
                           batch.wh, batch.mask), spatial_dims=[1] * 5)

        def runner(state, batch, step_idx):
            arrays = (batch.arrays if isinstance(batch, StagedBatch)
                      else stage(batch))
            with tracer.span("dispatch", step=step_idx):
                return step(state, *arrays, *scale_args())

        def scope_map(state, staged):
            """HLO instruction -> layer of the compiled step
            (obs/hlo_scopes.py), for reading a device trace by layer. On
            request only, never on the step path: `lower().compile()`
            traces and lowers again even where the executable is cached
            (and a persistent-cache hit carries the scope names of the
            commit that filled the entry: ServingEngine.scope_maps)."""
            from .obs.hlo_scopes import scope_map as of_text
            lowered = step.lower(state, *staged, *scale_args())
            # read as text, never executed: no collective starts here
            # graftlint: off=unbarriered-collective-start
            return of_text(lowered.compile().as_text())

        runner.stage = stage
        runner.scope_map = scope_map
        return runner

    sizes = (list(range(cfg.multiscale[0], cfg.multiscale[1],
                        cfg.multiscale[2]))
             if cfg.multiscale_flag else [cfg.multiscale[1]])
    base_key = jax.random.key(cfg.random_seed + 2)
    steps = {}  # target -> fused jitted step (bucketed multiscale)

    def pick_target(step_idx: int) -> int:
        # keyed on (seed, global step): resume-deterministic, unlike a
        # stateful generator that restarts its stream on every process
        return int(np.random.default_rng(
            (cfg.random_seed, step_idx)).choice(sizes))

    # base key staged on device once; per-step fold_in happens inside the
    # jitted step (host passes only a scalar step index with the call — no
    # extra per-step dispatches)
    base_key = jax.device_put(base_key, replicated(mesh))

    def prewarm(state, call_bucket):
        """Compile every multiscale bucket BEFORE the steady-state loop
        (`--prewarm`): each bucket's first compile otherwise stalls a
        mid-epoch step for the full XLA compile (20-40 s per bucket over a
        remote-TPU transport). Runs each bucket's jitted step once on
        zero-filled dummy inputs with a SACRIFICIAL copy of the state (the
        step donates its state argument), so the real state and the jit
        dispatch caches are both left in exactly the production call path.
        """
        # ONE jitted copy, then chain: bucket i's output state (same avals
        # and shardings as production) is bucket i+1's sacrificial input.
        # Per-leaf eager copies would cost one dispatch per leaf per
        # bucket.
        chief = jax.process_index() == 0
        sacrificial = jax.jit(lambda s: jax.tree.map(jnp.copy, s))(state)
        # timing here is the COMPILE stall being hidden, not device work —
        # the one legitimate per-call wall-clock: graftlint: off=per-call-timing
        for target in sizes:
            t0 = time.time()
            sacrificial, _ = call_bucket(sacrificial, target)
            jax.block_until_ready(jax.tree.leaves(sacrificial)[0])
            if chief:
                # host-visible time: dominated by the (synchronous) XLA
                # compile; on transports whose completion events resolve
                # early the dummy step's execution may land later
                print("%s: prewarmed bucket %d (compile+dispatch %.1fs)"
                      % (timestamp(), target, time.time() - t0), flush=True)

    if cache is not None:
        def get_step(target):
            if target not in steps:
                steps[target] = make_cached_device_train_step(
                    model, tx, cfg, mesh, target, cache, distill=distill)
            return steps[target]

        def runner(state, idx_batch, step_idx):
            step = get_step(pick_target(step_idx))
            with tracer.span("dispatch", step=step_idx):
                return step(state, base_key, np.int32(step_idx),
                            np.asarray(idx_batch, np.int32), *scale_args())

        runner.prewarm = lambda state: prewarm(
            state, lambda st, target: get_step(target)(
                st, base_key, np.int32(0),
                np.zeros((cfg.batch_size,), np.int32), *scale_args()))
        runner.steps = steps  # bucket -> jitted step (tests assert coverage)
        return runner

    def get_step(target):
        if target not in steps:
            steps[target] = make_device_train_step(model, tx, cfg, mesh,
                                                   target, distill=distill)
        return steps[target]

    def stage(batch):
        with tracer.span("h2d"):
            return shard_batch(
                mesh, (batch.image, batch.boxes, batch.labels, batch.valid))

    def runner(state, batch, step_idx):
        arrays = (batch.arrays if isinstance(batch, StagedBatch)
                  else stage(batch))
        images, boxes, labels, valid = arrays
        step = get_step(pick_target(step_idx))
        with tracer.span("dispatch", step=step_idx):
            return step(state, base_key, np.int32(step_idx), images, boxes,
                        labels, valid, *scale_args())

    def _dummy_call(st, target):
        canvas = cfg.multiscale[1]
        local_b = cfg.batch_size // jax.process_count()
        dummy = (np.zeros((local_b, canvas, canvas, 3), np.uint8),
                 np.zeros((local_b, cfg.max_boxes, 4), np.float32),
                 np.zeros((local_b, cfg.max_boxes), np.int32),
                 np.zeros((local_b, cfg.max_boxes), bool))
        images, boxes, labels, valid = shard_batch(mesh, dummy)
        return get_step(target)(st, base_key, np.int32(0), images, boxes,
                                labels, valid, *scale_args())

    runner.prewarm = lambda state: prewarm(state, _dummy_call)
    runner.steps = steps  # bucket -> jitted step (tests assert coverage)
    runner.stage = stage
    return runner


class FaultInjector:
    """Debug fault injection: raise ONE synthetic transient backend error
    at a given "EPOCH:ITER" (--fault-inject). The reference has no fault
    injection at all (SURVEY.md §5); this exists so the --auto-resume
    recovery path is testable without a real backend outage."""

    def __init__(self, spec: str = ""):
        if spec:
            parts = spec.split(":")
            if len(parts) != 2:
                raise ValueError(
                    "--fault-inject wants 'EPOCH:ITER', got %r" % spec)
            self.target = (int(parts[0]), int(parts[1]))
        else:
            self.target = None
        self.fired = False

    def maybe_fire(self, epoch: int, i: int) -> None:
        if self.target is not None and not self.fired \
                and (epoch, i) == self.target:
            self.fired = True
            raise InjectedBackendError(
                "injected backend fault at epoch %d iter %d (UNAVAILABLE)"
                % (epoch, i))


class SentinelMonitor:
    """Host half of the `--sentinel` self-healing loop (ISSUE 9).

    The jitted step already did the time-critical part (skip-step: a
    tripped step leaves the TrainState untouched); this monitor reads the
    sentinel scalars OFF the existing deferred loss fetch — so its
    decisions have the flush interval's latency, and cost zero extra D2H
    — and plays the two slower recovery cards:

    * **loss-scale backoff**: after a flush window containing skipped
      steps, the scale the runner feeds the step is multiplied by
      `cfg.sentinel_backoff` (floor 1/1024); each clean window doubles it
      back toward 1.0. The loss is scaled before backward and the grads
      unscaled after, so a transient bf16 overflow stops tripping without
      changing the converged optimum.
    * **rollback escalation**: `cfg.sentinel_divergence` CONSECUTIVE
      skipped steps mean the blowup is not transient — skipping forever
      would silently stall training — so observe() raises
      `TrainingDivergenceError` and train() restores the last good
      checkpoint (budget: `cfg.sentinel_rollbacks`).

    Every decision is flight-recorder evidence (`recover:skip-step` /
    `recover:backoff` / `recover:rollback` events) for obs_report's
    Faults section. No reference analogue (the reference has no numeric
    failure handling at all, ref train.py:86-162)."""

    MIN_SCALE = 1.0 / 1024.0

    def __init__(self, cfg: Config, tracer=None):
        from .obs.metrics import default_registry
        self.cfg = cfg
        self._tracer = tracer
        self.scale = 1.0
        self.skipped = 0
        self.consecutive_bad = 0
        self.rollbacks = 0
        # live metrics plane (ISSUE 10): the sentinel's decisions ride the
        # train.* namespace next to the loop histograms — host counters
        # over already-fetched scalars, zero extra D2H
        mreg = default_registry()
        self._m_skipped = mreg.counter("train.skipped_steps")
        self._m_rollbacks = mreg.counter("train.rollbacks")
        self._mg_scale = mreg.gauge("train.loss_scale")

    def scale_value(self) -> float:
        """The runner's per-call loss-scale source (make_step_runner)."""
        return self.scale

    def observe(self, fetched) -> None:
        """Consume one flush window of ALREADY-FETCHED loss dicts (host
        scalars — never device arrays: this must not hide a D2H). Raises
        TrainingDivergenceError on sustained divergence."""
        window_bad = 0
        diverged = False
        for rec in fetched:
            if float(rec.get("sentinel_bad", 0.0)) > 0.5:
                window_bad += 1
                self.skipped += 1
                self.consecutive_bad += 1
                if self.consecutive_bad >= self.cfg.sentinel_divergence:
                    diverged = True
            else:
                self.consecutive_bad = 0
        if window_bad:
            self._m_skipped.inc(window_bad)
            if self._tracer is not None:
                self._tracer.event("recover:skip-step", n=window_bad,
                                   total=self.skipped)
            new_scale = max(self.MIN_SCALE,
                            self.scale * self.cfg.sentinel_backoff)
            if new_scale != self.scale:
                if self._tracer is not None:
                    self._tracer.event("recover:backoff", scale=new_scale)
                self.scale = new_scale
        elif self.scale < 1.0:
            self.scale = min(1.0, self.scale * 2.0)
        self._mg_scale.set(self.scale)
        if diverged:
            raise TrainingDivergenceError(
                "sentinel: %d consecutive skipped steps (>= "
                "--sentinel-divergence %d) — sustained numeric divergence"
                % (self.consecutive_bad, self.cfg.sentinel_divergence))

    def note_rollback(self) -> None:
        """A checkpoint rollback happened: the restored state predates the
        blowup, so the backoff (aimed at the diverged trajectory) resets
        with it."""
        self.rollbacks += 1
        self._m_rollbacks.inc()
        self.consecutive_bad = 0
        self.scale = 1.0
        self._mg_scale.set(self.scale)


# --async-eval worker (ISSUE 11): a fresh interpreter pinned to the CPU
# platform BEFORE first backend use, so the evaluation never contends with
# the training devices (one process per chip: the trainer holds them, and
# a child that reached for the chip would fail or hang). The spec file
# carries the full
# eval Config; scores land next to it as scores.json (atomic write).
_ASYNC_EVAL_SRC = (
    "import json, os, sys\n"
    "sys.path.insert(0, sys.argv[2])\n"
    "import jax\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "from real_time_helmet_detection_tpu.config import Config\n"
    "from real_time_helmet_detection_tpu.evaluate import evaluate\n"
    "from real_time_helmet_detection_tpu.utils import save_json\n"
    "with open(sys.argv[1]) as f:\n"
    "    spec = json.load(f)\n"
    "cfg = Config(**spec['config'])\n"
    "m = evaluate(cfg)\n"
    "save_json(os.path.join(cfg.save_path, 'scores.json'),\n"
    "          {'epoch': spec['epoch'], 'checkpoint': spec['checkpoint'],\n"
    "           'map': float(m['map']),\n"
    "           'ap': {k: float(v) for k, v in m.get('ap', {}).items()}})\n"
)


class AsyncEvaluator:
    """Host side of `--async-eval` (ISSUE 11): per-checkpoint evaluation
    OFF the training devices, without stalling the train loop.

    At each checkpoint boundary the chief spawns ONE background subprocess
    (CPU platform — see `_ASYNC_EVAL_SRC`) evaluating the checkpoint just
    written; at most one eval is in flight, and a boundary arriving while
    one still runs is SKIPPED (counted) rather than queued — eval is a
    progress signal, not a training gate, and a queue would eventually
    stall the loop it exists not to stall. Results:
    `save_path/eval_async/e<N>/scores.json` (+ eval.log), reaped at the
    next boundary and awaited (bounded) at the end of training. An eval
    racing `--keep-ckpt` retention may lose its checkpoint mid-restore;
    that surfaces as ok=False for that epoch, never as a training failure.
    No reference analogue (train and eval are separate invocations there,
    ref main.py:9-17)."""

    FINALIZE_TIMEOUT_S = 900.0

    def __init__(self, cfg: Config, tracer=None):
        self.cfg = cfg
        self._tracer = tracer
        self._proc = None
        self._current = None        # (epoch, outdir)
        self._log_f = None
        self.completed: list = []   # [{"epoch", "ok", "map"}]
        self.skipped = 0

    # -- lifecycle ---------------------------------------------------------

    def _eval_config(self, ckpt_path: str, outdir: str) -> dict:
        import dataclasses
        d = dataclasses.asdict(self.cfg)
        d.update(train_flag=False, export_flag=False, model_load=ckpt_path,
                 save_path=outdir, platform="cpu", world_size=1, rank=0,
                 num_devices=0, device_prefetch=0, loader="thread",
                 device_augment=False, cache_device=False, async_eval=False,
                 async_ckpt=False, auto_resume=0, sentinel=False,
                 grad_accum=1, profile=False, summary=False, span_log="",
                 fault_inject="",
                 imsize=self.cfg.imsize or self.cfg.multiscale[1],
                 num_workers=min(2, max(1, self.cfg.num_workers)))
        return d

    def submit(self, epoch: int, ckpt_path: str) -> bool:
        """Launch an eval of `ckpt_path`; False (and counted) when one is
        already in flight. Never blocks on device or eval work."""
        self.poll()
        if self._proc is not None:
            self.skipped += 1
            print("%s: --async-eval: epoch %d eval still running; "
                  "skipping the epoch %d boundary (%d skipped so far)"
                  % (timestamp(), self._current[0], epoch, self.skipped),
                  flush=True)
            return False
        outdir = os.path.join(self.cfg.save_path, "eval_async",
                              "e%d" % epoch)
        os.makedirs(outdir, exist_ok=True)
        spec_path = os.path.join(outdir, "spec.json")
        save_json(spec_path, {"epoch": epoch, "checkpoint": ckpt_path,
                              "config": self._eval_config(ckpt_path,
                                                          outdir)})
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {k: v for k, v in os.environ.items()
               if k not in (HEARTBEAT_ENV, "TPU_QUEUE_STATUS")}
        # the eval must never beat the TRAIN job's heartbeat (it would
        # mask a hung trainer) nor write its status file
        self._log_f = open(os.path.join(outdir, "eval.log"), "ab")
        self._proc = subprocess.Popen(
            [sys.executable, "-c", _ASYNC_EVAL_SRC, spec_path, repo],
            stdout=self._log_f, stderr=subprocess.STDOUT, env=env)
        self._current = (epoch, outdir)
        if self._tracer is not None:
            self._tracer.event("eval-async:submit", epoch=epoch,
                               checkpoint=ckpt_path)
        print("%s: --async-eval: epoch %d eval -> %s (pid %d)"
              % (timestamp(), epoch, outdir, self._proc.pid), flush=True)
        return True

    def poll(self) -> None:
        """Reap a finished eval (non-blocking); report its score."""
        if self._proc is None or self._proc.poll() is None:
            return
        epoch, outdir = self._current
        rc = self._proc.returncode
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None
        self._proc = None
        self._current = None
        scores_path = os.path.join(outdir, "scores.json")
        rec = {"epoch": epoch, "ok": False, "map": None}
        if rc == 0 and os.path.exists(scores_path):
            try:
                with open(scores_path) as f:
                    rec.update(ok=True, map=json.load(f).get("map"))
            except (OSError, json.JSONDecodeError):
                pass
        self.completed.append(rec)
        if self._tracer is not None:
            self._tracer.event("eval-async:done", epoch=epoch,
                               ok=rec["ok"], map=rec["map"])
        print("%s: --async-eval: epoch %d eval %s%s (see %s)"
              % (timestamp(), epoch,
                 "done, mAP %s" % rec["map"] if rec["ok"]
                 else "FAILED (rc %s)" % rc,
                 "" if rec["ok"] else " — training unaffected", outdir),
              flush=True)

    def finalize(self) -> None:
        """Await the in-flight eval (bounded) at the end of training."""
        if self._proc is not None:
            try:
                self._proc.wait(timeout=self.FINALIZE_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                print("%s: --async-eval: final eval still running after "
                      "%.0fs; killing" % (timestamp(),
                                          self.FINALIZE_TIMEOUT_S),
                      flush=True)
                self._proc.kill()
                self._proc.wait()
        self.poll()
        if self._log_f is not None:
            self._log_f.close()
            self._log_f = None


def _poison_batch(batch):
    """Apply a chaos `nan-batch` fault to a host batch (tests/chaos only;
    never on the production path). Poisons the first float field so the
    forward pass — and therefore the in-jit sentinel — sees it."""
    import dataclasses
    for field in ("image", "heatmap", "boxes"):
        arr = getattr(batch, field, None)
        if isinstance(arr, np.ndarray) and arr.dtype.kind == "f":
            return dataclasses.replace(
                batch, **{field: np.full_like(arr, np.nan)})
    return batch  # staged/uint8 wires: nothing poisonable host-side


def train_epoch(cfg: Config, epoch: int, loader: BatchLoader, step_runner,
                state: TrainState, mesh, loss_log: LossLog,
                is_chief: bool = True, snapshot_fn=None,
                profile_this_epoch: bool = False,
                epoch_base_step: int = 0, watchdog=None,
                injector: Optional[FaultInjector] = None,
                tracer=None, monitor: Optional[SentinelMonitor] = None,
                chaos=None, mwriter=None, slo=None) -> TrainState:
    """One epoch of the hot loop (≡ ref train.py:86-162 `train_step`).

    `tracer` (obs/spans.py; default the process's ring alone): the loop's
    phases land in the flight recorder — `loader-wait` (host batch
    production), `step` (async dispatch + any un-hidden device wait) and
    `fetch` (the deferred loss flush, i.e. the real completion barrier);
    the step runner adds `h2d` and `dispatch` from inside — so a slow
    epoch is attributable after the fact instead of folklore.

    `monitor` (`--sentinel`): consumes each flush window's fetched
    sentinel scalars (same D2H as the losses) for skip accounting,
    loss-scale backoff and the divergence escalation. `chaos`
    (runtime.faults.ChaosInjector, tests only): fires the `train:batch`
    site per iteration — a `nan-batch` event poisons the host batch so
    the in-jit sentinel path is exercisable deterministically.

    `mwriter`/`slo` (ISSUE 10): the loop's host-side walls feed the
    train.* histograms of the live metrics plane and the SLO drift
    watchdog (step-time/loss z-scores -> `alert:*` events); `mwriter`
    gets its periodic flush point at the loss-flush barrier. All of it
    is host bookkeeping over ALREADY-measured values — the traced
    programs and the single-fetch D2H contract are untouched."""
    from .obs.metrics import default_registry
    from .obs.spans import default_tracer
    if tracer is None:
        tracer = default_tracer()  # the ring alone, no file
    mreg = default_registry()
    mh_step = mreg.histogram("train.step_ms")
    mh_wait = mreg.histogram("train.loader_wait_ms")
    mh_fetch = mreg.histogram("train.fetch_ms")
    mc_steps = mreg.counter("train.steps")
    # segment meters are host-visible averages made honest by the
    # periodic flush barrier (see `pending` below), not per-call device
    # timing — bench.py owns that: graftlint: off=per-call-timing
    meters = {k: AverageMeter() for k in ("data", "step")}
    loader.set_epoch(epoch)
    profiling = False
    # Losses stay on device between print intervals: a per-step device_get
    # would force a host<->device sync every step, breaking async dispatch.
    # The
    # pending scalars are fetched in one call every print_interval steps on
    # EVERY host — the periodic sync both bounds the in-flight dispatch
    # queue (each queued step pins its batch buffers in device memory) and
    # keeps per-interval AVERAGE step times honest: the flush runs inside
    # the timed window, so its iteration absorbs the device wait for the
    # whole interval.
    pending: list = []

    def flush_losses():
        if not pending:
            return
        # ONE device_get for the whole interval — the span around it is
        # the loop's true completion barrier (any device time the host
        # work failed to hide shows up here, not in `step`)
        with tracer.span("fetch", steps=len(pending)) as sp_fetch:
            fetched_all = jax.device_get(pending)
        mh_fetch.observe(sp_fetch.dur_s * 1e3)
        for fetched in fetched_all:
            loss_log.append(fetched)
        if slo is not None:
            # loss drift rides the already-fetched window (zero extra D2H)
            for fetched in fetched_all:
                slo.observe("train.loss", float(fetched.get("total", 0.0)))
        pending.clear()
        if mwriter is not None:
            mwriter.maybe_flush()  # the periodic export point: the flush
            # barrier is where the host is synced anyway
        if monitor is not None:
            # the sentinel scalars rode the SAME fetch; observe() may
            # raise TrainingDivergenceError -> train()'s rollback branch
            monitor.observe(fetched_all)

    iterator = loader
    if cfg.device_prefetch > 0 and hasattr(step_runner, "stage"):
        # H2D overlap: the prefetcher dispatches the sharded device_put of
        # the next `device_prefetch` batches while the current step runs.
        # The cached input path has no stage (its wire is B int32 indices).
        from .data import DevicePrefetcher
        # (`stage` records its own `h2d` span: one record a transfer)
        iterator = DevicePrefetcher(loader, step_runner.stage,
                                    depth=cfg.device_prefetch)
    from .data import StagedBatch
    tic = time.time()
    for i, batch in enumerate(iterator):
        if injector is not None:
            injector.maybe_fire(epoch, i)
        if chaos is not None:
            rk = chaos.fire("train:rank", epoch=epoch, it=i)
            if rk is not None and rk.kind == "worker-death":
                # a training RANK died (ISSUE 11 chaos site): in a real
                # multi-process run the survivors would hang at the next
                # collective — surface the documented transient signature
                # instead, so the shared classifier (runtime/errors.py)
                # sends the job supervisor down its requeue path rather
                # than a hung rendezvous eating the heartbeat deadline
                raise InjectedBackendError(
                    "UNAVAILABLE: injected worker death at epoch %d iter "
                    "%d — a training rank is gone; restart/requeue the "
                    "whole multi-process job" % (epoch, i))
            ev = chaos.fire("train:batch", epoch=epoch, it=i)
            if ev is not None and ev.kind == "nan-batch" \
                    and not isinstance(batch, StagedBatch):
                batch = _poison_batch(batch)
        data_t = time.time() - tic
        meters["data"].update(data_t)
        mh_wait.observe(data_t * 1e3)
        sctx = None
        if tracer.enabled:
            # per-step trace context (ISSUE 14): the trace id derives
            # from (run, epoch, step) alone, so every rank's span log
            # contributes to the SAME per-step trace with zero
            # coordination — obs/traceview.py joins them by rank tag
            from .obs.trace import step_context
            sctx = step_context(epoch_base_step + i, epoch=epoch,
                                rank=int(getattr(cfg, "rank", 0) or 0))
        tracer.record("loader-wait", data_t,
                      ctx=sctx.child() if sctx else None, epoch=epoch, it=i)

        if profile_this_epoch and is_chief and i == 2:
            # steps 0-1 include compiles; trace a few steady-state steps
            jax.profiler.start_trace(os.path.join(cfg.save_path, "trace"))
            profiling = True

        state, losses = step_runner(state, batch, epoch_base_step + i)
        pending.append(losses)
        if i % cfg.print_interval == 0:
            flush_losses()
            # beat at the flush barrier only: dispatch is async, so a
            # per-dispatch beat would overstate progress (and delay
            # detection) by up to print_interval queued-but-unexecuted
            # steps; the flush is where the host truly observes completion
            if watchdog is not None:
                watchdog.beat("epoch %d iter %d (flushed)" % (epoch, i))
        step_t = time.time() - tic - data_t
        meters["step"].update(step_t)
        mh_step.observe(step_t * 1e3)
        mc_steps.inc()
        if slo is not None:
            # drift on the same host wall the meter records; an alert is
            # an alert:train-step-drift event in the span log
            slo.observe("train.step_ms", step_t * 1e3)
        # async-dispatch time (+ the flush barrier's device wait when
        # this was a flush iteration) — same semantics as the meter
        tracer.record("step", step_t, ctx=sctx.child() if sctx else None,
                      epoch=epoch, it=i)

        if profiling and i >= 7:
            flush_losses()  # completion barrier: the trace must contain
            jax.profiler.stop_trace()  # the profiled steps, not their queue
            profiling = False
            print("%s: profiler trace -> %s" % (
                timestamp(), os.path.join(cfg.save_path, "trace")), flush=True)

        if is_chief and (i % cfg.print_interval == 0):
            print("%s: epoch %d iter %d/%d, %s | data %.3fs step %.3fs"
                  % (timestamp(), epoch, i, len(loader),
                     loss_log.get_log(length=cfg.print_interval),
                     meters["data"].avg, meters["step"].avg), flush=True)
            snapshot_dir = os.path.join(cfg.save_path, "training_log")
            # host-augment path only: raw batches carry no GT maps and
            # un-normalized images
            host = batch.host if isinstance(batch, StagedBatch) else batch
            if os.path.isdir(snapshot_dir) and not cfg.device_augment:
                blend_heatmap(host.image, host.heatmap, cfg.pretrained).save(
                    os.path.join(snapshot_dir, f"e{epoch}_i{i}_gt.png"))
                # single-host only: with multiple processes the snapshot
                # output spans non-addressable devices (device_get would
                # raise) and the global batch != the local batch.image
                if snapshot_fn is not None and jax.process_count() == 1:
                    pred = jax.device_get(snapshot_fn(
                        state.params, state.batch_stats, host.image))
                    blend_heatmap(host.image, pred, cfg.pretrained).save(
                        os.path.join(snapshot_dir, f"e{epoch}_i{i}_pred.png"))
        tic = time.time()
    flush_losses()
    if profiling:  # short epoch: close the trace cleanly
        jax.profiler.stop_trace()
    return state


def train(cfg: Config, chaos=None) -> TrainState:
    """Full training driver (≡ ref train.py:23-83
    `distributed_device_train` + `distributed_worker`).

    `chaos` (runtime.faults.ChaosInjector; tests/chaos suite only): fault
    events replayed into the epoch loop so the `--sentinel` recovery
    paths are exercised deterministically on CPU."""
    init_distributed(cfg)
    ndev = cfg.num_devices or len(jax.devices())
    if ndev % cfg.spatial:
        raise ValueError("--spatial %d must divide the device count %d"
                         % (cfg.spatial, ndev))
    # Only the data axis shards the batch; spatial shards H. Under
    # --grad-accum the sharded unit is the MICRO-batch (the in-jit scan
    # reshapes (B, ...) -> (k, B/k, ...)), so divisibility is against B/k.
    micro_batch = cfg.batch_size // max(1, cfg.grad_accum)
    data = ndev // cfg.spatial
    if jax.process_count() > 1:
        # Multi-host: shrinking the mesh would drop whole hosts' devices
        # while those processes still contribute local shards — fail loudly.
        if micro_batch % data:
            raise ValueError(
                "multi-host run: the micro-batch %d (--batch-size %d / "
                "--grad-accum %d) must be divisible by the data mesh axis "
                "%d (devices %d / spatial %d)"
                % (micro_batch, cfg.batch_size, cfg.grad_accum, data, ndev,
                   cfg.spatial))
    else:
        # Single-host: clamp + largest batch-dividing data axis (shared
        # helper with the eval driver's mesh sizing)
        from .parallel import fit_data_mesh
        ndev = fit_data_mesh(micro_batch, cfg.num_devices, cfg.spatial)
    mesh = make_mesh(ndev, spatial=cfg.spatial)
    is_chief = jax.process_index() == 0

    if cfg.async_eval:
        if cfg.async_ckpt:
            # the eval subprocess restores the checkpoint the boundary
            # just "wrote" — under async saves it may not be durable yet
            raise ValueError("--async-eval requires synchronous "
                             "checkpoints (drop --async-ckpt)")
        if not cfg.data or not os.path.isdir(str(cfg.data)):
            raise ValueError("--async-eval needs --data pointing at a "
                             "dataset root (the eval subprocess scores "
                             "the test split)")

    dataset, augmentor = load_dataset(cfg)
    if cfg.device_augment:
        # host does decode + deterministic canvas resize only; random
        # augmentation + GT encode run on-device inside the fused step
        from .data import TestAugmentor
        augmentor = TestAugmentor(imsize=cfg.multiscale[1])
    cache = None
    if cfg.cache_device:
        if not cfg.device_augment:
            raise ValueError("--cache-device requires --device-augment "
                             "(augmentation must run on-device; the cache "
                             "holds un-augmented canvases)")
        if jax.process_count() > 1:
            raise ValueError("--cache-device is single-host only (each "
                             "host would need its own dataset shard)")
        from .data import DeviceDatasetCache
        cache = DeviceDatasetCache(
            dataset, augmentor, batch_size=cfg.batch_size,
            max_boxes=cfg.max_boxes, shuffle=True, drop_last=True,
            seed=cfg.random_seed, num_workers=cfg.num_workers, mesh=mesh)
        loader = cache
    else:
        loader_cls = BatchLoader
        loader_extra = {}
        if cfg.loader == "process":
            # GIL-free host pipeline: spawned worker processes + shared-
            # memory batch transport (data/shm_pool.py); bit-identical to
            # the thread loader, with an automatic in-process fallback if
            # a worker dies. --sentinel additionally arms the poison-batch
            # quarantine: a produced batch carrying non-finite values is
            # dropped (and counted) instead of reaching the step.
            from .data import ProcessBatchLoader
            loader_cls = ProcessBatchLoader
            loader_extra = {"quarantine": cfg.sentinel}
        loader = loader_cls(
            dataset, augmentor,
            batch_size=cfg.batch_size // jax.process_count(),
            pretrained=cfg.pretrained, num_cls=cfg.num_cls,
            normalized_coord=cfg.normalized_coord,
            scale_factor=cfg.scale_factor,
            max_boxes=cfg.max_boxes, shuffle=True, drop_last=True,
            rank=jax.process_index(), world_size=jax.process_count(),
            seed=cfg.random_seed, num_workers=cfg.num_workers,
            raw=cfg.device_augment, **loader_extra)
    steps_per_epoch = max(1, len(loader))

    dtype = jnp.bfloat16 if cfg.amp else None
    model = build_model(cfg, dtype=dtype)
    tx = build_optimizer(cfg, steps_per_epoch)
    imsize = cfg.multiscale[1] if cfg.imsize is None else cfg.imsize
    state = create_train_state(model, cfg, jax.random.key(cfg.random_seed),
                               imsize, tx)
    loss_log = LossLog()
    start_epoch = cfg.start_epoch
    if cfg.model_load:
        state, ckpt_epoch, loss_log = load_checkpoint(
            resolve_model_load(cfg.model_load), state)
        start_epoch = cfg.start_epoch or (ckpt_epoch + 1)
        if is_chief:
            print("%s: resumed from %s (epoch %d)"
                  % (timestamp(), cfg.model_load, ckpt_epoch), flush=True)

    # Flight recorder (obs/): the in-memory ring is always on; a span log
    # is written as well when --span-log names a path (or $OBS_SPAN_LOG is
    # exported, e.g. by the job supervisor).
    from .obs.spans import maybe_tracer
    tracer = maybe_tracer(cfg.span_log or None)
    # --sentinel: the monitor is the host half of the self-healing loop;
    # the runner reads its loss scale per call
    monitor = SentinelMonitor(cfg) if cfg.sentinel else None
    distill = make_distiller(cfg)
    runner = make_step_runner(
        cfg, mesh, model, tx, cache=cache,
        sentinel_scale=monitor.scale_value if monitor else None,
        distill=distill, tracer=tracer)
    if cfg.prewarm:
        if hasattr(runner, "prewarm"):
            if is_chief:
                print("%s: prewarming %s multiscale buckets..."
                      % (timestamp(), "all" if cfg.multiscale_flag else "1"),
                      flush=True)
            runner.prewarm(state)
        elif is_chief:
            print("%s: --prewarm has no effect without --device-augment "
                  "(the host path has a single fixed-shape step)"
                  % timestamp(), flush=True)
    snapshot_fn = (make_snapshot_fn(model, cfg, mesh)
                   if is_chief and not cfg.device_augment else None)
    if is_chief:
        nparams = sum(x.size for x in jax.tree.leaves(state.params))
        print("%s: model built, %d params, mesh %s" % (
            timestamp(), nparams, dict(mesh.shape)), flush=True)
        if cfg.summary:
            # layer table (≡ reference torchsummary on rank 0, ref
            # train.py:50). nn.tabulate shape-infers via jax.eval_shape; a
            # HOST numpy input keeps the image tensor off the device (only
            # the tiny RNG key is device-side — tabulate requires a real
            # key).
            import flax.linen as nn
            print(nn.tabulate(
                model, jax.random.key(0), depth=2,
                compute_flops=False, compute_vjp_flops=False)(
                    np.zeros((1, imsize, imsize, 3), np.float32),
                    train=False), flush=True)

    if cfg.async_ckpt and jax.process_count() > 1:
        # the chief-only device-side snapshot + orbax save would touch
        # non-addressable devices / hang the multi-host save barrier
        raise ValueError("--async-ckpt is single-host only")
    if cfg.auto_resume and jax.process_count() > 1:
        # in-process recovery would need cross-host coordination (all
        # processes must restore the same checkpoint + re-rendezvous);
        # multi-host recovery = restart the job with --model-load
        raise ValueError("--auto-resume is single-host only")
    if cfg.auto_resume and cfg.async_ckpt:
        # recovery must restore a DURABLE checkpoint; an async save may
        # still be in flight (or half-written) at the moment of failure
        raise ValueError("--auto-resume requires synchronous checkpoints "
                         "(drop --async-ckpt)")
    # When running under scripts/tpu_queue.py the supervisor exports a
    # heartbeat path: the watchdog's beats double as the job's liveness
    # signal, so a wedged step trips the supervisor's kill-salvage too.
    # The recompile counter turns "why was this epoch slow" answerable
    # when a shape change silently retraced.
    if monitor is not None and tracer.enabled:
        monitor._tracer = tracer  # recover:* events join the span log
    recompiles = None
    if tracer.enabled:
        # rank tag on every record (ISSUE 14): N per-rank span logs join
        # into per-step traces (obs/traceview.py) — the tag is what maps
        # a slow span back to the rank that wrote it
        tracer.bind(rank=int(getattr(cfg, "rank", 0) or 0),
                    world=int(getattr(cfg, "world_size", 1) or 1))
    if tracer.enabled:
        from .obs.telemetry import install_recompile_counter
        recompiles = install_recompile_counter(tracer)
        if is_chief:
            print("%s: span log -> %s" % (timestamp(), tracer.path),
                  flush=True)
    # Live metrics plane + SLO watchdog (ISSUE 10): the loop's host-side
    # measurements (step/loader-wait/fetch walls, sentinel skips) feed
    # in-memory train.* metrics regardless — $OBS_METRICS only arms the
    # crash-safe periodic snapshot export, and the drift watchdog turns a
    # creeping step time or a wandering loss into `alert:*` span events.
    # Nothing here touches the jitted programs or adds a D2H (count-pinned
    # by tests/test_metrics_plane.py).
    from .obs.metrics import maybe_writer
    from .obs.slo import SloWatchdog, default_train_rules
    mwriter = maybe_writer()
    slo = SloWatchdog(default_train_rules(), tracer=tracer)
    if mwriter.enabled and is_chief:
        print("%s: metrics export -> %s" % (timestamp(), mwriter.path),
              flush=True)
    # --async-eval (ISSUE 11): chief-only background eval of each saved
    # checkpoint, off the training devices (CPU subprocess); the loop only
    # ever submit()s and poll()s — it never waits on eval work.
    evaluator = (AsyncEvaluator(cfg, tracer=tracer)
                 if cfg.async_eval and is_chief else None)
    watchdog = HangWatchdog(cfg.hang_warn_seconds,
                            beat_file=os.environ.get(HEARTBEAT_ENV))
    if hasattr(loader, "worker_status"):
        # the watchdog's stall warning names each loader worker's liveness
        # and heartbeat age, so an input-pipeline stall is attributable
        watchdog.set_status_fn(loader.worker_status)
    writer = CheckpointWriter(async_save=cfg.async_ckpt)
    injector = FaultInjector(cfg.fault_inject)
    epoch_flush = make_state_accum_flush(cfg, steps_per_epoch)
    resume_attempts = 0
    run_ckpts: list = []  # checkpoints written by THIS run, oldest first
    epoch = start_epoch
    try:
        while epoch < cfg.end_epoch:
            try:
                if tracer.enabled:
                    # per-epoch confounder sample: host load moves the
                    # host-side walls — cross-run deltas need this context
                    tracer.context(epoch=epoch)
                state = train_epoch(
                    cfg, epoch, loader, runner, state, mesh,
                    loss_log, is_chief, snapshot_fn,
                    profile_this_epoch=(cfg.profile and epoch == start_epoch),
                    epoch_base_step=epoch * steps_per_epoch,
                    watchdog=watchdog, injector=injector, tracer=tracer,
                    monitor=monitor, chaos=chaos, mwriter=mwriter,
                    slo=slo)
                if epoch_flush is not None and int(jax.device_get(
                        state.opt_state.mini_step)):
                    # partial accumulation window at epoch end: flush it
                    # (one scalar fetch + one dispatch per epoch, only
                    # when --sub-divisions > 1 and the epoch length does
                    # not divide k)
                    state = epoch_flush(state)
                # every N epochs + always the final one (a full-state save
                # costs a device_get of params+optimizer)
                if (epoch + 1) % max(1, cfg.ckpt_interval) == 0 \
                        or epoch == cfg.end_epoch - 1:
                    # warnings are suspended across the save on EVERY
                    # process: the chief's full-state device_get can
                    # legitimately take minutes, and non-chief processes
                    # spend that time blocked at the next collective —
                    # neither is a hang. (A non-chief resumes immediately
                    # and re-pauses nothing: its block inside the first
                    # post-boundary step cannot be distinguished from a
                    # wedge without cross-host signaling, so the boundary
                    # pause is the best local approximation.)
                    watchdog.pause("epoch %d boundary (checkpoint)" % epoch)
                    if is_chief:
                        with tracer.span("checkpoint", epoch=epoch):
                            path = writer.save(cfg.save_path, epoch, state,
                                               loss_log)
                        run_ckpts.append(path)
                        print("%s: epoch %d checkpoint -> %s"
                              % (timestamp(), epoch, path), flush=True)
                        if evaluator is not None:
                            # non-blocking: spawn (or skip, when one is
                            # still in flight) and return immediately
                            evaluator.submit(epoch, path)
                        # Retention applies to THIS run's checkpoints only.
                        # Async mode keeps one extra: the newest save may
                        # still be in flight (save() awaits only the
                        # PREVIOUS one), so the last durable checkpoint
                        # must survive until the next boundary.
                        n_keep = cfg.keep_ckpt + (1 if cfg.async_ckpt
                                                  else 0)
                        if cfg.keep_ckpt > 0 and len(run_ckpts) > n_keep:
                            import shutil
                            for old in run_ckpts[:-n_keep]:
                                try:
                                    shutil.rmtree(old)
                                    print("%s: retention: removed %s"
                                          % (timestamp(), old), flush=True)
                                except OSError as rm_err:
                                    print("%s: retention: could not remove "
                                          "%s: %s" % (timestamp(), old,
                                                      rm_err), flush=True)
                            del run_ckpts[:-n_keep]
                    watchdog.resume("epoch %d checkpoint done" % epoch)
            except TrainingDivergenceError as e:
                # Sentinel rollback (ISSUE 9): sustained numeric divergence
                # — the device is HEALTHY (no probe, no backoff, no cache
                # clear, runner/compiled steps stay valid); restore the
                # last good checkpoint and rerun from its epoch. The rerun
                # is deterministic (batch content is a pure function of
                # (seed, epoch, batch_idx)), so absent further faults it
                # matches a clean resume bit-for-bit (chaos-suite pinned).
                if not (monitor is not None and run_ckpts
                        and monitor.rollbacks < cfg.sentinel_rollbacks):
                    raise
                monitor.note_rollback()
                latest = run_ckpts[-1]
                state, ckpt_epoch, loss_log = load_checkpoint(latest, state)
                epoch = ckpt_epoch + 1
                tracer.event("recover:rollback", checkpoint=latest,
                             epoch=epoch, attempt=monitor.rollbacks)
                print("%s: sentinel divergence (%s); rollback %d/%d to %s "
                      "(epoch %d)"
                      % (timestamp(), str(e).splitlines()[0][:160],
                         monitor.rollbacks, cfg.sentinel_rollbacks, latest,
                         ckpt_epoch), flush=True)
                continue
            except Exception as e:  # noqa: BLE001 — filtered just below
                # Elastic recovery (--auto-resume N; the reference's only
                # recovery is a manual restart with --model-load, ref
                # train.py:190-199): on a TRANSIENT backend failure, back
                # off, restore the newest checkpoint, and continue the
                # epoch loop in-process. Anything non-transient (or beyond
                # the attempt budget) propagates.
                if not (cfg.auto_resume
                        and resume_attempts < cfg.auto_resume
                        and is_transient_backend_error(e)):
                    raise
                resume_attempts += 1
                wait = min(300.0, cfg.resume_backoff_s * resume_attempts)
                print("%s: transient backend failure in epoch %d (%s: %s); "
                      "recovery %d/%d in %.0fs"
                      % (timestamp(), epoch, type(e).__name__,
                         str(e).splitlines()[0][:200], resume_attempts,
                         cfg.auto_resume, wait), flush=True)
                watchdog.pause("auto-resume backoff")
                time.sleep(wait)
                # The probe below can hang on a wedged backend; rearm the
                # watchdog over it so the stall is diagnosable instead of
                # silent (r3 advisor finding).
                watchdog.resume("auto-resume device probe")
                # Re-stage device-resident context before restoring
                # (round-2 advisor finding: retrying with dead buffers
                # burns the whole attempt budget). Scope: in-process
                # recovery targets TRANSPORT-transient failures — the PJRT
                # client is cached per process and cannot be rebuilt here,
                # so if even a fresh tiny op fails the backend itself is
                # gone and the only recovery is a process restart with
                # --model-load; propagate instead of spinning.
                try:
                    # device_get of the RESULT: a real D2H fetch proves
                    # the backend executed something
                    float(jax.device_get(jnp.zeros(()) + 1.0))
                except Exception as probe_err:  # noqa: BLE001
                    raise RuntimeError(
                        "auto-resume aborted: device probe failed after "
                        "backoff (%s) — backend is dead, not transient; "
                        "restart the process with --model-load"
                        % str(probe_err).splitlines()[0][:200]) from e
                # drop compiled executables (they may pin buffers from the
                # failed step; they lazily re-JIT from the persistent
                # compile cache) and rebuild the runner so the device-held
                # RNG base key is re-staged
                jax.clear_caches()
                if cache is not None:
                    try:  # HBM canvases survive a transport blip...
                        int(jax.device_get(jnp.sum(cache.images[:1])))
                    except Exception:  # noqa: BLE001 — ...but not a wedge
                        print("%s: --cache-device HBM cache lost; "
                              "re-staging dataset" % timestamp(), flush=True)
                        cache = DeviceDatasetCache(
                            dataset, augmentor, batch_size=cfg.batch_size,
                            max_boxes=cfg.max_boxes, shuffle=True,
                            drop_last=True, seed=cfg.random_seed,
                            num_workers=cfg.num_workers, mesh=mesh)
                        loader = cache
                runner = make_step_runner(
                    cfg, mesh, model, tx, cache=cache,
                    sentinel_scale=monitor.scale_value if monitor else None,
                    distill=distill, tracer=tracer)
                # only checkpoints written by THIS run are trusted: a
                # reused save_path can hold a previous run's (possibly
                # later-epoch) checkpoints, which would silently replace
                # this run's weights or end training early
                if run_ckpts:
                    latest = run_ckpts[-1]
                    state, ckpt_epoch, loss_log = load_checkpoint(latest,
                                                                  state)
                    epoch = ckpt_epoch + 1
                    print("%s: auto-resumed from %s (epoch %d)"
                          % (timestamp(), latest, ckpt_epoch), flush=True)
                elif cfg.model_load:
                    # failed before this run's first save: fall back to the
                    # weights the run STARTED from, exactly as at entry
                    state, ckpt_epoch, loss_log = load_checkpoint(
                        cfg.model_load, state)
                    epoch = cfg.start_epoch or (ckpt_epoch + 1)
                    print("%s: no checkpoint from this run yet; "
                          "auto-resumed from --model-load %s (epoch %d)"
                          % (timestamp(), cfg.model_load, epoch), flush=True)
                else:
                    # fresh run, failed before the first save: re-init
                    state = create_train_state(
                        model, cfg, jax.random.key(cfg.random_seed), imsize,
                        tx)
                    loss_log = LossLog()
                    epoch = start_epoch
                    print("%s: no checkpoint yet; auto-restarting from "
                          "epoch %d" % (timestamp(), epoch), flush=True)
                watchdog.resume("auto-resume restored")
                continue
            epoch += 1
    finally:
        watchdog.pause("finalizing checkpoints")
        writer.finalize()
        if evaluator is not None:
            evaluator.finalize()  # bounded wait on the in-flight eval
        watchdog.stop()
        if hasattr(loader, "quarantined"):
            # the SHM loader's poison-batch quarantine count (ISSUE 9)
            # lands on the metrics plane next to the sentinel counters
            from .obs.metrics import default_registry
            default_registry().gauge("train.quarantined_batches").set(
                loader.quarantined)
        if hasattr(loader, "close"):
            loader.close()  # reap workers, unlink shared-memory slots
        if tracer.enabled and recompiles is not None:
            tracer.event("recompile-total", count=recompiles.count,
                         total_s=round(recompiles.total_s, 3))
        mwriter.close()  # final metrics snapshot (no-op unless exporting)
        tracer.close()
    return state
