"""Driver `train`: the host-path step runner `train_epoch` calls each step
(`train.make_step_runner`), on a one-device mesh, state donated.

Set-up builds ONE object — the runner with its compiled step and its state —
drives it from the seed's weights through `check_steps` steps of the window's
own call and feed (that is also the warm-up: the first call compiles or loads
the program), keeps what the comparison needs (each loss, the first gradient's
norms as Adam's first moment holds them, the norms of the parameters' change),
and hands the same runner and state to the window.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import compare, traffic, weights as wts
from ..reference import model as ref

ADAM_B1 = 0.9


def _norms(tree_flat):
    return {k: float(v) for k, v in jax.device_get(jax.jit(
        lambda t: {k: jnp.linalg.norm(x.astype(jnp.float32).ravel())
                   for k, x in t.items()})(tree_flat)).items()}


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.traffic
        self.batch = int(self.p["batch"])

    # ---- set-up ------------------------------------------------------------

    def setup(self):
        from real_time_helmet_detection_tpu.config import Config
        from real_time_helmet_detection_tpu.data import StagedBatch
        from real_time_helmet_detection_tpu.models import build_model
        from real_time_helmet_detection_tpu.optim import build_optimizer
        from real_time_helmet_detection_tpu.parallel import (make_mesh,
                                                             replicated)
        from real_time_helmet_detection_tpu.train import (TrainState,
                                                          make_step_runner)
        ctx, p = self.ctx, self.p
        self.StagedBatch = StagedBatch
        cfg = Config(**ctx.program_fields, batch_size=self.batch,
                     train_flag=True)
        self.imsize = int(cfg.imsize)
        model = build_model(cfg, dtype=jnp.bfloat16 if cfg.amp else None)
        # an epoch of 10^6 steps: no window reaches a learning-rate milestone
        tx = build_optimizer(cfg, 10 ** 6)
        mesh = make_mesh(1)
        self.spec = ref.param_spec(ctx.config)
        wts.check_tree(jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 64, 64, 3)), train=False)),
            self.spec)
        tree = wts.to_program_tree(wts.make_weights(self.spec, ctx.seed),
                                   self.spec)
        start = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(tree["params"])
        state = TrainState(step=jnp.zeros((), jnp.int32),
                           params=tree["params"],
                           batch_stats=tree["batch_stats"],
                           opt_state=jax.jit(tx.init)(tree["params"]))
        # on the mesh as the step leaves it, so that the first call and
        # every later one are the same program (handed a fresh single-device
        # state, the program's jit compiles its step twice: PERF.md section 7)
        state = jax.device_put(state, replicated(mesh))
        self.runner = make_step_runner(cfg, mesh, model, tx)
        self.pool = traffic.train_batches(
            ctx.seed, int(p["pool_batches"]), self.batch, self.imsize,
            int(cfg.num_cls), float(p["pos_rate"]))
        if ctx.sabotage is not None:
            ctx.sabotage(self)
        # the first steps, through the window's own call and feed
        self.steps = 0
        got = {"losses": []}
        for _ in range(int(p["check_steps"])):
            state, losses = self._step(state)
            got["losses"].append(float(losses["total"]))
            if self.steps == 1:
                mu = wts.flatten_tree(state.opt_state[0].mu)
                got["grad_norms"] = {k: v / (1.0 - ADAM_B1)
                                     for k, v in _norms(mu).items()}
        now, was = wts.flatten_tree(state.params), wts.flatten_tree(start)
        got["change_norms"] = _norms({k: now[k] - was[k] for k in now})
        del start, now, was
        self.got, self.state = got, state

    def _step(self, state):
        ctx = self.ctx
        batch = self.pool[self.steps % len(self.pool)]
        with ctx.span("bench:stage"):
            staged = self.runner.stage(batch)
        with ctx.span("bench:step"):
            state, losses = self.runner(
                state, self.StagedBatch(arrays=staged, host=None), self.steps)
        self.steps += 1
        return state, losses

    # ---- the window --------------------------------------------------------

    def run(self, seconds: float):
        ctx, every = self.ctx, int(self.p["fetch_every"])
        state, first = self.state, self.steps
        self.state = None
        bad = 0
        # the window's own clock around all its steps, closed by a fetch:
        # graftlint: off=per-call-timing
        t0 = time.monotonic()
        with ctx.span("bench:window"):
            while True:
                state, losses = self._step(state)
                if (self.steps - first) % every == 0:
                    # the loss fetch of --print-interval: the only thing
                    # that keeps the host from running unboundedly ahead,
                    # and the proof that every step up to here has finished
                    with ctx.span("bench:fetch"):
                        loss = float(losses["total"])
                    bad += not np.isfinite(loss)
                    if time.monotonic() - t0 >= seconds:
                        break
            jax.block_until_ready(state.step)
        window = time.monotonic() - t0
        steps = self.steps - first
        self.state = state
        return {"window_s": window, "t0": t0, "attempted": steps, "failed": int(bad),
                "steps": steps, "images": steps * self.batch,
                "e2e": {"train_img_per_s": steps * self.batch / window}}

    def free(self):
        self.state = self.runner = None

    # ---- the comparison (after the window, the program's state freed) ------

    def reference(self, quant="f32", rows=None):
        """The reference's side of the comparison; with `quant`/`rows` the
        control or a planted fault in the program's place."""
        ctx, n = self.ctx, int(self.p["check_steps"])
        weights = wts.make_weights(self.spec, ctx.seed)
        batches = [tuple(jnp.asarray(a) for a in (
            b.image, b.heatmap, b.offset, b.wh, b.mask))
            for b in (self.pool[i % len(self.pool)] for i in range(n))]
        cfg = dict(ctx.config, lr=ctx.program_fields.get("lr", 5e-4))
        losses, grads, change = ref.train_steps(cfg, weights, batches,
                                                self.spec, quant, rows)
        return {"losses": [float(x) for x in losses],
                "grad_norms": _norms(grads), "change_norms": _norms(change)}

    def check(self):
        return compare.train_numbers(self.got, self.reference()), 0
