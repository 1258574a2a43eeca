"""What the two serving drivers share: the engine built as `evaluate()`
builds it (uint8 wire, `normalize` inside the predict program), the frame
pool, per-request stamps taken by the benchmark's own clock, the warm-up of
every bucket, and the comparison of what the engine answered with the plain
reference.

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


class ServeCell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p = ctx.traffic
        # one entry per request, appended by the one thread that submits;
        # `done` is stamped by the engine's completion callback
        self.due, self.sub, self.done, self.futs = [], [], [], []

    def setup(self):
        from real_time_helmet_detection_tpu.config import Config
        from real_time_helmet_detection_tpu.models import build_model
        from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
        from real_time_helmet_detection_tpu.predict import make_predict_fn
        from real_time_helmet_detection_tpu.serving import ServingEngine
        ctx, eng = self.ctx, self.p["engine"]
        cfg = Config(**ctx.program_fields,
                     serve_buckets=list(eng["buckets"]))
        self.imsize = int(cfg.imsize)
        model = build_model(cfg, dtype=jnp.bfloat16 if cfg.amp else None)
        self.spec = ref.param_spec(ctx.config)
        wts.check_tree(jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 64, 64, 3)), train=False)),
            self.spec)
        self.frames = traffic.frame_pool(ctx.seed, int(self.p["pool_frames"]),
                                         self.imsize)
        variables = wts.to_program_tree(self.weights(), self.spec)
        predict = make_predict_fn(model, cfg, normalize=cfg.pretrained)
        self.registry = MetricsRegistry()
        self.engine = ServingEngine(
            predict, variables, (self.imsize, self.imsize, 3), np.uint8,
            buckets=tuple(eng["buckets"]),
            max_wait_ms=float(eng["max_wait_ms"]), depth=int(eng["depth"]),
            queue_capacity=int(eng["queue"]), metrics=self.registry,
            **({"tracer": ctx.engine_tracer} if ctx.engine_tracer else {}))
        if ctx.sabotage is not None:
            ctx.sabotage(self)
        # warm every bucket: a full batch of each, so that the window meets
        # no first execution
        for b in sorted(self.engine.buckets):
            for f in [self.engine.submit(self.frames[i % len(self.frames)])
                      for i in range(b)]:
                f.result(timeout=600)

    def weights(self):
        """From the seed alone: the draw, then running statistics from the
        first frames of the pool (the same call gives the same arrays)."""
        return wts.with_running_statistics(
            self.ctx.config, wts.make_weights(self.spec, self.ctx.seed),
            self.frames[:int(self.p["stats_frames"])])

    # ---- requests ----------------------------------------------------------

    def submit(self, due: float, block: bool):
        i = len(self.futs)
        fut = self.engine.submit(self.frames[i % len(self.frames)],
                                 block=block)
        self.due.append(due)
        self.sub.append(time.monotonic())
        self.done.append(None)
        self.futs.append(fut)
        fut.add_done_callback(lambda f, i=i: self._stamp(i))
        return fut

    def _stamp(self, i):
        self.done[i] = time.monotonic()

    def counters(self):
        return {name: self.registry.counter("serve." + name).value
                for name in ("submitted", "completed", "batches_total",
                             "batch_slots", "padded_slots",
                             "shed_queue_full", "shed_deadline", "retried",
                             "failed_batches")}

    def wait_all(self, deadline_s: float):
        """Wait for every submitted request, at most until `deadline_s`
        (monotonic). Returns the indices that failed or never came."""
        missing = []
        for i, f in enumerate(self.futs):
            try:
                f.result(timeout=max(0.0, deadline_s - time.monotonic()))
            except Exception:  # noqa: BLE001 - shed, failed or still pending
                missing.append(i)
        return missing

    def free(self):
        if self.engine is not None:
            self.engine.close()
        self.engine = None

    # ---- the comparison (after the window, the engine closed) --------------

    def sample(self, first: int):
        """Indices of the finished requests compared: `sample` of them drawn
        from the seed among those submitted from `first` on."""
        ok = [i for i in range(first, len(self.futs))
              if self.futs[i].done() and self.futs[i].exception() is None]
        rng = np.random.default_rng([int(self.ctx.seed), 5])
        k = min(int(self.p["sample"]), len(ok))
        return sorted(rng.choice(ok, size=k, replace=False).tolist())

    def reference_maps(self, picks, quant="f32", block=8):
        weights = self.weights()
        dense = jax.jit(lambda w, x: ref.dense_maps(self.ctx.config, w, x,
                                                    quant))
        maps = []
        for at in range(0, len(picks), block):
            part = picks[at:at + block]
            x = np.zeros((block, self.imsize, self.imsize, 3), np.uint8)
            for j, i in enumerate(part):
                x[j] = self.frames[i % len(self.frames)]
            # in blocks so that the float32 reference fits; after the window,
            # so there is nothing to pipeline the fetch with
            out = jax.device_get(  # graftlint: off=device-get-in-loop
                dense(weights, jnp.asarray(x)))
            maps += [{k: v[j] for k, v in out.items()}
                     for j in range(len(part))]
        return maps

    def check(self):
        picks = self.sample(self.first)
        served = [self.futs[i].result() for i in picks]
        numbers = compare.serve_numbers(self.ctx.config, served,
                                        self.reference_maps(picks))
        return numbers, (0 if picks else 1)
