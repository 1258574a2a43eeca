"""Driver `generate_latent`: the generate program of a latent-attention
decoder whose mapping holds no indexer, window or gate (the plain reference
`reference/mla_moe_decoder.py`), under `serve_backlog`'s closed backlog. It is
`generate_gqa`'s cell (the producer, the window arithmetic, `sampled()`, and
`counters()`, which waits until every delivered answer is counted) over
another reference, another check and the counters this program feeds besides:
group hits and slots, and the q blocks the fused attention kernel ran.

The program is reached the normal way only: `Config(family=...,
decoder={...})` -> `build_model` -> `make_generate_fn` -> `ServingEngine`,
its counters fed by the engine's `row_counters`. `faults` (empty in every
benchmark run) is the check module's and the tests': the model rebuilt with
a fault planted by name (`benchmark/latent_check.py`).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import latent_check
from ..reference import mla_moe_decoder as ref
from . import generate_gqa
from .generate_backlog import prompt_pool

# beside `generate_gqa.GEN_COUNTERS` (whose window counts stay 0 here)
MORE_COUNTERS = ("gen.group_hits", "gen.group_slots", "gen.q_blocks_run",
                 "gen.q_blocks_total", "gen.q_blocks_fused")


class Cell(generate_gqa.Cell):
    def setup(self):
        from real_time_helmet_detection_tpu.config import Config
        from real_time_helmet_detection_tpu.models import build_model
        from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
        from real_time_helmet_detection_tpu.predict import (
            generation_counters, make_generate_fn)
        from real_time_helmet_detection_tpu.serving import ServingEngine
        ctx, p, eng = self.ctx, self.p, self.p["engine"]
        fields = dict(ctx.program_fields)
        cfg = Config(family=fields.pop("family"), decoder=fields,
                     serve_buckets=list(eng["buckets"]))
        model = build_model(cfg)
        if self.faults:
            model = model.clone(faults=frozenset(self.faults))
        ref.check_tree(jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))),
            ref.param_spec(ctx.config))
        self.p_max, self.new_tokens = int(p["p_max"]), int(p["new_tokens"])
        self.frames = prompt_pool(
            ctx.seed, int(p["pool_prompts"]), int(p["prompt_min"]),
            int(p["prompt_max"]), self.p_max, int(ctx.config["vocab_size"]))
        self.registry = MetricsRegistry()
        self.engine = ServingEngine(
            make_generate_fn(model, cfg, self.new_tokens), self.weights(),
            (self.p_max + 1,), np.int32, buckets=tuple(eng["buckets"]),
            max_wait_ms=float(eng["max_wait_ms"]), depth=int(eng["depth"]),
            queue_capacity=int(eng["queue"]), metrics=self.registry,
            row_counters=generation_counters(self.p_max),
            **({"tracer": ctx.engine_tracer} if ctx.engine_tracer else {}))
        self.experts = int(ctx.config["n_routed_experts"])
        if ctx.sabotage is not None:
            ctx.sabotage(self)
        # warm every bucket: a full batch of each, so that the window meets
        # no first execution
        for b in sorted(self.engine.buckets):
            for f in [self.engine.submit(self.frames[i % len(self.frames)])
                      for i in range(b)]:
                f.result(timeout=1200)
        self.warm = sum(self.engine.buckets)

    def weights(self):
        """The program's tree from the seed alone (bfloat16, on the device)."""
        return ref.program_tree(self.ctx.config, self.ctx.seed)

    def counters(self):
        out = super().counters()  # waits for the lead-in's batch to be fed
        out.update({n: self.registry.counter(n).value for n in MORE_COUNTERS})
        return out

    def check(self):
        prompts, served = self.sampled()
        wants = latent_check.reference_answers(
            self.ctx.config, self.ctx.seed, prompts, served)
        return (latent_check.numbers(prompts, served, wants),
                0 if served else 1)
