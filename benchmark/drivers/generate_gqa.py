"""Driver `generate_gqa`: the grouped-query decoder family's generate program
under `serve_backlog`'s closed backlog, as `generate_backlog` is for the
latent family (whose prompt pool it shares): one producer keeps the engine's
queue full of token rows (`[length, ids..., padding]`, int32); one answer is
a `Generation`; the metric is requests answered per second, the window
arithmetic `serve_backlog`'s.

The program is reached the normal way only: `Config(family=...,
decoder={...})` -> `build_model` -> `make_generate_fn` -> `ServingEngine`,
its counters fed by the engine's `row_counters`. `faults` (empty in every
benchmark run) is the check module's and the tests': the model rebuilt with
a fault planted by name (`benchmark/gqa_check.py`).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import gqa_check
from ..reference import gqa_moe_decoder as ref
from . import serve_backlog
from .generate_backlog import prompt_pool

GEN_COUNTERS = ("gen.requests", "gen.prompt_tokens",
                "gen.padded_prompt_tokens", "gen.new_tokens",
                "gen.keys_causal", "gen.expert_passes", "gen.expert_visits",
                "gen.cache_slots.full", "gen.cache_keys.full",
                "gen.cache_slots.window", "gen.cache_keys.window")


class Cell(serve_backlog.Cell):
    faults = frozenset()

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
        self.experts = int(ctx.config["num_experts"])
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
        # the engine feeds a batch's `gen.*` counters just AFTER it delivers
        # the batch's answers: wait (a millisecond or so) until every answer
        # delivered so far is counted, so that the window's first reading
        # holds the lead-in's batch whole. Read a moment earlier it misses
        # that batch, and the window then counts 8 batches' work over 7
        # batches' time: the shares of a traced run read 8/7 of themselves
        # (PERF.md section 6, PR 33)
        fed = self.registry.counter("gen.requests")
        want = self.warm + sum(d is not None for d in self.done)
        deadline = time.monotonic() + 1.0
        while fed.value < want and time.monotonic() < deadline:
            time.sleep(0.001)
        out = super().counters()
        names = GEN_COUNTERS + tuple("gen.expert_pairs.e%02d" % e
                                     for e in range(self.experts))
        out.update({n: self.registry.counter(n).value for n in names})
        return out

    def sampled(self):
        """(payload rows, answers) of the requests the seed samples among
        those the window answered."""
        picks = self.sample(self.first)
        return ([self.frames[i % len(self.frames)] for i in picks],
                [self.futs[i].result() for i in picks])

    def check(self):
        prompts, served = self.sampled()
        wants = gqa_check.reference_answers(
            self.ctx.config, self.ctx.seed, prompts, served)
        return (gqa_check.numbers(prompts, served, wants),
                0 if served else 1)
