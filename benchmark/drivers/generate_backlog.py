"""Driver `generate_backlog`: the decoder family's generate program under
`serve_backlog`'s closed backlog. One producer keeps the engine's queue full
of token rows (`[length, ids..., padding]`, int32); one answer is a
`Generation` (new tokens, logits at the prompt's last token and at the last
step, the routing and indexer counts of the row); the metric is requests
answered per second, the window arithmetic `serve_backlog`'s.

The program is reached the normal way only: `Config(family=...,
decoder={...})` -> `build_model` -> `make_generate_fn` -> `ServingEngine`,
its counters fed by the engine's `row_counters`.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import decoder_check
from ..reference import latent_moe_decoder as ref
from . import serve_backlog

GEN_COUNTERS = ("gen.requests", "gen.prompt_tokens",
                "gen.padded_prompt_tokens", "gen.new_tokens",
                "gen.keys_kept", "gen.keys_causal")


def prompt_pool(seed: int, count: int, lo: int, hi: int, p_max: int,
                vocab: int) -> np.ndarray:
    """`count` payload rows int32 (count, p_max + 1): lengths independent and
    uniform in [lo, hi], ids uniform over the vocabulary rows held, zeros
    after."""
    rng = np.random.default_rng([int(seed), 6])
    rows = np.zeros((count, p_max + 1), np.int32)
    rows[:, 0] = rng.integers(lo, hi + 1, count)
    for row in rows:
        row[1:1 + row[0]] = rng.integers(0, vocab, row[0])
    return rows


class Cell(serve_backlog.Cell):
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

    def weights(self):
        """The program's tree from the seed alone (bfloat16, on the device)."""
        return ref.program_tree(self.ctx.config, self.ctx.seed)

    def counters(self):
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
        wants = decoder_check.reference_answers(
            self.ctx.config, self.ctx.seed, prompts, served)
        return (decoder_check.numbers(prompts, served, wants),
                0 if served else 1)
