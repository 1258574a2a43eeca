"""Time full-layer prefill attention of one sequence both ways, at a decoder
cell's shapes, on the chip: the fused kernel (`ops/pallas/attention.py`)
against the XLA path of `ops/attention.py:blockwise_attention` (PERF.md
section 6, PR 34, has the readings behind the rule in `runs_fused`).

    python scripts/attn_bench.py [--out chiprun_out/pr34/attn_bench.json]
    python scripts/attn_bench.py --tiles 512,512,8 512,1024,4 1024,1024,4

The shapes come from a benchmark configuration file (its `fields`:
`num_attention_heads`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`index_topk`) and `--slots`; defaults, dots3's: 128 heads, 8,192 slots, 128 +
64 / 128, a seeded top-2,048 mask (uniform scores: `top_k_mask` over the
causal keys of each q block), lengths 4,096, 6,144 and 8,192. A tile is `q
block, key block, heads a grid step`: arguments of this script only (the
program's are `DecoderSpec.q_block` and the kernel's `KEY_BLOCK`,
`HEAD_TILE`). A row reads ms a row-layer and the share of the bf16 peak of
the matrix products of the blocks that run (the causal rectangle of every q
block under `length`, nope + rope + values deep: what the XLA path computes).
Only on the chip (the reference has no such tool: ref train.py:92-140 keeps
per-segment meters only); a time from the CPU would say nothing.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import chip_peaks  # noqa: E402
from real_time_helmet_detection_tpu.obs.spans import SpanTracer  # noqa: E402
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    maybe_job_heartbeat, run_as_job)
from real_time_helmet_detection_tpu.utils import save_json  # noqa: E402

def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.ops import attention as att
    from real_time_helmet_detection_tpu.ops.pallas import attention as fused

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "dots3-note-prev-ep8-l5.json"))
    ap.add_argument("--slots", type=int, default=8192)
    ap.add_argument("--lengths", default="4096,6144,8192")
    ap.add_argument("--tiles", nargs="*", default=["%d,%d,%d" % (
        512, fused.KEY_BLOCK, fused.HEAD_TILE)])
    ap.add_argument("--xla-q-block", type=int, default=512,
                    help="0: leave the XLA path out")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        fields = json.load(f)["fields"]
    heads, total = fields["num_attention_heads"], args.slots
    nope, rope, dv = (fields["qk_nope_head_dim"], fields["qk_rope_head_dim"],
                      fields["v_head_dim"])
    topk, scale = fields["index_topk"], 1.0 / math.sqrt(nope + rope)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("attn_bench: no TPU; a time from %r says nothing"
                         % jax.devices()[0].platform)
    peak = chip_peaks(jax.devices()[0].device_kind)[0]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    draw = lambda key, *shape: jax.random.normal(  # noqa: E731
        key, shape, jnp.bfloat16)
    q, k, v = (draw(keys[0], heads, total, nope), draw(keys[1], heads, total,
                                                       nope),
               draw(keys[2], heads, total, dv))
    q_r, k_r = draw(keys[3], heads, total, rope), draw(keys[4], total, rope)

    @functools.partial(jax.jit, static_argnums=0)
    def chosen_of(q_block):
        out = []
        for i, r0 in enumerate(range(0, total, q_block)):
            r1 = r0 + q_block
            causal = att._causal(r0, r1)
            scores = jax.random.uniform(jax.random.fold_in(keys[5], i),
                                        (q_block, r1))
            out.append(att.top_k_mask(jnp.where(causal, scores, -jnp.inf),
                                      topk) & causal)
        return out

    def flops(length, q_block):
        live = -(-length // q_block)
        return 2 * heads * (nope + rope + dv) * q_block * q_block * (
            live * (live + 1) // 2)

    calls = {}
    for tile in args.tiles:
        bq, bk, hb = (int(t) for t in tile.split(","))
        calls["attn_fused %d,%d,%d" % (bq, bk, hb)] = (
            bq, (q, k, v, q_r, k_r), jax.jit(
                lambda q, k, v, q_r, k_r, n, mask, bq=bq, bk=bk, hb=hb:
                fused.attn_fused(q, k, v, n, mask, q_r, k_r, q_block=bq,
                                 scale=scale, k_block=bk, head_tile=hb)))
    if args.xla_q_block:
        # as the program without the kernel: nope and rope in one operand,
        # the rope keys copied a head, 32 heads a pass
        q_cat = jnp.concatenate([q, q_r], axis=-1)
        k_cat = jnp.concatenate([k, jnp.broadcast_to(
            k_r, (heads, total, rope))], axis=-1)
        att.kernel_compiles = lambda: False

        def xla(q, k, v, n, *chosen):
            return att.blockwise_attention(
                q, k, v, q_block=args.xla_q_block, scale=scale, length=n,
                chosen=list(chosen), head_block=32)
        calls["xla %d" % args.xla_q_block] = (
            args.xla_q_block, (q_cat, k_cat, v), jax.jit(xla))

    rows = []
    hb_job = maybe_job_heartbeat()
    tracer = SpanTracer(None)
    masks = {}
    for length in (int(n) for n in args.lengths.split(",")):
        want = {}  # by q block: each has a mask of its own
        for name, (q_block, arrays, fn) in calls.items():
            if q_block not in masks:
                chosen = chosen_of(q_block)
                masks[q_block] = (chosen, att.chosen_mask(chosen, total))
            chosen, mask = masks[q_block]
            operands = arrays + (jnp.int32(length),) + (
                tuple(chosen) if name.startswith("xla") else (mask,))
            try:
                out = jax.block_until_ready(fn(*operands))
                # `reps` calls enqueued back to back, one wait: the device's
                # time a call, not a dispatch's
                with tracer.span("attn:" + name, length=length) as sp:
                    for _ in range(args.reps):
                        out = fn(*operands)
                    jax.block_until_ready(out)
                ms = 1e3 * sp.dur_s / args.reps
                got = out[:, :length].astype(jnp.float32)
                row = {"length": length, "impl": name, "ms": ms,
                       "max_diff": float(jnp.abs(got - want.setdefault(
                           q_block, got)).max()),
                       "mxu_share": flops(length, q_block) / (ms * 1e-3)
                       / peak}
            except Exception as e:  # noqa: BLE001 - a refusal is a reading
                row = {"length": length, "impl": name,
                       "error": repr(e)[:300]}
            rows.append(row)
            print("ATTN " + json.dumps(row), flush=True)
            if hb_job is not None:
                hb_job.beat()
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        save_json(args.out, rows)
    return 0


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
