"""Time `decode_peak_scores` on the chip at a serve cell's shape for each
chunk length its two-level top-k could take (`ops/decode.py:chunk_length`;
0 is the direct `lax.top_k`, a sort of the whole map), and say which one the
rule picks (PERF.md section 6, PR 37, has the readings behind the rule).

    python scripts/topk_bench.py [--chunks 0,8,16,32,64,128] [--images 256]
        [--stacks 2] [--out chiprun_out/pr37/topk_bench.json]

Every candidate's `Detections` are compared with the direct call's, bit for
bit, on the same seeded peak maps. Only on the chip: a time from the CPU
would say nothing (the reference has no such tool: ref transform.py:81
calls `torch.topk` on the whole map and times nothing).
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from real_time_helmet_detection_tpu.obs.spans import SpanTracer  # noqa: E402
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    maybe_job_heartbeat, run_as_job)
from real_time_helmet_detection_tpu.utils import save_json  # noqa: E402


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.ops import decode

    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", default="0,8,16,32,64,128")
    ap.add_argument("--images", type=int, default=256)
    ap.add_argument("--stacks", type=int, default=2)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("topk_bench: no TPU; a time from %r says nothing"
                         % jax.devices()[0].platform)
    side, topk = 128, 100  # a 512^2 frame's map and `Config.topk`
    shape = (args.images, args.stacks, side, side, 2)
    heat, offset, wh = (jax.random.uniform(key, shape, jnp.float32) for key in
                        jax.random.split(jax.random.PRNGKey(0), 3))
    peaks = jnp.where(decode.peak_mask(heat), heat, 0.0)
    rule = decode.chunk_length(side * side * 2, topk)
    picks_of_rule = decode.chunk_length
    rows, direct = [], None
    hb = maybe_job_heartbeat()
    tracer = SpanTracer(None)
    for chunk in [int(c) for c in args.chunks.split(",")]:
        decode.chunk_length = lambda n, k, chunk=chunk: chunk
        jax.clear_caches()
        fn = jax.jit(jax.vmap(jax.vmap(
            lambda p, o, w: decode.decode_peak_scores(p, o, w, topk=topk))))
        out = jax.block_until_ready(fn(peaks, offset, wh))
        # `reps` calls enqueued back to back, one wait: the device's time a
        # call, not a dispatch's
        with tracer.span("topk:chunk", chunk=chunk) as sp:
            for _ in range(args.reps):
                out = fn(peaks, offset, wh)
            jax.block_until_ready(out)
        ms = 1e3 * sp.dur_s / args.reps
        out = jax.tree.map(np.asarray, out)
        direct = out if direct is None else direct
        rows.append({"chunk": chunk, "rule": chunk == rule, "ms_per_call": ms,
                     "ms_per_img": ms / args.images,
                     "same_bits_as_first": all(
                         np.array_equal(a, b) for a, b in zip(out, direct))})
        print(rows[-1], flush=True)
        if hb is not None:
            hb.beat()
    decode.chunk_length = picks_of_rule
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        save_json(args.out, {"device": jax.devices()[0].device_kind,
                             "shape": list(shape), "topk": topk,
                             "reps": args.reps, "rows": rows})
    return 0 if all(r["same_bits_as_first"] for r in rows) else 1


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
