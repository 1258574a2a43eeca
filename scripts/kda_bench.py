"""Time one prefill pass of the chunked delta rule on the chip at a cell's
shapes: `kda_prefill` (ops/pallas/kda.py) against its XLA twin
`chunked_prefill` (ops/linear_attention.py), and the largest difference of
their outputs and handed-on states.

    python scripts/kda_bench.py [--rows 16] [--slots 512] [--heads 32]
        [--dim 128] [--chunk 32] [--head-blocks 8,32] [--subs 8,16]
        [--out build/kda_bench.json]

The inputs are drawn as the program's are: q and k normalised a head (q
scaled by d^-1/2), the log decays in (-5, 0), beta in (0, 1), the rows'
lengths uniform in (slots/2, slots] and the positions past them writing
nothing. Each candidate (heads a grid step, the diagonal blocks' size) is
compared with the twin twice: at those decays, and at decays a hundred
times smaller (a state that remembers the whole chunk). A head block is 8
or all the heads (a block's heads are its sublanes). Only on the chip:
a time from the CPU or the interpreter would say nothing (the reference
has no linear attention: no analogue).
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


def _inputs(rows, slots, heads, dim, lower, seed):
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.ops import linear_attention as la

    rng = np.random.default_rng(seed)
    draw = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((rows, slots, heads, dim)), jnp.float32)
    q = la.l2_normalize(draw()) * dim ** -0.5
    k, v = la.l2_normalize(draw()), draw()
    g = jnp.asarray(lower * rng.uniform(0, 1, (rows, slots, heads, dim)),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (rows, slots, heads)), jnp.float32)
    lengths = rng.integers(slots // 2 + 1, slots + 1, rows)
    real = np.arange(slots)[None, :] < lengths[:, None]
    return (q, k, v, g * real[..., None, None], beta * real[..., None],
            jnp.asarray(lengths, jnp.int32))


def main(argv=None) -> int:
    import jax
    import numpy as np

    from real_time_helmet_detection_tpu.ops import linear_attention as la
    from real_time_helmet_detection_tpu.ops.pallas import kda

    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--slots", type=int, default=512)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--head-blocks", default="8")
    ap.add_argument("--subs", default=str(kda.SUB))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("kda_bench: no TPU; a time from %r says nothing"
                         % jax.devices()[0].platform)
    shape = (args.rows, args.slots, args.heads, args.dim)
    sets = {lower: _inputs(*shape, lower, seed)
            for seed, lower in enumerate((-5.0, -0.05))}
    hb = maybe_job_heartbeat()
    tracer = SpanTracer(None)
    twin = jax.jit(lambda *a: la.chunked_prefill(*a, chunk=args.chunk))
    kernel = jax.jit(lambda *a: la.prefill_pass(*a, chunk=args.chunk,
                                                interpret=False))

    def timed(fn, xs, **meta):
        out = jax.block_until_ready(fn(*xs))
        # `reps` calls enqueued back to back, one wait: the device's time
        with tracer.span("kda:pass", **meta) as sp:
            for _ in range(args.reps):
                out = fn(*xs)
            jax.block_until_ready(out)
        return 1e3 * sp.dur_s / args.reps, jax.tree.map(np.asarray, out)

    wants, rows = {}, []
    for lower, xs in sets.items():
        ms, wants[lower] = timed(twin, xs, form="xla", lower=lower)
        rows.append({"form": "chunked_prefill", "lower": lower,
                     "ms_per_pass": ms})
        print(rows[-1], flush=True)
    picks = kda.head_block, kda.SUB
    for block in [int(b) for b in args.head_blocks.split(",")]:
        for sub in [int(s) for s in args.subs.split(",")]:
            kda.head_block = lambda heads, block=block: block
            kda.SUB = sub
            jax.clear_caches()
            for lower, xs in sets.items():
                ms, got = timed(kernel, xs, form="kernel", lower=lower)
                want = wants[lower]
                rows.append({
                    "form": "kda_prefill", "head_block": block, "sub": sub,
                    "lower": lower, "ms_per_pass": ms,
                    "o_max_abs_diff": float(np.max(np.abs(got[0] - want[0]))),
                    "state_max_abs_diff": float(np.max(np.abs(
                        got[1] - want[1]))),
                    "ran_same": bool(np.array_equal(got[2], want[2]))})
                print(rows[-1], flush=True)
                if hb is not None:
                    hb.beat()
    kda.head_block, kda.SUB = picks
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        save_json(args.out, {"device": jax.devices()[0].device_kind,
                             "shape": list(shape), "chunk": args.chunk,
                             "reps": args.reps, "rows": rows})
    return 0


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
