"""Time the three grouped matmuls the expert layer could use, at the decoder
cell's two shapes, on the chip: `lax.ragged_dot`, jax's shipped megablox `gmm`
and `ops/pallas/expert_gmm.py` (PERF.md section 6, PR 29, has the readings
behind the choice in ops/moe.py).

    python scripts/gmm_bench.py [--out chiprun_out/pr29/gmm_bench.json]

Prefill: 65,536 sorted rows of which 32,768 belong to the 32 held experts
(about 1,024 an expert, uneven), hidden 5120 -> 3072 and 1536 -> 5120.
Decode: 32 rows of which 4 belong to 4 experts. Only on the chip (the
reference has no such tool: ref train.py:92-140 keeps per-segment meters
only); a time from the CPU would say nothing.
"""

from __future__ import annotations

import argparse
import json
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
    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox

    from real_time_helmet_detection_tpu.ops.pallas import expert_gmm as own

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("gmm_bench: no TPU; a time from %r says nothing"
                         % jax.devices()[0].platform)
    rng = np.random.default_rng(0)
    rows = []
    hb = maybe_job_heartbeat()
    tracer = SpanTracer(None)

    def sizes_of(total, groups, hit):
        """`total` rows over `hit` of the groups, uneven."""
        cuts = np.sort(rng.integers(0, total + 1, hit - 1))
        part = np.diff(np.concatenate([[0], cuts, [total]]))
        out = np.zeros(groups, np.int32)
        out[rng.permutation(groups)[:hit]] = part
        return out

    for phase, m, valid, hit, tiling in (
            ("prefill", 65536, 32768, 32, own.TILING),
            ("decode", 32, 4, 4, own.TILING)):
        sizes = jnp.asarray(sizes_of(valid, 32, hit))
        for k, n in ((5120, 3072), (1536, 5120)):
            lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            rhs = jnp.asarray(0.02 * rng.standard_normal((32, k, n)),
                              jnp.bfloat16)
            tm = own.row_tile(m, tiling)
            calls = {
                "ragged_dot": jax.jit(lambda a, b, s: jax.lax.ragged_dot(
                    a, b, s)),
                "expert_gmm": jax.jit(lambda a, b, s: own.expert_gmm(
                    a, b, own.group_metadata(s, m, tm))),
            }
            for name, tiles in (("megablox_128", (min(128, m), 128, 128)),
                                ("megablox_512", (tm, 1024 if k % 1024 == 0
                                                  else 512, 1024))):
                calls[name] = jax.jit(
                    lambda a, b, s, t=tiles: megablox(
                        a, b, s, preferred_element_type=jnp.bfloat16,
                        tiling=t))
            want = None
            for name, fn in calls.items():
                try:
                    out = jax.block_until_ready(fn(lhs, rhs, sizes))
                    # `reps` calls enqueued back to back, one wait: the
                    # device's time a call, not a dispatch's
                    with tracer.span("gmm:" + name, phase=phase) as sp:
                        for _ in range(args.reps):
                            out = fn(lhs, rhs, sizes)
                        jax.block_until_ready(out)
                    ms = 1e3 * sp.dur_s / args.reps
                    got = np.asarray(out[:valid], np.float32)
                    want = got if want is None else want
                    err = float(np.abs(got - want).max())
                    row = {"phase": phase, "m": m, "k": k, "n": n,
                           "impl": name, "ms": ms, "max_diff": err,
                           "tflops": 2 * valid * k * n / ms / 1e9}
                except Exception as e:  # noqa: BLE001 - a refusal is a reading
                    row = {"phase": phase, "m": m, "k": k, "n": n,
                           "impl": name, "error": repr(e)[:300]}
                rows.append(row)
                print("GMM " + json.dumps(row), flush=True)
                if hb is not None:
                    hb.beat()
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        save_json(args.out, rows)
    return 0


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
