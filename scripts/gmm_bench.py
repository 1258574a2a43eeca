"""Time the three grouped matmuls the expert layer could use, at a decoder
cell's two shapes, on the chip: `lax.ragged_dot`, jax's shipped megablox `gmm`
and `ops/pallas/expert_gmm.py` (PERF.md section 6, PR 29 and PR 33, has the
readings behind the choice in ops/moe.py).

    python scripts/gmm_bench.py [--out chiprun_out/pr29/gmm_bench.json]
    python scripts/gmm_bench.py --config benchmark/configs/laguna-xs2-l5.json \
        --prefill 65536,65536,256 --decode 256,256,162
    python scripts/gmm_bench.py --config benchmark/configs/axk1-ep8-l5.json \
        --prefill 32768,16384,24 --decode 256,32,18

The expert width and count come from a benchmark configuration file (its
`fields`: `hidden_size`, `moe_intermediate_size`, and the experts held,
`n_routed_experts` or `num_experts`); a phase is `rows,valid,hit`: sorted
rows, how many of them belong to held experts, over how many experts
(uneven). Defaults, dots3's: prefill 65,536 rows of which 32,768 belong to
the 32 held experts, hidden 5120 -> 3072 and 1536 -> 5120; decode 32 rows of
which 4 belong to 4 experts. Laguna's (above): a pass of 65,536 rows over
all 256 experts, 2048 -> 1024 and 512 -> 2048; a decode step's 256 rows over
about 162. A.X-K1's share (above): a pass of 32,768 rows of which 16,384
belong to the 24 held experts, 7168 -> 4096 and 2048 -> 7168 (88 MB an
expert); a decode step's pass of 256 rows of which 32 belong to about 18.
Only on the chip (the reference has no such tool: ref
train.py:92-140 keeps per-segment meters only); a time from the CPU would
say nothing.
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
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmark", "configs", "dots3-note-prev-ep8-l5.json"))
    ap.add_argument("--prefill", default="65536,32768,32")
    ap.add_argument("--decode", default="32,4,4")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        fields = json.load(f)["fields"]
    hidden, width = fields["hidden_size"], fields["moe_intermediate_size"]
    groups = fields.get("n_routed_experts", fields.get("num_experts"))
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

    for phase, shape, tiling in (("prefill", args.prefill, own.TILING),
                                 ("decode", args.decode, own.TILING)):
        m, valid, hit = (int(v) for v in shape.split(","))
        sizes = jnp.asarray(sizes_of(valid, groups, hit))
        for k, n in ((hidden, 2 * width), (width, hidden)):
            lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            rhs = jnp.asarray(0.02 * rng.standard_normal((groups, k, n)),
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
                           "tflops": 2 * valid * k * n / ms / 1e9,
                           # each hit expert's weights once, rows in and out
                           "gb_per_s": 2 * (hit * k * n + valid * (k + n))
                           / ms / 1e6}
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
