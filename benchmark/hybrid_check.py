"""The comparison that decides `correct` in a cell of the linear-hybrid
decoder (`reference/hybrid_moe_decoder.py`: KDA linear attention beside
latent attention, group-limited routing with a selection bias), and the
readings its limits are set from: `latent_check.py`'s numbers over this
family's reference.

For each sampled request the plain reference runs its full forward pass
(no cache, no chunks: KDA as its token-by-token recurrence) over
`prompt[:L] + served tokens[:N-1]` and gives the logits at the N positions
that produced the N served tokens, and its own routing. Compared, as
`latent_check.py` states them: prefill_logit_gap (position L-1: the chunked
prefill alone), decode_logit_gap (position L+N-2: N-1 steps through the
float32 states and the latent cache), token_gap_p99, expert_pairs_gap,
group_hits_gap.

`python3 -m benchmark.hybrid_check --workload <cell> --seeds 1,2
[--control-seeds 1] [--bf16-seeds 1] [--fault-seed 3 --faults
state_bf16,...]` prints, a seed, the program's numbers over ONE batch of the
cell's own prompts and those of the reference computed with fp8 (e4m3)
operands (`control`) or bfloat16 operands (`ref_bf16`) put in the program's
place; every side through the traffic file's limits. A fault is planted by
name in the program the driver builds (`FAULTS`; one more compile each), the
reference keeps its own. The reference's programs in each precision asked
for and the faults' programs are compiled on threads of their own from the
start (`compile_ahead`), while the seeds run on the device: each then finds
its program in the persistent compile cache.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List

import numpy as np

from . import gqa_check
from .decoder_check import judged
from .latent_check import control_answers, numbers  # noqa: F401
from .reference import hybrid_moe_decoder as ref

# the planted faults the program knows by name (models/decoder.py)
FAULTS = ("decay_dropped", "beta_one", "conv_dropped", "no_l2norm",
          "out_gate_dropped", "state_reset_at_decode",
          "padding_updates_state", "state_bf16", "no_shared",
          "no_group_limit")


def reference_answers(cfg: dict, seed: int, prompts: List[np.ndarray],
                      served: list, quant: str = "f32",
                      weights=None) -> List[dict]:
    """The reference's forward over the sampled requests, all padded to the
    same length and taken through a layer together. `weights`: the seed's
    draw, or (tests) a `ref.Held` tree."""
    if not served:
        return []
    model = ref.Reference(cfg, weights or ref.Drawn(cfg, seed), quant)
    new = len(served[0].tokens)
    padded = max(len(p) - 1 for p in prompts) + new
    tokens = np.zeros((len(served), padded), np.int32)
    lengths = [int(row[0]) for row in prompts]
    for out, row, answer, length in zip(tokens, prompts, served, lengths):
        out[:length] = row[1:1 + length]
        out[length:length + new - 1] = np.asarray(answer.tokens)[:new - 1]
    return model.forward(
        tokens, [length + new - 1 for length in lengths],
        np.stack([np.arange(n - 1, n - 1 + new) for n in lengths]))


def compile_ahead(parts: dict, seed: int, faults: List[str],
                  quants: List[str] = (), workers: int = 4) -> list:
    """Futures of programs compiled on threads (the host's cores are idle
    while the device runs the seeds), in the order they are needed: the
    reference's in each of `quants` at the shapes `reference_answers` gives
    it, then each fault's, lowered to the text the engine lowers (its
    parameters committed to one device carry no sharding into the program;
    neither do plain shapes), the largest bucket's payload. What a compile
    stores in the persistent cache, the seed's reference or the fault's
    engine finds there."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from . import run
    p = parts["traffic"]
    new = int(p["new_tokens"])

    def reference(quant):
        ref.Reference(parts["config"], ref.Drawn(parts["config"], seed),
                      quant).compile(int(p["sample"]), int(p["p_max"]) + new,
                                     new)
        return "reference " + quant

    def one(fault):
        cell = parts["driver"].Cell(
            run.Context(seed, parts["config"], parts["traffic"], 0))
        cell.faults = frozenset({fault})
        weights = jax.eval_shape(cell.weights)
        payload = jax.ShapeDtypeStruct(
            (max(cell.p["engine"]["buckets"]), int(cell.p["p_max"]) + 1),
            np.int32)
        cell.program().lower(weights, payload).compile()
        return fault
    pool = ThreadPoolExecutor(max_workers=workers)
    futures = ([pool.submit(reference, q) for q in quants]
               + [pool.submit(one, f) for f in faults])
    pool.shutdown(wait=False)
    return futures


def main(argv=None, root=None) -> int:
    """`root`: tests alone (a throw-away root at toy size)."""
    from . import run
    root = root or run.ROOT
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.hybrid_check")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--bf16-seeds", default="")
    ap.add_argument("--fault-seed", type=int, default=None)
    ap.add_argument("--faults", default="")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    def ints(text):
        return [int(s) for s in text.split(",") if s]
    faults = [f for f in args.faults.split(",") if f]
    if set(faults) - set(FAULTS) or (faults and args.fault_seed is None):
        ap.error("--faults takes names of %s and needs --fault-seed"
                 % (FAULTS,))
    low_seeds = {"control": ("fp8", set(ints(args.control_seeds))),
                 "ref_bf16": ("bf16", set(ints(args.bf16_seeds)))}
    parts = run.resolve_cell(root, run.load_manifest(root), args.workload)
    limits = parts["traffic"]["limits"]
    run.acquire_devices(int(parts["cell"]["chips"]), args.allow_cpu)
    run.use_compile_cache(root)
    quants = ["f32"] + [quant for quant, seeds in low_seeds.values() if seeds]
    ahead = compile_ahead(parts, (ints(args.seeds) + [args.fault_seed])[0],
                          faults, quants) if args.seeds or faults else []
    for seed, fault in ([(s, None) for s in ints(args.seeds)]
                        + [(args.fault_seed, f) for f in faults]):
        ctx = run.Context(seed, parts["config"], parts["traffic"], 0)
        cell = parts["driver"].Cell(ctx)
        if fault:
            cell.faults = frozenset({fault})
        cell.setup()
        prompts, served = gqa_check.one_batch(
            cell, int(parts["traffic"]["sample"]))
        cell.free()
        wants = reference_answers(ctx.config, seed, prompts, served)
        out = {"seed": seed,
               "fault:" + fault if fault else "program":
               judged(numbers(prompts, served, wants), limits)}
        for name, (quant, seeds) in low_seeds.items():
            if seed in seeds and not fault:
                low = reference_answers(ctx.config, seed, prompts, served,
                                        quant)
                out[name] = judged(numbers(
                    prompts, control_answers(low), wants), limits)
        print("READING " + json.dumps(out), flush=True)
        del cell, ctx
    for f in ahead:
        f.result()  # a compile that failed on its thread says so here
    return 0


if __name__ == "__main__":
    sys.exit(main())
