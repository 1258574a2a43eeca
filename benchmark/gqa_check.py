"""The comparison that decides `correct` in a cell of the grouped-query
decoder family, and the readings its limits are set from: what
`decoder_check.py` is for the latent family, over this family's reference.

For each sampled request the plain reference
(`reference/gqa_moe_decoder.py`) runs its full forward pass (no cache, one
sequence) over `prompt[:L] + served tokens[:N-1]`, padded to the cell's
longest, and gives the logits at the N positions that produced the N served
tokens and its own routing. Compared (`decoder_check.py` says why each is a
mean or a percentile and not a maximum):

* prefill_logit_gap: |served - reference| / |reference| of the logits at the
  prompt's last token (position L-1: the prefill path alone), the mean over
  the sampled requests;
* decode_logit_gap: the same at the last step (position L+N-2: through the
  full caches and the rings for N-1 steps; with every prompt at least the
  window long, every ring has wrapped);
* token_gap_p99: how far the reference's logit of a served token lies under
  the reference's best at that position, in standard deviations of the
  position's logits, 99th percentile over all sampled positions;
* expert_pairs_gap: sum |served - reference| over (expert layer, expert) of
  the pairs routed there, over the reference's total.

`python3 -m benchmark.gqa_check --workload <cell> --seeds 1,2
[--control-seeds 1] [--bf16-seeds 1] [--fault-seed 3 --faults no_gate,...]`
prints, a seed, the program's numbers over ONE batch of the cell's own
prompts (no window: `python3 -m benchmark.run` reads the same numbers under
the cell's own traffic) and the numbers of the reference computed with fp8
(e4m3) operands (`control`) or bfloat16 operands (`ref_bf16`) put in the
program's place: `benchmark.calibrate`'s rule (PERF.md section 2). Every
side is put through the traffic file's limits (`correct`, `over`), at the
cell's own size. A fault is planted by name in the program the driver builds
(`FAULTS`; one more compile each), the reference keeps its own.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import argparse
import json
import sys
import types
from typing import Dict, List

import numpy as np

from .decoder_check import _rel, judged
from .reference import gqa_moe_decoder as ref

# the planted faults the family's program knows by name (models/decoder.py)
FAULTS = ("kv_group_misassigned", "full_rope_whole_head", "yarn_dropped",
          "window_off_by_one", "stale_ring_row", "no_gate",
          "no_routed_scale", "no_shared")


def reference_answers(cfg: dict, seed: int, prompts: List[np.ndarray],
                      served: list, quant: str = "f32",
                      weights=None) -> List[dict]:
    """The reference's forward over the sampled requests (`prompts`: the
    payload rows, `served`: the engine's answers), each sequence on its own,
    all padded to the same length and taken through a layer together, so
    that a layer's weights are drawn once. `weights`: the seed's draw, or
    (tests) a `ref.Held` tree."""
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


def numbers(prompts, served, wants) -> Dict[str, float]:
    if not served:
        return {}
    token_gaps = np.concatenate([ref.token_gaps(w["logits"], s.tokens)
                                 for s, w in zip(served, wants)])
    pairs = sum(float(np.abs(np.asarray(s.expert_tokens, np.int64)
                             - w["expert_pairs"]).sum())
                for s, w in zip(served, wants))
    return {
        "prefill_logit_gap": float(np.mean(
            [_rel(s.logits_first, w["logits"][0])
             for s, w in zip(served, wants)])),
        "decode_logit_gap": float(np.mean(
            [_rel(s.logits_last, w["logits"][-1])
             for s, w in zip(served, wants)])),
        "token_gap_p99": float(np.percentile(token_gaps, 99)),
        "expert_pairs_gap": pairs / max(1.0, sum(
            float(w["expert_pairs"].sum()) for w in wants))}


def control_answers(wants_low: List[dict]) -> list:
    """What a server computing in the control's precision would answer over
    the same token sequences: its own logits, greedy tokens and routing."""
    return [types.SimpleNamespace(
        tokens=np.argmax(w["logits"], axis=-1),
        logits_first=w["logits"][0], logits_last=w["logits"][-1],
        expert_tokens=w["expert_pairs"]) for w in wants_low]


def one_batch(cell, sample: int):
    """(payload rows, answers) of one bucket of the cell's pool through the
    cell's engine; the first `sample` are compared."""
    bucket = max(cell.engine.buckets)
    rows = [cell.frames[i % len(cell.frames)] for i in range(bucket)]
    futs = [cell.engine.submit(r) for r in rows]
    return rows[:sample], [f.result(timeout=1200) for f in futs][:sample]


def main(argv=None, root=None) -> int:
    """`root`: tests alone (a throw-away root at toy size)."""
    from . import run
    root = root or run.ROOT
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.gqa_check")
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
    for seed, fault in ([(s, None) for s in ints(args.seeds)]
                        + [(args.fault_seed, f) for f in faults]):
        ctx = run.Context(seed, parts["config"], parts["traffic"], 0)
        cell = parts["driver"].Cell(ctx)
        if fault:
            cell.faults = frozenset({fault})
        cell.setup()
        prompts, served = one_batch(cell, int(parts["traffic"]["sample"]))
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
