"""The comparison that decides `correct` in a cell of the plain
latent-attention decoder (`reference/mla_moe_decoder.py`: no indexer, no
window, no gate; YaRN on the rotary part; group-limited routing), and the
readings its limits are set from: what `gqa_check.py` is for the grouped-query
family, over this configuration's reference.

For each sampled request the plain reference
(`reference/mla_moe_decoder.py`) runs its full forward pass (no cache, one
sequence) over `prompt[:L] + served tokens[:N-1]`, padded to the cell's
longest, and gives the logits at the N positions that produced the N served
tokens and its own routing. Compared (`decoder_check.py` says why each is a
mean or a percentile and not a maximum):

* prefill_logit_gap: |served - reference| / |reference| of the logits at the
  prompt's last token (position L-1: the prefill path alone), the mean over
  the sampled requests;
* decode_logit_gap: the same at the last step (position L+N-2: N-1 steps
  through the latent cache with W_uk / W_uv absorbed);
* token_gap_p99: how far the reference's logit of a served token lies under
  the reference's best at that position, in standard deviations of the
  position's logits, 99th percentile over all sampled positions;
* expert_pairs_gap: sum |served - reference| over (expert layer, expert) of
  the pairs routed there, over the reference's total;
* group_hits_gap: |served - reference| of the (position, expert layer) slots
  where a routing group this share holds was among the groups kept, over the
  reference's, summed over the sampled requests.

`python3 -m benchmark.latent_check --workload <cell> --seeds 1,2
[--control-seeds 1] [--bf16-seeds 1] [--fault-seed 3 --faults
no_group_limit,...]`
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
from typing import Dict, List

import numpy as np

from . import gqa_check
from .decoder_check import judged
from .reference import mla_moe_decoder as ref

# the planted faults the family's program knows by name (models/decoder.py)
FAULTS = ("no_group_limit", "score_scale_plain", "yarn_dropped",
          "rank_rescale_kept", "gate_kept", "no_shared", "no_routed_scale",
          "stale_cache_row")


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
    """The grouped-query check's four numbers (the same arithmetic over the
    same kinds of answer) and the group hits' gap."""
    if not served:
        return {}
    hits = sum(abs(int(s.group_hits) - int(w["group_hits"]))
               for s, w in zip(served, wants))
    return dict(gqa_check.numbers(prompts, served, wants),
                group_hits_gap=hits / max(1.0, sum(
                    float(w["group_hits"]) for w in wants)))


def control_answers(wants_low: List[dict]) -> list:
    """What a server computing in the control's precision would answer over
    the same token sequences: its own logits, greedy tokens, routing and
    group hits."""
    answers = gqa_check.control_answers(wants_low)
    for answer, w in zip(answers, wants_low):
        answer.group_hits = w["group_hits"]
    return answers


def main(argv=None, root=None) -> int:
    """`root`: tests alone (a throw-away root at toy size)."""
    from . import run
    root = root or run.ROOT
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.latent_check")
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
