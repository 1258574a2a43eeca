"""The comparison that decides `correct` in a cell of the decoder family,
and the readings its limits are set from.

For each sampled request the plain reference runs its full forward pass (no
cache, one sequence) over `prompt[:L] + served tokens[:N-1]`, padded to the
cell's longest, and gives the logits at the N positions that produced the N
served tokens, its own routing and its own indexer's count. Compared:

* prefill_logit_gap: |served - reference| / |reference| of the logits at the
  prompt's last token (position L-1: the prefill path alone), the mean over
  the sampled requests (a mean, not the widest: one request whose router
  took another expert at that very position reads several times the rest);
* decode_logit_gap: the same at the last step (position L+N-2: through both
  kinds of cache for N-1 steps);
* token_gap_p99: how far the reference's logit of a served token lies under
  the reference's best at that position, in standard deviations of the
  position's logits, 99th percentile over all sampled positions. A
  percentile, not the maximum: with random weights the two best logits of a
  position now and then lie within rounding, and the greedy choice flips;
* expert_pairs_gap: sum |served - reference| over (expert layer, held expert)
  of the pairs routed there, over the reference's total: a near-tie at the
  router's 8th place moves a pair on rounding, a dropped selection bias or
  a mis-cut share moves a large part of them;
* keys_kept_gap: |served - reference| keys the indexer kept, over the
  reference's: 0 but for ties at the 2,048th place; an indexer left out
  reads about 0.8.

`python3 -m benchmark.decoder_check --workload <cell> --seeds 1,2,3
[--control-seeds 1] [--bf16-seeds 1] [--fault-seeds 3 --fault no_shared]
[--rate-seeds 4,5] [--seconds 3]` prints, a seed, the program's numbers and
the numbers of the reference computed with fp8 (e4m3) operands (`control`) or
bfloat16 operands (`ref_bf16`: the stated precision, what rounding alone
costs) put in the program's place: `benchmark.calibrate`'s rule (PERF.md
section 2), for this family's answers. Every side is put through the
traffic file's limits (`correct`, `over`), at the cell's own size. On a
`--fault-seeds` seed the program is handed weights with the fault planted
(`FAULTS`: the same compiled program, so no second compile on the chip); a
`--rate-seeds` seed runs the window alone and prints its rate and how many
pairs a position this share's experts took.

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

from .reference import latent_moe_decoder as ref


def reference_answers(cfg: dict, seed: int, prompts: List[np.ndarray],
                      served: list, quant: str = "f32") -> List[dict]:
    """The reference's forward over each sampled request (`prompts`: the
    payload rows, `served`: the engine's answers), one sequence at a time,
    every sequence padded to the same length so that each layer compiles
    once."""
    if not served:
        return []
    model = ref.Reference(cfg, ref.Drawn(cfg, seed), quant)
    new = len(served[0].tokens)
    padded = max(len(p) - 1 for p in prompts) + new
    out = []
    for row, answer in zip(prompts, served):
        length = int(row[0])
        tokens = np.zeros((padded,), np.int32)
        tokens[:length] = row[1:1 + length]
        tokens[length:length + new - 1] = np.asarray(answer.tokens)[:new - 1]
        out.append(model.forward(tokens, length + new - 1,
                                 np.arange(length - 1, length - 1 + new)))
    return out


def _rel(got, want) -> float:
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def numbers(prompts, served, wants) -> Dict[str, float]:
    if not served:
        return {}
    token_gaps = np.concatenate([ref.token_gaps(w["logits"], s.tokens)
                                 for s, w in zip(served, wants)])
    pairs = sum(float(np.abs(np.asarray(s.expert_tokens, np.int64)
                             - w["expert_pairs"]).sum())
                for s, w in zip(served, wants))
    kept = sum(abs(int(s.keys_kept) - int(w["keys_kept"]))
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
            float(w["expert_pairs"].sum()) for w in wants)),
        "keys_kept_gap": kept / max(1.0, sum(float(w["keys_kept"])
                                             for w in wants))}


def control_answers(wants_low: List[dict]) -> list:
    """What a server computing in the control's precision would answer over
    the same token sequences: its own logits, greedy tokens and counts."""
    return [types.SimpleNamespace(
        tokens=np.argmax(w["logits"], axis=-1),
        logits_first=w["logits"][0], logits_last=w["logits"][-1],
        expert_tokens=w["expert_pairs"], keys_kept=w["keys_kept"])
        for w in wants_low]


def _zero(tree: dict, leaf: str) -> dict:
    """`tree` with every `moe/<leaf>` zeroed (a copy of the dicts, not of
    the arrays)."""
    params = dict(tree["params"])
    for name, layer in params.items():
        if isinstance(layer, dict) and "moe" in layer:
            params[name] = dict(layer, moe=dict(
                layer["moe"], **{leaf: layer["moe"][leaf] * 0}))
    return {"params": params}


# planted in the weights the program is handed, the reference keeps its own
FAULTS = {"no_shared": lambda tree: _zero(tree, "shared_down"),
          "no_select_bias": lambda tree: _zero(tree, "b_select")}


def judged(got: Dict[str, float], limits: Dict[str, float]) -> dict:
    over = sorted(k for k, v in got.items() if not v <= limits[k])
    return dict(got, correct=not over, over=over)


def _share_load(counters: Dict[str, float]) -> float:
    """Pairs this share's experts took, a position (prompt and new tokens),
    all expert layers summed: `ep` shares of an even router give
    layers x per_token / ep_size."""
    pairs = sum(v for k, v in counters.items()
                if k.startswith("gen.expert_pairs."))
    return pairs / max(1.0, counters["gen.prompt_tokens"]
                       + counters["gen.new_tokens"])


def main(argv=None, root=None) -> int:
    """`root`: tests alone (a throw-away root at toy size)."""
    from . import run
    root = root or run.ROOT
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.decoder_check")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--bf16-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS), default="no_shared")
    ap.add_argument("--rate-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    def ints(text):
        return [int(s) for s in text.split(",") if s]
    low_seeds = {"control": ("fp8", set(ints(args.control_seeds))),
                 "ref_bf16": ("bf16", set(ints(args.bf16_seeds)))}
    fault_seeds, rate_seeds = ints(args.fault_seeds), ints(args.rate_seeds)
    parts = run.resolve_cell(root, run.load_manifest(root), args.workload)
    limits = parts["traffic"]["limits"]
    run.acquire_devices(int(parts["cell"]["chips"]), args.allow_cpu)
    run.use_compile_cache(root)
    for seed in ints(args.seeds) + fault_seeds + rate_seeds:
        ctx = run.Context(seed, parts["config"], parts["traffic"], 0)
        cell = parts["driver"].Cell(ctx)
        if seed in fault_seeds:
            sound = cell.weights
            cell.weights = lambda: FAULTS[args.fault](sound())
        cell.setup()
        window = cell.run(args.seconds)
        cell.free()
        out = {"seed": seed, "e2e": window["e2e"], "failed": window["failed"],
               "attempted": window["attempted"],
               "share_pairs_per_position": _share_load(window["counters"])}
        if seed not in rate_seeds:
            prompts, served = cell.sampled()
            wants = reference_answers(ctx.config, seed, prompts, served)
            side = "fault:" + args.fault if seed in fault_seeds else "program"
            out[side] = judged(numbers(prompts, served, wants), limits)
            for name, (quant, seeds) in low_seeds.items():
                if seed in seeds:
                    low = reference_answers(ctx.config, seed, prompts, served,
                                            quant)
                    out[name] = judged(numbers(
                        prompts, control_answers(low), wants), limits)
        print("READING " + json.dumps(out), flush=True)
        del cell, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
