"""The readings the limits of `correct` were set from (PERF.md gives them).
Not run by the benchmark's own runs; run by hand on the chip:

    python3 -m benchmark.calibrate --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 3]
    python3 -m benchmark.calibrate --workload <open-loop cell> --seeds 1 \\
        --rates 480,430,390 --seconds 10      # the sweep behind its rate

For each seed, in one process: set the cell up, drive a short window at the
cell's own size and load, and print every compared number for

* `program`  - the timed path against the reference (the lower reading);
* `control`  - the reference computed in fp8 (e4m3), the nearest precision
  below the bfloat16 the configurations state, put in the program's place;
* training only, `half_batch` - the reference at bfloat16 with half of the
  batch left out and the mean taken over the rest, in the program's place.
  (A step that returns its state unchanged reads 1 on the change of the
  parameters by construction and needs no run.)

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import argparse
import json
import sys
import types

import numpy as np

from . import compare, run


def serve_readings(cell, control: bool):
    picks = cell.sample(cell.first)
    served = [cell.futs[i].result() for i in picks]
    maps = cell.reference_maps(picks)
    cfg = cell.ctx.config
    out = {"program": compare.serve_numbers(cfg, served, maps),
           "score_map": {"p50": float(np.median(maps[0]["score"])),
                         "kth": float(maps[0]["kth"][0]),
                         "max": float(maps[0]["score"].max())}}
    if control:
        out["control"] = compare.serve_numbers(
            cfg, control_answers(cfg, cell.reference_maps(picks, "fp8")),
            maps)
    return out


def control_answers(cfg, maps):
    """What a server computing in the control's precision would answer: its
    own top-k of its own peaks, its own boxes, its own NMS."""
    from .reference import model as ref
    topk, soft = int(cfg.get("topk", 100)), cfg.get("nms") == "soft-nms"
    answers = []
    for m in maps:
        boxes, classes, scores = [], [], []
        for s in range(m["score"].shape[0]):
            h, w, c = m["score"][s].shape
            peaks = np.where(m["score"][s] == m["nbr_max"][s],
                             m["score"][s], 0.0)
            flat = peaks.transpose(2, 0, 1).reshape(-1)
            top = np.argsort(-flat, kind="stable")[:topk]
            classes.append(top // (h * w))
            boxes.append(m["boxes"][s].reshape(-1, 4)[top % (h * w)])
            scores.append(flat[top])
        boxes, scores = np.concatenate(boxes), np.concatenate(scores)
        if soft:
            keep, scores = ref.soft_nms(boxes, scores)
        else:
            keep, _ = ref.hard_nms(boxes, scores, float(cfg.get("nms_th",
                                                                0.5)))
        answers.append(types.SimpleNamespace(
            boxes=boxes, classes=np.concatenate(classes), scores=scores,
            valid=keep))
    return answers


def sweep(parts, seed, rates, seconds) -> int:
    """The sweep that fixes an open-loop cell's rate: one engine, each rate
    offered for `seconds`; per rate the requests still unanswered when the
    window closed (a backlog that grows with the window means the rate is
    over what the system sustains), the tail and what completed."""
    ctx = run.Context(seed, parts["config"], dict(parts["traffic"]), 0)
    cell = parts["driver"].Cell(ctx)
    cell.setup()
    for rate in rates:
        cell.p["rate_per_s"] = rate
        w = cell.run(seconds)
        lat = w["latency_ms"]
        half = len(lat) // 2
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "backlog_at_close": w["backlog_at_close"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
            "completed_per_s": w["images"] / seconds,
            "failed": w["failed"], "attempted": w["attempted"],
            "batches": w["counters"]["batches_total"],
            "fill": 1 - w["counters"]["padded_slots"]
            / max(1, w["counters"]["batch_slots"])}), flush=True)
    cell.free()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--rates", default="", help="open loop only: sweep "
                    "these rates (frames/s) in one process, one seed")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    manifest = run.load_manifest(run.ROOT)
    parts = run.resolve_cell(run.ROOT, manifest, args.workload)
    run.acquire_devices(int(parts["cell"]["chips"]), args.allow_cpu)
    run.use_compile_cache(run.ROOT)
    if args.rates:
        return sweep(parts, seeds[0], [float(r) for r in
                                       args.rates.split(",")], args.seconds)
    for seed in seeds:
        ctx = run.Context(seed, parts["config"], parts["traffic"], 0)
        cell = parts["driver"].Cell(ctx)
        cell.setup()
        window = cell.run(args.seconds)
        if parts["traffic"]["driver"] == "train":
            cell.free()
            want = cell.reference()
            out = {"program": compare.train_numbers(cell.got, want),
                   "losses": {"program": cell.got["losses"],
                              "reference": want["losses"]},
                   "worst_grad": compare.worst_leaves(
                       cell.got["grad_norms"], want["grad_norms"],
                       compare.moved_leaves(want["grad_norms"])),
                   "worst_change": compare.worst_leaves(
                       cell.got["change_norms"], want["change_norms"],
                       compare.moved_leaves(want["grad_norms"]))}
            if seed in controls:
                out["control"] = compare.train_numbers(
                    cell.reference(quant="fp8"), want)
            if seed in faults:
                out["half_batch"] = compare.train_numbers(
                    cell.reference(quant="bf16",
                                   rows=slice(0, cell.batch // 2)), want)
                out["ref_bf16"] = compare.train_numbers(
                    cell.reference(quant="bf16"), want)
        else:
            cell.free()
            out = serve_readings(cell, seed in controls)
        out.update(seed=seed, e2e=window["e2e"],
                   failed=window["failed"], attempted=window["attempted"])
        print("READING " + json.dumps(out), flush=True)
        del cell, ctx
    return 0


if __name__ == "__main__":
    sys.exit(main())
