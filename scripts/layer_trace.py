"""Record one traced window of a benchmark cell and keep what reads it by
layer: the device trace, the program's scope map, the window on the
trace's clock.

The reference has no profiling tooling (ref train.py:92-140 keeps
per-segment meters only). `python3 -m benchmark.run --trace 1` reduces its
trace to the per-layer metrics and throws it away; the ledger's
`breakdown.device_ops` then names device time `fusion.276`. This drives
the same cell the same way (the harness's own set-up, marks, profiler
options and window), asks the program for its scope map afterwards
(`ServingEngine.scope_maps()`, the step runner's `scope_map()`), and
leaves under `--out`:

    <cell>.xplane.pb     the device-only trace (with --keep-trace: tens of MB)
    <cell>.scopes.json   {program: {instruction: layer}}
    <cell>.window.json   {"window_ns": [a, b], "window_s", "steps"|"images",
                          "compile_spans_in_window", "gen_counters" (the
                          generate cells: q blocks run / fused / held)}
    <cell>.layers.txt    scripts/trace_summary.py's table over that window
    <cell>.layers.json   the same summary whole: seconds of every layer and
                         of every instruction, not the table's top 40

    python scripts/layer_trace.py --workload flagship-train-b32 --seed 7 \
        --seconds 20 --out chiprun_out/layers

Only on the chip (the harness refuses to start without one); nothing here
is a benchmark result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))


def record(workload: str, seed: int, seconds: float, out: str,
           keep_trace: bool = False, root: str = REPO,
           allow_cpu: bool = False) -> dict:
    """Run the cell traced; returns what it wrote: {'scopes', 'window',
    'trace' (was there a device plane)}. `root` and `allow_cpu` are for
    the test (a toy benchmark root, no device plane on the CPU)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark import run as bench
    from benchmark import trace_reduce
    from real_time_helmet_detection_tpu.obs.spans import default_tracer
    from real_time_helmet_detection_tpu.utils import (atomic_write_bytes,
                                                      save_json)
    import trace_summary

    manifest = bench.load_manifest(root)
    parts = bench.resolve_cell(root, manifest, workload)
    bench.acquire_devices(int(parts["cell"]["chips"]), allow_cpu)
    # NO persistent compile cache: its key leaves metadata out, so a hit
    # hands back the executable of whichever commit filled the entry, with
    # THAT commit's scope names. The map must come from this program's own
    # compile (minutes cold on the chip; this is a diagnostic run).
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ctx = bench.Context(seed, parts["config"], parts["traffic"], traced=True)
    cell = parts["driver"].Cell(ctx)
    cell.setup()
    trace_dir = os.path.join(REPO, "build", "layer_trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.block_until_ready(bench._bench_mark(0.0))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0  # as the harness: device events only
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    ctx.mark()
    try:
        window = cell.run(float(seconds))
    finally:
        ctx.mark()
        jax.profiler.stop_trace()
    if hasattr(cell, "engine"):
        scopes = {"bucket-%d" % b: m
                  for b, m in cell.engine.scope_maps().items()}
    else:
        staged = cell.runner.stage(cell.pool[0])
        scopes = {"step": cell.runner.scope_map(cell.state, staged)}
    cell.free()

    os.makedirs(out, exist_ok=True)
    base = os.path.join(out, workload)
    save_json(base + ".scopes.json", scopes)
    found = trace_reduce.find_xplane(trace_dir)
    note = {"window_s": window["window_s"],
            **{k: window[k] for k in ("steps", "images") if k in window}}
    # a compile inside the window is a `compile` span with a time in the
    # program's ring (obs/telemetry.py): there should be none
    t1 = window["t0"] + window["window_s"]
    ring = default_tracer().snapshot(since=window["t0"])
    note["compile_spans_in_window"] = None if ring is None else sum(
        n == "compile" and t0 <= t1 for n, t0, _, _ in ring)
    if hasattr(cell, "registry"):
        # the generate cells' counters no driver's result line carries: q
        # blocks of prefill attention run / fused / held, over the whole run
        note["gen_counters"] = {
            name: cell.registry.counter(name).value
            for name in ("gen.requests", "gen.q_blocks_run",
                         "gen.q_blocks_fused", "gen.q_blocks_total")}
    if found:
        if keep_trace:
            shutil.copyfile(found, base + ".xplane.pb")
        devices, marks = trace_reduce.read_planes(found)
        offset = trace_reduce.clock_offset_ns(marks, ctx.marks)
        if offset is not None:
            note["window_ns"] = [window["t0"] * 1e9 + offset,
                                 (window["t0"] + window["window_s"]) * 1e9
                                 + offset]
        # the window runs one program: the step, or the largest bucket
        program = sorted(scopes, key=lambda k: len(scopes[k]))[-1]
        summary = trace_summary.by_layer(
            devices, scopes[program],
            tuple(note["window_ns"]) if "window_ns" in note else None)
        text = trace_summary.render(summary, scopes[program], top=40)
        atomic_write_bytes(base + ".layers.txt", (text + "\n").encode())
        save_json(base + ".layers.json", summary)
        print(text)
    save_json(base + ".window.json", note)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    return {"scopes": scopes, "window": note, "trace": bool(found)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "layers"))
    ap.add_argument("--keep-trace", action="store_true")
    args = ap.parse_args(argv)
    record(args.workload, args.seed, args.seconds, args.out,
           keep_trace=args.keep_trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
