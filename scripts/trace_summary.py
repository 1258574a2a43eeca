"""Device seconds by layer: a profiler trace joined with a scope map.

The reference has no profiling tooling (SURVEY.md §5 — its timing is the
per-segment AverageMeters of ref train.py:92-140); this is the trace-side
instrument.

A device-only trace (`.xplane.pb`, what `benchmark/run.py --trace 1` and
`scripts/layer_trace.py` record) names each device event after its HLO
instruction and nothing else. The program's compiled executables know
which layer each instruction belongs to (`ServingEngine.scope_maps()`,
the step runner's `scope_map()`; obs/hlo_scopes.py). This joins the two:

    python scripts/trace_summary.py <trace.xplane.pb | trace dir> \
        --scopes scope_map.json [--program KEY] [--window-ns A B] [--top N]

prints device seconds by layer, forward and backward apart, `other` (named
by the program, outside every layer) and `unattributed` (no name reached
it) last with their shares of busy time, then the top operations with the
layer that owns each. `--scopes` is `{instruction: layer}` or
`{key: {instruction: layer}}` (a dump of `scope_maps()`: `--program` picks
the bucket; a single key is taken as is).

Time is SELF time: an event that encloses others on the same line (a
`while` over its body's operations) counts only what its children do not
cover, so nothing is counted twice. `Async XLA Ops` (copy-start/done pairs
that overlap the compute) are left out, as `benchmark/trace_reduce.py`
leaves them out of busy time. `--window-ns` clips to an interval on the
trace's clock (`trace_reduce.clock_offset_ns` brings host stamps there).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.trace_reduce import (find_xplane, op_instance,  # noqa: E402
                                    read_planes)

UNATTRIBUTED, OTHER = "unattributed", "other"


def self_times(ops: List[Tuple[str, float, float]],
               window: Optional[Tuple[float, float]] = None
               ) -> List[Tuple[str, float]]:
    """[(event name, self ns)] of one line's events `(name, start, end)`:
    each event's duration inside `window` less what the events nested in
    it cover there."""
    if window:
        w0, w1 = window
        ops = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
               if b > w0 and a < w1]
    out: List[List] = []
    stack: List[Tuple[int, float]] = []  # (index into out, end)
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(b, stack[-1][1]) - a
        out.append([name, b - a])
        stack.append((len(out) - 1, b))
    return [(n, max(0.0, t)) for n, t in out]


def by_layer(devices: Dict[str, list], scopes: Dict[str, str],
             window: Optional[Tuple[float, float]] = None) -> dict:
    """{'layers': {layer: s}, 'ops': {instruction: s}, 'busy_s': s}, the
    mean over the traced chips."""
    layers: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    chips = max(1, len(devices))
    for events in devices.values():
        for name, ns in self_times(events, window):
            inst = op_instance(name)
            layer = scopes.get(inst, UNATTRIBUTED)
            layers[layer] = layers.get(layer, 0.0) + ns / chips / 1e9
            ops[inst] = ops.get(inst, 0.0) + ns / chips / 1e9
    return {"layers": layers, "ops": ops, "busy_s": sum(layers.values())}


def load_scopes(path: str, program: Optional[str] = None) -> Dict[str, str]:
    with open(path) as f:
        scopes = json.load(f)
    if scopes and all(isinstance(v, dict) for v in scopes.values()):
        if program is None and len(scopes) == 1:
            program = next(iter(scopes))
        if program not in scopes:
            raise SystemExit("trace_summary: --program must be one of %s"
                             % sorted(scopes))
        scopes = scopes[program]
    return scopes


def open_trace(path: str) -> Dict[str, list]:
    """Device plane -> XLA Ops events of a trace file, or of the newest
    `.xplane.pb` under a directory."""
    if os.path.isdir(path):
        found = find_xplane(path)
        if not found:
            raise SystemExit("trace_summary: no .xplane.pb under %s" % path)
        path = found
    return read_planes(path)[0]


def render(summary: dict, scopes: Dict[str, str], top: int) -> str:
    busy = summary["busy_s"] or 1e-30
    last = (OTHER, UNATTRIBUTED)
    rows = sorted((kv for kv in summary["layers"].items()
                   if kv[0] not in last), key=lambda kv: -kv[1])
    rows += [(k, summary["layers"].get(k, 0.0)) for k in last]
    lines = ["device seconds by layer (self time; busy %.4f s)" % busy]
    lines += ["  %-18s %10.4f s  %5.1f%%" % (k, v, 100.0 * v / busy)
              for k, v in rows]
    lines.append("top operations")
    for inst, s in sorted(summary["ops"].items(),
                          key=lambda kv: -kv[1])[:top]:
        lines.append("  %-40s %10.4f s  %5.1f%%  %s"
                     % (inst[:40], s, 100.0 * s / busy,
                        scopes.get(inst, UNATTRIBUTED)))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--scopes", required=True)
    ap.add_argument("--program")
    ap.add_argument("--window-ns", type=float, nargs=2)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", help="also write the summary here")
    args = ap.parse_args(argv)
    scopes = load_scopes(args.scopes, args.program)
    summary = by_layer(open_trace(args.trace), scopes,
                       tuple(args.window_ns) if args.window_ns else None)
    print(render(summary, scopes, args.top))
    if args.json:
        from real_time_helmet_detection_tpu.utils import save_json
        save_json(args.json, summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
