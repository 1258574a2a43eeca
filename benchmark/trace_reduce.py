"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle time
over the measured window, device time by kernel, the device operations that
took most time, and the longest idle gaps set against what the host was doing.

Read with `jax.profiler.ProfileData` alone. What a TPU v5e trace looks like
(jax 0.9.0, libtpu 0.0.34; looked at by hand before this was written):

* one plane per chip, `/device:TPU:<n>`, with lines `Steps`, `XLA Modules`
  (one event per program execution), `XLA Ops` (one event per operation that
  ran; a Pallas kernel is a `custom-call` named after its `name=`, e.g.
  `%bn_act_fwd.21 = bf16[...] custom-call(...)`) and `Async XLA Ops`
  (copy-start/done pairs that overlap the others: not counted as busy);
* every event has `start_ns` and `duration_ns` on the trace's own clock;
* host events (`/host:CPU`) are NOT taken: with the host tracer on at any
  level the traced train step ran at 38% of its untraced rate (the device
  idle 63% inside the host's loss fetch; my chip runs, PR 24), with it off at
  100%. So the benchmark's and the engine's host spans stay on the host's
  monotonic clock, and the two clocks are tied by marks: a tiny jitted
  program (`jit_bench_mark`) run, and waited for, on an idle device just
  after the trace starts and just before it stops. Its `XLA Modules` event
  gives the trace's time of an instant the host also stamped.

Busy is the union of the `XLA Ops` intervals inside the window; the window is
given by the caller (host stamps brought onto the trace's clock), else first
to last device event.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

OPS_LINE, MODULES_LINE, WINDOW_SPAN = "XLA Ops", "XLA Modules", "bench:window"
MARK_MODULE = "jit_bench_mark"
_SUFFIX = re.compile(r"\.\d+$")


def op_instance(event_name: str) -> str:
    """'%bn_act_fwd.21 = bf16[...] custom-call(...)' -> 'bn_act_fwd.21'."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_name(event_name: str) -> str:
    """... -> 'bn_act_fwd': the kernel's `name=`, all its call sites."""
    return _SUFFIX.sub("", op_instance(event_name))


def _union(intervals: List[Tuple[float, float]]):
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def read_planes(path: str):
    """(device planes -> [(name, start_ns, end_ns)] of XLA Ops, the marks'
    start_ns on the first device, ascending)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    marks: List[float] = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[plane.name] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
            elif line.name == MODULES_LINE and not marks:
                marks = sorted(e.start_ns for e in line.events
                               if e.name.startswith(MARK_MODULE))
    return devices, marks


def clock_offset_ns(marks_trace_ns: List[float],
                    marks_host_s: List[float]) -> Optional[float]:
    """trace clock minus host clock, ns, from the marks both sides saw (the
    i-th of each); None without a common mark."""
    pairs = list(zip(marks_trace_ns, marks_host_s))
    if not pairs:
        return None
    return sum(t - h * 1e9 for t, h in pairs) / len(pairs)


def spans_on_trace_clock(spans, offset_ns: float):
    """[(name, start s, seconds)] on the host clock -> [(name, start_ns,
    end_ns)] on the trace's."""
    return [(n, s * 1e9 + offset_ns, (s + d) * 1e9 + offset_ns)
            for n, s, d in spans]


def reduce_events(devices: Dict[str, list], spans: list,
                  window: Optional[Tuple[float, float]] = None,
                  top: int = 10) -> Optional[dict]:
    """The reduction proper, on plain lists (so a test can feed it by hand):
    `spans` and `window` in ns on the trace's clock. Returns None when no
    device operation was traced."""
    if not any(devices.values()):
        return None
    if window:
        w0, w1 = window
    else:
        w0 = min(a for ops in devices.values() for _, a, _ in ops)
        w1 = max(b for ops in devices.values() for _, _, b in ops)
    busy_ns, by_op, by_inst, gaps = [], {}, {}, []
    for ops in devices.values():
        clipped = [(name, max(a, w0), min(b, w1)) for name, a, b in ops
                   if b > w0 and a < w1]
        merged = _union([(a, b) for _, a, b in clipped])
        busy_ns.append(sum(b - a for a, b in merged))
        for name, a, b in clipped:
            for table, key in ((by_op, op_name(name)),
                               (by_inst, op_instance(name))):
                table[key] = table.get(key, 0.0) + (b - a)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    chips = len(busy_ns)
    # what the host was doing in each gap: the innermost benchmark span over
    # the gap's midpoint
    inner = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[2] - s[1])
    idle_by: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        name = next((n for n, s0, s1 in inner if s0 <= mid <= s1), "no-span")
        idle_by[name] = idle_by.get(name, 0.0) + (b - a)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / chips / 1e9,
        "op_ms": {k: v / chips / 1e6 for k, v in by_op.items()},
        "device_ops": [[k, v / chips / 1e9] for k, v in rank(by_inst)],
        "idle_gaps": [[k, v / chips / 1e9] for k, v in rank(idle_by)],
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0) / 1e9,
    }


def reduce_trace(path: str, host_spans=(), marks_host_s=(),
                 window_host_s: Optional[Tuple[float, float]] = None
                 ) -> Optional[dict]:
    """Reduce the trace at `path`. `host_spans` [(name, start s, seconds)],
    `marks_host_s` and `window_host_s` are on the host's monotonic clock."""
    devices, marks = read_planes(path)
    offset = clock_offset_ns(marks, list(marks_host_s))
    if offset is None:
        return reduce_events(devices, [])
    window = window_host_s and tuple(t * 1e9 + offset for t in window_host_s)
    return reduce_events(devices, spans_on_trace_clock(host_spans, offset),
                         window)


def find_xplane(trace_dir: str) -> Optional[str]:
    import glob
    import os
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None
