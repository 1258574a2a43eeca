"""What the readers of `program_span` metrics share: the program's own
flight recorder (`obs/spans.py`: one in-memory ring of `(name, t0 on
time.monotonic(), seconds, meta)` for the whole process), read over the
measured window or over set-up. The train and set-up readers take the
program's default tracer directly: that is the channel, since the harness
hands the train runner no tracer.

A program that has no such ring (a parent commit from before it) gives
`None` everywhere, and so does a ring that has overwritten part of what was
asked for: a reader then leaves its metric out of the line, never reports
a partial sum.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple


def ring_spans(since: float) -> Optional[List[Tuple]]:
    """The ring's spans that started at or after `since` (monotonic
    seconds), oldest first; None without a ring or with its start lost."""
    try:
        from real_time_helmet_detection_tpu.obs.spans import default_tracer
    except ImportError:
        return None
    return default_tracer().snapshot(since=since)


def window_ms_per_step(rec, name: str) -> Optional[float]:
    """Host milliseconds a step inside the program's span `name`, over the
    spans that started within the window."""
    t0, steps = rec.window.get("t0"), rec.window.get("steps")
    if t0 is None or not steps:
        return None
    spans = ring_spans(t0)
    if spans is None:
        return None
    t1 = t0 + rec.window["window_s"]
    inside = [d for n, s, d, _ in spans if n == name and s <= t1]
    return 1e3 * sum(inside) / steps if inside else None


def _union_s(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def compile_s_before_window(rec, stages: Tuple[str, ...]) -> Optional[float]:
    """Seconds of set-up inside jax's compile stages `stages` (`trace`,
    `lower`, `backend`): the union of the program's `compile` spans of
    those stages that ended before the window opened (a nested jit's
    tracing lies inside its caller's and is not counted twice). Counts
    from the install of the program's compile listener (its first step
    runner or engine) on."""
    t0 = rec.window.get("t0")
    spans = None if t0 is None else ring_spans(0.0)
    if spans is None:
        return None
    took = [(s, s + d) for n, s, d, meta in spans
            if n == "compile" and meta and meta.get("stage") in stages
            and s + d <= t0]
    return _union_s(took) if took else None
