"""The program's one record a served batch: `serve:deliver` (serving/engine.py,
PR 38), a span of the process ring (`obs/spans.py`) whatever tracer the engine
was handed, meta `b` (bucket), `n` (real rows) and `counters` (the dict the
engine's `row_counters` gave for the batch, or None without one). The engine
writes it on its fetch thread, in batch order, and opens it before it feeds the
batch's counts into the registry and before it resolves any of its answers.

So a window's batches are exactly the records whose START lies in (`t0`,
`t1`): the window opens at the last answer of the lead-in's batch (whose record
started before it) and closes at the last answer of the last whole batch (whose
record started before that); the next batch's record starts after it. Their
`n` sum to the window's `images`, and their counters are the window's counts
whatever the driver's own counter list names, read at the batches' edges and
not at two instants.

A program from before the record (a parent commit) has none: every reader
then returns None, as it does where the ring has lost the window's start or a
batch's counters lack a name asked for.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .program_spans import ring_spans

DELIVER = "serve:deliver"


def window_batches(rec) -> Optional[List[Tuple[float, float, dict]]]:
    """`(start, seconds, meta)` of each `serve:deliver` record whose start lies
    in (`t0`, `t1`), oldest first; None without a window start, without a ring
    or with its start lost, or with no such record at all."""
    t0 = rec.window.get("t0")
    if t0 is None:
        return None
    t1 = rec.window.get("t1", t0 + rec.window.get("window_s", 0.0))
    got = ring_spans(t0)
    if got is None:
        return None
    records = [(s, d, meta or {}) for n, s, d, meta in got if n == DELIVER]
    if not records:
        return None
    return [r for r in records if t0 < r[0] < t1]


def counter_sums(rec, names: Sequence[str]) -> Optional[dict]:
    """{name: sum over the window's batches} of the counters `names`; None
    where the window holds no batch or a batch's counters lack a name."""
    batches = window_batches(rec)
    if not batches:
        return None
    sums = dict.fromkeys(names, 0)
    for _, _, meta in batches:
        counts = meta.get("counters")
        if not counts or any(name not in counts for name in names):
            return None
        for name in names:
            sums[name] += counts[name]
    return sums


def share(rec, part: str, whole: str) -> Optional[float]:
    """100 x the window's sum of counter `part` over that of `whole`; None
    where `whole` sums to 0 or is missing."""
    sums = counter_sums(rec, (part, whole))
    if not sums or not sums[whole]:
        return None
    return 100.0 * sums[part] / sums[whole]


def median_ms(rec) -> Optional[float]:
    """Median `serve:deliver` milliseconds over the window's batches."""
    batches = window_batches(rec)
    if not batches:
        return None
    return 1e3 * float(np.median([d for _, d, _ in batches]))
