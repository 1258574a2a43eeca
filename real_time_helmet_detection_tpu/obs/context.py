"""Host-context sampler: the confounders behind cross-run wall-clock deltas.

The reference has no analogue (it never measures anything but its own
meters, ref train.py:92-140). Host-side timings (loader waits, dispatch,
fetch, CPU test walls) move with whatever else the machine is doing, so
every timing artifact carries the load average it was measured under.

`sample_context()` is stdlib-only and never raises.
"""

from __future__ import annotations

import os


def sample_context() -> dict:
    """One best-effort snapshot: {loadavg, ncpu}. Missing facilities
    degrade to None, never raise — a sampler that can kill the run it is
    observing is worse than none."""
    sample: dict = {"ncpu": os.cpu_count()}
    try:
        sample["loadavg"] = [round(x, 2) for x in os.getloadavg()]
    except OSError:
        sample["loadavg"] = None
    return sample
