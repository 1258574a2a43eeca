"""Host milliseconds per step inside the step runner's own `h2d` span (the sharded device_put of `stage`), over the window. The reference has no such metric."""
from benchmark.program_spans import window_ms_per_step


def read(rec):
    return window_ms_per_step(rec, "h2d")
