"""Host milliseconds per step inside the step runner's own `dispatch` span (the call of the jitted step), over the window. The reference has no such metric."""
from benchmark.program_spans import window_ms_per_step


def read(rec):
    return window_ms_per_step(rec, "dispatch")
