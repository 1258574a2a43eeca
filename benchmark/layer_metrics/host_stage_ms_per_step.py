"""Host milliseconds per step inside the benchmark's span around `runner.stage` (the H2D feed). The reference has no such metric."""
from benchmark.metrics_lib import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "bench:stage")
