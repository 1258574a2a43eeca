"""How late the generator submitted (submit - due), 95th percentile. The reference has no such metric."""
from benchmark.metrics_lib import window_percentile


def read(rec):
    return window_percentile(rec, "late_ms", 95)
