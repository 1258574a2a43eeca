"""Median latency from the due instant, from the benchmark's stamps. The reference has no such metric."""
from benchmark.metrics_lib import window_percentile


def read(rec):
    return window_percentile(rec, "latency_ms", 50)
