"""Median of the engine's own `serve:queue-wait` span (submit to batch formed). The reference has no such metric."""
from benchmark.metrics_lib import engine_span_percentile_ms


def read(rec):
    return engine_span_percentile_ms(rec, "serve:queue-wait", 50)
