"""Median of the engine's own `serve:device-wait` span (`block_until_ready` on a dispatched batch): the batch period as the host sees it. The reference has no such metric."""
from benchmark.metrics_lib import engine_span_percentile_ms


def read(rec):
    return engine_span_percentile_ms(rec, "serve:device-wait", 50)
