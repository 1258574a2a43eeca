"""Median of the engine's own `serve:device-wait` span (`block_until_ready` on a dispatched batch): the batch period as the host sees it. In the cell whose pace the host sets (`serve_img_per_s.hostbound`): `engine_device_wait_ms_per_batch.bulk` read there. The reference has no such metric."""
from benchmark.metrics_lib import engine_span_percentile_ms


def read(rec):
    return engine_span_percentile_ms(rec, "serve:device-wait", 50)
