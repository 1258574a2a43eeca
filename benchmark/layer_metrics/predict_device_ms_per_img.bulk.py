"""Device-busy milliseconds per image answered, from the trace. The reference has no such metric."""
from benchmark.metrics_lib import device_ms_per


def read(rec):
    return device_ms_per(rec, rec.window.get("images"))
