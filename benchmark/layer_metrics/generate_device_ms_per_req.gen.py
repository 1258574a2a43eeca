"""Device-busy milliseconds a generate request answered in the window. The reference has no such metric."""
from benchmark.metrics_lib import device_ms_per


def read(rec):
    return device_ms_per(rec, rec.window.get("images"))
