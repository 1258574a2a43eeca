"""Device-busy milliseconds per image answered, from the trace. In the cell whose pace the host sets (`serve_img_per_s.hostbound`): `predict_device_ms_per_img.bulk` read there. The reference has no such metric."""
from benchmark.metrics_lib import device_ms_per


def read(rec):
    return device_ms_per(rec, rec.window.get("images"))
