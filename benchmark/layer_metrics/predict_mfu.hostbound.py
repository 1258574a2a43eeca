"""Predict's share of the bf16 peak: forward conv FLOPs per image x completed img/s. In the cell whose pace the host sets (`serve_img_per_s.hostbound`): `predict_mfu.bulk` read there. The reference has no such metric."""
from benchmark.metrics_lib import mfu


def read(rec):
    return mfu(rec, rec.e2e.get("serve_img_per_s"), train=False)
