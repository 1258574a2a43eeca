"""Predict's share of the bf16 peak at the offered rate: forward conv FLOPs x completed img/s. The reference has no such metric."""
from benchmark.metrics_lib import completed_img_per_s, mfu


def read(rec):
    return mfu(rec, completed_img_per_s(rec), train=False)
