"""The train step's share of the bf16 peak: fwd+bwd conv FLOPs per image x img/s. The reference has no such metric."""
from benchmark.metrics_lib import mfu


def read(rec):
    return mfu(rec, rec.e2e.get("train_img_per_s"), train=True)
