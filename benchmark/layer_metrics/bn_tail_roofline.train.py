"""BN(+add)+activation kernels, forward and backward: share of the HBM roofline. The reference has no such metric."""
from benchmark.metrics_lib import bn_tail_roofline


def read(rec):
    return bn_tail_roofline(rec, rec.window.get("images"))
