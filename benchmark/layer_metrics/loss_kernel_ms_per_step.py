"""Device milliseconds per step in `detection_loss_fwd` + `detection_loss_bwd`. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms


def read(rec):
    ms, steps = kernel_ms(rec, lambda n: n.startswith("detection_loss_")), \
        rec.window.get("steps")
    return ms / steps if ms and steps else None
