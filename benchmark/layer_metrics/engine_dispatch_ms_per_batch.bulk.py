"""The dispatcher thread's serial host time a batch: median `serve:batch-form` + `serve:h2d` + `serve:dispatch` (the engine's own spans). When it reaches the device's batch period the host sets the pace. The reference has no such metric."""
from benchmark.metrics_lib import engine_span_percentile_ms

STAGES = ("serve:batch-form", "serve:h2d", "serve:dispatch")


def read(rec):
    medians = [engine_span_percentile_ms(rec, name, 50) for name in STAGES]
    return None if None in medians else sum(medians)
