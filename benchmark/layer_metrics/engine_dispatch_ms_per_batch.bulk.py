"""The dispatcher thread's serial host time a batch: median `serve:batch-form` + `serve:h2d` + `serve:dispatch` (the engine's own spans). When it reaches the device's batch period the host sets the pace. The reference has no such metric."""
from benchmark.metrics_lib import dispatcher_ms_per_batch as read  # noqa: F401
