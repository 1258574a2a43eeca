"""Real rows over bucket rows, from the engine's counters. In the cell whose pace the host sets (`serve_img_per_s.hostbound`): `engine_batch_fill.bulk` read there. The reference has no such metric."""
from benchmark.metrics_lib import batch_fill as read  # noqa: F401
