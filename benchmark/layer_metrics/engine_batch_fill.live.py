"""Real rows over bucket rows, from the engine's counters. The reference has no such metric."""
from benchmark.metrics_lib import batch_fill as read  # noqa: F401
