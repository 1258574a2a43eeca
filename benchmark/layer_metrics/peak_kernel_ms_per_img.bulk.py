"""Device milliseconds per image answered in the `peak_scores` Pallas kernel (sigmoid + 3x3 peak test). The reference has no such metric."""
from benchmark.metrics_lib import peak_kernel_ms_per_image as read  # noqa: F401
