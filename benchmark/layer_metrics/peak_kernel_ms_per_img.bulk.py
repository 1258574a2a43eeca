"""Device milliseconds per image answered in the `peak_scores` Pallas kernel (sigmoid + 3x3 peak test). `in`, not `==`: the kernel runs under vmap and batch_parallel, and jax may decorate such names. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms


def read(rec):
    ms, images = kernel_ms(rec, lambda n: "peak_scores" in n), \
        rec.window.get("images")
    return ms / images if ms and images else None
