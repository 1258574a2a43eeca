"""Arithmetic the per-layer readers share. A reader (one file per metric under
benchmark/layer_metrics/) takes the traced run's record and returns a number,
or None where it finds nothing to read: the harness then leaves the metric out
of the line. Nothing here returns 0 for a share of a roofline or of a peak.

The record (run.py builds it): `cell`, `config` (the configuration's fields),
`traffic` (the mix's parameters), `window` (what the driver's run returned),
`e2e` (this run's end-to-end values), `trace` (trace_reduce's output), `spans`
(the benchmark's host spans: name, start, seconds), `engine_spans` (the
program's spans the engine handed to the benchmark's tracer: name, seconds),
`peaks` (this chip's row of peaks.json).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .work import count


def is_bn_tail_kernel(name: str) -> bool:
    """The BatchNorm(+add)+activation kernels of ops/pallas/epilogue.py and
    residual.py, by their `name=`."""
    return (name == "bn_stats" or name.startswith("bn_act_")
            or name.startswith("bn_add_act_"))


def _imsize(rec) -> int:
    return int(rec.config["imsize"])


def span_ms_per_step(rec, name: str) -> Optional[float]:
    """Host milliseconds a step spent in the benchmark's span `name`."""
    t0, steps = rec.window.get("t0"), rec.window.get("steps")
    inside = [d for n, s, d in rec.spans if n == name and s >= t0]
    if not inside or not steps:
        return None
    return 1e3 * float(np.sum(inside)) / steps


def mfu(rec, img_per_s: Optional[float], train: bool) -> Optional[float]:
    """The whole step's share of the chip's bf16 peak, %: conv FLOPs the
    configuration needs per image (no recompute) x images per second."""
    if not img_per_s or not rec.peaks:
        return None
    flops = count.conv_flops_per_image(rec.config, _imsize(rec), train)
    return 100.0 * flops * img_per_s / rec.peaks["bf16_flops_per_s"]


def device_ms_per(rec, units: Optional[float]) -> Optional[float]:
    """Device-busy milliseconds per unit of work (step, image)."""
    if not units or not rec.trace:
        return None
    return 1e3 * rec.trace["busy_s"] / units


def kernel_ms(rec, match) -> Optional[float]:
    """Device milliseconds of the kernels whose name `match` accepts; None
    where none ran (the program chose another implementation)."""
    hits = [ms for name, ms in rec.trace["op_ms"].items() if match(name)]
    return float(np.sum(hits)) if hits else None


def bn_tail_roofline(rec, images: Optional[float]) -> Optional[float]:
    """Share of the HBM roofline the train step's BN-tail kernels reached, %:
    the bytes every BatchNorm(+add)+activation tail has to move forward and
    backward (work/count.py) at the chip's bandwidth, over the device time of
    those kernels. Bound by bandwidth: a tail does a handful of operations
    per byte."""
    ms = kernel_ms(rec, is_bn_tail_kernel)
    if not ms or not images or not rec.peaks:
        return None
    need = count.bn_tail_bytes_per_image(rec.config, _imsize(rec))
    least_s = need * images / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)


def batch_fill(rec) -> Optional[float]:
    """Real rows over bucket rows in the window, %, from the engine's own
    counters."""
    c = rec.window.get("counters") or {}
    if not c.get("batch_slots"):
        return None
    return 100.0 * (c["batch_slots"] - c["padded_slots"]) / c["batch_slots"]


def engine_span_percentile_ms(rec, name: str, q: float) -> Optional[float]:
    values = [d for n, d in rec.engine_spans if n == name]
    return 1e3 * float(np.percentile(values, q)) if values else None


DISPATCHER_STAGES = ("serve:batch-form", "serve:h2d", "serve:dispatch")


def dispatcher_ms_per_batch(rec) -> Optional[float]:
    """The dispatcher thread's serial host time a batch: the medians of the
    engine's own spans of its three stages, summed."""
    medians = [engine_span_percentile_ms(rec, name, 50)
               for name in DISPATCHER_STAGES]
    return None if None in medians else sum(medians)


def peak_kernel_ms_per_image(rec) -> Optional[float]:
    """Device milliseconds an image answered in the `peak_scores` Pallas
    kernel. `in`, not `==`: the kernel runs under vmap and batch_parallel, and
    jax may decorate such names."""
    ms = kernel_ms(rec, lambda n: "peak_scores" in n)
    images = rec.window.get("images")
    return ms / images if ms and images else None


def window_percentile(rec, key: str, q: float) -> Optional[float]:
    values = rec.window.get(key)
    if values is None or not len(values):
        return None
    return float(np.percentile(values, q))


def completed_img_per_s(rec) -> Optional[float]:
    if not rec.window.get("images"):
        return None
    return rec.window["images"] / rec.window["window_s"]
