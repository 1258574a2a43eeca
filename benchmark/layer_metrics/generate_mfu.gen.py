"""The whole generate step's share of the bf16 peak: the matrix-product FLOPs of the requests the window answered (benchmark/work/latent_moe_decoder.py, from the program's gen.* counters) over the window, against the chip's peak. The reference has no such metric."""
from benchmark.work.latent_moe_decoder import window_flops


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.requests") or not rec.peaks:
        return None
    return (100.0 * window_flops(rec.config, c) / rec.window["window_s"]
            / rec.peaks["bf16_flops_per_s"])
