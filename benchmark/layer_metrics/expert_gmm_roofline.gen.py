"""Share of the roofline the `expert_gmm` Pallas calls reached: max(FLOPs / peak, least bytes / bandwidth) of the window's grouped matmuls (benchmark/work/latent_moe_decoder.py) over their device time. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms
from benchmark.work.latent_moe_decoder import gmm_work


def read(rec):
    c = rec.window.get("counters") or {}
    ms = kernel_ms(rec, lambda name: "expert_gmm" in name)
    if not ms or not c.get("gen.requests") or not rec.peaks:
        return None
    flops, least_bytes = gmm_work(rec.config, c, rec.traffic["new_tokens"])
    least_s = max(flops / rec.peaks["bf16_flops_per_s"],
                  least_bytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
