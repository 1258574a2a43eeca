"""Share of the roofline the `expert_gmm` Pallas calls reached in a cell of the linear-hybrid decoder: max(FLOPs / peak, least bytes / bandwidth) of the window's grouped matmuls over their device time, the least bytes counting an expert's weights (11.8 MB at the published sizes) once a visit (gen.expert_visits; benchmark/work/hybrid_moe_decoder.py), each pair's rows in and out. Bandwidth-bound where decode does the work. None where the program has no linear-attention counters. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms


def read(rec):
    c = rec.window.get("counters") or {}
    ms = kernel_ms(rec, lambda name: "expert_gmm" in name)
    if not ms or not c.get("gen.expert_visits") \
            or not c.get("gen.linear_state_steps") or not rec.peaks:
        return None
    from benchmark.work.hybrid_moe_decoder import gmm_work
    flops, least_bytes = gmm_work(rec.config, c)
    least_s = max(flops / rec.peaks["bf16_flops_per_s"],
                  least_bytes / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
