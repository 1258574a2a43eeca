"""Share of the HBM roofline the `kda_step` Pallas calls (ops/pallas/kda.py: one decode step of a row through a KDA layer's float32 state, every head) reached in a cell of the linear-hybrid decoder: gen.linear_state_steps x the least bytes of a visit (the state read and written, q, k, g, v and beta in, o out: benchmark/work/hybrid_moe_decoder.py) at the chip's bandwidth, over the kernel's device time. Bandwidth-bound. None where the kernel did not run. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms


def read(rec):
    c = rec.window.get("counters") or {}
    ms = kernel_ms(rec, lambda name: "kda_step" in name)
    if not ms or not c.get("gen.linear_state_steps") or not rec.peaks:
        return None
    from benchmark.work.hybrid_moe_decoder import kda_step_bytes
    least_s = (c["gen.linear_state_steps"] * kda_step_bytes(rec.config)
               / rec.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
