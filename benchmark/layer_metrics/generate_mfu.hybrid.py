"""The whole generate step's share of the bf16 peak in a cell of the linear-hybrid decoder: the matrix-product FLOPs of the requests the window answered (benchmark/work/hybrid_moe_decoder.py, from the program's gen.* counters: positions, pairs held, the latent layer's causal keys, the linear layers' prefill chunks and state steps) over the window, against the chip's peak. None where the program has no linear-attention counters. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.requests") or not c.get("gen.linear_state_steps") \
            or not rec.peaks:
        return None
    from benchmark.work.hybrid_moe_decoder import window_flops
    return (100.0 * window_flops(rec.config, c) / rec.window["window_s"]
            / rec.peaks["bf16_flops_per_s"])
