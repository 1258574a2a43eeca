"""The whole generate step's share of the bf16 peak in a cell of the plain latent-attention decoder: the matrix-product FLOPs of the requests the window answered (benchmark/work/mla_moe_decoder.py, from the program's gen.* counters: positions, pairs held, causal keys) over the window, against the chip's peak. None where the program has no such counters (a parent without the group counts). The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.requests") or not c.get("gen.group_slots") \
            or not rec.peaks:
        return None
    from benchmark.work.mla_moe_decoder import window_flops
    return (100.0 * window_flops(rec.config, c) / rec.window["window_s"]
            / rec.peaks["bf16_flops_per_s"])
