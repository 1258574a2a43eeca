"""Share of the bf16 peak the fused prefill attention kernel (`attn_fused`, ops/pallas/attention.py) reached in a cell of the plain latent-attention decoder: the FLOPs of the visits that ran (gen.q_blocks_fused; a prompt's slots are one q block here, the diagonal tile's rectangle counted whole: benchmark/work/mla_moe_decoder.py) over the kernel's device time, against the chip's peak. Compute-bound. The reference has no such metric."""
from benchmark.metrics_lib import kernel_ms


def read(rec):
    c = rec.window.get("counters") or {}
    ms = kernel_ms(rec, lambda name: "attn_fused" in name)
    if not ms or not c.get("gen.q_blocks_fused") or not rec.peaks:
        return None
    from benchmark.work.mla_moe_decoder import fused_attention_flops
    flops = fused_attention_flops(
        rec.config, int(rec.traffic["p_max"]),
        int(rec.config.get("attn_q_block", 512)), c)
    if not flops:
        return None
    return 100.0 * flops / rec.peaks["bf16_flops_per_s"] / (ms / 1e3)
