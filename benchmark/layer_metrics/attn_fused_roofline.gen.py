"""Share of the bf16 peak the fused prefill attention kernel (`attn_fused`, ops/pallas/attention.py) reached: the FLOPs of the (q block, key block) visits that did work over the window's batches (gen.attn_fused_visits from the program's `serve:deliver` records, benchmark/deliver_records.py; a visit's FLOPs from benchmark/work/attn_fused.py, the diagonal tile's rectangle counted whole) over the kernel's device time, against the chip's peak. Compute-bound. Any prompt length, where `attn_fused_roofline.latent` needs one q block a prompt. The reference has no such metric."""
from benchmark import deliver_records
from benchmark.metrics_lib import kernel_ms


def read(rec):
    ms = kernel_ms(rec, lambda name: "attn_fused" in name)
    sums = deliver_records.counter_sums(rec, ("gen.attn_fused_visits",))
    if not ms or not sums or not sums["gen.attn_fused_visits"] \
            or not rec.peaks:
        return None
    from benchmark.work.attn_fused import visit_flops
    flops = sums["gen.attn_fused_visits"] * visit_flops(
        rec.config, int(rec.traffic["p_max"]),
        int(rec.config.get("attn_q_block", 512)))
    return 100.0 * flops / rec.peaks["bf16_flops_per_s"] / (ms / 1e3)
