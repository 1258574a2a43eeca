"""Experts visited over experts that could have been, in a cell of the plain latent-attention decoder: gen.expert_visits (for each prefill or step and expert layer, the held experts that had at least one pair) over gen.expert_passes x the experts held (benchmark/work/mla_moe_decoder.py): what part of this share's experts' weights a pass streams. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.expert_passes") or not c.get("gen.group_slots"):
        return None
    from benchmark.work.mla_moe_decoder import expert_slots
    return 100.0 * c["gen.expert_visits"] / expert_slots(rec.config, c)
