"""Experts visited over experts that could have been: gen.expert_visits (for each prefill or step and expert layer, the experts that had at least one pair) over gen.expert_passes x the experts held: what part of the experts' weights a pass streams. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.expert_passes"):
        return None
    from benchmark.work.gqa_moe_decoder import expert_slots
    return 100.0 * c["gen.expert_visits"] / expert_slots(rec.config, c)
