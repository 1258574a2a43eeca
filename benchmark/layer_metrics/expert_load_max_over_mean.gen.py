"""The busiest held expert's pairs over the mean held expert's, from the program's gen.expert_pairs.* counters over the window. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    pairs = [v for k, v in c.items() if k.startswith("gen.expert_pairs.")]
    if not pairs or not sum(pairs):
        return None
    return max(pairs) * len(pairs) / sum(pairs)
