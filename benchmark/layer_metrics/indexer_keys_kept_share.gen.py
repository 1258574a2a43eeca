"""Keys the indexer kept over keys causal, full layers, from the program's gen.keys_* counters over the window. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.keys_causal"):
        return None
    return 100.0 * c["gen.keys_kept"] / c["gen.keys_causal"]
