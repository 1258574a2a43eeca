"""Real keys over cache slots the decode steps read on the full layers, from the program's gen.cache_keys.full / gen.cache_slots.full counters over the window: what part of a full cache's traffic is keys a query may attend. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.cache_slots.full"):
        return None
    return 100.0 * c["gen.cache_keys.full"] / c["gen.cache_slots.full"]
