"""Of the (position, expert layer) slots of the window's requests, those where a routing group this share holds was among the groups the router kept: gen.group_hits over gen.group_slots. With one group of eight a chip and four kept, a level router reads 50%: the part of the tokens that come to this chip at all. The reference has no such metric."""


def read(rec):
    c = rec.window.get("counters") or {}
    if not c.get("gen.group_slots"):
        return None
    return 100.0 * c["gen.group_hits"] / c["gen.group_slots"]
