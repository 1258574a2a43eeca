"""The statement of an expert share: how many chips share an expert layer,
and which share this one is.

The reference has no experts and no model parallelism (ref train.py:23-45 is
data-parallel DDP only); this module is new capability. An expert layer is
told which experts it holds, routes over all of them, and computes the part
of the result its own experts give; what the others would add is left out. On
one chip the layer runs without its exchange: nothing here stands in for
absent chips or their traffic (the all-to-all across chips is ROADMAP's).
"""

from __future__ import annotations

from typing import NamedTuple


class ExpertShare(NamedTuple):
    """`n_routed` experts in the whole layer, divided evenly and in order
    over `ep_size` chips; this is share `ep_rank`."""
    ep_size: int
    ep_rank: int
    n_routed: int

    @property
    def held(self) -> int:
        return self.n_routed // self.ep_size

    @property
    def first(self) -> int:
        return self.ep_rank * self.held

    def ids(self) -> range:
        return range(self.first, self.first + self.held)


def expert_share(ep_size: int, ep_rank: int, n_routed: int) -> ExpertShare:
    if ep_size < 1 or n_routed % ep_size:
        raise ValueError("ep_size %d must divide the %d routed experts"
                         % (ep_size, n_routed))
    if not 0 <= ep_rank < ep_size:
        raise ValueError("ep_rank %d is not a share of %d" % (ep_rank, ep_size))
    return ExpertShare(int(ep_size), int(ep_rank), int(n_routed))
