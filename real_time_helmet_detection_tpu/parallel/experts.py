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
    over `ep_size` chips; this is share `ep_rank`. `n_group`: the routing
    groups the router limits a token's choice by (consecutive experts,
    `n_routed / n_group` each; 0: the router knows no groups)."""
    ep_size: int
    ep_rank: int
    n_routed: int
    n_group: int = 0

    @property
    def held(self) -> int:
        return self.n_routed // self.ep_size

    @property
    def first(self) -> int:
        return self.ep_rank * self.held

    def ids(self) -> range:
        return range(self.first, self.first + self.held)

    def groups(self) -> range:
        """The routing groups this share holds experts of (whole groups, or
        a part of one): `expert_share` lets nothing else be stated."""
        if not self.n_group:
            return range(0)
        size = self.n_routed // self.n_group
        return range(self.first // size, (self.first + self.held - 1) // size
                     + 1)


def expert_share(ep_size: int, ep_rank: int, n_routed: int,
                 n_group: int = 0) -> ExpertShare:
    if ep_size < 1 or n_routed % ep_size:
        raise ValueError("ep_size %d must divide the %d routed experts"
                         % (ep_size, n_routed))
    if not 0 <= ep_rank < ep_size:
        raise ValueError("ep_rank %d is not a share of %d" % (ep_rank, ep_size))
    if n_group:
        # a share is whole groups or a whole fraction of one, so that "a
        # token's experts lie on the holders of at most `topk_group` groups"
        # is a statement about chips
        held = n_routed // ep_size
        if n_routed % n_group or (held % (n_routed // n_group)
                                  and (n_routed // n_group) % held):
            raise ValueError(
                "a share of %d experts (%d over %d chips) is neither whole "
                "routing groups nor a whole fraction of one (%d groups)"
                % (held, n_routed, ep_size, n_group))
    return ExpertShare(int(ep_size), int(ep_rank), int(n_routed),
                       int(n_group))
