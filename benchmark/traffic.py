"""The one traffic generator: every cell's inputs come from here, from the
cell's data file (benchmark/workloads/<cell>.json) and `--seed`.

A cell adds no code: it names a `driver` and gives parameters. The same seed
gives the same inputs; another seed gives other images and, for an open loop,
the same set of burst sizes and gaps in another order, so that every seed
offers the same amount of work.

Copied and corrected from scripts/serve_bench.py `arrival_schedule` /
`open_loop` (a schedule from a seed, latency measured by the caller): that
one times from the submit, this one hands out DUE instants, and the driver
times from them and reports how late the generator ran.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import types
from typing import List

import numpy as np


def train_batches(seed: int, count: int, batch: int, imsize: int,
                  num_cls: int, pos_rate: float, scale_factor: int = 4
                  ) -> List[types.SimpleNamespace]:
    """`count` host batches in the collate format the train step takes
    (the contract of data/synthetic.py `synthetic_target_batch`): normalized
    float32 images, target heatmap in [0, 1), offsets in [0, 1), sizes in
    [1, 8) map cells, and a 0/1 centre mask with `pos_rate` positives. Every
    row of every batch differs."""
    m = imsize // scale_factor
    rng = np.random.default_rng([int(seed), 1])
    out = []
    for _ in range(count):
        out.append(types.SimpleNamespace(
            image=rng.standard_normal((batch, imsize, imsize, 3),
                                      dtype=np.float32),
            heatmap=rng.random((batch, m, m, num_cls), dtype=np.float32),
            offset=rng.random((batch, m, m, 2), dtype=np.float32),
            wh=(1.0 + 7.0 * rng.random((batch, m, m, 2), dtype=np.float32)),
            mask=(rng.random((batch, m, m, 1), dtype=np.float32)
                  < pos_rate).astype(np.float32)))
    return out


def frame_pool(seed: int, count: int, imsize: int) -> np.ndarray:
    """`count` distinct raw uint8 frames (count, imsize, imsize, 3): smooth
    random fields plus pixel noise, so that a frame has structure at every
    scale of the hourglass and no two frames agree."""
    rng = np.random.default_rng([int(seed), 2])
    coarse = rng.integers(0, 256, (count, imsize // 16, imsize // 16, 3),
                          dtype=np.uint8)
    frames = np.repeat(np.repeat(coarse, 16, axis=1), 16, axis=2)
    noise = rng.integers(0, 64, frames.shape, dtype=np.uint8)
    return (frames // 4 * 3 + noise).astype(np.uint8)


def open_schedule(seed: int, seconds: float, rate_per_s: float,
                  burst_min: int, burst_max: int, schedule_seed: int
                  ) -> np.ndarray:
    """Due instants (seconds from the window's start, ascending), one per
    request, of an open loop: bursts at Poisson instants, each of
    `burst_min..burst_max` frames (uniform) due at the same instant, at
    `rate_per_s` frames a second on average.

    The multiset of gaps and sizes is drawn from `schedule_seed` (the cell's,
    fixed) and scaled so that the bursts span exactly `seconds` and carry
    exactly round(rate * seconds) frames; `seed` (the run's) only permutes
    them. Every run of the cell therefore offers the same work."""
    total = int(round(rate_per_s * seconds))
    base = np.random.default_rng([int(schedule_seed), 3])
    mean_burst = (burst_min + burst_max) / 2.0
    n_bursts = max(1, int(round(total / mean_burst)))
    sizes = base.integers(burst_min, burst_max + 1, n_bursts)
    # one frame at a time off the largest burst, or onto the smallest, until
    # the bursts carry exactly `total` frames
    while sizes.sum() != total:
        if sizes.sum() > total:
            sizes[int(np.argmax(sizes))] -= 1
        else:
            sizes[int(np.argmin(sizes))] += 1
    gaps = base.exponential(1.0, n_bursts)
    gaps *= seconds / gaps.sum()
    run = np.random.default_rng([int(seed), 4])
    sizes = sizes[run.permutation(n_bursts)]
    gaps = gaps[run.permutation(n_bursts)]
    starts = np.cumsum(gaps) - gaps  # first burst due at 0
    return np.repeat(starts, sizes)
