"""Driver `serve_open`: an open loop at a rate fixed in the cell's file.
Bursts fall due at Poisson instants (benchmark/traffic.py `open_schedule`);
one generator thread sleeps until each instant and submits the burst without
blocking (a full queue sheds the request: that is a failure, never a stall of
the generator). Latency runs from the instant a request was DUE to the
instant its answer was ready; a request shed, failed or unanswered a minute
after the window counts with the time waited, which is the worst.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import time

import numpy as np

from .. import traffic
from ._serve import ServeCell


class Cell(ServeCell):
    def run(self, seconds: float):
        p = self.p
        due = traffic.open_schedule(
            self.ctx.seed, seconds, float(p["rate_per_s"]),
            int(p["burst"][0]), int(p["burst"][1]), int(p["schedule_seed"]))
        self.first = len(self.futs)
        c0 = self.counters()
        t0 = time.monotonic() + 0.05
        with self.ctx.span("bench:window"):
            for d in due:
                wait = t0 + d - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                self.submit(t0 + float(d), block=False)
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        backlog = sum(1 for d in self.done[self.first:] if d is None)
        c1 = self.counters()
        missing = self.wait_all(t0 + seconds + 60)
        end = time.monotonic()
        due_t = np.array(self.due[self.first:])
        done = np.array([end if (d is None or i + self.first in missing)
                         else d for i, d in enumerate(self.done[self.first:])])
        late = np.array(self.sub[self.first:]) - due_t
        latency_ms = (done - due_t) * 1e3
        return {"window_s": seconds, "t0": t0, "t1": t0 + seconds,
                "attempted": len(due), "failed": len(missing),
                "images": int(np.sum(done <= t0 + seconds)),
                "backlog_at_close": backlog,
                "latency_ms": latency_ms, "late_ms": late * 1e3,
                "counters": {k: c1[k] - c0[k] for k in c1},
                "e2e": {"serve_p95_ms": float(np.percentile(latency_ms, 95))}}
