"""Driver `serve_backlog`: one producer thread submits frames as fast as the
engine's bounded queue admits (`submit(block=True)`), so the backlog is never
empty; the metric is requests completed per second.

The window opens at the end of a batch (the one holding the lead-in's last
answer) and closes at the end of the last batch wholly inside `seconds`: whole
batches in, whole batches out, so the 256-frame granularity of completions does
not show as noise in the rate.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import sys
import threading
import time

import numpy as np

from ._serve import ServeCell


class Cell(ServeCell):
    def run(self, seconds: float):
        stop = threading.Event()

        def produce():
            while not stop.is_set():
                self.submit(time.monotonic(), block=True)

        lead = int(self.p["lead_in_requests"])
        self.first = len(self.futs)
        producer = threading.Thread(target=produce, name="bench-producer",
                                    daemon=True)
        c0 = None
        producer.start()
        try:
            self.futs_wait(self.first + lead - 1)
            t0 = max(d for d in self.done[self.first:self.first + lead]
                     if d is not None)
            c0 = self.counters()
            with self.ctx.span("bench:window"):
                time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            c1 = self.counters()
        finally:
            stop.set()
            producer.join(timeout=120)
        missing = self.wait_all(time.monotonic() + 60)
        done = np.sort([d for d in self.done[self.first:] if d is not None])
        # Answers come back a batch at a time (the fetcher stamps a batch's
        # rows within milliseconds, batches are a device step apart), and a
        # batch need not start at a multiple of the lead-in. The window runs
        # from the end of the batch that holds the lead-in's last answer to
        # the end of the last batch wholly inside `seconds`: whole batches
        # over the time they took. (Opening it mid-batch counted up to 255
        # answers in no time: +2.5% in two runs of three, on the chip.)
        gaps = np.diff(done)
        cut = max(1e-3, 20 * float(np.median(gaps))) if len(gaps) else 0.0
        ends = np.append(done[:-1][gaps > cut], done[-1])  # each batch's end
        t0 = float(ends[ends >= t0][0])
        whole = ends[(ends > t0) & (ends <= t0 + seconds)]
        t1 = float(whole[-1]) if len(whole) else t0 + seconds
        inside = done[(done > t0) & (done <= t1)]
        sub = np.array(self.sub[self.first:])
        if len(whole) > 1:
            steps = np.diff(whole)
            print("batch gaps s: min %.4f median %.4f max %.4f"
                  % (steps.min(), np.median(steps), steps.max()),
                  file=sys.stderr)
        return {"window_s": t1 - t0, "t0": t0, "t1": t1,
                "attempted": int(np.sum((sub > t0) & (sub <= t1))),
                "failed": len([i for i in missing if i >= self.first]),
                "images": int(len(inside)),
                "counters": {k: c1[k] - c0[k] for k in c1},
                "e2e": {"serve_img_per_s": len(inside) / (t1 - t0)}}

    def futs_wait(self, index: int):
        while len(self.futs) <= index:
            time.sleep(0.005)
        for f in self.futs[self.first:index + 1]:
            f.result(timeout=600)
