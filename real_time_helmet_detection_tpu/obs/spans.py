"""Host span tracer: an always-on in-memory ring, plus a crash-safe JSONL log.

The reference has no observability tooling of any kind (its training loop
prints averaged meters and nothing else, ref train.py:140-160); this module
is new capability. It exists because the repo's postmortems keep asking the
same unanswerable question — *why* was this step/round slow (loader wait?
H2D? a recompile? a 2x-loaded box?) — and the evidence was scattered across
log lines, bench's one JSON line and folklore.

Design rules, each load-bearing:

* **stdlib only.** `runtime/` (the job supervisor, which must never build
  the ML stack) imports this module; so does `scripts/obs_report.py`.
* **One ring, always on.** Every span, `record()` and event of every
  tracer in the process lands in ONE bounded in-memory ring as
  `(name, t0, dur_s, meta)`, `t0` on `time.monotonic()` — the clock
  `benchmark/run.py` stamps its `jit_bench_mark` marks with, so a reader
  brings the spans onto a device trace's clock with the offset it already
  has. `default_tracer()` is the process-wide file-less tracer;
  `snapshot(since=)` reads the ring oldest first and returns `None` (never
  a partial list) when the ring has overwritten part of what was asked
  for; `dropped` counts what it overwrote. With no file configured a span
  costs two clock reads and one tuple append: no record dict, no json, no
  wall-clock read, no lock beyond the deque's own. A component handed a
  tracer of someone else's (the benchmark hands the serving engine a
  narrow one) wraps it in `with_ring`: that tracer sees what it saw, and
  the ring sees it too, with meta and a start.
* **Every span has a place on the clock.** `record(name, dur_s)` stamps
  `t0 = now - dur_s` (the caller measured an interval that just ended);
  an explicit `t0=` wins.
* **The file is optional**, and `enabled` means exactly "a file is
  configured" (the serving engine mints per-request trace contexts only
  then). Wall time is recorded in the file alongside (`t`, `t0`) for
  joining with the tpu_queue journal and bench lines (wall can NTP-step;
  monotonic cannot).
* **Crash-safe appends**: the log is opened O_APPEND and every record is
  one `write(line)+flush`. A `kill -9` mid-append can tear only the FINAL
  line; `read_spans` drops a torn tail exactly as the job spool's journal
  replay does (runtime/spool.py). No fsync per record — span logs are
  diagnostics, not the artifact of record, and per-iteration fsyncs would
  tax the loop being measured.

Span taxonomy (docs/ARCHITECTURE.md "Observability & flight recorder"):
`loader-wait`, `h2d`, `dispatch`, `step`, `fetch`, `checkpoint`, `compile`
(one per jax compile stage, obs/telemetry.py), `calibrate`, `serve:*`
(serving/engine.py: `serve:lower` / `serve:compile` a bucket;
`serve:queue-wait` / `serve:e2e` a request; a batch `serve:batch-form` /
`serve:h2d` / `serve:dispatch` on the dispatcher thread and
`serve:device-wait` / `serve:d2h` / `serve:deliver` on the fetcher, the
last one record a batch, meta `b`, `n` and the batch's `row_counters`
dict, written before any of its answers resolves; events `serve:shed`,
`serve:state`, ...), `bench:*` section spans, `heartbeat` events (the
runtime heartbeat mirrors every beat here when tracing is on),
`recompile` events and `context` records (host loadavg).

Trace-context extension (ISSUE 14, obs/trace.py): every write method
takes an optional `ctx` (a TraceContext — serialized as the optional
`trace`/`span`/`parent` record fields) and `links` (fan-in edges: a
batch span names every member request's context). Span records written
with either also carry `t0`, the wall-clock START of the measured
interval (`t` alone is ambiguous across the two write paths: a span CM
stamps construction, `record()` stamps the write — the waterfall
assembler needs the interval, not a point). `bind(**tags)` attaches
process-constant fields (rank, world) to every subsequent record — the
cross-process join key for train/scaling rank logs. All fields are
OPTIONAL additions to obs-spans-v1: readers of pre-ISSUE logs see
nothing new, pre-ISSUE readers of new logs ignore the extras. Contexts
and links go to the file only; the ring keeps times.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import time
from typing import List, Optional, Tuple

SPAN_SCHEMA = "obs-spans-v1"
OBS_SPAN_ENV = "OBS_SPAN_LOG"
# a 60 s window of the bulk serving cell: ~1,400 per-request records a
# second plus a dozen per batch
RING_CAPACITY = 1 << 17


class SpanRing:
    """The bounded in-memory store: `(name, t0, dur_s, meta)` in the order
    the intervals ENDED (a span is appended when it closes). Appends are
    one `deque.append` of one tuple; the sequence number in front is what
    lets a reader count what the ring overwrote."""

    def __init__(self, capacity: int = RING_CAPACITY):
        self._items: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))
        self._seq = itertools.count()

    def append(self, name: str, t0: float, dur_s: float, meta) -> None:
        self._items.append((next(self._seq), name, t0, dur_s, meta))

    def clear(self) -> None:
        """Empty the ring and its `dropped` count."""
        self._items.clear()
        self._seq = itertools.count()

    def _copy(self) -> list:
        for _ in range(8):
            try:
                return list(self._items)
            except RuntimeError:  # an append raced the copy: take it again
                continue
        return list(self._items.copy())

    @property
    def dropped(self) -> int:
        """How many entries the ring has overwritten so far."""
        try:
            return self._items[0][0]  # the oldest kept entry's number
        except IndexError:
            return 0

    def snapshot(self, since: Optional[float] = None
                 ) -> Optional[List[Tuple]]:
        """`[(name, t0, dur_s, meta)]`, oldest first; with `since`, those
        that started at or after it. `None` when the ring has overwritten
        an entry that ended after `since`: the window's start is gone, and
        a partial list would read as a smaller sum."""
        items = self._copy()
        if since is None:
            return [it[1:] for it in items]
        if items and items[0][0] and items[0][2] + items[0][3] > since:
            return None
        return [it[1:] for it in items if it[2] >= since]


_RING = SpanRing()


def reset_ring() -> None:
    """Empty the process-wide ring and its `dropped` count (tests only: a
    prior test's spans must not leak into the next one's snapshot)."""
    _RING.clear()


class Span:
    """One in-flight span and its context manager. `t0` is the monotonic
    start; `dur_s` is set at close."""

    __slots__ = ("name", "meta", "t0", "dur_s", "_t_wall", "_tracer",
                 "_ctx", "_links")

    def __init__(self, tracer: "SpanTracer", name: str, meta: dict,
                 ctx=None, links=None):
        self.name = name
        self.meta = meta
        self._tracer = tracer
        self._ctx = ctx
        self._links = links
        self._t_wall = time.time() if tracer.enabled else None
        self.dur_s: Optional[float] = None
        self.t0 = time.monotonic()

    def close(self) -> float:
        if self.dur_s is None:
            self.dur_s = time.monotonic() - self.t0
        return self.dur_s

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
        meta = self.meta
        if exc_type is not None:
            meta = dict(meta, error=exc_type.__name__)
        tracer = self._tracer
        tracer._ring.append(self.name, self.t0, self.dur_s, meta or None)
        if self._t_wall is not None:
            tracer._write_span(self.name, self._t_wall, self._t_wall,
                               self.dur_s, meta, self._ctx, self._links)


def _trace_fields(rec: dict, ctx, links, t0: Optional[float] = None
                  ) -> None:
    """Fold optional trace-context fields into a record in place (ISSUE
    14). `t0` (interval start) rides along whenever the record is part of
    a trace — the waterfall assembler needs intervals, not points."""
    traced = False
    if ctx is not None:
        rec.update(ctx.to_fields())
        traced = True
    if links:
        rec["links"] = list(links)
        traced = True
    if traced and t0 is not None:
        rec["t0"] = t0


class SpanTracer:
    """Span/event recorder (see module docstring): always the in-memory
    ring, and the JSONL file as well when `path` is given.

    `path=None` (or "") builds a file-less tracer (`enabled` False): spans
    time and land in the ring, nothing touches the filesystem. `ring`
    defaults to the process-wide one; tests hand in a small `SpanRing`.
    """

    def __init__(self, path: Optional[str] = None,
                 ring: Optional[SpanRing] = None):
        self.path = path or None
        self._f = None
        self.enabled = self.path is not None
        self._bound: dict = {}
        self._ring = ring if ring is not None else _RING

    # ---- the ring --------------------------------------------------------

    def snapshot(self, since: Optional[float] = None):
        """The ring's spans, oldest first (`SpanRing.snapshot`)."""
        return self._ring.snapshot(since)

    @property
    def dropped(self) -> int:
        return self._ring.dropped

    # ---- the file --------------------------------------------------------

    def _write(self, rec: dict) -> None:
        if not self.enabled:
            return
        try:
            if self._f is None:
                parent = os.path.dirname(os.path.abspath(self.path))
                os.makedirs(parent, exist_ok=True)
                fresh = not os.path.exists(self.path)
                # O_APPEND via mode "a": concurrent writers (a job and its
                # supervisor) interleave whole writes, never overwrite
                self._f = open(self.path, "a")
                if fresh:
                    self._f.write(json.dumps(
                        {"v": 1, "kind": "meta", "schema": SPAN_SCHEMA,
                         "t": time.time()}, sort_keys=True) + "\n")
            rec.setdefault("v", 1)
            rec.setdefault("pid", os.getpid())
            for k, v in self._bound.items():
                rec.setdefault(k, v)
            self._f.write(json.dumps(rec, sort_keys=True) + "\n")
            self._f.flush()
        except (OSError, ValueError, TypeError):
            # tracing must never kill the instrumented job; a tracer that
            # failed once stays silent (half-dead appends help nobody)
            self.enabled = False

    def _write_span(self, name: str, t_wall: float, t0_wall: float,
                    dur_s: float, meta, ctx=None, links=None) -> None:
        """One `span` line of the file: `t` the legacy stamp, `t0_wall`
        the interval's wall-clock start (written only on traced records)."""
        rec = {"kind": "span", "name": name, "t": t_wall,
               "dur_s": round(float(dur_s), 6),
               **({"meta": meta} if meta else {})}
        _trace_fields(rec, ctx, links, t0=t0_wall)
        self._write(rec)

    # ---- public API ------------------------------------------------------

    def bind(self, **tags) -> None:
        """Attach process-constant fields (rank, world) to every record
        this tracer writes to its file from now on — the cross-process
        join key for per-rank span logs (ISSUE 14)."""
        self._bound.update(tags)

    def span(self, name: str, ctx=None, links=None, **meta) -> Span:
        """`with tracer.span("compile", batch=16) as sp: ...` — times the
        block, appends it to the ring on exit (and to the file, when one
        is configured), and leaves the duration readable as `sp.dur_s`.
        `ctx`/`links` attach the file record to a trace (obs/trace.py)."""
        return Span(self, name, meta, ctx, links)

    def record(self, name: str, dur_s: float, ctx=None, links=None,
               t0: Optional[float] = None, **meta) -> None:
        """A span whose duration the caller already measured (the train/
        eval segment meters, the compile listener): keep it without
        re-timing. `t0` is its start on `time.monotonic()`; left out, the
        interval is taken to end now. In the file the write stamp `t` is
        the interval END and a traced record carries `t0 = t - dur_s`."""
        dur_s = float(dur_s)
        self._ring.append(name, time.monotonic() - dur_s if t0 is None
                          else float(t0), dur_s, meta or None)
        if self.enabled:
            t = time.time()
            self._write_span(name, t, t - dur_s, dur_s, meta, ctx, links)

    def event(self, name: str, ctx=None, links=None, **meta) -> None:
        """Zero-duration marker (heartbeat, recompile, job transition)."""
        self._ring.append(name, time.monotonic(), 0.0, meta or None)
        if self.enabled:
            rec = {"kind": "event", "name": name, "t": time.time(),
                   **({"meta": meta} if meta else {})}
            _trace_fields(rec, ctx, links)
            self._write(rec)

    def context(self, **extra) -> Optional[dict]:
        """Sample host context (loadavg — obs/context.py) into a `context`
        record; returns the sample (also without a file, so callers can
        embed it in their own JSON lines)."""
        from .context import sample_context
        sample = sample_context()
        sample.update(extra)
        self._ring.append("context", time.monotonic(), 0.0, sample)
        self._write({"kind": "context", "name": "context",
                     "t": time.time(), "sample": sample})
        return sample

    def wrap(self, name: str, fn, **meta):
        """Timed wrapper emitting one span per call; identity without a
        file (callers whose callee records its own span lose nothing)."""
        if not self.enabled:
            return fn

        def timed(*args, **kw):
            with self.span(name, **meta):
                return fn(*args, **kw)

        return timed

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.close()
            except OSError:
                pass
            self._f = None


_DEFAULT: Optional[SpanTracer] = None


def default_tracer() -> SpanTracer:
    """The process-wide file-less tracer over the process-wide ring (as
    `obs.metrics.default_registry()` is for metrics): where code with no
    tracer handed to it records, and where readers take snapshots."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = SpanTracer(None)
    return _DEFAULT


class _RingTee:
    """A tracer that does not write the process ring, and the ring beside
    it (`with_ring`): every span, record and event goes to `tracer` as it
    went before and to the ring with its meta and a start."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._ring = default_tracer()

    @property
    def enabled(self) -> bool:
        return self._tracer.enabled

    @contextlib.contextmanager
    def span(self, name: str, ctx=None, links=None, **meta):
        with self._tracer.span(name, ctx=ctx, links=links, **meta) as sp, \
                self._ring.span(name, **meta):
            yield sp

    def record(self, name: str, dur_s: float, ctx=None, links=None,
               t0: Optional[float] = None, **meta) -> None:
        self._tracer.record(name, dur_s, ctx=ctx, links=links,
                            **({} if t0 is None else {"t0": t0}), **meta)
        self._ring.record(name, dur_s, t0=t0, **meta)

    def event(self, name: str, ctx=None, links=None, **meta) -> None:
        self._tracer.event(name, ctx=ctx, links=links, **meta)
        self._ring.event(name, **meta)


def with_ring(tracer):
    """`tracer` if it writes the process ring already (a `SpanTracer` over
    it), else a tee of it and the ring: whatever tracer a component is
    handed (the benchmark hands the serving engine a narrow one of its
    own), the ring stays the one flight recorder of the process."""
    if isinstance(tracer, SpanTracer) and tracer._ring is _RING:
        return tracer
    return _RingTee(tracer)


def maybe_tracer(path: Optional[str] = None,
                 env: Optional[dict] = None) -> SpanTracer:
    """The one construction point: explicit `path` wins, else
    $OBS_SPAN_LOG, else `default_tracer()`. With a path the tracer feeds
    the same process-wide ring and writes the JSONL as well. Mirrors
    `runtime.maybe_job_heartbeat`'s env-based wiring so every instrumented
    script shares one line."""
    p = path or (env if env is not None else os.environ).get(OBS_SPAN_ENV)
    return SpanTracer(p) if p else default_tracer()


def read_spans(path: str) -> list:
    """Every parseable record in a span log, torn tail dropped.

    The recovery contract mirrors runtime/spool.py's journal replay: a
    crash (kill -9) mid-append tears at most the final line — skip it
    silently; garbage MID-file is unexpected (concurrent writers torn
    across page boundaries) and is skipped loudly."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return []
    out = []
    lines = data.split(b"\n")
    for i, raw in enumerate(lines):
        if not raw.strip():
            continue
        try:
            out.append(json.loads(raw))
        except json.JSONDecodeError:
            if i != len(lines) - 1:
                print("[obs] WARNING: unparseable span-log line %d skipped"
                      % (i + 1), flush=True)
    return out
