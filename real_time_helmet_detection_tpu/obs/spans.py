"""Host span tracer: a crash-safe JSONL event log for the flight recorder.

The reference has no observability tooling of any kind (its training loop
prints averaged meters and nothing else, ref train.py:140-160); this module
is new capability. It exists because the repo's postmortems keep asking the
same unanswerable question — *why* was this step/round slow (loader wait?
H2D? a recompile? a 2x-loaded box?) — and the evidence was scattered across
log lines, bench's one JSON line and folklore.

Design rules, each load-bearing:

* **stdlib only.** `runtime/` (the job supervisor, which must never build
  the ML stack) imports this module; so does `scripts/obs_report.py`.
* **Durations from the monotonic clock**, wall time recorded alongside for
  joining with the tpu_queue journal and bench lines (wall can NTP-step;
  monotonic cannot).
* **Crash-safe appends**: the log is opened O_APPEND and every record is
  one `write(line)+flush`. A `kill -9` mid-append can tear only the FINAL
  line; `read_spans` drops a torn tail exactly as the job spool's journal
  replay does (runtime/spool.py). No fsync per record — span logs are
  diagnostics, not the artifact of record, and per-iteration fsyncs would
  tax the loop being measured.
* **Disabled == free.** `maybe_tracer()` with no path configured returns a
  tracer whose `span()` still measures (callers read `sp.dur_s` for their
  JSON artifacts) but writes nothing and whose `wrap()` returns the
  function unchanged.

Span taxonomy (docs/ARCHITECTURE.md "Observability & flight recorder"):
`loader-wait`, `h2d`, `dispatch`, `fetch`, `checkpoint`, `compile`,
`calibrate`, `bench:*` section spans, `heartbeat` events (the runtime
heartbeat mirrors every beat here when tracing is on), `recompile` events
and `context` records (host loadavg).

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
nothing new, pre-ISSUE readers of new logs ignore the extras.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

SPAN_SCHEMA = "obs-spans-v1"
OBS_SPAN_ENV = "OBS_SPAN_LOG"


class Span:
    """One in-flight (or pre-measured) span. `dur_s` is set at close."""

    __slots__ = ("name", "meta", "t_wall", "_mono0", "dur_s")

    def __init__(self, name: str, meta: dict):
        self.name = name
        self.meta = meta
        self.t_wall = time.time()
        self._mono0 = time.monotonic()
        self.dur_s: Optional[float] = None

    def close(self) -> float:
        if self.dur_s is None:
            self.dur_s = time.monotonic() - self._mono0
        return self.dur_s


class _SpanCM:
    """Context manager wrapping one Span; writes the record on exit."""

    __slots__ = ("_tracer", "_span", "_ctx", "_links")

    def __init__(self, tracer: "SpanTracer", span: Span, ctx=None,
                 links=None):
        self._tracer = tracer
        self._span = span
        self._ctx = ctx
        self._links = links

    def __enter__(self) -> Span:
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        sp = self._span
        sp.close()
        meta = dict(sp.meta)
        if exc_type is not None:
            meta["error"] = exc_type.__name__
        rec = {"kind": "span", "name": sp.name,
               "t": sp.t_wall, "dur_s": round(sp.dur_s, 6),
               **({"meta": meta} if meta else {})}
        _trace_fields(rec, self._ctx, self._links, t0=sp.t_wall)
        self._tracer._write(rec)


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
    """JSONL span/event writer (see module docstring).

    `path=None` (or "") builds a DISABLED tracer: spans still time (so
    callers can read `sp.dur_s`), nothing touches the filesystem.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or None
        self._f = None
        self.enabled = self.path is not None
        self._bound: dict = {}

    # ---- the write path --------------------------------------------------

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

    # ---- public API ------------------------------------------------------

    def bind(self, **tags) -> None:
        """Attach process-constant fields (rank, world) to every record
        this tracer writes from now on — the cross-process join key for
        per-rank span logs (ISSUE 14)."""
        self._bound.update(tags)

    def span(self, name: str, ctx=None, links=None, **meta) -> _SpanCM:
        """`with tracer.span("compile", batch=16) as sp: ...` — times the
        block (always), writes a span record on exit (when enabled), and
        leaves the duration readable as `sp.dur_s`. `ctx`/`links` attach
        the span to a trace (obs/trace.py)."""
        return _SpanCM(self, Span(name, meta), ctx=ctx, links=links)

    def record(self, name: str, dur_s: float, ctx=None, links=None,
               **meta) -> None:
        """A span whose duration the caller already measured (the train/
        eval segment meters): write it without re-timing. The write stamp
        is the interval END; a traced record carries `t0 = t - dur_s` so
        the waterfall assembler sees the interval."""
        t = time.time()
        rec = {"kind": "span", "name": name, "t": t,
               "dur_s": round(float(dur_s), 6),
               **({"meta": meta} if meta else {})}
        _trace_fields(rec, ctx, links, t0=t - float(dur_s))
        self._write(rec)

    def event(self, name: str, ctx=None, links=None, **meta) -> None:
        """Zero-duration marker (heartbeat, recompile, job transition)."""
        rec = {"kind": "event", "name": name, "t": time.time(),
               **({"meta": meta} if meta else {})}
        _trace_fields(rec, ctx, links)
        self._write(rec)

    def context(self, **extra) -> Optional[dict]:
        """Sample host context (loadavg — obs/context.py) into a `context`
        record; returns the sample (even when disabled,
        so callers can also embed it in their own JSON lines)."""
        from .context import sample_context
        sample = sample_context()
        sample.update(extra)
        self._write({"kind": "context", "name": "context",
                     "t": time.time(), "sample": sample})
        return sample

    def wrap(self, name: str, fn, **meta):
        """Timed wrapper emitting one span per call; identity when the
        tracer is disabled (the H2D stage hook must cost nothing off)."""
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


def maybe_tracer(path: Optional[str] = None,
                 env: Optional[dict] = None) -> SpanTracer:
    """The one construction point: explicit `path` wins, else
    $OBS_SPAN_LOG, else a disabled tracer. Mirrors
    `runtime.maybe_job_heartbeat`'s env-based wiring so every instrumented
    script shares one line."""
    p = path or (env if env is not None else os.environ).get(OBS_SPAN_ENV)
    return SpanTracer(p)


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
