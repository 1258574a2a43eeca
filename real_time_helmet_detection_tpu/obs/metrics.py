"""Live metrics plane: thread-safe counters/gauges/histograms + snapshots.

The reference has no metrics of any kind (its loop prints averaged meters
and exits, ref train.py:140-160), and until ISSUE 10 this repo's
observability was *post-hoc* only: span logs and obs_report joins answer
"what happened" after a round, but nothing exports the live state a
watchdog (obs/slo.py), a load balancer (ServingEngine.health()) or the
cross-round perf gate (scripts/perfgate.py) can act on while the process
runs. This module is that third leg — the in-datacenter-profiler stance
(Kanev et al., PAPERS.md) that fleet telemetry is an always-on subsystem,
not a debugging afterthought.

Design rules, each load-bearing:

* **stdlib only.** `runtime/` (the job supervisor, which must never build
  the ML stack) instruments its job-state gauges through this module, and
  `scripts/perfgate.py`/`scripts/obs_report.py` read snapshots without
  jax. Mirrors obs/spans.py.
* **Fixed shapes.** The latency histogram is log-linear with a FIXED
  bucket layout (`SUB` sub-buckets per power of two between `LO` and
  `HI`), so every snapshot is constant-size regardless of how much
  traffic it absorbed — the same fixed-shape discipline the jitted
  programs live by (CLAUDE.md), applied to telemetry payloads. Two
  histograms with the same layout MERGE by integer bucket addition
  (associative + commutative; property-tested), which is what lets
  per-thread/per-phase histograms roll up into one digest.
* **Host-side only, zero program impact.** Instrumented call sites update
  in-memory counters; nothing here touches jax, traces a program or adds
  a D2H fetch. With $OBS_METRICS unset the instrumented paths run the
  exact pre-PR programs (count-pinned by tests/test_metrics_plane.py);
  the env var only arms EXPORT.
* **Crash-safe export.** `MetricsWriter.maybe_flush()` appends one
  `obs-metrics-v1` snapshot line per period to the JSONL timeline via a
  single `write+flush` on an O_APPEND handle (a kill -9 tears at most
  the FINAL line; `read_metrics` drops it — the spans/spool recovery
  contract), and atomically replaces the constant-size `<path>.latest`
  sidecar (tmp + os.replace, utils.atomic_write_bytes's rule) so a
  dashboard/post-mortem always finds one complete current snapshot.
  $OBS_METRICS mirrors $OBS_SPAN_LOG: `maybe_writer()` is the one
  construction point, disabled (writes nothing, registry still counts)
  when no path is configured.

Metric name taxonomy (docs/ARCHITECTURE.md "Live metrics & SLO gates"):
`serve.*` (engine admission/shed/retry/requeue counters, queue-depth and
per-bucket fill gauges, per-stage h2d/compute/d2h/e2e latency
histograms), `train.*` (step/loader-wait/fetch histograms, sentinel skip
+ quarantine counters), `queue.*` (supervisor job-state gauges,
heartbeat-age), `bench.*` (the step-time histogram behind the JSON
line's step_p50_ms/step_p99_ms).
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
from typing import Dict, List, Optional

METRICS_SCHEMA = "obs-metrics-v1"
OBS_METRICS_ENV = "OBS_METRICS"


class Counter:
    """Monotonic integer counter. `inc` is lock-protected so concurrent
    serving/loader threads never lose increments (property-tested)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._v += int(n)

    @property
    def value(self) -> int:
        with self._lock:
            return self._v


class Gauge:
    """Last-write-wins float; None until first set (a gauge that was
    never measured must not read as 0.0)."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v: Optional[float] = None
        self._lock = threading.Lock()

    def set(self, v) -> None:
        with self._lock:
            self._v = float(v)

    @property
    def value(self) -> Optional[float]:
        with self._lock:
            return self._v


class Histogram:
    """Fixed-layout log-linear latency histogram (see module docstring).

    Buckets: index 0 is the underflow bucket (v < LO, incl. v <= 0), the
    last is overflow (v >= HI); between them each power of two in
    [LO, HI) is split into `sub` geometric sub-buckets, giving a relative
    resolution of 2^(1/sub) (~9% at the default sub=8) — enough for p50/
    p99 claims without per-sample storage. count/total/min/max are exact,
    so means are exact and quantiles clamp to the observed range."""

    __slots__ = ("name", "lo", "hi", "sub", "_buckets", "count", "total",
                 "min", "max", "_lock", "_noct", "_nbuckets")

    # value domain defaults cover ~1 us .. ~1e6 (unit-agnostic: callers
    # pick one unit per metric — the repo convention is milliseconds for
    # *_ms names, seconds otherwise)
    DEFAULT_LO = 1e-3
    DEFAULT_HI = 1e7
    DEFAULT_SUB = 8

    def __init__(self, name: str, lo: float = DEFAULT_LO,
                 hi: float = DEFAULT_HI, sub: int = DEFAULT_SUB):
        if not (lo > 0 and hi > lo and sub >= 1):
            raise ValueError("bad histogram layout lo=%r hi=%r sub=%r"
                             % (lo, hi, sub))
        self.name = name
        self.lo = float(lo)
        self.hi = float(hi)
        self.sub = int(sub)
        self._noct = int(math.ceil(math.log2(self.hi / self.lo)))
        # layout constant (bucket list length never changes): _index /
        # _bucket_mid read THIS, not len(_buckets), so the hot index
        # computation needs no lock
        self._nbuckets = self._noct * self.sub + 2
        self._buckets = [0] * self._nbuckets
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._lock = threading.Lock()

    # -- layout ------------------------------------------------------------

    def _index(self, v: float) -> int:
        if not (v >= self.lo):      # also catches NaN
            return 0
        if v >= self.hi:
            return self._nbuckets - 1
        i = int(math.log2(v / self.lo) * self.sub)
        return max(1, min(self._nbuckets - 2, 1 + i))

    def _bucket_mid(self, i: int) -> float:
        """Geometric midpoint of bucket i (underflow -> lo, overflow ->
        hi); quantiles report this, clamped to the exact observed
        min/max."""
        if i <= 0:
            return self.lo
        if i >= self._nbuckets - 1:
            return self.hi
        return self.lo * 2.0 ** ((i - 1 + 0.5) / self.sub)

    def same_layout(self, other: "Histogram") -> bool:
        return (self.lo == other.lo and self.hi == other.hi
                and self.sub == other.sub)

    # -- write path --------------------------------------------------------

    def observe(self, v) -> None:
        v = float(v)
        i = self._index(v)
        with self._lock:
            self._buckets[i] += 1
            self.count += 1
            self.total += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)

    def merge(self, other: "Histogram") -> None:
        """In-place bucket addition; layouts must match (merging two
        different layouts would silently mis-bin — refuse loudly)."""
        if not self.same_layout(other):
            raise ValueError("histogram layout mismatch: %s vs %s"
                             % (self.name, other.name))
        with other._lock:
            buckets = list(other._buckets)
            count, total = other.count, other.total
            omin, omax = other.min, other.max
        with self._lock:
            for i, n in enumerate(buckets):
                self._buckets[i] += n
            self.count += count
            self.total += total
            if omin is not None:
                self.min = omin if self.min is None else min(self.min, omin)
            if omax is not None:
                self.max = omax if self.max is None else max(self.max, omax)

    # -- read path ---------------------------------------------------------

    def _quantile_unlocked(self, q: float):  # guarded-by: _lock
        """Quantile body; callers (quantile/digest) hold `_lock` — split
        out so digest() can read count/mean/p50/p99/max in ONE coherent
        lock window instead of stitching per-field acquisitions (the
        same torn-digest class as the PR 12 engine `health()` bug)."""
        if self.count == 0:
            return None
        rank = min(self.count - 1,
                   max(0, int(round(float(q) * (self.count - 1)))))
        seen = 0
        for i, n in enumerate(self._buckets):
            seen += n
            if seen > rank:
                mid = self._bucket_mid(i)
                return max(self.min, min(self.max, mid))
        return self.max  # unreachable unless counts were torn

    def quantile(self, q: float) -> Optional[float]:
        """Nearest-rank quantile at bucket resolution (geometric bucket
        midpoint, clamped to exact min/max). None when empty."""
        with self._lock:
            return self._quantile_unlocked(q)

    @property
    def mean(self) -> Optional[float]:
        with self._lock:
            return self.total / self.count if self.count else None

    def snapshot(self) -> Dict:
        with self._lock:
            return {"lo": self.lo, "hi": self.hi, "sub": self.sub,
                    "count": self.count, "total": round(self.total, 9),
                    "min": self.min, "max": self.max,
                    "buckets": list(self._buckets)}

    @classmethod
    def from_snapshot(cls, name: str, snap: Dict) -> "Histogram":
        h = cls(name, lo=snap["lo"], hi=snap["hi"], sub=snap["sub"])
        h._buckets = list(snap["buckets"])
        h.count = int(snap["count"])
        h.total = float(snap["total"])
        h.min = snap.get("min")
        h.max = snap.get("max")
        return h

    def digest(self) -> Dict:
        """The compact human/health() form: count, mean, p50/p99, max —
        read under ONE lock acquisition so the digest is internally
        consistent (count matches the distribution the quantiles were
        scanned from; pinned by tests/test_lock_audit.py)."""
        with self._lock:
            count = self.count
            mean = self.total / count if count else None
            p50 = self._quantile_unlocked(0.50)
            p99 = self._quantile_unlocked(0.99)
            mx = self.max
        return {"count": count,
                "mean": None if mean is None else round(mean, 4),
                "p50": None if p50 is None else round(p50, 4),
                "p99": None if p99 is None else round(p99, 4),
                "max": mx}


class MetricsRegistry:
    """Named metric store: get-or-create handles, one coherent snapshot.

    Handle creation is lock-protected; the handles themselves carry their
    own locks, so hot-path `inc`/`observe` calls never contend on the
    registry. `snapshot()` is deterministic (sorted names)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str, lo: float = Histogram.DEFAULT_LO,
                  hi: float = Histogram.DEFAULT_HI,
                  sub: int = Histogram.DEFAULT_SUB) -> Histogram:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(name, lo=lo, hi=hi,
                                                  sub=sub)
            return h

    def snapshot(self) -> Dict:
        """One coherent `obs-metrics-v1` snapshot of everything. Counter/
        gauge reads are atomic per metric; the snapshot as a whole is a
        point-in-time view, not a transaction (fine for telemetry)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._hists)
        return {"v": 1, "schema": METRICS_SCHEMA, "t": time.time(),
                "pid": os.getpid(),
                "counters": {n: c.value for n, c in sorted(counters.items())},
                "gauges": {n: g.value for n, g in sorted(gauges.items())},
                "histograms": {n: h.snapshot()
                               for n, h in sorted(hists.items())}}

    def digest(self, prefix: str = "") -> Dict:
        """Compact view for health()/reports: counters + gauges verbatim,
        histograms as count/mean/p50/p99/max digests; optionally filtered
        to names starting with `prefix`.

        The handle dicts are COPIED under the registry lock first
        (snapshot()'s discipline): iterating them live races concurrent
        handle creation — a serving thread minting a new tenant counter
        mid-digest was a `RuntimeError: dictionary changed size` away
        from killing a health() call."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = dict(self._hists)
        snap_c = {n: c.value for n, c in sorted(counters.items())
                  if n.startswith(prefix)}
        snap_g = {n: g.value for n, g in sorted(gauges.items())
                  if n.startswith(prefix)}
        snap_h = {n: h.digest() for n, h in sorted(hists.items())
                  if n.startswith(prefix)}
        return {"counters": snap_c, "gauges": snap_g, "histograms": snap_h}


_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[MetricsRegistry] = None


def default_registry() -> MetricsRegistry:
    """THE process-wide registry instrumented modules share (engine,
    train, supervisor, bench) so one writer exports one coherent
    snapshot. Tests wanting isolation construct their own
    MetricsRegistry and pass it explicitly."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = MetricsRegistry()
        return _DEFAULT


def reset_default_registry() -> MetricsRegistry:
    """Replace the process-wide registry (tests only: a prior test's
    counts must not leak into the next one's snapshot)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = MetricsRegistry()
        return _DEFAULT


def _atomic_write(path: str, data: bytes) -> None:
    """tmp + os.replace, stdlib twin of utils.atomic_write_bytes (obs/
    must stay importable without numpy/PIL — same contract, same rule)."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    try:
        with open(tmp, "wb") as f:  # graftlint: off=raw-artifact-write
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def latest_path(path: str) -> str:
    return path + ".latest"


class MetricsWriter:
    """Periodic snapshot exporter (see module docstring). `path=None`
    builds a DISABLED writer: maybe_flush() is a cheap no-op, the
    registry keeps counting in memory."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 path: Optional[str] = None, period_s: float = 30.0):
        self.registry = registry if registry is not None \
            else default_registry()
        self.path = path or None
        self.enabled = self.path is not None
        self.period_s = max(0.0, float(period_s))
        self._f = None
        self._last_flush = None  # never: the first flush always lands
        self._lock = threading.Lock()

    def maybe_flush(self, force: bool = False) -> bool:
        """Append one snapshot line (+ refresh the .latest sidecar) when
        the period has elapsed (or `force`). Returns True when a snapshot
        was written. Never raises into the instrumented job: an export
        failure disables the writer (half-dead appends help nobody —
        obs/spans.py's rule)."""
        now = time.monotonic()
        with self._lock:
            # `enabled` is checked (and on failure flipped) under the
            # writer lock: an unlocked fast-path read raced the disable
            if not self.enabled:
                return False
            if (not force and self._last_flush is not None
                    and now - self._last_flush < self.period_s):
                return False
            self._last_flush = now
            try:
                snap = self.registry.snapshot()
                if self._f is None:
                    parent = os.path.dirname(os.path.abspath(self.path))
                    os.makedirs(parent, exist_ok=True)
                    # O_APPEND via "a": concurrent writers (a job and its
                    # supervisor) interleave whole lines, never overwrite
                    self._f = open(self.path, "a")
                self._f.write(json.dumps(snap, sort_keys=True) + "\n")
                self._f.flush()
                _atomic_write(latest_path(self.path),
                              json.dumps(snap, sort_keys=True).encode())
                return True
            except (OSError, ValueError, TypeError):
                self.enabled = False
                return False

    def close(self) -> None:
        self.maybe_flush(force=True)
        with self._lock:
            # swap under the lock, close outside it: a concurrent
            # maybe_flush either finished before the swap or finds None
            f, self._f = self._f, None
        if f is not None:
            try:
                f.close()
            except OSError:
                pass


def maybe_writer(path: Optional[str] = None, env: Optional[dict] = None,
                 registry: Optional[MetricsRegistry] = None,
                 period_s: float = 30.0) -> MetricsWriter:
    """The one construction point: explicit `path` wins, else
    $OBS_METRICS, else a disabled writer — mirroring
    obs.spans.maybe_tracer so every instrumented module shares one
    line."""
    p = path or (env if env is not None else os.environ).get(
        OBS_METRICS_ENV)
    return MetricsWriter(registry=registry, path=p, period_s=period_s)


def read_metrics(path: str) -> List[dict]:
    """Every parseable snapshot in a metrics JSONL, torn tail dropped
    (the kill -9 recovery contract, same as obs.spans.read_spans)."""
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
                print("[obs] WARNING: unparseable metrics line %d skipped"
                      % (i + 1), flush=True)
    return out


def read_latest(path: str) -> Optional[dict]:
    """The most recent complete snapshot: the atomic `.latest` sidecar if
    valid, else the last parseable JSONL line."""
    try:
        with open(latest_path(path)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        pass
    snaps = read_metrics(path)
    return snaps[-1] if snaps else None


def snapshot_digest(snap: dict) -> Dict:
    """Digest an ALREADY-READ snapshot dict (obs_report/perfgate: file
    work, no live registry): counters/gauges verbatim, histograms
    reduced to count/mean/p50/p99/max."""
    hists = {}
    for name, h in (snap.get("histograms") or {}).items():
        try:
            hists[name] = Histogram.from_snapshot(name, h).digest()
        except (KeyError, TypeError, ValueError):
            continue
    return {"counters": dict(snap.get("counters") or {}),
            "gauges": dict(snap.get("gauges") or {}),
            "histograms": hists}
