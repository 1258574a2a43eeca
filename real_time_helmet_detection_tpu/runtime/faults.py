"""Deterministic, seeded fault injection — the chaos layer (ISSUE 9).

The reference repo has no fault injection at all (SURVEY.md §5; its only
recovery is a manual restart, ref train.py:190-199). This repo's history
says failure is an input, not an exception: backends lost mid-round,
multi-hour service outages, fetches hanging with zero progress — each
one found an untested recovery path the hard way. This module makes every failure mode a REPLAYABLE input so the
recovery paths above it (ServingEngine in-flight recovery, the train
sentinel/rollback loop, the SHM loader quarantine) are tested code, not
post-mortem folklore.

Design rules, each load-bearing:

* **Stdlib-only.** Lives in runtime/ next to the job supervisor, which
  must never build the ML stack; the chaos suite runs on CPU in the
  smoke tier.
* **Seeded and replayable.** A schedule is a finite list of
  `(site, kind, at)` events — `at` is the Nth arrival at that injection
  site, so a replay against the same code hits the same program points
  regardless of wall clock. `FaultSchedule.seeded(seed, n)` generates
  schedules from a `random.Random(seed)`; `spec()`/`parse()` round-trip
  the textual form (`serve:dispatch=device-loss@3,...`) that
  `serve_bench.py --faults` takes.
* **One event fires once.** Counters are per-site and monotonic; a
  retried operation re-arrives at the site with a HIGHER count, so a
  single scheduled fault cannot permanently wedge a bounded-retry loop
  (the whole point of bounded retries).
* **Every injection is flight-recorder evidence.** `fire()` emits a
  `fault:<kind>` event (site/at/seq meta) through the tracer, so
  `scripts/obs_report.py`'s Faults section can join what was injected
  against the `recover:*` spans of what healed.

Fault taxonomy (docs/ARCHITECTURE.md "Fault injection & self-healing"):

=============  =====================================  =====================
kind           fire() behavior                        models
=============  =====================================  =====================
device-loss    raises InjectedBackendError            PJRT UNAVAILABLE /
               ("UNAVAILABLE: ...")                   backend loss mid-batch
hung-fetch     sleeps `hang_s` (default 0.25) then    the hung transfer:
               raises DEADLINE_EXCEEDED               a D2H that never
                                                      completes
slow-batch     sleeps `slow_s` (default 0.05),        a 2x-loaded box /
               returns the event                      GC pause
nan-batch      returns the event — the CALLER         fp blowup, corrupt
               poisons its data with NaN/Inf          input shard
worker-death   returns the event — the CALLER         OOM-killed loader
               kills/fails its worker                 worker
torn-write     returns the event — the CALLER         kill -9 mid-write
               truncates its write
dropped-frame  returns the event — the CALLER         a camera/RTSP frame
               (the stream session) answers from      lost on the wire
               its cache + emits recover:frame-gap
late-frame     returns the event — the CALLER marks   network jitter: the
               the frame late (in-order delivery      frame shows up after
               machinery absorbs it)                  its successor
corrupt-frame  returns the event — the CALLER         truncated/garbled
               quarantines the frame (never the       decode of one frame
               delta reference) + answers from cache
=============  =====================================  =====================

`fire()`'s contract: raising kinds raise, delay kinds sleep, data kinds
return the event for the caller to apply; `None` means "no fault here".
A `ChaosInjector` with an empty schedule is inert and costs one
attribute check per site arrival — production call sites pass
`injector=None` and skip even that.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from .errors import InjectedBackendError

# raising kinds / delay kinds / caller-applied data kinds (see table);
# the frame kinds (ISSUE 17) are data kinds the stream session applies
FAULT_KINDS = ("device-loss", "hung-fetch", "slow-batch", "nan-batch",
               "worker-death", "torn-write", "dropped-frame",
               "late-frame", "corrupt-frame")

# the documented injection sites (callers may use others; these are the
# instrumented ones and what seeded schedules draw from by default)
SERVE_SITES = ("serve:dispatch", "serve:fetch")
FLEET_SITES = ("fleet:dispatch", "fleet:replica")
# the cascade escalation hop (ISSUE 16): its own tuple, NOT folded into
# FLEET_SITES, so existing seeded fleet schedules replay bit-identically
CASCADE_SITES = ("fleet:escalate",)
# the stream session's frame-arrival site (ISSUE 17): its own tuple, NOT
# folded into SERVE/FLEET_SITES, so existing seeded schedules replay
# bit-identically
STREAM_SITES = ("stream:frame",)
TRAIN_SITES = ("train:batch", "train:rank")
LOADER_SITES = ("loader:batch", "loader:worker")
ARTIFACT_SITES = ("artifact:write",)
ALL_SITES = (SERVE_SITES + FLEET_SITES + CASCADE_SITES + STREAM_SITES
             + TRAIN_SITES + LOADER_SITES + ARTIFACT_SITES)

# which kinds make sense at which sites (seeded generation honors this;
# parse() accepts anything — a hand-written schedule may be adversarial)
SITE_KINDS: Dict[str, Tuple[str, ...]] = {
    "serve:dispatch": ("device-loss", "slow-batch"),
    "serve:fetch": ("device-loss", "hung-fetch", "slow-batch"),
    # the fleet router's own sites (ISSUE 12): a routing-layer dispatch
    # failure (the replica's front door errors before the engine sees the
    # request), and a whole-REPLICA death — the caller (FleetRouter)
    # kills the selected replica abruptly and must respawn-and-requeue
    "fleet:dispatch": ("device-loss", "slow-batch"),
    "fleet:replica": ("worker-death",),
    # the cascade escalation hop (ISSUE 16): device-loss models the quality
    # tier erroring as the second hop launches, worker-death kills the
    # SELECTED quality replica out from under the hop — either way the
    # router must degrade to the in-hand edge answer (`degraded_answer`),
    # never lose the ack
    "fleet:escalate": ("device-loss", "worker-death"),
    # one stream frame's arrival (ISSUE 17): all three are data kinds —
    # the session answers from its tile cache (dropped/corrupt, with a
    # recover:frame-gap event; corrupt additionally quarantined from the
    # delta reference) or absorbs the reorder (late); an acknowledged
    # frame is never lost
    "stream:frame": ("dropped-frame", "late-frame", "corrupt-frame"),
    "train:batch": ("nan-batch", "slow-batch"),
    # a data-parallel training RANK dies (ISSUE 11): the caller raises the
    # UNAVAILABLE signature so the surviving processes' job classifies
    # transient and requeues instead of hanging at the next collective
    "train:rank": ("worker-death",),
    "loader:batch": ("nan-batch", "slow-batch"),
    "loader:worker": ("worker-death",),
    "artifact:write": ("torn-write",),
}


class FaultEvent:
    """One scheduled fault: fire `kind` on the `at`-th arrival (1-based)
    at `site`. `meta` tunes the delay kinds (hang_s / slow_s)."""

    __slots__ = ("site", "kind", "at", "meta")

    def __init__(self, site: str, kind: str, at: int,
                 meta: Optional[dict] = None):
        if kind not in FAULT_KINDS:
            raise ValueError("unknown fault kind %r (have %s)"
                             % (kind, ", ".join(FAULT_KINDS)))
        if at < 1:
            raise ValueError("fault trigger count must be >= 1, got %d" % at)
        self.site = site
        self.kind = kind
        self.at = int(at)
        self.meta = dict(meta or {})

    @property
    def key(self) -> str:
        return "%s=%s@%d" % (self.site, self.kind, self.at)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return "FaultEvent(%s)" % self.key


class FaultSchedule:
    """A finite, ordered set of FaultEvents. Replayable: equality of
    `spec()` strings means equality of injected behavior."""

    def __init__(self, events: Sequence[FaultEvent] = ()):
        self.events: List[FaultEvent] = sorted(
            events, key=lambda e: (e.site, e.at, e.kind))

    def __len__(self) -> int:
        return len(self.events)

    def __iter__(self):
        return iter(self.events)

    def spec(self) -> str:
        """The textual round-trip form (`parse(s.spec())` == s)."""
        return ",".join(e.key for e in self.events)

    @classmethod
    def parse(cls, spec: str) -> "FaultSchedule":
        """Parse `site=kind@n[,site=kind@n...]`, or the seeded shorthand
        `seed=<int>[,n=<int>]` (replayable generation over the serving
        sites — what `serve_bench --faults` wants by default)."""
        spec = (spec or "").strip()
        if not spec:
            return cls(())
        events: List[FaultEvent] = []
        opts: Dict[str, int] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "@" not in part:
                k, _, v = part.partition("=")
                if k not in ("seed", "n") or not v:
                    raise ValueError(
                        "bad fault spec part %r (want site=kind@n, or "
                        "seed=<int>[,n=<int>])" % part)
                opts[k] = int(v)
                continue
            head, at = part.rsplit("@", 1)
            site, _, kind = head.rpartition("=")
            if not site or not kind:
                raise ValueError("bad fault spec part %r (want site=kind@n)"
                                 % part)
            events.append(FaultEvent(site, kind, int(at)))
        if "seed" in opts:
            if events:
                raise ValueError(
                    "fault spec mixes seed= with explicit events; pick one")
            return cls.seeded(opts["seed"], n=opts.get("n", 4))
        return cls(events)

    @classmethod
    def seeded(cls, seed: int, n: int = 4,
               sites: Sequence[str] = SERVE_SITES,
               kinds: Optional[Sequence[str]] = None,
               max_at: Optional[int] = None) -> "FaultSchedule":
        """`n` events drawn deterministically from `random.Random(seed)`.

        Triggers are distinct per site and spread over [2, max_at]
        (default `2 + 3n`) so the first arrival — usually a warmup — is
        never poisoned and faults interleave with healthy traffic."""
        rng = random.Random(seed)
        hi = max_at if max_at is not None else 2 + 3 * max(1, n)
        used: Dict[str, set] = {s: set() for s in sites}
        events: List[FaultEvent] = []
        for _ in range(n):
            site = rng.choice(list(sites))
            pool = kinds if kinds is not None else SITE_KINDS.get(
                site, FAULT_KINDS)
            kind = rng.choice(list(pool))
            # distinct trigger per site: a duplicate would silently merge
            free = [a for a in range(2, hi + 1) if a not in used[site]]
            if not free:
                continue
            at = rng.choice(free)
            used[site].add(at)
            events.append(FaultEvent(site, kind, at))
        return cls(events)


class ChaosInjector:
    """The injection registry instrumented call sites fire through.

    Thread-safe (the serving engine fires from its dispatcher AND fetcher
    threads). `fired` records every injected event in order — the chaos
    tests' ground truth for "what was injected", matching the `fault:*`
    events the tracer carries for post-mortems."""

    def __init__(self, schedule: Optional[FaultSchedule] = None,
                 tracer=None):
        self.schedule = schedule or FaultSchedule(())
        self._tracer = tracer
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        # (site, at) -> event, popped once fired
        self._armed: Dict[Tuple[str, int], FaultEvent] = {
            (e.site, e.at): e for e in self.schedule}
        self.fired: List[FaultEvent] = []

    @property
    def enabled(self) -> bool:
        return bool(self._armed)

    def pending(self) -> int:
        with self._lock:
            return len(self._armed)

    def summary(self) -> Dict[str, int]:
        """Injected-event counts by kind (+ 'total'), for JSON lines."""
        out: Dict[str, int] = {}
        with self._lock:
            for e in self.fired:
                out[e.kind] = out.get(e.kind, 0) + 1
            out["total"] = len(self.fired)
        return out

    def fire(self, site: str, **ctx) -> Optional[FaultEvent]:
        """Arrive at `site`. Returns None (no fault), returns a data-kind
        event for the caller to apply, sleeps for delay kinds, raises for
        error kinds (see the module-docstring table)."""
        with self._lock:
            count = self._counts.get(site, 0) + 1
            self._counts[site] = count
            event = self._armed.pop((site, count), None)
            if event is not None:
                self.fired.append(event)
        if event is None:
            return None
        if self._tracer is not None:
            # caller ctx wins on collision (a stream passes its own seq)
            meta = {"site": site, "at": event.at,
                    "arrival": len(self.fired)}
            meta.update(ctx)
            self._tracer.event("fault:%s" % event.kind, **meta)
        if event.kind == "device-loss":
            raise InjectedBackendError(
                "UNAVAILABLE: injected device-loss at %s (arrival %d)"
                % (site, event.at))
        if event.kind == "hung-fetch":
            time.sleep(float(event.meta.get("hang_s", 0.25)))
            raise InjectedBackendError(
                "DEADLINE_EXCEEDED: injected hung fetch at %s (arrival %d)"
                % (site, event.at))
        if event.kind == "slow-batch":
            time.sleep(float(event.meta.get("slow_s", 0.05)))
        # slow-batch (after its sleep) and the data kinds return the event;
        # nan-batch / worker-death / torn-write are applied by the caller
        # (only it can poison its own data / kill its own worker)
        return event


def maybe_injector(spec_or_schedule, tracer=None) -> Optional[ChaosInjector]:
    """The one construction point for CLI surfaces: '' / None -> None
    (production: zero overhead, not even an attribute check at sites that
    guard on `injector is not None`); a spec string or FaultSchedule ->
    a live ChaosInjector."""
    if not spec_or_schedule:
        return None
    sched = (spec_or_schedule
             if isinstance(spec_or_schedule, FaultSchedule)
             else FaultSchedule.parse(spec_or_schedule))
    if not len(sched):
        return None
    return ChaosInjector(sched, tracer=tracer)
