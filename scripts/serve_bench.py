"""serve_bench — p50/p99 + goodput vs offered load for the serving engine.

The reference's serving story is one frame at a time through its C++ app
(ref README.md:76); it has no load model at all. This bench drives the
continuous-batching engine (real_time_helmet_detection_tpu/serving/) with
an open- and closed-loop load generator and writes the curve the ROADMAP's
"millions of users" item asks for:

* **closed loop** — N clients submit back-to-back: measures the engine's
  saturation capacity (goodput ceiling) and its latency at saturation;
* **open loop** — Poisson arrivals at a set offered rate, each request
  carrying a deadline: measures goodput (on-time completions/s), shed
  counts and p50/p99 latency per offered load, including loads PAST
  saturation where admission control + deadline shedding is what keeps
  goodput at capacity;
* **serial baseline** — the status-quo server this engine replaces: one
  b1 predict per request, FIFO, no batching, no admission control, no
  deadline awareness. At sub-saturation loads it matches the engine; past
  saturation its unbounded queue delay blows through any deadline and its
  goodput collapses — the textbook overload failure the engine exists to
  prevent (and the acceptance ratio this artifact records).

Measurement notes: every latency here is a host-side request wall time
(submit -> result) — the quantity a client experiences — NOT a device
timing claim; bench.py owns those (scanned programs, dispatch-overhead
subtraction). Wall clocks are honest for END-TO-END request latency
because the result fetch is a real D2H.

* **fault scenario mode** (`--faults`, ISSUE 9) — replay a seeded,
  deterministic fault schedule (runtime/faults.py: device-loss, hung
  fetch, slow batch) at the engine's dispatch/fetch sites DURING the
  open-loop run: the curve then reports goodput/p99 under injected
  failure, plus `lost` per row (acknowledged requests that surfaced an
  error) and a `faults` object (what was injected, what the engine
  retried/requeued). The selfcheck pins `lost == 0` under the canned
  schedule — in-flight recovery keeps every acknowledged request.

* **live metrics + SLO (ISSUE 10)** — the engine runs with its own
  `obs.metrics` registry and an `obs.slo` watchdog (error-burn always,
  e2e latency-burn against the goodput deadline): the artifact carries
  the FINAL registry snapshot (`metrics`, schema obs-metrics-v1) and the
  alert list, and the ONE JSON line carries the shed/retry/fill
  aggregates — the same numbers a fleet dashboard would scrape, pinned
  by `--selfcheck` to agree with the engine's own stats rows. Latency
  digests (p50/p99) come from the fixed-layout metrics histogram, not
  hand-rolled percentile arithmetic (graftlint
  ast/raw-metric-aggregation; bucket resolution ~9% is the documented
  precision of these fields).

* **fleet mode (`--replicas N [N...]`, ISSUE 12)** — drive a
  `serving.FleetRouter` over N ServingEngine replicas through the SAME
  load loops and write the fleet-level curve
  (`serve_bench_fleet.json`, schema **serve-bench-fleet-v1**): per-N
  goodput at `--fleet-load`x the measured single-replica capacity,
  per-replica goodput and the scaling efficiency
  goodput@N / (N * goodput@1) that perfgate ratchet-gates in its tight
  `eff` class. The scaling rows run over SIMULATED replicas
  (`--replica-sim-ms`: a fixed-service-time predict whose wall time is a
  GIL-releasing wait — the remote-chip service model, where a replica's
  latency is link+device time the host only waits on). That is the
  CPU-valid fleet-scaling signal on this one-core box, exactly as
  scaling.py's sharding_efficiency is the CPU-valid multi-chip signal
  (r13): real compute cannot parallelize on one core, so real-engine
  rows would measure core contention, not the router. What the sim rows
  DO measure is everything the fleet layer adds: dispatch scoring,
  admission, per-tenant accounting, callback chaining — the router's
  own cost under 2x-overload Poisson load. The canary/death sections
  run REAL engines (bit-identity holds there; no scaling claimed):
  a fault-injected canary rollout that must ROLL BACK on the canary
  slice's `alert:*` and a fleet:replica worker-death — both with
  `lost_acks == 0` (the fleet half of the zero-lost-acks invariant).
  The ONE JSON line gains `replicas`/`tenants`/`canary` fields.

* **cascade mode (`--cascade`, ISSUE 16)** — edge-first serving with
  confidence-gated escalation vs the all-quality status quo, at the SAME
  offered load over the SAME seeded arrival trace and the SAME total
  replica count (`serve_bench_cascade.json`, schema
  **serve-bench-cascade-v1**). Both sides run simulated fixed-service
  replicas (the CPU-valid signal, exactly as fleet mode's scaling rows):
  the all-quality baseline is two quality-tier replicas
  (`--replica-sim-ms` service time); the cascade fleet is one edge
  replica (`--cascade-edge-ms`, emitting a per-row confidence derived
  from the image bytes — pixel[0,0,0]/255 — so the seeded pool fixes the
  escalation mix deterministically) plus one quality replica, routed by
  FleetRouter's cascade policy at `--cascade-threshold`. Offered load is
  `--cascade-load`x the measured all-quality capacity (past its
  saturation by construction): the baseline's goodput pins at its
  capacity while the cascade fleet keeps answering — the
  `cascade_goodput_ratio` >= 2.0 gate (`gate_cascade_2x`) is the
  artifact's headline, ratchet-gated by perfgate in the `eff` class. An
  escalation-fault replay section (`fleet:escalate` device-loss +
  worker-death; `--faults` / the `seed=N` shorthand overrides, drawn
  over the CASCADE sites) pins the degraded-answer contract: a dead or
  dying quality tier degrades to the in-hand edge answer — flagged,
  never a lost ack. The ONE JSON line gains
  `cascade`/`escalation_rate`/`cascade_goodput_ratio` fields.

* **streams mode (`--streams`, ISSUE 17)** — delta-gated tile inference
  vs full-inference for N seeded synthetic camera streams
  (`serving/streams.py` sessions over a FleetRouter of simulated
  PER-TILE-service tile replicas — host waits only, the CPU-valid
  signal as in fleet/cascade mode, but a bucket-b batch costs b x
  `--tile-sim-ms`: tile convs are compute-bound, so device time is
  linear in the padded batch and capacity is tiles/s — skipped tiles
  buy real headroom and batching buys none, which makes the closed-loop
  capacity the true saturation rate), over the SAME seeded
  frame-arrival trace at the SAME offered frame rate
  (`serve_bench_streams.json`, schema **serve-bench-streams-v1**). Each
  stream's frames share `--redundancy` of their tiles frame-to-frame;
  the full-inference arm runs the SAME session/tile path with the
  threshold forced below zero (every tile computes), so the comparison
  isolates the gating alone. Offered load is `--stream-load`x the full
  arm's measured closed-loop capacity (past its saturation by
  construction, within the gated arm's): frame goodput counts
  frames delivered on time with ZERO degraded tiles, and the
  `stream_goodput_ratio` >= 2.0 gate (`gate_streams_2x`) is the
  artifact's headline, ratchet-gated by perfgate in the `eff` class
  next to `computed_tile_fraction` (the compute the gating actually
  spent). A frame-fault replay section (`stream:frame` dropped/late/
  corrupt frames over STREAM_SITES) pins the acknowledged-frame
  contract: gaps answer from the tile cache with `recover:frame-gap`
  events, corrupt frames are quarantined, lost_acks must be 0. The ONE
  JSON line gains `streams`/`computed_tile_fraction`/
  `stream_goodput_ratio` fields.

* **tail exemplars (`--trace-exemplars N`, ISSUE 14)** — the load run
  records trace contexts (obs/trace.py rides the engine/fleet span
  taxonomy; a temp span log is armed automatically when none is
  configured) and the artifact embeds the N slowest requests' FULL
  reassembled waterfalls + critical paths (obs/traceview.py), plus the
  trace-completeness summary (orphans/broken chains — both must be 0:
  every acknowledged request reassembles into one causal chain, re-
  dispatch hops included). The ONE JSON line gains
  `exemplar_p99_stage`: the dominant stage of the slowest exemplar —
  every p99 claim ships with its explanation.

Artifact: `artifacts/<round>/serving/serve_bench.json`, schema
**serve-bench-v1**, atomic write; ONE JSON line on stdout (repo
convention). `--selfcheck` proves the engine contract (bit-identity vs
one-shot predict, shed paths, zero recompiles, zero lost acks under
faults, metrics/stats agreement) AND the fleet contract (fleet results
bit-identical to one-shot, per-tenant shed accounting, zero recompiles
across replicas, a canned fleet:replica death with lost_acks=0) on
seeded CPU load in ~a minute.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import threading
import time
from typing import Dict, List

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import acquire_backend, graft_round  # noqa: E402
from real_time_helmet_detection_tpu.obs.metrics import (  # noqa: E402
    Histogram, MetricsRegistry)
from real_time_helmet_detection_tpu.obs.slo import (  # noqa: E402
    SloWatchdog, default_serving_rules)
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    ChaosInjector, FaultSchedule, maybe_injector, maybe_job_heartbeat,
    run_as_job)
from real_time_helmet_detection_tpu.serving import (  # noqa: E402
    FleetRouter, SheddedError)
from real_time_helmet_detection_tpu.utils import save_json  # noqa: E402

SCHEMA = "serve-bench-v1"
FLEET_SCHEMA = "serve-bench-fleet-v1"
CASCADE_SCHEMA = "serve-bench-cascade-v1"
STREAMS_SCHEMA = "serve-bench-streams-v1"
HB = maybe_job_heartbeat()


def arm_trace_log(args, tracer):
    """Tail exemplars need span records (ISSUE 14): when exemplars are
    requested and no span log is configured, arm a temp one — the
    waterfalls land in the ARTIFACT; the raw log is scratch."""
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    if args.trace_exemplars > 0 and not tracer.enabled:
        import tempfile
        d = tempfile.mkdtemp(prefix="serve_bench_trace.")
        tracer = maybe_tracer(os.path.join(d, "spans.jsonl"))
    return tracer


def trace_sections(tracer, n: int):
    """(trace_exemplars, trace_summary) artifact sections from the run's
    span log: slowest-N waterfalls + the completeness analysis (orphans
    and broken chains are HARD errors — the fleet acceptance gate).
    (None, None) when tracing never armed."""
    if not tracer.enabled or n <= 0:
        return None, None
    from real_time_helmet_detection_tpu.obs import traceview
    tracer.close()
    traces = traceview.assemble_logs([tracer.path])
    summary = traceview.analyze(traces)
    exemplars = traceview.tail_exemplars(traces, n)
    return {"n": n, "exemplars": exemplars}, summary


def log(msg: str) -> None:
    print("[serve_bench] %s" % msg, file=sys.stderr, flush=True)


def _lat_ms(vals: List[float]) -> Dict:
    """p50/p99/mean over host latencies (seconds in, ms out) via the
    obs.metrics fixed-layout histogram — the metrics plane's OWN digest
    path, not hand-rolled percentile arithmetic (graftlint
    ast/raw-metric-aggregation); means are exact, quantiles carry the
    histogram's ~9% bucket resolution."""
    if not vals:
        return {"p50_ms": None, "p99_ms": None, "mean_ms": None}
    h = Histogram("lat_ms")
    for v in vals:
        h.observe(v * 1e3)
    return {"p50_ms": round(h.quantile(0.50), 2),
            "p99_ms": round(h.quantile(0.99), 2),
            "mean_ms": round(h.mean, 2)}


def arrival_schedule(rate_rps: float, duration_s: float,
                     seed: int) -> List[float]:
    """Seeded Poisson arrival offsets (seconds from start) — the SAME
    trace drives the engine and the serial baseline, so the overload
    comparison is apples-to-apples."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    while True:
        t += float(rng.exponential(1.0 / rate_rps))
        if t >= duration_s:
            return out
        out.append(t)


# ---------------------------------------------------------------------------
# load loops (engine-side; pure host threading, no backend assumptions)


def closed_loop(server, pool: List[np.ndarray], clients: int,
                duration_s: float, tracer=None) -> Dict:
    """N clients back-to-back: saturation goodput + latency. `server`
    is anything with the submit/future API — a ServingEngine or a
    FleetRouter (the fleet rows drive this same loop). The horizon
    wall comes from a flight-recorder span (a disabled tracer still
    times), so the measurement lands in the round's span log when
    $OBS_SPAN_LOG is set."""
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = tracer or maybe_tracer()
    stop = threading.Event()
    lats: List[float] = []
    lock = threading.Lock()
    done = [0]

    def client(ci: int) -> None:
        k = ci
        while not stop.is_set():
            fut = server.submit(pool[k % len(pool)])
            k += clients
            try:
                fut.result()
            except Exception:  # noqa: BLE001 — closed/shed at shutdown
                return
            with lock:
                done[0] += 1
                lats.append(fut.t_done - fut.t_submit)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    with tracer.span("serve-bench:closed", clients=clients) as sp:
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
    wall = sp.dur_s
    return {"mode": "closed", "clients": clients,
            "duration_s": round(wall, 2), "completed": done[0],
            "goodput_rps": round(done[0] / wall, 2), **_lat_ms(lats)}


def open_loop(server, pool: List[np.ndarray], schedule: List[float],
              duration_s: float, deadline_s: float,
              offered_rps: float) -> Dict:
    """Poisson arrivals with deadlines; goodput = on-time completions/s.
    Sheds (admission control) are counted, never retried. `lost` counts
    ACKNOWLEDGED (admitted, non-shed) requests that surfaced an error —
    the quantity the chaos selfcheck pins at ZERO under fault injection
    (the engine's bounded retries absorb every scheduled fault)."""
    futs = []
    t0 = time.monotonic()
    for i, at in enumerate(schedule):
        lag = t0 + at - time.monotonic()
        if lag > 0:
            time.sleep(lag)
        futs.append(server.submit(pool[i % len(pool)],
                                  deadline_s=deadline_s, block=False))
    # grace: whatever was admitted near the horizon may still complete
    deadline_wall = time.monotonic() + deadline_s + 2.0
    ontime, late, shed, lost, lats = 0, 0, 0, 0, []
    for fut in futs:
        try:
            fut.result(timeout=max(0.1, deadline_wall - time.monotonic()))
        except SheddedError:
            shed += 1
            continue
        except Exception:  # noqa: BLE001 — retry-exhausted / closed /
            lost += 1      # timed out: an acknowledged request was LOST
            continue
        lat = fut.t_done - fut.t_submit
        lats.append(lat)
        if lat <= deadline_s:
            ontime += 1
        else:
            late += 1
    return {"mode": "open", "offered_rps": round(offered_rps, 2),
            "duration_s": round(duration_s, 2), "n": len(schedule),
            "completed": ontime + late, "ontime": ontime, "late": late,
            "shed": shed, "lost": lost,
            "deadline_ms": round(deadline_s * 1e3, 1),
            "goodput_rps": round(ontime / duration_s, 2), **_lat_ms(lats)}


def serial_loop(predict_b1, variables, pool: List[np.ndarray],
                schedule: List[float], duration_s: float,
                deadline_s: float, offered_rps: float) -> Dict:
    """The status-quo server: per-request b1 predict, FIFO, unbounded
    queue, no deadline awareness. Requests cannot be served before they
    arrive; serving stops at the horizon (whatever is still queued is
    counted missed — the server would only fall further behind)."""
    t0 = time.monotonic()
    t_end = t0 + duration_s
    ontime, served, lats = 0, 0, []
    for i, at in enumerate(schedule):
        now = time.monotonic()
        if now >= t_end:
            break
        lag = t0 + at - now
        if lag > 0:
            time.sleep(lag)  # idle server waits for the next arrival
        out = predict_b1(variables, pool[i % len(pool)][None])
        # np.asarray fetch forces real completion (bench.py idiom) — this
        # loop IS the naive per-request dispatch+fetch server the engine
        # replaces; its wall time is the client-visible metric
        np.asarray(out.scores)
        t_done = time.monotonic()
        lat = t_done - (t0 + at)
        served += 1
        lats.append(lat)
        if lat <= deadline_s:
            ontime += 1
    return {"mode": "serial-b1", "offered_rps": round(offered_rps, 2),
            "duration_s": round(duration_s, 2), "n": len(schedule),
            "served": served, "ontime": ontime,
            "missed": len(schedule) - ontime,
            "deadline_ms": round(deadline_s * 1e3, 1),
            "goodput_rps": round(ontime / duration_s, 2), **_lat_ms(lats)}


# ---------------------------------------------------------------------------
# fleet harness (ISSUE 12)


# fixed-shape per-row output of the simulated replica predict: a
# namedtuple, so the engine's per-row split and jax.device_get treat it
# exactly like the real Detections block
_SimDetections = collections.namedtuple("_SimDetections", "boxes scores")


class _SimCompiled:
    def __init__(self, b: int, service_s: float):
        self.b = b
        self.service_s = service_s

    def __call__(self, variables, images):
        # a GIL-releasing wait IS the service model: a remote replica's
        # latency is link+device time the host only waits on
        time.sleep(self.service_s)
        imgs = np.asarray(images)
        boxes = imgs[:, :2, :2, 0].astype(np.float32).reshape(self.b, -1)
        return _SimDetections(boxes, boxes.sum(axis=1))


class SimServePredict:
    """`make_predict_fn`-shaped stand-in with a fixed service time: the
    engine AOT-compiles and dispatches it exactly like the real program
    (lower(...).compile() per bucket), so the fleet rows exercise the
    REAL router+engine host path end to end — only the device work is
    modeled (see the module docstring's fleet-mode note)."""

    def __init__(self, service_ms: float):
        self.service_s = max(0.0, float(service_ms)) / 1e3

    def lower(self, variables, spec):
        b, service_s = spec.shape[0], self.service_s

        class _Lowered:
            def compile(self):
                return _SimCompiled(b, service_s)

        return _Lowered()


# cascade sim output: the same fixed-shape per-row block plus the
# per-row `confidence` leaf the fleet's escalation gate reads — shaped
# exactly like the real CascadeDetections contract (an extra leaf on the
# output block, zero extra fetches)
_SimCascadeDetections = collections.namedtuple(
    "_SimCascadeDetections", "boxes scores confidence")


class _SimCascadeCompiled(_SimCompiled):
    def __call__(self, variables, images):
        time.sleep(self.service_s)
        imgs = np.asarray(images)
        boxes = imgs[:, :2, :2, 0].astype(np.float32).reshape(self.b, -1)
        # deterministic per-image confidence from the image bytes: the
        # seeded uint8 pool fixes the escalation mix exactly
        conf = imgs[:, 0, 0, 0].astype(np.float32) / 255.0
        return _SimCascadeDetections(boxes, boxes.sum(axis=1), conf)


class SimCascadePredict(SimServePredict):
    """Edge-tier sim predict: `SimServePredict` plus a per-row
    `confidence` in [0, 1] read off pixel[0,0,0] of each image —
    `sim_confidence()` is the host-side oracle, so the realized
    escalation fraction of a pool is known before the run."""

    def lower(self, variables, spec):
        b, service_s = spec.shape[0], self.service_s

        class _Lowered:
            def compile(self):
                return _SimCascadeCompiled(b, service_s)

        return _Lowered()

    @staticmethod
    def sim_confidence(img: np.ndarray) -> float:
        return float(img[0, 0, 0]) / 255.0


class _TenantPin:
    """submit-shim pinning every request to one tenant: the open/closed
    load loops stay tenant-agnostic while the cascade rows ride the
    enrolled cascade tenant."""

    def __init__(self, router, tenant: str):
        self.router, self.tenant = router, tenant

    def submit(self, image, **kw):
        return self.router.submit(image, tenant=self.tenant, **kw)


def make_replica_factory(predict, variables, imsize, buckets,
                         queue_capacity=64, max_wait_ms=2.0, depth=2,
                         max_retries=4, injector_for=None, tracer=None):
    """THE sanctioned replica-construction point for fleet runs
    (graftlint ast/engine-bypass-in-fleet allowlists this scope): each
    replica gets its own MetricsRegistry (per-replica health digests)
    and, optionally, its own chaos injector keyed by rid (the canary
    run arms faults on the canary replica only)."""
    from real_time_helmet_detection_tpu.serving import ServingEngine

    def factory(rid, start=True):
        inj = None
        if injector_for and rid in injector_for:
            inj = ChaosInjector(FaultSchedule.parse(injector_for[rid]),
                                tracer=tracer)
        return ServingEngine(predict, variables, (imsize, imsize, 3),
                             np.uint8, buckets=buckets,
                             max_wait_ms=max_wait_ms, depth=depth,
                             queue_capacity=queue_capacity,
                             max_retries=max_retries,
                             metrics=MetricsRegistry(), injector=inj,
                             tracer=tracer, start=start)

    return factory


def _perturb(variables):
    """A distinct checkpoint for rollout runs: one kernel shifted."""
    import jax as _jax
    leaves, treedef = _jax.tree.flatten(_jax.device_get(variables))
    leaves = [np.asarray(x) for x in leaves]
    leaves[0] = leaves[0] + 0.25
    return _jax.tree.unflatten(treedef, leaves)


def fleet_scaling_rows(args, tracer, parts=None) -> List[Dict]:
    """The headline fleet rows: open-loop goodput at `--fleet-load`x the
    per-replica capacity, for each N in --replicas, over simulated
    replicas by default (module docstring). `--replica-sim-ms 0` runs
    REAL engines instead (`parts` = the built predict/variables/pool) —
    the chip-mode rows, where N in-process replicas share the one
    chip and the curve measures real shared-device routing, not the
    one-core CPU contention artifact. scaling_eff@N = goodput@N /
    (N * goodput@1) — the quantity perfgate gates in the `eff` class."""
    if args.replica_sim_ms > 0:
        predict, variables = SimServePredict(args.replica_sim_ms), \
            {"w": np.zeros(1)}
    else:
        if parts is None:
            raise ValueError("--replica-sim-ms 0 needs the real parts")
        predict, variables = parts[0], parts[1]
    buckets = tuple(sorted(set(args.buckets)))
    deadline_s = args.deadline_ms / 1e3
    rows: List[Dict] = []
    cap1 = None
    for n in args.replicas:
        factory = make_replica_factory(predict, variables,
                                       args.imsize, buckets,
                                       queue_capacity=max(args.queue_cap,
                                                          64),
                                       max_wait_ms=args.max_wait_ms,
                                       depth=args.depth, tracer=tracer)
        router = FleetRouter(factory, n, metrics=MetricsRegistry(),
                             default_budget=1_000_000, tracer=tracer)
        try:
            if cap1 is None:
                closed = closed_loop(router, _sim_pool(args), args.clients,
                                     max(2.0, args.duration / 2),
                                     tracer=tracer)
                cap1 = max(closed["goodput_rps"] / n, 1e-6)
                log("fleet sim capacity: %.1f req/s per replica (N=%d "
                    "closed loop)" % (cap1, n))
            rate = args.fleet_load * n * cap1
            sched = arrival_schedule(rate, args.duration,
                                     args.seed + 31 * n)
            row = open_loop(router, _sim_pool(args), sched, args.duration,
                            deadline_s, rate)
        finally:
            router.close()
        row["replicas"] = n
        row["per_replica_goodput"] = round(row["goodput_rps"] / n, 2)
        rows.append(row)
        log("fleet x%d (%.0f rps offered): goodput %.1f (%.1f/replica), "
            "p99 %s ms, shed %d, lost %d"
            % (n, rate, row["goodput_rps"], row["per_replica_goodput"],
               row["p99_ms"], row["shed"], row["lost"]))
        HB.beat("fleet row N=%d done" % n)
    g1 = max(rows[0]["goodput_rps"], 1e-6)
    for row in rows:
        row["scaling_eff"] = round(row["goodput_rps"]
                                   / (row["replicas"] * g1), 4)
    return rows


def _sim_pool(args) -> List[np.ndarray]:
    rng = np.random.default_rng(args.seed)
    return [rng.integers(0, 256, (args.imsize, args.imsize, 3),
                         dtype=np.uint8) for _ in range(args.pool)]


def wait_canary_armed(router, rollout_thread, timeout_s: float = 60.0
                      ) -> None:
    """Block until the rollout has picked + reloaded its canary (the
    router's health() flips `canary` non-None only after the swap) — the
    deterministic replacement for the old fixed pre-traffic sleep.
    Control-path polling, mirrors engine.drain's discipline."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline and rollout_thread.is_alive():
        if router.health()["canary"] is not None:
            return
        time.sleep(0.005)
    if not rollout_thread.is_alive():
        return  # rollout already resolved (its outcome tells the story)
    raise RuntimeError("canary never armed within %.0fs" % timeout_s)


def fleet_canary_run(args, predict, variables, pool, tracer) -> Dict:
    """The fault-injected canary-rollback proof over REAL engines: faults
    armed on the canary replica burn its error budget mid-rollout, the
    watchdog fires `alert:*` on the canary slice, the rollout ROLLS BACK
    — and zero acknowledged requests are lost across the whole arc. A
    multi-tenant traffic mix rides along so the per-tenant counters land
    in the artifact."""
    new_vars = _perturb(variables)
    buckets = tuple(b for b in sorted(set(args.buckets)) if b <= 4) or (1,)
    factory = make_replica_factory(
        predict, variables, args.imsize, buckets,
        queue_capacity=64, max_wait_ms=1.0,
        injector_for={0: "serve:dispatch=device-loss@6,"
                         "serve:dispatch=device-loss@9"},
        tracer=tracer)
    mreg = MetricsRegistry()
    tenants = dict(args.tenant_budgets) or {"bulk": 64, "flagged": 64}
    router = FleetRouter(factory, 2, variables=variables, tenants=tenants,
                         default_budget=100_000, metrics=mreg,
                         tracer=tracer)
    names = sorted(tenants)
    stop = threading.Event()
    futs: List = []
    lock = threading.Lock()

    def traffic():
        # sub-saturation pacing on purpose: the claim here is recovery
        # accounting (lost_acks == 0), not overload behavior — and on a
        # one-core host a flat-out replica starves its neighbors' XLA:CPU
        # executions outright (the work queue is not fair across client
        # threads), which is a host artifact, not a fleet property
        k = 0
        while not stop.is_set():
            f = router.submit(pool[k % len(pool)],
                              tenant=names[k % len(names)])
            with lock:
                futs.append(f)
            k += 1
            time.sleep(0.02)

    res_box: Dict = {}
    rt = threading.Thread(target=lambda: res_box.update(
        res=router.rollout(new_vars, canary_frac=0.9, window=100_000,
                           timeout_s=60.0)), daemon=True)
    rt.start()
    # deterministic arming (ISSUE 14 satellite — the canary flake class):
    # wait for the rollout to PICK + RELOAD the canary on the quiescent
    # fleet before any traffic flows; a fixed sleep here was box-speed
    # dependent (a slow box let traffic race the pick, so the canary
    # could land on the un-injected replica and the watchdog never fired)
    wait_canary_armed(router, rt)
    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    rt.join(timeout=120)
    stop.set()
    th.join(timeout=30)
    lost = 0
    with lock:
        pending = list(futs)
    for f in pending:
        try:
            f.result(timeout=60)
        except SheddedError:
            pass
        except Exception:  # noqa: BLE001 — a lost acknowledged request
            lost += 1
    res = res_box.get("res") or {"outcome": "rollout-never-finished",
                                 "alerts": []}
    st = router.stats()
    health = router.health()
    router.close()
    out = {"outcome": res["outcome"], "canary_rid": res.get("canary"),
           "alerts": [a["rule"] for a in res.get("alerts", [])],
           "requests": len(pending), "lost_acks": lost,
           "router_lost": st["lost"], "redispatched": st["redispatched"],
           "rollbacks": st["rollbacks"], "promotes": st["promotes"],
           "tenants": health["tenants"]}
    log("fleet canary: %s (alerts %s), %d requests, lost acks %d"
        % (out["outcome"], out["alerts"] or "none", out["requests"],
           out["lost_acks"]))
    return out


def fleet_death_run(args, predict, variables, pool, tracer) -> Dict:
    """The fleet:replica acceptance run over REAL engines: a seeded
    worker-death kills a live replica mid-stream (plus a fleet:dispatch
    device-loss at the front door); re-dispatch + respawn keep every
    acknowledged request — lost_acks must be 0. `--faults` overrides the
    canned schedule (the `seed=N` shorthand draws over the FLEET sites
    here, spread across the burst)."""
    from real_time_helmet_detection_tpu.runtime.faults import FLEET_SITES
    buckets = tuple(b for b in sorted(set(args.buckets)) if b <= 4) or (1,)
    factory = make_replica_factory(predict, variables, args.imsize,
                                   buckets, queue_capacity=64,
                                   max_wait_ms=1.0, tracer=tracer)
    spec = (args.faults or "").strip()
    if spec.startswith("seed="):
        opts = dict(p.split("=", 1) for p in spec.split(",") if "=" in p)
        sched = FaultSchedule.seeded(int(opts["seed"]),
                                     n=int(opts.get("n", 3)),
                                     sites=FLEET_SITES, max_at=40)
    elif spec:
        sched = FaultSchedule.parse(spec)
    else:
        sched = FaultSchedule.parse(
            "fleet:dispatch=device-loss@3,fleet:replica=worker-death@40")
    inj = ChaosInjector(sched, tracer=tracer)
    router = FleetRouter(factory, 2, metrics=MetricsRegistry(),
                         default_budget=100_000, injector=inj,
                         tracer=tracer)
    futs = []
    # one dense burst deep enough to overrun each replica's pipeline
    # (forming batch + depth in-flight), so queued backlog exists when
    # the death fires and the kill exercises the re-dispatch path
    # (killed queued acks re-routed), not just respawn
    for k in range(48):
        futs.append(router.submit(pool[k % len(pool)]))
    lost = 0
    for f in futs:
        try:
            f.result(timeout=120)
        except Exception:  # noqa: BLE001 — a lost acknowledged request
            lost += 1
    st = router.stats()
    router.close()
    out = {"spec": inj.schedule.spec(), "injected": inj.summary(),
           "requests": len(futs), "lost_acks": lost,
           "replica_deaths": st["replica_deaths"],
           "respawns": st["respawns"],
           "redispatched": st["redispatched"]}
    log("fleet death: %d injected, deaths %d, respawns %d, lost acks %d"
        % (out["injected"]["total"], out["replica_deaths"],
           out["respawns"], out["lost_acks"]))
    return out


def run_fleet_bench(args) -> Dict:
    jax, devs = acquire_backend()
    platform = devs[0].platform
    log("backend up: %s (fleet mode, replicas %s)"
        % (platform, list(args.replicas)))
    HB.beat("backend up (%s, fleet)" % platform)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = arm_trace_log(args, maybe_tracer(args.span_log or None))

    out: Dict = {"schema": FLEET_SCHEMA, "tool": "serve_bench",
                 "platform": platform, "imsize": args.imsize,
                 "inch": args.inch, "topk": args.topk,
                 "infer_dtype": args.infer_dtype,
                 "buckets": list(args.buckets),
                 "replicas": list(args.replicas),
                 "replica_sim_ms": args.replica_sim_ms,
                 "fleet_load": args.fleet_load,
                 "deadline_ms": args.deadline_ms, "seed": args.seed,
                 "note": ("scaling rows run simulated replicas (fixed "
                          "service time, host waits only) — the CPU-"
                          "valid fleet signal on a one-core box; canary/"
                          "death sections run real engines (module "
                          "docstring, fleet-mode note)")}
    cfg, predict, variables, pool = build_parts(args, jax)
    out["rows"] = fleet_scaling_rows(
        args, tracer,
        parts=(predict, variables) if args.replica_sim_ms <= 0 else None)
    HB.beat("fleet scaling rows done")
    out["canary"] = fleet_canary_run(args, predict, variables, pool,
                                     tracer)
    HB.beat("fleet canary run done")
    out["death"] = fleet_death_run(args, predict, variables, pool, tracer)
    HB.beat("fleet death run done")
    out["tenants"] = sorted(out["canary"]["tenants"])
    out["gate_scaling_08"] = bool(all(
        r["scaling_eff"] >= 0.8 for r in out["rows"]))
    out["gate_zero_lost_acks"] = bool(
        out["canary"]["lost_acks"] == 0 and out["death"]["lost_acks"] == 0
        and all(r["lost"] == 0 for r in out["rows"]))
    # tail exemplars + trace completeness over the WHOLE fleet run
    # (scaling rows + canary + death — re-dispatch hops included): every
    # acknowledged request must reassemble into one causal chain
    exemplars, tsummary = trace_sections(tracer, args.trace_exemplars)
    if exemplars is not None:
        out["trace_exemplars"] = exemplars
        out["trace_summary"] = tsummary
        if exemplars["exemplars"]:
            out["exemplar_p99_stage"] = \
                exemplars["exemplars"][0]["critical_path"]["dominant_stage"]
        out["gate_traces_complete"] = bool(
            tsummary["orphans"] == 0 and tsummary["broken_chains"] == 0
            and tsummary["request_traces"] > 0)
        log("trace gate: %d request traces, orphans %d, broken %d, "
            "redispatched %d, p99 stage %s"
            % (tsummary["request_traces"], tsummary["orphans"],
               tsummary["broken_chains"], tsummary["redispatched_traces"],
               out.get("exemplar_p99_stage")))
    log("fleet gates: scaling>=0.8 %s, zero lost acks %s"
        % (out["gate_scaling_08"], out["gate_zero_lost_acks"]))
    return out


# ---------------------------------------------------------------------------
# cascade harness (ISSUE 16)


def make_cascade_sim_factory(args, tracer=None):
    """rid 0 -> edge-tier sim replica (fast service, confidence leaf),
    rid 1 -> quality-tier sim replica. Both inner factories come from
    `make_replica_factory` (THE sanctioned construction point — this
    wrapper only picks between them by rid, the mapping `replica_tiers`
    mirrors)."""
    buckets = tuple(sorted(set(args.buckets)))
    kw = dict(queue_capacity=max(args.queue_cap, 64),
              max_wait_ms=args.max_wait_ms, depth=args.depth,
              tracer=tracer)
    edge_f = make_replica_factory(SimCascadePredict(args.cascade_edge_ms),
                                  {"w": np.zeros(1)}, args.imsize,
                                  buckets, **kw)
    qual_f = make_replica_factory(SimServePredict(args.replica_sim_ms),
                                  {"w": np.zeros(1)}, args.imsize,
                                  buckets, **kw)

    def factory(rid, start=True):
        return (edge_f if rid == 0 else qual_f)(rid, start=start)

    return factory


def cascade_fault_run(args, tracer) -> Dict:
    """The escalation-hop acceptance run: a quality-tier device-loss and
    a quality-replica worker-death fire mid-cascade (`fleet:escalate`
    site; everything escalates — threshold above the sim confidence
    range) and every acknowledged request still answers — the loss
    degrades to the in-hand edge result (flagged `degraded_answer`),
    the death respawns and the hop proceeds. lost_acks must be 0."""
    from real_time_helmet_detection_tpu.runtime.faults import \
        CASCADE_SITES
    spec = (args.faults or "").strip()
    if spec.startswith("seed="):
        opts = dict(p.split("=", 1) for p in spec.split(",") if "=" in p)
        sched = FaultSchedule.seeded(int(opts["seed"]),
                                     n=int(opts.get("n", 2)),
                                     sites=CASCADE_SITES, max_at=24)
    elif spec:
        sched = FaultSchedule.parse(spec)
    else:
        sched = FaultSchedule.parse("fleet:escalate=device-loss@2,"
                                    "fleet:escalate=worker-death@5")
    inj = ChaosInjector(sched, tracer=tracer)
    pool = _sim_pool(args)
    # derived, not hand-picked: one above the pool's own sim-confidence
    # max, so every request escalates and the injected quality-tier
    # faults are guaranteed to land on an in-flight hop
    th_all = max(SimCascadePredict.sim_confidence(img)
                 for img in pool) + 1.0
    router = FleetRouter(make_cascade_sim_factory(args, tracer), 2,
                         replica_tiers=list(args.cascade_tiers),
                         cascade_tenants=["cascade"],
                         cascade_tiers=tuple(args.cascade_tiers),
                         cascade_threshold=th_all,
                         metrics=MetricsRegistry(),
                         default_budget=1_000_000, injector=inj,
                         tracer=tracer)
    futs = [router.submit(img, tenant="cascade")
            for img in pool * 2]
    lost = 0
    for f in futs:
        try:
            f.result(timeout=120)
        except Exception:  # noqa: BLE001 — a lost acknowledged request
            lost += 1
    st = router.stats()
    router.close()
    out = {"spec": inj.schedule.spec(), "injected": inj.summary(),
           "requests": len(futs), "lost_acks": lost,
           "degraded_answers": st["degraded_answers"],
           "escalated": st["escalated"],
           "replica_deaths": st["replica_deaths"],
           "respawns": st["respawns"]}
    log("cascade faults: %d injected, degraded %d, deaths %d, "
        "lost acks %d" % (out["injected"]["total"],
                          out["degraded_answers"],
                          out["replica_deaths"], out["lost_acks"]))
    return out


def run_cascade_bench(args) -> Dict:
    """Cascade vs all-quality at the SAME offered load over the SAME
    seeded arrival trace and the SAME total replica count (module
    docstring, cascade-mode note). Sections: all-quality capacity
    (closed loop) -> one overload open-loop row per side -> the
    escalation-fault replay -> trace completeness over the whole run."""
    jax, devs = acquire_backend()
    platform = devs[0].platform
    log("backend up: %s (cascade mode)" % platform)
    HB.beat("backend up (%s, cascade)" % platform)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = arm_trace_log(args, maybe_tracer(args.span_log or None))

    threshold = args.cascade_threshold
    pool = _sim_pool(args)
    pool_esc = sum(1 for img in pool
                   if SimCascadePredict.sim_confidence(img) < threshold) \
        / len(pool)
    out: Dict = {"schema": CASCADE_SCHEMA, "tool": "serve_bench",
                 "platform": platform, "imsize": args.imsize,
                 "buckets": list(sorted(set(args.buckets))),
                 "cascade": True,
                 "cascade_tiers": list(args.cascade_tiers),
                 "cascade_threshold": threshold,
                 "edge_sim_ms": args.cascade_edge_ms,
                 "quality_sim_ms": args.replica_sim_ms,
                 "cascade_load": args.cascade_load,
                 "deadline_ms": args.deadline_ms, "seed": args.seed,
                 "pool_escalation_frac": round(pool_esc, 3),
                 "note": ("both sides run simulated fixed-service "
                          "replicas (host waits only — the CPU-valid "
                          "signal, fleet-mode note); cascade = 1 edge + "
                          "1 quality replica vs 2 quality replicas, "
                          "same seeded Poisson trace at the same "
                          "offered load")}
    deadline_s = args.deadline_ms / 1e3

    def quality_factory():
        return make_replica_factory(
            SimServePredict(args.replica_sim_ms), {"w": np.zeros(1)},
            args.imsize, tuple(sorted(set(args.buckets))),
            queue_capacity=max(args.queue_cap, 64),
            max_wait_ms=args.max_wait_ms, depth=args.depth,
            tracer=tracer)

    # all-quality baseline: capacity, then one past-saturation row
    base = FleetRouter(quality_factory(), 2, metrics=MetricsRegistry(),
                       default_budget=1_000_000, tracer=tracer)
    try:
        closed = closed_loop(base, pool, args.clients,
                             max(2.0, args.duration / 2), tracer=tracer)
        cap = max(closed["goodput_rps"], 1e-6)
        out["all_quality_capacity_rps"] = closed["goodput_rps"]
        log("all-quality capacity: %.1f req/s (2 replicas, closed loop)"
            % cap)
        rate = args.cascade_load * cap
        sched = arrival_schedule(rate, args.duration, args.seed + 616)
        out["offered_rps"] = round(rate, 2)
        row_base = open_loop(base, pool, sched, args.duration,
                             deadline_s, rate)
    finally:
        base.close()
    row_base["mode"] = "all-quality"
    log("all-quality at %.1f rps offered: goodput %.1f, p99 %s ms, "
        "shed %d" % (rate, row_base["goodput_rps"], row_base["p99_ms"],
                     row_base["shed"]))
    HB.beat("all-quality row done")

    # cascade fleet over the SAME trace (identical schedule object)
    casc = FleetRouter(make_cascade_sim_factory(args, tracer), 2,
                       replica_tiers=list(args.cascade_tiers),
                       cascade_tenants=["cascade"],
                       cascade_tiers=tuple(args.cascade_tiers),
                       cascade_threshold=threshold,
                       metrics=MetricsRegistry(),
                       default_budget=1_000_000, tracer=tracer)
    try:
        row_casc = open_loop(_TenantPin(casc, "cascade"), pool, sched,
                             args.duration, deadline_s, rate)
    finally:
        st = casc.stats()
        casc.close()
    row_casc["mode"] = "cascade"
    hops = max(st["edge_resolved"] + st["escalated"], 1)
    out["escalation_rate"] = round(st["escalated"] / hops, 4)
    out["edge_resolved"] = st["edge_resolved"]
    out["escalated"] = st["escalated"]
    out["degraded_answers"] = st["degraded_answers"]
    out["rows"] = [row_casc, row_base]
    ratio = row_casc["goodput_rps"] / max(row_base["goodput_rps"], 1e-6)
    out["cascade_goodput_ratio"] = round(ratio, 2)
    out["gate_cascade_2x"] = bool(ratio >= 2.0)
    log("cascade at the same %.1f rps: goodput %.1f vs %.1f all-quality "
        "(%.2fx, escalation rate %.1f%%, gate_cascade_2x=%s)"
        % (rate, row_casc["goodput_rps"], row_base["goodput_rps"],
           ratio, 100 * out["escalation_rate"], out["gate_cascade_2x"]))
    HB.beat("cascade row done")

    out["faults"] = cascade_fault_run(args, tracer)
    HB.beat("cascade fault run done")
    out["gate_zero_lost_acks"] = bool(
        row_casc["lost"] == 0 and row_base["lost"] == 0
        and out["faults"]["lost_acks"] == 0)

    exemplars, tsummary = trace_sections(tracer, args.trace_exemplars)
    if exemplars is not None:
        out["trace_exemplars"] = exemplars
        out["trace_summary"] = tsummary
        if exemplars["exemplars"]:
            out["exemplar_p99_stage"] = \
                exemplars["exemplars"][0]["critical_path"]["dominant_stage"]
        out["gate_traces_complete"] = bool(
            tsummary["orphans"] == 0 and tsummary["broken_chains"] == 0
            and tsummary["request_traces"] > 0)
        log("trace gate: %d request traces, orphans %d, broken %d, "
            "p99 stage %s" % (tsummary["request_traces"],
                              tsummary["orphans"],
                              tsummary["broken_chains"],
                              out.get("exemplar_p99_stage")))
    log("cascade gates: 2x goodput %s, zero lost acks %s"
        % (out["gate_cascade_2x"], out["gate_zero_lost_acks"]))
    return out


# ---------------------------------------------------------------------------
# streams harness (ISSUE 17)


# per-tile sim output shaped EXACTLY like ops.decode.Detections (same
# field names, same order) so the stream session's smooth/stitch path
# treats sim tiles like real ones; every leaf is a pure function of the
# image bytes, so identical frame bytes give identical detections and
# the A/B arms are comparable row for row
_SimTileDetections = collections.namedtuple(
    "_SimTileDetections", "boxes classes scores valid")

_SIM_TILE_ROWS = 4


class _SimStreamCompiled(_SimCompiled):
    def __call__(self, variables, images):
        # per-TILE service: a bucket-b batch costs b x the tile time.
        # Tile convs at these sizes are compute-bound, so device time is
        # ~linear in the (padded) batch — a fixed per-batch service
        # would hand the full-inference arm free batching and the A/B
        # would measure router behavior, not compute savings.
        time.sleep(self.service_s * self.b)
        imgs = np.asarray(images)
        k = _SIM_TILE_ROWS
        base = imgs[:, :k, 0, 0].astype(np.float32)
        boxes = np.stack([base, base, base + 4.0, base + 4.0], axis=-1)
        classes = (imgs[:, :k, 1, 0] % 2).astype(np.int32)
        scores = imgs[:, :k, 2, 0].astype(np.float32) / 255.0
        valid = np.ones((self.b, k), bool)
        return _SimTileDetections(boxes, classes, scores, valid)


class SimStreamPredict(SimServePredict):
    """Tile-replica sim predict: per-TILE service time (a bucket-b
    batch sleeps b x `service_ms` — the compute-bound conv model, so
    capacity is tiles/s and skipping tiles buys real headroom),
    Detections-shaped output derived from the tile bytes (deterministic
    — the stream A/B arms see the same rows for the same tiles)."""

    def lower(self, variables, spec):
        b, service_s = spec.shape[0], self.service_s

        class _Lowered:
            def compile(self):
                return _SimStreamCompiled(b, service_s)

        return _Lowered()


def synth_stream_frames(args, sid: int, n_frames: int) -> List[np.ndarray]:
    """One seeded synthetic camera stream: frame 0 is random uint8; each
    later frame keeps every tile with probability `--redundancy` and
    re-randomizes it otherwise — the controlled-redundancy fixture the
    gating claim is measured on. Per-stream seed, so streams differ but
    both A/B arms replay the IDENTICAL sequences."""
    from real_time_helmet_detection_tpu.ops.delta import tile_origins
    rng = np.random.default_rng(args.seed * 1000 + 77 + sid)
    g = args.tile_grid
    fshape = (g * args.imsize, g * args.imsize, 3)
    origins = tile_origins(fshape, g)
    frames = [rng.integers(0, 256, fshape, dtype=np.uint8)]
    while len(frames) < n_frames:
        nxt = frames[-1].copy()
        for (y0, x0) in origins:
            if rng.random() >= args.redundancy:
                nxt[y0:y0 + args.imsize, x0:x0 + args.imsize] = \
                    rng.integers(0, 256, (args.imsize, args.imsize, 3),
                                 dtype=np.uint8)
        frames.append(nxt)
    return frames


def stream_closed_loop(sessions, seqs, duration_s: float,
                       tracer=None) -> Dict:
    """Each stream submits back-to-back (next frame when the previous
    delivers): the session path's saturation capacity in frames/s — the
    anchor the open-loop offered rate multiplies."""
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = tracer or maybe_tracer()
    stop = threading.Event()
    lock = threading.Lock()
    done = [0]

    def cam(si: int) -> None:
        sess, frames = sessions[si], seqs[si]
        k = 0
        while not stop.is_set():
            fut = sess.submit_frame(frames[k % len(frames)])
            k += 1
            try:
                fut.result(timeout=60)
            except Exception:  # noqa: BLE001 — closing down
                return
            with lock:
                done[0] += 1

    threads = [threading.Thread(target=cam, args=(i,), daemon=True)
               for i in range(len(sessions))]
    with tracer.span("serve-bench:stream-closed",
                     streams=len(sessions)) as sp:
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
    wall = sp.dur_s
    return {"mode": "stream-closed", "streams": len(sessions),
            "duration_s": round(wall, 2), "frames": done[0],
            "goodput_fps": round(done[0] / wall, 2)}


def stream_open_loop(sessions, seqs, schedules, duration_s: float,
                     deadline_s: float, offered_fps: float,
                     mode: str) -> Dict:
    """Seeded Poisson frame arrivals per stream; every frame is
    acknowledged at submit and ALWAYS delivers (the session contract).
    Frame goodput counts frames delivered on time with ZERO degraded
    tiles — a degraded frame answered (from the cache) but its evidence
    is stale, so it does not earn goodput. `lost` counts frames whose
    future never delivered: the quantity the chaos selfcheck and the
    artifact gate pin at ZERO. Completion is stamped by the session's
    delivery callback, so the latency is delivery time, not
    collector-poll time."""
    lock = threading.Lock()
    rows: List = []   # (latency_s, degraded_tiles, gap)
    lost = [0]
    t0 = time.monotonic() + 0.05

    def cam(si: int) -> None:
        sess, frames, sched = sessions[si], seqs[si], schedules[si]
        futs = []
        for k, at in enumerate(sched):
            lag = t0 + at - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            arrive = t0 + at

            def stamp(f, arrive=arrive):
                # delivery latency from the future's own t_done stamp
                # (the session's delivery thread writes it before the
                # callback fires) — no hand-rolled span timing here
                res = f.result(timeout=0)
                with lock:
                    rows.append((f.t_done - arrive,
                                 res.degraded_tiles, res.gap))

            fut = sess.submit_frame(frames[k % len(frames)])
            fut.add_done_callback(stamp)
            futs.append(fut)
        grace = time.monotonic() + deadline_s + 3.0
        for f in futs:
            try:
                f.result(timeout=max(0.1, grace - time.monotonic()))
            except Exception:  # noqa: BLE001 — an undelivered frame
                with lock:
                    lost[0] += 1

    threads = [threading.Thread(target=cam, args=(i,), daemon=True)
               for i in range(len(sessions))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with lock:
        got = list(rows)
        nlost = lost[0]
    lats = [lat for lat, _, _ in got]
    ontime = sum(1 for lat, deg, gap in got
                 if lat <= deadline_s and deg == 0 and not gap)
    degraded = sum(1 for _, deg, _ in got if deg > 0)
    n = sum(len(s) for s in schedules)
    return {"mode": mode, "offered_fps": round(offered_fps, 2),
            "duration_s": round(duration_s, 2), "n": n,
            "completed": len(got), "ontime": ontime,
            "degraded": degraded, "lost": nlost,
            "deadline_ms": round(deadline_s * 1e3, 1),
            "goodput_fps": round(ontime / duration_s, 2), **_lat_ms(lats)}


def make_stream_fleet(args, tracer=None):
    """Two simulated tile replicas behind the FleetRouter — the serving
    surface both A/B arms share (`make_replica_factory` is THE
    sanctioned construction point)."""
    return FleetRouter(
        make_replica_factory(SimStreamPredict(args.tile_sim_ms),
                             {"w": np.zeros(1)}, args.imsize,
                             tuple(sorted(set(args.buckets))),
                             queue_capacity=max(args.queue_cap, 64),
                             max_wait_ms=args.max_wait_ms,
                             depth=args.depth, tracer=tracer),
        2, metrics=MetricsRegistry(), default_budget=1_000_000,
        tracer=tracer)


def make_stream_sessions(args, router, threshold: float, deadline_s,
                         injector=None, tracer=None):
    from real_time_helmet_detection_tpu.serving import StreamSession
    g = args.tile_grid
    fshape = (g * args.imsize, g * args.imsize, 3)
    return [StreamSession(router, fshape, grid=g, threshold=threshold,
                          deadline_s=deadline_s, injector=injector,
                          tracer=tracer, sid=sid)
            for sid in range(args.streams_n)]


def stream_fault_run(args, tracer) -> Dict:
    """The frame-fault acceptance run: dropped/late/corrupt frames fire
    mid-stream (`stream:frame` site; `--faults` / the `seed=N` shorthand
    overrides, drawn over STREAM_SITES) and every acknowledged frame
    still delivers — gaps answer from the tile cache with
    `recover:frame-gap` events, corrupt frames are quarantined (never
    the delta reference). lost_acks must be 0."""
    from real_time_helmet_detection_tpu.runtime.faults import STREAM_SITES
    spec = (args.faults or "").strip()
    if spec.startswith("seed="):
        opts = dict(p.split("=", 1) for p in spec.split(",") if "=" in p)
        sched = FaultSchedule.seeded(int(opts["seed"]),
                                     n=int(opts.get("n", 3)),
                                     sites=STREAM_SITES, max_at=10)
    elif spec:
        sched = FaultSchedule.parse(spec)
    else:
        sched = FaultSchedule.parse("stream:frame=dropped-frame@2,"
                                    "stream:frame=corrupt-frame@5,"
                                    "stream:frame=late-frame@8")
    inj = ChaosInjector(sched, tracer=tracer)
    router = make_stream_fleet(args, tracer)
    from real_time_helmet_detection_tpu.serving import StreamSession
    g = args.tile_grid
    sess = StreamSession(router, (g * args.imsize, g * args.imsize, 3),
                         grid=g, threshold=args.stream_threshold,
                         injector=inj, tracer=tracer, sid=0)
    frames = synth_stream_frames(args, 0, 12)
    futs = [sess.submit_frame(f) for f in frames]
    lost = 0
    for f in futs:
        try:
            f.result(timeout=120)
        except Exception:  # noqa: BLE001 — a lost acknowledged frame
            lost += 1
    st = sess.stats()
    sess.close()
    router.close()
    out = {"spec": inj.schedule.spec(), "injected": inj.summary(),
           "frames": len(futs), "lost_acks": lost, "gaps": st["gaps"],
           "corrupt": st["corrupt"], "late": st["late"],
           "degraded_tiles": st["degraded_tiles"]}
    log("stream faults: %d injected, gaps %d, corrupt %d, late %d, "
        "lost acks %d" % (out["injected"]["total"], out["gaps"],
                          out["corrupt"], out["late"], out["lost_acks"]))
    return out


def run_streams_bench(args) -> Dict:
    """Delta-gated vs full-inference streaming at the SAME offered frame
    rate over the SAME seeded frame sequences and arrival trace (module
    docstring, streams-mode note). Sections: full-inference capacity
    (closed loop) -> one overload open-loop row per arm -> the
    frame-fault replay -> trace completeness over the whole run."""
    jax, devs = acquire_backend()
    platform = devs[0].platform
    log("backend up: %s (streams mode)" % platform)
    HB.beat("backend up (%s, streams)" % platform)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = arm_trace_log(args, maybe_tracer(args.span_log or None))

    n_tiles = args.tile_grid * args.tile_grid
    out: Dict = {"schema": STREAMS_SCHEMA, "tool": "serve_bench",
                 "platform": platform, "imsize": args.imsize,
                 "tile_grid": args.tile_grid, "tiles": n_tiles,
                 "streams": args.streams_n,
                 "redundancy": args.redundancy,
                 "stream_threshold": args.stream_threshold,
                 "tile_sim_ms": args.tile_sim_ms,
                 "stream_load": args.stream_load,
                 "deadline_ms": args.deadline_ms, "seed": args.seed,
                 "note": ("both arms run the SAME StreamSession/tile "
                          "path over simulated per-tile-service tile "
                          "replicas (host waits only — the CPU-valid "
                          "signal, fleet-mode note; service is linear "
                          "in the padded batch, so capacity is tiles/s "
                          "and the closed-loop anchor is the true "
                          "saturation rate); the full arm "
                          "forces the threshold below zero so every "
                          "tile computes, same seeded frame sequences "
                          "and Poisson trace at the same offered rate")}
    deadline_s = args.deadline_ms / 1e3
    seqs = [synth_stream_frames(args, sid, 128)
            for sid in range(args.streams_n)]

    # full-inference capacity, closed loop (threshold -1: every tile
    # computes through the same gated code path)
    router = make_stream_fleet(args, tracer)
    sess = make_stream_sessions(args, router, -1.0, deadline_s,
                                tracer=tracer)
    try:
        closed = stream_closed_loop(sess, seqs,
                                    max(2.0, args.duration / 2), tracer)
    finally:
        for s in sess:
            s.close()
        router.close()
    cap = max(closed["goodput_fps"], 1e-6)
    out["full_capacity_fps"] = closed["goodput_fps"]
    log("full-inference capacity: %.1f frames/s (%d streams, closed "
        "loop)" % (cap, args.streams_n))
    HB.beat("stream capacity measured")
    rate = args.stream_load * cap
    out["offered_fps"] = round(rate, 2)
    schedules = [arrival_schedule(rate / args.streams_n, args.duration,
                                  args.seed + 1700 + sid)
                 for sid in range(args.streams_n)]

    # full-inference arm over the trace
    router = make_stream_fleet(args, tracer)
    sess = make_stream_sessions(args, router, -1.0, deadline_s,
                                tracer=tracer)
    try:
        row_full = stream_open_loop(sess, seqs, schedules, args.duration,
                                    deadline_s, rate, "full-inference")
    finally:
        for s in sess:
            s.close()
        router.close()
    log("full-inference at %.1f fps offered: goodput %.1f, p99 %s ms, "
        "degraded %d" % (rate, row_full["goodput_fps"],
                         row_full["p99_ms"], row_full["degraded"]))
    HB.beat("full-inference row done")

    # delta-gated arm over the SAME trace (identical schedule objects)
    router = make_stream_fleet(args, tracer)
    sess = make_stream_sessions(args, router, args.stream_threshold,
                                deadline_s, tracer=tracer)
    try:
        row_gated = stream_open_loop(sess, seqs, schedules, args.duration,
                                     deadline_s, rate, "delta-gated")
        stats_g = [s.stats() for s in sess]
    finally:
        for s in sess:
            s.close()
        router.close()
    computed = sum(st["computed_tiles"] for st in stats_g)
    skipped = sum(st["skipped_tiles"] for st in stats_g)
    out["computed_tile_fraction"] = round(
        computed / max(computed + skipped, 1), 4)
    out["tile_skip_rate"] = round(
        skipped / max(computed + skipped, 1), 4)
    out["rows"] = [row_gated, row_full]
    ratio = row_gated["goodput_fps"] / max(row_full["goodput_fps"], 1e-6)
    out["stream_goodput_ratio"] = round(ratio, 2)
    out["gate_streams_2x"] = bool(ratio >= 2.0)
    log("delta-gated at the same %.1f fps: goodput %.1f vs %.1f full "
        "(%.2fx, computed tile fraction %.1f%%, gate_streams_2x=%s)"
        % (rate, row_gated["goodput_fps"], row_full["goodput_fps"],
           ratio, 100 * out["computed_tile_fraction"],
           out["gate_streams_2x"]))
    HB.beat("delta-gated row done")

    out["faults"] = stream_fault_run(args, tracer)
    HB.beat("stream fault run done")
    out["gate_zero_lost_acks"] = bool(
        row_gated["lost"] == 0 and row_full["lost"] == 0
        and out["faults"]["lost_acks"] == 0)

    exemplars, tsummary = trace_sections(tracer, args.trace_exemplars)
    if exemplars is not None:
        out["trace_exemplars"] = exemplars
        out["trace_summary"] = tsummary
        if exemplars["exemplars"]:
            out["exemplar_p99_stage"] = \
                exemplars["exemplars"][0]["critical_path"]["dominant_stage"]
        out["gate_traces_complete"] = bool(
            tsummary["orphans"] == 0 and tsummary["broken_chains"] == 0
            and tsummary["request_traces"] > 0)
        log("trace gate: %d request traces, orphans %d, broken %d, "
            "p99 stage %s" % (tsummary["request_traces"],
                              tsummary["orphans"],
                              tsummary["broken_chains"],
                              out.get("exemplar_p99_stage")))
    log("stream gates: 2x goodput %s, zero lost acks %s"
        % (out["gate_streams_2x"], out["gate_zero_lost_acks"]))
    return out


# ---------------------------------------------------------------------------
# harness assembly


def build_parts(args, jax):
    """(predict, variables, image pool) at the bench config — the raw
    uint8 wire (normalize baked in), int8 twin when asked (synthetic
    calibration, the bench.py int8-section recipe)."""
    import dataclasses

    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables

    dtype = jnp.bfloat16 if args.amp else None
    cfg = Config(num_stack=1, hourglass_inch=args.inch, num_cls=2,
                 topk=args.topk, conf_th=0.0, nms_th=0.5,
                 imsize=args.imsize, amp=args.amp,
                 serve_buckets=list(args.buckets),
                 infer_dtype=args.infer_dtype)
    model = build_model(cfg, dtype=dtype)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         args.imsize)
    variables = {"params": params, "batch_stats": batch_stats}
    quant_scales = None
    if args.infer_dtype == "int8":
        from real_time_helmet_detection_tpu.ops.quant import (
            calibrate_scales, synthetic_calibration_batches)
        icfg = dataclasses.replace(cfg)
        quant_scales = calibrate_scales(
            icfg, variables,
            synthetic_calibration_batches(max(args.buckets), args.imsize,
                                          n=2, raw=True),
            dtype=dtype, normalize="imagenet")
    predict = make_predict_fn(model, cfg, normalize="imagenet",
                              quant_scales=quant_scales)
    rng = np.random.default_rng(args.seed)
    pool = [rng.integers(0, 256, (args.imsize, args.imsize, 3),
                         dtype=np.uint8) for _ in range(args.pool)]
    return cfg, predict, variables, pool


def run_bench(args) -> Dict:
    jax, devs = acquire_backend()
    platform = devs[0].platform
    log("backend up: %s" % platform)
    HB.beat("backend up (%s)" % platform)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    from real_time_helmet_detection_tpu.serving import ServingEngine
    tracer = arm_trace_log(args, maybe_tracer(args.span_log or None))

    cfg, predict, variables, pool = build_parts(args, jax)
    out: Dict = {"schema": SCHEMA, "tool": "serve_bench",
                 "platform": platform, "imsize": args.imsize,
                 "inch": args.inch, "topk": args.topk,
                 "infer_dtype": args.infer_dtype,
                 "buckets": list(args.buckets),
                 "max_wait_ms": args.max_wait_ms, "depth": args.depth,
                 "queue_cap": args.queue_cap, "seed": args.seed}

    # serial b1 capacity: the status-quo server's throughput ceiling
    with tracer.span("serve-bench:serial-compile"):
        b1 = predict.lower(variables, jax.ShapeDtypeStruct(
            (1, args.imsize, args.imsize, 3), np.uint8)).compile()
    np.asarray(b1(variables, pool[0][None]).scores)  # warm
    n = 30
    with tracer.span("serve-bench:serial-capacity", n=n) as sp:
        for i in range(n):
            np.asarray(b1(variables, pool[i % len(pool)][None]).scores)
    serial_rps = n / sp.dur_s
    out["serial_b1_rps"] = round(serial_rps, 2)
    log("serial b1 capacity: %.1f req/s" % serial_rps)
    HB.beat("serial capacity measured")

    # --faults: deterministic chaos replay (ISSUE 9) — the seeded schedule
    # fires at the engine's serve:dispatch / serve:fetch sites while the
    # SAME load loops run, so the curve shows goodput/p99 UNDER injected
    # device-loss and hangs, and `lost` proves recovery kept every
    # acknowledged request
    injector = maybe_injector(args.faults, tracer=tracer)
    if injector is not None:
        out["faults_spec"] = injector.schedule.spec()
        log("fault injection armed: %s" % out["faults_spec"])
    # live metrics plane + SLO watchdog (ISSUE 10): a FRESH registry per
    # run (the artifact's snapshot is this run's evidence alone); the
    # watchdog's burn rules run against it and its alerts land in the
    # span log + the artifact
    mreg = MetricsRegistry()
    slo = SloWatchdog(default_serving_rules(deadline_ms=args.deadline_ms),
                      registry=mreg, tracer=tracer)
    engine = ServingEngine(predict, variables,
                           (args.imsize, args.imsize, 3), np.uint8,
                           buckets=args.buckets,
                           max_wait_ms=args.max_wait_ms, depth=args.depth,
                           queue_capacity=args.queue_cap, tracer=tracer,
                           max_retries=args.max_retries,
                           hang_timeout_s=(args.hang_timeout_ms / 1e3
                                           if args.hang_timeout_ms > 0
                                           else None),
                           injector=injector, metrics=mreg, watchdog=slo)
    try:
        # closed loop: engine saturation capacity
        warm = engine.predict_many(pool[:min(4, len(pool))])
        assert len(warm) == min(4, len(pool))
        closed = closed_loop(engine, pool, args.clients,
                             args.duration, tracer=tracer)
        out["closed"] = closed
        capacity = max(closed["goodput_rps"], 1e-6)
        out["engine_capacity_rps"] = closed["goodput_rps"]
        out["batch_capacity_ratio"] = round(capacity / serial_rps, 3)
        log("engine capacity (closed, %d clients): %.1f req/s "
            "(%.2fx serial b1)" % (args.clients, capacity,
                                   capacity / serial_rps))
        HB.beat("closed loop done")

        deadline_s = args.deadline_ms / 1e3
        curve = []
        for mult in args.loads:
            rate = mult * capacity
            sched = arrival_schedule(rate, args.duration,
                                     args.seed + int(mult * 1000))
            row = open_loop(engine, pool, sched, args.duration,
                            deadline_s, rate)
            row["load_multiplier"] = mult
            curve.append(row)
            log("open loop x%.2f (%.1f rps offered): goodput %.1f, "
                "p50 %s ms, p99 %s ms, shed %d"
                % (mult, rate, row["goodput_rps"], row["p50_ms"],
                   row["p99_ms"], row["shed"]))
            HB.beat("open loop x%.2f done" % mult)
        out["curve"] = curve
        if injector is not None:
            st = engine.stats()
            out["faults"] = {
                "spec": injector.schedule.spec(),
                "injected": injector.summary(),
                "retried": st["retried"],
                "requeued_batches": st["requeued_batches"],
                "hung_batches": st["hung_batches"],
                "lost_acks": sum(r.get("lost", 0) for r in curve),
                "engine_state": engine.state,
            }
            log("faults: injected %d, retried %d, lost acks %d"
                % (out["faults"]["injected"]["total"],
                   out["faults"]["retried"], out["faults"]["lost_acks"]))
    finally:
        engine.close()

    # the final metrics snapshot rides the artifact (ISSUE 10 satellite),
    # and the fleet-dashboard aggregates ride the ONE JSON line — pinned
    # by --selfcheck to agree with the engine's own stats
    st = engine.stats()
    out["metrics"] = mreg.snapshot()
    out["shed_total"] = st["shed_queue_full"] + st["shed_deadline"]
    out["retried"] = st["retried"]
    slots = mreg.counter("serve.batch_slots").value
    out["mean_batch_fill"] = (round(1.0 - st["padded_slots"] / slots, 3)
                              if slots else None)
    out["slo_alerts"] = [a["rule"] for a in slo.alerts]
    log("metrics: shed %d, retried %d, mean fill %s, alerts %s"
        % (out["shed_total"], out["retried"], out["mean_batch_fill"],
           out["slo_alerts"] or "none"))

    # serial baseline under the SAME past-saturation arrival trace
    over = max(args.loads)
    rate = over * capacity
    sched = arrival_schedule(rate, args.duration,
                             args.seed + int(over * 1000))
    serial_over = serial_loop(b1, variables, pool, sched, args.duration,
                              deadline_s, rate)
    out["serial_overload"] = serial_over
    HB.beat("serial overload done")

    # tail exemplars (ISSUE 14): slowest-N waterfalls + completeness
    exemplars, tsummary = trace_sections(tracer, args.trace_exemplars)
    if exemplars is not None:
        out["trace_exemplars"] = exemplars
        out["trace_summary"] = tsummary
        if exemplars["exemplars"]:
            out["exemplar_p99_stage"] = \
                exemplars["exemplars"][0]["critical_path"]["dominant_stage"]
        log("trace exemplars: %d, orphans %d, broken %d, p99 stage %s"
            % (len(exemplars["exemplars"]), tsummary["orphans"],
               tsummary["broken_chains"],
               out.get("exemplar_p99_stage")))

    eng_over = next(r for r in curve if r["load_multiplier"] == over)
    ratio = eng_over["goodput_rps"] / max(serial_over["goodput_rps"], 1e-6)
    out["goodput_vs_serial_at_overload"] = round(ratio, 2)
    out["gate_3x"] = bool(ratio >= 3.0)
    out["note"] = ("goodput = on-time completions/s under a %.0f ms "
                   "deadline; past saturation the serial b1 server's "
                   "unbounded FIFO delay misses every deadline while the "
                   "engine sheds at admission and keeps serving"
                   % args.deadline_ms)
    log("goodput at %.1fx saturation: engine %.1f vs serial %.1f rps "
        "(%.1fx, gate_3x=%s)"
        % (over, eng_over["goodput_rps"], serial_over["goodput_rps"],
           ratio, out["gate_3x"]))
    return out


# ---------------------------------------------------------------------------
# selfcheck: the engine contract on seeded CPU load (smoke tier)


def selfcheck() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    use_compile_cache()
    from real_time_helmet_detection_tpu.obs.spans import (maybe_tracer,
                                                          read_spans)
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    from real_time_helmet_detection_tpu.serving import ServingEngine

    failures: List[str] = []
    # the selfcheck times itself through a span (disabled tracers still
    # time), keeping the whole script on the flight-recorder contract
    sp_all = maybe_tracer(None).span("serve-bench:selfcheck").__enter__()

    def check(name, cond):
        print("selfcheck %-52s %s" % (name, "ok" if cond else "FAIL"),
              file=sys.stderr, flush=True)
        if not cond:
            failures.append(name)

    # graftlint layer-3 gate (trace-audit-gate pattern): the threaded
    # engine/fleet plane this selfcheck is about to exercise must be
    # lock-audit clean FIRST — proving behavior on top of a known lock
    # bug proves nothing (stdlib ast, ~1 s)
    from real_time_helmet_detection_tpu.analysis import (diff_baseline,
                                                         load_baseline,
                                                         lock_audit)
    check("lock audit clean (graftlint layer 3)",
          not diff_baseline(lock_audit.audit_repo(REPO),
                            load_baseline())["new"])

    ns = argparse.Namespace(imsize=64, inch=8, topk=16, amp=False,
                            infer_dtype="bf16", buckets=(1, 2, 4),
                            seed=7, pool=12)
    cfg, predict, variables, pool = build_parts(ns, jax)

    # one-shot oracle: the direct predict of each image at batch 1 —
    # dispatch every program first, ONE batched fetch (the engine's own
    # fetch discipline)
    pending = [predict(variables, img[None]) for img in pool]
    oracle = [type(d)(*(np.asarray(leaf[0]) for leaf in d))
              for d in jax.device_get(pending)]

    import tempfile
    with tempfile.TemporaryDirectory(prefix="serve_bench_selfcheck.") as tmp:
        span_path = os.path.join(tmp, "spans.jsonl")
        tracer = maybe_tracer(span_path)
        mreg = MetricsRegistry()
        engine = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                               buckets=(1, 2, 4), max_wait_ms=2.0,
                               depth=2, queue_capacity=32, tracer=tracer,
                               metrics=mreg)
        # warm every bucket, then pin zero recompiles over a random stream
        engine.predict_many(pool[:4])
        counter = install_recompile_counter()
        rng = np.random.default_rng(0)
        futs = []
        for _ in range(8):
            k = int(rng.integers(1, 6))
            idx = rng.integers(0, len(pool), k)
            futs += [(int(i), engine.submit(pool[int(i)])) for i in idx]
            time.sleep(float(rng.uniform(0, 0.004)))
        rows = [(i, f.result(timeout=30)) for i, f in futs]
        ident = all(
            np.array_equal(getattr(row, name), getattr(oracle[i], name))
            for i, row in rows
            for name in ("boxes", "classes", "scores", "valid"))
        check("stream bit-identical to one-shot predict", ident)
        check("zero recompiles after warmup", counter.count == 0)
        st = engine.stats()
        check("engine served the stream",  # + the 4 warmup requests
              st["completed"] == len(rows) + 4 and st["batches"] >= 1)
        # ISSUE 10: the live metrics snapshot must AGREE with the stats
        # rows (one truth, two surfaces) and the e2e histogram must have
        # absorbed exactly the completed requests. Snapshot AFTER close:
        # a future resolves before the fetch loop's e2e observe, so an
        # un-joined engine could still be mid-bookkeeping
        engine.close()
        snap = mreg.snapshot()
        check("metrics snapshot agrees with stats rows",
              snap["counters"]["serve.submitted"] == st["submitted"]
              and snap["counters"]["serve.completed"] == st["completed"]
              and snap["counters"]["serve.batches_total"] == st["batches"]
              and snap["counters"]["serve.padded_slots"]
              == st["padded_slots"])
        check("metrics e2e histogram absorbed the stream",
              snap["histograms"]["serve.e2e_ms"]["count"]
              == st["completed"])
        hl = engine.health()
        check("health() carries the metrics digest",
              hl["metrics"]["histograms"]["serve.e2e_ms"]["count"]
              == st["completed"]
              and hl["metrics"]["counters"]["serve.completed"]
              == st["completed"])

        # admission control: paused engine, tiny queue -> immediate shed
        eng2 = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                             buckets=(1, 2), max_wait_ms=0.0,
                             queue_capacity=2, tracer=tracer, start=False)
        futs2 = [eng2.submit(pool[0], block=False) for _ in range(4)]
        shed = [f for f in futs2 if f.done()]
        check("queue-full sheds immediately", len(shed) == 2
              and all(_raises_shed(f) for f in shed))
        eng2.start()
        ok_rows = [f.result(timeout=30) for f in futs2 if not _raises_shed(f)]
        check("admitted requests still served", len(ok_rows) == 2)
        check("queue-full counter recorded",
              eng2.stats()["shed_queue_full"] == 2)
        eng2.close()

        # deadline shed: an already-expired request never reaches the
        # device (paused engine with room in the queue, so the shed is
        # attributable to the deadline alone)
        eng3 = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                             buckets=(1, 2), max_wait_ms=0.0,
                             queue_capacity=8, tracer=tracer, start=False)
        late = eng3.submit(pool[0], deadline_s=0.001, block=False)
        time.sleep(0.05)
        eng3.start()
        check("expired request shed at batch formation", _raises_shed(late))
        check("deadline counter recorded",
              eng3.stats()["shed_deadline"] == 1)
        eng3.close()
        tracer.close()

        spans = read_spans(span_path)
        names = {r.get("name") for r in spans}
        check("serve spans recorded",
              {"serve:lower", "serve:compile", "serve:batch-form",
               "serve:h2d", "serve:dispatch", "serve:device-wait",
               "serve:d2h", "serve:queue-wait", "serve:e2e"} <= names)
        check("shed events recorded",
              sum(1 for r in spans if r.get("name") == "serve:shed") == 3)

        # open loop end-to-end on a tiny schedule, artifact roundtrip
        engine3 = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                                buckets=(1, 2, 4), max_wait_ms=2.0,
                                queue_capacity=32)
        sched = arrival_schedule(60.0, 1.0, seed=3)
        row = open_loop(engine3, pool, sched, 1.0, deadline_s=2.0,
                        offered_rps=60.0)
        engine3.close()
        check("open loop completes its schedule",
              row["completed"] + row["shed"] + row["lost"] == row["n"]
              and row["completed"] > 0 and row["lost"] == 0)
        check("p50 <= p99", (row["p50_ms"] or 0) <= (row["p99_ms"] or 0))

        # fault scenario mode (ISSUE 9): the canned schedule injects a
        # device-loss at dispatch and a hung fetch mid-stream; bounded
        # retries must keep ZERO acknowledged requests lost and every
        # survivor bit-identical to its one-shot predict
        canned = ("serve:dispatch=device-loss@2,"
                  "serve:fetch=hung-fetch@4,"
                  "serve:dispatch=device-loss@6")
        inj = ChaosInjector(FaultSchedule.parse(canned))
        reg4 = MetricsRegistry()
        slo4 = SloWatchdog(default_serving_rules(), registry=reg4)
        eng4 = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                             buckets=(1, 2, 4), max_wait_ms=2.0, depth=2,
                             queue_capacity=64,
                             max_retries=3, hang_timeout_s=0.1,
                             injector=inj, metrics=reg4, watchdog=slo4)
        futs4 = [(int(i), eng4.submit(pool[int(i)]))
                 for i in np.random.default_rng(5).integers(0, len(pool),
                                                            24)]
        rows4 = []
        lost4 = 0
        for i, f in futs4:
            try:
                rows4.append((i, f.result(timeout=60)))
            except Exception:  # noqa: BLE001 — would be a lost ack
                lost4 += 1
        st4 = eng4.stats()
        eng4.close()
        check("faults: all scheduled events fired",
              len(inj.fired) == 3 and inj.pending() == 0)
        check("faults: zero lost acknowledged requests",
              lost4 == 0 and st4["failed"] == 0
              and st4["completed"] == len(futs4))
        check("faults: retried results bit-identical to one-shot",
              all(np.array_equal(getattr(row, name),
                                 getattr(oracle[i], name))
                  for i, row in rows4
                  for name in ("boxes", "classes", "scores", "valid")))
        check("faults: recovery accounted",
              st4["retried"] >= 1 and st4["requeued_batches"] >= 2
              and st4["hung_batches"] == 1)
        # ISSUE 10: the retry/requeue counters on the metrics plane agree
        # with the stats rows even mid-chaos, and the injected batch
        # failures fired the SLO error-burn rule deterministically
        snap4 = reg4.snapshot()
        check("faults: metrics snapshot agrees with stats rows",
              snap4["counters"]["serve.retried"] == st4["retried"]
              and snap4["counters"]["serve.requeued_batches"]
              == st4["requeued_batches"]
              and snap4["counters"]["serve.hung_batches"]
              == st4["hung_batches"]
              and snap4["counters"]["serve.failed_batches"]
              == st4["failed_batches"])
        check("faults: SLO error-burn alerted",
              any(a["rule"] == "serve-error-burn" for a in slo4.alerts))
        art = os.path.join(tmp, "serve_bench.json")
        save_json(art, {"schema": SCHEMA, "curve": [row],
                        "metrics": snap4}, indent=1)
        with open(art) as f:
            back = json.load(f)
        check("artifact roundtrips", back["schema"] == SCHEMA)
        check("metrics snapshot rides the artifact",
              back["metrics"]["schema"] == "obs-metrics-v1"
              and back["metrics"]["counters"]["serve.retried"]
              == st4["retried"])

        # ---- fleet path (ISSUE 12): the router contract on the same
        # seeded CPU parts, ~15 s ----------------------------------------
        sp_fleet = maybe_tracer(None).span(
            "serve-bench:selfcheck-fleet").__enter__()
        factory = make_replica_factory(predict, variables, 64, (1, 2, 4),
                                       queue_capacity=64, max_wait_ms=2.0)
        fr = FleetRouter(factory, 2, metrics=MetricsRegistry())
        fr.predict_many(pool[:4])  # warm both replicas' paths
        counter_f = install_recompile_counter()
        rngf = np.random.default_rng(1)
        futsf = []
        for _ in range(6):
            idx = rngf.integers(0, len(pool), int(rngf.integers(1, 5)))
            futsf += [(int(i), fr.submit(pool[int(i)])) for i in idx]
            time.sleep(float(rngf.uniform(0, 0.004)))
        rowsf = [(i, f.result(timeout=30)) for i, f in futsf]
        stf = fr.stats()
        fr.close()
        check("fleet: stream bit-identical to one-shot predict",
              all(np.array_equal(getattr(r, name),
                                 getattr(oracle[i], name))
                  for i, r in rowsf
                  for name in ("boxes", "classes", "scores", "valid")))
        check("fleet: zero recompiles across replicas",
              counter_f.count == 0)
        check("fleet: zero lost acks on the clean stream",
              stf["lost"] == 0 and stf["completed"] == len(rowsf) + 4)

        # per-tenant shed accounting on a paused fleet: tenant A over its
        # budget sheds exactly its overflow, tenant B is untouched
        fr2 = FleetRouter(factory, 2, tenants={"a": 2, "b": 8},
                          metrics=MetricsRegistry(), start=False)
        fa = [fr2.submit(pool[0], tenant="a") for _ in range(5)]
        fb = [fr2.submit(pool[1], tenant="b") for _ in range(5)]
        shed_a = [f for f in fa if f.done()]
        fr2.start()
        served = [f.result(timeout=30) for f in fb] \
            + [f.result(timeout=30) for f in fa if f not in shed_a]
        h2 = fr2.health()
        fr2.close()
        check("fleet: tenant budget sheds the right tenant",
              len(shed_a) == 3
              and h2["tenants"]["a"]["shed"] == 3
              and h2["tenants"]["b"]["shed"] == 0
              and len(served) == 7)

        # canned fleet:replica death schedule: re-dispatch + respawn keep
        # every acknowledged request (lost_acks == 0)
        injf = ChaosInjector(FaultSchedule.parse(
            "fleet:dispatch=device-loss@2,fleet:replica=worker-death@5"))
        fr3 = FleetRouter(factory, 2, metrics=MetricsRegistry(),
                          injector=injf)
        futs3 = [(k % len(pool), fr3.submit(pool[k % len(pool)]))
                 for k in range(16)]
        lost3 = 0
        rows3 = []
        for i, f in futs3:
            try:
                rows3.append((i, f.result(timeout=60)))
            except Exception:  # noqa: BLE001 — would be a lost ack
                lost3 += 1
        st3 = fr3.stats()
        fr3.close()
        check("fleet: canned death schedule fired",
              len(injf.fired) == 2 and injf.pending() == 0)
        check("fleet: death run lost zero acknowledged requests",
              lost3 == 0 and st3["lost"] == 0
              and st3["replica_deaths"] == 1 and st3["respawns"] == 1)
        check("fleet: death-run survivors bit-identical",
              all(np.array_equal(getattr(r, name),
                                 getattr(oracle[i], name))
                  for i, r in rows3
                  for name in ("boxes", "classes", "scores", "valid")))

        # the fleet artifact row path end to end on simulated replicas
        # (tiny durations), incl. the ONE-JSON-line field contract
        nsf = argparse.Namespace(
            imsize=64, buckets=(1, 2, 4, 8), queue_cap=8, max_wait_ms=2.0,
            depth=2, deadline_ms=600.0, duration=1.5, clients=16, pool=8,
            seed=3, replicas=[1, 2], replica_sim_ms=30.0, fleet_load=2.0)
        rows_sim = fleet_scaling_rows(nsf, maybe_tracer(None))
        check("fleet: scaling rows carry the gated fields",
              [r["replicas"] for r in rows_sim] == [1, 2]
              and all(isinstance(r["scaling_eff"], float)
                      and r["lost"] == 0 for r in rows_sim)
              and rows_sim[0]["scaling_eff"] == 1.0)
        fleet_line = {"schema": FLEET_SCHEMA, "replicas": [1, 2],
                      "tenants": ["bulk", "flagged"],
                      "canary": {"outcome": "rolled-back",
                                 "lost_acks": 0},
                      "exemplar_p99_stage": "serve:queue-wait",
                      "rows": rows_sim}
        artf = os.path.join(tmp, "serve_bench_fleet.json")
        save_json(artf, fleet_line, indent=1)
        with open(artf) as f:
            backf = json.load(f)
        check("fleet: artifact roundtrips with line fields",
              backf["schema"] == FLEET_SCHEMA
              and backf["replicas"] == [1, 2]
              and backf["tenants"] == ["bulk", "flagged"]
              and backf["canary"]["lost_acks"] == 0
              and backf["exemplar_p99_stage"] == "serve:queue-wait")
        print("selfcheck fleet section elapsed %.1fs"
              % sp_fleet.close(), file=sys.stderr, flush=True)

        # ---- distributed tracing (ISSUE 14): exemplar reassembly over
        # a fixed-service sim engine (span-sum must explain the e2e) and
        # a canned fleet:replica death whose re-dispatch hop is visible
        # in the reassembled trace — with ZERO orphans/broken chains ----
        from real_time_helmet_detection_tpu.obs import traceview
        sp_tr = maybe_tracer(None).span(
            "serve-bench:selfcheck-traces").__enter__()
        tpath = os.path.join(tmp, "trace_spans.jsonl")
        ttr = maybe_tracer(tpath)
        # 80 ms fixed service: compute dominates e2e by construction, so
        # the span-sum pin is load-independent (the repo box's speed
        # varies ~2x — CLAUDE.md)
        st_eng = ServingEngine(SimServePredict(80.0), {"w": np.zeros(1)},
                               (64, 64, 3), np.uint8, buckets=(1, 2),
                               max_wait_ms=1.0, queue_capacity=32,
                               metrics=MetricsRegistry(), tracer=ttr)
        # sequential (no queueing): each request's e2e IS one 80 ms
        # compute + slop, so the dominant-stage pin is deterministic
        for i in range(4):
            st_eng.submit(pool[i % len(pool)]).result(timeout=30)
        st_eng.close()
        ttr.close()
        traces = traceview.assemble_logs([tpath])
        summ = traceview.analyze(traces)
        ex = traceview.tail_exemplars(traces, 3)
        check("traces: engine stream complete (no orphans/broken)",
              summ["request_traces"] == 4 and summ["orphans"] == 0
              and summ["broken_chains"] == 0)
        cp = ex[0]["critical_path"] if ex else {}
        check("traces: exemplar e2e equals its span-sum (tolerance)",
              len(ex) == 3
              and abs(cp["stage_sum_ms"] - cp["e2e_ms"])
              <= max(0.5 * cp["e2e_ms"], 40.0)
              and (cp["attributed_frac"] or 0) >= 0.5)
        check("traces: compute dominates the fixed-service exemplar",
              cp.get("dominant_stage") == "serve:dispatch")

        tpath2 = os.path.join(tmp, "trace_fleet.jsonl")
        ttr2 = maybe_tracer(tpath2)
        factory_t = make_replica_factory(
            SimServePredict(20.0), {"w": np.zeros(1)}, 64, (1, 2),
            queue_capacity=64, max_wait_ms=1.0, tracer=ttr2)
        injt = ChaosInjector(FaultSchedule.parse(
            "fleet:replica=worker-death@30"), tracer=ttr2)
        frt = FleetRouter(factory_t, 2, metrics=MetricsRegistry(),
                          injector=injt, tracer=ttr2)
        # dense burst: backlog must exist when the death fires, so the
        # killed queued acks exercise the re-dispatch path
        futt = [frt.submit(pool[k % len(pool)]) for k in range(40)]
        lostt = 0
        for f in futt:
            try:
                f.result(timeout=60)
            except Exception:  # noqa: BLE001 — would be a lost ack
                lostt += 1
        stt = frt.stats()
        frt.close()
        ttr2.close()
        traces2 = traceview.assemble_logs([tpath2])
        summ2 = traceview.analyze(traces2)
        check("traces: death run reassembles completely",
              lostt == 0 and summ2["request_traces"] == 40
              and summ2["orphans"] == 0
              and summ2["broken_chains"] == 0)
        hop_traces = [t for t in traces2.values()
                      if any(r.get("name") == "fleet:redispatch"
                             for r in t.records)]
        check("traces: re-dispatch hop visible in reassembled trace",
              stt["redispatched"] >= 1 and len(hop_traces) >= 1
              and summ2["redispatched_traces"] == len(hop_traces)
              and all(t.root_closure() is not None for t in hop_traces)
              and any(sum(1 for r in t.records
                          if r.get("name") == "fleet:dispatch") >= 2
                      for t in hop_traces))
        print("selfcheck traces section elapsed %.1fs"
              % sp_tr.close(), file=sys.stderr, flush=True)

        # ---- cascade serving (ISSUE 16): edge-first routing over REAL
        # predicts — zero lost acks + zero recompiles under the seeded
        # escalation-hop fault schedule (quality tier dead at the hop ->
        # degraded EDGE answer, flagged, never lost), bit-identity on
        # every path ------------------------------------------------------
        from real_time_helmet_detection_tpu.models import build_model
        from real_time_helmet_detection_tpu.predict import make_predict_fn
        sp_c = maybe_tracer(None).span(
            "serve-bench:selfcheck-cascade").__enter__()
        edge_predict = make_predict_fn(build_model(cfg), cfg,
                                       normalize="imagenet",
                                       cascade_summary=True)
        # edge oracle incl. the in-jit confidence — dispatch everything,
        # ONE batched fetch (the engine's own fetch discipline); its det
        # fields must equal the plain oracle (the summary only ADDS a
        # leaf), which doubles as the zero-extra-D2H contract check
        pend_c = [edge_predict(variables, img[None]) for img in pool]
        edge_oracle = [type(d)(*(np.asarray(leaf[0]) for leaf in d))
                       for d in jax.device_get(pend_c)]
        check("cascade: summary predict det-identical to plain predict",
              all(np.array_equal(getattr(e, name), getattr(o, name))
                  for e, o in zip(edge_oracle, oracle)
                  for name in ("boxes", "classes", "scores", "valid")))
        # fixture operating-point pick, NOT a latency digest: the middle
        # of the oracle confidence distribution makes both outcomes
        # (edge-resolve / escalate) happen over the 8-image pool
        confs = [float(d.confidence) for d in edge_oracle]
        th_c = float(np.median(confs))  # graftlint: off=raw-metric-aggregation

        def _cascade_factory(rid, start=True):
            pred = edge_predict if rid == 0 else predict
            return make_replica_factory(pred, variables, 64, (1, 2, 4),
                                        queue_capacity=64,
                                        max_wait_ms=2.0)(rid, start=start)

        injc = ChaosInjector(FaultSchedule.parse(
            "fleet:escalate=device-loss@2"))
        frc = FleetRouter(_cascade_factory, 2,
                          replica_tiers=["edge", "quality"],
                          cascade_tenants=["cas"],
                          cascade_tiers=("edge", "quality"),
                          cascade_threshold=th_c,
                          metrics=MetricsRegistry(), injector=injc)
        # warm both tiers through the cascade path itself, then pin zero
        # recompiles over the faulted stream (both engines AOT-compile
        # their buckets up front; a cascade hop must never trace afresh)
        for f in [frc.submit(pool[i], tenant="cas") for i in range(4)]:
            f.result(timeout=60)
        counter_c = install_recompile_counter()
        futc = [(i % len(pool), frc.submit(pool[i % len(pool)],
                                           tenant="cas"))
                for i in range(12)]
        lostc, rowsc = 0, []
        for i, f in futc:
            try:
                rowsc.append((i, f, f.result(timeout=120)))
            except Exception:  # noqa: BLE001 — would be a lost ack
                lostc += 1
        stc = frc.stats()
        frc.close()
        check("cascade: escalation-hop fault fired",
              len(injc.fired) == 1 and injc.pending() == 0)
        check("cascade: zero lost acks under escalation faults",
              lostc == 0 and stc["lost"] == 0)
        check("cascade: zero recompiles across both tiers",
              counter_c.count == 0)
        check("cascade: faulted hop degraded to the edge answer",
              stc["degraded_answers"] >= 1
              and all(_rows_equal_sc(r, edge_oracle[i])
                      for i, f, r in rowsc if f.degraded_answer))
        check("cascade: every answer bit-identical to its oracle",
              all(_rows_equal_sc(r, oracle[i]) for i, f, r in rowsc))
        check("cascade: edge answers carry the in-jit confidence",
              all(np.array_equal(r.confidence, edge_oracle[i].confidence)
                  for i, f, r in rowsc
                  if not f.escalated or f.degraded_answer))
        check("cascade: outcome follows the confidence vs threshold",
              all(f.escalated == (confs[i] < th_c)
                  for i, f, r in rowsc if not f.degraded_answer))

        # quality-replica worker-death mid-cascade: respawn + the hop
        # proceeds (or degrades) — the ack is never lost (recompiles NOT
        # pinned here: a respawned engine legitimately re-AOTs)
        injd = ChaosInjector(FaultSchedule.parse(
            "fleet:escalate=worker-death@2"))
        frd = FleetRouter(_cascade_factory, 2,
                          replica_tiers=["edge", "quality"],
                          cascade_tenants=["cas"],
                          cascade_tiers=("edge", "quality"),
                          # above every oracle confidence: all escalate
                          cascade_threshold=max(confs) + 1.0,
                          metrics=MetricsRegistry(), injector=injd)
        futd = [(i % len(pool), frd.submit(pool[i % len(pool)],
                                           tenant="cas"))
                for i in range(6)]
        lostd = 0
        for i, f in futd:
            try:
                f.result(timeout=120)
            except Exception:  # noqa: BLE001 — would be a lost ack
                lostd += 1
        std = frd.stats()
        frd.close()
        check("cascade: quality death respawned, zero lost acks",
              lostd == 0 and std["lost"] == 0
              and std["replica_deaths"] == 1 and std["respawns"] == 1)
        print("selfcheck cascade section elapsed %.1fs"
              % sp_c.close(), file=sys.stderr, flush=True)

        # ---- streaming sessions (ISSUE 17): delta-gated tile inference
        # over REAL predicts — gate-off bit-identity vs the whole-frame
        # predict, tile reassembly bit-identical to the per-tile oracle,
        # static tiles answered from the cache, in-order delivery, zero
        # lost acked frames under the canned frame-fault schedule --------
        from real_time_helmet_detection_tpu.ops.delta import (
            stitch_detections, tile_origins)
        from real_time_helmet_detection_tpu.serving import StreamSession
        sp_st = maybe_tracer(None).span(
            "serve-bench:selfcheck-streams").__enter__()
        det_fields = ("boxes", "classes", "scores", "valid")

        def mk_frame(i0, i1, i2, i3):
            # a 2x2 frame whose tiles are pool images — so the per-tile
            # oracle is the one-shot oracle already computed above
            top = np.concatenate([pool[i0], pool[i1]], axis=1)
            bot = np.concatenate([pool[i2], pool[i3]], axis=1)
            return np.concatenate([top, bot], axis=0)

        def frame_equal(det, want):
            return all(np.array_equal(getattr(det, n), getattr(want, n))
                       for n in det_fields)

        origins_st = tile_origins((128, 128, 3), 2)
        eng_st = ServingEngine(predict, variables, (64, 64, 3), np.uint8,
                               buckets=(1, 2, 4), max_wait_ms=2.0,
                               depth=2, queue_capacity=32, tracer=tracer)
        eng_st.predict_many(pool[:2])  # warm the tile buckets

        # derived, not hand-picked: halfway between the unchanged tiles'
        # exact-zero delta and the smallest changed-tile mean |delta|
        # across the fixture's pool swaps — any value in between gates
        # identically (the calibrated-artifact law governs serving;
        # fixtures derive their operating point from the data in hand)
        def _pair_delta(a, b):
            return float(np.abs(pool[a].astype(np.float32)
                                - pool[b].astype(np.float32)).mean())

        th_st = 0.5 * min(_pair_delta(a, b)
                          for a, b in ((2, 4), (0, 5), (1, 6), (3, 7)))
        # ema=0 isolates the reassembly arithmetic (smoothing determinism
        # has its own test in tests/test_streams.py)
        sess_st = StreamSession(eng_st, (128, 128, 3), grid=2,
                                threshold=th_st, ema=0.0, tracer=tracer)
        f0, f1 = mk_frame(0, 1, 2, 3), mk_frame(0, 1, 4, 3)
        r0 = sess_st.submit_frame(f0).result(timeout=60)
        check("streams: first frame computes every tile",
              r0.computed_tiles == 4 and r0.total_tiles == 4)
        check("streams: reassembly bit-identical to per-tile oracle",
              frame_equal(r0.detections,
                          stitch_detections([oracle[i] for i in
                                             (0, 1, 2, 3)], origins_st)))
        r1 = sess_st.submit_frame(f1).result(timeout=60)
        check("streams: only the changed tile recomputes",
              r1.computed_tiles == 1
              and frame_equal(r1.detections,
                              stitch_detections([oracle[i] for i in
                                                 (0, 1, 4, 3)],
                                                origins_st)))
        r2 = sess_st.submit_frame(f1).result(timeout=60)
        check("streams: identical frame answers fully from the cache",
              r2.computed_tiles == 0
              and frame_equal(r2.detections, r1.detections))
        sess_st.close()

        # gate-off bit-identity: the WHOLE frame passes straight through
        # (no delta program, no stitching) — the exact pre-gating answer
        eng_off = ServingEngine(predict, variables, (128, 128, 3),
                                np.uint8, buckets=(1,), max_wait_ms=0.0,
                                queue_capacity=8, tracer=tracer)
        pend_off = predict(variables, f0[None])
        whole = type(pend_off)(*(np.asarray(leaf[0]) for leaf in
                                 jax.device_get(pend_off)))
        sess_off = StreamSession(eng_off, (128, 128, 3), gate=False,
                                 tracer=tracer)
        roff = sess_off.submit_frame(f0).result(timeout=60)
        check("streams: gate-off bit-identical to whole-frame predict",
              frame_equal(roff.detections, whole)
              and roff.computed_tiles == roff.total_tiles)
        sess_off.close()
        eng_off.close()

        # frame faults: dropped@2 / corrupt@3 / late@5 over one stream —
        # every acknowledged frame delivers (gaps from the cache), the
        # corrupt frame never becomes the delta reference
        injst = ChaosInjector(FaultSchedule.parse(
            "stream:frame=dropped-frame@2,stream:frame=corrupt-frame@3,"
            "stream:frame=late-frame@5"), tracer=tracer)
        sess_f = StreamSession(eng_st, (128, 128, 3), grid=2,
                               threshold=th_st, ema=0.0, injector=injst,
                               tracer=tracer, sid=1)
        seq_frames = [mk_frame(0, 1, 2, 3), mk_frame(0, 1, 4, 3),
                      mk_frame(5, 1, 4, 3), mk_frame(5, 6, 4, 3),
                      mk_frame(5, 6, 4, 7), mk_frame(5, 6, 4, 7)]
        futs_f = [sess_f.submit_frame(f) for f in seq_frames]
        lost_f, res_f = 0, []
        for f in futs_f:
            try:
                res_f.append(f.result(timeout=60))
            except Exception:  # noqa: BLE001 — would be a lost ack
                lost_f += 1
        st_f = sess_f.stats()
        sess_f.close()
        eng_st.close()
        check("streams: zero lost acked frames under frame faults",
              lost_f == 0 and len(res_f) == 6 and injst.pending() == 0)
        check("streams: in-order delivery",
              [r.seq for r in res_f] == list(range(6)))
        check("streams: dropped/corrupt frames answer from the cache",
              res_f[1].gap and res_f[2].gap
              and frame_equal(res_f[1].detections, res_f[0].detections)
              and frame_equal(res_f[2].detections, res_f[0].detections))
        check("streams: frame-fault accounting",
              st_f["gaps"] == 2 and st_f["corrupt"] == 1
              and st_f["late"] == 1)
        gap_events = [s for s in read_spans(span_path)
                      if s.get("name") == "recover:frame-gap"]
        check("streams: recover:frame-gap events in the span log",
              len(gap_events) >= 2)
        print("selfcheck streams section elapsed %.1fs"
              % sp_st.close(), file=sys.stderr, flush=True)

    ok = not failures
    print(json.dumps({"tool": "serve_bench", "selfcheck": True, "ok": ok,
                      "failures": failures,
                      "elapsed_s": round(sp_all.close(), 1)}))
    sys.stdout.flush()
    return 0 if ok else 1


def _rows_equal_sc(row, oracle_row) -> bool:
    """Det-field bit-identity (the confidence leaf, when present on both
    sides, is checked separately — a plain-predict oracle has none)."""
    return all(np.array_equal(getattr(row, n), getattr(oracle_row, n))
               for n in ("boxes", "classes", "scores", "valid"))


def _raises_shed(fut) -> bool:
    try:
        fut.result(timeout=0.5)
        return False
    except SheddedError:
        return True
    except Exception:  # noqa: BLE001
        return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (bench.py convention)")
    p.add_argument("--imsize", type=int, default=None,
                   help="default: 512 on TPU, 64 on CPU")
    p.add_argument("--inch", type=int, default=None,
                   help="hourglass width (default: 128 TPU, 16 CPU)")
    p.add_argument("--topk", type=int, default=None,
                   help="default: 100 TPU, 32 CPU")
    p.add_argument("--amp", action="store_true", default=None,
                   help="bf16 compute (default on TPU)")
    p.add_argument("--infer-dtype", default=None,
                   choices=("bf16", "int8"),
                   help="serve dtype (default: int8 on TPU — the PR 5 "
                        "path is the serve default — bf16 on CPU)")
    p.add_argument("--buckets", type=int, nargs="+",
                   default=[1, 2, 4, 8, 16])
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--queue-cap", type=int, default=8,
                   help="admission bound: the queue is the engine's "
                        "latency budget (wait <= cap/capacity) — keep it "
                        "small so admitted requests finish inside the "
                        "deadline; excess load sheds at submit")
    p.add_argument("--deadline-ms", type=float, default=600.0,
                   help="goodput deadline; must exceed the engine's "
                        "saturated pipeline latency (~queue_cap/capacity "
                        "+ (depth+2) x max_bucket batch time) — the "
                        "engine's latency is BOUNDED by those knobs, the "
                        "serial baseline's queueing delay is not")
    p.add_argument("--duration", type=float, default=10.0,
                   help="seconds per load point")
    p.add_argument("--loads", type=float, nargs="+",
                   default=[0.5, 0.9, 2.0],
                   help="offered-load multipliers of measured capacity "
                        "(include one > 1: the past-saturation point)")
    p.add_argument("--clients", type=int, default=32)
    p.add_argument("--pool", type=int, default=32,
                   help="distinct request images")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--replicas", type=int, nargs="+", default=[],
                   help="fleet mode (ISSUE 12): run a FleetRouter over N "
                        "replicas for each N given (e.g. --replicas 1 2 "
                        "4) and write the serve-bench-fleet-v1 scaling "
                        "artifact instead of the single-engine curve")
    p.add_argument("--replica-sim-ms", type=float, default=40.0,
                   help="fleet scaling rows: simulated replica service "
                        "time (fixed, GIL-releasing — the remote-chip "
                        "model; 0 would measure one-core contention, "
                        "not the router)")
    p.add_argument("--fleet-load", type=float, default=2.0,
                   help="fleet rows' offered load as a multiple of "
                        "N x per-replica capacity (the past-saturation "
                        "point the 0.8x scaling gate is claimed at)")
    p.add_argument("--cascade", action="store_true",
                   help="cascade mode (ISSUE 16): edge-first serving "
                        "with confidence-gated escalation vs all-quality "
                        "routing at the same offered load over the same "
                        "seeded arrival trace; writes the "
                        "serve-bench-cascade-v1 artifact "
                        "(serve_bench_cascade.json)")
    # SIM-scale fixture knob on the synthetic pixel[0,0,0]/255 confidence;
    # real parts resolve via the calibrated config.cascade_overrides
    # artifact (see help text)
    p.add_argument("--cascade-threshold", type=float,
                   default=0.1,  # graftlint: off=hand-picked-threshold
                   help="cascade escalation threshold on the SIM "
                        "confidence scale (pixel[0,0,0]/255 in [0,1]; "
                        "~the escalation fraction of a uniform pool). "
                        "Real-parts serving resolves its threshold from "
                        "the calibrated quality_matrix --cascade "
                        "artifact via config.cascade_overrides instead")
    p.add_argument("--cascade-tiers", nargs=2, default=["edge", "quality"],
                   metavar=("EDGE", "QUALITY"),
                   help="the (edge, quality) tier pair the cascade spans")
    p.add_argument("--cascade-edge-ms", type=float, default=5.0,
                   help="edge-tier simulated service time (quality tier "
                        "uses --replica-sim-ms)")
    p.add_argument("--cascade-load", type=float, default=5.0,
                   help="cascade rows' offered load as a multiple of the "
                        "measured all-quality CLOSED-loop capacity (a "
                        "client-bound underestimate of the open-loop "
                        "ceiling — keep well past it: the "
                        "gate_cascade_2x headline is claimed at an "
                        "offered load the baseline saturates under)")
    p.add_argument("--streams", action="store_true",
                   help="streams mode (ISSUE 17): delta-gated tile "
                        "inference vs full-inference for N synthetic "
                        "camera streams over the same seeded frame trace "
                        "at the same offered rate; writes the "
                        "serve-bench-streams-v1 artifact "
                        "(serve_bench_streams.json)")
    p.add_argument("--streams-n", type=int, default=4,
                   help="number of synthetic camera streams")
    p.add_argument("--redundancy", type=float, default=0.75,
                   help="per-tile probability a tile is UNCHANGED frame-"
                        "to-frame in the synthetic streams (the "
                        "controlled-redundancy fixture the gating claim "
                        "is measured at)")
    # SIM-scale fixture knob (unchanged tiles delta exactly 0, changed
    # ~85); real parts resolve via the calibrated config.stream_overrides
    # artifact (see help text)
    p.add_argument("--stream-threshold", type=float,
                   default=1.0,  # graftlint: off=hand-picked-threshold
                   help="tile skip threshold (mean |delta| in [0, 255]) "
                        "for the SIM streams: any value between 0 and a "
                        "re-randomized tile's ~85 separates cleanly. "
                        "Real-parts serving resolves its threshold from "
                        "the calibrated quality_matrix --streams "
                        "artifact via config.stream_overrides instead")
    p.add_argument("--tile-grid", type=int, default=2,
                   help="frame tiling (grid x grid tiles, each the "
                        "engine's image size)")
    p.add_argument("--stream-load", type=float, default=2.5,
                   help="streams rows' offered frame rate as a multiple "
                        "of the full arm's measured closed-loop capacity "
                        "(per-tile service makes that the TRUE "
                        "saturation rate — batching buys no throughput; "
                        "keep 1 < load < 1/computed-fraction so the "
                        "full arm saturates while the gated arm fits)")
    p.add_argument("--tile-sim-ms", type=float, default=10.0,
                   help="streams rows: simulated PER-TILE service time "
                        "(a bucket-b tile batch costs b x this — the "
                        "compute-bound conv model under which skipped "
                        "tiles buy real capacity; fixed per-batch "
                        "service would measure the router, not the "
                        "compute savings)")
    p.add_argument("--tenants", default="bulk:64,flagged:64",
                   help="fleet canary run's tenant mix as "
                        "'name:budget,...' (per-tenant counters ride "
                        "the artifact)")
    p.add_argument("--faults", default="",
                   help="deterministic fault schedule replayed during the "
                        "load run (ISSUE 9): 'site=kind@n,...' (e.g. "
                        "'serve:dispatch=device-loss@9') or the seeded "
                        "shorthand 'seed=<int>[,n=<int>]'; the JSON line "
                        "gains a faults object and per-row lost counts")
    p.add_argument("--max-retries", type=int, default=2,
                   help="engine per-request retry budget after a batch "
                        "failure/hang")
    p.add_argument("--hang-timeout-ms", type=float, default=0.0,
                   help="engine fetch watchdog (0 disables; defaults to "
                        "500 when --faults is set so injected hangs are "
                        "detected instead of waited out)")
    p.add_argument("--span-log", default="",
                   help="flight-recorder span log (else $OBS_SPAN_LOG)")
    p.add_argument("--trace-exemplars", type=int, default=3,
                   help="embed the N slowest requests' reassembled "
                        "waterfalls + the trace-completeness summary in "
                        "the artifact (ISSUE 14; 0 disables — a temp "
                        "span log is armed when none is configured)")
    p.add_argument("--out", default=None,
                   help="artifact path (default artifacts/<round>/serving/"
                        "serve_bench.json)")
    p.add_argument("--selfcheck", action="store_true")
    args = p.parse_args(argv)
    if args.selfcheck:
        return selfcheck()

    # size defaults follow the platform that was ASKED for: --cpu is the
    # explicit CPU request (toy shapes), anything else is the chip
    on_cpu = args.cpu or "--cpu" in sys.argv
    args.imsize = args.imsize or (64 if on_cpu else 512)
    args.inch = args.inch or (16 if on_cpu else 128)
    args.topk = args.topk or (32 if on_cpu else 100)
    args.amp = (not on_cpu) if args.amp is None else args.amp
    args.infer_dtype = args.infer_dtype or ("bf16" if on_cpu else "int8")
    args.buckets = tuple(sorted(set(args.buckets)))
    if args.faults and args.hang_timeout_ms <= 0:
        args.hang_timeout_ms = 500.0
    args.tenant_budgets = {}
    for part in (args.tenants or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, budget = part.partition(":")
        args.tenant_budgets[name] = int(budget or 64)

    if args.streams:
        out = run_streams_bench(args)
        path = args.out or os.path.join(REPO, "artifacts", graft_round(),
                                        "serving",
                                        "serve_bench_streams.json")
    elif args.cascade:
        out = run_cascade_bench(args)
        path = args.out or os.path.join(REPO, "artifacts", graft_round(),
                                        "serving",
                                        "serve_bench_cascade.json")
    elif args.replicas:
        out = run_fleet_bench(args)
        path = args.out or os.path.join(REPO, "artifacts", graft_round(),
                                        "serving",
                                        "serve_bench_fleet.json")
    else:
        out = run_bench(args)
        path = args.out or os.path.join(REPO, "artifacts", graft_round(),
                                        "serving", "serve_bench.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    save_json(path, out, indent=1, sort_keys=True)
    out["artifact"] = os.path.relpath(path, REPO)
    log("artifact -> %s" % path)
    print(json.dumps(out))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(run_as_job(lambda: sys.exit(main())))
