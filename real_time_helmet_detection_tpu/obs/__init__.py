"""obs/ — the flight recorder: step telemetry, spans, metrics, SLO rules.

Coordinated parts (ISSUEs 6 + 10; the reference has no observability at
all — its loop prints averaged meters, ref train.py:140-160):

* `obs.telemetry` (jax): in-jit step scalars (grad/update/param norms +
  per-component losses) and the fixed-shape telemetry ring carried through
  the scanned train fn — fetched in the SAME single D2H as the loss; and
  the process's one compile listener (`compile` spans per jax stage).
* `obs.spans` (stdlib): the host span recorder — ONE always-on bounded
  in-memory ring per process (`default_tracer()`, spans on the monotonic
  clock, `snapshot(since=)`), plus the crash-safe JSONL span log when a
  path is configured (loader-wait/h2d/dispatch/step/fetch/checkpoint/
  compile/serve:lower|compile|queue-wait|batch-form|h2d|dispatch|
  device-wait|d2h|deliver|e2e/...); `with_ring` tees a tracer handed in
  from outside into the ring.
* `obs.hlo_scopes` (stdlib): compiled HLO text -> {instruction: layer};
  `ServingEngine.scope_maps()` and the step runner's `scope_map()` hand
  it their executables' text so a device-only trace reads by layer.
* `obs.context` (stdlib): host loadavg sampler.
* `obs.metrics` (stdlib): the LIVE metrics plane — thread-safe counters/
  gauges/fixed-layout mergeable histograms with crash-safe periodic
  `obs-metrics-v1` snapshot export ($OBS_METRICS). (The engine keeps one
  histogram, `serve.e2e_ms`; its per-stage times are spans.)
* `obs.slo` (stdlib): the SLO watchdog — EWMA/z-score drift + error/
  latency budget burn rules emitting `alert:*` events and degrading the
  serving engine.
* `obs.trace` (stdlib): trace contexts (ISSUE 14) — per-request
  causality minted at the fleet/engine front door, serialized as
  optional obs-spans-v1 fields; `obs.traceview` reassembles waterfalls
  + critical paths and flags orphan/broken chains.

This __init__ stays STDLIB-ONLY (spans/context/metrics/slo re-exports):
runtime/ — which must never build the ML stack — imports `obs.spans` for
beats-become-spans mirroring and `obs.metrics` for the supervisor gauges.
Import `obs.telemetry` directly where jax is already loaded (train.py,
bench.py).
"""

from .context import sample_context  # noqa: F401
from .metrics import (METRICS_SCHEMA, OBS_METRICS_ENV,  # noqa: F401
                      Counter, Gauge, Histogram, MetricsRegistry,
                      MetricsWriter, default_registry, maybe_writer,
                      read_latest, read_metrics, reset_default_registry,
                      snapshot_digest)
from .slo import (DriftDetector, DriftRule, ErrorBurnRule,  # noqa: F401
                  LatencyBurnRule, SloWatchdog, default_serving_rules,
                  default_train_rules)
from .spans import (OBS_SPAN_ENV, SPAN_SCHEMA, Span,  # noqa: F401
                    SpanRing, SpanTracer, default_tracer, maybe_tracer,
                    read_spans)
from .trace import (TraceContext, links_of, new_root,  # noqa: F401
                    reset_ids, step_context)
