"""Continuous-batching serving engine: bucketed AOT predict + pipelining
+ in-flight recovery.

The reference serves one frame per invocation through its C++ app (ref
README.md:76, export.py:55); the closest thing this repo had was the
eval driver's two-deep software pipeline (evaluate.py). Neither is a
server: many concurrent low-latency streams need *dynamic micro-batching*
(coalesce queued requests into the chip's efficient batch shapes without
waiting forever) plus *multiple in-flight batches* (H2D, compute and D2H
of consecutive batches overlap) plus *admission control* (bounded queue,
deadline shedding — an overloaded server that queues unboundedly serves
nobody: every response arrives too late) plus *in-flight recovery* (a
PJRT error or a hung D2H mid-batch must cost a retry, not the engine).
This engine is that
system, and it is the ONE predict surface eval, demo, bench, serve_bench
and the per-bucket export all sit on.

Design rules, each load-bearing:

* **Fixed-shape buckets, AOT-compiled once.** Requests coalesce into
  padded batches drawn from a static bucket set (default {1, 2, 4, 8,
  16}); every bucket's program is `predict.lower(...).compile()`d at
  construction from the SAME `make_predict_fn` program eval uses. After
  `__init__` returns, serving never traces or compiles again — bucket
  selection is a table lookup (tests pin zero recompiles via the PR 6
  listener), and RETRIES reuse the same executables, which is why a
  retried request's result is bit-identical to its one-shot predict.
  Padding rows are zeros; they are never read back (each request gets
  exactly its own row), and per-row results are bit-identical to a
  one-shot predict of the same image regardless of bucket or co-batched
  neighbors (per-image independence of the predict program;
  property-tested in tests/test_serving.py).
* **Batching policy = max-wait vs max-batch.** The dispatcher takes the
  oldest queued request, then accumulates until either the largest
  bucket fills or `max_wait_ms` has elapsed since that request was
  submitted; under backlog it drains without waiting so saturated
  serving runs at the largest bucket. The batch takes the smallest
  bucket >= its size.
* **Multi-in-flight pipelining.** JAX dispatch is async: the dispatcher
  stages H2D (`device_put`) and the compute dispatch, then hands the
  un-fetched device result to a fetcher thread through a depth-bounded
  queue — the generalization of evaluate.py's one-deep `pending` pattern
  and the C++ runner's `--depth` loop. `depth` bounds device memory
  (depth batches of images + detections) and provides backpressure.
* **A ring of reused staging buffers (ISSUE 30).** A batch is formed in
  the leading rows of a host buffer of the largest bucket's shape, taken
  from a free list and made only when that is empty: a fresh 201 MB
  `np.zeros` a batch cost 230 ms of first-touch page faults on the TPU
  host, the same 256 row copies into touched memory 16 ms. The buffer
  travels with its batch through `_inflight`, and the FETCHER hands it
  back once the batch's answer is on the host (after `serve:d2h`: until
  then the asynchronous H2D may still read it, on the CPU backend the
  device array may alias it, and an output may alias its input), or once
  a failed batch has ended; a batch that failed after its `device_put`
  began, or that the hang watchdog abandoned, keeps its buffer (the
  device may still be reading) and the ring makes another. At most
  `depth + 2` are out (one with the dispatcher, being formed or waiting
  for room in `_inflight`; `depth` queued there; one with the fetcher),
  so the engine holds up to `(depth + 2) x max(buckets) x row bytes` of
  host memory for good (805 MB at bucket 256 of 512^2 uint8 frames and
  depth 2, 524 KB for the decoder's token rows; rows never written are
  never resident) where it held as much in passing.
  Padding stays zeros: each buffer knows up to which row it was written,
  and a batch clears only the stale rows its bucket would send.
  `serve.staging_reused` / `serve.staging_allocated` (and `stats()`) say
  whether buffers come back: in steady state every batch reuses one.
* **Any static payload in, any NamedTuple of row-first arrays out.** The
  detector's frames (below) and the decoder family's token rows
  (`predict.make_generate_fn`: int32 `[length, ids..., padding]` in, a
  `Generation` out) ride the same buckets; a program whose answer should
  feed counters hands the engine a `row_counters`.
* **uint8 in, boxes out.** With a `normalize` predict (the eval wire),
  images cross H2D as uint8 and are normalized on-device; the ONLY D2H
  is the fixed-shape Detections block (boxes/classes/scores/valid) — no
  float image or heatmap ever crosses the host<->device link.
* **Admission control.** The request queue is bounded: `submit(...,
  block=False)` sheds immediately when full (`SheddedError`), and
  requests whose deadline passed before batch formation are shed
  instead of wasting a bucket slot. Shed events land in the flight
  recorder (`serve:shed`).
* **In-flight recovery (ISSUE 9).** A batch that fails at dispatch or
  fetch — or whose fetch exceeds the `hang_timeout_s` watchdog (a D2H
  that never completes) — does not fail
  its requests outright: each constituent request is requeued with a
  bounded per-request retry budget (`max_retries`; budget exhausted =>
  the error surfaces on that future, never silently). Requeues ride an
  internal deque the dispatcher drains FIRST, so recovery does not
  contend with admission control for queue capacity. The engine
  transitions SERVING -> DEGRADED on a batch failure and back after
  `recover_after` consecutive healthy batches; `health()` snapshots the
  state machine for load balancers / the chaos suite. Recovery is
  flight-recorder evidence: `recover:requeue` / `recover:retry-
  exhausted` events and `serve:state` transitions join the `fault:*`
  injections in obs_report's Faults section.
* **Graceful drain + hot reload.** `reload(variables, ...)` drains
  everything already admitted (served with the OLD weights), swaps the
  device-committed weights under the dispatch mutex, and resumes — no
  acknowledged request is dropped and no request ever sees a
  half-swapped checkpoint. The engine passes `variables` as a call
  argument to the AOT executables (never closes over them), which is
  what makes the swap possible without recompiling a single bucket.
* **Deterministic chaos hooks.** An optional `runtime.faults.
  ChaosInjector` fires at the `serve:dispatch` / `serve:fetch` sites;
  with `injector=None` (production default) the hot loops skip even the
  attribute check. The chaos property suite (tests/test_chaos.py)
  replays seeded schedules of device-loss/hung-fetch/slow-batch against
  the engine and asserts zero acknowledged requests are lost and every
  survivor is bit-identical to one-shot predict.
* **Flight-recorder spans.** `serve:lower` / `serve:compile` per bucket
  at construction; `serve:queue-wait` per request; per batch, on the
  dispatcher thread `serve:batch-form` / `serve:h2d` / `serve:dispatch`
  (the ASYNCHRONOUS call of the compiled bucket: host time, not device
  time), on the fetcher `serve:device-wait` (`block_until_ready`: the
  batch period as the host sees it) / `serve:d2h` (the `device_get` of a
  finished batch alone) / `serve:deliver` (the hand-out: the batch's
  `row_counters` into the registry, THEN its futures resolved; meta `b`,
  `n` real rows and the `counters` dict as computed, so every count of a
  batch is in the registry before any of its answers is visible, and a
  window's batches are the `serve:deliver` records that START inside
  it); `serve:e2e` per request. They go to the process's in-memory ring
  (obs/spans.py) and, when `$OBS_SPAN_LOG` or `tracer=` names a file,
  to the span log. The engine calls nothing of its tracer but
  `span`/`record`/`event`/`enabled`: the benchmark hands in its own,
  and the engine writes the ring beside it (`obs.spans.with_ring`).
* **Trace contexts (ISSUE 14).** With tracing enabled, every request
  carries a `TraceContext` (obs/trace.py): `submit(ctx=...)` accepts
  one from the FleetRouter, else the engine mints a root itself.
  Per-request spans (`serve:queue-wait`/`serve:e2e`/`serve:shed`)
  carry the context; batch-level spans (`serve:batch-form`/`h2d`/
  `dispatch`/`device-wait`/`d2h`) and the `recover:*` events carry
  fan-in `links`
  naming every member request's context — one slow batch explains N
  tails (obs/traceview.py reassembles the waterfalls). CLOSURE
  OWNERSHIP: the root minter accounts for the request's end — when the
  engine minted the root it emits the root-closure `serve:e2e` (or a
  terminal `serve:failed`/`serve:shed`); under a router-minted root
  everything engine-side is a child and the router closes. Tracing OFF
  (the production default without $OBS_SPAN_LOG) threads `None`
  everywhere: the executed programs, the single per-batch D2H and the
  device_get count are IDENTICAL on or off (pinned by
  tests/test_trace.py) — contexts are host-side bookkeeping only.
* **Live metrics plane (ISSUE 10).** Every admission decision, batch
  outcome and pipeline stage also lands in an `obs.metrics` registry:
  `serve.*` counters (submitted/completed/shed/retried/requeued/
  failed), queue-depth + per-bucket fill gauges, and the `serve.e2e_ms`
  latency histogram (the per-stage times are the spans above, exact and
  with a place on the clock) — all HOST-side bookkeeping
  (the executed programs are bit-identical with metrics on or off, and
  the per-batch D2H stays the only fetch). `health()` folds the
  digested registry in; `$OBS_METRICS` arms crash-safe periodic
  snapshot export. An optional `obs.slo.SloWatchdog` is poked after
  every batch outcome: a burning error/latency budget flips the engine
  to DEGRADED via `degrade()` BEFORE the chaos-ladder failure modes
  would — alerts are deterministic under `runtime/faults.py` replay
  because they derive from the (deterministic) batch outcome sequence.
"""

from __future__ import annotations

import collections
import gc
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import links_of, new_root

DEFAULT_BUCKETS = (1, 2, 4, 8, 16)

# engine states (the ISSUE 9 state machine; docs/ARCHITECTURE.md "Fault
# injection & self-healing" has the transition table)
SERVING = "serving"      # healthy steady state
DEGRADED = "degraded"    # >=1 recent batch failure; still serving, retries
# in flight; exits after `recover_after` consecutive healthy batches
DRAINING = "draining"    # reload(): serving admitted work, not yet swapped
CLOSED = "closed"        # terminal

_SENTINEL = object()
_WAKE = object()         # fetcher->dispatcher nudge: "check the retry deque"


class SheddedError(RuntimeError):
    """The request was shed by admission control (queue full or deadline
    passed before dispatch) — the caller should retry/downgrade, not
    crash."""


class EngineClosedError(RuntimeError):
    """The engine was closed before this request completed."""


class FetchHungError(RuntimeError):
    """A batch's D2H exceeded the hang watchdog (`hang_timeout_s`):
    completion that never arrives. The batch's requests are requeued; the stuck fetch is
    abandoned (its eventual result, if any, is discarded)."""


def resolve_buckets(cfg) -> Tuple[int, ...]:
    """The static bucket set from `cfg.serve_buckets`, validated + sorted.

    ONE definition shared by the engine, export's per-bucket artifacts and
    graftlint's per-bucket trace audit, so every consumer serves the same
    shape set."""
    raw = list(getattr(cfg, "serve_buckets", None) or DEFAULT_BUCKETS)
    buckets = sorted({int(b) for b in raw})
    if not buckets or buckets[0] < 1:
        raise ValueError("serve_buckets must be positive ints, got %r"
                         % (raw,))
    return tuple(buckets)


class ServeFuture:
    """Completion handle for one request. `result()` blocks; a shed or
    engine-close surfaces as the recorded exception. `t_submit`/`t_done`
    (monotonic) let load generators compute client-side latency without
    re-timing. Completion is FIRST-WINS: a hang-abandoned fetch that
    eventually lands cannot overwrite the retry's result.

    `add_done_callback(fn)` (ISSUE 12) is the fleet-router chaining hook:
    `fn(self)` runs exactly once, on the completing thread (or inline
    when already done) — the router uses it to re-dispatch a replica
    failure to another replica without a polling thread. Callback
    exceptions are swallowed (a completion must never kill the engine's
    fetcher)."""

    __slots__ = ("_event", "_value", "_error", "t_submit", "t_done",
                 "deadline", "_cb", "_cb_lock", "_cb_fired", "ctx")

    def __init__(self, deadline: Optional[float] = None):
        self._event = threading.Event()
        self._value = None
        self._error: Optional[BaseException] = None
        self.t_submit = time.monotonic()
        self.t_done: Optional[float] = None
        self.deadline = deadline
        self._cb = None
        self._cb_lock = threading.Lock()
        self._cb_fired = False
        self.ctx = None  # TraceContext when tracing is on (ISSUE 14)

    def _run_callback(self) -> None:
        with self._cb_lock:
            cb = self._cb
            if cb is None or self._cb_fired:
                return
            self._cb_fired = True
        try:
            cb(self)
        except Exception:  # noqa: BLE001 — see docstring
            pass

    def add_done_callback(self, fn) -> None:
        """Register the ONE completion callback (last registration wins;
        the engine itself registers none). Fires inline when the future
        is already done — the submit-then-attach race is closed here,
        not at the call site."""
        with self._cb_lock:
            self._cb = fn
        if self._event.is_set():
            self._run_callback()

    def _set(self, value) -> bool:
        if self._event.is_set():
            return False
        self._value = value
        self.t_done = time.monotonic()
        self._event.set()
        self._run_callback()
        return True

    def _fail(self, error: BaseException) -> bool:
        if self._event.is_set():
            return False
        self._error = error
        self.t_done = time.monotonic()
        self._event.set()
        self._run_callback()
        return True

    def done(self) -> bool:
        return self._event.is_set()

    def exception(self) -> Optional[BaseException]:
        """The recorded error of a DONE future, else None — the
        non-raising peek the fleet router's dispatch/redispatch decisions
        read (concurrent.futures naming)."""
        return self._error if self._event.is_set() else None

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("serve request still pending after %ss"
                               % timeout)
        if self._error is not None:
            raise self._error
        return self._value


class _Request:
    __slots__ = ("payload", "future", "attempts", "ctx", "ctx_owner")

    def __init__(self, payload: np.ndarray, future: ServeFuture,
                 ctx=None, ctx_owner: bool = False):
        self.payload = payload
        self.future = future
        self.attempts = 0    # completed dispatch attempts that failed
        self.ctx = ctx       # TraceContext (ISSUE 14); stable across
        self.ctx_owner = ctx_owner  # retries. owner=True: WE minted the
        # root (standalone serving) and owe the trace its closure


class _Staging:
    """One host buffer of the staging ring: `buf` holds the largest bucket's
    rows, rows at or after `mark` are zeros."""
    __slots__ = ("buf", "mark")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.mark = 0


class ServingEngine:
    """Persistent continuous-batching server over a jitted predict fn.

    Parameters
    ----------
    predict : the `make_predict_fn` jitted callable
        `(variables, images(B,H,W,C)) -> Detections` — batch-shape
        polymorphic under AOT lowering; eval/demo/export pass exactly the
        fn they already use.
    variables : checkpoint pytree, device-committed once at construction
        (hot-swappable later via `reload`).
    payload_shape : static per-request shape: (H, W, C) for the detector's
        frames, (P_max + 1,) for the generate program's token rows.
    payload_dtype : np dtype of the wire (uint8 for the raw eval wire,
        int32 for token ids).
    buckets : static batch-size set, AOT-compiled at construction.
    max_wait_ms : batch-formation wait bound (0 = dispatch immediately).
    depth : max in-flight batches (>=1); device memory is bounded by
        `depth` image+detection batches.
    queue_capacity : admission bound on queued (not yet batched) requests.
    sharding : optional `jax.sharding` for the image batch (the meshed
        eval path); variables are replicated when a sharding is given.
    tracer : `obs.spans.SpanTracer`; default `maybe_tracer()` honors
        $OBS_SPAN_LOG. Any other tracer (`span`/`record`/`event`/
        `enabled`) gets what it always got, and the process ring gets
        the same (`with_ring`).
    start : tests may construct paused (`start=False`) to exercise
        admission control deterministically, then call `.start()`.
    max_retries : per-REQUEST retry budget after a batch failure/hang
        (0 restores the pre-recovery fail-fast behavior).
    hang_timeout_s : fetch watchdog — a batch D2H exceeding this is
        treated as hung and its requests requeued (None disables; keep
        it well above the honest p99 fetch time for the largest bucket).
    recover_after : consecutive healthy batches that clear DEGRADED.
    injector : optional `runtime.faults.ChaosInjector` for deterministic
        fault replay (tests/serve_bench --faults); None = zero overhead.
    metrics : optional `obs.metrics.MetricsRegistry`; default = the
        process-wide registry (so one $OBS_METRICS export covers every
        instrumented module). Pass a fresh registry for isolated runs
        (serve_bench, tests).
    watchdog : optional `obs.slo.SloWatchdog`, poked after every batch
        outcome; serving alerts degrade THIS engine.
    row_counters : optional `rows -> {counter name: increment}`, called on
        the fetch thread once a batch is on the host and BEFORE any of its
        answers is delivered, with the fetched answer cut to its real
        rows; the increments go into `metrics` (how a program's answer
        feeds counters: `predict.generation_counters`) and the dict into
        the batch's `serve:deliver` record. A callback that raises is
        counted (`serve.row_counter_errors`) and the engine serves on.
    """

    def __init__(self, predict, variables, payload_shape: Sequence[int],
                 payload_dtype, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_wait_ms: float = 5.0, depth: int = 2,
                 queue_capacity: int = 128, sharding=None, tracer=None,
                 start: bool = True, max_retries: int = 2,
                 hang_timeout_s: Optional[float] = None,
                 recover_after: int = 2, injector=None, metrics=None,
                 watchdog=None, row_counters=None):
        import jax

        from ..obs import metrics as metrics_mod
        from ..obs.spans import maybe_tracer, with_ring
        from ..obs.telemetry import install_compile_listener

        self._buckets = tuple(sorted({int(b) for b in buckets}))
        if not self._buckets or self._buckets[0] < 1:
            raise ValueError("buckets must be positive, got %r" % (buckets,))
        self._payload_shape = tuple(int(s) for s in payload_shape)
        self._payload_dtype = np.dtype(payload_dtype)
        self._row_counters = row_counters
        self._max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self._depth = max(1, int(depth))
        self._sharding = sharding
        self._tracer = with_ring(tracer if tracer is not None
                                 else maybe_tracer())
        self._max_retries = max(0, int(max_retries))
        self._hang_timeout_s = (None if hang_timeout_s is None
                                else max(1e-3, float(hang_timeout_s)))
        self._recover_after = max(1, int(recover_after))
        self._injector = injector
        # live metrics plane (ISSUE 10): host-side handles, created once
        # so the hot loops do dict-free inc/observe calls
        self._metrics = (metrics if metrics is not None
                         else metrics_mod.default_registry())
        self._m_writer = metrics_mod.maybe_writer(registry=self._metrics)
        self._watchdog = watchdog
        mm = self._metrics
        self._mc = {name: mm.counter("serve." + name) for name in (
            "submitted", "completed", "batches_total", "batch_slots",
            "padded_slots", "shed_queue_full", "shed_deadline", "retried",
            "requeued_batches", "failed_batches", "hung_batches",
            "retry_exhausted", "reloads", "row_counter_errors",
            "staging_reused", "staging_allocated")}
        self._mg_queue = mm.gauge("serve.queue_depth")
        self._mg_retry = mm.gauge("serve.retry_depth")
        self._mg_inflight = mm.gauge("serve.inflight_batches")
        self._mh_e2e = mm.histogram("serve.e2e_ms")

        self._variables = self._commit_variables(variables)
        # AOT: one compile per bucket, at construction, from the SAME
        # predict program — the serve path never traces again
        # (every jax compile stage also lands as a `compile` span in the
        # process's ring: obs/telemetry.py)
        install_compile_listener()
        self._compiled: Dict[int, object] = {}
        for b in self._buckets:
            spec = jax.ShapeDtypeStruct((b,) + self._payload_shape,
                                        self._payload_dtype)
            with self._tracer.span("serve:lower", b=b):
                lowered = predict.lower(self._variables, spec)
            with self._tracer.span("serve:compile", b=b):
                self._compiled[b] = lowered.compile()
        # (n, links) of the batch the fetcher thread is about to `_fetch`;
        # its watchdog worker reads it before the fetcher moves on
        self._fetch_meta = (0, None)  # lock-free: the fetcher thread's own

        self._q: "queue.Queue" = queue.Queue(maxsize=max(1,
                                                         int(queue_capacity)))
        self._retry: "collections.deque" = collections.deque()
        self._inflight: "queue.Queue" = queue.Queue(maxsize=self._depth)
        # the staging ring's free list: a hand-off queue, fetcher ->
        # dispatcher, no lock of the engine's (see `_take_staging`)
        self._staging_free: "queue.SimpleQueue" = queue.SimpleQueue()
        self._lock = threading.Lock()
        # serializes batch dispatch against reload's weight swap; the
        # dispatcher holds it across one batch's form+H2D+compute
        self._dispatch_mutex = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "batches": 0,
                       "shed_queue_full": 0, "shed_deadline": 0,
                       "padded_slots": 0, "failed": 0, "retried": 0,
                       "requeued_batches": 0, "hung_batches": 0,
                       "failed_batches": 0, "reloads": 0,
                       "staging_reused": 0, "staging_allocated": 0}
        self._state = SERVING
        self._consecutive_failures = 0
        self._consecutive_ok = 0
        self._inflight_batches = 0
        self._dispatch_busy = False  # a batch is being formed/dispatched
        # (visible to _is_idle: batch formation can last max_wait_ms)
        self._last_error: Optional[str] = None
        self._closed = False
        self._started = False
        self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                            daemon=True,
                                            name="serve-dispatch")
        self._fetcher = threading.Thread(target=self._fetch_loop,
                                         daemon=True, name="serve-fetch")
        if start:
            self.start()

    def _commit_variables(self, variables):
        import jax
        if self._sharding is not None:
            from ..parallel import replicated
            return jax.device_put(variables,
                                  replicated(self._sharding.mesh))
        return jax.device_put(variables)

    # ---- lifecycle -------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        self._dispatcher.start()
        self._fetcher.start()

    def close(self) -> None:
        """Drain in-flight work, stop the threads, fail whatever is still
        queued. Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._started:
            self._q.put(_SENTINEL)  # unbounded-safe: put may block only on
            # a full queue, which the dispatcher is actively draining
            self._dispatcher.join()
            self._fetcher.join()
        # anything still queued (engine never started, raced close, or
        # retries stranded behind the sentinel)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req not in (_SENTINEL, _WAKE):
                err = EngineClosedError("engine closed")
                req.future._fail(err)
                self._note_request_failed(req, err)
        while self._retry:
            req = self._retry.popleft()
            err = EngineClosedError("engine closed")
            req.future._fail(err)
            self._note_request_failed(req, err)
        self._set_state(CLOSED)
        self._m_writer.close()  # final metrics snapshot (when $OBS_METRICS)

    def kill(self, reason: str = "replica death") -> int:
        """Abrupt death (the `fleet:replica` chaos path, ISSUE 12): fail
        every request still QUEUED (admission queue + retry deque) with
        `EngineClosedError` NOW — they were acknowledged, so the caller
        (FleetRouter) must re-dispatch them elsewhere — then shut the
        threads down. Batches already dispatched cannot be un-dispatched;
        they complete normally (first-wins futures), which mirrors a real
        replica loss where in-flight device work may still land. Returns
        the number of requests failed out of the queues. Idempotent."""
        if self._closed:
            return 0
        self._closed = True
        failed = 0
        err = EngineClosedError("replica killed: %s" % str(reason)[:200])
        # drain the admission queue ahead of the dispatcher: anything we
        # win goes to the router's re-dispatch; anything the dispatcher
        # wins is served (both end states keep the ack)
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req not in (_SENTINEL, _WAKE):
                req.future._fail(err)
                self._note_request_failed(req, err)
                failed += 1
        while self._retry:
            req = self._retry.popleft()
            req.future._fail(err)
            self._note_request_failed(req, err)
            failed += 1
        self._tracer.event("serve:killed", reason=str(reason)[:200],
                           failed=failed)
        if self._started:
            self._q.put(_SENTINEL)
            self._dispatcher.join()
            self._fetcher.join()
        # requests the dispatcher raced into the queue after our drain
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req not in (_SENTINEL, _WAKE):
                req.future._fail(err)
                self._note_request_failed(req, err)
                failed += 1
        while self._retry:
            req = self._retry.popleft()
            req.future._fail(err)
            self._note_request_failed(req, err)
            failed += 1
        self._set_state(CLOSED)
        self._m_writer.close()
        return failed

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---- state machine ---------------------------------------------------

    def _set_state(self, new: str) -> None:
        with self._lock:
            old = self._state
            if old == new or old == CLOSED:
                return
            self._state = new
        self._tracer.event("serve:state", **{"from": old, "to": new})

    @property
    def state(self) -> str:
        # _state is _lock-guarded everywhere it is written; an unlocked
        # read here was the one hole (lock/unguarded-shared-write)
        with self._lock:
            return self._state

    def degrade(self, reason: str) -> None:
        """External DEGRADED flip (the SLO watchdog's lever, ISSUE 10):
        the engine keeps serving but advertises trouble, exactly as after
        a batch failure; `recover_after` consecutive healthy batches
        clear it. A closed engine ignores the poke."""
        with self._lock:
            self._consecutive_ok = 0
            self._last_error = "degraded: %s" % str(reason)[:200]
        self._tracer.event("serve:degrade", reason=str(reason)[:200])
        self._set_state(DEGRADED)

    def health(self, include_metrics: bool = True) -> Dict:
        """Point-in-time health snapshot (the load-balancer / chaos-suite
        API): state machine position, backlog depths, failure counters,
        plus the digested live metrics (per-stage latency p50/p99, fill
        and depth gauges — ISSUE 10's extended health surface).

        The whole digest is read under ONE `_lock` acquisition (ISSUE 12
        bugfix: the state used to be read after the lock was released, so
        a reload between the two reads could hand a load balancer a
        `stats` snapshot from before the swap stitched to the state from
        after it; `FleetRouter` consumes this on every dispatch, so the
        snapshot must be internally consistent — pinned by
        tests/test_fleet.py's single-acquisition test). The queue/retry
        depth reads stay outside (queue.Queue carries its own lock; each
        is an independently-atomic instantaneous depth — a tolerated,
        documented skew, not an interleaved digest).

        `include_metrics=False` is the dispatch fast path: the metrics
        digest walks every histogram (quantile scans); a per-submit
        router decision only needs the state/backlog fields."""
        with self._lock:
            state = self._state
            stats = dict(self._stats)
            consec_fail = self._consecutive_failures
            inflight = self._inflight_batches
            last_error = self._last_error
        out = {"state": state, "queued": self._q.qsize(),
               "retry_queued": len(self._retry),
               "inflight_batches": inflight,
               "consecutive_failures": consec_fail,
               "buckets": list(self._buckets),
               "max_retries": self._max_retries,
               "hang_timeout_s": self._hang_timeout_s,
               "last_error": last_error, "stats": stats}
        if include_metrics:
            out["metrics"] = self._metrics.digest(prefix="serve.")
            if self._watchdog is not None:
                out["alerts"] = list(self._watchdog.alerts)
        return out

    def _after_batch_outcome(self) -> None:
        """Post-outcome hook shared by the healthy and failed paths: poke
        the SLO watchdog (alerts may degrade THIS engine) and give the
        metrics exporter its periodic flush point. Host-side only."""
        if self._watchdog is not None:
            self._watchdog.check(engine=self)
        self._m_writer.maybe_flush()

    def _is_idle(self) -> bool:
        with self._lock:
            inflight = self._inflight_batches
            forming = self._dispatch_busy
        return (self._q.qsize() == 0 and not self._retry
                and inflight == 0 and not forming)

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Wait until everything admitted so far has completed (queues
        empty, zero in-flight batches). Returns False on timeout. Rare
        control-path polling, not a hot loop."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while not self._is_idle():
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return True

    def reload(self, variables, timeout_s: float = 30.0) -> None:
        """Hot checkpoint/scales swap: drain admitted work (served with
        the OLD weights), swap the device-committed variables under the
        dispatch mutex, resume. Zero recompiles (the AOT executables take
        variables as a call argument) and zero dropped requests; requests
        admitted during the drain are served with the NEW weights."""
        if self._closed:
            raise EngineClosedError("engine closed")
        self._set_state(DRAINING)
        with self._tracer.span("recover:reload"):
            if not self.drain(timeout_s):
                self._set_state(DEGRADED)
                raise TimeoutError(
                    "reload: engine did not drain within %.1fs" % timeout_s)
            with self._dispatch_mutex:
                # dispatcher is between batches: nothing references the
                # old weights; anything queued dispatches with the new
                self._variables = self._commit_variables(variables)
                with self._lock:
                    self._stats["reloads"] += 1
                self._mc["reloads"].inc()
        self._set_state(SERVING)

    # ---- client API ------------------------------------------------------

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._buckets

    def scope_maps(self) -> Dict[int, Dict[str, str]]:
        """bucket -> {HLO instruction: layer} of the bucket's compiled
        executable (obs/hlo_scopes.py): what turns the instruction names
        of a device-only profiler trace (`fusion.15`, `copy.681`) into the
        layers that own them. Only the engine holds the executables.
        (The names are those of the compile that produced an executable:
        jax's persistent cache keys on the program without its metadata,
        so a cache hit carries the scope names of whichever commit filled
        the entry — `scripts/layer_trace.py` compiles with the cache off.)
        """
        from ..obs.hlo_scopes import scope_map
        return {b: scope_map(c.as_text()) for b, c in self._compiled.items()}

    @property
    def metrics(self):
        """This engine's MetricsRegistry — the canary watchdog's read
        surface (FleetRouter builds its burn rules over the canary
        replica's own registry, so the canary slice is judged on its own
        counters, not the fleet's)."""
        return self._metrics

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._stats)

    def _req_ctx(self, req: "_Request"):
        """The context a per-request record should carry: the ROOT when
        this engine minted it (closure ownership), a child hop when the
        router did, None when tracing is off."""
        if req.ctx is None:
            return None
        return req.ctx if req.ctx_owner else req.ctx.child()

    def _note_request_failed(self, req: "_Request",
                             error: BaseException) -> None:
        """Terminal closure for a request whose root WE minted and whose
        error is about to surface (retry budget exhausted, engine
        closed/killed): without it the trace would read as an orphan.
        Router-minted roots are closed by the router (re-dispatch may
        still complete them elsewhere)."""
        if req.ctx is not None and req.ctx_owner:
            self._tracer.event("serve:failed", ctx=req.ctx,
                               error=type(error).__name__)

    def submit(self, payload: np.ndarray, deadline_s: Optional[float] = None,
               block: bool = True, timeout: Optional[float] = None,
               ctx=None) -> ServeFuture:
        """Enqueue one request; returns its future immediately.

        `deadline_s` (relative seconds) arms deadline shedding: a request
        still un-dispatched past its deadline is shed instead of wasting a
        bucket slot. `block=False` is the admission-control edge: a full
        queue sheds NOW (`SheddedError` raised from `result()`), it never
        stalls the caller — pipelined producers (eval) keep the default
        blocking backpressure instead. An admitted (non-shed) request is
        ACKNOWLEDGED: it completes with a result or a surfaced error,
        never disappears (the chaos suite's zero-lost-acks invariant).

        `ctx` (ISSUE 14): the request's TraceContext — the FleetRouter
        passes the root it minted; standalone callers leave it None and
        the engine mints one itself when tracing is on."""
        if self._closed:
            raise EngineClosedError("engine closed")
        payload = np.asarray(payload)
        if payload.shape != self._payload_shape \
                or payload.dtype != self._payload_dtype:
            raise ValueError(
                "request payload must be %s %s, got %s %s"
                % (self._payload_shape, self._payload_dtype, payload.shape,
                   payload.dtype))
        fut = ServeFuture(
            deadline=None if deadline_s is None
            else time.monotonic() + float(deadline_s))
        owner = False
        if ctx is None and self._tracer.enabled:
            ctx = new_root()
            owner = True
        fut.ctx = ctx
        req = _Request(payload, fut, ctx=ctx, ctx_owner=owner)
        with self._lock:
            self._stats["submitted"] += 1
        self._mc["submitted"].inc()
        try:
            self._q.put(req, block=block, timeout=timeout)
        except queue.Full:
            with self._lock:
                self._stats["shed_queue_full"] += 1
            self._mc["shed_queue_full"].inc()
            self._tracer.event("serve:shed", ctx=self._req_ctx(req),
                               reason="queue-full")
            fut._fail(SheddedError("queue full (admission control)"))
        self._mg_queue.set(self._q.qsize())
        return fut

    def predict_many(self, images: Sequence[np.ndarray]) -> List:
        """Blocking convenience: submit every image, wait for all rows."""
        futs = [self.submit(img) for img in images]
        return [f.result() for f in futs]

    # ---- recovery --------------------------------------------------------

    def _requeue_or_fail(self, live: List[_Request], error: BaseException,
                         stage: str, b: int) -> None:
        """Batch failed at `stage`: requeue each request inside its retry
        budget, surface the error on the rest. The retry deque is drained
        ahead of the admission queue, and a _WAKE token pops a dispatcher
        blocked in q.get() so recovery never waits for fresh traffic."""
        retried, exhausted = 0, 0
        retried_reqs: List[_Request] = []
        exhausted_reqs: List[_Request] = []
        for r in live:
            r.attempts += 1
            if r.attempts <= self._max_retries:
                self._retry.append(r)
                retried += 1
                retried_reqs.append(r)
            else:
                exhausted += 1
                exhausted_reqs.append(r)
                r.future._fail(error)
        with self._lock:
            self._stats["failed_batches"] += 1
            self._stats["retried"] += retried
            self._stats["failed"] += exhausted
            if retried:
                self._stats["requeued_batches"] += 1
            self._consecutive_failures += 1
            self._consecutive_ok = 0
            self._last_error = "%s: %s" % (type(error).__name__,
                                           str(error).splitlines()[0][:200]
                                           if str(error) else "")
        self._mc["failed_batches"].inc()
        self._mc["retried"].inc(retried)
        self._mc["retry_exhausted"].inc(exhausted)
        if retried:
            self._mc["requeued_batches"].inc()
        self._mg_retry.set(len(self._retry))
        self._set_state(DEGRADED)
        self._tracer.event(
            "recover:requeue", stage=stage, b=b, n=retried,
            links=links_of([r.ctx for r in retried_reqs]) or None,
            error=type(error).__name__)
        if exhausted:
            self._tracer.event(
                "recover:retry-exhausted", stage=stage, n=exhausted,
                links=links_of([r.ctx for r in exhausted_reqs]) or None,
                error=type(error).__name__)
            for r in exhausted_reqs:
                self._note_request_failed(r, error)
        if retried:
            try:
                self._q.put_nowait(_WAKE)
            except queue.Full:
                pass  # a full queue means the dispatcher wakes anyway
        self._after_batch_outcome()

    def _note_batch_ok(self) -> None:
        with self._lock:
            self._consecutive_ok += 1
            self._consecutive_failures = 0
            recovered = (self._state == DEGRADED
                         and self._consecutive_ok >= self._recover_after)
        if recovered:
            self._set_state(SERVING)
        self._after_batch_outcome()

    # ---- dispatcher ------------------------------------------------------

    def _pick_bucket(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _shed_expired(self, batch: List[_Request], now: float
                      ) -> List[_Request]:
        live = []
        for r in batch:
            if r.future.deadline is not None and now > r.future.deadline:
                with self._lock:
                    self._stats["shed_deadline"] += 1
                self._mc["shed_deadline"].inc()
                self._tracer.event("serve:shed", ctx=self._req_ctx(r),
                                   reason="deadline")
                r.future._fail(SheddedError("deadline passed before "
                                            "dispatch"))
            else:
                live.append(r)
        return live

    def _take_blocking(self):
        """Next request, retries first; blocks on the admission queue.
        Returns _SENTINEL at shutdown."""
        while True:
            if self._retry:
                return self._retry.popleft()
            item = self._q.get()
            if item is _WAKE:
                continue
            return item

    def _poll_next(self, timeout_s: float):
        """Non-blocking-ish intake used during batch accumulation:
        retries first, then the queue with `timeout_s` (<=0 = no wait).
        None = nothing available in time."""
        if self._retry:
            return self._retry.popleft()
        try:
            item = (self._q.get_nowait() if timeout_s <= 0
                    else self._q.get(timeout=timeout_s))
        except queue.Empty:
            return None
        if item is _WAKE:
            if self._retry:
                return self._retry.popleft()
            return None
        return item

    def _take_staging(self) -> _Staging:
        """A staging buffer for the batch being formed: one the fetcher
        handed back, else a fresh one. No cap is needed: never more than
        `depth + 2` are out (module docstring), and a fresh one is made
        only when all that exist are."""
        try:
            st, key = self._staging_free.get_nowait(), "staging_reused"
        except queue.Empty:
            st, key = _Staging(np.zeros(
                (self._buckets[-1],) + self._payload_shape,
                self._payload_dtype)), "staging_allocated"
        with self._lock:
            self._stats[key] += 1
        self._mc[key].inc()
        return st

    def _dispatch_loop(self) -> None:
        import jax

        maxb = self._buckets[-1]
        stop = False
        while not stop:
            req = self._take_blocking()
            if req is _SENTINEL:
                break
            with self._lock:
                self._dispatch_busy = True
            batch = [req]
            # max-wait vs max-batch: anchor on the FIRST request's submit
            # time; under backlog (anchor already expired) drain without
            # waiting so a saturated server runs full buckets
            anchor = req.future.t_submit + self._max_wait_s
            while len(batch) < maxb:
                nxt = self._poll_next(anchor - time.monotonic())
                if nxt is None:
                    if anchor - time.monotonic() <= 0:
                        break
                    continue
                if nxt is _SENTINEL:
                    stop = True
                    break
                batch.append(nxt)
            live = self._shed_expired(batch, time.monotonic())
            if not live:
                with self._lock:
                    self._dispatch_busy = False
                continue
            # fan-in edges: every batch-level span names its member
            # requests' contexts (None — tracing off — folds to no links)
            blinks = links_of([r.ctx for r in live]) or None
            with self._dispatch_mutex:
                with self._tracer.span("serve:batch-form", links=blinks,
                                       n=len(live)):
                    b, n = self._pick_bucket(len(live)), len(live)
                    # a buffer of the ring, which no H2D still reads (the
                    # fetcher handed it back); padding stays zeros: the
                    # rows an earlier batch wrote (below the mark) are
                    # cleared, as far as this bucket sends them
                    st = self._take_staging()
                    for i, r in enumerate(live):
                        st.buf[i] = r.payload
                    stale = min(st.mark, b)
                    if n < stale:
                        st.buf[n:stale] = 0
                    if st.mark <= b:
                        st.mark = n
                    buf = st.buf[:b]
                now = time.monotonic()
                for r in live:
                    self._tracer.record("serve:queue-wait",
                                        now - r.future.t_submit,
                                        ctx=(r.ctx.child() if r.ctx
                                             else None))
                handed = False  # True: the runtime may be reading `buf`
                try:
                    if self._injector is not None:
                        self._injector.fire("serve:dispatch", b=b)
                    with self._tracer.span("serve:h2d", b=b, links=blinks):
                        handed = True
                        dev = (jax.device_put(buf, self._sharding)
                               if self._sharding is not None
                               else jax.device_put(buf))
                    # the call returns once the batch is enqueued: host
                    # time. The device's time shows in the fetcher's
                    # `serve:device-wait`.
                    with self._tracer.span("serve:dispatch", b=b,
                                           links=blinks):
                        out = self._compiled[b](self._variables, dev)
                except Exception as e:  # noqa: BLE001 — requeue, serve on
                    if not handed:  # else dropped: a transfer may be live
                        self._staging_free.put(st)
                    self._requeue_or_fail(live, e, stage="dispatch", b=b)
                    with self._lock:
                        self._dispatch_busy = False
                    continue
                with self._lock:
                    self._stats["batches"] += 1
                    self._stats["padded_slots"] += b - len(live)
                    self._inflight_batches += 1
                    self._dispatch_busy = False
                    inflight = self._inflight_batches
                self._mc["batches_total"].inc()
                self._mc["batch_slots"].inc(b)
                self._mc["padded_slots"].inc(b - len(live))
                self._mg_inflight.set(inflight)
                self._mg_queue.set(self._q.qsize())
            self._inflight.put((out, live, b, st))
            # depth-bounded: blocks at `depth` in-flight batches — the
            # pipelining backpressure
        self._inflight.put(_SENTINEL)

    # ---- fetcher ---------------------------------------------------------

    def _fetch(self, out, b: int):
        """Wait for the batch, then its D2H, under the hang watchdog when
        configured: `serve:device-wait` (`block_until_ready`: until the
        device has finished the batch) and `serve:d2h` (the `device_get`
        of the finished Detections block alone). No extra sync: the fetch
        blocked on the batch anyway. The pull runs in a short-lived worker
        thread ONLY so a hang can be abandoned (the thread is daemonic; a
        late result is discarded — futures are first-wins); without a
        watchdog it runs inline. `(out, b)` is the whole signature (fault
        tests wrap it); the spans' `n`/`links` come from `_fetch_meta`,
        set by the fetch loop, this method's one caller."""
        import jax
        n, links = self._fetch_meta

        def pull():
            with self._tracer.span("serve:device-wait", b=b, links=links):
                if self._injector is not None:
                    self._injector.fire("serve:fetch", b=b)
                jax.block_until_ready(out)
            with self._tracer.span("serve:d2h", b=b, n=n, links=links):
                # the ONE sanctioned batched fetch (graftlint
                # ast/device-get-in-serving-loop polices per-request
                # fetches; this one D2H serves the whole batch)
                return jax.device_get(out)

        if self._hang_timeout_s is None:
            return pull()
        box: Dict = {}
        done = threading.Event()

        def _d2h():
            try:
                box["v"] = pull()
            except BaseException as e:  # noqa: BLE001 — surfaced below
                box["e"] = e
            finally:
                done.set()

        th = threading.Thread(target=_d2h, daemon=True, name="serve-d2h")
        th.start()
        if not done.wait(self._hang_timeout_s):
            with self._lock:
                self._stats["hung_batches"] += 1
            self._mc["hung_batches"].inc()
            raise FetchHungError(
                "batch (bucket %d) D2H exceeded the %.3fs hang watchdog"
                % (b, self._hang_timeout_s))
        if "e" in box:
            raise box["e"]
        return box["v"]

    @staticmethod
    def _await_end(out) -> None:
        """Return once a FAILED batch has ended, well or badly: from then
        on the device no longer reads its staging buffer."""
        import jax
        try:
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 — it has ended
            pass

    def _deliver(self, host, live, b: int, links) -> None:
        """Hand a fetched batch out, as one `serve:deliver` span: first
        its counts (the registry's `completed` and whatever `row_counters`
        gives, the dict also the span's `counters`), then its answers. A
        batch's counts are thus in the registry before any of its answers
        is visible, and the span's start precedes every answer's."""
        n = len(live)
        counts: Optional[dict] = {} if self._row_counters is not None \
            else None
        with self._tracer.span("serve:deliver", b=b, n=n, counters=counts,
                               links=links):
            with self._lock:
                self._stats["completed"] += n
            self._mc["completed"].inc(n)
            if self._row_counters is not None:
                # never the fetch thread's death: a callback that raises
                # costs its batch's increments only (and the record's)
                try:
                    got = self._row_counters(
                        type(host)(*(leaf[:n] for leaf in host)))
                    for name, by in got.items():
                        self._metrics.counter(name).inc(by)
                    counts.update(got)
                except Exception:  # noqa: BLE001 — counted, serve on
                    self._mc["row_counter_errors"].inc()
            # The cyclic collector stays out of the delivery loop: the
            # loop allocates a result per request, so a full collection
            # (60-130 ms beside a process that keeps 10^4 futures: PERF.md
            # section 6, PR 25) would otherwise start in the middle of it
            # and hold the rest of the batch's answers that long. Deferred,
            # it runs in the wait for the next batch.
            collecting = gc.isenabled()
            gc.disable()
            try:
                for i, r in enumerate(live):
                    # completion stamps come from the future itself (_set
                    # records t_done), so the e2e record is pure arithmetic
                    # over stored clocks — client-visible latency, not a
                    # device-timing claim (bench.py owns those)
                    r.future._set(type(host)(*(np.asarray(leaf[i])
                                               for leaf in host)))
                    self._tracer.record(
                        "serve:e2e", r.future.t_done - r.future.t_submit,
                        ctx=self._req_ctx(r), b=b)
                    self._mh_e2e.observe(
                        (r.future.t_done - r.future.t_submit) * 1e3)
            finally:
                if collecting:
                    gc.enable()

    def _fetch_loop(self) -> None:
        while True:
            item = self._inflight.get()
            if item is _SENTINEL:
                return
            out, live, b, st = item
            flinks = links_of([r.ctx for r in live]) or None
            try:
                self._fetch_meta = (len(live), flinks)
                host = self._fetch(out, b)
            except Exception as e:  # noqa: BLE001 — requeue, serve on
                if not isinstance(e, FetchHungError):
                    self._await_end(out)
                    self._staging_free.put(st)
                # (an abandoned batch keeps its buffer: the device may still
                # be reading it; the ring makes another)
                self._requeue_or_fail(live, e, stage="fetch", b=b)
                with self._lock:
                    self._inflight_batches -= 1
                continue
            # the answer is on the host (after the D2H, not the wait: an
            # output may alias its input): the staging buffer is free
            self._staging_free.put(st)
            self._deliver(host, live, b, flinks)
            with self._lock:
                self._inflight_batches -= 1
                inflight = self._inflight_batches
            self._mg_inflight.set(inflight)
            self._note_batch_ok()
