"""Distributed-tracing tests (ISSUE 14): trace-context roundtrip through
router -> engine -> batch fan-in, cross-process span-log joins, the
tracing-OFF bit-identity + device_get-count pin (the PR 6 pattern), and
torn-line tolerance.

The reference has no observability tooling at all (its loop prints
averaged meters, ref train.py:140-160); everything here guards new
capability. Structure tests run over a fixed-service sim predict (no
model compile — the engine AOT-lowers it exactly like the real program);
the bit-identity pin runs the REAL tiny predict, because that is the
claim's subject.
"""

import collections
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from real_time_helmet_detection_tpu.obs import trace, traceview
from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
from real_time_helmet_detection_tpu.obs.spans import (SpanTracer,
                                                      maybe_tracer,
                                                      read_spans)
from real_time_helmet_detection_tpu.runtime import (ChaosInjector,
                                                    FaultSchedule)
from real_time_helmet_detection_tpu.serving import (FleetRouter,
                                                    ServingEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "distributed_worker.py")
IMSIZE = 32


# ---------------------------------------------------------------------------
# sim predict: fixed service time, engine-compatible lower().compile()


_SimDetections = collections.namedtuple("_SimDetections", "boxes scores")


class _SimCompiled:
    def __init__(self, b, service_s):
        self.b = b
        self.service_s = service_s

    def __call__(self, variables, images):
        if self.service_s:
            time.sleep(self.service_s)
        imgs = np.asarray(images)
        boxes = imgs[:, :2, :2, 0].astype(np.float32).reshape(self.b, -1)
        return _SimDetections(boxes, boxes.sum(axis=1))


class SimPredict:
    def __init__(self, service_ms=5.0):
        self.service_s = service_ms / 1e3

    def lower(self, variables, spec):
        b, svc = spec.shape[0], self.service_s

        class _L:
            def compile(self):
                return _SimCompiled(b, svc)

        return _L()


def _pool(n=4, imsize=IMSIZE):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 256, (imsize, imsize, 3), dtype=np.uint8)
            for _ in range(n)]


def _sim_engine(tracer, buckets=(1, 2, 4), service_ms=5.0, start=True,
                **kw):
    return ServingEngine(SimPredict(service_ms), {"w": np.zeros(1)},
                         (IMSIZE, IMSIZE, 3), np.uint8, buckets=buckets,
                         max_wait_ms=1.0, queue_capacity=64,
                         metrics=MetricsRegistry(), tracer=tracer,
                         start=start, **kw)


# ---------------------------------------------------------------------------
# context primitives


def test_context_ids_deterministic_and_unique():
    trace.reset_ids(9)
    a = trace.new_root()
    b = trace.new_root()
    trace.reset_ids(9)
    a2 = trace.new_root()
    b2 = trace.new_root()
    assert a == a2 and b == b2  # seeded replay mints the same ids
    assert a.trace_id != b.trace_id and a.span_id != b.span_id
    c = a.child()
    assert c.trace_id == a.trace_id and c.parent_id == a.span_id
    assert c.span_id not in (a.span_id, b.span_id)
    trace.reset_ids()  # restore the pid-derived production prefix


def test_context_field_roundtrip_and_optionality():
    trace.reset_ids(3)
    root = trace.new_root()
    child = root.child()
    assert "parent" not in root.to_fields()  # root closure marker
    assert child.to_fields()["parent"] == root.span_id
    assert trace.TraceContext.from_fields(child.to_fields()) == child
    # pre-ISSUE records (no trace fields) parse to None, never raise
    assert trace.TraceContext.from_fields({"kind": "span",
                                           "name": "step"}) is None
    assert trace.links_of([root, None, child]) == [root.link(),
                                                   child.link()]
    trace.reset_ids()


def test_step_context_joins_across_ranks():
    s0 = trace.step_context(7, epoch=2, rank=0, run="t")
    s1 = trace.step_context(7, epoch=2, rank=1, run="t")
    assert s0.trace_id == s1.trace_id  # the cross-process join key
    assert s0.span_id != s1.span_id
    assert trace.step_context(8, epoch=2, rank=0,
                              run="t").trace_id != s0.trace_id


# ---------------------------------------------------------------------------
# roundtrip: router -> engine -> batch fan-in


def test_router_engine_batch_fanin_roundtrip(tmp_path):
    """A paused fleet forces co-batching: every request's trace closes
    (fleet:e2e), replica-side spans are children of the SAME trace the
    router minted, and one batch-level compute span fans into ALL
    member traces."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    pool = _pool(4)

    def factory(rid, start=True):
        return _sim_engine(tracer, start=start)

    router = FleetRouter(factory, 1, metrics=MetricsRegistry(),
                         tracer=tracer, start=False)
    futs = [router.submit(pool[i]) for i in range(4)]
    assert all(f.ctx is not None for f in futs)
    router.start()
    for f in futs:
        f.result(timeout=30)
    router.close()
    tracer.close()

    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 4
    assert summary["orphans"] == 0 and summary["broken_chains"] == 0
    for f in futs:
        t = traces[f.ctx.trace_id]
        closure = t.root_closure()
        assert closure is not None and closure["name"] == "fleet:e2e"
        names = {r.get("name") for r in t.records}
        assert "fleet:dispatch" in names  # the router hop
        assert "serve:queue-wait" in names  # the replica-side child
        # every child's parent is the ONE root span the router minted
        assert all(r["parent"] == f.ctx.span_id for r in t.records
                   if r.get("parent") is not None)
        linked_names = {r.get("name") for r in t.linked}
        assert {"serve:dispatch", "serve:device-wait",
                "serve:d2h"} <= linked_names
    # fan-in: the 4 requests were co-batched (paused fleet, bucket 4),
    # so ONE dispatch span links all member traces
    computes = [r for t in traces.values() for r in t.linked
                if r.get("name") == "serve:dispatch"]
    assert any(len(r.get("links", [])) == 4 for r in computes)


def test_standalone_engine_owns_root_and_closure(tmp_path):
    """Without a router, the engine mints the root at submit and closes
    it with serve:e2e — the standalone serving path is fully traced."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    eng = _sim_engine(tracer)
    pool = _pool(3)
    futs = [eng.submit(img) for img in pool]
    for f in futs:
        f.result(timeout=30)
    assert all(f.ctx is not None for f in futs)
    eng.close()
    tracer.close()
    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 3
    assert summary["orphans"] == 0 and summary["broken_chains"] == 0
    for f in futs:
        closure = traces[f.ctx.trace_id].root_closure()
        assert closure is not None and closure["name"] == "serve:e2e"


def test_redispatch_hop_visible_and_chain_complete(tmp_path):
    """A canned fleet:replica worker-death mid-burst: every acknowledged
    request still reassembles into ONE complete causal chain, and the
    re-dispatched requests' traces carry the fleet:redispatch hop plus
    BOTH dispatch hops (the ISSUE 14 acceptance shape)."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    pool = _pool(4)

    def factory(rid, start=True):
        return _sim_engine(tracer, buckets=(1, 2), service_ms=20.0,
                           start=start)

    inj = ChaosInjector(FaultSchedule.parse(
        "fleet:replica=worker-death@30"), tracer=tracer)
    router = FleetRouter(factory, 2, metrics=MetricsRegistry(),
                         tracer=tracer, injector=inj)
    futs = [router.submit(pool[k % 4]) for k in range(40)]
    lost = 0
    for f in futs:
        try:
            f.result(timeout=60)
        except Exception:  # noqa: BLE001 — would be a lost ack
            lost += 1
    st = router.stats()
    router.close()
    tracer.close()
    assert lost == 0 and st["replica_deaths"] == 1
    assert st["redispatched"] >= 1

    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 40
    assert summary["orphans"] == 0, summary["orphan_ids"]
    assert summary["broken_chains"] == 0, summary["broken_detail"]
    assert summary["redispatched_traces"] == st["redispatched"]
    hop = [t for t in traces.values()
           if any(r.get("name") == "fleet:redispatch"
                  for r in t.records)]
    assert len(hop) == st["redispatched"]
    for t in hop:
        assert t.root_closure() is not None
        dispatches = [r for r in t.records
                      if r.get("name") == "fleet:dispatch"]
        assert len(dispatches) >= 2  # the hop is visible: two replicas


_SimCascadeDetections = collections.namedtuple(
    "_SimCascadeDetections", "boxes scores confidence")


class _SimCascadePredict(SimPredict):
    """Sim edge predict with a per-image confidence leaf (mean/255), so
    the router's confidence gate routes deterministically on the image
    bytes — bright pool images resolve at edge, dark ones escalate."""

    def lower(self, variables, spec):
        base = SimPredict.lower(self, variables, spec)

        class _L:
            def compile(self):
                plain = base.compile()

                def run(variables, images):
                    det = plain(variables, images)
                    conf = (np.asarray(images).mean(axis=(1, 2, 3))
                            .astype(np.float32) / 255.0)
                    return _SimCascadeDetections(det.boxes, det.scores,
                                                 conf)

                return run

        return _L()


def test_cascade_two_hop_trace_integrity(tmp_path):
    """ISSUE 16 acceptance shape: an escalated cascade request keeps
    BOTH hops under ONE trace id — the edge dispatch, the
    fleet:escalate hop marker, the quality dispatch and exactly one
    fleet:e2e closure all reassemble into one complete causal chain
    with zero orphans and zero broken chains; edge-resolved requests
    stay single-hop."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    rng = np.random.default_rng(1)

    def img(level):
        jitter = rng.integers(0, 8, (IMSIZE, IMSIZE, 3), dtype=np.uint8)
        return (jitter + level).astype(np.uint8)

    # conf = mean/255: level 200 -> ~0.8 (edge-resolves), 20 -> ~0.09
    pool = [img(200), img(20), img(200), img(20)]

    def factory(rid, start=True):
        svc = _SimCascadePredict(5.0) if rid == 0 else SimPredict(5.0)
        return ServingEngine(svc, {"w": np.zeros(1)},
                             (IMSIZE, IMSIZE, 3), np.uint8,
                             buckets=(1, 2), max_wait_ms=1.0,
                             queue_capacity=64,
                             metrics=MetricsRegistry(), tracer=tracer,
                             start=start)

    router = FleetRouter(factory, 2, replica_tiers=["edge", "quality"],
                         cascade_tenants=["cas"],
                         cascade_tiers=("edge", "quality"),
                         cascade_threshold=0.5,
                         metrics=MetricsRegistry(), tracer=tracer)
    futs = [router.submit(pool[k % 4], tenant="cas") for k in range(8)]
    for f in futs:
        f.result(timeout=60)
    st = router.stats()
    router.close()
    tracer.close()
    assert [f.escalated for f in futs] == [False, True] * 4
    assert st["escalated"] == 4 and st["edge_resolved"] == 4
    assert st["degraded_answers"] == 0 and st["lost"] == 0

    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 8
    assert summary["orphans"] == 0, summary["orphan_ids"]
    assert summary["broken_chains"] == 0, summary["broken_detail"]
    esc, edge = [], []
    for t in traces.values():
        names = [r.get("name") for r in t.records]
        if "fleet:e2e" not in names:
            continue  # step/aux traces
        assert names.count("fleet:e2e") == 1  # completion fires ONCE
        (esc if "fleet:escalate" in names else edge).append(t)
    assert len(esc) == 4 and len(edge) == 4
    for t in esc:
        names = [r.get("name") for r in t.records]
        # both hops visible under the one trace id
        assert names.count("fleet:dispatch") == 2
        ev = next(r for r in t.records
                  if r.get("name") == "fleet:escalate")
        assert ev["meta"]["threshold"] == 0.5
        assert ev["meta"]["confidence"] < 0.5
        assert t.root_closure() is not None
    for t in edge:
        names = [r.get("name") for r in t.records]
        assert names.count("fleet:dispatch") == 1
        assert "fleet:escalate" not in names


def test_shed_and_failure_close_their_traces(tmp_path):
    """Terminal outcomes are closures too: a queue-full shed on a paused
    standalone engine and a retry-exhausted failure both end their
    traces — surfaced errors never read as orphans."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    pool = _pool(1)
    eng = _sim_engine(tracer, buckets=(1, 2), start=False)
    eng._q = __import__("queue").Queue(maxsize=2)
    shed = [eng.submit(pool[0], block=False) for _ in range(4)]
    # partition BEFORE start: sheds complete synchronously inside
    # submit; deciding by done() after start raced the batch completing
    # the admitted pair (pre-existing flake, fixed with ISSUE 15)
    shed_now = [f for f in shed if f.done()]
    admitted = [f for f in shed if not f.done()]
    assert len(shed_now) == 2
    eng.start()
    for f in admitted:
        f.result(timeout=30)
    for f in shed_now:
        with pytest.raises(Exception):
            f.result(timeout=1)
    eng.close()
    tracer.close()
    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 4
    assert summary["orphans"] == 0
    shed_closures = [t for t in traces.values()
                     if (t.root_closure() or {}).get("name")
                     == "serve:shed"]
    assert len(shed_closures) == 2


# ---------------------------------------------------------------------------
# tracing OFF: bit-identity + unchanged device_get count (PR 6 pattern)


REAL_IMSIZE = 64  # the hourglass needs >=64^2 (32^2 over-downsamples)


@pytest.fixture(scope="module")
def real_parts():
    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables
    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, topk=8,
                 conf_th=0.0, nms_th=0.5, imsize=REAL_IMSIZE)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         REAL_IMSIZE)
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    return predict, variables


def test_tracing_off_bit_identity_and_device_get_count(tmp_path,
                                                       count_device_get,
                                                       real_parts):
    """The acceptance pin: tracing ON vs OFF over the REAL predict —
    results byte-identical, and the number of jax.device_get calls (the
    engine's one-per-batch D2H) IDENTICAL. A paused engine + fixed burst
    makes the batching (and therefore the fetch count) deterministic."""
    predict, variables = real_parts
    pool = _pool(4, imsize=REAL_IMSIZE)

    def run(tracer):
        eng = ServingEngine(predict, variables,
                            (REAL_IMSIZE, REAL_IMSIZE, 3),
                            np.uint8, buckets=(1, 2, 4), max_wait_ms=5.0,
                            queue_capacity=16,
                            metrics=MetricsRegistry(), tracer=tracer,
                            start=False)
        with count_device_get() as counter:
            futs = [eng.submit(img) for img in pool]  # one bucket-4 batch
            eng.start()
            rows = [f.result(timeout=60) for f in futs]
            eng.close()
        return counter.calls, rows

    off_calls, off_rows = run(SpanTracer(None))  # disabled tracer
    on_path = str(tmp_path / "spans.jsonl")
    on_tracer = SpanTracer(on_path)
    on_calls, on_rows = run(on_tracer)
    on_tracer.close()

    assert len(on_calls) == len(off_calls), \
        "tracing ON changed the device_get count"
    for a, b in zip(off_rows, on_rows):
        for name in ("boxes", "classes", "scores", "valid"):
            assert np.asarray(getattr(a, name)).tobytes() \
                == np.asarray(getattr(b, name)).tobytes(), \
                "tracing ON changed a result bit"
    # and the ON run really did trace: complete chains on disk
    summary = traceview.analyze(traceview.assemble(read_spans(on_path)))
    assert summary["request_traces"] == 4 and summary["orphans"] == 0


def test_tracing_off_futures_carry_no_context():
    """Disabled tracer => ctx stays None end to end (no id minting on
    the hot path)."""
    eng = _sim_engine(SpanTracer(None))
    fut = eng.submit(_pool(1)[0])
    fut.result(timeout=30)
    eng.close()
    assert fut.ctx is None


# ---------------------------------------------------------------------------
# torn-line tolerance (kill -9 twin) + broken-chain detection


def test_torn_trace_tail_tolerated(tmp_path):
    """A writer killed mid-append tears at most the final line; the
    assembler recovers every complete trace and reports the torn
    request as an ORPHAN (its closure was the torn record) — a hard
    error, not a crash."""
    path = str(tmp_path / "spans.jsonl")
    tracer = SpanTracer(path)
    trace.reset_ids(5)
    done = trace.new_root()
    tracer.record("serve:queue-wait", 0.001, ctx=done.child())
    tracer.record("serve:e2e", 0.01, ctx=done)
    torn = trace.new_root()
    tracer.record("serve:queue-wait", 0.001, ctx=torn.child())
    tracer.close()
    with open(path, "a") as f:
        f.write(json.dumps({"kind": "span", "name": "serve:e2e",
                            "trace": torn.trace_id,
                            "span": torn.span_id,
                            "dur_s": 0.01})[:40])  # torn mid-record
    traces = traceview.assemble(read_spans(path))
    summary = traceview.analyze(traces)
    assert summary["request_traces"] == 2
    assert summary["closed"] == 1
    assert summary["orphan_ids"] == [torn.trace_id]
    trace.reset_ids()


def test_broken_chain_detected_as_hard_error():
    recs = [
        {"kind": "span", "name": "serve:queue-wait", "t": 1.0, "t0": 1.0,
         "dur_s": 0.001, "trace": "T", "span": "c1",
         "parent": "never-written"},
        {"kind": "span", "name": "serve:e2e", "t": 1.0, "t0": 1.0,
         "dur_s": 0.01, "trace": "T", "span": "root"},
    ]
    summary = traceview.analyze(traceview.assemble(recs))
    assert summary["broken_chains"] == 1
    assert summary["broken_detail"][0]["parent"] == "never-written"
    assert summary["complete"] == 0  # broken => not complete


# ---------------------------------------------------------------------------
# cross-process join over two REAL worker span logs


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow  # 50 s measured (warm cache, idle box): two real
# 2-process ddp workers with a model compile per rank — the smoke tier
# already carries one 2-process rendezvous canary (test_distributed);
# this adds the span-log join assertions on the same harness, so it
# rides the slow tier per the 870 s tier-1 budget rule
def test_cross_process_step_trace_join(tmp_path):
    """Two REAL distributed_worker ranks, each writing its own span log
    ($OBS_SPAN_LOG per rank): the per-step trace id derives from the
    (run, step) alone, so the two logs assemble into ONE step trace with
    both ranks' scale:step spans — the cross-process causality join that
    disjoint per-rank logs never allowed."""
    port = _free_port()
    env_base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    logs = [str(tmp_path / ("rank%d.jsonl" % r)) for r in range(2)]
    procs = []
    for rank in range(2):
        env = dict(env_base, OBS_SPAN_LOG=logs[rank])
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(rank), "2", str(port),
             str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, "worker failed:\n%s" % out

    traces = traceview.assemble_logs(logs)
    summary = traceview.analyze(traces)
    assert summary["step_traces"] == 1
    assert summary["step_ranks"] == [0, 1]
    step_trace = next(t for t in traces.values() if t.is_step)
    steps = [r for r in step_trace.records
             if r.get("name") == "scale:step"]
    assert sorted(r["rank"] for r in steps) == [0, 1]
    assert len({r["pid"] for r in steps}) == 2  # really two processes
    # rank tags ride EVERY record of each per-rank log (bind contract)
    for rank, log_path in enumerate(logs):
        recs = [r for r in read_spans(log_path)
                if r.get("kind") in ("span", "event")]
        assert recs and all(r.get("rank") == rank for r in recs)
