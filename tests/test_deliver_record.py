"""One flight recorder whatever tracer the engine is handed, one
`serve:deliver` record a batch written before its answers resolve, and the
fused attention kernel's visits counted a row (ISSUE 38). All CPU, toy
sizes. (The reference has no serving engine, tracer or attention: no
analogue.)"""

import os
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_decoder as toy  # noqa: E402

from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.obs import spans  # noqa: E402
from real_time_helmet_detection_tpu.obs.metrics import \
    MetricsRegistry  # noqa: E402
from real_time_helmet_detection_tpu.ops import attention as att  # noqa: E402
from real_time_helmet_detection_tpu.ops.pallas import (  # noqa: E402
    attention as fused)
from real_time_helmet_detection_tpu.predict import \
    generation_counters  # noqa: E402
from real_time_helmet_detection_tpu.serving import ServingEngine  # noqa: E402


class NarrowTracer:
    """What the benchmark's stand-in keeps: names and starts, no meta, no
    start on `record`, no events."""
    enabled = False

    def __init__(self):
        self.records = []

    def span(self, name, ctx=None, links=None, **meta):
        tracer = self

        class _CM:
            dur_s = None

            def __enter__(self):
                self.t0 = time.monotonic()
                return self

            def __exit__(self, *exc):
                self.dur_s = time.monotonic() - self.t0
                tracer.records.append((name, self.t0, self.dur_s))

        return _CM()

    def record(self, name, dur_s, ctx=None, links=None, **meta):
        self.records.append((name, None, float(dur_s)))

    def event(self, name, ctx=None, links=None, **meta):
        pass


class Answer(NamedTuple):
    doubled: jax.Array


def _engine(tracer, registry, row_counters, buckets=(2,)):
    return ServingEngine(jax.jit(lambda variables, x: Answer(x * 2)), {},
                         (4,), np.int32, buckets=buckets, max_wait_ms=50.0,
                         metrics=registry, tracer=tracer,
                         row_counters=row_counters)


def _rows_counted(rows):
    return {"gen.requests": len(rows.doubled),
            "gen.prompt_tokens": int(np.sum(rows.doubled[:, 0]) // 2)}


# ---- (a) the tee ------------------------------------------------------------------

def test_a_tee_gives_a_narrow_tracer_what_it_saw_and_the_ring_the_same():
    spans.reset_ring()
    narrow = NarrowTracer()
    tee = spans.with_ring(narrow)
    assert tee is not narrow and tee.enabled is False
    t_start = time.monotonic()
    with tee.span("serve:h2d", b=4) as sp:
        pass
    assert sp.dur_s is not None  # the narrow tracer's own span object
    tee.record("serve:queue-wait", 0.25, b=4)
    tee.record("serve:e2e", 0.5, t0=t_start, b=4)
    tee.event("serve:shed", reason="deadline")
    assert [r[0] for r in narrow.records] == [
        "serve:h2d", "serve:queue-wait", "serve:e2e"]
    ring = spans.default_tracer().snapshot(since=0.0)
    assert [r[0] for r in ring] == ["serve:h2d", "serve:queue-wait",
                                    "serve:e2e", "serve:shed"]
    for name, t0, dur, meta in ring:
        assert t0 >= t_start - 0.25 and dur >= 0.0
    assert ring[0][3] == {"b": 4} and ring[1][3] == {"b": 4}
    assert ring[2][1] == t_start and ring[3][3] == {"reason": "deadline"}
    assert ring[1][1] == pytest.approx(time.monotonic() - 0.25, abs=0.2)
    spans.reset_ring()


@pytest.mark.parametrize("make,teed", [
    (lambda tmp: spans.default_tracer(), False),
    (lambda tmp: spans.SpanTracer(str(tmp / "spans.jsonl")), False),
    (lambda tmp: spans.SpanTracer(None, ring=spans.SpanRing(8)), True),
    (lambda tmp: NarrowTracer(), True),
])
def test_a_tracer_that_writes_the_ring_is_used_as_it_is(tmp_path, make,
                                                        teed):
    """Nothing is recorded twice: a tracer over the process ring is itself;
    one over a ring of its own, or none, is teed."""
    tracer = make(tmp_path)
    assert (spans.with_ring(tracer) is not tracer) == teed


def test_the_engine_writes_the_ring_beside_a_narrow_tracer():
    """The narrow tracer still sees every name it saw; the ring holds the
    same spans with their meta and a start."""
    spans.reset_ring()
    narrow, registry = NarrowTracer(), MetricsRegistry()
    t_start = time.monotonic()
    with _engine(narrow, registry, _rows_counted) as eng:
        got = [f.result(timeout=60) for f in
               [eng.submit(np.full((4,), i, np.int32)) for i in range(4)]]
    assert [int(g.doubled[0]) for g in got] == [0, 2, 4, 6]
    ring = spans.default_tracer().snapshot(since=t_start)
    seen = {r[0] for r in narrow.records}
    assert {"serve:lower", "serve:compile", "serve:queue-wait",
            "serve:batch-form", "serve:h2d", "serve:dispatch",
            "serve:device-wait", "serve:d2h", "serve:deliver",
            "serve:e2e"} <= seen
    assert seen <= {r[0] for r in ring}
    for name in seen:
        assert sum(r[0] == name for r in ring) \
            == sum(r[0] == name for r in narrow.records), name
    by_name = {}
    for name, t0, dur, meta in ring:
        by_name.setdefault(name, []).append((t0, dur, meta))
    for name in ("serve:h2d", "serve:dispatch", "serve:device-wait",
                 "serve:d2h", "serve:e2e"):
        assert all(meta["b"] == 2 and t0 >= t_start
                   for t0, _, meta in by_name[name]), name
    deliver = by_name["serve:deliver"]
    assert sum(meta["n"] for _, _, meta in deliver) == 4
    assert sum(meta["counters"]["gen.requests"]
               for _, _, meta in deliver) == 4
    spans.reset_ring()


# ---- (b) counts before answers ----------------------------------------------------

@pytest.mark.parametrize("narrow", [False, True])
def test_a_batchs_counts_are_in_the_registry_before_its_answers(narrow):
    """A future's done-callback reads the registry: its own batch's `gen.*`
    increments are there already; and the batch's `serve:deliver` record
    started before the answer resolved."""
    spans.reset_ring()
    registry = MetricsRegistry()
    seen = []

    def look(i, fut):
        seen.append((i, registry.counter("gen.requests").value,
                     registry.counter("serve.completed").value,
                     time.monotonic()))

    t_start = time.monotonic()
    with _engine(NarrowTracer() if narrow else None, registry,
                 _rows_counted) as eng:
        for batch in range(3):
            futs = [eng.submit(np.full((4,), 2 * batch + j, np.int32))
                    for j in range(2)]
            for j, f in enumerate(futs):
                f.add_done_callback(lambda f, i=2 * batch + j: look(i, f))
            for f in futs:
                f.result(timeout=60)
    assert sorted(i for i, *_ in seen) == list(range(6))
    for i, requests, completed, _ in seen:
        assert requests >= 2 * (i // 2 + 1), (i, requests)
        assert completed >= 2 * (i // 2 + 1), (i, completed)
    starts = sorted(t0 for name, t0, _, _ in
                    spans.default_tracer().snapshot(since=t_start)
                    if name == "serve:deliver")
    assert len(starts) == 3
    for i, *_, t_seen in seen:
        assert starts[i // 2] < t_seen
    spans.reset_ring()


def test_a_callback_that_raises_leaves_its_batchs_record_without_counts():
    spans.reset_ring()
    registry, calls = MetricsRegistry(), []

    def counters(rows):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("the first batch's callback fails")
        return {"gen.requests": len(rows.doubled)}

    t_start = time.monotonic()
    with _engine(None, registry, counters, buckets=(1,)) as eng:
        for i in range(2):
            eng.submit(np.full((4,), i, np.int32)).result(timeout=60)
    metas = [m for n, _, _, m in spans.default_tracer().snapshot(
        since=t_start) if n == "serve:deliver"]
    assert [m["counters"] for m in metas] == [{}, {"gen.requests": 1}]
    assert registry.counter("serve.row_counter_errors").value == 1
    spans.reset_ring()


# ---- (c) the fused kernel's visits a row ----------------------------------------

@pytest.mark.parametrize("total,bq,bk", [(16, 8, 16), (64, 8, 16),
                                         (64, 16, 32), (48, 8, 48)])
@pytest.mark.parametrize("edge", ["under", "at", "over"])
def test_visits_run_counts_the_kernels_pairs_of_live_q_blocks(total, bq, bk,
                                                             edge):
    """A row of `length` does work in the visits of its live q blocks (i * bq
    < length) of the kernel's own table: lengths under, at and over each
    block edge; `fused_visits` reads the table at the kernel's key block."""
    q_of, _ = fused.visits(total, bq, bk)
    table = fused.visits_run(total, bq, bk)
    assert table[0] == 0 and table[-1] == len(q_of)
    count = jax.jit(lambda r: att.fused_visits(total, bq, r))
    for edge_at in range(bq, total + 1, bq):
        length = {"under": edge_at - 1, "at": edge_at,
                  "over": min(total, edge_at + 1)}[edge]
        ran = -(-length // bq)
        want = int(np.sum(q_of * bq < length))
        assert int(table[ran]) == want, (length, ran)
        if bk == fused.key_block(total):
            assert int(count(jnp.int32(ran))) == want


@pytest.fixture(scope="module")
def fields():
    return toy.bench_toy.toy_fields("dots3-note-prev-ep8-l5")


def test_a_generation_counts_the_fused_kernels_visits(fields, monkeypatch):
    """Through the toy program with the kernel forced (the interpreter): a
    row's `attn_fused_visits` is its live q blocks' visits on each fused
    (full) layer; with the XLA path, zeros; and `gen.attn_fused_visits`
    sums the rows."""
    spec = dec.DecoderSpec.from_mapping(toy._config(fields).decoder)
    table = fused.visits_run(toy.P_MAX, spec.q_block,
                             fused.key_block(toy.P_MAX))
    _, plain = toy._generate(fields)
    assert [int(s.attn_fused_visits) for s in plain] == [0] * len(plain)
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    rows, served = toy._generate(fields)
    want = [spec.full_layers * int(table[-(-n // spec.q_block)])
            for n in toy.LENGTHS]
    assert [int(s.attn_fused_visits) for s in served] == want
    assert min(want) > 0
    stacked = type(served[0])(*(np.stack(leaf) for leaf in zip(*served)))
    assert generation_counters(toy.P_MAX)(stacked)[
        "gen.attn_fused_visits"] == sum(want)


def test_a_prefill_shorter_than_a_q_block_counts_no_visits(fields,
                                                           monkeypatch):
    """`init` runs a prefill of 8 tokens where the published q block is
    512 (the drivers' set-up): no whole q block, so no fused kernel and no
    visit table, even where the kernel compiles."""
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    model = dec.LatentMoEDecoder(dec.DecoderSpec.from_mapping(
        toy._config(dict(fields, attn_q_block=16)).decoder))
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.key(0), tokens)
    _, cache = model.apply(params, tokens, jnp.array([8, 3], jnp.int32),
                           method="prefill")
    assert cache["counts"]["attn_fused_visits"].tolist() == [0, 0]
    assert cache["counts"]["q_blocks_fused"].tolist() == [0, 0]
