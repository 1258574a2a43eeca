"""Serving-engine tests (ISSUE 8): the bucketed AOT continuous-batching
engine must be bit-identical to one-shot predict for ANY request stream,
never recompile after construction, and shed deterministically under
admission control. All CPU; the tiny predict fixture is module-scoped so
the per-bucket AOT compiles happen once.
"""

import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.predict import \
    make_predict_fn  # noqa: E402
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    ChaosInjector, FaultEvent, FaultSchedule)
from real_time_helmet_detection_tpu.serving import (  # noqa: E402
    DEFAULT_BUCKETS, DEGRADED, SERVING, EngineClosedError, FetchHungError,
    ServingEngine, SheddedError, resolve_buckets)
from real_time_helmet_detection_tpu.train import init_variables  # noqa: E402

IMSIZE = 64
BUCKETS = (1, 2, 4)


@pytest.fixture(scope="module")
def parts():
    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, topk=16,
                 conf_th=0.0, nms_th=0.5, imsize=IMSIZE)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0), IMSIZE)
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    rng = np.random.default_rng(3)
    pool = [rng.integers(0, 256, (IMSIZE, IMSIZE, 3), dtype=np.uint8)
            for _ in range(10)]
    # one-shot oracle rows at batch 1: dispatch all, one batched fetch
    pending = [predict(variables, img[None]) for img in pool]
    oracle = [type(d)(*(np.asarray(leaf[0]) for leaf in d))
              for d in jax.device_get(pending)]
    return cfg, predict, variables, pool, oracle


@pytest.fixture(scope="module")
def engine(parts):
    _, predict, variables, _, _ = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=2.0, depth=2,
                        queue_capacity=64)
    yield eng
    eng.close()


def _rows_equal(a, b) -> bool:
    return all(np.array_equal(getattr(a, n), getattr(b, n))
               for n in ("boxes", "classes", "scores", "valid"))


def test_any_stream_bit_identical_to_one_shot(parts, engine):
    """The acceptance property: ANY request stream — sizes, arrival
    order, interleaving, pacing — yields detections bit-identical to the
    one-shot predict of each image (property-style over seeded random
    streams; per-image independence means bucket choice and co-batched
    neighbors must not change a single bit)."""
    _, _, _, pool, oracle = parts
    rng = np.random.default_rng(17)
    for stream in range(3):
        futs = []
        for _ in range(6):
            k = int(rng.integers(1, 7))  # burst size spanning buckets
            for i in rng.integers(0, len(pool), k):
                futs.append((int(i), engine.submit(pool[int(i)])))
            if rng.random() < 0.5:
                time.sleep(float(rng.uniform(0, 0.004)))  # pacing jitter
        for i, fut in futs:
            assert _rows_equal(fut.result(timeout=60), oracle[i]), \
                "stream %d: request for image %d diverged" % (stream, i)


def test_partial_batch_takes_smallest_bucket(parts):
    _, predict, variables, pool, oracle = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=50.0, depth=1,
                        queue_capacity=16, start=False)
    futs = [eng.submit(pool[i]) for i in range(3)]
    eng.start()
    rows = [f.result(timeout=60) for f in futs]
    st = eng.stats()
    eng.close()
    # 3 requests coalesce into ONE bucket-4 batch: 1 padded slot
    assert st["batches"] == 1
    assert st["padded_slots"] == 1
    assert all(_rows_equal(r, oracle[i]) for i, r in enumerate(rows))


def test_zero_recompiles_after_warmup(parts, engine):
    """Bucket selection NEVER recompiles: after construction (all buckets
    AOT-compiled) a stream spanning every bucket size fires zero
    backend-compile events (the PR 6 recompile listener is the pin)."""
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    _, _, _, pool, _ = parts
    engine.predict_many(pool[:4])  # touch every bucket-sized path once
    counter = install_recompile_counter()
    for n in (1, 2, 3, 4, 1):
        [f.result(timeout=60) for f in
         [engine.submit(pool[i]) for i in range(n)]]
    assert counter.count == 0


def test_queue_full_sheds_immediately(parts):
    _, predict, variables, pool, _ = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2), max_wait_ms=0.0,
                        queue_capacity=2, start=False)
    futs = [eng.submit(pool[0], block=False) for _ in range(5)]
    shed = [f for f in futs if f.done()]
    assert len(shed) == 3
    for f in shed:
        with pytest.raises(SheddedError):
            f.result()
    eng.start()
    served = [f for f in futs if f not in shed]
    assert all(f.result(timeout=60) is not None for f in served)
    st = eng.stats()
    eng.close()
    assert st["shed_queue_full"] == 3
    assert st["completed"] == 2


def test_deadline_shed_before_dispatch(parts):
    _, predict, variables, pool, _ = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2), max_wait_ms=0.0,
                        queue_capacity=8, start=False)
    late = eng.submit(pool[0], deadline_s=0.001)
    ok = eng.submit(pool[1])  # no deadline: must still be served
    time.sleep(0.05)
    eng.start()
    with pytest.raises(SheddedError):
        late.result(timeout=60)
    assert ok.result(timeout=60) is not None
    st = eng.stats()
    eng.close()
    assert st["shed_deadline"] == 1 and st["completed"] == 1


def test_close_fails_pending_and_rejects_new(parts):
    _, predict, variables, pool, _ = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1,), max_wait_ms=0.0,
                        queue_capacity=4, start=False)
    fut = eng.submit(pool[0])
    eng.close()
    with pytest.raises(EngineClosedError):
        fut.result(timeout=10)
    with pytest.raises(EngineClosedError):
        eng.submit(pool[0])


def test_submit_validates_shape_and_dtype(parts, engine):
    with pytest.raises(ValueError):
        engine.submit(np.zeros((IMSIZE, IMSIZE, 3), np.float32))
    with pytest.raises(ValueError):
        engine.submit(np.zeros((32, 32, 3), np.uint8))


def test_spans_cover_the_taxonomy(parts, tmp_path):
    """The engine's flight-recorder contract: lower + compile spans per
    bucket at construction, then queue-wait/batch-form/h2d/dispatch/
    device-wait/d2h per batch and e2e per request ($OBS_SPAN_LOG honored
    via maybe_tracer)."""
    from real_time_helmet_detection_tpu.obs.spans import (maybe_tracer,
                                                          read_spans)
    _, predict, variables, pool, _ = parts
    path = str(tmp_path / "serve_spans.jsonl")
    tracer = maybe_tracer(path)
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2), max_wait_ms=1.0,
                        queue_capacity=8, tracer=tracer)
    eng.predict_many(pool[:3])
    eng.close()
    tracer.close()
    recs = read_spans(path)
    names = {r.get("name") for r in recs}
    assert {"serve:lower", "serve:compile", "serve:queue-wait",
            "serve:batch-form", "serve:h2d", "serve:dispatch",
            "serve:device-wait", "serve:d2h", "serve:e2e"} <= names
    assert "serve:compute" not in names
    assert sum(1 for r in recs if r.get("name") == "serve:compile") == 2
    assert sum(1 for r in recs if r.get("name") == "serve:e2e") == 3


def test_resolve_buckets_contract():
    assert resolve_buckets(Config()) == tuple(DEFAULT_BUCKETS)
    assert resolve_buckets(Config(serve_buckets=[8, 2, 2])) == (2, 8)
    with pytest.raises(ValueError):
        Config(serve_buckets=[0, 2])
    with pytest.raises(ValueError):
        Config(serve_buckets=[])


def test_injected_dispatch_fault_retries_bit_identical(parts):
    """ISSUE 9 in-flight recovery: an injected device-loss at dispatch
    requeues the batch's requests; the retry reuses the SAME AOT
    executable, so results stay bit-identical to one-shot predict and
    zero acknowledged requests are lost."""
    _, predict, variables, pool, oracle = parts
    inj = ChaosInjector(FaultSchedule.parse("serve:dispatch=device-loss@2"))
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=1.0, depth=2,
                        queue_capacity=32, max_retries=2, injector=inj)
    futs = [(i, eng.submit(pool[i])) for i in range(6)]
    rows = [(i, f.result(timeout=60)) for i, f in futs]
    st = eng.stats()
    health = eng.health()
    eng.close()
    assert all(_rows_equal(r, oracle[i]) for i, r in rows)
    assert len(inj.fired) == 1 and inj.fired[0].kind == "device-loss"
    assert st["failed"] == 0 and st["completed"] == 6
    assert st["retried"] >= 1 and st["requeued_batches"] == 1
    assert health["stats"]["failed_batches"] == 1


def test_hung_fetch_watchdog_requeues(parts):
    """An injected hung fetch (sleep past the watchdog) is detected, the
    batch requeued, and the retried requests complete bit-identically —
    the never-completing D2H as a tested code path."""
    _, predict, variables, pool, oracle = parts
    # hang_s must exceed the watchdog for the timeout to fire
    inj = ChaosInjector(FaultSchedule([
        FaultEvent("serve:fetch", "hung-fetch", 1, {"hang_s": 1.0})]))
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=1.0, depth=2,
                        queue_capacity=32, max_retries=2,
                        hang_timeout_s=0.15, injector=inj)
    futs = [(i, eng.submit(pool[i])) for i in range(3)]
    rows = [(i, f.result(timeout=60)) for i, f in futs]
    st = eng.stats()
    eng.close()
    assert all(_rows_equal(r, oracle[i]) for i, r in rows)
    assert st["hung_batches"] == 1
    assert st["failed"] == 0 and st["completed"] == 3


def test_retry_budget_exhaustion_surfaces_error(parts):
    """Budget exhausted => the error surfaces on the future (never a
    silent loss), and the lost request is accounted in stats."""
    _, predict, variables, pool, _ = parts
    spec = ",".join("serve:dispatch=device-loss@%d" % n for n in (1, 2, 3))
    inj = ChaosInjector(FaultSchedule.parse(spec))
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1,), max_wait_ms=0.0, depth=1,
                        queue_capacity=8, max_retries=2, injector=inj)
    fut = eng.submit(pool[0])
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        fut.result(timeout=60)
    st = eng.stats()
    eng.close()
    assert st["failed"] == 1 and st["retried"] == 2


def test_state_machine_degraded_and_recovery(parts):
    """SERVING -> DEGRADED on a batch failure, back to SERVING after
    `recover_after` consecutive healthy batches; health() snapshots it."""
    _, predict, variables, pool, _ = parts
    inj = ChaosInjector(FaultSchedule.parse("serve:dispatch=device-loss@1"))
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1,), max_wait_ms=0.0, depth=1,
                        queue_capacity=8, max_retries=1, recover_after=2,
                        injector=inj)
    assert eng.state == SERVING
    eng.submit(pool[0]).result(timeout=60)  # fault -> retry succeeds
    assert eng.state == DEGRADED  # one healthy batch < recover_after
    eng.submit(pool[1]).result(timeout=60)
    assert eng.drain(10.0)
    assert eng.state == SERVING
    h = eng.health()
    eng.close()
    assert h["state"] == SERVING and h["consecutive_failures"] == 0
    assert h["queued"] == 0 and h["inflight_batches"] == 0
    assert h["stats"]["failed_batches"] == 1
    assert eng.health()["state"] == "closed"


def test_hot_reload_swaps_weights_without_dropping(parts):
    """Graceful drain + hot reload: requests before the swap match the
    old-weight oracle, requests after match the NEW weights' one-shot
    predict, zero recompiles, zero dropped requests."""
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    _, predict, variables, pool, oracle = parts
    # a distinct checkpoint: perturb one conv kernel
    new_vars = jax.tree.map(lambda x: x, variables)
    new_vars = jax.device_get(new_vars)
    leaves, treedef = jax.tree.flatten(new_vars)
    leaves = [np.asarray(x) for x in leaves]
    leaves[0] = leaves[0] + 0.25
    new_vars = jax.tree.unflatten(treedef, leaves)
    pending = [predict(new_vars, img[None]) for img in pool[:4]]
    new_oracle = [type(d)(*(np.asarray(leaf[0]) for leaf in d))
                  for d in jax.device_get(pending)]

    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=1.0, depth=2,
                        queue_capacity=32)
    before = [(i, eng.submit(pool[i])) for i in range(4)]
    counter = install_recompile_counter()
    eng.reload(new_vars, timeout_s=30.0)
    after = [(i, eng.submit(pool[i])) for i in range(4)]
    rows_before = [(i, f.result(timeout=60)) for i, f in before]
    rows_after = [(i, f.result(timeout=60)) for i, f in after]
    st = eng.stats()
    eng.close()
    assert counter.count == 0  # the swap never recompiles a bucket
    assert all(_rows_equal(r, oracle[i]) for i, r in rows_before)
    assert all(_rows_equal(r, new_oracle[i]) for i, r in rows_after)
    assert any(not _rows_equal(a, b) for (_, a), (_, b)
               in zip(rows_before, rows_after))  # the swap actually took
    assert st["reloads"] == 1 and st["failed"] == 0
    assert st["completed"] == 8


def test_recovery_spans_land_in_flight_recorder(parts, tmp_path):
    """fault:* injections and recover:* evidence are joined later by
    obs_report; the engine must emit them ($OBS_SPAN_LOG contract)."""
    from real_time_helmet_detection_tpu.obs.spans import (maybe_tracer,
                                                          read_spans)
    _, predict, variables, pool, _ = parts
    path = str(tmp_path / "chaos_spans.jsonl")
    tracer = maybe_tracer(path)
    inj = ChaosInjector(FaultSchedule.parse(
        "serve:dispatch=device-loss@1,serve:dispatch=device-loss@2"),
        tracer=tracer)
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1,), max_wait_ms=0.0, depth=1,
                        queue_capacity=8, max_retries=1, tracer=tracer,
                        injector=inj)
    with pytest.raises(RuntimeError):
        eng.submit(pool[0]).result(timeout=60)
    eng.close()
    tracer.close()
    recs = read_spans(path)
    names = [r.get("name") for r in recs]
    assert names.count("fault:device-loss") == 2
    assert names.count("recover:requeue") == 2
    assert "recover:retry-exhausted" in names
    states = [r["meta"] for r in recs if r.get("name") == "serve:state"]
    assert {"from": "serving", "to": "degraded"} in states


def test_results_in_submission_order_across_batches(parts):
    """FIFO completion: per-request futures complete in dispatch order
    even when requests span several partial batches (the eval driver
    drains its pending deque head-first and relies on this)."""
    _, predict, variables, pool, oracle = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=BUCKETS, max_wait_ms=0.5, depth=2,
                        queue_capacity=64)
    futs = [eng.submit(pool[i % len(pool)]) for i in range(11)]
    rows = [f.result(timeout=60) for f in futs]
    eng.close()
    assert all(_rows_equal(r, oracle[i % len(pool)])
               for i, r in enumerate(rows))


# ---------------------------------------------------------------------------
# the staging ring (ISSUE 30): batches are formed into reused host buffers

ECHO_W = 8      # an int32 row of 8: the token-row kind of payload
ECHO_DEPTH = 2


@pytest.fixture(scope="module")
def echo():
    """A program that answers every request its own input row and the
    column sums of the WHOLE batch the device was handed, padding
    included: what a stale or rewritten staging row would show in."""
    from typing import NamedTuple

    class Echo(NamedTuple):
        row: jax.Array
        batch_sum: jax.Array

    @jax.jit
    def program(variables, x):
        total = x.sum(axis=0) + variables
        return Echo(x, jax.numpy.broadcast_to(total, x.shape))

    return program, np.int32(0)


def _echo_engine(echo, **kw):
    program, variables = echo
    kw.setdefault("max_wait_ms", 1.0)
    return ServingEngine(program, variables, (ECHO_W,), np.int32,
                         buckets=(2, 4), depth=ECHO_DEPTH,
                         queue_capacity=128, **kw)


def _echo_payloads(n):
    return [np.arange(ECHO_W, dtype=np.int32) + 1000 * (j + 1)
            for j in range(n)]


def test_staging_stale_rows_never_reach_the_device(echo):
    """Padding stays zeros though the buffer is reused: after a full
    bucket, the padded rows of smaller batches formed into the same buffer
    are cleared, and no answer ever holds a row of another batch."""
    eng = _echo_engine(echo, max_wait_ms=20.0)
    bit = 0
    for size in (4, 1, 3, 1, 4, 2, 1, 2, 3):
        # request j's row is 2^j everywhere, so the batch's column sum
        # names its members: a stale row would add an earlier request's bit
        rows = [np.full(ECHO_W, 1 << (bit + j), np.int32)
                for j in range(size)]
        mine = sum(1 << (bit + j) for j in range(size))
        bit += size
        # one batch at a time: each is formed into the buffer the last one
        # handed back, whose rows that batch wrote
        for row, ans in zip(rows, [f.result(timeout=60) for f in
                                   [eng.submit(r) for r in rows]]):
            assert np.array_equal(ans.row, row)
            seen = int(ans.batch_sum[0])
            assert np.all(ans.batch_sum == seen)
            assert seen & ~mine == 0, \
                "a padded row held an earlier batch's frame (bits %x)" % seen
    assert eng.stats()["staging_allocated"] == 1  # the reuse was exercised
    eng.close()
    # a backlog: full batches in submission order, several in flight; a
    # buffer rewritten while the device still read it would show here
    eng = _echo_engine(echo, start=False)
    rows = _echo_payloads(4 * 5 * (ECHO_DEPTH + 2))
    futs = [eng.submit(r) for r in rows]
    eng.start()
    answers = [f.result(timeout=60) for f in futs]
    eng.close()
    for j, (row, ans) in enumerate(zip(rows, answers)):
        assert np.array_equal(ans.row, row)
        first = j - j % 4
        assert np.array_equal(ans.batch_sum, sum(rows[first:first + 4]))


def test_staging_ring_is_bounded_and_engages(echo):
    from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
    eng = _echo_engine(echo, start=False, metrics=MetricsRegistry())
    rows = _echo_payloads(4 * 20)
    futs = [eng.submit(r) for r in rows]
    eng.start()
    assert all(np.array_equal(f.result(timeout=60).row, r)
               for f, r in zip(futs, rows))
    st = eng.stats()
    eng.close()
    assert st["batches"] == 20
    assert 1 <= st["staging_allocated"] <= ECHO_DEPTH + 2
    assert st["staging_reused"] == st["batches"] - st["staging_allocated"]
    # the registry's counters (what a metric would read) say the same
    for key in ("staging_reused", "staging_allocated"):
        assert eng.metrics.counter("serve." + key).value == st[key]


def test_staging_failure_paths_give_the_buffer_back(echo):
    """10 batches failed at dispatch and 10 at fetch, requeued and served:
    zero lost acks, and the failed batches' buffers came back (none leaked
    to a fresh allocation)."""
    spec = ",".join(["serve:dispatch=device-loss@%d" % n
                     for n in range(2, 40, 4)]
                    + ["serve:fetch=device-loss@%d" % n
                       for n in range(3, 33, 3)])
    inj = ChaosInjector(FaultSchedule.parse(spec))
    eng = _echo_engine(echo, start=False, max_retries=25, injector=inj)
    rows = _echo_payloads(4 * 30)
    futs = [eng.submit(r) for r in rows]
    eng.start()
    answers = [f.result(timeout=60) for f in futs]
    st = eng.stats()
    eng.close()
    assert all(np.array_equal(a.row, r) for a, r in zip(answers, rows))
    assert len(inj.fired) == 20
    assert st["failed_batches"] == 20 and st["failed"] == 0
    assert st["completed"] == len(rows)
    assert st["staging_allocated"] <= ECHO_DEPTH + 2


def test_staging_abandoned_buffer_is_not_reused(echo, monkeypatch):
    """A batch the hang watchdog abandons may still be read by the device:
    its buffer never carries a later batch."""
    inj = ChaosInjector(FaultSchedule([
        FaultEvent("serve:fetch", "hung-fetch", 1, {"hang_s": 1.0})]))
    eng = _echo_engine(echo, max_retries=2, hang_timeout_s=0.15,
                       injector=inj)
    bases = []  # the staging buffer behind each dispatch, kept alive so
    # that no later buffer can take a dead one's place in memory
    real_put = jax.device_put

    def spy(x, *a, **k):
        if isinstance(x, np.ndarray) and x.shape[1:] == (ECHO_W,):
            bases.append(x if x.base is None else x.base)
        return real_put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    rows = _echo_payloads(2 * 8)
    answers = []
    for k in range(0, len(rows), 2):  # a batch at a time
        futs = [eng.submit(r) for r in rows[k:k + 2]]
        answers += [f.result(timeout=60) for f in futs]
    st = eng.stats()
    eng.close()
    assert all(np.array_equal(a.row, r) for a, r in zip(answers, rows))
    assert st["hung_batches"] == 1 and st["failed"] == 0
    assert len(bases) >= 9  # 8 batches and the abandoned one's retry
    assert all(b is not bases[0] for b in bases[1:])
    assert st["staging_allocated"] >= 2
