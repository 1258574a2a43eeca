"""Chaos property suite (ISSUE 9): seeded random fault schedules against
the self-healing serving engine and the sentinel train loop.

The acceptance invariants under deterministic injected failure:

* serving — every ACKNOWLEDGED request either completes bit-identical to
  its one-shot predict or is explicitly shed; zero requests are lost to
  an injected device-loss / hung fetch / slow batch;
* training — an injected run of NaN batches triggers the sentinel's
  rollback to the last good checkpoint, and the healed run's losses and
  final weights are BIT-identical to a clean run restarted from that
  same checkpoint.

Every test runs under a hard SIGALRM (the test_supervisor.py pattern): a
recovery path that hangs is itself a failed recovery. All CPU, smoke
tier. The reference has no fault injection or recovery of any kind (ref
train.py:190-199 — its only recovery is a manual restart).
"""

import os
import signal

import numpy as np
import pytest

import jax

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.data import make_synthetic_voc
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.predict import make_predict_fn
from real_time_helmet_detection_tpu.runtime import (ChaosInjector,
                                                    FaultEvent,
                                                    FaultSchedule,
                                                    maybe_injector)
from real_time_helmet_detection_tpu.serving import ServingEngine
from real_time_helmet_detection_tpu.train import init_variables

TIMEOUT_S = 600  # hard per-test ceiling — a hung recovery IS a failure

IMSIZE = 64


@pytest.fixture(autouse=True)
def _hard_timeout():
    def _fire(signum, frame):
        raise RuntimeError(
            "chaos test exceeded the %ds hard timeout — a recovery path "
            "hung (watchdog/retry/rollback did not fire?)" % TIMEOUT_S)

    old = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


# ---------------------------------------------------------------------------
# the fault layer itself: seeded, replayable, fire-once


def test_schedule_spec_roundtrip_and_seeded_determinism():
    s = FaultSchedule.parse(
        "serve:dispatch=device-loss@3,serve:fetch=hung-fetch@5")
    assert FaultSchedule.parse(s.spec()).spec() == s.spec()
    a = FaultSchedule.seeded(42, n=6)
    b = FaultSchedule.seeded(42, n=6)
    assert a.spec() == b.spec() and len(a) == 6
    assert FaultSchedule.seeded(43, n=6).spec() != a.spec()
    # the seeded shorthand the serve_bench CLI takes
    c = FaultSchedule.parse("seed=42,n=6")
    assert c.spec() == a.spec()


def test_schedule_parse_rejects_malformed():
    for bad in ("x@3", "serve:fetch=nonsense@3", "serve:fetch=hung-fetch@0",
                "seed=1,serve:dispatch=device-loss@2", "seed="):
        with pytest.raises(ValueError):
            FaultSchedule.parse(bad)


def test_injector_fires_each_event_exactly_once():
    inj = ChaosInjector(FaultSchedule.parse("a=slow-batch@2,a=slow-batch@4"))
    hits = [inj.fire("a") is not None for _ in range(6)]
    assert hits == [False, True, False, True, False, False]
    assert inj.summary() == {"slow-batch": 2, "total": 2}
    assert inj.pending() == 0 and not inj.enabled


def test_maybe_injector_disabled_forms():
    assert maybe_injector("") is None
    assert maybe_injector(None) is None
    assert maybe_injector(FaultSchedule(())) is None
    assert maybe_injector("a=slow-batch@1").enabled


# ---------------------------------------------------------------------------
# serving under seeded chaos


@pytest.fixture(scope="module")
def serve_parts():
    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, topk=16,
                 conf_th=0.0, nms_th=0.5, imsize=IMSIZE)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0), IMSIZE)
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    rng = np.random.default_rng(3)
    pool = [rng.integers(0, 256, (IMSIZE, IMSIZE, 3), dtype=np.uint8)
            for _ in range(8)]
    pending = [predict(variables, img[None]) for img in pool]
    oracle = [type(d)(*(np.asarray(leaf[0]) for leaf in d))
              for d in jax.device_get(pending)]
    return predict, variables, pool, oracle


def _rows_equal(a, b) -> bool:
    return all(np.array_equal(getattr(a, n), getattr(b, n))
               for n in ("boxes", "classes", "scores", "valid"))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_serving_survives_seeded_random_schedules(serve_parts, seed):
    """The serving acceptance property: a seeded random interleaving of
    device-loss, hung-fetch and slow-batch faults loses ZERO acknowledged
    requests, and every survivor is bit-identical to one-shot predict."""
    import time
    predict, variables, pool, oracle = serve_parts
    sched = FaultSchedule.seeded(seed, n=5, max_at=20)
    # injected hangs must overrun the watchdog to exercise detection
    for ev in sched:
        if ev.kind == "hung-fetch":
            ev.meta["hang_s"] = 0.5
        if ev.kind == "slow-batch":
            ev.meta["slow_s"] = 0.02
    inj = ChaosInjector(sched)
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2, 4), max_wait_ms=1.0, depth=2,
                        queue_capacity=64, max_retries=len(sched),
                        hang_timeout_s=0.15, injector=inj)
    rng = np.random.default_rng(100 + seed)
    futs = []
    for _ in range(30):
        i = int(rng.integers(0, len(pool)))
        futs.append((i, eng.submit(pool[i])))
        if rng.random() < 0.3:
            time.sleep(float(rng.uniform(0, 0.003)))  # force many batches
    rows = [(i, f.result(timeout=120)) for i, f in futs]
    st = eng.stats()
    health = eng.health()
    eng.close()
    assert st["failed"] == 0, "acknowledged requests were lost"
    assert st["completed"] == len(futs)
    assert all(_rows_equal(r, oracle[i]) for i, r in rows), \
        "a retried request diverged from its one-shot predict"
    # accounting closes: whatever was injected shows up as recovery work
    dispatch_faults = sum(1 for e in inj.fired
                          if e.kind in ("device-loss", "hung-fetch"))
    if dispatch_faults:
        assert st["requeued_batches"] >= 1
    assert health["stats"]["retried"] == st["retried"]


def test_serving_chaos_with_deadlines_accounts_every_request(serve_parts):
    """With deadlines armed, every submitted request resolves to exactly
    one of {completed-bit-identical, shed} — nothing disappears, even
    when retries race deadline shedding."""
    from real_time_helmet_detection_tpu.serving import SheddedError
    predict, variables, pool, oracle = serve_parts
    inj = ChaosInjector(FaultSchedule([
        FaultEvent("serve:dispatch", "device-loss", 2),
        FaultEvent("serve:fetch", "hung-fetch", 3, {"hang_s": 0.5}),
    ]))
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2), max_wait_ms=0.5, depth=2,
                        queue_capacity=64, max_retries=4,
                        hang_timeout_s=0.1, injector=inj)
    futs = [(i % len(pool), eng.submit(pool[i % len(pool)],
                                       deadline_s=30.0))
            for i in range(12)]
    completed = shed = 0
    for i, f in futs:
        try:
            row = f.result(timeout=120)
            assert _rows_equal(row, oracle[i])
            completed += 1
        except SheddedError:
            shed += 1
    st = eng.stats()
    eng.close()
    assert completed + shed == len(futs)
    assert st["completed"] == completed
    assert st["shed_deadline"] + st["shed_queue_full"] == shed
    assert st["failed"] == 0


# ---------------------------------------------------------------------------
# fleet sites (ISSUE 12): router-level faults heal by re-dispatch/respawn


def test_fleet_sites_registered_and_seedable():
    """The classification links for the two new sites, pinned directly:
    fleet:replica only draws worker-death (the caller — FleetRouter —
    kills and respawns the replica), fleet:dispatch draws the transient
    routing faults, both are in ALL_SITES, and seeded schedules can draw
    them replayably."""
    from real_time_helmet_detection_tpu.runtime.faults import (ALL_SITES,
                                                               FLEET_SITES,
                                                               SITE_KINDS)
    assert FLEET_SITES == ("fleet:dispatch", "fleet:replica")
    assert set(FLEET_SITES) <= set(ALL_SITES)
    assert SITE_KINDS["fleet:replica"] == ("worker-death",)
    assert set(SITE_KINDS["fleet:dispatch"]) == {"device-loss",
                                                 "slow-batch"}
    a = FaultSchedule.seeded(7, n=4, sites=FLEET_SITES)
    assert a.spec() == FaultSchedule.seeded(7, n=4,
                                            sites=FLEET_SITES).spec()
    assert all(e.site in FLEET_SITES for e in a)


def test_cascade_site_registered_and_seedable():
    """ISSUE 16: the fleet:escalate chaos site is first-class — in
    ALL_SITES with its two hop-fault kinds (device-loss -> the quality
    hop errors as it launches -> degrade; worker-death -> the selected
    quality replica dies -> respawn + the hop proceeds), and seeded
    schedules draw it replayably like every other site."""
    from real_time_helmet_detection_tpu.runtime.faults import (
        ALL_SITES, CASCADE_SITES, SITE_KINDS)
    assert CASCADE_SITES == ("fleet:escalate",)
    assert set(CASCADE_SITES) <= set(ALL_SITES)
    assert set(SITE_KINDS["fleet:escalate"]) == {"device-loss",
                                                 "worker-death"}
    a = FaultSchedule.seeded(11, n=3, sites=CASCADE_SITES)
    assert a.spec() == FaultSchedule.seeded(11, n=3,
                                            sites=CASCADE_SITES).spec()
    assert all(e.site == "fleet:escalate" for e in a)


def test_stream_site_registered_and_seedable():
    """ISSUE 17: the stream:frame chaos site is first-class — in
    ALL_SITES with its three frame-fault kinds (dropped-frame /
    late-frame / corrupt-frame — the camera-side failure modes the
    StreamSession must absorb without losing an acked frame), and
    seeded schedules draw it replayably like every other site."""
    from real_time_helmet_detection_tpu.runtime.faults import (
        ALL_SITES, SITE_KINDS, STREAM_SITES)
    assert STREAM_SITES == ("stream:frame",)
    assert set(STREAM_SITES) <= set(ALL_SITES)
    assert set(SITE_KINDS["stream:frame"]) == {
        "dropped-frame", "late-frame", "corrupt-frame"}
    a = FaultSchedule.seeded(13, n=3, sites=STREAM_SITES)
    assert a.spec() == FaultSchedule.seeded(13, n=3,
                                            sites=STREAM_SITES).spec()
    assert all(e.site == "stream:frame" for e in a)


class _StreamFakeFut:
    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        return self._value


class _StreamFakeServer:
    """Deterministic submit surface for stream chaos: the answer is a
    pure function of the submitted bytes (engine-backed bit-identity is
    serve_bench --selfcheck's job; here the session's own fault
    absorption is the contract under test)."""

    def submit(self, image, block=False, deadline_s=None, **kw):
        from real_time_helmet_detection_tpu.ops.decode import Detections
        img = np.asarray(image)
        base = img[:4, 0, 0].astype(np.float32)
        return _StreamFakeFut(Detections(
            boxes=np.stack([base, base, base + 4.0, base + 4.0],
                           axis=-1),
            classes=(img[:4, 1, 0].astype(np.int32) % 2),
            scores=img[:4, 2, 0].astype(np.float32) / 255.0,
            valid=np.ones((4,), bool)))


@pytest.mark.parametrize("seed", [2, 5, 8])
def test_stream_frame_faults_zero_lost_acked_frames(seed):
    """THE stream acceptance row: under a seeded stream:frame schedule
    every submitted frame DELIVERS in order (dropped/corrupt frames
    answer from the tile cache as gaps — never a lost ack, and a
    corrupt frame never becomes the delta reference), and the session
    accounting matches the schedule exactly."""
    from real_time_helmet_detection_tpu.runtime.faults import STREAM_SITES
    from real_time_helmet_detection_tpu.serving.streams import \
        StreamSession
    sched = FaultSchedule.seeded(seed, n=3, sites=STREAM_SITES,
                                 max_at=10)
    inj = ChaosInjector(sched)
    n_gap = sum(1 for e in sched
                if e.kind in ("dropped-frame", "corrupt-frame"))
    n_corrupt = sum(1 for e in sched if e.kind == "corrupt-frame")
    n_late = sum(1 for e in sched if e.kind == "late-frame")
    sess = StreamSession(_StreamFakeServer(), (IMSIZE, IMSIZE, 3),
                         grid=2, threshold=1.0, ema=0.0, injector=inj)
    rng = np.random.default_rng(seed)
    try:
        futs = [sess.submit_frame(
            rng.integers(0, 256, (IMSIZE, IMSIZE, 3), np.uint8))
            for _ in range(12)]
        results = [f.result(timeout=60) for f in futs]
        assert [r.seq for r in results] == list(range(12))  # in order,
        # every ack delivered
        assert inj.pending() == 0  # the whole schedule fired
        st = sess.stats()
        assert st["delivered"] == 12
        assert st["gaps"] == n_gap
        assert st["corrupt"] == n_corrupt
        assert st["late"] == n_late
        # a gap frame answers from the cache: bit-identical to the
        # previous delivered detections
        for i, r in enumerate(results):
            if r.gap and i > 0:
                prev = results[i - 1].detections
                for name in prev._fields:
                    assert np.array_equal(getattr(r.detections, name),
                                          getattr(prev, name))
    finally:
        sess.close()


def test_fleet_replica_death_acceptance(serve_parts):
    """THE fleet acceptance row: an injected fleet:replica worker-death
    plus a fleet:dispatch device-loss against a live 2-replica router
    loses ZERO acknowledged requests — the killed replica's queued acks
    re-dispatch to the survivor, a fresh replica respawns into the slot,
    and every survivor is bit-identical to one-shot predict."""
    import time

    from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
    from real_time_helmet_detection_tpu.serving import FleetRouter

    predict, variables, pool, oracle = serve_parts

    def factory(rid, start=True):
        return ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3),
                             np.uint8, buckets=(1, 2), max_wait_ms=1.0,
                             depth=2, queue_capacity=64, max_retries=4,
                             metrics=MetricsRegistry(), start=start)

    inj = ChaosInjector(FaultSchedule([
        FaultEvent("fleet:dispatch", "device-loss", 3),
        FaultEvent("fleet:replica", "worker-death", 6),
    ]))
    router = FleetRouter(factory, 2, metrics=MetricsRegistry(),
                         injector=inj)
    futs = []
    for k in range(20):
        i = k % len(pool)
        futs.append((i, router.submit(pool[i])))
        if k % 3 == 0:
            time.sleep(0.002)
    rows = [(i, f.result(timeout=120)) for i, f in futs]
    st = router.stats()
    router.close()
    assert len(inj.fired) == 2 and inj.pending() == 0
    assert st["lost"] == 0, "acknowledged requests were lost"
    assert st["replica_deaths"] == 1 and st["respawns"] == 1
    assert st["dispatch_faults"] == 1
    assert all(_rows_equal(r, oracle[i]) for i, r in rows)


# ---------------------------------------------------------------------------
# training: injected NaN -> sentinel rollback == clean resume


@pytest.fixture(scope="module")
def voc_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("voc_chaos")
    return make_synthetic_voc(str(root), num_train=4, num_test=1,
                              imsize=(48, 40), seed=3)


def _train_cfg(voc_root, save, **kw):
    base = dict(train_flag=True, num_stack=1, hourglass_inch=8, num_cls=2,
                imsize=64, batch_size=2, end_epoch=2, ckpt_interval=1,
                print_interval=1, num_workers=0, data=voc_root,
                save_path=save, hang_warn_seconds=0, summary=False,
                sentinel=True, sentinel_divergence=2,
                sentinel_rollbacks=2,
                # keep the loss scale pinned at 1.0: the healed rerun must
                # be BIT-identical to the clean twin
                sentinel_backoff=1.0)
    base.update(kw)
    return Config(**base)


def _params_of(ckpt_dir):
    import orbax.checkpoint as ocp
    raw = ocp.StandardCheckpointer().restore(os.path.abspath(ckpt_dir))
    return [np.asarray(x) for x in jax.tree.leaves(raw["state"]["params"])]


def test_train_nan_rollback_matches_clean_resume(voc_root, tmp_path):
    """THE training acceptance property: epoch 1 is poisoned with enough
    consecutive NaN batches to trip the divergence escalation; the run
    rolls back to the epoch-0 checkpoint and reruns epoch 1 clean. Its
    final checkpoint and loss history must be BIT-identical to a control
    run resumed from the SAME checkpoint with no faults injected."""
    from real_time_helmet_detection_tpu.train import train

    save_a = str(tmp_path / "chaotic")
    # 4 imgs / batch 2 => 2 steps per epoch; arrivals 3,4 = epoch 1 —
    # two consecutive poisoned steps >= sentinel_divergence
    chaos = ChaosInjector(FaultSchedule([
        FaultEvent("train:batch", "nan-batch", 3),
        FaultEvent("train:batch", "nan-batch", 4),
    ]))
    train(_train_cfg(voc_root, save_a), chaos=chaos)
    assert len(chaos.fired) == 2, "the poison was never injected"
    ck_a1 = os.path.join(save_a, "check_point_1")  # epoch 0 (rolled back to)
    ck_a2 = os.path.join(save_a, "check_point_2")  # epoch 1, healed
    assert os.path.isdir(ck_a1) and os.path.isdir(ck_a2)

    # control: clean resume from the SAME epoch-0 checkpoint
    save_b = str(tmp_path / "clean")
    train(_train_cfg(voc_root, save_b, model_load=ck_a1))
    ck_b2 = os.path.join(save_b, "check_point_2")
    assert os.path.isdir(ck_b2)

    pa, pb = _params_of(ck_a2), _params_of(ck_b2)
    assert len(pa) == len(pb)
    for x, y in zip(pa, pb):
        assert x.tobytes() == y.tobytes(), \
            "healed run diverged from the clean resume"

    # the healed loss history carries NO poisoned entries: the rollback
    # restored the sidecar and the rerun appended only clean losses
    import json
    with open(os.path.join(ck_a2, "loss_log.json")) as f:
        log_a = json.load(f)
    with open(os.path.join(ck_b2, "loss_log.json")) as f:
        log_b = json.load(f)
    assert log_a["total"] == log_b["total"]
    assert all(np.isfinite(v) for v in log_a["total"])


_RANK_JOB = (
    "import os, sys\n"
    "sys.path.insert(0, os.environ['REPO'])\n"
    "import jax\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "from real_time_helmet_detection_tpu.config import Config\n"
    "from real_time_helmet_detection_tpu.runtime import (ChaosInjector,"
    " FaultSchedule, run_as_job)\n"
    "from real_time_helmet_detection_tpu.train import ("
    "find_latest_checkpoint, train)\n"
    "def main():\n"
    "    save = os.environ['SAVE']\n"
    "    marker = os.environ['MARKER']\n"
    "    kw = dict(train_flag=True, num_stack=1, hourglass_inch=8,\n"
    "              num_cls=2, imsize=64, batch_size=2, end_epoch=2,\n"
    "              ckpt_interval=1, print_interval=1, num_workers=0,\n"
    "              data=os.environ['VOC'], save_path=save,\n"
    "              hang_warn_seconds=0, summary=False)\n"
    "    chaos = None\n"
    "    if not os.path.exists(marker):\n"
    "        open(marker, 'w').write('1')\n"
    "        # seeded worker-death drawn from the train:rank site; max_at=4\n"
    "        # keeps the trigger inside this run's 4 iterations\n"
    "        chaos = ChaosInjector(FaultSchedule.seeded(\n"
    "            int(os.environ['SEED']), n=1, sites=('train:rank',),\n"
    "            max_at=4))\n"
    "    else:\n"
    "        latest = find_latest_checkpoint(save)\n"
    "        if latest:\n"
    "            kw['model_load'] = latest\n"
    "    train(Config(**kw), chaos=chaos)\n"
    "run_as_job(main)\n"
)


def test_worker_death_classified_transient_supervisor_requeues(
        voc_root, tmp_path):
    """ISSUE 11 satellite: a SEEDED worker-death schedule kills a training
    rank mid-run. The acceptance chain: the raised error carries the
    UNAVAILABLE signature (runtime/errors.py classifies it TRANSIENT —
    never a hung rendezvous), the job supervisor salvages + requeues with
    backoff, attempt 2 resumes from the newest complete checkpoint, and
    the healed run's loss history + final weights are BIT-identical to an
    uninterrupted run of the same config."""
    import json
    import sys

    from real_time_helmet_detection_tpu.runtime import (
        InjectedBackendError, JobSpec, Spool, Supervisor,
        is_transient_backend_error)
    from real_time_helmet_detection_tpu.runtime.faults import SITE_KINDS
    from real_time_helmet_detection_tpu.train import train

    # the classification link, pinned directly: the train:rank site only
    # draws worker-death, and the error train_epoch raises for it is
    # transient for the shared classifier
    assert SITE_KINDS["train:rank"] == ("worker-death",)
    assert is_transient_backend_error(InjectedBackendError(
        "UNAVAILABLE: injected worker death at epoch 0 iter 1"))

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    save = str(tmp_path / "killed")
    spool = Spool(str(tmp_path / "queue"))
    env = {"REPO": repo, "SAVE": save, "VOC": voc_root, "SEED": "11",
           "MARKER": str(tmp_path / "attempt_marker"),
           "PYTHONPATH": os.pathsep.join(
               [repo] + [p for p in os.environ.get(
                   "PYTHONPATH", "").split(os.pathsep) if p])}
    spool.enqueue(JobSpec(
        job="train-dp", argv=[sys.executable, "-c", _RANK_JOB], cwd=repo,
        heartbeat_timeout_s=500.0, max_attempts=3,
        backoff_base_s=0.1, backoff_cap_s=0.2, env=env))

    sup = Supervisor(spool, poll_s=0.1, kill_grace_s=2.0)
    summary = sup.run()
    assert summary["jobs"]["train-dp"]["state"] == "done"
    assert summary["jobs"]["train-dp"]["attempt"] == 2, \
        "the killed rank never triggered a requeue"

    # journal truth: the first attempt died TRANSIENT (the UNAVAILABLE
    # signature), was salvaged and requeued behind a backoff gate
    with open(spool.path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    spool.close()
    salv = [r for r in recs if r.get("kind") == "state"
            and r.get("state") == "salvaged"]
    assert salv and "UNAVAILABLE" in str(salv[0].get("reason"))
    requeues = [r for r in recs if r.get("kind") == "state"
                and r.get("state") == "queued"
                and r.get("attempt", 1) == 2]
    assert requeues and requeues[0].get("not_before", 0) > 0

    # the healed run vs an uninterrupted twin: bit-identical history +
    # weights (batch content is a pure function of (seed, epoch, idx))
    save_b = str(tmp_path / "clean")
    train(_train_cfg(voc_root, save_b, sentinel=False,
                     sentinel_backoff=0.5))
    for x, y in zip(_params_of(os.path.join(save, "check_point_2")),
                    _params_of(os.path.join(save_b, "check_point_2"))):
        assert x.tobytes() == y.tobytes(), \
            "resumed run diverged from the uninterrupted twin"
    with open(os.path.join(save, "check_point_2", "loss_log.json")) as f:
        log_a = json.load(f)
    with open(os.path.join(save_b, "check_point_2", "loss_log.json")) as f:
        log_b = json.load(f)
    assert log_a["total"] == log_b["total"]


def test_train_skip_only_when_divergence_not_sustained(voc_root, tmp_path):
    """A SINGLE poison batch is absorbed by the in-jit skip (no rollback,
    no crash): the run completes with exactly one skipped step counted by
    the monitor, and training carries on."""
    from real_time_helmet_detection_tpu.train import train

    save = str(tmp_path / "skip_only")
    chaos = ChaosInjector(FaultSchedule([
        FaultEvent("train:batch", "nan-batch", 3),
    ]))
    # divergence=2 but only ONE consecutive bad step: never escalates
    train(_train_cfg(voc_root, save), chaos=chaos)
    assert len(chaos.fired) == 1
    assert os.path.isdir(os.path.join(save, "check_point_2"))
    import json
    with open(os.path.join(save, "check_point_2", "loss_log.json")) as f:
        log = json.load(f)
    # the poisoned step was SKIPPED, not recorded as a converged loss:
    # the final checkpoint's history holds only finite entries... except
    # the skipped step's own (NaN) loss record, which IS appended (the
    # loss_log records what happened; the STATE is what was protected)
    assert sum(1 for v in log["total"] if not np.isfinite(v)) == 1
