"""Fault-injection suite for the TPU job supervisor (ISSUE 3).

Every recovery path runs HERE, on CPU, through the supervisor's injectable
seams (spawn/clock/heartbeat):

* a queued job is the FIRST process the supervisor starts — no probe, no
  waiter touches the device before it (one process per chip);
* a stale-heartbeat job is killed, its flushed partial artifacts are
  recorded as salvaged, and the job is requeued with backoff;
* `kill -9` of the supervisor between ANY two state transitions loses no
  queued job on restart (journal-prefix replay — fsync order makes every
  prefix a legal on-disk state).

Nothing here imports jax, and a hard SIGALRM per test bounds it (the
suite has no pytest-timeout plugin).
"""

import json
import os
import signal

import pytest

from real_time_helmet_detection_tpu.runtime import (JobSpec, Spool,
                                                    Supervisor)
from real_time_helmet_detection_tpu.runtime import spool as spool_mod

TIMEOUT_S = 120  # hard per-test ceiling; every test is sub-second on CPU


@pytest.fixture(autouse=True)
def _hard_timeout():
    def _fire(signum, frame):
        raise RuntimeError(
            "test exceeded the %ds hard timeout — something blocked"
            % TIMEOUT_S)

    old = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(TIMEOUT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


class FakeClock:
    """Deterministic time: sleep() advances it; nothing waits for real."""

    def __init__(self, t0=1_000_000.0):
        self.t = t0
        self.slept = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        assert s >= 0
        self.t += max(s, 1e-3)
        self.slept += s


class FakeHandle:
    """A spawned job: exits with `rc` after `runtime` fake-seconds, or
    never (rc=None). Records kill signals."""

    _next_pid = 30000

    def __init__(self, clock, rc=0, runtime=0.0):
        FakeHandle._next_pid += 1
        self.pid = FakeHandle._next_pid
        self.clock = clock
        self.rc = rc
        self.done_at = clock.t + runtime
        self.terminated = False
        self.killed = False

    def poll(self):
        if self.terminated or self.killed:
            return -15
        if self.rc is None:
            return None
        return self.rc if self.clock.t >= self.done_at else None

    def terminate(self):
        self.terminated = True

    def kill(self):
        self.killed = True


def make_sup(spool, clock, *, spawner=None, hb_age=None, **kw):
    """Supervisor with every external effect faked."""
    spawned = []

    def spawn(spec, env, log_path):
        h = (spawner or (lambda s: FakeHandle(clock)))(spec)
        spawned.append((spec.job, h, env))
        return h

    sup = Supervisor(
        spool,
        spawn=spawn,
        clock=clock, sleep=clock.sleep, rng=lambda: 0.0,
        heartbeat_age=hb_age or (lambda path, started: 0.0),
        kill_grace_s=kw.pop("kill_grace_s", 1.0),
        poll_s=kw.pop("poll_s", 0.5),
        log=lambda m: None, **kw)
    sup.spawned = spawned
    return sup


def enqueue(spool, job="j1", **kw):
    kw.setdefault("argv", ["true"])
    kw.setdefault("heartbeat_timeout_s", 60.0)
    return spool.enqueue(JobSpec(job=job, **kw))


def journal(spool):
    with open(spool.path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def states_of(spool, job):
    return [r["state"] for r in journal(spool)
            if r.get("kind") == "state" and r.get("job") == job]


# --------------------------------------------------------------------------
# spool durability: the kill -9 contract
# --------------------------------------------------------------------------

def test_spool_roundtrip_and_replay(tmp_path):
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "a", artifacts=["*.json"])
    enqueue(sp, "b")
    sp.transition("a", spool_mod.RUNNING, pid=123)
    sp.transition("a", spool_mod.DONE, rc=0)
    sp.close()

    sp2 = Spool(str(tmp_path / "q"))
    assert sp2.jobs["a"].state == spool_mod.DONE
    assert sp2.jobs["b"].state == spool_mod.QUEUED
    assert sp2.jobs["a"].spec.artifacts == ["*.json"]
    assert [j.spec.job for j in sp2.ordered()] == ["a", "b"]
    sp2.close()


def test_spool_every_journal_prefix_is_a_legal_state(tmp_path):
    """kill -9 between ANY two transitions == the journal truncated at a
    line boundary. Replay of every prefix must load, and must never lose
    an enqueued job."""
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "a")
    enqueue(sp, "b")
    sp.transition("a", spool_mod.RUNNING, pid=1)
    sp.transition("a", spool_mod.SALVAGED, reason="hb stale",
                  salvaged_artifacts=[])
    sp.transition("a", spool_mod.QUEUED, attempt=2, not_before=0.0)
    sp.transition("a", spool_mod.RUNNING, pid=2)
    sp.transition("a", spool_mod.DONE, rc=0)
    sp.transition("b", spool_mod.RUNNING, pid=3)
    sp.close()

    with open(sp.path, "rb") as f:
        lines = f.read().splitlines(keepends=True)
    for cut in range(1, len(lines) + 1):
        prefix_dir = tmp_path / ("cut%d" % cut)
        os.makedirs(prefix_dir / "q")
        with open(prefix_dir / "q" / "jobs.jsonl", "wb") as f:
            f.write(b"".join(lines[:cut]))
        sp2 = Spool(str(prefix_dir / "q"))
        # no enqueued job may vanish, and states replay to a known value
        assert set(sp2.jobs) == ({"a"} if cut < 3 else {"a", "b"})
        for js in sp2.jobs.values():
            assert js.state in {"queued", "claim-wait", "running", "done",
                                "failed", "salvaged"}
        sp2.close()


def test_spool_tolerates_torn_final_line(tmp_path):
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "a")
    sp.close()
    with open(sp.path, "ab") as f:
        f.write(b'{"kind": "state", "job": "a", "state": "runn')  # torn
    sp2 = Spool(str(tmp_path / "q"))
    assert sp2.jobs["a"].state == spool_mod.QUEUED  # torn record dropped
    # and the spool keeps working after the torn tail
    sp2.transition("a", spool_mod.RUNNING, pid=9)
    sp2.close()
    sp3 = Spool(str(tmp_path / "q"))
    assert sp3.jobs["a"].state == spool_mod.RUNNING
    sp3.close()


def test_spool_rejects_illegal_transition(tmp_path):
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "a")
    sp.transition("a", spool_mod.RUNNING)
    sp.transition("a", spool_mod.DONE)
    with pytest.raises(ValueError):
        sp.transition("a", spool_mod.RUNNING)  # done is terminal
    sp.close()


def test_spool_rejects_duplicate_job_id(tmp_path):
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "a")
    with pytest.raises(ValueError):
        enqueue(sp, "a")
    sp.close()


# --------------------------------------------------------------------------
# the acceptance scenarios, end to end through run()
# --------------------------------------------------------------------------

def test_queued_job_is_the_first_process_started(tmp_path):
    """One process per chip: on a machine with nothing but the chip, `run`
    starts the queued job itself — no health probe, no claim waiter, no
    parked state in front of it."""
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "j1")
    enqueue(sp, "j2")
    sup = make_sup(sp, clock)
    summary = sup.run()
    assert [job for job, _, _ in sup.spawned] == ["j1", "j2"]
    assert summary["jobs"]["j1"]["state"] == "done"
    assert summary["jobs"]["j2"]["state"] == "done"
    assert states_of(sp, "j1") == ["queued", "running", "done"]
    sp.close()


def test_stale_heartbeat_kill_salvage_requeue_backoff(tmp_path):
    clock = FakeClock()
    qdir = tmp_path / "q"
    sp = Spool(str(qdir))
    # the job "flushed" one partial artifact before hanging
    art_dir = tmp_path / "work"
    os.makedirs(art_dir)
    with open(art_dir / "sweep.json", "w") as f:
        f.write('{"partial": true}')
    enqueue(sp, "hang", artifacts=["sweep.json"], cwd=str(art_dir),
            heartbeat_timeout_s=30.0, max_attempts=2, backoff_base_s=60.0,
            backoff_cap_s=600.0)

    hangs = []

    def spawner(spec):
        h = FakeHandle(clock, rc=None)  # never exits, never beats
        hangs.append(h)
        return h

    sup = make_sup(sp, clock, spawner=spawner,
                   hb_age=lambda path, started: clock.t - started)
    summary = sup.run()

    # acceptance: killed, salvaged with the flushed partial, requeued with
    # backoff; attempt budget (2) exhausted -> failed
    assert all(h.terminated for h in hangs)
    assert len(hangs) == 2
    recs = journal(sp)
    salvages = [r for r in recs if r.get("kind") == "state"
                and r["state"] == "salvaged"]
    assert len(salvages) == 2
    assert salvages[0]["salvaged_artifacts"][0]["path"] == "sweep.json"
    requeues = [r for r in recs if r.get("kind") == "state"
                and r["state"] == "queued" and r.get("attempt", 1) == 2]
    assert len(requeues) == 1
    assert requeues[0]["not_before"] > 0  # backoff gate recorded
    assert summary["jobs"]["hang"]["state"] == "failed"
    sp.close()


def test_backoff_is_capped_exponential(tmp_path):
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    sup = make_sup(sp, clock)
    spec = JobSpec(job="x", argv=["true"], backoff_base_s=30.0,
                   backoff_cap_s=100.0)
    assert sup._backoff_s(1, spec) == 30.0
    assert sup._backoff_s(2, spec) == 60.0
    assert sup._backoff_s(3, spec) == 100.0  # capped
    assert sup._backoff_s(9, spec) == 100.0
    sp.close()


def test_transient_exit_code_requeues_then_succeeds(tmp_path):
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "flaky", max_attempts=3, backoff_base_s=5.0,
            backoff_cap_s=10.0)
    rcs = [75, 0]  # EXIT_TRANSIENT then success

    def spawner(spec):
        return FakeHandle(clock, rc=rcs.pop(0))

    sup = make_sup(sp, clock, spawner=spawner)
    summary = sup.run()
    assert summary["jobs"]["flaky"] == {"state": "done", "attempt": 2}
    assert clock.slept >= 5.0  # backoff actually waited
    sp.close()


def test_permanent_failure_no_requeue(tmp_path):
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "broken", max_attempts=5)
    sup = make_sup(sp, clock,
                   spawner=lambda spec: FakeHandle(clock, rc=1))
    summary = sup.run()
    assert summary["jobs"]["broken"] == {"state": "failed", "attempt": 1}
    sp.close()


def test_status_file_error_class_wins_over_exit_code(tmp_path):
    """A job exiting 1 but writing error_class=transient to its status
    file is retried: the status file is the contract, the code a
    fallback."""
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    js = enqueue(sp, "statusy", max_attempts=2, backoff_base_s=1.0)

    attempts = []

    def spawner(spec):
        attempts.append(1)
        # write the status file the way write_job_status would
        path = sp.status_path("statusy", len(attempts))
        with open(path, "w") as f:
            json.dump({"ok": len(attempts) > 1,
                       "error": "UNAVAILABLE: socket closed",
                       "error_class": "transient"}, f)
        return FakeHandle(clock, rc=1 if len(attempts) == 1 else 0)

    sup = make_sup(sp, clock, spawner=spawner)
    summary = sup.run()
    assert summary["jobs"]["statusy"] == {"state": "done", "attempt": 2}
    assert js.spec.max_attempts == 2
    sp.close()


def test_recover_requeues_interrupted_jobs(tmp_path):
    """Supervisor restart: claim-wait goes back to queued; a running job
    whose pid is gone is salvaged + requeued — no job lost."""
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "was-waiting")
    enqueue(sp, "was-running")
    sp.transition("was-waiting", spool_mod.CLAIM_WAIT)
    sp.transition("was-running", spool_mod.RUNNING, pid=2 ** 22 + 12345)
    sp.close()

    sp2 = Spool(str(tmp_path / "q"))
    sup = make_sup(sp2, clock)
    sup.recover()
    assert sp2.jobs["was-waiting"].state == spool_mod.QUEUED
    assert sp2.jobs["was-running"].state == spool_mod.QUEUED
    assert sp2.jobs["was-running"].attempt == 2
    assert "salvaged" in states_of(sp2, "was-running")
    sp2.close()


def test_jobs_run_fifo_and_serially(tmp_path):
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    for name in ("first", "second", "third"):
        enqueue(sp, name)
    order = []

    def spawner(spec):
        order.append(spec.job)
        return FakeHandle(clock, rc=0, runtime=1.0)

    sup = make_sup(sp, clock, spawner=spawner)
    sup.run()
    assert order == ["first", "second", "third"]
    sp.close()


def test_job_env_carries_heartbeat_and_status_paths(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_SPAN_LOG", raising=False)
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "j1", env={"EXTRA": "1"})
    sup = make_sup(sp, clock)
    sup.run()
    _, _, env = sup.spawned[0]
    assert env["TPU_QUEUE_HEARTBEAT"] == sp.heartbeat_path("j1")
    assert env["TPU_QUEUE_STATUS"] == sp.status_path("j1", 1)
    assert env["EXTRA"] == "1"
    # flight recorder (ISSUE 6): every queued job writes spans into the
    # round's obs/ log next to the queue dir, so obs_report.py can join
    # the journal with what each job was actually doing
    assert env["OBS_SPAN_LOG"] == os.path.join(
        os.path.dirname(sp.root), "obs", "spans.jsonl")
    sp.close()


def test_job_env_respects_explicit_span_log(tmp_path):
    clock = FakeClock()
    sp = Spool(str(tmp_path / "q"))
    enqueue(sp, "j1", env={"OBS_SPAN_LOG": "/custom/spans.jsonl"})
    sup = make_sup(sp, clock)
    sup.run()
    _, _, env = sup.spawned[0]
    assert env["OBS_SPAN_LOG"] == "/custom/spans.jsonl"
    sp.close()
