"""Crash-restartable TPU job supervisor (ISSUE 3 tentpole).

The reference has no supervision layer (SURVEY.md §5; its only recovery
is a manual restart with --model-load, ref train.py:190-199).

Owns a persistent spool of jobs (runtime/spool.py), runs them strictly
one at a time (one process per chip), and gives every running job a
heartbeat + hang-kill-salvage contract and capped-exponential-backoff
requeue for transient failures.

There is no health probe before a job: the chip is local, a probe child
would hold it before the process that needs it, and a killed holder is
the hazard a probe was meant to avoid. The job itself is the probe — a
job that cannot reach the backend exits through the classified status
contract below and is requeued or failed like any other.

Job contract: the supervisor exports $TPU_QUEUE_HEARTBEAT and
$TPU_QUEUE_STATUS into every job. Jobs beat the former at natural flush
points (runtime/heartbeat.py `maybe_job_heartbeat`; train.py's
HangWatchdog beats it automatically) and write a machine-readable exit
status to the latter (`write_job_status`). A beat gone stale past the
job's deadline -> SIGTERM (SIGKILL after a grace), record which declared
artifact globs have survivors (tpu_sweep's per-config flush makes the
partials real), requeue with backoff. Exit codes: 0 done, EXIT_TRANSIENT
(75) transient, else permanent — the status file wins over the code when
both exist.

Every external effect sits behind an injectable seam (spawn, clock,
sleep, rng), so the whole recovery surface runs in the CPU smoke tier
(tests/test_supervisor.py).
"""

from __future__ import annotations

import glob
import os
import random
import subprocess
import time
from typing import Callable, Optional

from .errors import EXIT_TRANSIENT, classify_error_text
from .heartbeat import HEARTBEAT_ENV, STATUS_ENV, read_heartbeat
from .spool import (CLAIM_WAIT, DONE, FAILED, QUEUED, RUNNING, SALVAGED,
                    JobState, Spool)

def default_spawn(spec, env: dict, log_path: str):
    """Launch one job, stdout+stderr appended to its per-attempt log."""
    logf = open(log_path, "ab")
    try:
        return subprocess.Popen(
            spec.argv, env=env, cwd=spec.cwd or None,
            stdout=logf, stderr=subprocess.STDOUT)
    finally:
        logf.close()  # Popen holds its own fd


class Supervisor:
    """See module docstring. All seams default to the real thing."""

    def __init__(self, spool: Spool, *,
                 spawn: Callable = default_spawn,
                 clock: Callable[[], float] = time.time,
                 sleep: Callable[[float], None] = time.sleep,
                 rng: Callable[[], float] = random.random,
                 heartbeat_age: Optional[Callable] = None,
                 kill_grace_s: float = 20.0,
                 poll_s: float = 1.0,
                 log: Callable[[str], None] = None):
        self.spool = spool
        self.spawn = spawn
        self.clock = clock
        self.sleep = sleep
        self.rng = rng
        self._hb_age = heartbeat_age or self._default_hb_age
        self.kill_grace_s = kill_grace_s
        self.poll_s = poll_s
        self._log = log or (lambda m: print("[tpu_queue] %s" % m,
                                            flush=True))
        # Live metrics plane (ISSUE 10): job-state gauges + heartbeat age
        # + requeue/salvage counters, exported when $OBS_METRICS is set
        # (crash-safe periodic snapshots; obs.metrics is stdlib-only, so
        # the no-ML-stack rule holds). queue.jobs.<state> gauges track the
        # spool's live census; queue.heartbeat_age_s is the running job's
        # silence — the number the stale-kill deadline acts on.
        from ..obs.metrics import default_registry, maybe_writer
        self._metrics = default_registry()
        self._m_writer = maybe_writer(registry=self._metrics)
        self._mg_hb_age = self._metrics.gauge("queue.heartbeat_age_s")
        self._mc_requeues = self._metrics.counter("queue.requeues")
        self._mc_salvages = self._metrics.counter("queue.salvages")

    # ---- metrics seam ----------------------------------------------------

    def _sample_metrics(self, hb_age: Optional[float] = None) -> None:
        """Refresh the queue.* gauges from the spool census (+ the running
        job's heartbeat age when given) and give the exporter its periodic
        flush point. Pure host bookkeeping; called from the poll loops."""
        counts: dict = {}
        for js in self.spool.ordered():
            counts[js.state] = counts.get(js.state, 0) + 1
        for state in (QUEUED, CLAIM_WAIT, RUNNING, DONE, FAILED, SALVAGED):
            self._metrics.gauge("queue.jobs.%s" % state).set(
                counts.get(state, 0))
        if hb_age is not None:
            self._mg_hb_age.set(hb_age)
        self._m_writer.maybe_flush()

    # ---- heartbeat seam --------------------------------------------------

    def _default_hb_age(self, path: str, started_at: float) -> float:
        """Seconds of silence: since the last beat, or since spawn if the
        job never beat (backend init / first compile count against the
        deadline too — a job wedged before its first beat is still
        wedged)."""
        try:
            mtime = os.stat(path).st_mtime
        except OSError:
            mtime = started_at
        return max(0.0, self.clock() - max(mtime, started_at))

    # ---- recovery after a supervisor crash/restart -----------------------

    def recover(self) -> None:
        """Resume exactly where a dead supervisor stopped: claim-wait jobs
        (a state only journals of older supervisors hold) go back to
        queued — they never started; running jobs' processes
        are orphans — if the pid is still alive we must NOT start anything
        (one process per chip) and instead re-adopt by waiting for it to
        exit; a dead pid is salvaged and requeued."""
        for js in list(self.spool.pending()):
            if js.state == CLAIM_WAIT:
                self.spool.transition(js.spec.job, QUEUED,
                                      reason="supervisor restart")
            elif js.state == RUNNING:
                if js.pid and _pid_alive(js.pid):
                    self._log("job %s: orphan pid %d still alive from a "
                              "previous supervisor; terminating before "
                              "requeue (one process per chip)"
                              % (js.spec.job, js.pid))
                    _terminate_pid(js.pid, self.kill_grace_s, self.sleep)
                self._salvage_and_requeue(
                    js, reason="supervisor restart found job interrupted")

    # ---- running a single job --------------------------------------------

    def _job_env(self, js: JobState) -> dict:
        env = dict(os.environ)
        env.update(js.spec.env)
        env[HEARTBEAT_ENV] = self.spool.heartbeat_path(js.spec.job)
        env[STATUS_ENV] = self.spool.status_path(js.spec.job, js.attempt)
        # Flight recorder (ISSUE 6): every queued job writes its spans into
        # the round's obs/ log next to the queue dir, so obs_report.py can
        # join the journal with what each job was actually doing. An
        # explicit $OBS_SPAN_LOG (operator or job spec env) wins.
        env.setdefault(
            "OBS_SPAN_LOG",
            os.path.join(os.path.dirname(self.spool.root), "obs",
                         "spans.jsonl"))
        return env

    def _run_job(self, js: JobState) -> None:
        job = js.spec.job
        hb_path = self.spool.heartbeat_path(job)
        # a previous attempt's stale beat must not count for this one
        try:
            os.remove(hb_path)
        except OSError:
            pass
        started = self.clock()
        handle = self.spawn(js.spec, self._job_env(js),
                            self.spool.log_path(job, js.attempt))
        self.spool.transition(job, RUNNING, pid=getattr(handle, "pid", None),
                              started_at=started)
        self._log("job %s attempt %d/%d running (pid %s)"
                  % (job, js.attempt, js.spec.max_attempts,
                     getattr(handle, "pid", "?")))
        while True:
            rc = handle.poll()
            if rc is not None:
                self._finish_job(js, rc)
                self._sample_metrics(hb_age=0.0)
                return
            age = self._hb_age(hb_path, started)
            self._sample_metrics(hb_age=age)
            if age > js.spec.heartbeat_timeout_s:
                self._log("job %s heartbeat stale %.0fs (deadline %.0fs); "
                          "killing" % (job, age,
                                       js.spec.heartbeat_timeout_s))
                _terminate_handle(handle, self.kill_grace_s, self.sleep)
                self._salvage_and_requeue(
                    js, reason="heartbeat stale %.0fs" % age)
                return
            self.sleep(self.poll_s)

    def _finish_job(self, js: JobState, rc: int) -> None:
        job = js.spec.job
        status = read_heartbeat(self.spool.status_path(job, js.attempt))
        if rc == 0 and (status is None or status.get("ok", True)):
            self.spool.transition(job, DONE, rc=rc)
            self._log("job %s done" % job)
            return
        # classification: the status file wins; then the exit-code
        # contract; log text is never scraped (that's the point)
        if status is not None and status.get("error_class"):
            klass = status["error_class"]
        elif rc == EXIT_TRANSIENT:
            klass = "transient"
        elif status is not None and status.get("error"):
            klass = classify_error_text(str(status["error"]))
        else:
            klass = "permanent"
        err = (status or {}).get("error", "exit code %d" % rc)
        if klass == "transient":
            self._salvage_and_requeue(js, reason="transient failure: %s"
                                      % str(err)[:200], rc=rc)
        else:
            self.spool.transition(job, FAILED, rc=rc,
                                  error=str(err)[:500],
                                  error_class=klass)
            self._log("job %s FAILED permanently: %s" % (job, err))

    # ---- salvage + requeue ----------------------------------------------

    def _salvage(self, js: JobState) -> list:
        """Which declared artifacts survived (tpu_sweep's per-config flush
        and the tmp+rename writes make partials trustworthy)."""
        found = []
        base = js.spec.cwd or os.getcwd()
        for pattern in js.spec.artifacts:
            for path in sorted(glob.glob(os.path.join(base, pattern))):
                try:
                    st = os.stat(path)
                    found.append({"path": os.path.relpath(path, base),
                                  "bytes": st.st_size,
                                  "mtime": st.st_mtime})
                except OSError:
                    continue
        return found

    def _backoff_s(self, attempt: int, spec) -> float:
        """Capped exponential with jitter: base * 2^(attempt-1), capped,
        +0-25% jitter so a fleet of requeues cannot synchronize."""
        raw = min(spec.backoff_cap_s,
                  spec.backoff_base_s * (2 ** max(0, attempt - 1)))
        return raw * (1.0 + 0.25 * self.rng())

    def _salvage_and_requeue(self, js: JobState, reason: str,
                             rc: Optional[int] = None) -> None:
        job = js.spec.job
        salvaged = self._salvage(js)
        self._mc_salvages.inc()
        self.spool.transition(job, SALVAGED, reason=reason, rc=rc,
                              salvaged_artifacts=salvaged)
        self._log("job %s salvaged (%d artifact(s) survived): %s"
                  % (job, len(salvaged), reason))
        if js.attempt >= js.spec.max_attempts:
            self.spool.transition(job, FAILED, error="attempt budget "
                                  "exhausted after: %s" % reason,
                                  error_class="transient")
            self._log("job %s FAILED: attempt budget (%d) exhausted"
                      % (job, js.spec.max_attempts))
            return
        delay = self._backoff_s(js.attempt, js.spec)
        self._mc_requeues.inc()
        self.spool.transition(job, QUEUED, attempt=js.attempt + 1,
                              not_before=self.clock() + delay,
                              reason=reason)
        self._log("job %s requeued (attempt %d/%d) with %.0fs backoff"
                  % (job, js.attempt, js.spec.max_attempts, delay))

    # ---- the loop --------------------------------------------------------

    def run(self) -> dict:
        """Drain the queue, one job at a time. Returns a summary."""
        self.recover()
        self._sample_metrics()
        while True:
            job = self.spool.next_runnable(self.clock())
            if job is None:
                pending = self.spool.pending()
                if not pending:
                    break
                gate = self.spool.earliest_gate()
                if gate is None:
                    break  # only non-queued pendings: nothing left to do
                self.sleep(max(self.poll_s,
                               min(gate - self.clock(), 30.0)))
                continue
            self._run_job(job)
        self._sample_metrics()
        self._m_writer.maybe_flush(force=True)
        return self.summary()

    def summary(self) -> dict:
        return {"jobs": {js.spec.job: {"state": js.state,
                                       "attempt": js.attempt}
                         for js in self.spool.ordered()}}


# ---- process plumbing ----------------------------------------------------

def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _terminate_pid(pid: int, grace_s: float, sleep) -> None:
    try:
        os.kill(pid, 15)
    except OSError:
        return
    deadline = time.time() + grace_s
    while time.time() < deadline:
        if not _pid_alive(pid):
            return
        sleep(0.2)
    try:
        os.kill(pid, 9)
    except OSError:
        pass


def _terminate_handle(handle, grace_s: float, sleep) -> None:
    """SIGTERM first (jobs flush on it), SIGKILL after the grace."""
    try:
        handle.terminate()
    except OSError:
        pass
    waited = 0.0
    while waited < grace_s:
        if handle.poll() is not None:
            return
        sleep(0.2)
        waited += 0.2
    try:
        handle.kill()
    except OSError:
        pass
    # collect: poll until it reaps (bounded — a kill -9 cannot be ignored)
    for _ in range(50):
        if handle.poll() is not None:
            return
        sleep(0.1)
