"""Liveness signals: in-process stall warnings + cross-process heartbeats.

The reference has no liveness detection (SURVEY.md §5 — a wedged run just
sits there); both views here are new capability.

Two views of the same contract:

* `HangWatchdog` (moved here from train.py, re-exported there) watches the
  CURRENT process: warn (with thread stacks) when no progress beat arrives
  for `warn_seconds`. It cannot unstick a wedged transport, but it turns a
  silent stall into a diagnosable one.
* `FileHeartbeat` makes those beats visible to a SUPERVISING process
  (`runtime/supervisor.py`): every beat atomically rewrites a small JSON
  file whose mtime is the liveness signal. The supervisor SIGTERMs a job
  whose file goes stale past the job's deadline and salvages its flushed
  partial artifacts — the recovery the in-process watchdog cannot perform
  (it dies with the process; the file survives).

Job-side wiring is env-based so every enqueueable script shares one line:
`hb = maybe_job_heartbeat()` returns a real FileHeartbeat when
$TPU_QUEUE_HEARTBEAT names a path (i.e. the job runs under
scripts/tpu_queue.py) and an inert stub otherwise — unsupervised runs pay
nothing. `write_job_status` is the matching exit contract: one JSON file
at $TPU_QUEUE_STATUS the supervisor reads instead of log-scraping.

Stdlib-only: imported by the supervisor/CLI, which must never initialize
a JAX backend.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

HEARTBEAT_ENV = "TPU_QUEUE_HEARTBEAT"
STATUS_ENV = "TPU_QUEUE_STATUS"


def _atomic_write_text(path: str, text: str) -> None:
    """tmp + os.replace so a reader (or a crash) never sees a torn file.

    A stdlib-only twin of utils.atomic_write_bytes: runtime/ must stay
    importable without numpy/PIL (supervisor processes never build the
    ML stack), so it cannot import utils."""
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:  # graftlint: off=raw-artifact-write
        f.write(text)
    os.replace(tmp, path)


class FileHeartbeat:
    """Per-job heartbeat file: `beat(label)` atomically rewrites
    `{"t": wall, "pid": ..., "label": ...}`; the file's mtime is what the
    supervisor watches (content is for the human reading a postmortem).

    Beats also land as `heartbeat` EVENTS in the flight-recorder span log
    when one is configured ($OBS_SPAN_LOG — obs/spans.py, stdlib like this
    module): the heartbeat file keeps only the LAST beat, the span log
    keeps them all, so a postmortem can see the job's whole progress
    timeline, not just where it died (ISSUE 6)."""

    def __init__(self, path: str, tracer=None):
        self.path = path
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        if tracer is None:
            # lazy sibling import: obs.spans is stdlib-only by contract
            from ..obs.spans import maybe_tracer
            tracer = maybe_tracer()
        self._tracer = tracer

    def beat(self, label: str = "beat") -> None:
        try:
            _atomic_write_text(self.path, json.dumps(
                {"t": time.time(), "pid": os.getpid(), "label": str(label)}))
        except OSError:
            # liveness reporting must never kill the job doing the work
            pass
        if getattr(self._tracer, "enabled", False):
            self._tracer.event("heartbeat", label=str(label))


class _NoopHeartbeat:
    """Inert stand-in when the process is not running under the queue."""

    path = None

    def beat(self, label: str = "beat") -> None:
        pass


def maybe_job_heartbeat(env: Optional[dict] = None):
    """FileHeartbeat bound to $TPU_QUEUE_HEARTBEAT, or an inert stub."""
    path = (env if env is not None else os.environ).get(HEARTBEAT_ENV)
    return FileHeartbeat(path) if path else _NoopHeartbeat()


def read_heartbeat(path: str) -> Optional[dict]:
    """Last beat record, or None (absent / torn / not yet beaten)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def heartbeat_age_s(path: str, now: Optional[float] = None) -> Optional[float]:
    """Seconds since the file was last touched; None when it never was.
    mtime-based: robust even if the writer died mid-beat."""
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    return max(0.0, (time.time() if now is None else now) - mtime)


def write_job_status(ok: bool, error: str = "", error_class: str = "",
                     extra: Optional[dict] = None,
                     env: Optional[dict] = None) -> None:
    """Machine-readable exit status at $TPU_QUEUE_STATUS (no-op when the
    job is unsupervised). The supervisor prefers this file over exit-code
    guessing; `error_class` follows runtime.errors ('transient' or
    'permanent')."""
    path = (env if env is not None else os.environ).get(STATUS_ENV)
    if not path:
        return
    rec = {"ok": bool(ok), "t": time.time(), "pid": os.getpid()}
    if error:
        rec["error"] = str(error)[:500]
    if error_class:
        rec["error_class"] = error_class
    if extra:
        rec.update(extra)
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        _atomic_write_text(path, json.dumps(rec))
    except OSError:
        pass


def run_as_job(main_fn) -> None:
    """Exit shim for enqueueable scripts (tpu_sweep, mfu_breakdown,
    runner_drive): run `main_fn`, write the machine-readable
    $TPU_QUEUE_STATUS file, and map failures onto the exit-code contract
    (0 done / EXIT_TRANSIENT transient / 1 permanent). bench.py has its
    own wrapper because it must additionally keep its ONE-JSON-line
    promise on the error path."""
    from .errors import EXIT_TRANSIENT, classify_exception
    try:
        main_fn()
    except KeyboardInterrupt:
        raise
    except SystemExit as e:
        if e.code in (None, 0):
            write_job_status(True)
            raise
        if isinstance(e.code, int):
            write_job_status(False, error="exit code %d" % e.code,
                             error_class="permanent")
            raise
        # string SystemExits are the scripts' own refusals (a bad flag
        # value, a missing input): retrying changes nothing
        write_job_status(False, error=str(e.code), error_class="permanent")
        raise SystemExit(1) from e
    except Exception as e:  # noqa: BLE001 — classified, not swallowed
        klass = classify_exception(e)
        head = str(e).splitlines()[0] if str(e) else repr(e)
        write_job_status(False, error="%s: %s" % (type(e).__name__, head),
                         error_class=klass)
        raise SystemExit(EXIT_TRANSIENT if klass == "transient"
                         else 1) from e
    else:
        write_job_status(True)


class HangWatchdog:
    """Background failure detector: warns (with thread stacks) when no
    progress beat arrives for `warn_seconds`.

    The reference has no failure detection (SURVEY.md §5); this exists
    because remote accelerator transports can wedge mid-run with the
    process stuck in an uninterruptible wait — the watchdog cannot unstick
    it, but it turns a silent stall into a diagnosable one (and tells the
    operator the last good step, so they know which checkpoint to salvage).

    `beat_file` (new): mirror every beat into a FileHeartbeat so a job
    supervisor can watch this process from outside. Pause/resume beat the
    file too — a legitimate slow phase (checkpoint save) must read as
    alive to the supervisor exactly as it reads as non-stalled in here.
    """

    def __init__(self, warn_seconds: float, where: str = "train",
                 beat_file: Optional[str] = None):
        import threading
        self.warn_seconds = float(warn_seconds)
        self.where = where
        # _mu guards the beat state shared with the watchdog thread
        # (_beat/_label/_warned/_paused/_status_fn): beat() racing _run()
        # could lose a pause flag or re-arm a warning mid-print
        # (lock/unguarded-shared-write — graftlint layer 3)
        self._mu = threading.Lock()
        self._beat = time.monotonic()  # immune to wall-clock NTP steps
        self._label = "start"
        self._stop = threading.Event()
        self._warned = False
        self._paused = False
        self._thread = None
        self._status_fn = None
        self._file = FileHeartbeat(beat_file) if beat_file else None
        if self._file is not None:
            self._file.beat("start")
        if self.warn_seconds > 0:
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

    def set_status_fn(self, fn) -> None:
        """Attach a () -> str status provider whose output is appended to
        every warning — e.g. the process loader's per-worker heartbeat
        ages (`ProcessBatchLoader.worker_status`), so a stall can be
        attributed to the input pipeline vs the device transport at a
        glance."""
        with self._mu:
            self._status_fn = fn

    def beat(self, label: str) -> None:
        with self._mu:
            self._beat = time.monotonic()
            self._label = label
            self._warned = False
        if self._file is not None:
            self._file.beat(label)

    def pause(self, label: str) -> None:
        """Suspend warnings across a known-slow operation (checkpoint save:
        a full-state device_get can legitimately take minutes on a slow
        transport). A point beat only resets the clock; pause holds it."""
        with self._mu:
            self._paused = True
            self._label = label
        if self._file is not None:
            self._file.beat("paused: %s" % label)

    def resume(self, label: str) -> None:
        with self._mu:
            self._paused = False
        self.beat(label)

    def _run(self) -> None:
        import faulthandler
        import sys
        while not self._stop.wait(min(30.0, self.warn_seconds / 4)):
            # snapshot + decide under the lock; warn (print, status
            # callback, stack dump) OUTSIDE it — slow I/O must not stall
            # a beating trainer on the mutex
            with self._mu:
                stalled = time.monotonic() - self._beat
                paused, label = self._paused, self._label
                status_fn = self._status_fn
                fire = (stalled > self.warn_seconds and not self._warned
                        and not paused)
                if fire:
                    self._warned = True
            if paused and self._file is not None:
                # a paused watchdog is a process that DECLARED itself busy,
                # not a dead one: keep the external heartbeat alive so the
                # supervisor's stale-kill deadline only fires on real hangs
                self._file.beat("paused: %s" % label)
            if fire:
                extra = ""
                if status_fn is not None:
                    try:
                        extra = " | " + str(status_fn())
                    except Exception:  # noqa: BLE001 — status is best-effort
                        pass
                print("%s: WATCHDOG: no %s progress for %.0fs (last: %s) — "
                      "the device transport may be wedged; if this "
                      "persists, kill and resume from the last checkpoint%s"
                      % (time.ctime(), self.where, stalled, label, extra),
                      flush=True)
                try:  # where is the main thread stuck? (needs a real fd —
                    faulthandler.dump_traceback(file=sys.__stderr__)
                except Exception:  # absent under captured/redirected stderr
                    pass

    def stop(self) -> None:
        self._stop.set()
