"""Transient-vs-permanent failure classification, shared process-wide.

The reference has no failure classification (SURVEY.md §5; its only
recovery is the manual restart of ref train.py:190-199).

One definition used by three layers so they cannot drift:

* `train.py --auto-resume` (in-process recovery) classifies the caught
  exception object;
* `bench.py` and the other enqueueable scripts classify the exception
  they are dying with into the machine-readable JSON/status line;
* the job supervisor (`runtime/supervisor.py`) classifies a dead job's
  status file / exit code without log-scraping.

Stdlib-only on purpose: the supervisor and `scripts/tpu_queue.py` must be
importable (and CPU-testable) without initializing any JAX backend.
"""

from __future__ import annotations

# Status markers that identify a device/transport failure worth retrying
# (vs a programming error, which must propagate). XLA status-prefix form
# ("UNAVAILABLE: ...") rather than bare substrings: a genuine programming
# error whose message merely contains the word "connection" (e.g. a
# data-loader connection-string bug) must NOT trigger restore-and-retry
# (round-2 advisor finding). Matched against JaxRuntimeError/RuntimeError.
#
# `INTERNAL:` is deliberately NOT here. It is XLA's generic assertion
# bucket and it is how a Mosaic kernel the compiler refuses is reported
# ("INTERNAL: Mosaic failed to compile TPU kernel: ..."): retrying —
# --auto-resume, ServingEngine's requeue, the supervisor's backoff — would
# recompile the same kernel to the same refusal. A compile error is
# permanent.
TRANSIENT_MARKERS = ("UNAVAILABLE:", "DEADLINE_EXCEEDED:",
                     "Unable to initialize backend", "Socket closed")

# Exit-code contract for enqueueable TPU jobs (bench.py, tpu_sweep.py,
# mfu_breakdown.py, runner_drive.py): 0 = done, EXIT_TRANSIENT = the
# backend failed in a way a later retry may survive (EX_TEMPFAIL from
# sysexits.h — conventional "try again"), anything else = permanent.
EXIT_TRANSIENT = 75


class InjectedBackendError(RuntimeError):
    """Synthetic transient backend failure raised by FaultInjector."""


class TrainingDivergenceError(RuntimeError):
    """Sustained numeric divergence detected by the train sentinel
    (ISSUE 9): >= cfg.sentinel_divergence consecutive steps tripped the
    in-jit NaN/Inf/grad-spike check. NOT a backend failure — the device
    is healthy, the numerics are not — so it is deliberately NOT
    transient for `is_transient_backend_error` (a backend re-init would
    not help); train() handles it with its own checkpoint-rollback
    branch, bounded by cfg.sentinel_rollbacks."""


def is_transient_backend_error(e: BaseException) -> bool:
    """Would retrying after a backend re-init plausibly succeed?"""
    if isinstance(e, InjectedBackendError):
        return True
    if type(e).__name__ not in ("JaxRuntimeError", "RuntimeError"):
        return False
    return any(m in str(e) for m in TRANSIENT_MARKERS)


def classify_exception(e: BaseException) -> str:
    """'transient' | 'permanent' for status lines and job status files."""
    return "transient" if is_transient_backend_error(e) else "permanent"


def classify_error_text(text: str) -> str:
    """Best-effort classification when only message TEXT survives (a job
    log tail, a status file written by an older script): the same
    status-prefix markers, without the exception-type check."""
    return ("transient" if any(m in text for m in TRANSIENT_MARKERS)
            else "permanent")
