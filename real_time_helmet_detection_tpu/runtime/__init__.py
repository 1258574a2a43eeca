"""Job-level runtime supervision (ISSUE 3).

Stdlib-only package: importable — and fully CPU-testable — without
initializing any JAX backend. The in-process robustness layer
(HangWatchdog, FaultInjector, --auto-resume in train.py) stops at the
process boundary; this package supervises the *jobs*:

* `compile_cache` — where the persistent XLA compile cache lives
* `errors`     — one transient-vs-permanent classifier for all layers
* `faults`     — deterministic seeded fault injection (the chaos layer
                 the self-healing serving/train paths are tested against)
* `heartbeat`  — HangWatchdog (in-process) + FileHeartbeat (cross-process)
* `spool`      — persistent fsynced JSON-lines job journal
* `supervisor` — serial job runner: hang-kill-salvage, backoff requeue

CLI: `scripts/tpu_queue.py` (docs/ARCHITECTURE.md "Failure domains &
supervision").
"""

from .compile_cache import use_compile_cache  # noqa: F401
from .errors import (EXIT_TRANSIENT, InjectedBackendError,  # noqa: F401
                     TrainingDivergenceError, classify_error_text,
                     classify_exception, is_transient_backend_error)
from .faults import (ALL_SITES, FAULT_KINDS, FLEET_SITES,  # noqa: F401
                     SERVE_SITES, TRAIN_SITES, ChaosInjector, FaultEvent,
                     FaultSchedule, maybe_injector)
from .heartbeat import (FileHeartbeat, HangWatchdog,  # noqa: F401
                        heartbeat_age_s, maybe_job_heartbeat,
                        read_heartbeat, run_as_job, write_job_status)
from .spool import (CLAIM_WAIT, DONE, FAILED, QUEUED,  # noqa: F401
                    RUNNING, SALVAGED, JobSpec, JobState, Spool)
from .supervisor import Supervisor  # noqa: F401
