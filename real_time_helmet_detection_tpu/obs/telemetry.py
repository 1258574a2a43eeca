"""In-jit step telemetry: norms + per-component losses, zero extra D2H.

The reference logs only its four loss scalars, fetched synchronously every
step (ref train.py:104-140, loss.py:27-30); it has no gradient/update/param
norm visibility at all. Here the extra scalars are computed INSIDE the
jitted train step (guarded by `--telemetry`, off by default) and ride the
SAME fetch as the loss:

* per-step dispatch path (train_epoch): the scalars join the `losses` dict
  the step already returns — the deferred print-interval flush fetches
  them in its existing single `device_get`;
* scanned path (bench/scaling, `make_scanned_train_fn`): the scalars are
  pushed into a fixed-shape RING BUFFER carried through the scan carry and
  returned next to the last-loss scalar — one D2H for the whole scan, a
  few KiB.

With `--telemetry` off nothing here is traced: the step program is the
PRE-PR program and the loss is bit-identical (pinned by
tests/test_obs.py on the 8-device mesh).

Also home to the process's ONE compile listener: `jax.monitoring`
listeners on jax's three compile stages (tracing to a jaxpr, lowering to
MLIR, XLA's backend compile). Each event lands in the default tracer's
ring (obs/spans.py) as a `compile` span with `stage=trace|lower|backend`,
`cache_hit` and `fun`, stamped `t0 = now - dur`: a compile inside a
measured window is a span with a time, in every entry point. It is
installed (idempotently) by `train.make_step_runner` and
`ServingEngine.__init__`; `install_recompile_counter()` is a view over it
that counts backend compiles from the call on. Caveats
(docs/ARCHITECTURE.md): the count is per-process and includes every
backend compile jax performs (internal jits — `jnp.copy` helpers,
donation snapshots — count too). A program found in the persistent
compile cache still fires the event (its duration is then the retrieval
time) and is counted in `cache_hits` as well, so `count - cache_hits` is
what XLA actually compiled.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, Mapping, Optional, Sequence

import jax.numpy as jnp

from .spans import default_tracer

# The scalars the ring carries, in row order. The first four mirror
# LossLog.KEYS (ops/loss.py); the last three are the in-jit norms.
SCAN_TELEMETRY_KEYS = ("hm", "offset", "size", "total",
                       "grad_norm", "update_norm", "param_norm")
NORM_KEYS = ("grad_norm", "update_norm", "param_norm")

DEFAULT_RING_CAPACITY = 64


def telemetry_scalars(grads, old_params, new_params) -> Dict[str, jnp.ndarray]:
    """Global-l2 grad/update/param norms as f32 scalars (traced inside the
    step; ~one extra pass over the param tree, only when --telemetry)."""
    import jax
    import optax
    update = jax.tree.map(lambda n, o: n - o, new_params, old_params)
    return {
        "grad_norm": optax.global_norm(grads).astype(jnp.float32),
        "update_norm": optax.global_norm(update).astype(jnp.float32),
        "param_norm": optax.global_norm(new_params).astype(jnp.float32),
    }


# ---------------------------------------------------------------------------
# the telemetry ring (scan-carry resident)

def ring_init(capacity: int = DEFAULT_RING_CAPACITY,
              nkeys: int = len(SCAN_TELEMETRY_KEYS)) -> dict:
    """Fixed-shape ring: {(C, K) f32 buffer, scalar int32 write count}.
    Fixed shapes are non-negotiable under jit (CLAUDE.md); the ring keeps
    the fetched payload bounded no matter the scan length."""
    return {"buf": jnp.zeros((capacity, nkeys), jnp.float32),
            "n": jnp.zeros((), jnp.int32)}


def ring_push(ring: dict, scalars: Sequence) -> dict:
    """Append one row (oldest row overwritten once full). Pure; safe in a
    scan body."""
    cap = ring["buf"].shape[0]
    row = jnp.stack([jnp.asarray(s, jnp.float32) for s in scalars])
    return {"buf": ring["buf"].at[ring["n"] % cap].set(row),
            "n": ring["n"] + 1}


def ring_to_host(ring_host: Mapping,
                 keys: Sequence[str] = SCAN_TELEMETRY_KEYS) -> Dict[str, list]:
    """Decode an ALREADY-FETCHED ring (numpy, post-device_get) into
    chronological per-key lists. Host-side numpy only — calling this with
    device arrays would hide a D2H."""
    import numpy as np
    buf = np.asarray(ring_host["buf"])
    n = int(ring_host["n"])
    cap = buf.shape[0]
    m = min(n, cap)
    idx = (np.arange(n - m, n) % cap) if m else np.zeros((0,), np.int64)
    rows = buf[idx]
    return {k: [float(v) for v in rows[:, j]] for j, k in enumerate(keys)}


# ---------------------------------------------------------------------------
# the compile listener and its counter views

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CompileListener:
    """Process-wide totals of the backend-compile events, fed by the two
    `jax.monitoring` callbacks below. jax fires the cache-hit event inside
    the backend compile it belongs to, on the compiling thread, before
    that compile's duration event: a thread-local flag pairs them."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        self.total_s = 0.0
        self.last_dur_s: Optional[float] = None
        self.file_tracers = weakref.WeakSet()  # mirror backend compiles
        self._thread = threading.local()

    def on_event(self, name: str, **kw) -> None:
        if name == _CACHE_HIT_EVENT:
            self.cache_hits += 1
            self._thread.hit = True

    def on_duration(self, name: str, dur_s: float, **kw) -> None:
        stage = _STAGE_OF_EVENT.get(name)
        if stage is None:
            return
        dur_s = float(dur_s)
        if stage != "backend":
            default_tracer().record("compile", dur_s, stage=stage,
                                    fun=kw.get("fun_name"))
            return
        hit = getattr(self._thread, "hit", False)
        self._thread.hit = False
        self.count += 1
        self.total_s += dur_s
        self.last_dur_s = dur_s
        default_tracer().record("compile", dur_s, stage=stage,
                                cache_hit=hit, fun=kw.get("fun_name"),
                                seq=self.count)
        for tracer in list(self.file_tracers):
            # the span log keeps one `compile` line a backend compile,
            # as before the ring (obs_report counts them)
            tracer._write_span("compile", time.time(), None, dur_s,
                               {"seq": self.count})


_LISTENER: Optional[_CompileListener] = None  # guarded-by: _INSTALL_LOCK
_INSTALL_LOCK = threading.Lock()


def install_compile_listener() -> _CompileListener:
    """Register the process's compile listener with `jax.monitoring`, once
    however often this is called (jax keeps every registered callback for
    the life of the process)."""
    global _LISTENER
    with _INSTALL_LOCK:
        if _LISTENER is None:
            import jax.monitoring as monitoring
            listener = _CompileListener()
            monitoring.register_event_duration_secs_listener(
                listener.on_duration)
            monitoring.register_event_listener(listener.on_event)
            _LISTENER = listener
        return _LISTENER


class RecompileCounter:
    """Backend-compile events observed since this counter was made (see
    the module docstring's caveats): a view over the one listener.
    `last_dur_s` is the most recent compile's duration; `cache_hits` of
    them were served by the persistent cache."""

    def __init__(self, listener: _CompileListener):
        self._listener = listener
        self._count0 = listener.count
        self._hits0 = listener.cache_hits
        self._total0 = listener.total_s

    @property
    def count(self) -> int:
        return self._listener.count - self._count0

    @property
    def cache_hits(self) -> int:
        return self._listener.cache_hits - self._hits0

    @property
    def total_s(self) -> float:
        return self._listener.total_s - self._total0

    @property
    def last_dur_s(self) -> Optional[float]:
        return self._listener.last_dur_s if self.count else None


def install_recompile_counter(tracer=None) -> RecompileCounter:
    """A counter of backend compiles from now on. When `tracer` writes a
    span log, each backend compile is also mirrored there as a `compile`
    line (the flight recorder's recompile evidence)."""
    listener = install_compile_listener()
    if tracer is not None and getattr(tracer, "enabled", False):
        listener.file_tracers.add(tracer)
    return RecompileCounter(listener)
