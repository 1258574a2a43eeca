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

Also home to the runtime recompile counter: `jax.monitoring` listeners on
XLA's backend-compile event. Caveats (docs/ARCHITECTURE.md): the count is
per-process and includes every backend compile jax performs (internal
jits — `jnp.copy` helpers, donation snapshots — count too). A program
found in the persistent compile cache still fires the event (its duration
is then the retrieval time) and is counted in `cache_hits` as well, so
`count - cache_hits` is what XLA actually compiled.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Sequence

import jax.numpy as jnp

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
# runtime recompile counter

class RecompileCounter:
    """Count of backend-compile events observed since `install` (see the
    module docstring's caveats). `last_dur_s` is the most recent compile's
    duration; `cache_hits` of them were served by the persistent cache."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        self.total_s = 0.0
        self.last_dur_s: Optional[float] = None

    def _on_event(self, dur_s: float) -> None:
        self.count += 1
        self.total_s += float(dur_s)
        self.last_dur_s = float(dur_s)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def install_recompile_counter(tracer=None) -> RecompileCounter:
    """Register a jax.monitoring listener counting backend compiles; when
    `tracer` is an enabled SpanTracer each compile also lands as a
    `compile` span (the flight recorder's recompile evidence). Returns the
    live counter. Each call installs an independent counter (jax has no
    public unregister; listeners are tiny)."""
    import jax.monitoring as monitoring
    counter = RecompileCounter()

    def listen(name: str, dur_s: float, **kw) -> None:
        if name != _COMPILE_EVENT:
            return
        counter._on_event(dur_s)
        if tracer is not None and getattr(tracer, "enabled", False):
            tracer.record("compile", dur_s, seq=counter.count)

    def listen_hit(name: str, **kw) -> None:
        if name == _CACHE_HIT_EVENT:
            counter.cache_hits += 1

    monitoring.register_event_duration_secs_listener(listen)
    monitoring.register_event_listener(listen_hit)
    return counter
