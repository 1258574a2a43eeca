"""Multi-process process-group plumbing + the compile/execute barrier law.

The reference launches one process per GPU with `mp.spawn` + NCCL
(/root/reference/train.py:23-45); here one process per HOST joins a
`jax.distributed` coordination service and all devices form one mesh
(parallel/mesh.py). This module holds the pieces of that lifecycle that
every multi-process entry point (tests/distributed_worker.py, scaling.py's
multi-process rows, a real pod launch) must share — they were folklore
inlined in the test worker until ISSUE 11 promoted them to API:

* `use_gloo_cpu_collectives()` — names the CPU client's cross-process
  collective implementation explicitly (multi-process CPU runs: the test
  suite and scaling.py's --cpu multiproc rows).
* `init_process_group()` — the idempotent `jax.distributed.initialize`
  rendezvous (keeps the reference's tcp://host:port convention via
  `parallel.init_distributed`, which delegates here).
* `coordination_barrier()` — the coordination-service barrier (gRPC). The
  PUBLIC `sync_global_devices` would create a fresh Gloo context with its
  own hard 30 s KeyValue-exchange deadline — exactly the failure this
  barrier exists to avoid — so jax's own coordination client is used
  (jax has no public handle to it). A barrier that times out (a dead/stuck
  rank — the worker-death failure mode) raises a `DEADLINE_EXCEEDED:`-
  prefixed RuntimeError, which `runtime.errors.is_transient_backend_error`
  classifies TRANSIENT: the job supervisor requeues the run instead of the
  surviving ranks hanging in a half-dead rendezvous forever.
* `barrier_synced_compile()` — THE barrier law (CLAUDE.md Gloo pitfall,
  now enforced API + graftlint rule `ast/unbarriered-collective-start`):
  every compiled multi-process program creates its own fresh Gloo context
  at FIRST execution (keys cpu:gloo/<devices>/1, /2, ...) whose KeyValue
  exchange carries a hard 30 s deadline, but per-rank compile times on a
  loaded box skew by minutes — so AOT-compile first, realign every rank at
  the coordination barrier, and only then execute: the first execution
  starts within milliseconds on every rank.
"""

from __future__ import annotations

from typing import Optional

import jax

_INITIALIZED = False

# Barrier names must be unique per (program, use); the helpers suffix a
# caller-chosen name so two compiles in one run cannot collide.
DEFAULT_BARRIER_TIMEOUT_S = 15 * 60.0


def use_gloo_cpu_collectives() -> None:
    """Select the Gloo CPU cross-process collective backend (call BEFORE
    first backend use)."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init_process_group(coordinator_address: str, num_processes: int,
                       process_id: int) -> None:
    """Idempotent `jax.distributed.initialize` (≡ reference
    `dist.init_process_group`, ref train.py:42-45). No-op for world size 1
    and for repeat calls within a process (train() and evaluate() both
    rendezvous at their top; a driver composing them must not
    double-initialize)."""
    global _INITIALIZED
    if num_processes <= 1 or _INITIALIZED:
        return
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True


def _coordination_client():
    """The process's coordination-service client.

    jax's own (private) handle on purpose: the public sync_global_devices
    would recreate the Gloo 30 s deadline this barrier works around (see
    module docstring)."""
    from jax._src import distributed
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "no coordination client: jax.distributed.initialize has not "
            "run in this multi-process job (parallel.init_process_group)")
    return client


def coordination_barrier(name: str,
                         timeout_s: float = DEFAULT_BARRIER_TIMEOUT_S,
                         tracer=None) -> None:
    """Realign every process at the coordination service's `name` barrier.

    Single-process runs are a no-op (no coordination client exists). A
    timeout means some rank never arrived — the worker-death failure mode
    — and is re-raised as a `DEADLINE_EXCEEDED:` RuntimeError so the
    shared classifier (runtime/errors.py) reads it as TRANSIENT and the
    job supervisor requeues instead of the survivors hanging."""
    if jax.process_count() <= 1:
        return
    client = _coordination_client()
    span = (tracer.span("scale:barrier", program=name) if tracer is not None
            else None)
    try:
        if span is not None:
            with span:
                client.wait_at_barrier(name,
                                       timeout_in_ms=int(timeout_s * 1000))
        else:
            client.wait_at_barrier(name, timeout_in_ms=int(timeout_s * 1000))
    except RuntimeError:
        raise  # our own _coordination_client error: already actionable
    except Exception as e:  # noqa: BLE001 — barrier failures vary by version
        raise RuntimeError(
            "DEADLINE_EXCEEDED: coordination barrier %r did not clear in "
            "%.0fs — a rank died or wedged before arriving (%s). This is "
            "transient for the job supervisor: requeue/restart the whole "
            "multi-process job rather than waiting on a half-dead "
            "rendezvous." % (name, timeout_s,
                             str(e).splitlines()[0][:200])) from e


def barrier_synced_compile(jitted, args, name: str,
                           timeout_s: float = DEFAULT_BARRIER_TIMEOUT_S,
                           tracer=None):
    """AOT-compile `jitted` on example `args`, then BARRIER, then return
    the compiled executable — the only legal way to start a compiled
    collective program in a multi-process run (see module docstring; the
    graftlint rule `ast/unbarriered-collective-start` enforces it).

    `tracer` (obs/spans.py, optional): the compile and barrier phases land
    in the flight recorder as `scale:compile` / `scale:barrier` spans —
    per-rank compile skew is exactly the number a post-mortem needs."""
    if tracer is not None:
        with tracer.span("scale:compile", program=name):
            compiled = jitted.lower(*args).compile()
    else:
        compiled = jitted.lower(*args).compile()
    coordination_barrier("compiled:%s" % name, timeout_s=timeout_s,
                         tracer=tracer)
    return compiled
