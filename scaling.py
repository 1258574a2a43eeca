"""Data-parallel scaling harness: strong + weak curves, sharding efficiency,
and the real multi-process path (ISSUE 11 tentpole instrument).

The reference has no scaling measurement at all — its DDP launcher (ref
train.py:23-45) scales but nothing records how well. This harness measures
three things per device count N and writes ONE schema-tagged artifact
(`scaling-v2`, default `artifacts/<round>/scaling.json`) that perfgate.py
ratchet-gates like every other perf claim:

* **weak scaling** — fixed per-chip batch, global batch N*pc: `img/s/chip`
  and `weak_efficiency` vs the 1-device run (the FireCaffe curve; a REAL
  hardware signal only on a real multi-chip slice);
* **sharding efficiency** — the same global batch run N-way sharded vs
  UNSHARDED on one device: the overhead of the partitioned program
  (collective layout, halo exchange, reshape traffic) isolated from host
  contention — the number that IS meaningful on the virtual CPU mesh,
  where N virtual devices share the same cores and raw img/s/chip
  necessarily collapses as 1/N;
* **strong scaling** — fixed global batch (max_devices * pc) across N:
  `speedup` and per-chip `strong_efficiency`.

The **multi-process path** (`--only multiproc`, world `--processes`, ≥2
real processes by default) runs the identical measurement through the full
production lifecycle: `parallel.init_process_group` rendezvous, Gloo CPU
collectives, per-process local-shard global-batch assembly (`shard_batch`'s
`make_array_from_process_local_data` branch) and the
`parallel.barrier_synced_compile` AOT-compile -> coordination-barrier ->
execute law (CLAUDE.md's Gloo 30 s pitfall as enforced API).

Timing methodology matches bench.py: `iters` steps are scanned INSIDE one
jitted program with an inter-step data dependency, only a scalar is
fetched, and the separately-measured dispatch overhead is subtracted.
Compile/barrier/step phases land in the flight recorder as
`scale:compile`/`scale:barrier`/`scale:step` spans ($OBS_SPAN_LOG), which
obs_report.py's Scaling section joins against this artifact.

Backend: rows run on the accelerator, one fresh child process per row —
this parent never touches JAX, so each child is the only process on the
chips. A child that finds no accelerator, or fewer devices than its row
needs, fails its row. `--cpu` asks for virtual CPU devices instead (the
only home of the multi-process Gloo rows).

Resume: every measured row flushes immediately (atomic save_json), reruns
skip already-measured rows (`--force` remeasures), and `--only
weak,strong,multiproc` narrows a run — the tpu_sweep per-config-flush
contract, so a killed chip job salvages its partial curve.

Usage:
  python scaling.py --devices 1 2 4      # weak + strong rows on the chips
  python scaling.py --cpu                # full plan on virtual CPU devices
  python scaling.py --cpu --only multiproc   # just the 2-process rows
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SCHEMA = "scaling-v2"

NOTE = ("rows with hardware_signal=false ran on virtual CPU devices "
        "sharing host cores: their weak/strong efficiencies read host "
        "contention, NOT hardware scaling — sharding_efficiency (sharded "
        "vs unsharded program at the SAME global batch) is the CPU-valid "
        "signal; efficiencies are computed within one config only")


def log(msg: str) -> None:
    print("[scaling] %s" % msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the measurement core (runs inside --child / --worker subprocesses)


def measure(devices: int, world: int, rank: int, global_batch: int,
            imsize: int, iters: int, spatial: int) -> dict:
    """One scaling observation: `iters` production train steps scanned in
    ONE program on a (devices/spatial, spatial) mesh spanning `world`
    process(es). Single- and multi-process runs share this code path —
    `barrier_synced_compile`'s barrier is a no-op at world 1, so the
    multi-process rows measure exactly the single-process program plus
    the real rendezvous/collective machinery."""
    import jax
    import numpy as np
    if os.environ.get("SCALING_PLATFORM") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform == "cpu":
        raise SystemExit("scaling row: no accelerator (platform=cpu); "
                         "pass --cpu to ask for virtual CPU devices")
    if len(jax.devices()) < devices // world:
        raise SystemExit("scaling row needs %d devices per process, "
                         "this one has %d"
                         % (devices // world, len(jax.devices())))
    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.parallel import (
        barrier_synced_compile, batch_sharding, make_mesh, replicated,
        shard_batch, under_kernel_mesh)
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    from real_time_helmet_detection_tpu.train import (create_train_state,
                                                      make_scanned_train_fn,
                                                      make_train_step_body)

    use_compile_cache()
    tracer = maybe_tracer()
    if tracer.enabled:
        # rank-tagged records + a per-step trace id derived from the row
        # config alone (ISSUE 14): every rank of a multi-process row
        # contributes to the SAME trace, so obs/traceview.py joins the
        # per-rank span logs into one cross-process step trace
        tracer.bind(rank=int(rank), world=int(world))
    cfg = Config(num_stack=1,
                 hourglass_inch=128 if imsize >= 256 else 32,
                 num_cls=2, batch_size=global_batch)
    model = build_model(cfg)
    tx = build_optimizer(cfg, 100)
    state = create_train_state(model, cfg, jax.random.key(0), imsize, tx)
    mesh = make_mesh(devices, spatial=spatial)
    body = make_train_step_body(model, tx, cfg)

    train_n = make_scanned_train_fn(body, iters)
    repl = replicated(mesh)
    map_sh = batch_sharding(mesh, 4, spatial_dim=1)
    # donate the state exactly as the production train step does, so the
    # benched program has the same buffer-aliasing/memory regime
    step = jax.jit(under_kernel_mesh(train_n, mesh),
                   in_shardings=(repl,) + (map_sh,) * 5,
                   out_shardings=(repl, repl),
                   donate_argnums=(0,))

    # deterministic GLOBAL batch; this process contributes its contiguous
    # row block (mesh device order = process order on the data axis — the
    # DistributedSampler contract, ref train.py:54)
    g = synthetic_target_batch(global_batch, imsize, pos_rate=0.01)
    per = global_batch // world
    local = tuple(a[rank * per:(rank + 1) * per] for a in g)
    arrs = shard_batch(mesh, local, spatial_dims=[1] * 5)

    # shared timing helpers: one validated methodology (see bench.py)
    from bench import measure_dispatch_overhead, timed_fetch
    overhead = measure_dispatch_overhead()

    # THE barrier law: AOT-compile, realign every rank, only then execute
    # (the first execution creates the fresh Gloo context whose KeyValue
    # exchange carries the hard 30 s deadline; skewed compiles must never
    # count against it). scale:compile / scale:barrier spans land in the
    # flight recorder when $OBS_SPAN_LOG is exported.
    compiled = barrier_synced_compile(
        step, (state, *arrs),
        name="scaling_d%d_b%d_w%d" % (devices, global_batch, world),
        tracer=tracer)
    np.asarray(compiled(state, *arrs)[1])  # warm (donates `state`)
    state = create_train_state(model, cfg, jax.random.key(0), imsize, tx)
    # fetch ONLY the scalar loss: the program also returns the final state
    # (so donation has an output to alias) which must never enter the D2H
    dt = timed_fetch(lambda *a: compiled(*a)[1], (state, *arrs), overhead,
                     repeats=1)
    sctx = None
    if tracer.enabled:
        from real_time_helmet_detection_tpu.obs.trace import step_context
        sctx = step_context(0, epoch=devices, rank=int(rank),
                            run="scaling-d%d-b%d-w%d"
                            % (devices, global_batch, world))
    tracer.record("scale:step", dt / iters,
                  ctx=(sctx.child() if sctx is not None else None),
                  devices=devices, world=world, batch=global_batch)
    platform = jax.devices()[0].platform
    return {
        "devices": devices, "processes": world,
        "global_batch": global_batch,
        "per_chip_batch": global_batch // devices,
        "platform": platform,
        "hardware_signal": platform == "tpu",
        "spatial": spatial, "imsize": imsize,
        "img_per_sec": round(global_batch * iters / dt, 2),
        "img_per_sec_per_chip": round(global_batch * iters / dt / devices,
                                      2),
        "step_ms": round(dt / iters * 1e3, 2),
    }


def child_entry(args) -> None:
    row = measure(args.child, 1, 0, args.global_batch, args.imsize,
                  args.iters, args.spatial)
    print(json.dumps(row))


def worker_entry(args) -> None:
    """One rank of a multi-process row: rendezvous + gloo + the barrier
    law, then the shared measurement. Rank 0 prints the row."""
    import jax
    if os.environ.get("SCALING_PLATFORM") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from real_time_helmet_detection_tpu.parallel import (
        init_process_group, use_gloo_cpu_collectives)
    use_gloo_cpu_collectives()
    init_process_group("127.0.0.1:%d" % args.port, args.world, args.worker)
    assert jax.process_count() == args.world, jax.process_count()
    row = measure(args.row_devices, args.world, args.worker,
                  args.global_batch, args.imsize, args.iters, args.spatial)
    if args.worker == 0:
        print(json.dumps(row))


# ---------------------------------------------------------------------------
# plan + curves


def plan_rows(counts, pc, only, world):
    """The measurement plan: (mode-tags, devices, processes, global_batch)
    specs, deduplicated by key. Baseline (unsharded, same-global-batch)
    rows ride along whenever a mode that needs them is selected."""
    maxn = max(counts)
    specs = {}

    def add(devices, processes, batch):
        key = (devices, processes, batch)
        specs.setdefault(key, {"devices": devices, "processes": processes,
                               "global_batch": batch})

    if "weak" in only:
        for n in counts:
            add(n, 1, n * pc)
            add(1, 1, n * pc)  # unsharded twin -> sharding_efficiency
    if "strong" in only:
        for n in counts:
            add(n, 1, maxn * pc)
        add(1, 1, maxn * pc)
    if "multiproc" in only:
        if maxn % world == 0 and world >= 2:
            add(maxn, world, maxn * pc)
            add(1, 1, maxn * pc)  # its unsharded twin
        else:
            log("skipping multiproc: --processes %d must divide max "
                "device count %d" % (world, maxn))
    # stable order: cheap single-device baselines first, multiproc last
    return sorted(specs.values(),
                  key=lambda s: (s["processes"], s["devices"],
                                 s["global_batch"]))


def compute_curves(config: dict, rows) -> dict:
    """Derived curves over the measured rows (pure arithmetic, recomputed
    at every flush so a partial run's artifact is internally consistent)."""
    ok = [r for r in rows if "img_per_sec" in r]

    def find(devices, processes, batch):
        for r in ok:
            if (r["devices"] == devices and r["processes"] == processes
                    and r["global_batch"] == batch):
                return r
        return None

    pc = config["per_chip_batch"]
    maxn = config["max_devices"]

    def entry(r):
        return {"devices": r["devices"], "img_per_sec": r["img_per_sec"],
                "img_per_sec_per_chip": r["img_per_sec_per_chip"],
                "step_ms": r["step_ms"]}

    weak = []
    for r in sorted((r for r in ok if r["processes"] == 1
                     and r["global_batch"] == r["devices"] * pc),
                    key=lambda r: r["devices"]):
        e = entry(r)
        base1 = find(1, 1, pc)
        if base1:
            e["weak_efficiency"] = round(
                r["img_per_sec_per_chip"]
                / base1["img_per_sec_per_chip"], 4)
        unsharded = find(1, 1, r["global_batch"])
        if unsharded:
            e["sharding_efficiency"] = round(
                r["img_per_sec"] / unsharded["img_per_sec"], 4)
        weak.append(e)

    strong_b = maxn * pc
    strong = []
    base = find(1, 1, strong_b)
    for r in sorted((r for r in ok if r["processes"] == 1
                     and r["global_batch"] == strong_b),
                    key=lambda r: r["devices"]):
        e = entry(r)
        if base:
            e["speedup"] = round(r["img_per_sec"] / base["img_per_sec"], 4)
            e["strong_efficiency"] = round(e["speedup"] / r["devices"], 4)
        strong.append(e)

    multiproc = []
    for r in sorted((r for r in ok if r["processes"] > 1),
                    key=lambda r: (r["devices"], r["processes"])):
        e = entry(r)
        e["processes"] = r["processes"]
        unsharded = find(1, 1, r["global_batch"])
        if unsharded:
            e["sharding_efficiency"] = round(
                r["img_per_sec"] / unsharded["img_per_sec"], 4)
        multiproc.append(e)

    return {"weak": weak, "strong": strong, "multiproc": multiproc}


# ---------------------------------------------------------------------------
# orchestration


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _row_key(r) -> tuple:
    return (r.get("devices"), r.get("processes"), r.get("global_batch"))


def run_spec(spec, args, use_cpu: bool, timeout_s: float = 1800.0):
    """Run one plan row in subprocess(es); returns the measured row or an
    error row. A fresh process per row because
    --xla_force_host_platform_device_count is read once at backend init."""
    me = os.path.abspath(__file__)
    devices, world, batch = (spec["devices"], spec["processes"],
                             spec["global_batch"])
    common = ["--global-batch", str(batch), "--imsize", str(args.imsize),
              "--iters", str(args.iters), "--spatial", str(args.spatial)]
    err_row = dict(spec, imsize=args.imsize, spatial=args.spatial,
                   hardware_signal=not use_cpu)
    env = dict(os.environ)
    ndev_local = devices // world
    if use_cpu:
        env["SCALING_PLATFORM"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=%d"
                            % ndev_local).strip()
    if world == 1:
        cmd = [sys.executable, me, "--child", str(devices)] + common
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout_s, env=env)
        except subprocess.TimeoutExpired:
            return dict(err_row, error="timeout")
        if r.returncode != 0:
            log("row %s FAILED:\n%s" % (spec, r.stderr[-2000:]))
            return dict(err_row, error=r.stderr[-500:])
        return json.loads(r.stdout.strip().splitlines()[-1])

    port = _free_port()
    procs = []
    for rank in range(world):
        cmd = [sys.executable, me, "--worker", str(rank),
               "--world", str(world), "--port", str(port),
               "--row-devices", str(devices)] + common
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True,
                                      env=env))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        return dict(err_row, error="multiproc timeout")
    finally:
        for p in procs:  # a wedged rendezvous must not leak workers
            if p.poll() is None:
                p.kill()
    if any(p.returncode != 0 for p in procs):
        tail = "\n---\n".join(o[-1000:] for o in outs)
        log("multiproc row %s FAILED:\n%s" % (spec, tail))
        return dict(err_row, error=tail[-500:])
    return json.loads(outs[0].strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--per-chip-batch", type=int, default=None)
    ap.add_argument("--imsize", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--spatial", type=int, default=1,
                    help="spatial-axis size of the 2D (data x spatial) "
                         "mesh; must divide every device count")
    ap.add_argument("--only", default="weak,strong,multiproc",
                    help="comma list of weak|strong|multiproc")
    ap.add_argument("--processes", type=int, default=2,
                    help="world size of the multiproc rows (>= 2 real "
                         "processes; must divide the max device count)")
    ap.add_argument("--cpu", action="store_true",
                    help="run every row on virtual CPU devices (counts and "
                         "sharding_efficiency only — never a device speed)")
    ap.add_argument("--force", action="store_true",
                    help="remeasure rows the artifact already holds")
    ap.add_argument("--out", default=None,
                    help="artifact path (default artifacts/<round>/"
                         "scaling.json)")
    # internal subprocess modes
    ap.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--world", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--row-devices", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--global-batch", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child is not None:
        child_entry(args)
        return
    if args.worker is not None:
        worker_entry(args)
        return

    from bench import graft_round
    out_path = args.out or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "artifacts",
        graft_round(), "scaling.json")

    # No backend probe: a probe child would hold the chip before the row
    # that needs it, and this parent must stay off JAX. Each row's child
    # finds the backend itself and fails its row if it is not there.
    platform = "cpu" if args.cpu else "tpu"
    on_tpu = not args.cpu
    pc = args.per_chip_batch or (16 if on_tpu else 2)
    args.imsize = args.imsize or (512 if on_tpu else 64)
    args.iters = args.iters or (10 if on_tpu else 4)

    counts = sorted({n for n in args.devices if n % args.spatial == 0})
    for n in set(args.devices) - set(counts):
        log("skipping n=%d: not divisible by --spatial %d"
            % (n, args.spatial))
    only = {m.strip() for m in args.only.split(",") if m.strip()}
    bad_modes = only - {"weak", "strong", "multiproc"}
    if bad_modes:
        raise SystemExit("--only: unknown mode(s) %s" % sorted(bad_modes))

    config = {"per_chip_batch": pc, "imsize": args.imsize,
              "iters": args.iters, "spatial": args.spatial,
              "max_devices": max(counts), "platform": platform}

    # resume: keep prior rows only when the artifact's config matches —
    # a changed config would silently mix incomparable measurements
    prior_rows = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prior = json.load(f)
            if prior.get("schema") == SCHEMA \
                    and prior.get("config") == config:
                prior_rows = prior.get("results", [])
            else:
                log("existing artifact config/schema differs; starting "
                    "fresh (old rows dropped)")
        except (json.JSONDecodeError, OSError):
            prior_rows = []

    measured = {_row_key(r) for r in prior_rows if "img_per_sec" in r}
    rows = list(prior_rows)

    specs = plan_rows(counts, pc, only, args.processes)

    # supervised-job contract (scripts/tpu_queue.py): beat per row — each
    # subprocess run is the natural progress unit
    from real_time_helmet_detection_tpu.runtime import maybe_job_heartbeat
    from real_time_helmet_detection_tpu.utils import save_json
    hb = maybe_job_heartbeat()

    def flush():
        out = {"schema": SCHEMA, "config": config, "note": NOTE,
               "results": rows,
               "curves": compute_curves(config, rows)}
        save_json(out_path, out, indent=2)  # atomic: crash-safe artifact
        return out

    out = flush()
    for spec in specs:
        key = (spec["devices"], spec["processes"], spec["global_batch"])
        if key in measured and not args.force:
            log("row %s already measured; skipping (use --force)" % (key,))
            continue
        use_cpu = args.cpu
        if spec["processes"] > 1 and not use_cpu:
            # several processes cannot share one host's chips; the
            # multi-process rows exist for the Gloo CPU lifecycle
            log("row %s is multi-process: run it with --cpu; skipping"
                % (key,))
            continue
        hb.beat("scaling row d=%d p=%d b=%d" % key)
        log("row devices=%d processes=%d batch=%d (%s)..."
            % (*key, "cpu-virtual" if use_cpu else "tpu"))
        row = run_spec(spec, args, use_cpu)
        # a measured row is never evicted by an error rerun; a fresh
        # measurement replaces whatever stood (old error row included)
        row_ok = "img_per_sec" in row
        had_ok = any(_row_key(r) == key and "img_per_sec" in r
                     for r in rows)
        if row_ok or not had_ok:
            rows[:] = [r for r in rows if _row_key(r) != key]
            rows.append(row)
        if row_ok:
            measured.add(key)
        out = flush()
    print(json.dumps(out))


if __name__ == "__main__":
    from real_time_helmet_detection_tpu.runtime import run_as_job
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
