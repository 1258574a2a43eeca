"""Benchmark: single-chip perf evidence for the TPU framework.

Headline reference number: 100 FPS at 512x512 on a GTX 1080 Ti via the
TorchScript C++ app (/root/reference/README.md:76). This bench measures, on
one chip, steady-state:

* `inference_fps_512` (primary) — the fused predict path (network forward
  -> sigmoid -> decode -> NMS) as ONE jitted XLA program at batch 16.
  Batch choice is from the r02 sweep (scripts/tpu_sweep.py): batch 8 sits
  in a tiling dip (~1000 img/s), 16 gives ~1214, and 32 is the true peak
  (~1271) at double the per-batch latency — 16 is the near-peak default;
* `latency_ms_b1` — batch-1 device latency (the reference's "real-time"
  framing);
* `train_img_per_sec_chip` — train-step throughput at the flagship config
  (batch 16, 512^2, bf16) — BASELINE.json's north-star metric;
* `mfu_fwd` / `mfu_train` — analytic MFU from XLA's compiled cost
  analysis vs the chip's peak bf16 FLOP/s;
* `peak_pallas_us` / `peak_xla_us` — the fused Pallas sigmoid+3x3-peak
  kernel vs the XLA reduce_window path it replaces, plus an on-device
  bit-identity check;
* `donation_ok` — the graftlint trace-audit donation check over the timed
  train program (analysis/trace_audit.py): every chip run self-reports
  buffer-aliasing health instead of hiding it in a chip-log warning;
* `transfer_audit_ok` — the graftlint layer-4 budget check over the SAME
  timed program (analysis/transfer_audit.py): fetched-leaf / fresh-input /
  host-callback counts vs the committed transfer manifest's mode-matched
  train entry (shape-independent, eval_shape only) — a chip number that
  paid unbudgeted fetches says so on its own JSON line.

Measurement methodology: every section scans N iterations *inside* one
jitted program (`lax.scan`/`fori_loop`) with a data dependency between
iterations, returns only scalars, and times the single dispatch + host
fetch of the scalar; the separately-measured one-dispatch overhead
(`dispatch_ms`, reported) is subtracted. Whether plain per-call
`block_until_ready` timing agrees with it on a local chip is ROADMAP S1's
question; until S1 keeps one method, this is the one every number here
uses.

No chip, no number: the backend is initialised in THIS process (no probe
child — a child would hold the chip before the process that needs it), and
when JAX finds no accelerator the bench exits non-zero and prints no
result line. It never re-runs itself on the CPU, never shrinks its shapes,
and a `device_kind` missing from the peak table is an error, not a
default. A failed section fails the run: the partial line is printed with
`error`/`error_class` and the exit code is non-zero.

Prints ONE JSON line; the primary metric fields come first.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_FPS = 100.0  # reference README.md:76

# Peak bf16 FLOP/s and HBM bytes/s per chip, keyed by a substring of
# `device_kind` (v5e: Google Cloud "TPU v5e" documentation, 197 TFLOP/s and
# 819 GB/s; the rest: jax-ml scaling-book). ONE table, shared by
# scripts/roofline.py, scripts/mfu_breakdown.py and scripts/tpu_sweep.py
# through `chip_peaks`. A device that is not here is an error, not a
# default: an assumed peak is how a number gets the wrong denominator.
PEAK_BF16 = {
    "v4": 2.75e14,
    "v5e": 1.97e14,
    "v5 lite": 1.97e14,
    "v5p": 4.59e14,
    "v6e": 9.18e14,
    "v6 lite": 9.18e14,
    "trillium": 9.18e14,
}
HBM_GBPS = {"v5e": 819e9, "v5 lite": 819e9, "v4": 1228e9, "v5p": 2765e9,
            "v6e": 1640e9, "v6 lite": 1640e9, "trillium": 1640e9}


# The chip BASELINE.json targets: what an explicitly count-only CPU run
# (--cpu: HLO bytes and FLOPs, nothing timed) classifies its counts
# against. Never the denominator of a measured number.
TARGET_CHIP = "v5e"


def chip_peaks(device_kind: str) -> tuple:
    """(peak bf16 FLOP/s, HBM bytes/s) of `device_kind`, or ValueError."""
    for key, flops in PEAK_BF16.items():
        if key in device_kind.lower():
            return flops, HBM_GBPS[key]
    raise ValueError(
        "device_kind %r is not in bench.PEAK_BF16/HBM_GBPS: add its "
        "published peaks (with their source) before computing MFU or "
        "roofline shares for it" % device_kind)


# The artifacts/<round> directory every round-scoped script writes into.
# ONE default, shared by quality_matrix.py, tpu_sweep.py, mfu_breakdown.py
# and runner_drive.py (they diverged in r5: mfu_breakdown defaulted to r05
# while the rest stayed at r04, scattering same-round artifacts — ADVICE
# r5 #3); bump it here when a new round starts, or override per-run with
# $GRAFT_ROUND. r18 = the step-compression round (ISSUE 20: fused
# residual-block pass — ops/pallas/residual.py's one-pass BN+add+Mish
# with analytic backward, --block-fuse selection — plus --fwd-dtype int8
# STE training; roofline --diff byte evidence + tpu_sweep block-fuse ×
# fwd-dtype A/B twins); earlier rounds' artifact dirs are committed
# history and must not be overwritten.
GRAFT_ROUND_DEFAULT = "r18"

# The arch fields every bench line carries (ISSUE 13): the residual-block
# variant, stack count, width and the resolved tier name. Pre-tier lines
# lack them — `bench_arch_of` parses ANY bench line (old or new) into the
# full dict, defaulting absent fields to the historical bench config
# (residual, 1 stack, width 128 = the "flagship" tier name), so every
# committed BENCH_r* trajectory keeps reading as the same program.
ARCH_DEFAULTS = {"variant": "residual", "num_stack": 1, "width": 128,
                 "tier": "flagship"}


def bench_arch_of(rec: dict) -> dict:
    """The (variant, num_stack, width, tier) of a bench JSON line;
    pre-tier lines parse as the flagship defaults (regression-tested —
    the ONE-line contract and every committed trajectory keep reading)."""
    return {k: rec.get(k, v) for k, v in ARCH_DEFAULTS.items()}


# The cascade fields (ISSUE 16): whether the benched predict carried the
# in-jit confidence summary, and the fraction of the bench batch that
# would escalate at the resolved threshold. Pre-cascade lines lack them —
# `bench_cascade_of` parses ANY line into the full dict, defaulting to
# cascade-off (same back-compat contract as bench_arch_of).
CASCADE_DEFAULTS = {"cascade": False, "escalation_rate": None}


def bench_cascade_of(rec: dict) -> dict:
    """The (cascade, escalation_rate) of a bench JSON line; pre-cascade
    lines parse as cascade-off (regression-tested like the arch fields)."""
    return {k: rec.get(k, v) for k, v in CASCADE_DEFAULTS.items()}


# The stream fields (ISSUE 17): whether the line carried the delta-gated
# streaming probe, the fraction of tiles the calibrated threshold would
# skip on the probe's synthetic stream, and the gated-loop fps estimate.
# Pre-stream lines lack them — `bench_stream_of` parses ANY line into
# the full dict, defaulting to stream-off (same back-compat contract as
# bench_arch_of / bench_cascade_of).
STREAM_DEFAULTS = {"stream": False, "tile_skip_rate": None,
                   "stream_fps": None}


def bench_stream_of(rec: dict) -> dict:
    """The (stream, tile_skip_rate, stream_fps) of a bench JSON line;
    pre-stream lines parse as stream-off (regression-tested like the
    tier/cascade fields)."""
    return {k: rec.get(k, v) for k, v in STREAM_DEFAULTS.items()}


# The step-compression fields (ISSUE 20): which residual-block tail the
# benched train step ran (xla = the unfused BN→add→act chain, fused =
# ops/pallas/residual.py's one-pass custom_vjp) and the forward compute
# dtype (--fwd-dtype: bf16, or int8 STE training). Pre-ISSUE-20 lines
# lack them — `bench_block_fuse_of` parses ANY line into the full dict,
# defaulting to the historical unfused bf16 step (same back-compat
# contract as bench_arch_of / bench_cascade_of / bench_stream_of).
STEP_FUSE_DEFAULTS = {"block_fuse": "xla", "fwd_dtype": "bf16"}


def bench_block_fuse_of(rec: dict) -> dict:
    """The (block_fuse, fwd_dtype) of a bench JSON line; pre-ISSUE-20
    lines parse as the unfused bf16 step (regression-tested like the
    tier/cascade/stream fields)."""
    return {k: rec.get(k, v) for k, v in STEP_FUSE_DEFAULTS.items()}

# v5e int8 MXU peak (2x the bf16 peak — jax-ml scaling-book): the
# denominator for int8-path MFU and the hardware case for --infer-dtype
# int8 (ops/quant.py).
PEAK_INT8_V5E = 3.94e14


def graft_round() -> str:
    """artifacts/<round> name: $GRAFT_ROUND or the shared default."""
    return os.environ.get("GRAFT_ROUND", GRAFT_ROUND_DEFAULT)


def log(msg: str) -> None:
    print("[bench] %s" % msg, file=sys.stderr, flush=True)


def acquire_backend():
    """Initialise the JAX backend in THIS process; returns (jax, devices).

    One process per chip: no probe child, no waiter — whoever calls this
    is the process that uses the device. The default backend must be an
    accelerator; when JAX finds none this raises SystemExit(1) after
    saying so on stderr (no CPU re-run, no result line). `--cpu` in
    argv is a REQUEST for the CPU backend, honoured by the count-only
    scripts that share this helper (roofline.py, mfu_breakdown.py, the
    selfchecks): counts and control flow, never a device speed.

    The persistent compile cache is placed here for every script that
    shares this helper (runtime/compile_cache.py)."""
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    use_compile_cache()
    import jax
    want_cpu = "--cpu" in sys.argv
    if want_cpu:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform == "cpu" and not want_cpu:
        log("no accelerator: jax.devices()[0].platform is 'cpu'. This "
            "script measures the chip; it does not fall back. (Count-only "
            "scripts take an explicit --cpu.)")
        raise SystemExit(1)
    return jax, devs


def find_last_tpu_result(repo_root: str | None = None) -> dict | None:
    """Newest on-chip bench line under artifacts/*/BENCH_*_local.json.

    Kept for scripts/mfu_breakdown.py's measured-step anchor; bench.py
    itself no longer embeds it (a run without a chip prints nothing —
    ROADMAP D1 owns this function's deletion). Returns None when no
    on-chip artifact exists (e.g. a fresh clone).
    """
    import glob
    import re
    root = repo_root or os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in glob.glob(os.path.join(root, "artifacts", "*",
                                       "BENCH_*_local.json")):
        try:
            with open(path) as f:
                lines = [ln for ln in f.read().splitlines() if ln.strip()]
            rec = json.loads(lines[-1])
            mtime = os.path.getmtime(path)
        except (OSError, json.JSONDecodeError, IndexError):
            continue
        if rec.get("platform") != "tpu":
            continue
        # "Newest" = highest round dir (artifacts/rNN), mtime only as the
        # tiebreak: a fresh clone writes files in arbitrary order, so
        # mtime alone could surface r02 over r04 (review finding)
        m = re.search(r"r(\d+)", os.path.basename(os.path.dirname(path)))
        key = (int(m.group(1)) if m else -1, mtime)
        if best is None or key > best[0]:
            best = (key, path, rec, mtime)
    if best is None:
        return None
    _, path, rec, mtime = best
    committed_at = None
    try:
        import subprocess
        r = subprocess.run(
            ["git", "-C", root, "log", "-1", "--format=%cI", "--", path],
            capture_output=True, text=True, timeout=10)
        committed_at = r.stdout.strip() or None
    except Exception:  # noqa: BLE001 — git absent/broken must not kill bench
        pass
    out = {"path": os.path.relpath(path, root),
           # committed_at only when git actually has the file; an artifact
           # whose commit lost the index-lock race must not claim commit
           # provenance it lacks (review finding)
           "committed_at": committed_at,
           "file_mtime_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime(mtime)),
           "note": "newest on-chip bench%s; this run fell back to CPU"
                   % ("" if committed_at else " (NOT yet committed)")}
    keep = ("metric", "value", "unit", "vs_baseline", "imsize", "batch",
            "latency_ms_b1", "train_img_per_sec_chip", "train_step_ms",
            "mfu_train", "mfu_fwd", "device_kind", "peak_pallas_us",
            "peak_xla_us", "pallas_matches_xla", "infer_dtype", "int8_fps",
            "int8_vs_bf16", "recompile_count", "loadavg", "param_policy",
            "epilogue", "serve_p50_ms", "serve_p99_ms", "serve_goodput",
            "sentinel", "skipped_steps", "step_p50_ms", "step_p99_ms",
            "device_count", "mesh_shape",
            # arch fields (ISSUE 13): absent on pre-tier lines — the
            # consumer parses via bench_arch_of (flagship defaults)
            "variant", "num_stack", "width", "tier",
            # cascade fields (ISSUE 16): absent on pre-cascade lines —
            # the consumer parses via bench_cascade_of (cascade-off)
            "cascade", "escalation_rate",
            # stream fields (ISSUE 17): absent on pre-stream lines —
            # the consumer parses via bench_stream_of (stream-off)
            "stream", "tile_skip_rate", "stream_fps",
            # step-compression fields (ISSUE 20): absent on older lines —
            # the consumer parses via bench_block_fuse_of (xla/bf16)
            "block_fuse", "fwd_dtype",
            # audit self-reports (ISSUE 19): a surfaced on-chip number
            # keeps its hygiene verdicts attached
            "donation_ok", "lock_audit_clean", "transfer_audit_ok")
    out.update({k: rec[k] for k in keep if k in rec})
    return out


def measure_dispatch_overhead() -> float:
    """Median wall time of dispatching a trivial program and fetching its
    scalar — the fixed per-call cost every scanned measurement subtracts."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0)
    z = jnp.zeros(())
    float(f(z))  # compile
    times = []
    for _ in range(7):
        t0 = time.perf_counter()
        float(f(z))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timed_fetch(compiled, args, overhead: float, repeats: int = 2):
    """Best-of-`repeats` wall time of one dispatch of `compiled` (which must
    return only scalars/tiny arrays) including the fetch, minus the
    measured dispatch overhead."""
    import jax
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.tree.map(np.asarray, out)  # host fetch: forces real completion
        best = min(best, time.perf_counter() - t0)
    return max(best - overhead, 1e-9)


def flops_of(compiled) -> float:
    """Total FLOPs from XLA cost analysis. A scan/while body is counted
    ONCE regardless of trip count."""
    return float(compiled.cost_analysis()["flops"])


def bytes_of(compiled) -> float | None:
    """'bytes accessed' from XLA cost analysis (None when the backend does
    not report it). Like flops, a scan/while body is counted ONCE
    regardless of trip count (verified empirically: n=1 vs n=2 scans
    differ by <3%), so a scanned N-step program's value reads as
    per-step bytes."""
    val = compiled.cost_analysis().get("bytes accessed")
    return float(val) if val is not None else None


def chain_timed_fetch(compiled, variables, images, overhead: float,
                      repeats: int = 2):
    """`timed_fetch` for image-donating predict chains: each call's final
    carry (same aval/sharding as the input, content = input + O(1e-12))
    becomes the next call's donated input, so repeats never touch a
    deleted buffer and only the scalar crosses D2H."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        images, scalar = compiled(variables, images)
        np.asarray(scalar)  # host fetch: forces real completion
        best = min(best, time.perf_counter() - t0)
    return max(best - overhead, 1e-9)


def chained_scan_step_samples(compiled, state, args, overhead: float,
                              chunks: int = 3):
    """`timed_fetch` for the state-donating scanned train program, run
    `chunks` times CHAINED: each dispatch's returned final state (same
    avals/shardings as the donated input — the scan's aliasing contract)
    becomes the next dispatch's input, so repeats never touch a
    donated-away buffer, and each dispatch fetches ONLY the scalar tail.

    Returns (per-dispatch wall seconds, final state). The primary step
    time stays best-of (min — `timed_fetch`'s semantics, now over
    `chunks` real dispatches instead of one); the per-dispatch spread is
    what feeds the `bench.step_ms` histogram behind the JSON line's
    step_p50_ms/step_p99_ms (ISSUE 10). Same methodology as everything
    here: scanned program, scalar fetch, measured overhead subtracted."""
    import jax
    samples = []
    for _ in range(max(1, int(chunks))):
        t0 = time.perf_counter()
        state, tail = compiled(state, *args)
        jax.tree.map(np.asarray, tail)  # scalar fetch: forces completion
        samples.append(max(time.perf_counter() - t0 - overhead, 1e-9))
    return samples, state


def main() -> None:
    """Wrapper keeping the ONE-JSON-line contract on failure: once the
    chip is up, a crash in any section still prints the line — the fields
    measured so far plus `{"error": ..., "error_class": "transient"|
    "permanent"}` — and exits non-zero (75 transient, 1 permanent), so the
    supervisor (scripts/tpu_queue.py) and the driver classify without
    log-scraping. No accelerator prints NO line: there is nothing
    measured to report (acquire_backend's SystemExit(1) passes through).
    """
    from real_time_helmet_detection_tpu.runtime import (
        EXIT_TRANSIENT, classify_exception, maybe_job_heartbeat,
        write_job_status)
    hb = maybe_job_heartbeat()
    out: dict = {"metric": None, "value": None, "platform": None}

    def _emit_error(msg: str, klass: str) -> None:
        out.update({"error": msg[:500], "error_class": klass})
        print(json.dumps(out))
        sys.stdout.flush()
        write_job_status(False, error=msg, error_class=klass)

    try:
        _bench(out, hb)
    except KeyboardInterrupt:
        raise
    except SystemExit as e:
        if e.code is None or isinstance(e.code, int):
            raise  # no accelerator / plain exit: nothing to report
        _emit_error(str(e.code), "permanent")  # a bad flag value
        raise SystemExit(1) from e
    except Exception as e:  # noqa: BLE001 — classified, not swallowed
        klass = classify_exception(e)
        head = str(e).splitlines()[0] if str(e) else repr(e)
        _emit_error("%s: %s" % (type(e).__name__, head), klass)
        raise SystemExit(EXIT_TRANSIENT if klass == "transient"
                         else 1) from e
    write_job_status(True)


def _bench(out: dict, hb) -> None:
    jax, devs = acquire_backend()
    import jax.numpy as jnp
    from jax import lax

    platform = devs[0].platform
    device_kind = devs[0].device_kind
    peak, _ = chip_peaks(device_kind)
    log("backend up: %d x %s (%s)" % (len(devs), device_kind, platform))
    hb.beat("backend up (%s)" % platform)
    # ISSUE 11 satellite: the line says what hardware was VISIBLE and what
    # mesh the timed programs actually spanned — bench's programs are
    # deliberately single-device (scaling.py owns the multi-device curves),
    # so a chip line from a pod slice can't be misread as whole-slice
    # throughput.
    out["device_count"] = len(devs)
    out["mesh_shape"] = {"data": 1, "spatial": 1}

    # Flight recorder (ISSUE 6): span tracing when $OBS_SPAN_LOG is set
    # (the job supervisor exports it per round), a recompile counter
    # always, and the host-context sample whose loadavg rides the JSON
    # line — cross-run wall-clock deltas finally carry their confounders
    # (host load moves host-side numbers).
    from real_time_helmet_detection_tpu.obs.metrics import maybe_writer
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    tracer = maybe_tracer()
    recompiles = install_recompile_counter(tracer)
    # live metrics plane (ISSUE 10): the step-time histogram behind
    # step_p50_ms/step_p99_ms always counts in memory; $OBS_METRICS arms
    # the crash-safe snapshot export next to the span log
    mwriter = maybe_writer()
    ctx = tracer.context(phase="bench", platform=platform)
    out["loadavg"] = ctx.get("loadavg")
    out["span_log"] = tracer.path
    if tracer.enabled:
        log("span log -> %s" % tracer.path)

    def _finalize_obs() -> None:
        """Late fields for the ONE JSON line (both print sites)."""
        out["recompile_count"] = recompiles.count
        mwriter.close()  # final metrics snapshot (when $OBS_METRICS)

    # the flagship shapes (reference README: 512x512; batch 16 from the r02
    # sweep). Scan lengths: long enough that dispatch overhead is noise.
    imsize, batch, train_batch = 512, 16, 16
    n_inf, n_b1, n_train, n_peak = 512, 512, 64, 20000

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables

    dtype = None if os.environ.get("BENCH_DTYPE") == "fp32" else jnp.bfloat16
    # --infer-dtype int8 (or BENCH_INFER_DTYPE=int8 from a chain): ALSO
    # measure the quantized predict path (ops/quant.py). The primary
    # metric stays the float path so BENCH_rNN trajectories remain
    # comparable; the int8 numbers ride along as int8_fps/int8_vs_bf16.
    infer_dtype = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    if "--infer-dtype" in sys.argv:
        i = sys.argv.index("--infer-dtype")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--infer-dtype needs a value (bf16|int8)")
        infer_dtype = sys.argv[i + 1]
    if infer_dtype not in ("bf16", "int8"):
        raise SystemExit("--infer-dtype must be bf16 or int8, got %r"
                         % infer_dtype)
    # --tier <name> / BENCH_TIER (ISSUE 13): bench the named tier's
    # ARCHITECTURE (variant/stacks/width from config.TIER_PRESETS) instead
    # of the historical flagship config; the arch fields ride the ONE JSON
    # line either way, so every line says which program it measured.
    tier = os.environ.get("BENCH_TIER", "")
    if "--tier" in sys.argv:
        i = sys.argv.index("--tier")
        if i + 1 >= len(sys.argv):
            raise SystemExit("--tier needs a value (edge|throughput|"
                             "quality)")
        tier = sys.argv[i + 1]
    from real_time_helmet_detection_tpu.config import TIER_PRESETS
    arch = {"variant": "residual", "num_stack": 1, "hourglass_inch": 128,
            "stem_width": 0}
    if tier:
        if tier not in TIER_PRESETS:
            raise SystemExit("--tier must be one of %s, got %r"
                             % (sorted(TIER_PRESETS), tier))
        arch = {k: TIER_PRESETS[tier].get(k, arch[k]) for k in arch}
    cfg = Config(num_cls=2, topk=100,
                 conf_th=0.0, nms_th=0.5, imsize=imsize, **arch)
    model = build_model(cfg, dtype=dtype)
    rng = np.random.default_rng(0)
    out.update({
        "metric": "inference_fps_%d" % imsize, "value": None, "unit": "img/s",
        "vs_baseline": None, "platform": platform,
        "device_kind": device_kind,
        "dtype": "float32" if dtype is None else "bfloat16",
        "infer_dtype": infer_dtype,
        "imsize": imsize, "batch": batch,
        "variant": cfg.variant, "num_stack": cfg.num_stack,
        "width": cfg.hourglass_inch, "tier": tier or "flagship",
    })

    overhead = measure_dispatch_overhead()
    out["dispatch_ms"] = round(overhead * 1e3, 3)
    log("dispatch overhead: %.1f ms" % (overhead * 1e3))

    params, batch_stats = init_variables(model, jax.random.key(0), imsize)
    variables = {"params": params, "batch_stats": batch_stats}
    # --cascade / BENCH_CASCADE=1 (ISSUE 16): the timed predict carries the
    # in-jit confidence summary (ops/decode.confidence_summary riding the
    # detection block — the zero-extra-D2H contract means `value` should
    # match the plain program within noise), and the line reports the
    # fraction of the bench batch that would escalate at the resolved
    # threshold ($BENCH_CASCADE_THRESHOLD, else the newest committed
    # calibration artifact via config.cascade_overrides). Off = the exact
    # pre-PR program; pre-cascade lines parse via bench_cascade_of.
    cascade_on = (os.environ.get("BENCH_CASCADE") == "1"
                  or "--cascade" in sys.argv)
    out["cascade"] = cascade_on
    predict = make_predict_fn(model, cfg, cascade_summary=cascade_on)
    if cascade_on:
        try:
            th_env = os.environ.get("BENCH_CASCADE_THRESHOLD")
            if th_env is not None:
                casc_th = float(th_env)
            else:
                from real_time_helmet_detection_tpu.config import (
                    cascade_overrides)
                casc_th = float(cascade_overrides()["cascade_threshold"])
            out["cascade_threshold"] = casc_th
        except FileNotFoundError:
            casc_th = None
            log("cascade: no calibration artifact and no "
                "$BENCH_CASCADE_THRESHOLD; escalation_rate omitted")

    def make_predict_chain(pred, n):
        """N sequential predicts in ONE program; each iteration's input
        depends (negligibly: +score*1e-12) on the previous output so XLA
        cannot collapse or parallelize the chain.

        The image batch is DONATED and the final carry returned, so the
        scan's carry aliases the input buffer instead of holding a second
        image batch in HBM for the whole chain (the same contract
        make_scanned_train_fn keeps for the train state — previously the
        eval/predict program was the one remaining bench program that
        failed to alias its inputs). Callers fetch ONLY the scalar and
        thread the returned carry into the next timed call as the freshly
        donated input (`chain_timed_fetch`)."""
        def prog(variables, images):
            def body(imgs, _):
                det = pred(variables, imgs)
                eps = (jnp.tanh(jnp.sum(det.scores)) * 1e-12).astype(
                    imgs.dtype)
                return imgs + eps, ()
            final, _ = lax.scan(body, images, None, length=n)
            return final, jnp.sum(final[0, 0, 0])
        return jax.jit(prog, donate_argnums=(1,))

    # --- inference throughput (primary) + MFU(fwd) ------------------------
    images = jnp.asarray(rng.standard_normal(
        (batch, imsize, imsize, 3)).astype(np.float32))
    with tracer.span("bench:inference-compile", batch=batch):
        compiled = make_predict_chain(predict, n_inf).lower(
            variables, images).compile()
    chain_flops = flops_of(compiled)
    images, s = compiled(variables, images)  # warmup (donates images;
    np.asarray(s)  # the returned carry is the next call's input)
    dt = chain_timed_fetch(compiled, variables, images, overhead)
    fps = batch * n_inf / dt
    out["value"] = round(fps, 2)
    out["n_scan"] = n_inf
    out["vs_baseline"] = round(fps / BASELINE_FPS, 3)  # the ref's 512^2
    # XLA cost analysis counts a scan/while body ONCE regardless of trip
    # count (verified empirically) -> multiply by n_inf
    out["mfu_fwd"] = round(chain_flops * n_inf / dt / peak, 4)
    log("inference: %.1f img/s (%.3f ms/batch-%d)"
        % (fps, dt / n_inf * 1e3, batch))
    hb.beat("inference section done")

    # --- cascade escalation rate (--cascade) ------------------------------
    # One dispatch + one fetch of the confidence leaf on a fresh bench
    # batch — OFF the timed path (the timed chain above already carried
    # the summary computation and fetched only its scalar).
    if cascade_on and casc_th is not None:
        cimgs = jnp.asarray(rng.standard_normal(
            (batch, imsize, imsize, 3)).astype(np.float32))
        conf = np.asarray(predict(variables, cimgs).confidence)
        out["escalation_rate"] = round(
            float(np.mean(conf < casc_th)), 4)
        log("cascade: escalation rate %.3f at threshold %.4f (batch %d)"
            % (out["escalation_rate"], casc_th, batch))
        hb.beat("cascade section done")

    # --- batch-1 latency ---------------------------------------------------
    img1 = jnp.asarray(rng.standard_normal(
        (1, imsize, imsize, 3)).astype(np.float32))
    c1 = make_predict_chain(predict, n_b1).lower(variables, img1).compile()
    img1, s1 = c1(variables, img1)  # warmup (donates img1)
    np.asarray(s1)
    dt = chain_timed_fetch(c1, variables, img1, overhead)
    out["latency_ms_b1"] = round(dt / n_b1 * 1e3, 3)
    log("batch-1 device latency: %.3f ms" % (dt / n_b1 * 1e3))
    hb.beat("latency section done")

    # --- int8 inference (--infer-dtype int8) ------------------------------
    # The quantized predict chain (ops/quant.py: BN fold + per-channel
    # int8 weights inside the program, calibrated activation scales closed
    # over). Same chain/donation/timing methodology as the float section;
    # the speedup ratio int8_vs_bf16 is the headline the v5e's 2x int8
    # MXU peak predicts for a conv-bound program.
    if infer_dtype == "int8":
        import dataclasses

        from real_time_helmet_detection_tpu.ops.quant import (
            calibrate_scales, synthetic_calibration_batches)
        icfg = dataclasses.replace(cfg, infer_dtype="int8")
        scales = calibrate_scales(
            icfg, variables,
            synthetic_calibration_batches(batch, imsize, n=2),
            dtype=dtype)
        ipredict = make_predict_fn(model, icfg, quant_scales=scales)
        imgs8 = jnp.asarray(rng.standard_normal(
            (batch, imsize, imsize, 3)).astype(np.float32))
        ic = make_predict_chain(ipredict, n_inf).lower(
            variables, imgs8).compile()
        imgs8, s8 = ic(variables, imgs8)  # warmup (donates imgs8)
        np.asarray(s8)
        dt = chain_timed_fetch(ic, variables, imgs8, overhead)
        int8_fps = batch * n_inf / dt
        out["int8_fps"] = round(int8_fps, 2)
        if out.get("value"):
            out["int8_vs_bf16"] = round(int8_fps / out["value"], 3)
        log("int8 inference: %.1f img/s (%.3f ms/batch-%d, %sx bf16)"
            % (int8_fps, dt / n_inf * 1e3, batch,
               out.get("int8_vs_bf16", "?")))
        hb.beat("int8 section done")

    # --- serving engine closed loop (--serve) -----------------------------
    # A short saturation probe of the continuous-batching engine
    # (serving/engine.py) at this bench's predict config: serve_goodput is
    # completions/s with --serve-buckets coalescing + pipelining,
    # serve_p50/p99 the client-side latency at saturation. The full
    # open-loop offered-load curve is scripts/serve_bench.py's job; this
    # section just puts the serving headline on the ONE JSON line.
    if "--serve" in sys.argv or os.environ.get("BENCH_SERVE") == "1":
        from real_time_helmet_detection_tpu.serving import ServingEngine
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        from serve_bench import closed_loop
        sbuckets = tuple(b for b in (1, 2, 4, 8, 16) if b <= batch)
        simgs = [rng.integers(0, 256, (imsize, imsize, 3),
                              dtype=np.uint8) for _ in range(16)]
        spredict = make_predict_fn(model, cfg, normalize="imagenet")
        with tracer.span("bench:serve-compile", buckets=len(sbuckets)):
            sengine = ServingEngine(
                spredict, variables, (imsize, imsize, 3), np.uint8,
                buckets=sbuckets, max_wait_ms=5.0, depth=2,
                queue_capacity=4 * batch, tracer=tracer)
        try:
            sengine.predict_many(simgs[:2])  # warm
            row = closed_loop(
                sengine, simgs, clients=2 * batch,
                duration_s=float(os.environ.get("BENCH_SERVE_S", "3")),
                tracer=tracer)
        finally:
            sengine.close()
        out["serve_p50_ms"] = row["p50_ms"]
        out["serve_p99_ms"] = row["p99_ms"]
        out["serve_goodput"] = row["goodput_rps"]
        log("serve closed loop: %.1f req/s, p50 %s ms p99 %s ms"
            % (row["goodput_rps"], row["p50_ms"], row["p99_ms"]))
        hb.beat("serve section done")

    # --- delta-gated streaming probe (--stream / BENCH_STREAM=1) ----------
    # ISSUE 17: two numbers for the ONE JSON line, both OFF the timed
    # chain above. tile_skip_rate = fraction of tiles the resolved skip
    # threshold ($BENCH_STREAM_THRESHOLD, else the newest committed
    # calibration artifact via config.stream_overrides — never a
    # hand-picked constant) marks static on a seeded synthetic camera
    # stream (each tile re-randomizes with prob 0.25 per frame — the
    # serve_bench --streams default redundancy). stream_fps = delivered
    # frames/s of a gated StreamSession over a small ServingEngine on
    # that same stream, read from the session's own stats() clock — a
    # goodput-style figure amortized over the run (like serve_goodput),
    # NOT a per-call timing. The real offered-load curves are
    # scripts/serve_bench.py --streams; pre-stream lines parse via
    # bench_stream_of (stream-off).
    stream_on = (os.environ.get("BENCH_STREAM") == "1"
                 or "--stream" in sys.argv)
    out["stream"] = stream_on
    if stream_on:
        from real_time_helmet_detection_tpu.ops.delta import (
            tile_delta_summary, tile_origins)
        from real_time_helmet_detection_tpu.serving import (
            ServingEngine, StreamSession)
        th_env = os.environ.get("BENCH_STREAM_THRESHOLD")
        if th_env is not None:
            stream_th = float(th_env)
        else:
            from real_time_helmet_detection_tpu.config import (
                stream_overrides)
            stream_th = float(stream_overrides()["stream_threshold"])
        out["stream_threshold"] = stream_th

        grid = 2
        fshape = (grid * imsize, grid * imsize, 3)
        n_frames = int(os.environ.get("BENCH_STREAM_FRAMES", "8"))
        srng = np.random.default_rng(17)
        origins = tile_origins(fshape, grid)
        frames = [srng.integers(0, 256, fshape, dtype=np.uint8)]
        for _ in range(n_frames - 1):
            nxt = frames[-1].copy()
            for (y0, x0) in origins:
                if srng.random() >= 0.75:  # this tile changes
                    nxt[y0:y0 + imsize, x0:x0 + imsize] = srng.integers(
                        0, 256, (imsize, imsize, 3), dtype=np.uint8)
            frames.append(nxt)
        # consecutive-pair delta summaries (this also warms the delta
        # program the session reuses, so compile stays off its clock)
        summaries = np.stack([
            np.asarray(tile_delta_summary(
                jnp.asarray(a), jnp.asarray(b), grid=grid))
            for a, b in zip(frames, frames[1:])])
        out["tile_skip_rate"] = round(
            float(np.mean(summaries < stream_th)), 4)

        stpredict = make_predict_fn(model, cfg, normalize="imagenet")
        with tracer.span("bench:stream-compile"):
            stengine = ServingEngine(
                stpredict, variables, (imsize, imsize, 3), np.uint8,
                buckets=(1, 2, 4), max_wait_ms=2.0, depth=2,
                queue_capacity=4 * grid * grid, tracer=tracer)
        try:
            stengine.predict_many(  # warm the tile-shaped buckets
                [np.ascontiguousarray(frames[0][:imsize, :imsize])])
            sess = StreamSession(
                stengine, fshape, grid=grid, threshold=stream_th,
                tracer=tracer)
            for f in frames:
                sess.submit_frame(f)
            sess.drain(timeout=300.0)
            st = sess.stats()
            sess.close()
        finally:
            stengine.close()
        out["stream_fps"] = st["fps"]
        log("stream: %s fps gated (skip rate %.3f at threshold %.4f, "
            "%d frames)" % (out["stream_fps"], out["tile_skip_rate"],
                            stream_th, n_frames))
        hb.beat("stream section done")

    # --- train-step throughput + MFU(train) -------------------------------
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.train import (
        create_train_state, make_scanned_train_fn, make_train_step_body)
    # step-compression knobs under A/B from the driver/chains:
    # BENCH_REMAT={none,stacks,full}, BENCH_LOSS_KERNEL={auto,fused,xla},
    # BENCH_PARAM_POLICY={fp32,bf16-compute}, BENCH_EPILOGUE=
    # {auto,fused,xla} (ISSUE 7; bf16-compute needs the bf16 policy,
    # so it is forced to fp32 under BENCH_DTYPE=fp32)
    param_policy = os.environ.get("BENCH_PARAM_POLICY", "fp32")
    if dtype is None and param_policy != "fp32":
        log("BENCH_PARAM_POLICY=%s needs bf16 (--amp); forcing fp32"
            % param_policy)
        param_policy = "fp32"
    # BENCH_SENTINEL=1 (or --sentinel): the ISSUE-9 in-jit NaN/spike
    # sentinel rides the timed train program; the scanned skip counter
    # returns NEXT TO the loss scalar (same single D2H) and lands on
    # the ONE JSON line as skipped_steps. Off = the exact pre-PR
    # program, and the line says so (sentinel: "off").
    sentinel_on = (os.environ.get("BENCH_SENTINEL") == "1"
                   or "--sentinel" in sys.argv)
    # BENCH_BLOCK_FUSE={auto,fused,xla} / BENCH_FWD_DTYPE={bf16,int8}
    # (ISSUE 20): the residual-block tail pass family and the STE
    # forward dtype under A/B, same contract as BENCH_EPILOGUE. int8
    # forward needs the bf16 compute dtype (STE accumulates in int32
    # and rescales into the compute dtype), so it is forced back to
    # bf16 under BENCH_DTYPE=fp32 like the param policy above.
    fwd_dtype = os.environ.get("BENCH_FWD_DTYPE", "bf16")
    if dtype is None and fwd_dtype != "bf16":
        log("BENCH_FWD_DTYPE=%s needs bf16 (--amp); forcing bf16"
            % fwd_dtype)
        fwd_dtype = "bf16"
    tcfg = Config(num_cls=2,
                  batch_size=train_batch, amp=dtype is not None,
                  imsize=imsize, **arch,
                  remat=os.environ.get("BENCH_REMAT", "none"),
                  loss_kernel=os.environ.get("BENCH_LOSS_KERNEL",
                                             "auto"),
                  param_policy=param_policy,
                  epilogue=os.environ.get("BENCH_EPILOGUE", "auto"),
                  block_fuse=os.environ.get("BENCH_BLOCK_FUSE",
                                            "auto"),
                  fwd_dtype=fwd_dtype,
                  sentinel=sentinel_on)
    tmodel = build_model(tcfg, dtype=dtype)
    tx = build_optimizer(tcfg, 100)
    state = create_train_state(tmodel, tcfg, jax.random.key(0), imsize, tx)
    body = make_train_step_body(tmodel, tx, tcfg)
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        train_batch, imsize, pos_rate=0.01))

    train_n = make_scanned_train_fn(body, n_train,
                                    sentinel=sentinel_on)
    with tracer.span("bench:train-compile", batch=train_batch):
        tcompiled = jax.jit(train_n, donate_argnums=(0,)).lower(
            state, *arrs).compile()
    train_flops = flops_of(tcompiled)
    train_bytes = bytes_of(tcompiled)  # scan body counted once -> /step
    try:
        # donation_ok: chip runs self-report aliasing health in the
        # ONE JSON line — the trace-audit aval check (graftlint layer
        # 1), eval_shape only, no device work. False would mean the
        # timed program holds TWO states in HBM and the chip log
        # carries the "donated buffers were not usable" warning.
        from real_time_helmet_detection_tpu.analysis.trace_audit import \
            donation_ok
        out["donation_ok"] = donation_ok(train_n, (0,), (state, *arrs))
    except Exception as e:  # noqa: BLE001 — never block the bench
        log("donation audit unavailable: %r" % e)
    try:
        # lock_audit_clean: the concurrency audit (graftlint layer
        # 3) self-reported the same way — a chip number produced by
        # a serving/metrics plane with a known lock bug should say
        # so in its own JSON line (stdlib ast, ~1 s, no device work)
        from real_time_helmet_detection_tpu.analysis import (
            diff_baseline, load_baseline, lock_audit)
        _lroot = os.path.dirname(os.path.abspath(__file__))
        out["lock_audit_clean"] = not diff_baseline(
            lock_audit.audit_repo(_lroot), load_baseline())["new"]
    except Exception as e:  # noqa: BLE001 — never block the bench
        log("lock audit unavailable: %r" % e)
    from real_time_helmet_detection_tpu.ops.pallas.select import kernel_plan
    plan = kernel_plan(tcfg)
    try:
        # transfer_audit_ok: the D2H/H2D budget (graftlint layer 4)
        # self-reported the same way — the TIMED program's fetched-
        # leaf / fresh-input / host-callback counts vs the committed
        # manifest's mode-matched train entry (shape-independent:
        # the bench runs real archs while the manifest pins the tiny
        # audit config; eval_shape only, no device work). False
        # means the chip number paid fetches the budget never
        # approved.
        from real_time_helmet_detection_tpu.analysis.transfer_audit \
            import bench_transfer_ok
        # mode-matched manifest entry: sentinel wins (it changes the
        # fetched-leaf count), then the ISSUE-20 train modes — both
        # budget-identical to the base step, pinned as their own
        # entries so a regression names the mode that grew
        if sentinel_on:
            _t_entry = "train_step_scanned[sentinel]"
        elif tcfg.fwd_dtype == "int8":
            _t_entry = "train_step_scanned[fwd=int8]"
        elif plan["block_fuse"] == "fused":
            _t_entry = "train_step_scanned[block-fuse]"
        else:
            _t_entry = "train_step_scanned"
        out["transfer_audit_ok"] = bench_transfer_ok(
            train_n, (state, *arrs), donate_argnums=(0,),
            entry=_t_entry)
    except Exception as e:  # noqa: BLE001 — never block the bench
        log("transfer audit unavailable: %r" % e)
    # warmup run consumes (donates) `state`; rebuild for the timed run.
    # The program returns (final state, last loss) so every donated
    # buffer has an output to alias (donation actually elides the
    # copy — no "donated buffers were not usable" warning); fetch ONLY
    # the scalar loss (+ the sentinel's skip-count scalar, same fetch)
    # so the full state never crosses D2H.
    out["sentinel"] = "on" if sentinel_on else "off"
    if sentinel_on:
        warm_loss, warm_skipped = tcompiled(state, *arrs)[1]
        np.asarray(warm_loss)
        # the warmup scan ran the same n_train steps on the same
        # batch as the timed run: its skip count IS the program's
        out["skipped_steps"] = int(np.asarray(warm_skipped))
    else:
        np.asarray(tcompiled(state, *arrs)[1])
        out["skipped_steps"] = 0
    state = create_train_state(tmodel, tcfg, jax.random.key(0), imsize, tx)
    # three CHAINED timed dispatches of the same compiled scan (state
    # threads through donation): min is the primary step time
    # (timed_fetch best-of semantics), the spread feeds the metrics
    # histogram behind step_p50_ms/step_p99_ms (ISSUE 10)
    samples, _ = chained_scan_step_samples(tcompiled, state, arrs,
                                           overhead, chunks=3)
    dt = min(samples)
    out["train_img_per_sec_chip"] = round(train_batch * n_train / dt, 2)
    out["train_batch"] = train_batch
    out["train_step_ms"] = round(dt / n_train * 1e3, 3)
    from real_time_helmet_detection_tpu.obs.metrics import \
        default_registry
    step_hist = default_registry().histogram("bench.step_ms")
    for s in samples:
        step_hist.observe(s / n_train * 1e3)
    p50, p99 = step_hist.quantile(0.50), step_hist.quantile(0.99)
    out["step_p50_ms"] = None if p50 is None else round(p50, 3)
    out["step_p99_ms"] = None if p99 is None else round(p99, 3)
    # scan body counted once by cost analysis -> multiply by n_train
    out["mfu_train"] = round(train_flops * n_train / dt / peak, 4)
    # why-MFU-moved context for the BENCH_rNN trajectory: the active
    # step-compression settings + the step's cost-analysis HBM bytes
    out["hbm_bytes_per_step"] = train_bytes
    out["remat"] = tcfg.remat
    out["loss_kernel"] = plan["loss"]
    out["param_policy"] = tcfg.param_policy
    out["epilogue"] = plan["epilogue"]
    out["block_fuse"] = plan["block_fuse"]
    out["fwd_dtype"] = tcfg.fwd_dtype
    out["mfu_peak_flops"] = peak
    try:
        # convert_bytes_pct: the roofline counting model's convert
        # class share of the timed train program (operand+result per
        # reportable op, scripts/roofline.py) — the ONE JSON line's
        # own evidence of whether the param-policy/epilogue levers
        # are doing their job on this exact program
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import roofline as _roofline
        _comps, _fb, _ap = _roofline.parse_hlo(tcompiled.as_text())
        _rows = _roofline.attribute(_comps, _fb, _ap)
        _tot = sum(r["bytes"] for r in _rows)
        _cvt = sum(r["bytes"] for r in _rows
                   if r["class"] == "convert")
        out["convert_bytes_pct"] = (round(100.0 * _cvt / _tot, 2)
                                    if _tot else None)
    except Exception as e:  # noqa: BLE001 — never block the bench
        log("convert-bytes attribution unavailable: %r" % e)
    log("train: %.1f img/s/chip (%.2f ms/step)"
        % (train_batch * n_train / dt, dt / n_train * 1e3))
    hb.beat("train section done")

    # --- Pallas fused peak kernel vs XLA path -----------------------------
    # Runs inline like every other section: a kernel that fails to compile
    # fails the run. BENCH_PALLAS=0 skips the A/B.
    if os.environ.get("BENCH_PALLAS", "1") != "0":
        from real_time_helmet_detection_tpu.ops.pallas.peak import (
            fused_peak_scores, peak_scores_reference)
        logits = jnp.asarray(rng.standard_normal(
            (batch, imsize // 4, imsize // 4, 2)).astype(np.float32) * 4)

        def chain(fn, n):
            def prog(x):
                def body(i, y):
                    o = jax.vmap(fn)(y)
                    return y + o * 1e-20
                return jnp.sum(lax.fori_loop(0, n, body, x)[0, 0, 0])
            return jax.jit(prog)

        def per_iter(fn):
            """Probe with n_peak iters, then re-measure with a chain
            long enough that device time >= 10x dispatch overhead —
            a fast microkernel (us-scale) would otherwise hide inside
            the subtracted overhead and the result would be the
            difference of two same-magnitude noisy numbers."""
            c = chain(fn, n_peak).lower(logits).compile()
            np.asarray(c(logits))
            t = timed_fetch(c, (logits,), overhead) / n_peak
            n = int(min(2e6, max(n_peak, 10 * overhead / max(t, 1e-9))))
            if n > n_peak:
                c = chain(fn, n).lower(logits).compile()
                np.asarray(c(logits))
                t = timed_fetch(c, (logits,), overhead) / n
            return t

        a = jax.vmap(lambda x: fused_peak_scores(x, interpret=False))(
            logits)
        b = jax.vmap(peak_scores_reference)(logits)
        out["pallas_matches_xla"] = bool(
            np.array_equal(np.asarray(a), np.asarray(b)))
        tp = per_iter(lambda x: fused_peak_scores(x, interpret=False))
        txla = per_iter(peak_scores_reference)
        out["peak_pallas_us"] = round(tp * 1e6, 3)
        out["peak_xla_us"] = round(txla * 1e6, 3)
        log("pallas peak: %.2f us vs xla %.2f us (match=%s)"
            % (tp * 1e6, txla * 1e6, out["pallas_matches_xla"]))
        hb.beat("pallas section done")

    _finalize_obs()
    tracer.close()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
