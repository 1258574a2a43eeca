"""Where does the train step's non-MXU time go? (round-3 VERDICT item #2)

Round 2 measured mfu_train ~0.47-0.52 at the flagship config and the judge
asked for a committed breakdown: which components eat the time, and is the
residue schedulable (fusion/layout) or fundamental (memory-bound ops whose
bytes/FLOP ratio puts them under the HBM roofline, ref train loop
/root/reference/train.py:86-162).

Method: bench.py's scanned-chain methodology (N iterations inside ONE
program with an inter-iteration data dependency; subtract measured dispatch
overhead) applied to each component of the flagship train step separately:

  stem (PreLayer), one Hourglass, neck+head, full forward, loss,
  forward+backward (jax.grad), full train step (fwd+bwd+Adam+BN-stats)

plus calibration microbenches that bound what XLA can do on this chip:

  dominant-op proxy (3x3 128ch conv @128^2), the 7x7 s2 stem conv alone
  (3 input channels -> MXU contraction-starved), BatchNorm alone
  (memory-bound by construction), nearest-2x upsample alone.

For every entry we record time, FLOPs (XLA cost analysis: scan body counted
once -> multiplied by trip count), bytes accessed when available, and the
implied MFU and HBM-bandwidth utilization. The roofline argument the judge
asked for falls out of comparing each component's achieved FLOP/s against
min(peak_flops, bytes_per_s_peak * flops/bytes).

Also attempts a real `jax.profiler` device trace (plugin support permitting)
into artifacts/r03/trace/.

Writes artifacts/r03/mfu_breakdown.json incrementally (a killed run keeps
its finished components).

`--analytic --cpu` (r5, chip-outage mode): compile every component at the
FLAGSHIP shapes (512^2, batch 16, bf16) on the CPU backend — compile-only,
no execution — and record FLOPs + bytes accessed from XLA cost analysis
plus the v5e roofline-implied minimum time max(flops/peak, bytes/BW) and
ceiling MFU per component. Caveat, stated in the artifact: bytes accessed
reflect the CPU pipeline's fusion choices, a proxy for the TPU compiler's;
the verdict it supports ("is ~0.53 the HBM-bound ceiling?") is provisional
until the on-chip run lands. Writes mfu_roofline_analytic.json (separate
artifact — never clobbers the measured one).
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (TARGET_CHIP, acquire_backend, bytes_of, chip_peaks,
                   find_last_tpu_result,
                   flops_of, graft_round, log, measure_dispatch_overhead,
                   timed_fetch)
from real_time_helmet_detection_tpu.runtime import (maybe_job_heartbeat,
                                                    run_as_job)
from real_time_helmet_detection_tpu.utils import save_json

ANALYTIC = "--analytic" in sys.argv

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts",
    graft_round(),
    "mfu_roofline_analytic.json" if ANALYTIC else "mfu_breakdown.json")

# Fallback on-chip train-step measurement (the number the roofline
# analysis is explaining) — artifacts/r04/BENCH_r04_local.json. Used only
# when no committed on-chip bench artifact is discoverable; otherwise the
# anchor comes from the NEWEST one (ADVICE r5 #2: the hardcoded r4
# constants silently went stale whenever a newer on-chip bench landed).
_FALLBACK_STEP_MS = 36.774
_FALLBACK_MFU = 0.5278


def measured_train_anchor():
    """(step_ms, mfu, source) of the newest committed on-chip train bench,
    falling back to the pinned r4 constants when none exists (fresh
    clone / artifacts pruned)."""
    last = find_last_tpu_result()
    if last and last.get("train_step_ms") and last.get("mfu_train"):
        return (float(last["train_step_ms"]), float(last["mfu_train"]),
                last.get("path", "artifacts (unknown path)"))
    return (_FALLBACK_STEP_MS, _FALLBACK_MFU,
            "pinned r4 constants (no on-chip BENCH_*_local.json found)")


MEASURED_STEP_MS, MEASURED_MFU, MEASURED_SRC = measured_train_anchor()

# HBM-bandwidth table and bytes_of moved to bench.py (r7): one shared
# definition for this script, bench.py's hbm_bytes_per_step field and
# scripts/roofline.py's per-fusion roofline.


def main() -> None:
    jax, devs = acquire_backend()
    import jax.numpy as jnp
    from jax import lax

    platform = devs[0].platform
    device_kind = devs[0].device_kind
    on_tpu = platform == "tpu"
    # a --cpu run only counts: it classifies against the named target chip
    peak, hbm = chip_peaks(TARGET_CHIP if platform == "cpu" else device_kind)
    log("backend: %s (%s)" % (device_kind, platform))

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.models.hourglass import (
        Hourglass, Neck, Head, PreLayer)
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.ops.loss import detection_loss
    from real_time_helmet_detection_tpu.train import (
        create_train_state, init_variables, make_scanned_train_fn,
        make_train_step_body)
    import flax.linen as nn

    # analytic mode compiles the FLAGSHIP shapes regardless of backend
    # (nothing executes, so CPU can carry 512^2 batch-16 programs)
    imsize = 512 if (on_tpu or ANALYTIC) else 64
    batch = 16 if (on_tpu or ANALYTIC) else 2
    n = 64 if on_tpu else 2
    dtype = jnp.bfloat16
    overhead = 0.0 if ANALYTIC else measure_dispatch_overhead()
    if not ANALYTIC:
        log("dispatch overhead: %.1f ms" % (overhead * 1e3))
    rng = np.random.default_rng(0)

    results = {"platform": platform, "device_kind": device_kind,
               "imsize": imsize, "batch": batch,
               "peak_flops": peak, "hbm_bytes_per_s": hbm,
               "dispatch_ms": round(overhead * 1e3, 3), "components": {}}
    if ANALYTIC:
        # roofline constants are ALWAYS the target chip's in analytic mode
        # (the local backend only provides the HLO pipeline)
        peak, hbm = chip_peaks(TARGET_CHIP)
        results.update({
            "analytic": True, "peak_flops": peak, "hbm_bytes_per_s": hbm,
            "note": "compile-only roofline at v5e constants; bytes "
                    "accessed come from the LOCAL (cpu) pipeline's fusion "
                    "choices — a proxy for the TPU compiler's, provisional "
                    "until the on-chip mfu_breakdown.json lands"})

    hb = maybe_job_heartbeat()

    def flush():
        # atomic (tmp + rename) per-component flush doubles as the job
        # heartbeat — see tpu_sweep.py's flush for the rationale
        os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
        save_json(OUT_PATH, results, indent=1)
        hb.beat("flushed %s" % os.path.basename(OUT_PATH))

    def chained(step_fn, x0, n_iter, extra_args=()):
        """Scan `step_fn` n_iter times with a data dependency through x0.
        step_fn maps (x, *extra) -> y of ANY shape; feedback folds y into a
        scalar perturbation of x so XLA cannot dead-code or parallelize."""
        def prog(x, *extra):
            def body(carry, _):
                y = step_fn(carry, *extra)
                leaves = jax.tree.leaves(y)
                s = sum(jnp.sum(l.astype(jnp.float32) * 1e-20) for l in leaves)
                return carry + s.astype(carry.dtype), ()
            final, _ = lax.scan(body, x, None, length=n_iter)
            return jnp.sum(final.astype(jnp.float32).ravel()[:1])
        return jax.jit(prog)

    def analytic_rec(fl, by):
        """Roofline record from cost analysis alone (scan body counted once
        by XLA -> fl/by are already per-iteration)."""
        rec = {}
        if fl:
            rec["gflops"] = round(fl / 1e9, 2)
            rec["t_mxu_ms"] = round(fl / peak * 1e3, 4)
        if by:
            rec["gbytes"] = round(by / 1e9, 3)
            rec["t_hbm_ms"] = round(by / hbm * 1e3, 4)
        if fl and by:
            t_min = max(fl / peak, by / hbm)
            rec["t_roofline_ms"] = round(t_min * 1e3, 4)
            rec["roofline_mfu"] = round(fl / peak / t_min, 4)
            rec["binds"] = "hbm" if by / hbm > fl / peak else "mxu"
        return rec

    def measure(name, step_fn, x0, n_iter, extra_args=()):
        try:
            c = chained(step_fn, x0, n_iter).lower(x0, *extra_args).compile()
            fl = flops_of(c)
            by = bytes_of(c)
            if ANALYTIC:
                rec = analytic_rec(fl, by)
                results["components"][name] = rec
                log("%-22s %s" % (name, rec))
                flush()
                return rec
            np.asarray(c(x0, *extra_args))  # warmup
            dt = timed_fetch(c, (x0, *extra_args), overhead)
            per = dt / n_iter
            rec = {"ms": round(per * 1e3, 4)}
            if fl:
                rec["gflops"] = round(fl / 1e9, 2)
                rec["mfu"] = round(fl / per / peak, 4)
            if by:
                rec["gbytes"] = round(by / 1e9, 3)
                rec["hbm_util"] = round(by / per / hbm, 4)
                if fl:
                    # achievable MFU if perfectly overlapped: bounded by
                    # whichever roofline binds
                    rec["roofline_mfu"] = round(
                        min(1.0, (fl / peak) / max(fl / peak, by / hbm)), 4)
            results["components"][name] = rec
            log("%-22s %8.3f ms  mfu=%s  hbm=%s" % (
                name, per * 1e3, rec.get("mfu"), rec.get("hbm_util")))
            flush()
            return rec
        except Exception as e:  # noqa: BLE001
            results["components"][name] = {
                "error": str(e).splitlines()[-1][:200]}
            log("%s FAILED: %r" % (name, e))
            flush()
            return None

    cfg = Config(num_stack=1, hourglass_inch=128, num_cls=2,
                 batch_size=batch, amp=True, imsize=imsize)
    model = build_model(cfg, dtype=dtype)
    key = jax.random.key(0)

    # ---- full train step (the number being explained) --------------------
    tx = build_optimizer(cfg, 100)
    state = create_train_state(model, cfg, key, imsize, tx)
    body = make_train_step_body(model, tx, cfg)
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        batch, imsize, pos_rate=0.01))

    try:
        train_n = make_scanned_train_fn(body, n)
        c = jax.jit(train_n, donate_argnums=(0,)).lower(state, *arrs).compile()
        fl, by = flops_of(c), bytes_of(c)
        if ANALYTIC:
            rec = analytic_rec(fl, by)
            # the verdict VERDICT r4 #2 asks for: the ceiling the roofline
            # allows for the WHOLE step vs the newest measured mfu_train
            rec["measured_mfu"] = MEASURED_MFU
            rec["measured_ms"] = MEASURED_STEP_MS
            rec["measured_src"] = MEASURED_SRC
            results["components"]["train_step"] = rec
            log("train_step (analytic): %s" % rec)
            flush()
        else:
            np.asarray(c(state, *arrs)[1])
            state2 = create_train_state(model, cfg, key, imsize, tx)
            # fetch only the scalar loss; the returned final state is the
            # donated input's aliasing target, never D2H traffic
            dt = timed_fetch(lambda *a: c(*a)[1], (state2, *arrs), overhead,
                             repeats=1)
            per = dt / n
            rec = {"ms": round(per * 1e3, 3)}
            if fl:
                rec["gflops"] = round(fl / 1e9, 2)
                rec["mfu"] = round(fl / per / peak, 4)
            if by:
                rec["gbytes"] = round(by / 1e9, 3)
                rec["hbm_util"] = round(by / per / hbm, 4)
            results["components"]["train_step"] = rec
            log("train_step: %s" % rec)
            flush()
    except Exception as e:  # noqa: BLE001
        results["components"]["train_step"] = {
            "error": str(e).splitlines()[-1][:200]}
        flush()

    params, batch_stats = init_variables(model, key, imsize)
    variables = {"params": params, "batch_stats": batch_stats}
    images = jnp.asarray(rng.standard_normal(
        (batch, imsize, imsize, 3)).astype(np.float32))

    # ---- full forward (train=False: running stats, no BN update) ---------
    measure("forward", lambda x: model.apply(variables, x, train=False),
            images, n)

    # ---- forward+backward (grad wrt params, incl. BN stat updates) -------
    from real_time_helmet_detection_tpu.train import loss_fn
    _, heat, off, whmap, mask = arrs

    def fwd_loss(p, x):
        total, _ = loss_fn(p, batch_stats, model, x, heat, off, whmap, mask,
                           cfg)
        return total

    measure("forward_backward", lambda x: jax.grad(fwd_loss)(params, x),
            images, n)

    # ---- stem / hourglass / neck+head in isolation -----------------------
    stem = PreLayer(mid_ch=128, out_ch=128, activation=cfg.activation,
                    pool=cfg.pool, dtype=dtype)
    sv = jax.jit(stem.init)(key, images[:1])
    measure("stem_fwd", lambda x: stem.apply(sv, x), images, n)

    feat = jnp.asarray(rng.standard_normal(
        (batch, imsize // 4, imsize // 4, 128)).astype(np.float32))
    hg = Hourglass(num_layer=4, in_ch=128, increase_ch=0,
                   activation=cfg.activation, pool=cfg.pool, dtype=dtype)
    hv = jax.jit(hg.init)(key, feat[:1])
    measure("hourglass_fwd", lambda x: hg.apply(hv, x), feat, n)

    neck = Neck(128, cfg.neck_activation, cfg.neck_pool, dtype=dtype)
    nv = jax.jit(neck.init)(key, feat[:1])
    measure("neck_fwd", lambda x: neck.apply(nv, x), feat, n)

    head = Head(6, dtype=dtype)
    hdv = jax.jit(head.init)(key, feat[:1])
    measure("head_fwd", lambda x: head.apply(hdv, x), feat, n)

    # ---- loss alone (one stack's split predictions) ----------------------
    m = imsize // 4
    ph = jax.nn.sigmoid(jnp.asarray(rng.standard_normal(
        (batch, m, m, 2)).astype(np.float32)))
    po = jnp.asarray(rng.standard_normal((batch, m, m, 2)).astype(np.float32))
    ps = jnp.asarray(rng.standard_normal((batch, m, m, 2)).astype(np.float32))
    measure("loss", lambda p: detection_loss(
        p, po, ps, heat, off, whmap, mask)["total"], ph, n)

    # ---- calibration microbenches ---------------------------------------
    nb = n * 4 if on_tpu else n
    conv = nn.Conv(128, (3, 3), padding=((1, 1), (1, 1)), use_bias=False,
                   dtype=dtype)
    cv = jax.jit(conv.init)(key, feat[:1])
    measure("conv3x3_128ch_128sq", lambda x: conv.apply(cv, x), feat, nb)

    stemconv = nn.Conv(64, (7, 7), strides=(2, 2), padding=((3, 3), (3, 3)),
                       dtype=dtype)
    scv = jax.jit(stemconv.init)(key, images[:1])
    measure("conv7x7s2_3to64", lambda x: stemconv.apply(scv, x), images, nb)

    # the same stem in its space-to-depth formulation (--stem-s2d): same
    # arithmetic, 12-channel contraction — the MXU-starvation A/B
    from real_time_helmet_detection_tpu.models.hourglass import StemConv
    s2d = StemConv(64, s2d=True, dtype=dtype)
    s2dv = jax.jit(s2d.init)(key, images[:1])
    measure("conv7x7s2_s2d", lambda x: s2d.apply(s2dv, x), images, nb)

    # full train step with --stem-s2d, for the end-to-end delta
    try:
        import dataclasses as _dc
        cfg_s2d = _dc.replace(cfg, stem_s2d=True)
        model_s2d = build_model(cfg_s2d, dtype=dtype)
        tx2 = build_optimizer(cfg_s2d, 100)
        st2 = create_train_state(model_s2d, cfg_s2d, key, imsize, tx2)
        body2 = make_train_step_body(model_s2d, tx2, cfg_s2d)
        train2 = make_scanned_train_fn(body2, n)
        c2 = jax.jit(train2, donate_argnums=(0,)).lower(st2, *arrs).compile()
        fl2 = flops_of(c2)
        if ANALYTIC:
            rec2 = analytic_rec(fl2, bytes_of(c2))
            results["components"]["train_step_stem_s2d"] = rec2
            log("train_step_stem_s2d (analytic): %s" % rec2)
            flush()
        else:
            np.asarray(c2(st2, *arrs)[1])
            st2 = create_train_state(model_s2d, cfg_s2d, key, imsize, tx2)
            dt2 = timed_fetch(lambda *a: c2(*a)[1], (st2, *arrs), overhead,
                              repeats=1)
            rec2 = {"ms": round(dt2 / n * 1e3, 3)}
            if fl2:
                rec2["mfu"] = round(fl2 * n / dt2 / peak, 4)
            results["components"]["train_step_stem_s2d"] = rec2
            log("train_step_stem_s2d: %s" % rec2)
            flush()
    except Exception as e:  # noqa: BLE001
        results["components"]["train_step_stem_s2d"] = {
            "error": str(e).splitlines()[-1][:200]}
        flush()

    bnm = nn.BatchNorm(use_running_average=False, momentum=0.9, epsilon=1e-5,
                       dtype=dtype)
    bv = jax.jit(bnm.init)(key, feat[:1])
    measure("batchnorm_128sq",
            lambda x: bnm.apply(bv, x, mutable=["batch_stats"])[0], feat, nb)

    measure("upsample2x_64sq", lambda x: jnp.repeat(
        jnp.repeat(x, 2, axis=-3), 2, axis=-2),
        feat[:, ::2, ::2, :], nb)

    if ANALYTIC:
        # Interpretation (computed, not hand-waved): what the compile-only
        # numbers can and cannot conclude about the r4 ~0.53 MFU plateau.
        ts = results["components"].get("train_step", {})
        if "gflops" in ts:
            t_mxu = ts["t_mxu_ms"]
            meas = ts.get("measured_ms", MEASURED_STEP_MS)
            t_hbm = ts.get("t_hbm_ms")  # None when bytes unavailable
            resid_gb = (meas - t_mxu) * 1e-3 * hbm / 1e9
            verdict = (
                "FLOPs are backend-independent: the step's %.2f TFLOP "
                "runs in %.1f ms at 100%% MFU, measured %.1f ms (%.2f "
                "MFU). " % (ts["gflops"] / 1e3, t_mxu, meas,
                            t_mxu / meas))
            if t_hbm is not None and t_hbm > meas:
                verdict += (
                    "The local pipeline's %.0f GB bytes-accessed would "
                    "imply a %.0f ms floor — the chip measured %.1fx "
                    "faster, so those bytes provably overestimate TPU "
                    "traffic and CANNOT prove the plateau is "
                    "HBM-fundamental. " % (ts.get("gbytes", 0), t_hbm,
                                           t_hbm / meas))
            elif t_hbm is None:
                verdict += ("No bytes-accessed metric from this "
                            "pipeline; no HBM-side conclusion. ")
            verdict += (
                "The residual %.1f ms equals ~%.0f GB of unoverlapped "
                "HBM traffic at %.0f GB/s — plausible for bf16 "
                "activations + remat-free backward at 512^2, but only "
                "the on-chip per-component timings (this script without "
                "--analytic) can attribute it."
                % (meas - t_mxu, resid_gb, hbm / 1e9))
            results["summary"] = {
                "pure_compute_floor_ms": t_mxu,
                "measured_ms": meas,
                "measured_src": MEASURED_SRC,
                "gap_to_compute_floor_ms": round(meas - t_mxu, 3),
                # measurement BEATS the cpu-bytes roofline -> those bytes
                # overestimate TPU traffic and cannot prove an HBM ceiling
                "cpu_bytes_roofline_ms": t_hbm,
                "cpu_bytes_are_tpu_bound": (None if t_hbm is None
                                            else bool(t_hbm <= meas)),
                # if the whole residual were unoverlapped HBM stall, the
                # traffic it implies (an upper bound on what the chip moves
                # beyond overlapped-with-compute bytes)
                "residual_as_hbm_gb": round(resid_gb, 2),
                "max_total_traffic_gb_at_measured": round(
                    meas * 1e-3 * hbm / 1e9, 2),
                "verdict": verdict,
            }
            flush()

    # ---- profiler trace attempt (plugin support permitting) --------------
    if on_tpu and "--no-trace" not in sys.argv:
        trace_dir = os.path.join(os.path.dirname(OUT_PATH), "trace")
        try:
            fwd = jax.jit(lambda x: model.apply(variables, x, train=False))
            np.asarray(fwd(images))  # compiled
            jax.profiler.start_trace(trace_dir)
            np.asarray(fwd(images))
            jax.profiler.stop_trace()
            found = []
            for root, _, files in os.walk(trace_dir):
                found += [os.path.join(root, f) for f in files]
            results["profiler_trace"] = {
                "dir": trace_dir, "files": len(found),
                "has_device_trace": any("xplane" in f or "trace" in f
                                        for f in found)}
            log("profiler trace: %d files" % len(found))
        except Exception as e:  # noqa: BLE001
            results["profiler_trace"] = {
                "error": str(e).splitlines()[-1][:200]}
        flush()

    flush()
    print(json.dumps(results))


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
