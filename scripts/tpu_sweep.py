"""Single-chip TPU sweep: batch scaling, num_stack=2, remat, step grid.

The single-chip experiment matrix: how throughput and MFU scale with batch size
for inference and training, what a deeper model (num_stack=2 — the
reference's self-test config, ref hourglass.py:241) costs, and what
`--remat` buys in HBM versus FLOPs at the flagship config.

Methodology is bench.py's (scan N iters inside ONE program, subtract
dispatch overhead — see bench.py's module docstring for why); this script
imports those helpers rather than re-deriving them. Each config is
independently guarded: a failed compile (e.g. OOM at large batch) records
the error string instead of killing the sweep.

A run can be killed mid-way, so results MERGE into
artifacts/<round>/sweep.json (round from $GRAFT_ROUND, default
bench.GRAFT_ROUND_DEFAULT — one constant for every round-scoped script) after
every single config — a killed run loses at most the in-flight config —
and `--only <section>[,<section>]` reruns just the missing sections
(inference, train, stack2, remat, stack4_768, step_grid, int8,
serve).

`step_grid` (ISSUE 2, grown by ISSUE 7 and ISSUE 20) is the (batch x
remat x loss-kernel x param-policy x epilogue x block-fuse x fwd-dtype)
matrix that picks the step-compression default: batches {16, 32, 64} x
--remat {none, stacks, full} x --loss-kernel {xla, fused} at the
fp32/xla baseline, plus the ISSUE-7 lever cells (--param-policy
bf16-compute and --epilogue fused, alone and together) per batch, plus
the ISSUE-20 lever cells (--block-fuse fused and --fwd-dtype int8,
alone and together, on the best ISSUE-7 base — the A/B twin is the
matching cell with the lever off), flagship 512^2 num_stack=1 bf16. The
record with the best img/s that compiled lands in `step_grid_selected`
(a record for the reader; no code reads it). Cells resume individually (a mid-sweep kill
re-measures only failed/missing cells, even under `--only step_grid`).
On-chip etiquette: queue this behind the single claim waiter (CLAUDE.md);
each config flushes before the next compiles.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (TARGET_CHIP, acquire_backend, chain_timed_fetch,
                   chip_peaks, flops_of, graft_round, log,
                   measure_dispatch_overhead, timed_fetch)
from real_time_helmet_detection_tpu.runtime import (maybe_job_heartbeat,
                                                    run_as_job)
from real_time_helmet_detection_tpu.utils import save_json


def memory_analysis_of(compiled):
    """Peak/argument/output HBM bytes from XLA, when the plugin supports it."""
    try:
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        return {
            "temp_mb": round(mem.temp_size_in_bytes / 2**20, 1),
            "argument_mb": round(mem.argument_size_in_bytes / 2**20, 1),
            "output_mb": round(mem.output_size_in_bytes / 2**20, 1),
            "peak_mb": round(
                (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes) / 2**20, 1),
        }
    except Exception as e:  # noqa: BLE001 — plugin-dependent API
        log("memory_analysis unavailable: %r" % e)
        return None


OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts",
    graft_round(), "sweep.json")

# section name (CLI --only vocabulary) -> results key
SECTION_KEYS = {"inference": "inference_batch_sweep",
                "train": "train_batch_sweep",
                "stack2": "num_stack2", "remat": "remat",
                "stack4_768": "stack4_768", "step_grid": "step_grid",
                "int8": "int8_inference", "serve": "serve_buckets",
                "arch_grid": "arch_grid"}


def merge_prior(results: dict, prior: dict, only: set) -> dict:
    """Carry prior-run records into `results` for sections NOT being rerun.

    A section in `only` starts empty (its records would duplicate on
    re-append). Platform-mismatched priors must never reach here — the
    caller redirects the output to a platform-suffixed file instead (a
    `--cpu --only X` rerun must not rewrite a merged TPU artifact with
    emptied TPU sections, round-2 advisor finding). Mutates and returns
    `results`; no I/O, so tests/test_bench_helpers.py can pin the
    semantics directly.
    """
    if prior.get("platform") != results.get("platform"):
        raise ValueError(
            "platform mismatch: prior %r vs current %r — write to a "
            "platform-suffixed file instead of merging"
            % (prior.get("platform"), results.get("platform")))
    for sec, k in SECTION_KEYS.items():
        if sec not in only:
            if k in prior:
                results[k] = prior[k]
            # else: prior predates this section (older sweep.json) — keep
            # the fresh empty value, if the caller's dict has one at all
            if sec == "step_grid" and "step_grid_selected" in prior:
                # the derived pick rides with its section
                results["step_grid_selected"] = prior["step_grid_selected"]
            if sec == "arch_grid" and "arch_grid_selected" in prior:
                results["arch_grid_selected"] = prior["arch_grid_selected"]
    return results


def main() -> None:
    only = None
    for i, a in enumerate(sys.argv):
        if a == "--only" and i + 1 < len(sys.argv):
            only = set(sys.argv[i + 1].split(","))
            unknown = only - set(SECTION_KEYS)
            if unknown:
                # a typo would silently run nothing while still rewriting
                # the output file (round-2 advisor finding)
                raise SystemExit("unknown --only section(s) %s; valid: %s"
                                 % (sorted(unknown), sorted(SECTION_KEYS)))

    # never silently fall back: a CPU-platform rerun would discard the
    # merged TPU records (merge_prior drops other-platform priors)
    jax, devs = acquire_backend()
    import jax.numpy as jnp
    from jax import lax

    platform = devs[0].platform
    device_kind = devs[0].device_kind
    on_tpu = platform == "tpu"
    # an explicit --cpu run (plumbing check) classifies against the target
    peak, _ = chip_peaks(TARGET_CHIP if platform == "cpu" else device_kind)
    log("backend: %s (%s)" % (device_kind, platform))

    # flight recorder: compile spans + host context into the round's span
    # log when $OBS_SPAN_LOG is set (tpu_queue exports it for every job);
    # disabled spans still TIME (the per-cell compile_s fields read them)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = maybe_tracer()
    tracer.context(phase="tpu_sweep", platform=platform)

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import (
        create_train_state, init_variables, make_scanned_train_fn,
        make_train_step_body)

    imsize = 512 if on_tpu else 64
    overhead = measure_dispatch_overhead()
    log("dispatch overhead: %.1f ms" % (overhead * 1e3))
    rng = np.random.default_rng(0)
    results = {
        "platform": platform, "device_kind": device_kind, "imsize": imsize,
        "dispatch_ms": round(overhead * 1e3, 3),
        "inference_batch_sweep": [], "train_batch_sweep": [],
        "num_stack2": {}, "remat": [], "stack4_768": [], "step_grid": [],
        "int8_inference": [], "serve_buckets": [], "arch_grid": [],
    }
    def read_prior(path):
        """Prior results at `path`, or None if absent/unreadable — a kill
        mid-flush can truncate the JSON; the salvage rerun must proceed as
        if no prior existed rather than crash before reaching the chip."""
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return json.load(f)
        except (json.JSONDecodeError, OSError) as e:
            log("prior %s unreadable (%r); treating as absent" % (path, e))
            return None

    # TPU owns the canonical filename UNCONDITIONALLY: non-TPU runs write
    # to a platform-suffixed file, and a TPU run that finds a legacy
    # non-TPU sweep.json (e.g. a pre-r3 CPU fallback) migrates it aside
    # and takes the canonical path (review finding: the earlier version
    # protected whichever platform wrote first).
    out_path = OUT_PATH if platform == "tpu" else \
        OUT_PATH.replace(".json", ".%s.json" % platform)
    if out_path != OUT_PATH:
        log("non-TPU run: writing to %s (canonical %s is TPU-only)"
            % (out_path, OUT_PATH))
    prior = read_prior(out_path)
    if prior is not None and prior.get("platform") != platform:
        if platform == "tpu":
            aside = OUT_PATH.replace(
                ".json", ".%s.json" % prior.get("platform", "unknown"))
            n = 1
            while os.path.exists(aside):  # never clobber a newer suffixed
                aside = OUT_PATH.replace(  # file with the legacy one
                    ".json", ".%s.%d.json" % (prior.get("platform",
                                                        "unknown"), n))
                n += 1
            os.replace(out_path, aside)
            log("migrated legacy platform=%r sweep.json aside to %s"
                % (prior.get("platform"), aside))
        else:
            # a mismatched prior in an already-suffixed file is garbage;
            # never double-suffix — treat it as absent
            log("prior in %s is platform=%r; ignoring it"
                % (out_path, prior.get("platform")))
        prior = None
    if prior is not None and only:
        results = merge_prior(results, prior, only)

    hb = maybe_job_heartbeat()

    def flush():
        # tmp + os.replace: the documented truncation hazard — a kill
        # (or the supervisor's stale-heartbeat SIGTERM) mid-flush must
        # never destroy the per-config partials the salvage step records.
        # Each flush is also the job's natural heartbeat.
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        save_json(out_path, results, indent=1)
        hb.beat("flushed %s" % os.path.basename(out_path))

    def want(section):
        return only is None or section in only

    def predict_chain(predict, n):
        # donates the image batch and returns the final carry as its
        # aliasing target (bench.py's make_predict_chain contract — no
        # second image buffer held, no donation warning)
        def prog(variables, images):
            def body(imgs, _):
                det = predict(variables, imgs)
                eps = (jnp.tanh(jnp.sum(det.scores)) * 1e-12).astype(
                    imgs.dtype)
                return imgs + eps, ()
            final, _ = lax.scan(body, images, None, length=n)
            return final, jnp.sum(final[0, 0, 0])
        return jax.jit(prog, donate_argnums=(1,))

    def bench_inference(num_stack, batch, n):
        cfg = Config(num_stack=num_stack, hourglass_inch=128, num_cls=2,
                     topk=100, conf_th=0.0, nms_th=0.5, imsize=imsize)
        model = build_model(cfg, dtype=jnp.bfloat16)
        params, batch_stats = init_variables(model, jax.random.key(0), imsize)
        variables = {"params": params, "batch_stats": batch_stats}
        predict = make_predict_fn(model, cfg)
        images = jnp.asarray(rng.standard_normal(
            (batch, imsize, imsize, 3)).astype(np.float32))
        with tracer.span("compile", section="inference", batch=batch) as sp:
            compiled = predict_chain(predict, n).lower(
                variables, images).compile()
        fl = flops_of(compiled)
        images, s = compiled(variables, images)  # warmup (donates images)
        np.asarray(s)
        dt = chain_timed_fetch(compiled, variables, images, overhead)
        rec = {"batch": batch, "img_per_sec": round(batch * n / dt, 1),
               "ms_per_batch": round(dt / n * 1e3, 3),
               "compile_s": round(sp.dur_s, 1)}
        if fl:
            rec["mfu_fwd"] = round(fl * n / dt / peak, 4)
        return rec

    def bench_train(num_stack, batch, n, remat, imsize_=None,
                    loss_kernel="auto", param_policy="fp32",
                    epilogue="auto", block_fuse="auto", fwd_dtype="bf16"):
        sz = imsize_ or imsize
        cfg = Config(num_stack=num_stack, hourglass_inch=128, num_cls=2,
                     batch_size=batch, amp=True, imsize=sz, remat=remat,
                     loss_kernel=loss_kernel, param_policy=param_policy,
                     epilogue=epilogue, block_fuse=block_fuse,
                     fwd_dtype=fwd_dtype)
        model = build_model(cfg, dtype=jnp.bfloat16)
        tx = build_optimizer(cfg, 100)
        state = create_train_state(model, cfg, jax.random.key(0), sz, tx)
        body = make_train_step_body(model, tx, cfg)
        arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
            batch, sz, pos_rate=0.01))
        train_n = make_scanned_train_fn(body, n)
        with tracer.span("compile", section="train", batch=batch,
                         remat=cfg.remat) as sp:
            compiled = jax.jit(train_n, donate_argnums=(0,)).lower(
                state, *arrs).compile()
        compile_s = sp.dur_s
        fl = flops_of(compiled)
        mem = memory_analysis_of(compiled)
        np.asarray(compiled(state, *arrs)[1])  # warmup (donates state)
        state = create_train_state(model, cfg, jax.random.key(0), sz, tx)
        # fetch only the scalar loss — the returned final state exists to
        # give the donated input an aliasing target, not to be fetched
        dt = timed_fetch(lambda *a: compiled(*a)[1], (state, *arrs),
                         overhead, repeats=1)
        from real_time_helmet_detection_tpu.ops.pallas.select import \
            kernel_plan
        from bench import bytes_of
        plan = kernel_plan(cfg)
        rec = {"batch": batch, "remat": cfg.remat, "imsize": sz,
               "num_stack": num_stack,
               "loss_kernel": plan["loss"],
               "param_policy": cfg.param_policy,
               "epilogue": plan["epilogue"],
               "block_fuse": plan["block_fuse"],
               "fwd_dtype": cfg.fwd_dtype,
               "img_per_sec_chip": round(batch * n / dt, 1),
               "step_ms": round(dt / n * 1e3, 3),
               "compile_s": round(compile_s, 1)}
        if fl:
            rec["mfu_train"] = round(fl * n / dt / peak, 4)
        hbm_bytes = bytes_of(compiled)
        if hbm_bytes:
            rec["hbm_bytes_per_step"] = hbm_bytes
        if mem:
            rec["memory"] = mem
        return rec

    def bench_int8(batch, n):
        """Float vs int8 predict chain at one batch size (ISSUE 5): same
        checkpoint pytree, scales from a synthetic calibration pass (the
        chip measurement wants the CONV speedup; mAP parity is the CPU
        fixture's job, tests/test_quant.py). Both chains use the same
        donation/timing methodology as bench_inference."""
        import dataclasses

        from real_time_helmet_detection_tpu.ops.quant import (
            calibrate_scales, synthetic_calibration_batches)
        cfg = Config(num_stack=1, hourglass_inch=128, num_cls=2,
                     topk=100, conf_th=0.0, nms_th=0.5, imsize=imsize)
        model = build_model(cfg, dtype=jnp.bfloat16)
        params, batch_stats = init_variables(model, jax.random.key(0), imsize)
        variables = {"params": params, "batch_stats": batch_stats}
        scales = calibrate_scales(
            cfg, variables,
            synthetic_calibration_batches(batch, imsize, n=2),
            dtype=jnp.bfloat16)
        rec = {"batch": batch}
        for dtype_name in ("bf16", "int8"):
            icfg = dataclasses.replace(cfg, infer_dtype=dtype_name)
            predict = make_predict_fn(
                model, icfg,
                quant_scales=scales if dtype_name == "int8" else None)
            images = jnp.asarray(rng.standard_normal(
                (batch, imsize, imsize, 3)).astype(np.float32))
            with tracer.span("compile", section="int8", batch=batch,
                             dtype=dtype_name) as sp:
                compiled = predict_chain(predict, n).lower(
                    variables, images).compile()
            images, s = compiled(variables, images)  # warmup (donates)
            np.asarray(s)
            dt = chain_timed_fetch(compiled, variables, images, overhead)
            rec[dtype_name] = {
                "img_per_sec": round(batch * n / dt, 1),
                "ms_per_batch": round(dt / n * 1e3, 3),
                "compile_s": round(sp.dur_s, 1)}
            hb.beat("int8 section b=%d %s done" % (batch, dtype_name))
        rec["int8_vs_bf16"] = round(
            rec["int8"]["img_per_sec"] / rec["bf16"]["img_per_sec"], 3)
        return rec

    # --- 1. inference batch sweep ----------------------------------------
    if want("inference"):
        for batch in ([1, 2, 4, 8, 16, 32] if on_tpu else [1, 2]):
            n = max(32, min(512, 4096 // batch)) if on_tpu else 2
            try:
                rec = bench_inference(1, batch, n)
                results["inference_batch_sweep"].append(rec)
                log("infer b=%d: %s" % (batch, rec))
            except Exception as e:  # noqa: BLE001
                results["inference_batch_sweep"].append(
                    {"batch": batch, "error": str(e).splitlines()[-1][:200]})
                log("infer b=%d FAILED: %r" % (batch, e))
            flush()

    # --- 2. train batch sweep --------------------------------------------
    if want("train"):
        # 16 (the flagship config, known-good compile) first: if IT hangs,
        # the backend is the problem; if only another batch hangs, that
        # config is.
        for batch in ([16, 8, 32, 64] if on_tpu else [2]):
            n = max(8, min(64, 1024 // batch)) if on_tpu else 2
            try:
                rec = bench_train(1, batch, n, remat=False)
                results["train_batch_sweep"].append(rec)
                log("train b=%d: %s" % (batch, rec))
            except Exception as e:  # noqa: BLE001
                results["train_batch_sweep"].append(
                    {"batch": batch, "error": str(e).splitlines()[-1][:200]})
                log("train b=%d FAILED: %r" % (batch, e))
            flush()

    # --- 3. num_stack=2 datapoint (ref hourglass.py:241 self-test config) -
    if want("stack2"):
        try:
            results["num_stack2"]["inference"] = bench_inference(
                2, 8 if on_tpu else 1, 256 if on_tpu else 2)
            log("stack2 infer: %s" % results["num_stack2"]["inference"])
        except Exception as e:  # noqa: BLE001
            results["num_stack2"]["inference"] = {
                "error": str(e).splitlines()[-1][:200]}
        flush()
        try:
            results["num_stack2"]["train"] = bench_train(
                2, 16 if on_tpu else 2, 32 if on_tpu else 2, remat=False)
            log("stack2 train: %s" % results["num_stack2"]["train"])
        except Exception as e:  # noqa: BLE001
            results["num_stack2"]["train"] = {
                "error": str(e).splitlines()[-1][:200]}
        flush()

    # --- 4. remat on/off at flagship + large batch ------------------------
    if want("remat"):
        for batch, remat in ([(16, True), (64, True)] if on_tpu
                             else [(2, True)]):
            n = max(8, min(64, 1024 // batch)) if on_tpu else 2
            try:
                rec = bench_train(1, batch, n, remat=remat)
                results["remat"].append(rec)
                log("remat b=%d: %s" % (batch, rec))
            except Exception as e:  # noqa: BLE001
                results["remat"].append(
                    {"batch": batch, "remat": remat,
                     "error": str(e).splitlines()[-1][:200]})
                log("remat b=%d FAILED: %r" % (batch, e))
            flush()

    # --- 5. BASELINE config #4: num_stack=4 @768^2 with remat -------------
    # (BASELINE.json configs[3]; remat is the memory lever that makes this
    # fit — record step time, MFU and the HBM high-water from XLA's
    # memory analysis. Smaller batch first: the known-good compile.)
    if want("stack4_768"):
        for batch, remat in ([(8, True), (16, True), (16, False)] if on_tpu
                             else [(1, True)]):
            n = 8 if on_tpu else 2
            try:
                rec = bench_train(4, batch, n, remat=remat,
                                  imsize_=768 if on_tpu else 64)
                results["stack4_768"].append(rec)
                log("stack4_768 b=%d remat=%s: %s" % (batch, remat, rec))
            except Exception as e:  # noqa: BLE001
                results["stack4_768"].append(
                    {"batch": batch, "remat": remat,
                     "error": str(e).splitlines()[-1][:200]})
                log("stack4_768 b=%d FAILED: %r" % (batch, e))
            flush()

    # --- 6. step-compression grid: batch x remat x loss-kernel ------------
    # (ISSUE 2: the matrix that picks the new default train-step config.
    # Known-good compile first (b16/none/xla ~ the flagship baseline); the
    # big-batch remat=none cells are EXPECTED to OOM — that is the datum
    # that makes remat the batch-32/64 enabler, recorded not skipped.)
    if want("step_grid"):
        # Cells are (batch, remat, loss_kernel, param_policy, epilogue,
        # block_fuse, fwd_dtype). The ISSUE-2 (batch x remat x loss-kernel)
        # matrix keeps its explicit epilogue="xla" baseline cells; the
        # ISSUE-7 axes ride as a focused sub-grid (each new lever alone +
        # both together, per batch) rather than the full 108-cell cross
        # product — the levers are byte-additive, not interacting, per the
        # roofline class tables. The ISSUE-20 axes follow the same law:
        # block-fuse and int8-forward each alone on the best known base
        # (remat=none, fused loss, fused epilogue), then both together,
        # per batch — the A/B twin is the matching cell with the lever off.
        if on_tpu:
            grid = [(b, r, k, "fp32", "xla", "xla", "bf16")
                    for b in (16, 32, 64)
                    for r in ("none", "stacks", "full")
                    for k in ("xla", "fused")]
            grid += [(b, "none", "fused", pp, epi, "xla", "bf16")
                     for b in (16, 32, 64)
                     for pp, epi in (("bf16-compute", "xla"),
                                     ("fp32", "fused"),
                                     ("bf16-compute", "fused"))]
            grid += [(b, "none", "fused", "bf16-compute", "fused", bf, fd)
                     for b in (16, 32, 64)
                     for bf, fd in (("fused", "bf16"),
                                    ("xla", "int8"),
                                    ("fused", "int8"))]
        else:
            grid = [(2, "none", "xla", "fp32", "xla", "xla", "bf16"),
                    (2, "stacks", "fused", "fp32", "xla", "xla", "bf16"),
                    (2, "full", "fused", "fp32", "xla", "xla", "bf16"),
                    (2, "none", "xla", "bf16-compute", "xla", "xla", "bf16"),
                    (2, "none", "xla", "fp32", "fused", "xla", "bf16"),
                    (2, "none", "xla", "bf16-compute", "fused", "xla",
                     "bf16"),
                    (2, "none", "xla", "fp32", "xla", "fused", "bf16"),
                    (2, "none", "xla", "fp32", "xla", "xla", "int8")]
        # per-cell resume (the int8 section's pattern): successful cells
        # from the prior run survive a mid-sweep kill even under
        # `--only step_grid` — only failed/missing cells re-measure
        prior_cells = [r for r in (prior or {}).get("step_grid", [])
                       if "img_per_sec_chip" in r]
        for r in prior_cells:
            if r not in results["step_grid"]:
                results["step_grid"].append(r)
        # pre-ISSUE-20 records lack the new axes: they were measured with
        # the unfused bf16 step, so they default to the (xla, bf16) cell
        done = {(r.get("batch"), r.get("remat"), r.get("loss_kernel"),
                 r.get("param_policy", "fp32"), r.get("epilogue", "xla"),
                 r.get("block_fuse", "xla"), r.get("fwd_dtype", "bf16"))
                for r in results["step_grid"] if "img_per_sec_chip" in r}
        for batch, remat, kernel, policy, epilogue, bfuse, fdt in grid:
            # grid cells are fully explicit (no "auto"), so the raw tuple
            # matches the resolved fields bench_train records
            cell = (batch, remat, kernel, policy, epilogue, bfuse, fdt)
            if cell in done:
                log("step_grid %s already measured; skipping" % (cell,))
                continue
            n = max(8, min(64, 1024 // batch)) if on_tpu else 2
            try:
                rec = bench_train(1, batch, n, remat=remat,
                                  loss_kernel=kernel, param_policy=policy,
                                  epilogue=epilogue, block_fuse=bfuse,
                                  fwd_dtype=fdt)
                results["step_grid"].append(rec)
                log("step_grid b=%d remat=%s loss=%s pp=%s epi=%s bf=%s "
                    "fwd=%s: %s" % (batch, remat, kernel, policy, epilogue,
                                    bfuse, fdt, rec))
            except Exception as e:  # noqa: BLE001
                results["step_grid"].append(
                    {"batch": batch, "remat": remat, "loss_kernel": kernel,
                     "param_policy": policy, "epilogue": epilogue,
                     "block_fuse": bfuse, "fwd_dtype": fdt,
                     "error": str(e).splitlines()[-1][:200]})
                log("step_grid b=%d remat=%s loss=%s pp=%s epi=%s bf=%s "
                    "fwd=%s FAILED: %r" % (batch, remat, kernel, policy,
                                           epilogue, bfuse, fdt, e))
            flush()
        ok = [r for r in results["step_grid"] if "img_per_sec_chip" in r]
        if ok:
            results["step_grid_selected"] = max(
                ok, key=lambda r: r["img_per_sec_chip"])
            log("step_grid selected: %s" % results["step_grid_selected"])
            flush()

    # --- 7. int8 inference A/B (ISSUE 5) ----------------------------------
    # (the v5e's int8 MXU path is 2x the bf16 peak; the predict step is
    # conv-bound per PR 2's roofline — this section measures how much of
    # the 2x the BN-folded quantized predict actually realizes, per batch.
    # Each batch cell flushes independently so a killed run loses at most
    # the in-flight cell; `--only int8` reruns just this section.)
    if want("int8"):
        # per-config resume: successful cells from the prior run survive a
        # mid-sweep kill even when `--only int8` reruns the section —
        # only failed/missing batches are re-measured
        prior_cells = [r for r in (prior or {}).get("int8_inference", [])
                       if "int8_vs_bf16" in r]
        for r in prior_cells:
            if r not in results["int8_inference"]:
                results["int8_inference"].append(r)
        done = {r.get("batch") for r in results["int8_inference"]
                if "int8_vs_bf16" in r}
        for batch in ([1, 4, 16, 32] if on_tpu else [2]):
            if batch in done:
                log("int8 b=%d already measured; skipping" % batch)
                continue
            n = max(32, min(512, 4096 // batch)) if on_tpu else 2
            try:
                rec = bench_int8(batch, n)
                results["int8_inference"].append(rec)
                log("int8 b=%d: %s" % (batch, rec))
            except Exception as e:  # noqa: BLE001
                results["int8_inference"].append(
                    {"batch": batch, "error": str(e).splitlines()[-1][:200]})
                log("int8 b=%d FAILED: %r" % (batch, e))
            flush()

    # --- 8. serve bucket latency table (ISSUE 8) --------------------------
    # The per-bucket batch latency of the SERVE-WIRE program (raw uint8 in,
    # normalize on-device — the engine's ingress contract), one cell per
    # bucket of the default serve set. This is the table that sizes the
    # serving knobs: deadline >= queue_wait + (depth+2) x the largest
    # bucket's ms_per_batch (docs/ARCHITECTURE.md "Serving engine").
    # Per-cell flush + prior-cell resume, the int8 section's discipline.
    if want("serve"):
        def bench_serve(bucket, n):
            cfg = Config(num_stack=1, hourglass_inch=128, num_cls=2,
                         topk=100, conf_th=0.0, nms_th=0.5, imsize=imsize)
            model = build_model(cfg, dtype=jnp.bfloat16 if on_tpu
                                else None)
            params, batch_stats = init_variables(model, jax.random.key(0),
                                                 imsize)
            variables = {"params": params, "batch_stats": batch_stats}
            predict = make_predict_fn(model, cfg, normalize="imagenet")
            images = jnp.asarray(rng.integers(
                0, 256, (bucket, imsize, imsize, 3)).astype(np.uint8))
            with tracer.span("compile", section="serve",
                             bucket=bucket) as sp:
                compiled = predict_chain(predict, n).lower(
                    variables, images).compile()
            images, s = compiled(variables, images)  # warmup (donates)
            np.asarray(s)
            dt = chain_timed_fetch(compiled, variables, images, overhead)
            return {"bucket": bucket,
                    "img_per_sec": round(bucket * n / dt, 1),
                    "ms_per_batch": round(dt / n * 1e3, 3),
                    "compile_s": round(sp.dur_s, 1)}

        prior_cells = [r for r in (prior or {}).get("serve_buckets", [])
                       if "ms_per_batch" in r]
        for r in prior_cells:
            if r not in results["serve_buckets"]:
                results["serve_buckets"].append(r)
        done = {r.get("bucket") for r in results["serve_buckets"]
                if "ms_per_batch" in r}
        for bucket in ([1, 2, 4, 8, 16] if on_tpu else [1, 2]):
            if bucket in done:
                log("serve b=%d already measured; skipping" % bucket)
                continue
            n = max(32, min(512, 4096 // bucket)) if on_tpu else 2
            try:
                rec = bench_serve(bucket, n)
                results["serve_buckets"].append(rec)
                log("serve b=%d: %s" % (bucket, rec))
            except Exception as e:  # noqa: BLE001
                results["serve_buckets"].append(
                    {"bucket": bucket,
                     "error": str(e).splitlines()[-1][:200]})
                log("serve b=%d FAILED: %r" % (bucket, e))
            flush()

    # --- 9. architecture grid: variant x stacks x width (ISSUE 13) --------
    # The outer loop of the latency-tier architecture search (Lighter
    # Stacked Hourglass variants, arxiv 2107.13643, searched with the
    # full-stack-search methodology of arxiv 2105.12842, PAPERS.md): each
    # cell compiles the b1 SERVE-WIRE predict program at (variant, stacks,
    # width) and scores it with the roofline counting model (analytic
    # FLOPs + operand/result HBM bytes via parse_hlo/attribute —
    # deterministic, CPU-valid) plus XLA cost analysis. `--arch-map`
    # additionally trains a synthetic-fixture smoke model per cell and
    # records its mAP (the chip twin runs this; the counting model alone
    # already orders the tiers). The tier pick lands in
    # `arch_grid_selected` — the committed record config.TIER_PRESETS is
    # calibrated against. Per-cell flush + prior-cell resume, the int8
    # section's discipline.
    if want("arch_grid"):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import roofline as _roofline
        arch_map = "--arch-map" in sys.argv

        def bench_arch(variant, stacks, width):
            cfg = Config(num_stack=stacks, hourglass_inch=width,
                         variant=variant,
                         stem_width=min(128, width),  # tier geometry
                         num_cls=2, topk=100,
                         conf_th=0.0, nms_th=0.5, imsize=imsize)
            model = build_model(cfg, dtype=jnp.bfloat16)
            params, batch_stats = init_variables(model, jax.random.key(0),
                                                 imsize)
            variables = {"params": params, "batch_stats": batch_stats}
            predict = make_predict_fn(model, cfg, normalize="imagenet")
            images = jnp.zeros((1, imsize, imsize, 3), jnp.uint8)
            with tracer.span("compile", section="arch_grid",
                             variant=variant, stacks=stacks,
                             width=width) as sp:
                compiled = predict.lower(variables, images).compile()
            rows = _roofline.attribute(
                *_roofline.parse_hlo(compiled.as_text()))
            by_class = _roofline.class_totals(rows)
            rec = {"variant": variant, "num_stack": stacks, "width": width,
                   "imsize": imsize, "batch": 1,
                   "params_m": round(sum(
                       x.size for x in jax.tree.leaves(params)) / 1e6, 4),
                   "predict_bytes": round(sum(r["bytes"] for r in rows)),
                   "conv_bytes": round(by_class["conv"]["bytes"]),
                   "compile_s": round(sp.dur_s, 1)}
            fl = flops_of(compiled)
            if fl:
                rec["predict_gflops"] = round(fl / 1e9, 3)
            if arch_map:
                rec.update(arch_cell_map(variant, stacks, width))
            return rec

        def arch_cell_map(variant, stacks, width):
            """Smoke-scale fixture mAP for one cell: train a scaled-down
            twin (width/8 off-chip — CPU cannot train real widths in
            sweep time) on the shared synthetic fixture, eval held-out
            mAP. The RANKING signal that joins the counting model; the
            real-width per-tier mAP is quality_matrix --tiers' job."""
            from real_time_helmet_detection_tpu.data import \
                make_synthetic_voc
            from real_time_helmet_detection_tpu.evaluate import evaluate
            from real_time_helmet_detection_tpu.train import train
            map_imsize = 256 if on_tpu else 64
            map_width = width if on_tpu else max(8, width // 8)
            n_train, n_test = (128, 32) if on_tpu else (16, 8)
            epochs = 6 if on_tpu else 2
            root = "/tmp/voc_arch_%d" % map_imsize
            if not os.path.isdir(root):
                make_synthetic_voc(root, num_train=n_train,
                                   num_test=n_test,
                                   imsize=(map_imsize, map_imsize),
                                   max_objects=8, seed=42, style="scenes")
            save = "/tmp/arch_map/%s_s%d_w%d" % (variant, stacks, width)
            if os.path.isdir(save):
                import shutil
                shutil.rmtree(save)
            cfg = Config(train_flag=True, data=root, save_path=save,
                         variant=variant, num_stack=stacks,
                         hourglass_inch=map_width,
                         stem_width=min(128, map_width), num_cls=2,
                         batch_size=4, amp=on_tpu, end_epoch=epochs,
                         imsize=map_imsize,
                         multiscale=[map_imsize, map_imsize, 64],
                         keep_ckpt=1, ckpt_interval=epochs,
                         num_workers=2, print_interval=10, summary=False)
            train(cfg)
            cks = [d for d in os.listdir(save)
                   if d.startswith("check_point_")]
            ckpt = os.path.join(save, max(
                cks, key=lambda d: int(d.rsplit("_", 1)[1])))
            m = evaluate(Config(
                data=root, save_path=save, model_load=ckpt,
                variant=variant, num_stack=stacks,
                hourglass_inch=map_width,
                stem_width=min(128, map_width), num_cls=2, batch_size=4,
                imsize=map_imsize, topk=100, conf_th=0.01, nms="nms",
                nms_th=0.5, num_workers=2))
            return {"map": round(float(m["map"]), 4),
                    "map_imsize": map_imsize, "map_width": map_width}

        if on_tpu:
            grid = [(v, s, w) for v in ("residual", "depthwise", "ghost")
                    for s in (1, 2) for w in (64, 96, 128)]
        else:
            # CPU: the three tier archetypes plus enough neighbors to
            # order the frontier, at compile-feasible cost
            grid = ([(v, 1, w)
                     for v in ("residual", "depthwise", "ghost")
                     for w in (64, 96)]
                    + [("residual", 2, 128)])
        prior_cells = [r for r in (prior or {}).get("arch_grid", [])
                       if "predict_bytes" in r]
        for r in prior_cells:
            if r not in results["arch_grid"]:
                results["arch_grid"].append(r)
        done = {(r.get("variant"), r.get("num_stack"), r.get("width"))
                for r in results["arch_grid"] if "predict_bytes" in r}
        for variant, stacks, width in grid:
            if (variant, stacks, width) in done:
                log("arch_grid %s/s%d/w%d already measured; skipping"
                    % (variant, stacks, width))
                continue
            try:
                rec = bench_arch(variant, stacks, width)
                results["arch_grid"].append(rec)
                log("arch_grid %s/s%d/w%d: %s"
                    % (variant, stacks, width, rec))
            except Exception as e:  # noqa: BLE001
                results["arch_grid"].append(
                    {"variant": variant, "num_stack": stacks,
                     "width": width,
                     "error": str(e).splitlines()[-1][:200]})
                log("arch_grid %s/s%d/w%d FAILED: %r"
                    % (variant, stacks, width, e))
            hb.beat("arch_grid %s/s%d/w%d done" % (variant, stacks,
                                                   width))
            flush()
        ok = [r for r in results["arch_grid"]
              if "predict_gflops" in r and "predict_bytes" in r]
        if ok:
            import math

            def ident(r):
                keep = ("variant", "num_stack", "width", "predict_gflops",
                        "predict_bytes", "map")
                return {k: r[k] for k in keep if k in r}

            by_flops = sorted(ok, key=lambda r: (r["predict_gflops"],
                                                 r["predict_bytes"]))
            edge, quality = by_flops[0], by_flops[-1]
            inner = [r for r in ok
                     if r is not edge and r is not quality] or ok
            mid = math.sqrt(edge["predict_gflops"]
                            * quality["predict_gflops"])
            throughput = min(inner, key=lambda r: (
                abs(math.log(r["predict_gflops"]) - math.log(mid)),
                r["predict_bytes"]))
            results["arch_grid_selected"] = {
                "policy": "edge = min predict FLOPs; quality = max "
                          "(the flagship cell); throughput = the "
                          "geometric-mid FLOPs cell — fixture mAP "
                          "(--arch-map / quality_matrix --tiers) "
                          "refines ties",
                "edge": ident(edge), "throughput": ident(throughput),
                "quality": ident(quality)}
            log("arch_grid selected: %s" % results["arch_grid_selected"])
            flush()

    flush()
    print(json.dumps(results))


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
