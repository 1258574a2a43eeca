"""Quality-lever matrix on the hard 'scenes' fixture (round-3 verdict #3).

Scores each lever with the same train->eval->mAP loop the reference runs
by hand (ref train.py:86-162 + evaluate.py:15-97); the matrix harness
itself has no reference analogue.

Round 2 left the framework's quality levers built but unmeasured: the
saturated blocks fixture (mAP 0.96-0.98) could not show a delta for
num_stack=2, EMA eval, multiscale training, or soft-NMS. This script
trains the flagship config and its variants on the HARD scenes fixture
(data/synthetic.py style="scenes": occlusion, 5-10x scale range, decoys,
class imbalance) and records held-out mAP for each lever:

  base        num_stack=1, fixed 512, hard NMS        (1 training)
  base+soft   same weights, soft-NMS eval             (eval only)
  base+ema    same training's EMA weight stream       (eval only;
              the base run trains with --ema-decay so both weight sets
              come out of ONE run — ref has no EMA at all. decay 0.998
              is budget-appropriate for this run: horizon 1/(1-d) = 500
              steps ~ 28% of the 45ep x 40step budget, spanning the
              final LR-drop phase — the regime EMA is meant for; the r3
              -3.2 mAP result used the same horizon at a 600-step-shorter
              budget, so this row resolves decay-vs-budget with data)
  base+pool5  same weights, 5x5 peak window           (eval only)
  base+int8   same weights, BN-folded int8 predict    (eval only;
              --infer-dtype int8, ops/quant.py — records
              delta_map_vs_bf16, the mAP-parity gate for the int8
              inference engine: same checkpoint, both dtypes)
  stack2      num_stack=2                             (1 training)
  multiscale  bucketed {384,448,512} on a 576 canvas  (1 training)
  multiscale+soft         same multiscale weights, soft-NMS (eval only)
  stack2+multiscale       the two biggest levers composed  (1 training)
  stack2+multiscale+soft  same composed weights, soft-NMS  (eval only)

Rows merge into artifacts/r03/quality_matrix.json after every eval, so a
killed run loses at most the in-flight row; rerunning skips completed
rows (delete a row to force its rerun). Run on the chip; CPU would take
days at 512^2.

`--tiers` (ISSUE 13) runs the latency-tier Pareto rows instead: the
quality tier (flagship recipe) trains first and becomes the DISTILLATION
TEACHER; the edge tier trains twice (scratch AND `--distill`ed — the
distilled-beats-scratch comparison is the acceptance gate for the
distillation recipe) and the throughput tier distills + evals through
int8 PTQ. Every tier row carries fixture mAP, the roofline counting
model of its b1 serve-wire predict (analytic FLOPs + operand/result HBM
bytes — reused from scripts/roofline.py, CPU-valid), and a measured
serve-wire latency (bench.chain_timed_fetch over a donating predict
chain — the sanctioned timing harness). The artifact
(schema quality-matrix-v2) is the latency<->mAP Pareto frontier perfgate
ratchet-gates per tier (the `quality` tolerance class).

`--cascade` (ISSUE 16) calibrates the cascade escalation threshold on
the SAME tier fixture (and the same /tmp tier checkpoints — a prior
`--tiers` run's trainings are reused via their DONE markers): the edge
tier's confidence-summary predict (`make_predict_fn(cascade_summary=
True)`) and the quality tier's plain predict each score the held-out
split once, then the threshold sweep blends them per image (escalate iff
edge confidence < t -> take the quality answer) into an
escalation-rate vs blended-mAP curve. The chosen operating point — the
SMALLEST escalation rate whose blended mAP is within 2 pts of
all-quality routing — lands in `artifacts/<round>/cascade.json` (schema
cascade-calibration-v1), which `config.cascade_overrides` loads for
`--cascade` serving exactly the way quant scales artifacts are loaded,
and perfgate gates in its ABSOLUTE `quality` class.

`--streams` (ISSUE 17) calibrates the temporal tile-skip threshold on a
VIDEO fixture synthesized from the same held-out split (tiles drawn
from the pool, per-tile replacement with prob 1-redundancy per frame,
plus a small uint8 sensor jitter so static tiles carry a nonzero delta
floor): every noisy tile is scored once by the quality tier and every
consecutive-frame `ops.delta.tile_delta_summary` leaf is fetched once,
then each candidate threshold replays the stream-session cache OFFLINE
(a tile recomputes iff its delta >= t, else its last computed answer
stands) into a tile-skip-rate vs blended-video-mAP curve. The chosen
operating point — the LARGEST skip rate whose blended video mAP is
within 2 pts of full inference — lands in
`artifacts/<round>/streams.json` (schema stream-calibration-v1), which
`config.stream_overrides` resolves for `--stream` serving, and perfgate
gates in its ABSOLUTE `quality` class.

Usage: python scripts/quality_matrix.py [--epochs N] [--train N] [--test N]
       [--only row[,row]] [--smoke] [--tiers] [--cascade] [--streams]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import graft_round  # noqa: E402 — one shared round default
from real_time_helmet_detection_tpu.runtime import \
    maybe_job_heartbeat  # noqa: E402
from real_time_helmet_detection_tpu.utils import (  # noqa: E402
    atomic_write_bytes, save_json)

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts",
    graft_round(), "quality_matrix.json")
DATA_ROOT = "/tmp/voc_scenes_512"
WORK_ROOT = "/tmp/qmatrix"


def log(msg: str) -> None:
    print("[qmatrix] %s" % msg, file=sys.stderr, flush=True)


def arg(name: str, default: int) -> int:
    for i, a in enumerate(sys.argv):
        if a == name and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
    return default


def run_tiers(smoke: bool, only) -> None:
    """`--tiers` (ISSUE 13): the latency-tier Pareto rows — see module
    docstring. Writes the SAME artifact path, schema quality-matrix-v2
    (legacy lever rows, when present, are preserved under "rows")."""
    if smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import chain_timed_fetch, measure_dispatch_overhead
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import roofline as _roofline
    from real_time_helmet_detection_tpu.config import (Config, TIER_PRESETS,
                                                       save_config)
    from real_time_helmet_detection_tpu.data import make_synthetic_voc
    from real_time_helmet_detection_tpu.evaluate import evaluate
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables, train

    epochs = arg("--epochs", 45)
    n_train = arg("--train", 128 if smoke else 640)
    n_test = arg("--test", 32 if smoke else 96)
    imsize = 64 if smoke else 512
    batch = 4 if smoke else 16
    # smoke scores on the EASY blocks fixture: at 64^2 the scenes style
    # (occlusion/decoys) is below the trainable floor for every budget a
    # CPU matrix can afford (probed: mAP 0.0 at 20 epochs vs 0.20 on
    # blocks at 45) — the tier ORDERING is the smoke signal, scenes
    # absolute numbers are the chip run's job
    style = "blocks" if smoke else "scenes"
    max_objects = 4 if smoke else 12
    # smoke runs scale every tier width by /4 (CPU cannot train real
    # widths in matrix time; /8 put the edge student below the trainable
    # floor — mAP pinned at ~0, making distilled-vs-scratch vacuous); the
    # VARIANT/STACK relationships — the thing the Pareto frontier orders
    # — are preserved, and each row records the width it actually ran
    wscale = 4 if smoke else 1
    archs = {
        name: {"variant": p["variant"], "num_stack": p["num_stack"],
               "width": max(8, p["hourglass_inch"] // wscale)}
        for name, p in TIER_PRESETS.items()}

    data_root = "/tmp/voc_%s_tiers_%d" % (style, imsize)
    work_root = "/tmp/qmatrix_tiers" + ("_smoke" if smoke else "")
    ds_meta = {"n_train": n_train, "n_test": n_test, "imsize": imsize,
               "style": style, "max_objects": max_objects}
    meta_path = os.path.join(data_root, "dataset_meta.json")
    have = None
    if os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                have = json.load(f)
        except (json.JSONDecodeError, OSError):
            have = None
    if have != ds_meta:
        if os.path.isdir(data_root):
            import shutil
            shutil.rmtree(data_root)
        log("generating %s dataset (%d train / %d test @%d^2)..."
            % (style, n_train, n_test, imsize))
        make_synthetic_voc(data_root, num_train=n_train, num_test=n_test,
                           imsize=(imsize, imsize),
                           max_objects=max_objects, seed=42, style=style)
        save_json(meta_path, ds_meta)

    platform = jax.default_backend()
    tier_meta = {"platform": platform, "smoke": smoke, "imsize": imsize,
                 "fixture": style,
                 "n_train": n_train, "n_test": n_test, "epochs": epochs,
                 "width_scale": wscale}
    results = {"schema": "quality-matrix-v2", "tier_meta": tier_meta,
               "tiers": {}}
    if os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH) as f:
                prior = json.load(f)
            for k in ("fixture", "imsize", "n_train", "n_test", "epochs",
                      "rows"):
                if k in prior:
                    results[k] = prior[k]  # legacy lever rows ride along
            if prior.get("tier_meta") == tier_meta:
                results["tiers"] = prior.get("tiers", {})
        except (json.JSONDecodeError, OSError):
            pass

    hb = maybe_job_heartbeat()

    def flush():
        os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
        save_json(OUT_PATH, results, indent=1)
        hb.beat("flushed %s (tiers)" % os.path.basename(OUT_PATH))

    def want(row):
        return (only is None or row in only) \
            and row not in results["tiers"]

    def tier_cfg(name, save, train_mode=True, **kw):
        a = archs[name]
        base = dict(
            train_flag=train_mode, data=data_root, save_path=save,
            variant=a["variant"], num_stack=a["num_stack"],
            hourglass_inch=a["width"],
            stem_width=min(128, a["width"]),  # tier geometry
            num_cls=2, batch_size=batch,
            amp=True, optim="adam", lr=5e-4,
            lr_milestone=[int(epochs * 0.5), int(epochs * 0.9)],
            end_epoch=epochs, device_augment=train_mode,
            cache_device=train_mode,
            multiscale_flag=False, multiscale=[imsize, imsize, 64],
            keep_ckpt=2, ckpt_interval=max(1, epochs // 2),
            hang_warn_seconds=1200, num_workers=4, print_interval=10,
            summary=False)
        base.update(kw)
        return Config(**base)

    def latest_ckpt(save):
        cks = [d for d in os.listdir(save) if d.startswith("check_point_")]
        if not cks:
            raise RuntimeError("no checkpoint under %s" % save)
        return os.path.join(save, max(
            cks, key=lambda d: int(d.rsplit("_", 1)[1])))

    def run_training(save, cfg):
        marker = os.path.join(save, "TRAIN_DONE")
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    float(f.read().strip().split("=")[1])
            except (ValueError, IndexError, OSError):
                pass
            else:
                log("training %s already complete (marker)" % save)
                return
        if os.path.isdir(save) and os.listdir(save):
            log("partial training at %s; clearing and retraining" % save)
            import shutil
            shutil.rmtree(save)
        os.makedirs(save, exist_ok=True)
        from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
        with maybe_tracer().span("train-tier", save=save) as sp:
            train(cfg)
        # the teacher checkpoint must carry its architecture snapshot so
        # --distill restores the TEACHER graph, not the student's
        save_config(cfg, save)
        atomic_write_bytes(marker, ("wall_s=%.1f\n" % sp.dur_s).encode())
        log("training %s done in %.0fs" % (save, sp.dur_s))

    overhead = measure_dispatch_overhead()

    def predict_stats(name):
        """Counting model + measured serve-wire latency of the tier's b1
        predict program AT THE REAL PRESET WIDTH (fresh-init weights:
        both are weight-independent; mAP comes from the trained
        checkpoint's eval, which smoke runs score on a width-scaled
        training twin — the row records both archs). Latency at the
        smoke-scaled widths would not order the tiers: at width 8 the
        program is op-count-bound, not conv-bound."""
        pr = TIER_PRESETS[name]
        cfg = Config(variant=pr["variant"], num_stack=pr["num_stack"],
                     hourglass_inch=pr["hourglass_inch"],
                     stem_width=pr.get("stem_width", 0), num_cls=2,
                     topk=100, conf_th=0.0, nms_th=0.5, imsize=imsize)
        model = build_model(cfg, dtype=jnp.bfloat16)
        params, batch_stats = init_variables(model, jax.random.key(0),
                                             imsize)
        variables = {"params": params, "batch_stats": batch_stats}
        predict = make_predict_fn(model, cfg, normalize="imagenet")
        images = jnp.zeros((1, imsize, imsize, 3), jnp.uint8)
        compiled = predict.lower(variables, images).compile()
        rows = _roofline.attribute(
            *_roofline.parse_hlo(compiled.as_text()))
        by_class = _roofline.class_totals(rows)
        stats = {
            "predict_bytes": round(sum(r["bytes"] for r in rows)),
            "conv_bytes": round(by_class["conv"]["bytes"]),
            "params_m": round(sum(
                x.size for x in jax.tree.leaves(params)) / 1e6, 4)}
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            stats["predict_gflops"] = round(float(cost["flops"]) / 1e9, 3)
        except Exception as e:  # noqa: BLE001 — plugin-dependent
            log("cost_analysis unavailable: %r" % e)

        # serve-wire b1 latency: donating predict chain, scalar fetch,
        # dispatch overhead subtracted (bench.py's methodology; labeled
        # with the platform above)
        n = 4 if smoke else 64
        from jax import lax

        def prog(variables, images):
            def body(imgs, _):
                det = predict(variables, imgs)
                eps = (jnp.tanh(jnp.sum(det.scores)) * 1e-12).astype(
                    imgs.dtype)
                return imgs + eps, ()
            final, _ = lax.scan(body, images, None, length=n)
            return final, jnp.sum(final[0, 0, 0].astype(jnp.float32))

        rng = np.random.default_rng(0)
        imgs = jnp.asarray(rng.integers(
            0, 256, (1, imsize, imsize, 3)).astype(np.uint8))
        chain = jax.jit(prog, donate_argnums=(1,)).lower(
            variables, imgs).compile()
        imgs, s = chain(variables, imgs)  # warmup (donates imgs)
        np.asarray(s)
        dt = chain_timed_fetch(chain, variables, imgs, overhead)
        stats["serve_wire_ms_b1"] = round(dt / n * 1e3, 3)
        return stats

    def eval_tier(name, save, **kw):
        a = archs[name]
        base = dict(
            train_flag=False, data=data_root, save_path=save,
            model_load=latest_ckpt(save), variant=a["variant"],
            num_stack=a["num_stack"], hourglass_inch=a["width"],
            stem_width=min(128, a["width"]),
            num_cls=2, batch_size=batch, imsize=imsize, topk=100,
            conf_th=0.01, nms="nms", nms_th=0.5, num_workers=4)
        base.update(kw)
        return evaluate(Config(**base))

    def record_tier(row, rec):
        results["tiers"][row] = rec
        log("tier %s: %s" % (row, rec))
        flush()

    # ---- quality tier: the flagship recipe, and the distill teacher ----
    qsave = os.path.join(work_root, "quality")
    need_teacher = any(want(r) for r in
                       ("quality", "edge", "edge_scratch", "throughput"))
    if need_teacher:
        run_training(qsave, tier_cfg("quality", qsave))
    teacher_ckpt = latest_ckpt(qsave) if need_teacher else None
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = maybe_tracer()
    if want("quality"):
        pq = TIER_PRESETS["quality"]
        with tracer.span("eval-tier", tier="quality") as sp:
            m = eval_tier("quality", qsave, nms="soft-nms")
        rec = {"arch": {"variant": pq["variant"],
                        "num_stack": pq["num_stack"],
                        "width": pq["hourglass_inch"]},
               "map_arch": dict(archs["quality"]),
               "preset": pq,
               "mAP": round(float(m["map"]), 4), "distilled": False,
               "eval_wall_s": round(sp.dur_s, 1)}
        rec.update(predict_stats("quality"))
        record_tier("quality", rec)

    # ---- edge tier: scratch vs distilled (the acceptance comparison) ---
    es_save = os.path.join(work_root, "edge_scratch")
    if want("edge_scratch"):
        run_training(es_save, tier_cfg("edge", es_save))
        m = eval_tier("edge", es_save)
        record_tier("edge_scratch", {
            "arch": dict(archs["edge"]), "mAP": round(float(m["map"]), 4),
            "distilled": False})
    if want("edge"):
        ed_save = os.path.join(work_root, "edge")
        run_training(ed_save, tier_cfg("edge", ed_save,
                                       distill=teacher_ckpt))
        with tracer.span("eval-tier", tier="edge") as sp:
            m = eval_tier("edge", ed_save)
        pe = TIER_PRESETS["edge"]
        rec = {"arch": {"variant": pe["variant"],
                        "num_stack": pe["num_stack"],
                        "width": pe["hourglass_inch"]},
               "map_arch": dict(archs["edge"]),
               "preset": pe,
               "mAP": round(float(m["map"]), 4), "distilled": True,
               "teacher": teacher_ckpt,
               "eval_wall_s": round(sp.dur_s, 1)}
        rec.update(predict_stats("edge"))
        sc = results["tiers"].get("edge_scratch")
        if sc:
            rec["distill_vs_scratch_dmap"] = round(
                rec["mAP"] - sc["mAP"], 4)
            log("edge distill vs scratch dmAP: %+.4f"
                % rec["distill_vs_scratch_dmap"])
        record_tier("edge", rec)

    # ---- throughput tier: ghost + int8 PTQ eval ------------------------
    if want("throughput"):
        th_save = os.path.join(work_root, "throughput")
        run_training(th_save, tier_cfg("throughput", th_save,
                                       distill=teacher_ckpt))
        with tracer.span("eval-tier", tier="throughput") as sp:
            m_f = eval_tier("throughput", th_save)
            m_q = eval_tier("throughput", th_save, infer_dtype="int8")
        pt = TIER_PRESETS["throughput"]
        rec = {"arch": {"variant": pt["variant"],
                        "num_stack": pt["num_stack"],
                        "width": pt["hourglass_inch"]},
               "map_arch": dict(archs["throughput"]),
               "preset": pt,
               "mAP": round(float(m_q["map"]), 4),
               "map_bf16": round(float(m_f["map"]), 4),
               "delta_map_int8_vs_bf16": round(
                   float(m_q["map"]) - float(m_f["map"]), 4),
               "infer_dtype": "int8", "distilled": True,
               "teacher": teacher_ckpt,
               "eval_wall_s": round(sp.dur_s, 1)}
        rec.update(predict_stats("throughput"))
        record_tier("throughput", rec)

    # ---- the Pareto frontier table -------------------------------------
    frontier = []
    for name in ("edge", "throughput", "quality"):
        r = results["tiers"].get(name)
        if r and "serve_wire_ms_b1" in r:
            frontier.append({
                "tier": name, "mAP": r["mAP"],
                "serve_wire_ms_b1": r["serve_wire_ms_b1"],
                "predict_gflops": r.get("predict_gflops"),
                "predict_bytes": r.get("predict_bytes"),
                "params_m": r.get("params_m")})
    if frontier:
        results["tier_pareto"] = sorted(
            frontier, key=lambda r: r["serve_wire_ms_b1"])
    flush()
    print(json.dumps({"tiers": {k: {kk: vv for kk, vv in v.items()
                                    if kk != "preset"}
                                for k, v in results["tiers"].items()},
                      "tier_pareto": results.get("tier_pareto"),
                      "out": OUT_PATH}))


def run_cascade(smoke: bool) -> None:
    """`--cascade` (ISSUE 16): escalation-threshold calibration — see
    module docstring. Shares the tier fixture AND the tier work_root
    with `--tiers` (trainings are reused through their DONE markers)."""
    if smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as np

    from real_time_helmet_detection_tpu.config import (Config, TIER_PRESETS,
                                                       save_config)
    from real_time_helmet_detection_tpu.data import (BatchLoader,
                                                     load_dataset,
                                                     make_synthetic_voc)
    from real_time_helmet_detection_tpu.data.voc import boxes_from_voc_dict
    from real_time_helmet_detection_tpu.evaluate import (_origin_size,
                                                         load_eval_state)
    from real_time_helmet_detection_tpu.metrics import compute_map
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import train

    epochs = arg("--epochs", 45)
    n_train = arg("--train", 128 if smoke else 640)
    n_test = arg("--test", 32 if smoke else 96)
    imsize = 64 if smoke else 512
    batch = 4 if smoke else 16
    style = "blocks" if smoke else "scenes"  # the tier-fixture choice:
    # smoke scores on blocks (scenes is below the CPU trainable floor —
    # run_tiers' note); the CURVE SHAPE is the smoke signal
    max_objects = 4 if smoke else 12
    wscale = 4 if smoke else 1
    archs = {
        name: {"variant": p["variant"], "num_stack": p["num_stack"],
               "width": max(8, p["hourglass_inch"] // wscale)}
        for name, p in TIER_PRESETS.items()}
    data_root = "/tmp/voc_%s_tiers_%d" % (style, imsize)
    work_root = "/tmp/qmatrix_tiers" + ("_smoke" if smoke else "")

    ds_meta = {"n_train": n_train, "n_test": n_test, "imsize": imsize,
               "style": style, "max_objects": max_objects}
    meta_path = os.path.join(data_root, "dataset_meta.json")
    have = None
    if os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                have = json.load(f)
        except (json.JSONDecodeError, OSError):
            have = None
    if have != ds_meta:
        if os.path.isdir(data_root):
            import shutil
            shutil.rmtree(data_root)
        log("generating %s dataset (%d train / %d test @%d^2)..."
            % (style, n_train, n_test, imsize))
        make_synthetic_voc(data_root, num_train=n_train, num_test=n_test,
                           imsize=(imsize, imsize),
                           max_objects=max_objects, seed=42, style=style)
        save_json(meta_path, ds_meta)

    hb = maybe_job_heartbeat()

    def tier_cfg(name, save, train_mode=True, **kw):
        a = archs[name]
        base = dict(
            train_flag=train_mode, data=data_root, save_path=save,
            variant=a["variant"], num_stack=a["num_stack"],
            hourglass_inch=a["width"], stem_width=min(128, a["width"]),
            num_cls=2, batch_size=batch,
            amp=True, optim="adam", lr=5e-4,
            lr_milestone=[int(epochs * 0.5), int(epochs * 0.9)],
            end_epoch=epochs, device_augment=train_mode,
            cache_device=train_mode,
            multiscale_flag=False, multiscale=[imsize, imsize, 64],
            keep_ckpt=2, ckpt_interval=max(1, epochs // 2),
            hang_warn_seconds=1200, num_workers=4, print_interval=10,
            summary=False)
        base.update(kw)
        return Config(**base)

    def latest_ckpt(save):
        cks = [d for d in os.listdir(save) if d.startswith("check_point_")]
        if not cks:
            raise RuntimeError("no checkpoint under %s" % save)
        return os.path.join(save, max(
            cks, key=lambda d: int(d.rsplit("_", 1)[1])))

    def run_training(save, cfg):
        marker = os.path.join(save, "TRAIN_DONE")
        if os.path.exists(marker):
            log("training %s already complete (marker)" % save)
            return
        if os.path.isdir(save) and os.listdir(save):
            log("partial training at %s; clearing and retraining" % save)
            import shutil
            shutil.rmtree(save)
        os.makedirs(save, exist_ok=True)
        from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
        with maybe_tracer().span("train-cascade-tier", save=save) as sp:
            train(cfg)
        save_config(cfg, save)
        atomic_write_bytes(marker, ("wall_s=%.1f\n" % sp.dur_s).encode())
        log("training %s done in %.0fs" % (save, sp.dur_s))
        hb.beat("trained %s" % os.path.basename(save))

    # the two cascade endpoints: quality (flagship recipe) and edge
    # (scratch — the serving edge tier; distillation is --tiers' story)
    qsave = os.path.join(work_root, "quality")
    esave = os.path.join(work_root, "edge_scratch")
    run_training(qsave, tier_cfg("quality", qsave))
    run_training(esave, tier_cfg("edge", esave))

    def eval_state(name, save):
        a = archs[name]
        cfg = Config(train_flag=False, data=data_root, save_path=save,
                     model_load=latest_ckpt(save), variant=a["variant"],
                     num_stack=a["num_stack"], hourglass_inch=a["width"],
                     stem_width=min(128, a["width"]), num_cls=2,
                     batch_size=batch, imsize=imsize, topk=100,
                     conf_th=0.01, nms="nms", nms_th=0.5, num_workers=2)
        model, variables = load_eval_state(cfg)
        return cfg, model, variables

    ecfg, emodel, evars = eval_state("edge", esave)
    qcfg, qmodel, qvars = eval_state("quality", qsave)
    edge_predict = make_predict_fn(emodel, ecfg, normalize=ecfg.pretrained,
                                   cascade_summary=True)
    quality_predict = make_predict_fn(qmodel, qcfg,
                                      normalize=qcfg.pretrained)

    # one pass over the held-out split per tier: dispatch every b1
    # predict, ONE batched fetch (fetch discipline; masks on the host)
    dataset, augmentor = load_dataset(ecfg)
    loader = BatchLoader(dataset, augmentor, batch_size=batch,
                         pretrained=ecfg.pretrained, num_cls=2,
                         normalized_coord=ecfg.normalized_coord,
                         scale_factor=ecfg.scale_factor,
                         max_boxes=ecfg.max_boxes, shuffle=False,
                         drop_last=False, num_workers=2, raw=True)
    images, infos = [], []
    for b in loader:
        for j in range(len(b.infos)):
            images.append(np.asarray(b.image[j]))
            infos.append(b.infos[j])
    if hasattr(loader, "close"):
        loader.close()
    log("scoring %d held-out images per tier" % len(images))

    def collect(predict, variables):
        pend = [predict(variables, img[None]) for img in images]
        return [type(d)(*(np.asarray(leaf[0]) for leaf in d))
                for d in jax.device_get(pend)]

    edge_rows = collect(edge_predict, evars)
    hb.beat("edge tier scored")
    quality_rows = collect(quality_predict, qvars)
    hb.beat("quality tier scored")

    gt_boxes, gt_labels, dets = {}, {}, {}
    scale = float(imsize)
    for k, (info, er, qr) in enumerate(zip(infos, edge_rows,
                                           quality_rows)):
        image_id = os.path.splitext(
            info["annotation"].get("filename") or "%06d" % k)[0]
        ow, oh = _origin_size(info)
        gb, gl = boxes_from_voc_dict(info)
        gt_boxes[image_id], gt_labels[image_id] = gb, gl
        resc = np.array([ow / scale, oh / scale, ow / scale, oh / scale],
                        np.float32)

        def host_row(row):
            keep = row.valid
            return {"box": row.boxes[keep] * resc,
                    "cls": row.classes[keep], "score": row.scores[keep]}

        dets[image_id] = {"edge": host_row(er), "quality": host_row(qr),
                          "confidence": float(er.confidence)}

    def map_of(pick):
        """mAP of a per-image tier choice (image_id -> 'edge'|'quality')."""
        m = compute_map(
            gt_boxes, gt_labels,
            {k: dets[k][pick(k)]["box"] for k in dets},
            {k: dets[k][pick(k)]["cls"] for k in dets},
            {k: dets[k][pick(k)]["score"] for k in dets}, num_cls=2)
        return round(float(m["map"]), 4)

    map_edge = map_of(lambda k: "edge")
    map_quality = map_of(lambda k: "quality")
    confs = {k: dets[k]["confidence"] for k in dets}
    log("all-edge mAP %.4f, all-quality mAP %.4f, confidence range "
        "[%.3f, %.3f]" % (map_edge, map_quality, min(confs.values()),
                          max(confs.values())))

    # the sweep: one candidate threshold per distinct confidence (the
    # curve's only knees) plus "escalate everything"; large splits thin
    # to ~33 quantile points so the chip-scale sweep stays bounded
    cand = sorted(set(confs.values()))
    cand.append(max(cand) + 1.0)
    if len(cand) > 33:
        idx = np.linspace(0, len(cand) - 1, 33).round().astype(int)
        cand = [cand[i] for i in sorted(set(idx.tolist()))]
    sweep = []
    for t in cand:
        esc = {k for k, c in confs.items() if c < t}
        row = {"threshold": round(float(t), 6),
               "escalation_rate": round(len(esc) / len(confs), 4),
               "blended_mAP": map_of(
                   lambda k: "quality" if k in esc else "edge")}
        row["delta_vs_all_quality"] = round(
            row["blended_mAP"] - map_quality, 4)
        sweep.append(row)
        log("t=%.4f: escalation %.0f%%, blended mAP %.4f (%+.4f vs "
            "all-quality)" % (t, 100 * row["escalation_rate"],
                              row["blended_mAP"],
                              row["delta_vs_all_quality"]))
    hb.beat("threshold sweep done")

    # operating point: SMALLEST escalation rate within 2 pts of
    # all-quality routing (always satisfiable: rate 1.0 IS all-quality)
    ok_rows = [r for r in sweep if r["delta_vs_all_quality"] >= -0.02]
    selected = dict(min(ok_rows, key=lambda r: r["escalation_rate"]))
    selected["rule"] = ("min escalation rate with blended mAP >= "
                        "all-quality - 0.02")

    out_path = os.path.join(os.path.dirname(OUT_PATH), "cascade.json")
    out = {"schema": "cascade-calibration-v1",
           "platform": jax.default_backend(), "smoke": smoke,
           "fixture": {"style": style, "imsize": imsize,
                       "n_train": n_train, "n_test": n_test,
                       "epochs": epochs, "width_scale": wscale},
           "tiers": {"edge": dict(archs["edge"]),
                     "quality": dict(archs["quality"])},
           "all_edge_mAP": map_edge, "all_quality_mAP": map_quality,
           "confidence": {
               "min": round(min(confs.values()), 4),
               "median": round(float(np.median(list(confs.values()))), 4),
               "max": round(max(confs.values()), 4)},
           "sweep": sweep, "selected": selected}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    save_json(out_path, out, indent=1)
    log("selected threshold %.4f (escalation %.0f%%, blended mAP %.4f) "
        "-> %s" % (selected["threshold"],
                   100 * selected["escalation_rate"],
                   selected["blended_mAP"], out_path))
    print(json.dumps({"tool": "quality_matrix", "cascade": True,
                      "all_edge_mAP": map_edge,
                      "all_quality_mAP": map_quality,
                      "selected": selected, "sweep_points": len(sweep),
                      "out": out_path}))


def run_streams(smoke: bool) -> None:
    """`--streams` (ISSUE 17): tile-skip-threshold calibration — see
    module docstring. Shares the tier fixture AND the quality tier's
    training with `--tiers`/`--cascade` (reused via its DONE marker);
    the video fixture is synthesized from the held-out split."""
    if smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import numpy as np

    from real_time_helmet_detection_tpu.config import (Config,
                                                       TIER_PRESETS,
                                                       save_config)
    from real_time_helmet_detection_tpu.data import (BatchLoader,
                                                     load_dataset,
                                                     make_synthetic_voc)
    from real_time_helmet_detection_tpu.data.voc import boxes_from_voc_dict
    from real_time_helmet_detection_tpu.evaluate import (_origin_size,
                                                         load_eval_state)
    from real_time_helmet_detection_tpu.metrics import compute_map
    from real_time_helmet_detection_tpu.ops.delta import (make_delta_fn,
                                                          tile_origins)
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import train

    epochs = arg("--epochs", 45)
    n_train = arg("--train", 128 if smoke else 640)
    n_test = arg("--test", 32 if smoke else 96)
    imsize = 64 if smoke else 512
    batch = 4 if smoke else 16
    style = "blocks" if smoke else "scenes"  # run_cascade's fixture note
    max_objects = 4 if smoke else 12
    wscale = 4 if smoke else 1
    # the video fixture: grid x grid tiles drawn from the held-out pool,
    # per-tile replacement with prob (1 - redundancy) per frame, plus a
    # +/-`noise` uint8 sensor jitter so STATIC tiles still carry a
    # nonzero delta floor — gating has a real operating curve, not a
    # trivial ==0 split
    grid = 2
    T = arg("--frames", 8 if smoke else 16)
    n_seq = arg("--seqs", 8 if smoke else 16)
    redundancy = 0.75
    noise = 2
    archs = {
        name: {"variant": p["variant"], "num_stack": p["num_stack"],
               "width": max(8, p["hourglass_inch"] // wscale)}
        for name, p in TIER_PRESETS.items()}
    data_root = "/tmp/voc_%s_tiers_%d" % (style, imsize)
    work_root = "/tmp/qmatrix_tiers" + ("_smoke" if smoke else "")

    ds_meta = {"n_train": n_train, "n_test": n_test, "imsize": imsize,
               "style": style, "max_objects": max_objects}
    meta_path = os.path.join(data_root, "dataset_meta.json")
    have = None
    if os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                have = json.load(f)
        except (json.JSONDecodeError, OSError):
            have = None
    if have != ds_meta:
        if os.path.isdir(data_root):
            import shutil
            shutil.rmtree(data_root)
        log("generating %s dataset (%d train / %d test @%d^2)..."
            % (style, n_train, n_test, imsize))
        make_synthetic_voc(data_root, num_train=n_train, num_test=n_test,
                           imsize=(imsize, imsize),
                           max_objects=max_objects, seed=42, style=style)
        save_json(meta_path, ds_meta)

    hb = maybe_job_heartbeat()

    # quality tier only — the stream serves whatever tier the tenant
    # routes to, but the CALIBRATION scores the flagship recipe (the
    # skip threshold is about frame dynamics, not model capacity)
    a = archs["quality"]
    qsave = os.path.join(work_root, "quality")
    marker = os.path.join(qsave, "TRAIN_DONE")
    if os.path.exists(marker):
        log("training %s already complete (marker)" % qsave)
    else:
        if os.path.isdir(qsave) and os.listdir(qsave):
            log("partial training at %s; clearing and retraining" % qsave)
            import shutil
            shutil.rmtree(qsave)
        os.makedirs(qsave, exist_ok=True)
        cfg = Config(
            train_flag=True, data=data_root, save_path=qsave,
            variant=a["variant"], num_stack=a["num_stack"],
            hourglass_inch=a["width"], stem_width=min(128, a["width"]),
            num_cls=2, batch_size=batch,
            amp=True, optim="adam", lr=5e-4,
            lr_milestone=[int(epochs * 0.5), int(epochs * 0.9)],
            end_epoch=epochs, device_augment=True, cache_device=True,
            multiscale_flag=False, multiscale=[imsize, imsize, 64],
            keep_ckpt=2, ckpt_interval=max(1, epochs // 2),
            hang_warn_seconds=1200, num_workers=4, print_interval=10,
            summary=False)
        from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
        with maybe_tracer().span("train-streams-tier", save=qsave) as sp:
            train(cfg)
        save_config(cfg, qsave)
        atomic_write_bytes(marker, ("wall_s=%.1f\n" % sp.dur_s).encode())
        log("training %s done in %.0fs" % (qsave, sp.dur_s))
        hb.beat("trained quality tier")

    cks = [d for d in os.listdir(qsave) if d.startswith("check_point_")]
    if not cks:
        raise RuntimeError("no checkpoint under %s" % qsave)
    ckpt = os.path.join(qsave, max(
        cks, key=lambda d: int(d.rsplit("_", 1)[1])))
    qcfg = Config(train_flag=False, data=data_root, save_path=qsave,
                  model_load=ckpt, variant=a["variant"],
                  num_stack=a["num_stack"], hourglass_inch=a["width"],
                  stem_width=min(128, a["width"]), num_cls=2,
                  batch_size=batch, imsize=imsize, topk=100,
                  conf_th=0.01, nms="nms", nms_th=0.5, num_workers=2)
    qmodel, qvars = load_eval_state(qcfg)
    predict = make_predict_fn(qmodel, qcfg, normalize=qcfg.pretrained)

    # the held-out split is the tile pool
    dataset, augmentor = load_dataset(qcfg)
    loader = BatchLoader(dataset, augmentor, batch_size=batch,
                         pretrained=qcfg.pretrained, num_cls=2,
                         normalized_coord=qcfg.normalized_coord,
                         scale_factor=qcfg.scale_factor,
                         max_boxes=qcfg.max_boxes, shuffle=False,
                         drop_last=False, num_workers=2, raw=True)
    images, infos = [], []
    for b in loader:
        for j in range(len(b.infos)):
            images.append(np.asarray(b.image[j]))
            infos.append(b.infos[j])
    if hasattr(loader, "close"):
        loader.close()
    n_pool = len(images)
    tiles_per = grid * grid
    log("synthesizing %d streams x %d frames from %d held-out tiles"
        % (n_seq, T, n_pool))

    # seeded sequence content: seqs[s][f][k] = pool index of tile k
    rng = np.random.default_rng(1717)
    seq_idx = []
    for s in range(n_seq):
        cur = [int(i) for i in rng.integers(0, n_pool, size=tiles_per)]
        fr = [list(cur)]
        for f in range(1, T):
            cur = [int(rng.integers(0, n_pool))
                   if rng.random() >= redundancy else i for i in cur]
            fr.append(list(cur))
        seq_idx.append(fr)
    # per-(s,f,k) noisy tile (the noise draw is part of the fixture —
    # identical across candidate thresholds)
    noisy = {}
    for s in range(n_seq):
        for f in range(T):
            for k in range(tiles_per):
                img = images[seq_idx[s][f][k]].astype(np.int16)
                jit = rng.integers(-noise, noise + 1, size=img.shape)
                noisy[(s, f, k)] = np.clip(
                    img + jit, 0, 255).astype(np.uint8)

    # dispatch EVERY noisy-tile b1 predict, ONE batched fetch (the
    # fetch discipline run_cascade's collect() uses)
    keys = sorted(noisy)
    pend = [predict(qvars, noisy[k][None]) for k in keys]
    preds = {k: type(d)(*(np.asarray(leaf[0]) for leaf in d))
             for k, d in zip(keys, jax.device_get(pend))}
    hb.beat("tile predictions scored")

    # every consecutive-frame delta summary — the EXACT in-jit program
    # the stream session runs (ops/delta.py), dispatched-all fetched-once
    fshape = (grid * imsize, grid * imsize, 3)
    origins = tile_origins(fshape, grid)
    delta_fn = make_delta_fn(grid)

    def assemble(s, f):
        ts = [noisy[(s, f, k)] for k in range(tiles_per)]
        rows = [np.concatenate(ts[r * grid:(r + 1) * grid], axis=1)
                for r in range(grid)]
        return np.concatenate(rows, axis=0)

    frames = {(s, f): assemble(s, f)
              for s in range(n_seq) for f in range(T)}
    dkeys = [(s, f) for s in range(n_seq) for f in range(1, T)]
    dpend = [delta_fn(frames[(s, f - 1)], frames[(s, f)])
             for s, f in dkeys]
    deltas = {k: np.asarray(v)
              for k, v in zip(dkeys, jax.device_get(dpend))}
    hb.beat("delta summaries scored")

    # frame-level ground truth in MODEL coordinates: each tile's VOC
    # boxes scaled to the model canvas, offset to its tile origin
    gt_boxes, gt_labels = {}, {}
    tile_gt = {}
    for idx in {i for fr in seq_idx for tl in fr for i in tl}:
        ow, oh = _origin_size(infos[idx])
        gb, gl = boxes_from_voc_dict(infos[idx])
        sc = np.array([imsize / ow, imsize / oh,
                       imsize / ow, imsize / oh], np.float32)
        tile_gt[idx] = (gb * sc, gl)
    for s in range(n_seq):
        for f in range(T):
            fid = "s%02d_f%02d" % (s, f)
            bs, ls = [], []
            for k in range(tiles_per):
                y0, x0 = origins[k]
                gb, gl = tile_gt[seq_idx[s][f][k]]
                bs.append(gb + np.array([x0, y0, x0, y0], np.float32))
                ls.append(gl)
            gt_boxes[fid] = (np.concatenate(bs) if bs
                             else np.zeros((0, 4), np.float32))
            gt_labels[fid] = (np.concatenate(ls) if ls
                              else np.zeros((0,), np.int64))

    def blended(t):
        """Offline replay of the session cache at threshold `t`:
        (blended video mAP, tile_skip_rate). A tile computes iff first
        frame or its delta >= t (streams.py's `changed` rule); a
        skipped tile answers with its LAST COMPUTED detections."""
        computed, total = 0, 0
        db, dc, dsc = {}, {}, {}
        for s in range(n_seq):
            cache = [None] * tiles_per
            for f in range(T):
                fid = "s%02d_f%02d" % (s, f)
                bs, cs, ss = [], [], []
                for k in range(tiles_per):
                    total += 1
                    if (f == 0 or cache[k] is None
                            or float(deltas[(s, f)][k]) >= t):
                        cache[k] = preds[(s, f, k)]
                        computed += 1
                    row = cache[k]
                    keep = row.valid
                    y0, x0 = origins[k]
                    bs.append(row.boxes[keep]
                              + np.array([x0, y0, x0, y0], np.float32))
                    cs.append(row.classes[keep])
                    ss.append(row.scores[keep])
                db[fid] = (np.concatenate(bs) if bs
                           else np.zeros((0, 4), np.float32))
                dc[fid] = np.concatenate(cs)
                dsc[fid] = np.concatenate(ss)
        m = compute_map(gt_boxes, gt_labels, db, dc, dsc, num_cls=2)
        return (round(float(m["map"]), 4),
                round(1.0 - computed / total, 4))

    full_map, _ = blended(0.0)  # t=0: every tile computes (delta >= 0)
    dvals = np.concatenate([deltas[k] for k in dkeys])
    log("full-inference video mAP %.4f, delta range [%.2f, %.2f]"
        % (full_map, float(dvals.min()), float(dvals.max())))

    # the sweep: one candidate per distinct observed delta (the curve's
    # only knees) plus 0.0 (= full inference), thinned to ~33 quantile
    # points exactly like run_cascade's confidence sweep
    cand = sorted(set([0.0] + [round(float(v), 4) for v in dvals]))
    if len(cand) > 33:
        idx = np.linspace(0, len(cand) - 1, 33).round().astype(int)
        cand = [cand[i] for i in sorted(set(idx.tolist()))]
    sweep = []
    for t in cand:
        m, skip = blended(t)
        row = {"threshold": round(float(t), 6), "tile_skip_rate": skip,
               "blended_video_mAP": m,
               "delta_vs_full": round(m - full_map, 4)}
        sweep.append(row)
        log("t=%.4f: skip %.0f%%, blended video mAP %.4f (%+.4f vs "
            "full)" % (t, 100 * skip, m, row["delta_vs_full"]))
    hb.beat("threshold sweep done")

    # operating point: LARGEST tile-skip rate within 2 pts of full
    # inference (always satisfiable: t=0 IS full inference)
    ok_rows = [r for r in sweep if r["delta_vs_full"] >= -0.02]
    selected = dict(max(ok_rows, key=lambda r: r["tile_skip_rate"]))
    selected["rule"] = ("max tile_skip_rate with blended video mAP >= "
                        "full - 0.02")

    out_path = os.path.join(os.path.dirname(OUT_PATH), "streams.json")
    out = {"schema": "stream-calibration-v1",
           "platform": jax.default_backend(), "smoke": smoke,
           "fixture": {"style": style, "imsize": imsize,
                       "n_train": n_train, "n_test": n_test,
                       "epochs": epochs, "width_scale": wscale,
                       "tile_grid": grid, "frames": T,
                       "sequences": n_seq, "redundancy": redundancy,
                       "noise": noise},
           "arch": dict(a),
           "full_video_mAP": full_map,
           "delta": {"min": round(float(dvals.min()), 4),
                     "median": round(float(np.median(dvals)), 4),
                     "max": round(float(dvals.max()), 4)},
           "sweep": sweep, "selected": selected}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    save_json(out_path, out, indent=1)
    log("selected threshold %.4f (skip %.0f%%, blended video mAP %.4f) "
        "-> %s" % (selected["threshold"],
                   100 * selected["tile_skip_rate"],
                   selected["blended_video_mAP"], out_path))
    print(json.dumps({"tool": "quality_matrix", "streams": True,
                      "full_video_mAP": full_map,
                      "selected": selected, "sweep_points": len(sweep),
                      "out": out_path}))


def main() -> None:
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    use_compile_cache()
    only = None
    for i, a in enumerate(sys.argv):
        if a == "--only" and i + 1 < len(sys.argv):
            only = set(sys.argv[i + 1].split(","))

    if "--streams" in sys.argv:
        run_streams("--smoke" in sys.argv)
        return

    if "--cascade" in sys.argv:
        run_cascade("--smoke" in sys.argv)
        return

    if "--tiers" in sys.argv:
        run_tiers("--smoke" in sys.argv, only)
        return

    smoke = "--smoke" in sys.argv  # CPU pipe-clean: tiny model/shapes,
    # same code path — verifies the matrix plumbing without a chip
    epochs = arg("--epochs", 2 if smoke else 45)
    n_train = arg("--train", 8 if smoke else 640)
    n_test = arg("--test", 4 if smoke else 96)
    imsize = 64 if smoke else 512
    inch = 16 if smoke else 128
    batch = 4 if smoke else 16

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.data import make_synthetic_voc
    from real_time_helmet_detection_tpu.evaluate import evaluate
    from real_time_helmet_detection_tpu.train import train

    global DATA_ROOT, OUT_PATH, WORK_ROOT
    if smoke:
        DATA_ROOT = "/tmp/voc_scenes_smoke"
        WORK_ROOT = "/tmp/qmatrix_smoke"
        OUT_PATH = "/tmp/qmatrix_smoke/quality_matrix.json"
        import jax
        jax.config.update("jax_platforms", "cpu")
    # dataset reuse is gated on the GENERATION PARAMETERS, not bare dir
    # existence: a stale smaller pipe-clean dataset must be regenerated,
    # not silently trained on while the artifact records the larger sizes
    # (review finding)
    ds_meta = {"n_train": n_train, "n_test": n_test, "imsize": imsize}
    meta_path = os.path.join(DATA_ROOT, "dataset_meta.json")
    have = None
    if os.path.exists(meta_path):
        try:
            with open(meta_path) as f:
                have = json.load(f)
        except (json.JSONDecodeError, OSError):
            have = None
    if have != ds_meta:
        if os.path.isdir(DATA_ROOT):
            import shutil
            shutil.rmtree(DATA_ROOT)
        log("generating scenes dataset (%d train / %d test @%d^2)..."
            % (n_train, n_test, imsize))
        make_synthetic_voc(DATA_ROOT, num_train=n_train, num_test=n_test,
                           imsize=(imsize, imsize), max_objects=12, seed=42,
                           style="scenes")
        save_json(meta_path, ds_meta)

    results = {"fixture": "scenes", "imsize": imsize, "n_train": n_train,
               "n_test": n_test, "epochs": epochs, "rows": {}}
    if os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH) as f:
                prior = json.load(f)
            if (prior.get("n_train"), prior.get("epochs")) == (n_train,
                                                               epochs):
                results["rows"] = prior.get("rows", {})
        except (json.JSONDecodeError, OSError):
            pass

    hb = maybe_job_heartbeat()

    def flush():
        # atomic per-row flush doubles as the job heartbeat (runtime/)
        os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
        save_json(OUT_PATH, results, indent=1)
        hb.beat("flushed %s" % os.path.basename(OUT_PATH))

    def want(row):
        return (only is None or row in only) and row not in results["rows"]

    # shared training knobs: the reference README's training example
    # (batch 16, Adam 5e-4, milestones at 50%/90% of the run) on the
    # fast HBM-cached input path measured in r2
    def train_cfg(save, **kw):
        base = dict(
            train_flag=True, data=DATA_ROOT, save_path=save,
            num_stack=1, hourglass_inch=inch, num_cls=2, batch_size=batch,
            amp=True, optim="adam", lr=5e-4,
            lr_milestone=[int(epochs * 0.5), int(epochs * 0.9)],
            end_epoch=epochs, device_augment=True, cache_device=True,
            multiscale_flag=False, multiscale=[imsize, imsize, 64],
            ema_decay=0.998, keep_ckpt=2, ckpt_interval=5,
            auto_resume=2,  # ride out backend blips inside a training row
            hang_warn_seconds=1200, num_workers=8, print_interval=10)
        base.update(kw)
        return Config(**base)

    def eval_cfg(save, ckpt, **kw):
        base = dict(
            train_flag=False, data=DATA_ROOT, save_path=save,
            model_load=ckpt, num_stack=1, hourglass_inch=inch, num_cls=2,
            batch_size=batch, imsize=imsize, topk=100, conf_th=0.01,
            nms="nms", nms_th=0.5, num_workers=8)
        base.update(kw)
        return Config(**base)

    def latest_ckpt(save):
        cks = [d for d in os.listdir(save) if d.startswith("check_point_")]
        if not cks:
            raise RuntimeError("no checkpoint under %s" % save)
        return os.path.join(save, max(
            cks, key=lambda d: int(d.rsplit("_", 1)[1])))

    def run_training(save, cfg):
        """Train into `save` unless its DONE marker exists; returns the
        training wall seconds (from the marker if already complete). Dir
        existence is not evidence of completion — a wedged run leaves a
        partial checkpoint that would silently skew every row scored from
        it (review finding); only a training that RETURNED writes the
        marker. A partial dir is cleared and retrained from scratch."""
        marker = os.path.join(save, "TRAIN_DONE")
        if os.path.exists(marker):
            try:
                with open(marker) as f:
                    wall = float(f.read().strip().split("=")[1])
            except (ValueError, IndexError, OSError) as e:
                # empty/truncated marker (crash between create and write):
                # NOT evidence of completion — fall through to the
                # clear-and-retrain path below (ADVICE r5 #1; previously
                # this raised and killed the whole matrix stage)
                log("unparseable TRAIN_DONE marker at %s (%r); treating as "
                    "a partial run" % (marker, e))
            else:
                log("training %s already complete (marker)" % save)
                return wall
        if os.path.isdir(save) and os.listdir(save):
            log("partial training at %s; clearing and retraining" % save)
            import shutil
            shutil.rmtree(save)
        os.makedirs(save, exist_ok=True)
        # flight-recorder span: the duration feeds the DONE marker, and
        # when $OBS_SPAN_LOG is exported (tpu_queue does) the round report
        # sees each row's training phase
        from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
        with maybe_tracer().span("train-row", save=save) as sp:
            train(cfg)
        wall = sp.dur_s
        # atomic: a truncated marker would read as "training complete"
        atomic_write_bytes(marker, ("wall_s=%.1f\n" % wall).encode())
        log("training %s done in %.0fs" % (save, wall))
        return wall

    def record(row, mapping, t0, save, extra=None):
        # compute_map returns {"ap": {class_index: ap}, "map": float}
        rec = {"mAP": round(float(mapping["map"]), 4),
               "ap_hat": round(float(mapping["ap"].get(0, float("nan"))), 4),
               "ap_person": round(float(
                   mapping["ap"].get(1, float("nan"))), 4),
               "wall_s": round(time.time() - t0, 1), "save": save}
        if extra:
            rec.update(extra)
        results["rows"][row] = rec
        log("row %s: %s" % (row, rec))
        flush()

    # ---- base training (also yields EMA weights + soft-NMS eval rows) ---
    base_save = os.path.join(WORK_ROOT, "base")
    if want("base") or want("base+soft") or want("base+ema") \
            or want("base+pool5") or want("base+int8"):
        run_training(base_save, train_cfg(base_save))
    if want("base"):
        t0 = time.time()
        m = evaluate(eval_cfg(base_save, latest_ckpt(base_save)))
        record("base", m, t0, base_save)
    if want("base+soft"):
        t0 = time.time()
        m = evaluate(eval_cfg(base_save, latest_ckpt(base_save),
                              nms="soft-nms"))
        record("base+soft", m, t0, base_save)
    if want("base+ema"):
        t0 = time.time()
        m = evaluate(eval_cfg(base_save, latest_ckpt(base_save),
                              ema_eval=True, ema_decay=0.998))
        record("base+ema", m, t0, base_save)
    if want("base+pool5"):
        # the newly-threaded --pool-size lever: a wider peak window on the
        # same weights (eval only)
        t0 = time.time()
        m = evaluate(eval_cfg(base_save, latest_ckpt(base_save),
                              pool_size=5))
        record("base+pool5", m, t0, base_save)
    if want("base+int8"):
        # the int8-vs-bf16 column (ISSUE 5): the SAME base checkpoint
        # through the BN-folded post-training-quantized predict
        # (--infer-dtype int8; scales self-calibrated from the first
        # --calib-batches eval batches and persisted under the run's
        # calibration/). The parity gate is delta_map_vs_bf16 against the
        # float row — quantization must buy speed, not quality.
        t0 = time.time()
        m = evaluate(eval_cfg(base_save, latest_ckpt(base_save),
                              infer_dtype="int8"))
        extra = {"infer_dtype": "int8"}
        if "base" in results["rows"]:
            extra["delta_map_vs_bf16"] = round(
                float(m["map"]) - results["rows"]["base"]["mAP"], 4)
            log("int8 vs bf16 dmAP: %+.4f" % extra["delta_map_vs_bf16"])
        record("base+int8", m, t0, base_save, extra=extra)

    # ---- num_stack=2 ----------------------------------------------------
    if want("stack2"):
        save = os.path.join(WORK_ROOT, "stack2")
        t0 = time.time()
        run_training(save, train_cfg(save, num_stack=2))
        m = evaluate(eval_cfg(save, latest_ckpt(save), num_stack=2))
        record("stack2", m, t0, save)

    # ---- bucketed multiscale training -----------------------------------
    ms_save = os.path.join(WORK_ROOT, "multiscale")
    ms_kw = dict(multiscale_flag=True, prewarm=True,
                 multiscale=([64, 128, 64] if smoke else [384, 576, 64]))
    ms_train_wall = None
    if want("multiscale") or want("multiscale+soft"):
        ms_train_wall = run_training(ms_save, train_cfg(ms_save, **ms_kw))
    if want("multiscale"):
        # wall_s on shared-training rows is EVAL-only; the training cost
        # is recorded once as train_wall_s (review finding: silently
        # changing wall_s's meaning vs prior rounds' train+eval rows)
        t0 = time.time()
        m = evaluate(eval_cfg(ms_save, latest_ckpt(ms_save)))
        record("multiscale", m, t0, ms_save,
               extra={"train_wall_s": ms_train_wall})
    if want("multiscale+soft"):
        # the r4 CPU matrix's best two-lever composition (+5.8 at 256^2:
        # multiscale 0.5611 -> +soft-NMS 0.5881, artifacts/r04/README.md)
        # confirmed at flagship scale for free — eval-only on the same
        # multiscale weights (VERDICT r4 next #9)
        t0 = time.time()
        m = evaluate(eval_cfg(ms_save, latest_ckpt(ms_save),
                              nms="soft-nms"))
        record("multiscale+soft", m, t0, ms_save)

    # ---- best composed recipe: stack2 + multiscale (+ soft-NMS eval) ----
    # stack2 is the biggest single lever (+21.3 at 256^2) and multiscale/
    # soft-NMS compose on top of each other; whether they compose with
    # stack2 has never been measured at any scale. One extra training
    # yields both composed rows (soft-NMS is eval-only).
    s2m_save = os.path.join(WORK_ROOT, "stack2_multiscale")
    s2m_train_wall = None
    if want("stack2+multiscale") or want("stack2+multiscale+soft"):
        s2m_train_wall = run_training(
            s2m_save, train_cfg(s2m_save, num_stack=2, **ms_kw))
    if want("stack2+multiscale"):
        t0 = time.time()
        m = evaluate(eval_cfg(s2m_save, latest_ckpt(s2m_save), num_stack=2))
        record("stack2+multiscale", m, t0, s2m_save,
               extra={"train_wall_s": s2m_train_wall})
    if want("stack2+multiscale+soft"):
        t0 = time.time()
        m = evaluate(eval_cfg(s2m_save, latest_ckpt(s2m_save), num_stack=2,
                              nms="soft-nms"))
        record("stack2+multiscale+soft", m, t0, s2m_save)

    flush()
    print(json.dumps(results))


if __name__ == "__main__":
    main()
