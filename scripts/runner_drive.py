"""Drive the C++ PJRT runner against a real PJRT plug-in ($PJRT_PLUGIN).

The reference's deployment story is a C++ libtorch app running the traced
model at 100 FPS @512^2 (ref README.md:76, .gitmodules:4-6). Ours is
cpp/pjrt_runner consuming a `jax.export` StableHLO artifact through the
PJRT C API. This script is the hardware proof of that runner:

  1. exports the TRAINED flagship checkpoint (quality_matrix base row, if
     present — fresh-init otherwise, flagged) with --export-raw-input
     (uint8 wire: a quarter of the f32 H2D bytes),
  2. renders one 512^2 scenes image to raw NHWC uint8 bytes,
  3. runs the runner at --depth 1, 4 and 8 (software pipelining: fetch of
     frame i overlaps execute of i+1..) against the plug-in named by
     $PJRT_PLUGIN — there is no default path: without it the drive fails,
  4. checks detections parity against the SAME exported artifact
     deserialized and executed on CPU (same program, TPU-vs-CPU numerics),
  5. writes artifacts/<round>/runner_fps.json incrementally.

This process keeps its own JAX strictly on CPU: the C++ runner must be the
only process on the chip (one process per chip).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import graft_round  # noqa: E402 — one shared round default
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    maybe_job_heartbeat, run_as_job)
from real_time_helmet_detection_tpu.utils import save_json  # noqa: E402

HB = maybe_job_heartbeat()

ROUND = graft_round()
OUT_PATH = os.path.join(REPO, "artifacts", ROUND, "runner_fps.json")
PLUGIN = os.environ.get("PJRT_PLUGIN")
RUNNER = os.path.join(REPO, "build", "pjrt_runner", "pjrt_runner")
QMATRIX_BASE = "/tmp/qmatrix/base"
WORK = "/tmp/runner_drive"
IMSIZE = 512


def log(msg: str) -> None:
    print("[runner_drive] %s" % msg, file=sys.stderr, flush=True)


def flush(results: dict) -> None:
    # atomic incremental flush doubles as the job heartbeat (runtime/)
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    save_json(OUT_PATH, results, indent=1)
    HB.beat("flushed %s" % os.path.basename(OUT_PATH))


def find_trained_ckpt() -> str | None:
    """Latest quality_matrix base checkpoint, only if its training RAN TO
    COMPLETION (TRAIN_DONE marker — a wedged run leaves a partial dir).
    The pick itself validates orbax finalization (train.py
    find_latest_checkpoint): a kill mid-save must not hand the export a
    truncated checkpoint."""
    if not os.path.exists(os.path.join(QMATRIX_BASE, "TRAIN_DONE")):
        return None
    from real_time_helmet_detection_tpu.train import find_latest_checkpoint
    return find_latest_checkpoint(QMATRIX_BASE)


def render_image(path: str) -> "tuple":
    """One 512^2 scenes test image as raw NHWC uint8 bytes + the array."""
    import numpy as np
    from PIL import Image

    from real_time_helmet_detection_tpu.data import make_synthetic_voc

    root = os.path.join(WORK, "scene_img")
    marker = os.path.join(root, "done")
    if not os.path.exists(marker):
        make_synthetic_voc(root, num_train=1, num_test=1,
                           imsize=(IMSIZE, IMSIZE), max_objects=8, seed=7,
                           style="scenes")
        from real_time_helmet_detection_tpu.utils import atomic_write_bytes
        atomic_write_bytes(marker, b"ok")  # atomic completion marker
    jpg_dir = os.path.join(root, "JPEGImages")
    jpg = os.path.join(jpg_dir, sorted(os.listdir(jpg_dir))[-1])
    arr = np.asarray(Image.open(jpg).convert("RGB"), dtype=np.uint8)
    arr = arr[None]  # NHWC batch 1
    arr.tofile(path)
    return arr


def parse_runner(stdout: str) -> dict:
    rec: dict = {}
    m = re.search(r"compiled StableHLO \(([\d.]+) KB\) in ([\d.]+)s", stdout)
    if m:
        rec["artifact_kb"] = float(m.group(1))
        rec["compile_s"] = float(m.group(2))
    m = re.search(
        r"timing: (\d+) iters, batch (\d+).*?: ([\d.]+) img/s "
        r"\(([\d.]+) ms/batch", stdout)
    if m:
        rec["iters"] = int(m.group(1))
        rec["batch"] = int(m.group(2))
        rec["img_per_sec"] = float(m.group(3))
        rec["ms_per_frame"] = float(m.group(4))
    rec["detections"] = re.findall(
        r"det\[\d+\] cls=(\d+) score=([\d.]+) "
        r"box=\(([-\d.]+), ([-\d.]+), ([-\d.]+), ([-\d.]+)\)", stdout)
    return rec


def cpu_reference_dets(export_dir: str, image) -> list:
    """Deserialize the SAME exported artifact and run it on CPU: the
    strongest parity oracle (identical program, only backend differs)."""
    import jax
    import numpy as np

    with open(os.path.join(export_dir, "exported_predict.bin"), "rb") as f:
        exported = jax.export.deserialize(f.read())
    boxes, classes, scores, valid = [
        np.asarray(a) for a in exported.call(image)]
    dets = []
    for i in range(boxes.shape[1]):
        if valid[0, i]:
            dets.append({"cls": int(classes[0, i]),
                         "score": round(float(scores[0, i]), 4),
                         "box": [round(float(v), 2)
                                 for v in boxes[0, i].tolist()]})
    return dets


def serve_smoke(export_dir: str, imsize: int = 64,
                buckets=(1, 2, 4)) -> dict:
    """Serve-mode smoke (ISSUE 8): export the per-bucket StableHLO set
    (`--export-serve`) at CPU-friendly shapes, then prove every bucket
    artifact round-trips — deserialize, execute a zeros batch at the
    bucket's shape, check the fixed-shape Detections contract. This is
    the C++ server's artifact contract checked end-to-end without a chip
    (the real runner consumes the same .mlir files; artifacts/r02/README
    §5 has the chip invocation)."""
    import jax
    import numpy as np

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.export import (export_predict,
                                                       load_exported)

    cfg = Config(num_stack=1, hourglass_inch=16, num_cls=2, imsize=imsize,
                 topk=16, conf_th=0.0, nms="nms", nms_th=0.5,
                 save_path=export_dir, export_raw_input=True,
                 export_serve=True, serve_buckets=list(buckets))
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    with maybe_tracer().span("serve-smoke-export", dir=export_dir) as sp:
        export_predict(cfg, export_dir)
    rec: dict = {"export_s": round(sp.dur_s, 1), "buckets": {}}
    with open(os.path.join(export_dir, "meta.json")) as f:
        meta = json.load(f)
    rec["meta_serve_buckets"] = meta.get("serve_buckets")
    n_boxes = int(meta["num_boxes"])
    for b in buckets:
        bdir = os.path.join(export_dir, "serving", "b%d" % b)
        exported = load_exported(
            os.path.join(bdir, "exported_predict.bin"))
        boxes, classes, scores, valid = [
            np.asarray(a) for a in exported.call(
                np.zeros((b, imsize, imsize, 3), np.uint8))]
        # a complete C++ runner artifact dir: program + meta +
        # compile options (runner.cc reads all three from its dir arg)
        bmeta = json.load(open(os.path.join(bdir, "meta.json")))
        ok = (boxes.shape == (b, n_boxes, 4)
              and classes.shape == (b, n_boxes)
              and scores.shape == (b, n_boxes)
              and valid.shape == (b, n_boxes)
              and bmeta["input_shape"][0] == b
              and bmeta["serve_bucket"] == b
              and os.path.exists(os.path.join(
                  bdir, "exported_predict.stablehlo.mlir"))
              and os.path.exists(os.path.join(bdir,
                                              "compile_options.pb")))
        rec["buckets"]["b%d" % b] = {
            "ok": bool(ok), "mlir": True,
            "valid_count": int(valid.sum())}
        HB.beat("serve smoke b=%d" % b)
    rec["ok"] = all(v["ok"] for v in rec["buckets"].values()) \
        and list(meta.get("serve_buckets", [])) == sorted(buckets)
    return rec


def main() -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")  # C++ runner owns the chip
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    use_compile_cache()

    if "--serve-smoke" in sys.argv:
        # CPU-only bucket-set artifact proof; no chip, no runner binary
        out = os.path.join(REPO, "artifacts", ROUND, "serving",
                           "runner_serve_smoke.json")
        rec = serve_smoke(os.path.join(WORK, "export_serve"))
        os.makedirs(os.path.dirname(out), exist_ok=True)
        save_json(out, rec, indent=1)
        print(json.dumps(rec))
        if not rec["ok"]:
            raise SystemExit("serve smoke failed: %s" % rec)
        return

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.export import export_predict

    os.makedirs(WORK, exist_ok=True)
    results = {"plugin": PLUGIN, "imsize": IMSIZE, "runs": {}}

    ckpt = find_trained_ckpt()
    results["checkpoint"] = ckpt
    results["trained_weights"] = ckpt is not None
    if ckpt is None:
        log("no completed quality_matrix base training; exporting "
            "fresh-init weights (FPS still valid, detections are noise)")

    export_dir = os.path.join(WORK, "export_u8")
    cfg = Config(num_stack=1, hourglass_inch=128, num_cls=2, imsize=IMSIZE,
                 topk=100, conf_th=0.3 if ckpt else 0.01, nms="nms",
                 nms_th=0.5, amp=True, model_load=ckpt or "",
                 save_path=export_dir, export_raw_input=True)
    from real_time_helmet_detection_tpu.obs.spans import maybe_tracer
    tracer = maybe_tracer()
    with tracer.span("export", dir=export_dir) as sp:
        export_predict(cfg, export_dir)
    results["export_s"] = round(sp.dur_s, 1)
    log("exported to %s in %.1fs" % (export_dir, results["export_s"]))

    img_path = os.path.join(WORK, "img.u8")
    image = render_image(img_path)
    flush(results)

    # CPU oracle first (cheap, hermetic). The runner prints at most 10
    # detections, so storing 20 keeps the artifact readable while leaving
    # headroom to eyeball ordering.
    ref_dets = cpu_reference_dets(export_dir, image)
    results["cpu_reference_valid_count"] = len(ref_dets)
    results["cpu_reference_detections"] = ref_dets[:20]
    log("CPU reference detections (%d valid): %s"
        % (len(ref_dets), ref_dets[:5]))
    flush(results)

    if not os.path.exists(RUNNER):
        results["error"] = "runner binary missing at %s" % RUNNER
        flush(results)
        raise SystemExit(results["error"])
    if not PLUGIN or not os.path.exists(PLUGIN):
        results["error"] = ("no PJRT plug-in: set $PJRT_PLUGIN to the "
                            "plug-in .so (got %r)" % PLUGIN)
        flush(results)
        raise SystemExit(results["error"])

    for depth, iters in ((1, 100), (4, 200), (8, 400)):
        cmd = [RUNNER, PLUGIN, export_dir, "--image", img_path,
               "--iters", str(iters), "--depth", str(depth)]
        log("running depth=%d: %s" % (depth, " ".join(cmd[:6]) + " ..."))
        with tracer.span("runner", depth=depth) as run_span:
            try:
                # Popen + beating wait instead of a blind subprocess.run:
                # the C++ runner's compile legitimately takes a while,
                # and a silent wait would read as a hang to the
                # supervisor — whose SIGTERM would orphan the child that
                # holds the chip.
                proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                        stderr=subprocess.PIPE, text=True)
                deadline = time.time() + 1800
                while proc.poll() is None and time.time() < deadline:
                    HB.beat("runner depth=%d running" % depth)
                    time.sleep(10)
                if proc.poll() is None:
                    proc.kill()
                    proc.communicate()
                    raise subprocess.TimeoutExpired(cmd, 1800)
                r_stdout, r_stderr = proc.communicate()
                r = subprocess.CompletedProcess(cmd, proc.returncode,
                                                r_stdout, r_stderr)
            except subprocess.TimeoutExpired:
                # A timeout here killed a TPU-claiming process — the claim
                # may now be wedged (CLAUDE.md). Launching the next depth
                # would block on the wedged claim and get timeout-killed in
                # turn, serially re-wedging the chip; abort the sweep.
                results["runs"]["depth%d" % depth] = {
                    "error": "timeout 1800s"}
                results["aborted"] = ("depth%d timed out; remaining depths "
                                      "skipped to avoid re-wedging the "
                                      "device claim" % depth)
                flush(results)
                r = None
        if r is None:
            break
        rec = parse_runner(r.stdout)
        rec["wall_s"] = round(run_span.dur_s, 1)
        rec["rc"] = r.returncode
        if r.returncode != 0:
            rec["stderr_tail"] = r.stderr.strip().splitlines()[-3:]
        results["runs"]["depth%d" % depth] = rec
        log("depth=%d: %s" % (depth, {k: v for k, v in rec.items()
                                      if k != "detections"}))
        flush(results)

    # detections parity: runner (TPU) vs CPU oracle on the same artifact.
    # The runner prints at most 10 detections (runner.cc:433), so compare
    # the common prefix; tolerances absorb TPU-vs-CPU bf16 numerics.
    ref = ref_dets
    for name, rec in results["runs"].items():
        dets = rec.get("detections")
        if not dets or rec.get("rc") != 0:
            continue
        ok = abs(len(dets) - min(len(ref), 10)) <= 1
        for d_run, d_ref in zip(dets, ref):
            cls, score, *box = d_run
            if int(cls) != d_ref["cls"]:
                ok = False
            elif abs(float(score) - d_ref["score"]) > 0.05:
                ok = False
            elif max(abs(float(a) - b)
                     for a, b in zip(box, d_ref["box"])) > 2.0:
                ok = False
        rec["parity_vs_cpu"] = ok
    flush(results)
    print(json.dumps(results))


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
