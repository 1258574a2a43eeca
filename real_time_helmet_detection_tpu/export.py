"""Model export: fused predict function -> serialized StableHLO artifacts.

Capability parity with the reference export path (/root/reference/export.py:
`Export` module composing network -> sigmoid -> hm2box -> scripted NMS,
`torch.jit.trace` + `save` producing `jit_traced_model_{cpu,gpu}.pth` for the
C++ libtorch app), re-designed TPU-first:

* the traced artifact is the SAME fused jitted predict function used by
  eval (predict.py) — network, sigmoid, decode, NMS in one XLA program with
  fixed shapes (topk padding + validity mask instead of the reference's
  batch-item-0-only dynamic outputs, ref export.py:55);
* `jax.export` serializes it with the weights closed over as constants
  (= TorchScript's baked-in parameters). Two artifacts are written:
  - `exported_predict.bin` — jax.export round-trippable (Python consumers);
  - `exported_predict.stablehlo.mlir` — the raw StableHLO module consumed
    by the native C++ PJRT runner (cpp/pjrt_runner), the PytorchToCpp
    equivalent (SURVEY.md §2.2);
* a `meta.json` records shapes/flags so runners need no Python config.

Parity (traced-vs-eager, ≡ ref hourglass.py:251-256, export.py:145-152) is
enforced by tests/test_export.py.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .predict import make_predict_fn
from .utils import atomic_write_bytes, save_json


def build_export_fn(model, variables, cfg: Config,
                    normalize: Optional[str] = None, quant_scales=None):
    """Close the variables over the fused predict fn: images -> Detections
    as a flat tuple (boxes, classes, scores, valid).

    `normalize` bakes the input normalization INTO the artifact (see
    make_predict_fn): the deployment app then feeds raw [0, 255] pixels —
    a self-contained artifact, unlike the reference's TorchScript trace
    whose normalization lives in the C++ app (ref PytorchToCpp).
    `quant_scales` (with cfg.infer_dtype == "int8") bakes the BN-folded
    int8-quantized network into the artifact instead — the serialized
    StableHLO then carries int8 convolution bodies end to end."""
    predict = make_predict_fn(model, cfg, normalize=normalize,
                              quant_scales=quant_scales)

    def fn(images: jax.Array):
        d = predict(variables, images)
        return d.boxes, d.classes, d.scores, d.valid

    return fn


def export_predict(cfg: Config, out_dir: Optional[str] = None,
                   batch_size: int = 1) -> Tuple[str, str]:
    """Export the fused predict function for `cfg` (weights from
    `cfg.model_load`, fresh init if unset — useful for smoke tests).

    Returns (bin_path, mlir_path).
    """
    from .evaluate import load_eval_state

    out_dir = out_dir or cfg.save_path
    os.makedirs(out_dir, exist_ok=True)
    imsize = cfg.imsize or 512

    # serialized artifacts always take the XLA epilogue: a Pallas
    # custom-call inside exported StableHLO would pin the artifact to the
    # exporting libtpu (the C++ runner dlopens arbitrary plugins), and
    # the eval-mode epilogue is a pointwise nicety, not the conv-bound
    # artifact's bottleneck. Checkpoints are epilogue-agnostic, so this
    # changes nothing about the weights.
    import dataclasses as _dc
    cfg = _dc.replace(cfg, epilogue="xla")

    model, variables = load_eval_state(cfg)
    normalize = cfg.pretrained if cfg.export_raw_input else None

    # --infer-dtype int8: the exported program is the BN-folded quantized
    # predict. Scales come from a saved calibration artifact
    # (--quant-scales, the production path — calibrate on real data via
    # `evaluate`), else from a synthetic calibration pass (smoke tests /
    # fresh-init exports); either way the scales used are re-persisted
    # next to the artifact and their hash pinned in meta.json so the
    # served program is traceable to its calibration run.
    quant_scales = None
    scales_sha = None
    scales_rel = None
    if cfg.infer_dtype == "int8":
        from .ops.quant import (calibrate_scales, load_scales, save_scales,
                                synthetic_calibration_batches)
        if cfg.quant_scales:
            quant_scales = load_scales(cfg.quant_scales)
        else:
            print("warning: --infer-dtype int8 export without "
                  "--quant-scales; calibrating on synthetic batches "
                  "(smoke-quality scales — pass the eval-produced "
                  "artifact for a served deployment)")
            import jax.numpy as _jnp
            quant_scales = calibrate_scales(
                cfg, variables,
                synthetic_calibration_batches(
                    batch_size, imsize, n=cfg.calib_batches,
                    raw=cfg.export_raw_input),
                dtype=_jnp.bfloat16 if cfg.amp else None,
                normalize=normalize,
                percentile=cfg.calib_percentile)
        scales_path = os.path.join(out_dir, "calibration",
                                   "quant_scales.json")
        scales_sha = save_scales(scales_path, quant_scales, meta={
            "source": cfg.quant_scales or "synthetic",
            "calib_percentile": cfg.calib_percentile})
        scales_rel = os.path.relpath(scales_path, out_dir)

    fn = build_export_fn(model, variables, cfg, normalize=normalize,
                         quant_scales=quant_scales)

    # raw-input artifacts take uint8 pixels: 4x less wire traffic per
    # frame, with the cast + normalization baked into the program
    in_dtype = jnp.uint8 if cfg.export_raw_input else jnp.float32
    spec = jax.ShapeDtypeStruct((batch_size, imsize, imsize, 3), in_dtype)
    exported = jax.export.export(jax.jit(fn))(spec)

    # atomic (tmp + os.replace) like every other artifact write: the C++
    # runner and runner_drive.py trust any file they find at these paths,
    # and a kill mid-write must never leave a truncated program there
    bin_path = os.path.join(out_dir, "exported_predict.bin")
    atomic_write_bytes(bin_path, exported.serialize())

    mlir_path = os.path.join(out_dir, "exported_predict.stablehlo.mlir")
    atomic_write_bytes(mlir_path, exported.mlir_module().encode())

    # serialized default CompileOptionsProto for the C++ PJRT runner
    # (PJRT_Client_Compile requires one; building the proto in C++ would
    # drag in the whole schema). jaxlib's own class: jax has no public
    # constructor for the default options.
    from jax._src.lib import xla_client
    compile_options = xla_client.CompileOptions().SerializeAsString()
    atomic_write_bytes(os.path.join(out_dir, "compile_options.pb"),
                       compile_options)

    # --export-serve: one artifact per serve bucket (ISSUE 8), the SAME
    # fused fn lowered at every batch shape the Python engine AOT-compiles
    # (serving.resolve_buckets is the one bucket-set definition), so the
    # C++ runner can serve the engine's bucket set. Each bucket dir is
    # self-contained (bin + mlir + compile options); meta.json (below)
    # records the set.
    serve_buckets = []
    serve_rel = {}
    if cfg.export_serve:
        from .serving import resolve_buckets
        serve_buckets = list(resolve_buckets(cfg))
        for b in serve_buckets:
            bdir = os.path.join(out_dir, "serving", "b%d" % b)
            os.makedirs(bdir, exist_ok=True)
            bspec = jax.ShapeDtypeStruct((b, imsize, imsize, 3), in_dtype)
            bexp = jax.export.export(jax.jit(fn))(bspec)
            atomic_write_bytes(os.path.join(bdir, "exported_predict.bin"),
                               bexp.serialize())
            atomic_write_bytes(
                os.path.join(bdir, "exported_predict.stablehlo.mlir"),
                bexp.mlir_module().encode())
            # each bucket dir is a COMPLETE runner artifact: the C++
            # runner reads meta.json (input_shape) + compile_options.pb
            # from whatever dir it is pointed at (runner.cc:248-250), so
            # `pjrt_runner <plugin> <out_dir>/serving/b<N>` serves bucket N
            save_json(os.path.join(bdir, "meta.json"), {
                "input_shape": [b, imsize, imsize, 3],
                "input_dtype": "uint8" if cfg.export_raw_input
                               else "float32",
                "num_boxes": cfg.num_stack * cfg.topk,
                "imsize": imsize, "num_cls": cfg.num_cls,
                "raw_input": bool(cfg.export_raw_input),
                "infer_dtype": cfg.infer_dtype,
                "serve_bucket": b,
            }, indent=2)
            atomic_write_bytes(os.path.join(bdir, "compile_options.pb"),
                               compile_options)
            serve_rel["b%d" % b] = os.path.relpath(bdir, out_dir)

    save_json(os.path.join(out_dir, "meta.json"), {
        "input_shape": [batch_size, imsize, imsize, 3],
        "input_dtype": "uint8" if cfg.export_raw_input else "float32",
        "outputs": ["boxes[B,N,4]", "classes[B,N]", "scores[B,N]",
                    "valid[B,N]"],
        "num_boxes": cfg.num_stack * cfg.topk,
        "imsize": imsize,
        "num_cls": cfg.num_cls,
        "conf_th": cfg.conf_th,
        "nms": cfg.nms,
        "nms_th": cfg.nms_th,
        "pretrained": cfg.pretrained,
        # raw_input: artifact expects [0, 255] pixels (normalization
        # baked in); else pre-normalized floats
        "raw_input": bool(cfg.export_raw_input),
        # inference-compression provenance: which numeric path the
        # artifact bakes in, and (int8) the sha256 + location of the
        # exact activation-scales pytree it was built with — a served
        # artifact is traceable to its calibration run
        "infer_dtype": cfg.infer_dtype,
        "quant_scales_sha256": scales_sha,
        "quant_scales_path": scales_rel,
        # the serve bucket set (--export-serve): per-bucket artifact dirs,
        # each holding the same program at that batch shape — a C++ server
        # compiles them all at startup exactly like the Python engine
        "serve_buckets": serve_buckets,
        "serve_artifacts": serve_rel,
    }, indent=2)
    return bin_path, mlir_path


def load_exported(bin_path: str):
    """Round-trip a serialized artifact back to a callable (Python side)."""
    with open(bin_path, "rb") as f:
        return jax.export.deserialize(f.read())
