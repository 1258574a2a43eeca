"""Evaluation driver and single-image demo.

Capability parity with the reference eval runtime
(/root/reference/evaluate.py:15-97 `single_device_evaluate`,
`evaluate_step`; :245-290 demo `__main__`):

* builds the fused jitted predictor (predict.py ≡ `Prediction`);
* iterates the test split with the deterministic resize augmentor, rescales
  boxes back to each image's original WxH from its VOC XML size
  (ref evaluate.py:73-84, 100-112);
* writes `prediction_results.pickle` plus per-image
  `cls score x1 y1 x2 y2` txt files (ref evaluate.py:43-54) — and, beyond
  the reference, scores them in-repo with the hermetic VOC mAP evaluator
  (metrics.py) instead of requiring the external mAP submodule;
* `demo()` runs one image end to end, clamps boxes to the frame, draws
  boxes/labels and saves `image.png` (ref evaluate.py:245-290 — without
  reproducing its console-print quirk of rescaling ymax by the width,
  ref evaluate.py:285).
"""

from __future__ import annotations

import os
import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .data import (CLASS2COLOR, INDEX2CLASS, BatchLoader, TestAugmentor,
                   VOCDataset, load_dataset)
from .models import build_model
from .predict import make_predict_fn
from .serving import ServingEngine, resolve_buckets
from .train import init_variables, resolve_model_load, restore_variables
from .utils import (AverageMeter, draw_box, imload, save_pickle, timestamp,
                    write_text)


def load_eval_state(cfg: Config) -> Tuple:
    """Build model + restore weights for inference (≡ ref evaluate.py:20,
    train.py:164-193 eval path). Returns (model, variables). No optimizer
    state is ever built — eval shouldn't spend 2x model params of device
    memory on Adam moments it discards."""
    # --amp selects bf16 compute for inference too (params stay fp32, the
    # checkpoint format is identical): the TPU-idiomatic fast path.
    dtype = jnp.bfloat16 if cfg.amp else None
    model = build_model(cfg, dtype=dtype)
    imsize = cfg.imsize or 512
    params, batch_stats = init_variables(model, jax.random.key(cfg.random_seed),
                                         imsize)
    if cfg.model_load:
        # a save dir resolves to its newest COMPLETE checkpoint (a killed
        # async save must not poison the pick — see find_latest_checkpoint)
        params, batch_stats = restore_variables(
            resolve_model_load(cfg.model_load), params, batch_stats,
            prefer_ema=cfg.ema_eval)
    return model, {"params": params, "batch_stats": batch_stats}


def _origin_size(voc_dict: Dict) -> Tuple[int, int]:
    """(width, height) from the VOC XML (ref evaluate.py:75-76)."""
    size = voc_dict["annotation"]["size"]
    return int(size["width"]), int(size["height"])


def _eval_quant_scales(cfg: Config, variables, loader, chief: bool = True):
    """Activation scales for `--infer-dtype int8`: the saved artifact when
    `--quant-scales` names one, else an on-the-fly calibration pass over
    the first `--calib-batches` batches of the (deterministic, raw-uint8)
    eval loader — each batch is ONE jitted dispatch fetching only
    per-layer scalars (ops/quant.py). The freshly calibrated scales are
    persisted atomically under `<save_path>/calibration/` so the run is
    reproducible and export can pin its hash."""
    from .ops.quant import calibrate_scales, load_scales, save_scales

    if cfg.quant_scales:
        print("%s: int8 scales <- %s" % (timestamp(), cfg.quant_scales),
              flush=True)
        return load_scales(cfg.quant_scales)

    def batches():
        n = 0
        it = iter(loader)
        try:
            for batch in it:
                images = batch.image
                if images.shape[0] < cfg.batch_size:
                    # pad to the steady-state shape: one calibration
                    # program, no second XLA compile on an odd tail batch
                    pad = cfg.batch_size - images.shape[0]
                    images = np.concatenate(
                        [images,
                         np.zeros((pad,) + images.shape[1:], images.dtype)])
                yield images
                n += 1
                if n >= cfg.calib_batches:
                    break
        finally:
            if hasattr(it, "close"):
                it.close()  # reap the loader's producer thread

    dtype = jnp.bfloat16 if cfg.amp else None
    scales = calibrate_scales(cfg, variables, batches(), dtype=dtype,
                              normalize=cfg.pretrained,
                              percentile=cfg.calib_percentile)
    path = os.path.join(cfg.save_path, "calibration", "quant_scales.json")
    if chief:
        digest = save_scales(path, scales, meta={
            "calib_batches": cfg.calib_batches,
            "calib_percentile": cfg.calib_percentile,
            "model_load": cfg.model_load})
        print("%s: int8 calibration (%d batches, p%.5g) -> %s (sha256 %s)"
              % (timestamp(), cfg.calib_batches, cfg.calib_percentile,
                 path, digest[:12]), flush=True)
    return scales


def evaluate(cfg: Config) -> Dict:
    """Full test-split evaluation (≡ ref evaluate.py:15-97) + in-repo mAP.

    Returns the metrics dict from `compute_map` (plus timing info).
    """
    from .metrics import compute_map, write_detection_txt

    # Multi-host: each process scores its `indices[rank::world]` shard of
    # the test split (BatchLoader's DistributedSampler-equivalent) on its
    # own local device, then fixed-shape detection blocks are allgathered
    # via multihost_utils and scored identically on every process (rank 0
    # owns the txt/pickle side effects). The reference eval is single-GPU
    # only (ref evaluate.py:16); this extends it to the pod shapes the
    # training path already supports. The rendezvous lives HERE, not in
    # the caller, so the production CLI (`main.py --world-size 2 --rank N`
    # in eval mode) reaches the sharded path exactly like train() does
    # (review finding: without it every process would silently evaluate
    # the full split independently).
    from .parallel import init_distributed
    init_distributed(cfg)
    rank, world = jax.process_index(), jax.process_count()
    # Flight recorder (obs/): the eval loop's phases land in the span log
    # when --span-log/$OBS_SPAN_LOG is set — disabled it costs nothing.
    from .obs.spans import maybe_tracer
    tracer = maybe_tracer(cfg.span_log or None)
    if tracer.enabled:
        tracer.context(phase="evaluate", rank=rank)
    model, variables = load_eval_state(cfg)
    # Multi-device eval: shard the batch over a data mesh when the batch
    # divides the device count (single-host; the reference's eval is
    # single-GPU only, ref evaluate.py:16). Oversized meshes are trimmed
    # to the batch-divisible prefix rather than skipping DP entirely.
    mesh = None
    if world == 1:
        from .parallel import fit_data_mesh, make_mesh
        ndev = fit_data_mesh(cfg.batch_size, cfg.num_devices)
        if ndev > 1:
            mesh = make_mesh(ndev)
            print("%s: eval sharded over %d devices"
                  % (timestamp(), ndev), flush=True)
    else:
        # per-process single-device predict: the split shard is process-
        # local numpy, so a global mesh would mis-shard it; cross-process
        # work happens only at the final allgather
        print("%s: multi-host eval rank %d/%d (split sharded by rank)"
              % (timestamp(), rank, world), flush=True)
    dataset, augmentor = load_dataset(cfg)
    loader_cls = BatchLoader
    if cfg.loader == "process":
        # same GIL-free pipeline as training (data/shm_pool.py); eval's
        # deterministic augmentor makes the backends trivially identical
        from .data import ProcessBatchLoader
        loader_cls = ProcessBatchLoader
    loader = loader_cls(dataset, augmentor, batch_size=cfg.batch_size,
                        pretrained=cfg.pretrained, num_cls=cfg.num_cls,
                        normalized_coord=cfg.normalized_coord,
                        scale_factor=cfg.scale_factor,
                        max_boxes=cfg.max_boxes, shuffle=False,
                        drop_last=False, num_workers=cfg.num_workers,
                        rank=rank, world_size=world, raw=True)

    # raw wire: images ship as uint8 canvases and are normalized on-device
    # inside the jitted predict program (see make_predict_fn).
    # --infer-dtype int8 additionally needs the calibrated activation
    # scales: a saved artifact (--quant-scales), or an on-the-fly
    # calibration pass over the first --calib-batches eval batches (one
    # jitted dispatch per batch fetching only per-layer scalars).
    quant_scales = None
    if cfg.infer_dtype == "int8":
        with tracer.span("calibrate", batches=cfg.calib_batches):
            quant_scales = _eval_quant_scales(cfg, variables, loader,
                                              chief=rank == 0)
    predict = make_predict_fn(model, cfg, normalize=cfg.pretrained,
                              mesh=mesh, quant_scales=quant_scales)

    txt_dir = os.path.join(cfg.save_path, "results", "txt")
    results: Dict[str, Dict] = {}
    gt_boxes: Dict[str, np.ndarray] = {}
    gt_labels: Dict[str, np.ndarray] = {}
    # "dispatch" = engine submit wall (async — the engine batches and
    # dispatches in its own threads; bench.py owns device timing);
    # "consume" = result wait + host box rescale/txt writes. Host-side
    # pipeline meters by design: graftlint: off=per-call-timing
    meters = {k: AverageMeter() for k in ("data", "dispatch", "consume")}

    imsize = float(cfg.imsize or 512)
    seen = 0

    def consume_row(row, info):
        """Host-side consumption of one request's detections row."""
        nonlocal seen
        from .data.voc import boxes_from_voc_dict
        # `or` (not a .get default): a self-closed <filename/> parses
        # to "" since the r2 parser rewrite, which would silently make
        # every such image_id "" (round-2 advisor finding)
        image_id = os.path.splitext(
            info["annotation"].get("filename") or "%06d" % seen)[0]
        seen += 1
        ow, oh = _origin_size(info)
        keep = row.valid
        boxes = row.boxes[keep]
        # augmented (imsize x imsize) -> original WxH
        # (ref evaluate.py:100-112)
        boxes = boxes * np.array([ow / imsize, oh / imsize,
                                  ow / imsize, oh / imsize], np.float32)
        classes = row.classes[keep]
        scores = row.scores[keep]
        results[image_id] = {"box": boxes, "cls": classes, "score": scores}
        if world == 1:
            # multi-host defers all side effects to rank 0 after the
            # allgather, and scores GT from the local XML files
            write_detection_txt(txt_dir, image_id, boxes, classes, scores)
            gb, gl = boxes_from_voc_dict(info)
            gt_boxes[image_id], gt_labels[image_id] = gb, gl

    # The serving engine IS the eval predict path (ISSUE 8): per-image
    # requests coalesce into fixed-shape buckets (the final partial batch
    # simply takes a smaller AOT-compiled bucket — no host-side padding,
    # still zero recompiles), H2D/compute/D2H of consecutive batches
    # overlap at --serve-depth (subsuming the old one-deep pending
    # pipeline and eval's --device-prefetch staging), and the uint8 raw
    # wire + box-only egress are the engine's native contract. The meshed
    # path keeps the single batch-size bucket (the batch sharding's
    # divisibility constraint); results are bit-identical either way
    # (per-image independence, tests/test_serving.py).
    if mesh is not None:
        from .parallel import batch_sharding
        sharding = batch_sharding(mesh, 4)
        buckets = (cfg.batch_size,)
    else:
        sharding = None
        buckets = tuple(sorted(
            {b for b in resolve_buckets(cfg) if b <= cfg.batch_size}
            | {cfg.batch_size}))
    depth = max(cfg.serve_depth, 1 + cfg.device_prefetch)
    # in-flight recovery (ISSUE 9): a transient PJRT error or hung fetch
    # mid-eval costs a bounded retry of that batch's requests, not the
    # whole eval run (retries reuse the same AOT programs — bit-identical)
    engine = ServingEngine(
        predict, variables, (int(imsize), int(imsize), 3), np.uint8,
        buckets=buckets, max_wait_ms=cfg.serve_max_wait_ms, depth=depth,
        queue_capacity=cfg.serve_queue, sharding=sharding, tracer=tracer,
        max_retries=cfg.serve_max_retries,
        hang_timeout_s=(cfg.serve_hang_timeout_ms / 1e3
                        if cfg.serve_hang_timeout_ms > 0 else None))

    from collections import deque
    pending: "deque" = deque()  # (futures, infos) per loader batch

    def consume_batch(futs, infos):
        t0 = time.time()
        for fut, info in zip(futs, infos):
            consume_row(fut.result(), info)
        # includes the result wait, i.e. any device time not hidden
        # behind the host work
        consume_t = time.time() - t0
        meters["consume"].update(consume_t)
        if tracer.enabled:
            tracer.record("fetch", consume_t)

    try:
        tic = time.time()
        for i, batch in enumerate(loader):
            data_t = time.time() - tic
            meters["data"].update(data_t)
            if tracer.enabled:
                tracer.record("loader-wait", data_t, it=i)
            t0 = time.time()
            futs = [engine.submit(batch.image[j])
                    for j in range(len(batch.infos))]
            dispatch_t = time.time() - t0
            meters["dispatch"].update(dispatch_t)
            if tracer.enabled:
                tracer.record("dispatch", dispatch_t, it=i)
            pending.append((futs, batch.infos))
            # drain completed heads without blocking: host work (box
            # rescale, txt writes) overlaps the engine's device pipeline
            while len(pending) > 1 and all(f.done()
                                           for f in pending[0][0]):
                consume_batch(*pending.popleft())

            if i % max(1, cfg.print_interval // 10) == 0:
                print("%s: eval iter %d/%d, data %.3fs submit %.3fs "
                      "fetch+consume %.3fs"
                      % (timestamp(), i, len(loader), meters["data"].avg,
                         meters["dispatch"].avg, meters["consume"].avg),
                      flush=True)
            tic = time.time()
        while pending:
            consume_batch(*pending.popleft())
    finally:
        engine.close()
        if hasattr(loader, "close"):
            loader.close()  # reap workers, unlink shared-memory slots
    tracer.close()

    if world > 1:
        m = _score_multihost(cfg, dataset, results, txt_dir, rank, world)
        m["timing"] = {k: v.avg for k, v in meters.items()}
        return m

    save_pickle(os.path.join(cfg.save_path, "prediction_results.pickle"),
                results)

    det_b = {k: v["box"] for k, v in results.items()}
    det_l = {k: v["cls"] for k, v in results.items()}
    det_s = {k: v["score"] for k, v in results.items()}
    m = compute_map(gt_boxes, gt_labels, det_b, det_l, det_s,
                    num_cls=cfg.num_cls)
    names = {c: INDEX2CLASS.get(c, str(c)) for c in m["ap"]}
    print("%s: mAP %.4f (%s)" % (
        timestamp(), m["map"],
        ", ".join("%s %.4f" % (names[c], ap) for c, ap in m["ap"].items())),
        flush=True)
    m["timing"] = {k: v.avg for k, v in meters.items()}
    return m


def _score_multihost(cfg: Config, dataset, results: Dict, txt_dir: str,
                     rank: int, world: int) -> Dict:
    """Gather every rank's detections and score the full split.

    JAX has no object gather, so each rank packs its (already rescaled-to-
    original-size) detections into fixed-shape blocks — `M` images of at
    most `num_stack * topk` boxes, `M = ceil(n_images / world)` identical
    on every rank because `epoch_indices` wrap-pads the split — and the
    blocks are allgathered with `multihost_utils.process_allgather`.
    Wrap-padded duplicate images are deduped by id (first occurrence
    wins). Every process computes the same mAP from the same gathered
    data; rank 0 owns the txt/pickle side effects. GT comes from each
    process's own copy of the annotation XMLs (every host mounts the full
    dataset, exactly as in training)."""
    import xml.etree.ElementTree as ET

    from jax.experimental import multihost_utils

    from .data.voc import boxes_from_voc_dict, parse_voc_xml
    from .metrics import compute_map, write_detection_txt

    id_bytes = 64
    # Validate id lengths on the FULL split — identical on every rank —
    # BEFORE the collective: a rank-local raise inside the packing loop
    # would leave the peer ranks blocked in process_allgather waiting for
    # a collective that never arrives (review finding). Raising here is
    # symmetric: every rank sees the same ids and fails the same way.
    for _iid in dataset.ids:
        if len(_iid.encode()) > id_bytes:
            raise ValueError(
                "image id %r exceeds the %d-byte multi-host gather slot"
                % (_iid, id_bytes))
    D = cfg.num_stack * cfg.topk
    M = -(-len(dataset) // world)
    ids = np.zeros((M, id_bytes), np.uint8)
    boxes = np.zeros((M, D, 4), np.float32)
    classes = np.zeros((M, D), np.int32)
    scores = np.zeros((M, D), np.float32)
    nval = np.zeros((M,), np.int32)
    for i, (image_id, r) in enumerate(sorted(results.items())):
        enc = image_id.encode()
        if len(enc) > id_bytes:
            # real split ids were pre-validated above; only a synthetic
            # consume() fallback id could trip this, and those are short —
            # an overflow here is an invariant violation worth the
            # (asymmetric) crash
            raise ValueError("image id %r exceeds the %d-byte gather slot"
                             % (image_id, id_bytes))
        ids[i, :len(enc)] = np.frombuffer(enc, np.uint8)
        n = min(len(r["box"]), D)
        boxes[i, :n] = r["box"][:n]
        classes[i, :n] = r["cls"][:n]
        scores[i, :n] = r["score"][:n]
        nval[i] = n

    # (world, M, ...) stacked blocks, identical on every process
    g_ids, g_boxes, g_classes, g_scores, g_nval = (
        np.asarray(multihost_utils.process_allgather(x))
        for x in (ids, boxes, classes, scores, nval))

    id2ann = dict(zip(dataset.ids, dataset.annotations))
    det_b: Dict[str, np.ndarray] = {}
    det_l: Dict[str, np.ndarray] = {}
    det_s: Dict[str, np.ndarray] = {}
    gt_boxes: Dict[str, np.ndarray] = {}
    gt_labels: Dict[str, np.ndarray] = {}
    for p in range(world):
        for i in range(M):
            iid = bytes(g_ids[p, i]).rstrip(b"\0").decode()
            if not iid or iid in det_b:  # pad row / wrap duplicate
                continue
            if iid not in id2ann:
                # consume()'s synthetic fallback ids (self-closed
                # <filename/>) cannot be mapped back to an annotation on a
                # foreign rank; refuse loudly rather than scoring a split
                # with silently-dropped images
                raise ValueError(
                    "multi-host eval cannot resolve image id %r to an "
                    "annotation file (images must carry real <filename> "
                    "tags)" % iid)
            n = int(g_nval[p, i])
            det_b[iid] = g_boxes[p, i, :n]
            det_l[iid] = g_classes[p, i, :n]
            det_s[iid] = g_scores[p, i, :n]
            voc = parse_voc_xml(ET.parse(id2ann[iid]).getroot())
            gb, gl = boxes_from_voc_dict(voc)
            gt_boxes[iid], gt_labels[iid] = gb, gl

    m = compute_map(gt_boxes, gt_labels, det_b, det_l, det_s,
                    num_cls=cfg.num_cls)
    if rank == 0:
        for iid in det_b:
            write_detection_txt(txt_dir, iid, det_b[iid], det_l[iid],
                                det_s[iid])
        save_pickle(
            os.path.join(cfg.save_path, "prediction_results.pickle"),
            {k: {"box": det_b[k], "cls": det_l[k], "score": det_s[k]}
             for k in det_b})
        names = {c: INDEX2CLASS.get(c, str(c)) for c in m["ap"]}
        print("%s: multi-host mAP %.4f over %d images (%s)" % (
            timestamp(), m["map"], len(det_b),
            ", ".join("%s %.4f" % (names[c], ap)
                      for c, ap in m["ap"].items())), flush=True)
    return m


def demo(cfg: Config) -> Dict:
    """Single-image demo (≡ ref evaluate.py:245-290). `cfg.data` is the
    image path. Saves the overlay as `image.png` in save_path."""
    model, variables = load_eval_state(cfg)

    imsize = cfg.imsize or 512
    img, img_pil, origin_size = imload(cfg.data, cfg.pretrained, imsize)
    quant_scales = None
    if cfg.infer_dtype == "int8":
        # one-image demo: the saved artifact when given, else
        # self-calibrate on the demo image (the normalized-input wire)
        from .ops.quant import calibrate_scales, load_scales
        quant_scales = (load_scales(cfg.quant_scales) if cfg.quant_scales
                        else calibrate_scales(
                            cfg, variables, [img],
                            dtype=jnp.bfloat16 if cfg.amp else None,
                            percentile=cfg.calib_percentile))
    predict = make_predict_fn(model, cfg, quant_scales=quant_scales)
    # one-image serve through the engine API (bucket {1}): the demo is the
    # smallest consumer of the same serving surface eval and the C++
    # runner use — same program, same result bits as a direct predict
    with ServingEngine(predict, variables, (imsize, imsize, 3),
                       np.float32, buckets=(1,),
                       max_wait_ms=0.0) as engine:
        row = engine.submit(np.asarray(img)[0]).result()

    keep = row.valid
    boxes = np.clip(row.boxes[keep], 0, imsize)  # clamp (ref :270)
    classes = row.classes[keep]
    scores = row.scores[keep]

    pil = img_pil.resize((imsize, imsize))
    for box, c, s in zip(boxes, classes, scores):
        color = CLASS2COLOR.get(int(c), (0, 0, 255))
        pil = draw_box(pil, box, color=color)
        pil = write_text(pil, "%s: %.2f" % (INDEX2CLASS.get(int(c), c), s),
                         (box[0], box[1]), fontsize=cfg.fontsize)
        # console print at original scale (ref evaluate.py:278-287)
        rw = origin_size[0] / imsize
        rh = origin_size[1] / imsize
        print("%s %.2f: (%d, %d) (%d, %d)"
              % (INDEX2CLASS.get(int(c), c), s, box[0] * rw, box[1] * rh,
                 box[2] * rw, box[3] * rh), flush=True)
    out = os.path.join(cfg.save_path, "image.png")
    pil.save(out)
    print("%s: demo overlay -> %s" % (timestamp(), out), flush=True)
    return {"boxes": boxes, "classes": classes, "scores": scores}
