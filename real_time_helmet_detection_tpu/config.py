"""Config / flag system for the TPU framework.

Capability parity with the reference CLI (/root/reference/config.py:11-136:
~45 argparse flags over device, train, precision, distributed, eval/demo,
augmentation, loss, network, optimizer, logging), re-designed TPU-first:

* a typed `Config` dataclass is the single source of truth; the argparse
  parser is generated from its fields, so every flag exists exactly once;
* snapshots are human-readable `argument.txt` plus **JSON** `argument.json`
  (the reference pickles the whole namespace, config.py:168 — JSON is
  portable and safe to load);
* eval mode overrides the architecture fields from the checkpoint dir's
  snapshot so a CLI mistake can't build a mismatched network
  (ref config.py:157-158, 171-179);
* GPU-only knobs are re-interpreted for TPU: `--amp` selects the bf16
  compute policy (no GradScaler exists on TPU), `--dist-backend` is
  accepted for CLI compatibility but the backend is always XLA collectives,
  and `--num-devices` replaces `--gpu-no` (device *count* on the mesh,
  not CUDA ids).

Reference flags that were dead upstream are LIVE here (upgrades, each
tested): `--pool-size` (parsed but never read by the reference, ref
config.py:58 — here it is threaded through `predict`'s peak test, both the
XLA and Pallas paths), `--optim` (reference hard-codes Adam, ref
optim.py:4 — here it actually selects the optax optimizer).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import random
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

# The architecture fields restored from a checkpoint's snapshot at eval time
# (ref config.py:171-179's `targets` list). `variant` (ISSUE 13) is an
# architecture field like num_stack: evaluating a depthwise checkpoint
# with the residual graph would fail the restore (different param tree).
ARCHITECTURE_FIELDS = (
    "scale_factor", "num_cls", "pretrained", "normalized_coord",
    "num_stack", "hourglass_inch", "increase_ch", "activation", "pool",
    "neck_activation", "neck_pool", "variant", "stem_width",
)

# Residual-block variants (ISSUE 13, per Lighter Stacked Hourglass arxiv
# 2107.13643): "residual" = the reference's two-3x3-conv block,
# "depthwise" = depthwise-separable convs (kxk depthwise + 1x1 pointwise),
# "ghost" = ghost modules (1x1 primary half + cheap depthwise half).
# models/hourglass.py consumes this vocabulary; defined here (stdlib-only
# module) so config validation never imports the model stack.
MODEL_VARIANTS = ("residual", "depthwise", "ghost")
# model families `models.build_model` dispatches on: the reference's stacked
# hourglass, and the decoder of models/decoder.py (no reference analogue)
# `DECODER_FAMILIES`: one body, the attention each layer's kind names in its
# family (models/decoder.py `ATTENTIONS`), served by predict.make_generate_fn
DECODER_FAMILIES = ("latent_moe_decoder", "gqa_moe_decoder",
                    "hybrid_moe_decoder")
MODEL_FAMILIES = ("hourglass",) + DECODER_FAMILIES

# Latency-tier presets (ISSUE 13): named architecture+serving bundles —
# the product tiers the fleet router mixes per tenant. `--tier edge`
# overrides the listed Config fields (tier wins over individual arch
# flags); everything else stays at CLI/default values.
# Widths/stacks/variants come from the
# r15 arch_grid counting-model sweep (artifacts/r15/sweep.json) with the
# quality tier pinned to the flagship stack2+soft-NMS recipe (0.7734
# held-out mAP, r05). serve_buckets per tier = each tier's own AOT bucket
# set (engine/export/C++ runner all read cfg.serve_buckets).
TIER_PRESETS = {
    # b1-latency-first: the arch_grid counting model's FLOPs AND bytes
    # floor (ghost-w64: 0.049 GF / 10.9 MB vs depthwise-w64's 0.072 GF /
    # 14.5 MB at 64^2 — artifacts/r15/sweep.cpu.json arch_grid_selected);
    # small buckets, never wait. Chip arch_grid --arch-map (queued)
    # re-decides with real mAP columns.
    "edge": dict(variant="ghost", num_stack=1, hourglass_inch=64,
                 stem_width=64, increase_ch=0, serve_buckets=[1, 2, 4],
                 serve_max_wait_ms=0.0),
    # batch-16 goodput + int8 PTQ (PR 5) — the bulk-traffic tier
    "throughput": dict(variant="ghost", num_stack=1, hourglass_inch=96,
                       stem_width=96, increase_ch=0, infer_dtype="int8",
                       serve_buckets=[4, 8, 16]),
    # the flagship recipe: stack2 + soft-NMS (quality_matrix r05 winner)
    "quality": dict(variant="residual", num_stack=2, hourglass_inch=128,
                    increase_ch=0, nms="soft-nms",
                    serve_buckets=[1, 2, 4, 8, 16]),
}


@dataclass
class Config:
    """All flags. Field name -> CLI flag: underscores become dashes."""

    # model family (ROADMAP D11/D13: one switch and one mapping, not a flat
    # field per size of every family)
    family: str = "hourglass"     # MODEL_FAMILIES
    decoder: Dict[str, Any] = field(default_factory=dict)  # a family of
    # DECODER_FAMILIES: the source's own config.json keys as they stand
    # (models/decoder.py `DecoderSpec.from_mapping` names each family's),
    # plus `ep_size` / `ep_rank` (how many chips share an expert layer, which
    # share this is; the source's count of routed experts and `vocab_size`
    # then count what is held HERE). Not a CLI flag: a mapping comes from a
    # file.

    # device
    num_devices: int = 0          # 0 = use every visible device
    spatial: int = 1              # spatial mesh-axis size (shards H of the maps)
    platform: str = ""            # force a jax platform ("cpu"/"tpu"); "" = default
    random_seed: int = 777

    # train
    train_flag: bool = False
    data: Optional[str] = None
    batch_size: int = 16
    sub_divisions: int = 1        # gradient accumulation (ref train.py:124)
    grad_accum: int = 1           # IN-STEP cross-replica gradient
    # accumulation (ISSUE 11): the jitted step splits the global batch
    # into this many equal micro-batches, scans them sequentially
    # (accumulating gradients in fp32) and applies ONE optimizer update —
    # effective batch = --batch-size at the HBM footprint of a
    # batch/grad_accum step, and the cross-replica gradient all-reduce
    # happens once per UPDATE instead of once per micro-batch (the
    # FireCaffe communication/batch-size tradeoff, PAPERS.md). Differs
    # from --sub-divisions (optax.MultiSteps across host steps: k host
    # dispatches per update) — the two compose. BatchNorm statistics
    # update sequentially per micro-batch, exactly as k consecutive
    # steps would. Host path only (--device-augment keeps its fused
    # per-batch step); requires batch-size % grad-accum == 0.
    start_epoch: int = 0
    end_epoch: int = 100
    num_workers: int = 8          # host-side data pipeline workers
    # (threads or processes, per --loader)
    loader: str = "thread"        # host input-pipeline backend:
    # "thread" = GIL-bound worker threads (zero setup cost; fine when the
    # device step dominates); "process" = spawn-safe worker processes with
    # SharedMemory batch transport (data/shm_pool.py) — GIL-free scaling
    # over host cores for input-bound configs; bit-identical batches
    # (tested), auto-fallback to the thread path if a worker dies
    device_prefetch: int = 0      # stage the next N batches' sharded
    # jax.device_put ahead of the train/eval step so H2D overlaps device
    # compute (0 disables); each staged batch pins one batch of device
    # memory. No reference analogue (DataLoader pin_memory + CUDA streams
    # do this implicitly on GPU)

    # precision (TPU: bf16 policy replaces CUDA AMP + GradScaler)
    amp: bool = False
    param_policy: str = "fp32"    # train-step parameter dtype policy
    # (ISSUE 7): "fp32" = the pre-PR program, params fp32 in TrainState
    # and recast to bf16 at every use site under --amp (the r07 roofline's
    # convert_convert_fusion rows); "bf16-compute" = TrainState carries a
    # once-cast bf16 compute copy, the fp32 MASTER lives inside the
    # optimizer state (optim.with_fp32_master) and the bf16 re-emission
    # fuses into the Adam update — the per-step param-convert traffic
    # disappears. Requires --amp (without it the compute dtype would
    # silently change) and --sub-divisions 1 (MultiSteps would accumulate
    # micro-grads in bf16; the accumulation path keeps its fp32 master in
    # params). Gradient-equality vs fp32 is pinned by
    # tests/test_param_policy.py; checkpoints record the policy's dtypes,
    # so resume with the same --param-policy.

    # distributed (multi-host over DCN; in-host over ICI mesh)
    world_size: int = 1           # number of hosts
    rank: int = 0                 # this host's index
    dist_backend: str = "xla"     # accepted for CLI parity; always XLA
    dist_url: str = "tcp://localhost:29500"  # jax.distributed coordinator

    # evaluation, demo, export
    export_flag: bool = False     # export the fused predict fn and exit
    export_raw_input: bool = False  # bake normalization into the export:
    # the artifact takes raw [0,255] pixels (self-contained deployment)
    imsize: Optional[int] = None
    topk: int = 100
    conf_th: float = 0.0
    nms_th: float = 0.5
    pool_size: int = 3            # peak-test window (3x3, as the reference)
    model_load: Optional[str] = None
    nms: str = "nms"              # nms | soft-nms | maxpool (PSRR-style
    # parallel maxpool suppression, ops/nms.py — approximate, no serial
    # greedy chain)
    fontsize: int = 10
    infer_dtype: str = "bf16"     # predict/eval/export numeric path:
    # "bf16" = the existing float graph (actual compute dtype follows
    # --amp: bf16 when set, fp32 otherwise); "int8" = BN-folded
    # post-training-quantized convs (ops/quant.py) — eval/export ONLY,
    # training always stays float. Gated on mAP parity, not just speed
    # (docs/ARCHITECTURE.md "Inference compression").
    quant_scales: Optional[str] = None  # path to a saved activation-scales
    # artifact (ops.quant.save_scales); unset = calibrate on the fly from
    # the first --calib-batches eval batches and save one
    calib_batches: int = 4        # calibration batches when no --quant-scales
    calib_percentile: float = 100.0  # activation clip statistic: 100 =
    # abs-max, <100 = that upper percentile of |x| (outlier-robust)

    # serving (ISSUE 8: the continuous-batching engine, serving/engine.py)
    serve_buckets: List[int] = field(
        default_factory=lambda: [1, 2, 4, 8, 16])  # static batch buckets:
    # every bucket is AOT-compiled once at engine construction and a
    # request batch takes the smallest bucket >= its size. ONE set shared
    # by the engine, export's per-bucket StableHLO artifacts and
    # graftlint's per-bucket trace audit (serving.resolve_buckets).
    serve_max_wait_ms: float = 5.0  # batch-formation policy: dispatch when
    # the largest bucket fills OR this long after the oldest queued
    # request arrived, whichever first (0 = never wait — latency-first)
    serve_depth: int = 2          # max in-flight batches (H2D/compute/D2H
    # pipelining depth; bounds device memory at `depth` batches) — the
    # engine generalization of the C++ runner's --depth loop
    serve_queue: int = 128        # admission bound: queued-but-unbatched
    # requests beyond this are shed (non-blocking submitters) or apply
    # backpressure (blocking submitters, e.g. the eval driver)
    export_serve: bool = False    # export additionally emits one StableHLO
    # artifact per serve bucket (out_dir/serving/b<N>/) so the C++ runner
    # can serve the same bucket set the Python engine does
    serve_max_retries: int = 2    # in-flight recovery (ISSUE 9): per-
    # REQUEST retry budget after a batch dispatch/fetch failure or hang —
    # requeued requests reuse the same AOT bucket programs, so retried
    # results stay bit-identical to one-shot predict; budget exhausted
    # surfaces the error on the future (0 = fail-fast, the pre-PR
    # behavior)
    serve_hang_timeout_ms: float = 0.0  # engine fetch watchdog: a batch
    # D2H exceeding this is declared hung and its requests requeued.
    # 0 disables (default — on a healthy backend the watchdog is pure
    # overhead); when set, keep it WELL above the largest bucket's honest
    # p99 fetch time.

    # cascade serving (ISSUE 16: edge-first inference with confidence-
    # gated escalation, serving/fleet.py + docs/ARCHITECTURE.md "Cascade
    # serving")
    cascade: bool = False         # enroll fleet tenants in the cascade:
    # requests dispatch to the edge tier first; the in-jit confidence
    # summary (ops.decode.confidence_summary, riding the box D2H with
    # zero extra fetches) decides escalation to the quality tier
    cascade_threshold: Optional[float] = None  # escalate iff confidence
    # < threshold. None = load the calibrated operating point from the
    # newest committed artifacts/*/cascade.json (`quality_matrix
    # --cascade`) via cascade_overrides — the sweep-best promotion idiom;
    # an explicit value wins (experiments off the calibrated point)
    cascade_tiers: List[str] = field(
        default_factory=lambda: ["edge", "quality"])  # (edge, quality)
    # tier pair the cascade spans; both must be named TIER_PRESETS tiers
    # with replica slots in the fleet

    # streaming video (ISSUE 17: delta-gated tile inference,
    # serving/streams.py + docs/ARCHITECTURE.md "Streaming video")
    stream: bool = False          # route video through a StreamSession:
    # per-tile change detection (ops.delta.tile_delta_summary) skips the
    # backbone for static tiles; only changed tiles hit the serving plane
    stream_threshold: Optional[float] = None  # a tile is CHANGED iff its
    # mean |delta| >= threshold ([0, 255] scale). None = load the
    # calibrated operating point from the newest committed
    # artifacts/*/streams.json (`quality_matrix --streams`) via
    # stream_overrides — the cascade promotion idiom; an explicit value
    # wins (experiments off the calibrated point)
    stream_tile_grid: int = 2     # frames split into grid x grid tiles,
    # each the tile model's input size (fixed shapes under jit)
    stream_ema: float = 0.5       # EMA weight of the PREVIOUS score when
    # a recomputed tile's detection associates to a cached track
    # (0 = no smoothing)
    stream_track_radius: float = 8.0  # center-distance association
    # radius (tile pixels) for the track stitching above

    # augmentation
    crop_percent: List[float] = field(default_factory=lambda: [0.0, 0.1])
    color_multiply: List[float] = field(default_factory=lambda: [1.2, 1.5])
    translate_percent: float = 0.1
    affine_scale: List[float] = field(default_factory=lambda: [0.5, 1.5])
    multiscale_flag: bool = False
    multiscale: List[int] = field(default_factory=lambda: [320, 512, 64])
    device_augment: bool = False  # augment+encode on the TPU inside the step
    cache_device: bool = False    # stage the whole dataset in HBM once;
    # each step gathers its batch on-device by index (single-host,
    # requires --device-augment; for datasets that fit in HBM)

    # loss
    hm_weight: float = 1.0
    offset_weight: float = 1.0
    size_weight: float = 0.1
    focal_alpha: float = 2.0
    focal_beta: float = 4.0

    # distillation (ISSUE 13): teacher-student training for the small
    # tiers. --distill names a teacher checkpoint (dir or save dir); the
    # teacher runs INSIDE the jitted step under stop_gradient (fixed
    # shapes, composes with --grad-accum/--sentinel/bf16-compute) and its
    # last stack's heatmap/offset/size soft targets mix into the loss at
    # weight --distill-alpha. The soft-loss scalars ride the SAME
    # deferred loss fetch as every other loss component (zero extra D2H,
    # the --telemetry contract). Teacher architecture comes from the
    # checkpoint dir's argument.json snapshot, so a flagship teacher can
    # distill into any tier's student.
    distill: Optional[str] = None
    distill_alpha: float = 0.5

    # network
    tier: str = ""                # "" | edge | throughput | quality: named
    # latency-tier preset (ISSUE 13) — overrides the TIER_PRESETS fields
    # (variant/stacks/width/serving); see apply_tier
    variant: str = "residual"     # residual-block variant (MODEL_VARIANTS;
    # Lighter-Hourglass depthwise/ghost blocks, ISSUE 13). Checkpoint
    # param trees differ per variant — eval restores it from the snapshot
    # like num_stack.
    stem_width: int = 0           # PreLayer mid width; 0 = the reference's
    # fixed 128 (every pre-tier checkpoint keeps its exact graph). Tier
    # presets set it to the model width so narrow tiers don't carry a
    # flagship-width stem at full resolution. Architecture field (snapshot
    # restores it).
    scale_factor: int = 4        # structurally 4: PreLayer's stem downsample
    # is 2x conv + 2x pool (ref hourglass.py:163-165); unlike the reference
    # (which reads it in decode only and would silently mis-decode,
    # SURVEY §5 dead flags) any other value fails loudly in __post_init__
    num_cls: int = 2
    pretrained: str = "imagenet"  # selects normalization stats only (as ref)
    normalized_coord: bool = False
    num_stack: int = 1
    hourglass_inch: int = 128
    increase_ch: int = 0
    activation: str = "ReLU"
    pool: str = "Max"
    neck_activation: str = "ReLU"
    neck_pool: str = "None"

    # optimization
    lr: float = 5e-4
    optim: str = "Adam"
    lr_milestone: List[int] = field(default_factory=lambda: [50, 90])
    lr_gamma: float = 0.1

    # data-pipeline limits (TPU static shapes; no reference analogue)
    max_boxes: int = 128          # per-image GT padding for encode

    # kernels
    use_pallas: bool = True       # fused Pallas peak kernel on TPU decode

    # log
    print_interval: int = 100
    ckpt_interval: int = 1        # checkpoint every N epochs (final epoch
    # always saved); the reference saves every epoch (its train.py:76)
    keep_ckpt: int = 0            # retain only the newest N checkpoints of
    # THIS run (0 = keep all, the reference's behavior); never touches
    # checkpoints from other runs in the same save-path
    async_ckpt: bool = False      # overlap checkpoint D2H+write with the
    # next epoch's training (orbax AsyncCheckpointer). Single-host only;
    # transiently holds a second on-device copy of the train state, so
    # avoid when already at the HBM limit (e.g. --remat-sized configs)
    remat: str = "none"           # activation rematerialization policy:
    # "none" stores every activation; "stacks" recomputes each Hourglass
    # stack in backward (nn.remat per stack — the pre-r7 --remat boolean,
    # still accepted: True/False coerce to stacks/none); "full" wraps the
    # WHOLE forward in jax.checkpoint(nothing_saveable) — max HBM savings
    # (stem + neck + head activations too), max recompute. Trade FLOPs for
    # HBM: the lever that fits batch 32/64 @512^2 and num-stack=4 @768^2.
    # Numerically identical in all three modes (gradient-equality tested);
    # param tree unchanged, so checkpoints are interchangeable.
    loss_kernel: str = "auto"     # detection-loss implementation: "xla"
    # (ops/loss.py reference composition), "fused" (one-pass Pallas
    # sigmoid+focal+masked-L1 kernel with custom_vjp backward,
    # ops/pallas/loss.py), "auto" = fused on TPU, xla elsewhere (same
    # backend gating as the fused peak kernel). Off-TPU "fused" runs in
    # (slow) interpret mode — test/debug only.
    epilogue: str = "auto"        # conv BN+activation tail implementation:
    # "xla" (nn.BatchNorm + Activation, the pre-PR composition), "fused"
    # (one-pass BN-normalize+activation with a recompute backward,
    # ops/pallas/epilogue.py — Pallas on TPU, the jnp custom_vjp twin
    # elsewhere), "auto" = fused on TPU, xla elsewhere (the --loss-kernel
    # gating). Eligibility per conv: BN present and unfolded, activation
    # in {Mish, ReLU, Linear}, per-replica BN (sync-BN keeps xla) —
    # ineligible convs silently keep the xla tail. Checkpoints
    # interchange across modes (identical param tree, tested).
    block_fuse: str = "auto"      # residual-block TAIL implementation:
    # "xla" (per-conv epilogue + XLA skip-add + Activation, the pre-PR
    # composition), "fused" (the block's second BN, the skip-add and the
    # closing activation collapse into ONE custom_vjp pass family with
    # the analytic BN backward extended through the add,
    # ops/pallas/epilogue.py — Pallas on TPU, the jnp twin elsewhere),
    # "auto" = fused on TPU, xla elsewhere (ops/pallas/select.py decides
    # for all four kernel fields). Eligibility per block: residual/depthwise variants (ghost's tail
    # is a concat of two separately-normalized halves), per-replica
    # unfolded BN, no quantization, closing activation in {Mish, ReLU,
    # Linear} — ineligible blocks silently keep the xla tail. Param/stat
    # trees are IDENTICAL to today, so checkpoints interchange and
    # fold_batchnorm/int8 export apply unchanged (tested).
    fwd_dtype: str = "bf16"       # TRAIN-time forward conv compute dtype:
    # "bf16" (the --amp baseline) or "int8" — eligible convs (BN'd,
    # bias-free, unquantized, unfolded) run their train-mode forward as
    # int8 x int8 -> int32 via PR 5's quantization algebra with a
    # PER-STEP in-jit absmax scale refresh (no persisted scale state:
    # trees, donation and the D2H budget are unchanged), and a
    # straight-through-estimator backward differentiates the float conv
    # twin. v5e int8 peak is 2x bf16 (394 TOPS). Train-only: eval/
    # predict bind the same float params; composes with --grad-accum/
    # --sentinel/--distill. Gate on loss-curve parity vs the bf16 twin
    # exactly like bf16-compute was (tests/test_fwd_dtype.py).
    stem_s2d: bool = False        # compute the 7x7 s2 stem conv in its
    # space-to-depth formulation (same arithmetic, MXU-friendlier
    # contraction; checkpoint-compatible either way)
    hang_warn_seconds: float = 300.0  # watchdog: warn when no train step
    # completes for this long (0 disables). The reference has no failure
    # detection at all.
    ema_decay: float = 0.0        # keep an exponential moving average of
    # the params inside the jitted step (0 disables); a capability the
    # reference lacks. Helps only when decay matches the training budget
    # (measured both ways on the same 256^2 setup: 0.998 -> -3.2 mAP,
    # 0.99 -> +0.45; builders' r04 calibration runs): pick the decay so the
    # averaging window fits inside the final-LR phase.
    ema_eval: bool = False        # evaluate/demo/export with the EMA
    # weights from the checkpoint (requires a --ema-decay training run)
    prewarm: bool = False         # compile every multiscale bucket before
    # epoch 0 (device-augment paths): each bucket's first XLA compile
    # otherwise stalls a mid-epoch step for the length of that compile
    async_eval: bool = False      # evaluate each saved checkpoint OFF the
    # training devices (ISSUE 11): the chief spawns ONE background eval
    # subprocess per checkpoint boundary, pinned to the CPU platform, on
    # the checkpoint just written — training never stalls for eval (a
    # busy evaluator skips a boundary rather than queueing). Results land
    # in save-path/eval_async/e<N>/scores.json; train() reaps finished
    # evals at each boundary and awaits the last one at exit. Single-host
    # chief only. The reference has no in-training eval at all (its
    # train/eval are separate invocations, ref main.py:9-17).
    auto_resume: int = 0          # elastic recovery: on a transient backend
    # failure, back off, probe the device, re-stage device-held state
    # (RNG key, HBM cache if lost), restore the newest checkpoint in
    # save-path and continue in-process, up to N times (0 disables;
    # single-host only). Scope: TRANSPORT-transient failures — the PJRT
    # client cannot be rebuilt in-process, so a dead backend aborts with
    # advice to restart with --model-load. The reference's only recovery
    # is a manual restart (its train.py:190).
    resume_backoff_s: float = 15.0  # auto-resume backoff base: attempt k
    # sleeps min(300, k * this) before probing the device (tests use a
    # near-zero value; a real transport blip needs the full pause)
    fault_inject: str = ""        # debug: "EPOCH:ITER" raises one synthetic
    # transient backend error at that step, to exercise --auto-resume
    sentinel: bool = False        # self-healing numerics (ISSUE 9): a
    # fixed-shape NaN/Inf + grad-norm-spike check computed INSIDE the
    # jitted step; a tripped step is SKIPPED in-jit (the whole TrainState
    # — params, optimizer moments, batch stats, EMA — keeps its pre-step
    # value, so one poison batch cannot contaminate a run) and the
    # sentinel scalars ride the SAME deferred loss fetch (zero extra D2H,
    # the --telemetry contract). The host-side SentinelMonitor backs the
    # loss scale off after bad flush windows and triggers an automatic
    # rollback to the last good checkpoint on sustained divergence. Off
    # (the default) traces the exact pre-PR step program (bit-identity
    # pinned by tests/test_sentinel.py). The reference has no numeric
    # failure handling at all (a NaN poisons the run silently).
    sentinel_spike: float = 0.0   # grad-norm spike threshold: an
    # otherwise-finite step whose global grad norm exceeds this is also
    # skipped (0 disables the spike check — NaN/Inf only). Calibrate from
    # the telemetry grad_norm history of a healthy run (obs_report).
    sentinel_backoff: float = 0.5  # loss-scale multiplier applied after a
    # flush window containing skipped steps (recovers x2 per clean window,
    # capped at 1.0, floored at 1/1024); 1.0 disables the backoff.
    sentinel_divergence: int = 3  # consecutive skipped steps that count as
    # sustained divergence -> rollback to the last good checkpoint
    sentinel_rollbacks: int = 2   # automatic rollback budget per run (0
    # disables rollback; the sentinel then only skips and backs off)
    save_path: str = "./WEIGHTS/"
    profile: bool = False         # jax.profiler trace of early train steps
    telemetry: bool = False       # in-jit step telemetry (obs/telemetry.py):
    # grad/update/param global norms computed INSIDE the jitted step and
    # fetched in the SAME D2H as the loss scalars (deferred flush / the
    # scanned telemetry ring) — zero extra D2H. Off (the
    # default) traces the exact pre-telemetry program: loss bit-identical
    # (tested). The reference has no analogue (it logs only its four loss
    # scalars, ref train.py:104-140).
    span_log: str = ""            # flight-recorder span log (obs/spans.py):
    # path to a JSONL file recording loader-wait/h2d/dispatch/fetch/
    # checkpoint/compile spans + host-context samples in train and eval.
    # "" = $OBS_SPAN_LOG when exported (the job supervisor sets it for
    # every queued job), else disabled (zero cost). Read it back with
    # scripts/obs_report.py.
    summary: bool = True          # print a layer table at train start on
    # the chief (≡ reference torchsummary on rank 0, ref train.py:50;
    # --no-summary disables). Shape inference only — no device compute.

    def __post_init__(self):
        # pre-r7 compatibility: --remat was a boolean (Config(remat=True)
        # in sweeps/tests); coerce to the policy vocabulary
        if isinstance(self.remat, bool):
            self.remat = "stacks" if self.remat else "none"
        if self.remat not in ("none", "stacks", "full"):
            raise ValueError("--remat must be one of none|stacks|full, "
                             "got %r" % (self.remat,))
        if self.loss_kernel not in ("auto", "fused", "xla"):
            raise ValueError("--loss-kernel must be one of auto|fused|xla, "
                             "got %r" % (self.loss_kernel,))
        if self.epilogue not in ("auto", "fused", "xla"):
            raise ValueError("--epilogue must be one of auto|fused|xla, "
                             "got %r" % (self.epilogue,))
        if self.block_fuse not in ("auto", "fused", "xla"):
            raise ValueError("--block-fuse must be one of auto|fused|xla, "
                             "got %r" % (self.block_fuse,))
        if self.fwd_dtype not in ("bf16", "int8"):
            raise ValueError("--fwd-dtype must be 'bf16' or 'int8', "
                             "got %r" % (self.fwd_dtype,))
        if self.param_policy not in ("fp32", "bf16-compute"):
            raise ValueError("--param-policy must be 'fp32' or "
                             "'bf16-compute', got %r" % (self.param_policy,))
        if self.param_policy == "bf16-compute":
            if not self.amp:
                raise ValueError(
                    "--param-policy bf16-compute requires --amp: without "
                    "the bf16 compute policy the once-cast params would "
                    "silently change the compute dtype itself")
            if self.sub_divisions > 1:
                raise ValueError(
                    "--param-policy bf16-compute is incompatible with "
                    "--sub-divisions > 1: optax.MultiSteps would "
                    "accumulate micro-gradients in bf16 — keep the fp32 "
                    "policy for accumulation runs")
        if self.grad_accum < 1:
            raise ValueError("--grad-accum must be >= 1, got %d"
                             % self.grad_accum)
        if self.grad_accum > 1:
            if self.batch_size % self.grad_accum:
                raise ValueError(
                    "--grad-accum %d must divide --batch-size %d (equal "
                    "fixed-shape micro-batches under jit)"
                    % (self.grad_accum, self.batch_size))
            if self.device_augment:
                raise ValueError(
                    "--grad-accum > 1 is host-input-path only: the fused "
                    "--device-augment step augments per batch and has no "
                    "micro-batch scan")
        if self.family not in MODEL_FAMILIES:
            raise ValueError("--family must be one of %s, got %r"
                             % (MODEL_FAMILIES, self.family))
        if self.variant not in MODEL_VARIANTS:
            raise ValueError("--variant must be one of %s, got %r"
                             % (MODEL_VARIANTS, self.variant))
        if self.tier and self.tier not in TIER_PRESETS:
            raise ValueError("--tier must be '' or one of %s, got %r"
                             % (sorted(TIER_PRESETS), self.tier))
        if not self.distill_alpha > 0:
            raise ValueError("--distill-alpha must be > 0, got %r"
                             % (self.distill_alpha,))
        if self.stem_width < 0:
            raise ValueError("--stem-width must be >= 0 (0 = the "
                             "reference's 128), got %d" % self.stem_width)
        if self.infer_dtype not in ("bf16", "int8"):
            raise ValueError("--infer-dtype must be 'bf16' or 'int8', "
                             "got %r" % (self.infer_dtype,))
        if self.calib_batches < 1:
            raise ValueError("--calib-batches must be >= 1, got %d"
                             % self.calib_batches)
        if not 0.0 < self.calib_percentile <= 100.0:
            raise ValueError("--calib-percentile must be in (0, 100], "
                             "got %r" % (self.calib_percentile,))
        if not self.serve_buckets or any(int(b) < 1
                                         for b in self.serve_buckets):
            raise ValueError("--serve-buckets must be a non-empty list of "
                             "positive batch sizes, got %r"
                             % (self.serve_buckets,))
        if self.serve_max_wait_ms < 0:
            raise ValueError("--serve-max-wait-ms must be >= 0, got %r"
                             % (self.serve_max_wait_ms,))
        if self.serve_depth < 1:
            raise ValueError("--serve-depth must be >= 1, got %d"
                             % self.serve_depth)
        if self.serve_queue < 1:
            raise ValueError("--serve-queue must be >= 1, got %d"
                             % self.serve_queue)
        if self.serve_max_retries < 0:
            raise ValueError("--serve-max-retries must be >= 0, got %d"
                             % self.serve_max_retries)
        if self.serve_hang_timeout_ms < 0:
            raise ValueError("--serve-hang-timeout-ms must be >= 0, got %r"
                             % (self.serve_hang_timeout_ms,))
        if self.cascade:
            if (len(self.cascade_tiers) != 2
                    or self.cascade_tiers[0] == self.cascade_tiers[1]):
                raise ValueError(
                    "--cascade-tiers must name two distinct tiers "
                    "(edge-hop first), got %r" % (self.cascade_tiers,))
            bad = [t for t in self.cascade_tiers if t not in TIER_PRESETS]
            if bad:
                raise ValueError(
                    "--cascade-tiers must be named tier presets %s, got %r"
                    % (sorted(TIER_PRESETS), self.cascade_tiers))
        if self.cascade_threshold is not None \
                and not math.isfinite(self.cascade_threshold):
            raise ValueError("--cascade-threshold must be finite, got %r"
                             % (self.cascade_threshold,))
        if self.stream_tile_grid < 1:
            raise ValueError("--stream-tile-grid must be >= 1, got %d"
                             % self.stream_tile_grid)
        if self.stream_threshold is not None \
                and not math.isfinite(self.stream_threshold):
            raise ValueError("--stream-threshold must be finite, got %r"
                             % (self.stream_threshold,))
        if not 0.0 <= self.stream_ema < 1.0:
            raise ValueError("--stream-ema must be in [0, 1), got %r"
                             % (self.stream_ema,))
        if self.sentinel_spike < 0:
            raise ValueError("--sentinel-spike must be >= 0, got %r"
                             % (self.sentinel_spike,))
        if not 0.0 < self.sentinel_backoff <= 1.0:
            raise ValueError("--sentinel-backoff must be in (0, 1], got %r"
                             % (self.sentinel_backoff,))
        if self.sentinel_divergence < 1:
            raise ValueError("--sentinel-divergence must be >= 1, got %d"
                             % self.sentinel_divergence)
        if self.sentinel_rollbacks < 0:
            raise ValueError("--sentinel-rollbacks must be >= 0, got %d"
                             % self.sentinel_rollbacks)
        if self.loader not in ("thread", "process"):
            raise ValueError("--loader must be 'thread' or 'process', got %r"
                             % self.loader)
        if self.device_prefetch < 0:
            raise ValueError("--device-prefetch must be >= 0, got %d"
                             % self.device_prefetch)
        if self.scale_factor != 4:
            raise ValueError(
                "--scale_factor must be 4: the stem's 4x downsample is "
                "structural (ref hourglass.py:163-165); other values would "
                "mis-size the encoded GT maps vs the network output")


def build_parser() -> argparse.ArgumentParser:
    """Generate the argparse parser from `Config`'s fields."""
    parser = argparse.ArgumentParser(
        description="TPU-native real-time helmet detection framework")
    for f in dataclasses.fields(Config):
        flag = "--" + f.name.replace("_", "-")
        default = (f.default_factory() if f.default_factory is not dataclasses.MISSING
                   else f.default)
        if isinstance(default, dict):
            continue  # a mapping comes from a file, not from the CLI
        if f.type in ("bool", bool):
            # BooleanOptionalAction adds --no-<flag>, so default-True bools
            # (e.g. --use-pallas) can actually be switched off from the CLI
            parser.add_argument(flag, action=argparse.BooleanOptionalAction,
                                default=default)
        elif isinstance(default, list):
            elem = type(default[0]) if default else str
            parser.add_argument(flag, type=elem, nargs="+", default=default)
        elif f.type in ("Optional[int]",):
            parser.add_argument(flag, type=int, default=default)
        elif f.type in ("Optional[float]",):
            parser.add_argument(flag, type=float, default=default)
        elif f.type in ("Optional[str]",):
            parser.add_argument(flag, type=str, default=default)
        else:
            parser.add_argument(flag, type=type(default), default=default)
    # reference-compat aliases
    parser.add_argument("--multiscale_flag", dest="multiscale_flag",
                        action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--scale_factor", dest="scale_factor", type=int,
                        help=argparse.SUPPRESS)
    return parser


def parse_args(argv=None) -> Config:
    ns = build_parser().parse_args(argv)
    d = vars(ns)
    return Config(**{f.name: d[f.name] for f in dataclasses.fields(Config)
                     if f.name in d})


def cascade_overrides(repo_root: Optional[str] = None) -> dict:
    """Calibrated cascade operating point from the newest committed
    `quality_matrix --cascade` artifact (the committed artifact IS the
    promotion record, highest round wins).

    Scans artifacts/*/cascade.json for a `selected` record (threshold +
    the escalation-rate/blended-mAP evidence it was chosen on) and maps
    it onto `cascade_threshold`. Raises FileNotFoundError when no
    artifact carries a selection (a fresh clone, or no calibration round
    yet) — passing --cascade-threshold explicitly sidesteps the scan."""
    import glob
    import re
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    best = None
    for path in glob.glob(os.path.join(root, "artifacts", "*",
                                       "cascade.json")):
        try:
            with open(path) as f:
                rec = json.load(f).get("selected")
        except (OSError, json.JSONDecodeError):
            continue
        if not rec or "threshold" not in rec:
            continue
        m = re.search(r"r(\d+)",
                      os.path.basename(os.path.dirname(path)))
        key = int(m.group(1)) if m else -1
        if best is None or key > best[0]:
            best = (key, path, rec)
    if best is None:
        raise FileNotFoundError(
            "--cascade: no artifacts/*/cascade.json carries a selected "
            "operating point — run `quality_matrix --cascade` first, or "
            "pass --cascade-threshold explicitly")
    _, path, rec = best
    return {"cascade_threshold": float(rec["threshold"]),
            "_source": os.path.relpath(path, root)}


def apply_cascade(cfg: Config) -> Config:
    """Resolve `--cascade` with no explicit threshold into the calibrated
    operating point (no-op when cascade is off or a threshold was
    passed)."""
    if not cfg.cascade or cfg.cascade_threshold is not None:
        return cfg
    over = cascade_overrides()
    src = over.pop("_source")
    print("--cascade: %s -> %s" % (src, over), flush=True)
    return dataclasses.replace(cfg, **over)


def stream_overrides(repo_root: Optional[str] = None) -> dict:
    """Calibrated tile-skip operating point from the newest committed
    `quality_matrix --streams` artifact (same promotion idiom as
    cascade_overrides: the committed artifact IS the record, highest
    round wins).

    Scans artifacts/*/streams.json for a `selected` record (threshold +
    the skip-rate/blended-mAP evidence it was chosen on) and maps it
    onto `stream_threshold`. Raises FileNotFoundError when no artifact
    carries a selection — passing --stream-threshold explicitly
    sidesteps the scan."""
    import glob
    import re
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    best = None
    for path in glob.glob(os.path.join(root, "artifacts", "*",
                                       "streams.json")):
        try:
            with open(path) as f:
                rec = json.load(f).get("selected")
        except (OSError, json.JSONDecodeError):
            continue
        if not rec or "threshold" not in rec:
            continue
        m = re.search(r"r(\d+)",
                      os.path.basename(os.path.dirname(path)))
        key = int(m.group(1)) if m else -1
        if best is None or key > best[0]:
            best = (key, path, rec)
    if best is None:
        raise FileNotFoundError(
            "--stream: no artifacts/*/streams.json carries a selected "
            "operating point — run `quality_matrix --streams` first, or "
            "pass --stream-threshold explicitly")
    _, path, rec = best
    return {"stream_threshold": float(rec["threshold"]),
            "_source": os.path.relpath(path, root)}


def apply_streams(cfg: Config) -> Config:
    """Resolve `--stream` with no explicit threshold into the calibrated
    operating point (no-op when streaming is off or a threshold was
    passed)."""
    if not cfg.stream or cfg.stream_threshold is not None:
        return cfg
    over = stream_overrides()
    src = over.pop("_source")
    print("--stream: %s -> %s" % (src, over), flush=True)
    return dataclasses.replace(cfg, **over)


def apply_tier(cfg: Config) -> Config:
    """Resolve `--tier` into concrete Config fields (no-op when unset).

    The tier WINS over individually-passed architecture/serving flags —
    it is the "give me the edge product" button."""
    if not cfg.tier:
        return cfg
    over = TIER_PRESETS[cfg.tier]
    print("--tier %s: %s" % (cfg.tier, over), flush=True)
    return dataclasses.replace(cfg, **over)


def tier_of(cfg) -> str:
    """The tier name whose ARCHITECTURE fields (variant/stacks/width)
    match `cfg`, else "flagship" for the historical bench default
    (residual, 1 stack, width 128 — every pre-tier bench line parses as
    this) or "custom". Used by bench.py's arch fields; serving knobs
    deliberately don't participate (a bench overrides buckets freely)."""
    arch = (getattr(cfg, "variant", "residual"), cfg.num_stack,
            cfg.hourglass_inch)
    for name, over in TIER_PRESETS.items():
        if arch == (over["variant"], over["num_stack"],
                    over["hourglass_inch"]):
            return name
    if arch == ("residual", 1, 128):
        return "flagship"
    return "custom"


def seed_everything(seed: int) -> None:
    """Global seeding (ref config.py:143-147). JAX RNG is explicit
    (jax.random.key), threaded through the train/data code; host-side
    python/numpy randomness (augmentation sampling) is seeded here."""
    random.seed(seed)
    np.random.seed(seed)


def save_config(cfg: Config, save_path: str) -> None:
    """Persist `argument.txt` + `argument.json` (ref config.py:164-168)."""
    os.makedirs(save_path, exist_ok=True)
    from .utils import atomic_write_bytes, save_json
    d = dataclasses.asdict(cfg)
    txt = "".join("%s: %s\n" % (key, value) for key, value in
                  sorted(d.items()))
    atomic_write_bytes(os.path.join(save_path, "argument.txt"),
                       txt.encode())
    save_json(os.path.join(save_path, "argument.json"), d, indent=2,
              sort_keys=True)


def load_config(path: str) -> Config:
    """Load a JSON snapshot back into a Config (unknown keys ignored)."""
    with open(path) as f:
        d = json.load(f)
    names = {f.name for f in dataclasses.fields(Config)}
    return Config(**{k: v for k, v in d.items() if k in names})


def update_config_for_eval(cfg: Config, loaded: Config) -> Config:
    """Override the architecture fields from the training-time snapshot
    (ref config.py:171-179)."""
    return dataclasses.replace(
        cfg, **{k: getattr(loaded, k) for k in ARCHITECTURE_FIELDS})


def get_config(argv=None) -> Config:
    """Full CLI entry (ref config.py:139-169): parse, seed, snapshot dirs,
    eval-time architecture restore."""
    cfg = parse_args(argv)
    cfg = apply_tier(cfg)
    cfg = apply_cascade(cfg)
    cfg = apply_streams(cfg)
    seed_everything(cfg.random_seed)

    if cfg.platform:
        # must happen before the first backend init
        import jax
        jax.config.update("jax_platforms", cfg.platform)

    os.makedirs(cfg.save_path, exist_ok=True)
    if cfg.train_flag:
        os.makedirs(os.path.join(cfg.save_path, "training_log"), exist_ok=True)
    elif cfg.model_load:
        # a save DIR resolves to its newest complete checkpoint up front,
        # so the architecture-snapshot lookup below and every downstream
        # restore agree on the same path (local import: train.py imports
        # this module at its top)
        from .train import resolve_model_load
        cfg = dataclasses.replace(
            cfg, model_load=resolve_model_load(cfg.model_load))
        snap = os.path.join(os.path.dirname(cfg.model_load), "argument.json")
        if os.path.exists(snap):
            cfg = update_config_for_eval(cfg, load_config(snap))

    save_config(cfg, cfg.save_path)
    return cfg
