"""Fused prediction path: network -> sigmoid -> decode -> cross-stack NMS.

Capability parity with the reference `Prediction` module
(/root/reference/evaluate.py:114-180): per-batch-item, per-stack `hm2box`
decode with sigmoid, concatenation of all stacks' boxes, then one
class-agnostic NMS (hard `torchvision.ops.nms` or Gaussian soft-NMS) —
re-designed as a **single jitted function** with static shapes:

* the reference loops over batch items and stacks in Python on the host;
  here both axes are `vmap`ped, so the whole predict path (conv stacks,
  peak test, top-k, gather, NMS) compiles to ONE XLA program — this is the
  export artifact too (ref export.py traces the same composition);
* variable-length outputs (conf filtering at ref transform.py:108-110, NMS
  survivors) become a fixed `(B, num_stack * topk)` box set with a `valid`
  mask; hosts filter when writing files.

The decoder family (models/decoder.py; no reference analogue) has its own
program at the end of this file: `make_generate_fn` (prefill, then greedy
decode steps through the cache, one jitted call a batch), its answer
`Generation` and the counters that answer feeds (`generation_counters`).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import DECODER_FAMILIES, MODEL_FAMILIES
from .ops.decode import (CascadeDetections, Detections, confidence_summary,
                         decode_heatmap, decode_peak_scores)
from .ops.nms import maxpool_nms_mask, nms_mask, soft_nms_mask
from .ops.pallas import fused_peak_scores
from .ops.pallas.partition import batch_parallel
from .ops.pallas.select import kernel_plan


def make_predict_fn(model, cfg, normalize: str | None = None,
                    mesh=None, quant_scales=None,
                    cascade_summary: bool = False) -> Callable:
    """Build `predict(variables, images) -> Detections` (batched, jitted).

    images: (B, H, W, 3) normalized float32 — or, when `normalize` names a
    stats set ("imagenet"/"scratch"), raw un-normalized pixels (uint8 or
    float [0, 255]) that are cast + normalized INSIDE the program. The eval
    driver uses the latter so images cross the host->device boundary as
    uint8 (4x less traffic, same bits: the host path merely casts the
    augmentor's uint8 canvases before normalizing).

    `mesh`: optional `jax.sharding.Mesh` — the batch dim shards over its
    "data" axis (variables replicated), so evaluation data-parallelizes
    over every device. The reference's eval is single-GPU only
    (ref evaluate.py:16); this is the multi-chip eval path.

    `--infer-dtype int8` (cfg.infer_dtype; requires `quant_scales`, the
    calibrated activation-scales pytree from `ops.quant.calibrate_scales`
    / `load_scales`): the network runs the BN-folded int8-quantized twin —
    BN fold and weight quantization happen INSIDE the jitted program from
    the SAME checkpoint pytree, so `predict(variables, images)` keeps its
    signature and the artifact contract is "checkpoint + scales in".
    Decode/NMS always stay float. Eval/export only — training is never
    quantized (docs/ARCHITECTURE.md "Inference compression").

    `cascade_summary`: when True the program additionally computes the
    per-image cascade escalation confidence (`ops.decode.confidence_summary`
    over the final masked detections — masks, not filtering) and returns a
    `CascadeDetections`; the scalar rides the same output block so it adds
    ZERO extra D2H. When False (default) the traced program is bit-identical
    to the pre-cascade predict — the flag only ever ADDS a leaf.

    Returns `Detections` (or `CascadeDetections`) with leading batch dim and
    N = num_stack * topk entries per image; `valid` combines the conf
    threshold and the NMS keep mask.
    """
    if normalize is not None:
        from .utils import normalizer_stats
        norm_mean, norm_std = (jnp.asarray(s) for s in
                               normalizer_stats(normalize))
    num_cls = int(cfg.num_cls)
    topk = int(cfg.topk)
    conf_th = float(cfg.conf_th)
    nms_th = float(cfg.nms_th)
    scale_factor = int(cfg.scale_factor)
    pool_size = int(getattr(cfg, "pool_size", 3))
    if pool_size % 2 != 1 or pool_size < 1:
        # validate where the flag enters the pipeline: the XLA reduce_window
        # path would otherwise die with a cryptic shape error inside jit
        raise ValueError("pool_size must be odd and >= 1, got %d" % pool_size)
    normalized = bool(cfg.normalized_coord)
    use_soft = cfg.nms == "soft-nms"
    use_maxpool = cfg.nms == "maxpool"
    if cfg.nms not in ("nms", "soft-nms", "maxpool"):
        raise NotImplementedError("Not expected nms algorithm: %s" % cfg.nms)
    use_pallas = kernel_plan(cfg)["peak"] == "fused"
    imsize = int(cfg.imsize or 512)  # maxpool-NMS grid extent (static)

    infer_dtype = getattr(cfg, "infer_dtype", "bf16")
    if infer_dtype not in ("bf16", "int8"):
        raise NotImplementedError("Not expected infer dtype: %s"
                                  % infer_dtype)
    if infer_dtype == "int8":
        if quant_scales is None:
            raise ValueError(
                "--infer-dtype int8 needs calibrated activation scales: "
                "pass quant_scales (ops.quant.calibrate_scales or "
                "load_scales of a saved artifact)")
        from .ops.quant import fold_batchnorm, make_quant_model
        qmodel = make_quant_model(cfg, dtype=model.dtype, mode="int8")
        scales = jax.tree.map(jnp.asarray, quant_scales)

    def peak_scores(logits: jax.Array) -> jax.Array:
        """(B, S, H, W, num_cls) heatmap logits -> masked sigmoid peak
        scores, the fused kernel over every (image, stack) map — per
        batch shard under a mesh."""
        per_map = jax.vmap(jax.vmap(
            lambda x: fused_peak_scores(x, pool_size=pool_size)))
        return batch_parallel(per_map, [True])(logits)

    def decode_one(o: jax.Array, peaks=None) -> Detections:
        """One stack of one image: (H, W, num_cls+4) raw (and, on the
        Pallas path, its (H, W, num_cls) peak scores) -> Detections."""
        offset = o[..., num_cls:num_cls + 2]
        wh = o[..., num_cls + 2:num_cls + 4]
        if normalized:
            offset = jax.nn.sigmoid(offset)
            wh = jax.nn.sigmoid(wh)
        if peaks is not None:
            return decode_peak_scores(peaks, offset, wh,
                                      scale_factor=scale_factor, topk=topk,
                                      conf_th=conf_th, normalized=normalized)
        heat = jax.nn.sigmoid(o[..., :num_cls])
        return decode_heatmap(heat, offset, wh, scale_factor=scale_factor,
                              topk=topk, conf_th=conf_th,
                              normalized=normalized, pool_size=pool_size)

    def suppress(boxes, scores, valid):
        """Cross-stack class-agnostic NMS (ref evaluate.py:155-163, 167-180)."""
        if use_maxpool:
            # PSRR-MaxpoolNMS-style parallel suppression (ops/nms.py):
            # no sort, no serial greedy chain — approximate parity with
            # `nms` (agreement-rate tested, not exactness)
            keep = maxpool_nms_mask(boxes, scores, valid, extent=float(imsize))
            return keep, scores
        if use_soft:
            # score_th = conf_th matches the reference CALL SITE, which
            # overrides soft_nms_pytorch's 0.001 default with the --conf-th
            # flag: `soft_nms_pytorch(boxes, scores, thresh=self.conf_th)`
            # (ref evaluate.py:177 vs the :184 signature default). With eval
            # defaults (conf_th 0.0) the reference drops nothing either;
            # tests/test_nms.py pins the full decay recurrence against a
            # sequential oracle port of ref evaluate.py:184-243.
            keep, new_scores = soft_nms_mask(boxes, scores, valid,
                                             score_th=conf_th)
            return keep, new_scores
        keep = nms_mask(boxes, scores, valid, nms_th)
        return keep, scores

    def predict_impl(variables, images: jax.Array) -> Detections:
        # `jax.named_scope`s: flax names the network's modules; these name
        # the plain functions around it, so that the compiled program's
        # metadata says which layer owns each instruction
        # (obs/hlo_scopes.py)
        if normalize is not None:
            with jax.named_scope("normalize"):
                images = (images.astype(jnp.float32) / 255.0 - norm_mean) \
                    / norm_std
        if infer_dtype == "int8":
            # BN fold + per-channel weight quantization run INSIDE the
            # program from the training checkpoint (O(params) once per
            # dispatch, fused by XLA); the calibrated activation scales
            # ride along as the `quant` collection
            folded = fold_batchnorm(variables["params"],
                                    variables["batch_stats"])
            out = qmodel.apply({"params": folded, "quant": scales},
                               images, train=False)
        else:
            out = model.apply(variables, images, train=False)
        # (B, S, H, W, C+4)
        b, s = out.shape[0], out.shape[1]
        with jax.named_scope("peak"):
            peaks = peak_scores(out[..., :num_cls]) if use_pallas else None
        with jax.named_scope("decode"):
            dets = jax.vmap(jax.vmap(decode_one))(out, peaks)
            # (B, S, topk, ...)
            boxes = dets.boxes.reshape(b, s * topk, 4)
            classes = dets.classes.reshape(b, s * topk)
            scores = dets.scores.reshape(b, s * topk)
            valid = dets.valid.reshape(b, s * topk)
        with jax.named_scope("nms"):
            keep, scores = jax.vmap(suppress)(boxes, scores, valid)
            valid = keep & valid
        if cascade_summary:
            conf = jax.vmap(confidence_summary)(scores, valid)
            return CascadeDetections(boxes=boxes, classes=classes,
                                     scores=scores, valid=valid,
                                     confidence=conf)
        return Detections(boxes=boxes, classes=classes, scores=scores,
                          valid=valid)

    if mesh is None:
        return jax.jit(predict_impl)
    from .parallel import batch_sharding, replicated, under_kernel_mesh
    predict_impl = under_kernel_mesh(predict_impl, mesh)
    if cascade_summary:
        out_sh = CascadeDetections(boxes=batch_sharding(mesh, 3),
                                   classes=batch_sharding(mesh, 2),
                                   scores=batch_sharding(mesh, 2),
                                   valid=batch_sharding(mesh, 2),
                                   confidence=batch_sharding(mesh, 1))
        return jax.jit(predict_impl,
                       in_shardings=(replicated(mesh),
                                     batch_sharding(mesh, 4)),
                       out_shardings=out_sh)
    out_sh = Detections(boxes=batch_sharding(mesh, 3),
                        classes=batch_sharding(mesh, 2),
                        scores=batch_sharding(mesh, 2),
                        valid=batch_sharding(mesh, 2))
    return jax.jit(predict_impl,
                   in_shardings=(replicated(mesh), batch_sharding(mesh, 4)),
                   out_shardings=out_sh)


# ---- the decoder families' generate program --------------------------------------

class Generation(NamedTuple):
    """What one generate call answers, rows first (the engine slices rows).
    `expert_tokens`: pairs routed to each held expert by expert layer, over
    every position of the row (prompt and fed-back tokens, padding excluded);
    `keys_kept` / `keys_causal`: keys the indexer kept / keys causal (what a
    full layer's queries may read), the full layers summed; `q_blocks_run` /
    `q_blocks_total`: q blocks of prefill attention the program ran (a block
    past the row's length is a branch not taken) / that the padded row
    holds, the attention layers summed; `q_blocks_fused`: those of
    `q_blocks_run` that the fused attention kernel ran (`attn_fused`: the
    window-less per-head layers where Mosaic compiles, zeros elsewhere);
    `attn_fused_visits`: the (q block, key block) visits the kernel did
    work in for those blocks (its own visit table, key blocks of up to
    1,024: a q block's visits follow where it sits), the fused layers
    summed;
    `expert_visits`: for each prefill or step and expert layer, the experts
    that had at least one pair, credited to the lowest row that routed there
    (rows add up to the batch's count: what part of the experts' weights the
    batch streamed); `cache_slots_read` / `cache_keys_real`: cache slots the
    row's decode steps read and the real keys among them, by layer kind
    (full, sliding); `group_hits` / `group_slots`: under a router whose
    choice is limited by groups, the (position, expert layer) slots where a
    group this share holds was among the groups kept / all such slots;
    `linear_state_steps`: the linear-attention layers' state
    read-modify-writes (decode steps x such layers); `linear_chunks_run` /
    `linear_chunks_live` / `linear_chunks_total`: chunks of their prefill
    that ran for the row (a pass of rows runs to its longest row; a chunk
    past that is a branch not taken) / that the row's own tokens fill / that
    the padded row holds, such layers summed. A count the
    program does not have is zeros (the
    grouped-query family's indexer, a router without groups, a family
    without linear attention; a latent family's caches behind an indexer or
    a window: only its full layers WITHOUT an indexer, which read every slot
    and may attend every causal key, count slots and keys)."""
    tokens: jax.Array          # int32 (B, N)
    logits_first: jax.Array    # float32 (B, V): at the prompt's last token
    logits_last: jax.Array     # float32 (B, V): the step that gave token N
    expert_tokens: jax.Array   # int32 (B, expert layers, held)
    keys_kept: jax.Array       # int32 (B,)
    keys_causal: jax.Array     # int32 (B,)
    prompt_len: jax.Array      # int32 (B,): the length the row stated
    q_blocks_run: jax.Array    # int32 (B,)
    q_blocks_total: jax.Array  # int32 (B,)
    q_blocks_fused: jax.Array  # int32 (B,)
    attn_fused_visits: jax.Array  # int32 (B,)
    expert_visits: jax.Array     # int32 (B,)
    cache_slots_read: jax.Array  # int32 (B, 2): full, sliding
    cache_keys_real: jax.Array   # int32 (B, 2)
    group_hits: jax.Array        # int32 (B,)
    group_slots: jax.Array       # int32 (B,)
    linear_state_steps: jax.Array   # int32 (B,)
    linear_chunks_run: jax.Array    # int32 (B,)
    linear_chunks_live: jax.Array   # int32 (B,)
    linear_chunks_total: jax.Array  # int32 (B,)


def make_generate_fn(model, cfg, new_tokens: int) -> Callable:
    """Build `generate(variables, payload int32 (B, P_max + 1)) ->
    Generation` (batched, jitted): one call a batch, as `make_predict_fn` is
    for the detector (no reference analogue: ref evaluate.py has no
    generation). A payload row is `[length, ids..., padding]`. Prefill over
    P_max with per-row lengths, then `new_tokens - 1` greedy decode steps in
    one `lax.scan` through the cache at per-row positions; the last step's
    logits are the ones that gave token `new_tokens`."""
    new_tokens = int(new_tokens)
    if new_tokens < 1:
        raise ValueError("new_tokens must be >= 1, got %d" % new_tokens)
    if getattr(cfg, "family", "hourglass") not in DECODER_FAMILIES:
        raise ValueError("make_generate_fn serves the families %s (of %s), "
                         "got %r" % (", ".join(DECODER_FAMILIES),
                                     ", ".join(MODEL_FAMILIES),
                                     getattr(cfg, "family", None)))

    def generate(variables, payload):
        lengths = jnp.clip(payload[:, 0], 1, payload.shape[1] - 1)
        logits, cache = model.apply(variables, payload[:, 1:], lengths,
                                    new_tokens - 1, method="prefill")
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def step(carry, _):
            token, pos, cache, _ = carry
            logits, cache = model.apply(variables, token, pos, cache,
                                        method="step")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, cache, logits), nxt

        (_, _, cache, last), rest = jax.lax.scan(
            step, (first, lengths, cache, logits), None,
            length=new_tokens - 1)
        counts = cache["counts"]
        return Generation(
            tokens=jnp.concatenate([first[:, None], rest.T], axis=1),
            logits_first=logits, logits_last=last,
            expert_tokens=counts["expert_tokens"],
            keys_kept=counts["keys_kept"], keys_causal=counts["keys_causal"],
            prompt_len=lengths, q_blocks_run=counts["q_blocks_run"],
            q_blocks_total=counts["q_blocks_total"],
            q_blocks_fused=counts["q_blocks_fused"],
            attn_fused_visits=counts["attn_fused_visits"],
            expert_visits=counts["expert_visits"],
            cache_slots_read=jnp.stack([counts["slots_full"],
                                        counts["slots_window"]], axis=1),
            cache_keys_real=jnp.stack([counts["keys_full"],
                                       counts["keys_window"]], axis=1),
            group_hits=counts["group_hits"],
            group_slots=counts["group_slots"],
            linear_state_steps=counts["linear_state_steps"],
            linear_chunks_run=counts["linear_chunks_run"],
            linear_chunks_live=counts["linear_chunks_live"],
            linear_chunks_total=counts["linear_chunks_total"])

    return jax.jit(generate)


def generation_counters(p_max: int) -> Callable:
    """`row_counters` for `ServingEngine` over `make_generate_fn`'s answers:
    what a fetched batch's real rows add to the `gen.*` counters (host
    arithmetic on the fetch thread, after the one D2H)."""
    def counters(rows: Generation) -> dict:
        n = len(rows.prompt_len)
        prompt = int(np.sum(rows.prompt_len))
        out = {"gen.requests": n, "gen.prompt_tokens": prompt,
               "gen.padded_prompt_tokens": n * int(p_max) - prompt,
               "gen.new_tokens": int(rows.tokens.shape[0] * rows.tokens.shape[1]),
               "gen.keys_kept": int(np.sum(rows.keys_kept, dtype=np.int64)),
               "gen.keys_causal": int(np.sum(rows.keys_causal,
                                             dtype=np.int64)),
               "gen.q_blocks_run": int(np.sum(rows.q_blocks_run)),
               "gen.q_blocks_total": int(np.sum(rows.q_blocks_total)),
               "gen.q_blocks_fused": int(np.sum(rows.q_blocks_fused)),
               "gen.attn_fused_visits": int(np.sum(rows.attn_fused_visits)),
               # one call is one batch: every expert layer is passed once
               # by the prefill and once by each step after the first token
               "gen.expert_passes": int(rows.expert_tokens.shape[1]
                                        * rows.tokens.shape[1]),
               "gen.expert_visits": int(np.sum(rows.expert_visits)),
               "gen.group_hits": int(np.sum(rows.group_hits)),
               "gen.group_slots": int(np.sum(rows.group_slots)),
               "gen.linear_state_steps": int(np.sum(rows.linear_state_steps)),
               "gen.linear_chunks_run": int(np.sum(rows.linear_chunks_run)),
               "gen.linear_chunks_live": int(np.sum(rows.linear_chunks_live)),
               "gen.linear_chunks_total": int(np.sum(
                   rows.linear_chunks_total))}
        for j, kind in enumerate(("full", "window")):
            out["gen.cache_slots." + kind] = int(np.sum(
                rows.cache_slots_read[:, j], dtype=np.int64))
            out["gen.cache_keys." + kind] = int(np.sum(
                rows.cache_keys_real[:, j], dtype=np.int64))
        by_expert = np.sum(rows.expert_tokens, axis=(0, 1), dtype=np.int64)
        for e, pairs in enumerate(by_expert):
            out["gen.expert_pairs.e%02d" % e] = int(pairs)
        return out
    return counters
