"""The comparison that decides `correct`: the numbers compared, and the
judgement of each against its limit. Pure numpy on what the timed path produced
and what the plain reference says; used by the harness (every run), by
benchmark/calibrate.py (the readings the limits were set from) and by the
tests (the control and the planted faults).

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from .reference import model as ref


# ---- training --------------------------------------------------------------

def _worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                    leaves: List[str]) -> float:
    """max over leaves of |‖got‖ - ‖want‖| / max(‖want‖, median ‖want‖): the
    gap between the two norms (not the norm of a difference), against the
    reference's norm of that leaf or of the median leaf, whichever is larger,
    since some gradients are all but zero."""
    median = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], median, 1e-30)
               for k in leaves)


def worst_leaves(got: Dict[str, float], want: Dict[str, float],
                 leaves: Optional[List[str]] = None, n: int = 3):
    """[(leaf, gap, got norm, reference norm)] of the `n` worst leaves, and
    the median reference norm: what a look at a wide gap starts from."""
    median = float(np.median(list(want.values())))
    rows = [(k, abs(got[k] - want[k]) / max(want[k], median, 1e-30), got[k],
             want[k]) for k in (leaves or sorted(want))]
    return sorted(rows, key=lambda r: -r[1])[:n], median


def moved_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is at least a thousandth of the median
    leaf's. The others (a conv bias in front of a BatchNorm has gradient 0 to
    rounding: 7e-8 against a median of 3e-3 on the chip) move under Adam by
    round-off alone and are left out of the parameters' change; and out of
    the gradient's norm, where bfloat16 round-off alone gives the program
    5e-4..7e-4 on that one leaf, a fifth of the MEDIAN leaf's norm, on every
    seed (PERF.md section 2 has the look)."""
    median = float(np.median(list(ref_grad_norms.values())))
    return [k for k, v in ref_grad_norms.items() if v >= 1e-3 * median]


def train_numbers(got: dict, want: dict) -> Dict[str, float]:
    """`got` / `want`: {'losses': [l1, l2, l3], 'grad_norms': {leaf: norm of
    the first step's gradient}, 'change_norms': {leaf: norm of the
    parameters' change after the last step}} of the timed path and of the
    reference."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        out["loss_gap_step%d" % (i + 1)] = abs(a - b) / abs(b)
    # both over the leaves that the reference moves (see `moved_leaves`)
    leaves = moved_leaves(want["grad_norms"])
    out["grad_norm_gap"] = _worst_leaf_gap(got["grad_norms"],
                                           want["grad_norms"], leaves)
    out["change_norm_gap"] = _worst_leaf_gap(
        got["change_norms"], want["change_norms"], leaves)
    return out


MATCH_SLACK_PX = 2.0


# ---- serving ---------------------------------------------------------------

def serve_numbers(cfg: dict, served: list, maps: list) -> Dict[str, float]:
    """`served[i]`: what the engine answered for sampled request i (boxes
    (N, 4), classes (N,), scores (N,), valid (N,), N = stacks * topk, stack
    of entry j = j // topk). `maps[i]`: the reference's dense maps of the same
    image (`reference.model.dense_maps`, one image).

    Each served detection is matched to the reference cell whose box is
    nearest (largest coordinate difference, pixels; ties within a pixel go to
    the cell the reference scores highest); the reference then says
    what that cell's score is, whether it is a peak and in the top k, and,
    run over the matched cells, what NMS keeps.

    * box_gap_px: widest distance from a served box to the nearest reference
      cell's box (network regression heads, decode, bucket padding: a row of
      another request, of padding, or an altered box matches nothing).
    * select_gap_p99: how far a served cell's reference score lies under the
      reference's k-th best peak, or under its own 3x3 neighbourhood's
      maximum (sigmoid, peak test, top-k): the 99th percentile over the
      sampled detections.
    * score_gap_p99 (hard NMS, whose served scores are the undecayed ones):
      |served score - reference score| at the matched cell, 99th percentile.
    * final_score_gap_mean: mean |served score x served valid - reference
      score x reference keep| with the reference's NMS run over the matched
      cells: greedy NMS at the configured IoU, or the Gaussian soft-NMS
      recurrence, whose decayed scores are what the engine serves.

    Percentiles and a mean, not maxima, for the scores: over a dozen seeds
    the widest score gap of sound runs read 0.03..0.29 (one detection among
    3,200 whose peak sits next to a near-tie) and soft-NMS's 0.6..0.95 (two
    near-equal scores taken in the other order decay each other the other
    way round) while the control's read 0.28..0.91: no limit separates them.
    The 99th percentiles and the mean are steady to 2x and stand 4x..12x
    under the control's (PERF.md section 2 gives the readings).
    """
    topk = int(cfg.get("topk", 100))
    soft = cfg.get("nms", "nms") == "soft-nms"
    box_gaps, select_gaps, score_gaps, final_gaps = [], [], [], []
    for det, m in zip(served, maps):
        boxes = np.asarray(det.boxes, np.float32)
        n = boxes.shape[0]
        ref_boxes = np.empty((n, 4), np.float32)
        ref_scores = np.empty((n,), np.float32)
        for s in range(n // topk):
            rows = slice(s * topk, (s + 1) * topk)
            cells = m["boxes"][s].reshape(-1, 4)
            dist = np.abs(boxes[rows, None, :] - cells[None]).max(axis=-1)
            cls = np.asarray(det.classes[rows])
            scores = m["score"][s].reshape(-1, m["score"].shape[-1])
            # Among 16,384 cells with arbitrary offsets and sizes, another
            # cell's box now and then lies as near as the right one's (a
            # few detections a run, on the chip). Of the cells within
            # MATCH_SLACK_PX of the nearest, take the one the reference
            # scores highest for the served class: the right cell is one of
            # its top k, a chance neighbour almost never.
            near = dist <= dist.min(axis=1, keepdims=True) + MATCH_SLACK_PX
            cell = np.where(near, scores[:, cls].T, -1.0).argmax(axis=1)
            box_gaps.append(dist.min(axis=1))
            score = scores[cell, cls]
            nbr = m["nbr_max"][s].reshape(-1, scores.shape[-1])[cell, cls]
            # an entry the program scored 0 is a filler (fewer peaks than
            # k); where the reference has fewer than k peaks too, its cell
            # means nothing and its score is 0 on both sides
            filler = (np.asarray(det.scores[rows]) == 0) & (m["kth"][s] == 0)
            score = np.where(filler, 0.0, score)
            select_gaps.append(np.where(
                filler, 0.0, np.maximum(m["kth"][s], nbr) - score))
            ref_boxes[rows], ref_scores[rows] = cells[cell], score
        if soft:
            keep, final = ref.soft_nms(
                ref_boxes, ref_scores, score_th=float(cfg.get("conf_th", 0)))
        else:
            keep, final = ref.hard_nms(ref_boxes, ref_scores,
                                       float(cfg.get("nms_th", 0.5)))
            score_gaps.append(np.abs(np.asarray(det.scores) - ref_scores))
        final_gaps.append(np.abs(np.asarray(det.scores) * np.asarray(det.valid)
                                 - final * keep))
    out = {"box_gap_px": float(np.concatenate(box_gaps).max()),
           "select_gap_p99": float(np.percentile(np.concatenate(select_gaps),
                                                 99)),
           "final_score_gap_mean": float(np.concatenate(final_gaps).mean())}
    if score_gaps:
        out["score_gap_p99"] = float(np.percentile(np.concatenate(score_gaps),
                                                   99))
    return out


# ---- judgement -------------------------------------------------------------

def judge(numbers: Dict[str, float], limits: Dict[str, float],
          missing: Optional[int] = 0) -> dict:
    """{'correct': bool, 'checked': {name: {'value', 'limit'}}}. Every limit
    of the cell must have its number, finite and at or under the limit;
    `missing` counts answers that never came."""
    checked, ok = {}, missing == 0
    for name, limit in limits.items():
        value = numbers.get(name)
        checked[name] = {"value": value, "limit": limit}
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    if missing:
        checked["answers_missing"] = {"value": missing, "limit": 0}
    return {"correct": bool(ok), "checked": checked}
