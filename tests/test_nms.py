"""NMS tests: greedy hard NMS vs a trivial O(N^2) numpy oracle, soft-NMS
decay semantics, masked fixed-shape behavior, and the PSRR-style maxpool
NMS's agreement rate vs the greedy chain (approximate by design — ISSUE 5
satellite)."""

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from real_time_helmet_detection_tpu.analysis.trace_audit import _walk_jaxprs
from real_time_helmet_detection_tpu.ops import (maxpool_nms_mask, nms_mask,
                                                soft_nms_mask)
from real_time_helmet_detection_tpu.ops.nms import _NEG, _iou_matrix


def _np_greedy_nms(boxes, scores, iou_th):
    """Oracle with torchvision semantics (no +1, suppress iou > th)."""
    idx = np.argsort(-scores, kind="stable")
    keep = []
    suppressed = np.zeros(len(boxes), bool)
    for i in idx:
        if suppressed[i]:
            continue
        keep.append(i)
        x1, y1, x2, y2 = boxes[i]
        for j in idx:
            if suppressed[j] or j == i:
                continue
            ax1, ay1 = max(x1, boxes[j][0]), max(y1, boxes[j][1])
            ax2, ay2 = min(x2, boxes[j][2]), min(y2, boxes[j][3])
            inter = max(0, ax2 - ax1) * max(0, ay2 - ay1)
            a = (x2 - x1) * (y2 - y1)
            b = (boxes[j][2] - boxes[j][0]) * (boxes[j][3] - boxes[j][1])
            if inter / (a + b - inter) > iou_th:
                suppressed[j] = True
    return sorted(keep)


def test_nms_matches_oracle_random():
    rng = np.random.RandomState(0)
    for seed in range(5):
        rng = np.random.RandomState(seed)
        n = 32
        xy = rng.uniform(0, 100, (n, 2))
        wh = rng.uniform(5, 40, (n, 2))
        boxes = np.concatenate([xy, xy + wh], 1).astype(np.float32)
        scores = rng.uniform(0.1, 1.0, n).astype(np.float32)
        valid = np.ones(n, bool)
        keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores),
                                   jnp.asarray(valid), 0.5))
        assert sorted(np.nonzero(keep)[0].tolist()) == _np_greedy_nms(boxes, scores, 0.5)


def test_nms_identical_boxes_keep_best():
    boxes = jnp.asarray([[0, 0, 10, 10]] * 3, jnp.float32)
    scores = jnp.asarray([0.5, 0.9, 0.7])
    keep = nms_mask(boxes, scores, jnp.ones(3, bool), 0.5)
    assert np.asarray(keep).tolist() == [False, True, False]


def test_nms_disjoint_boxes_all_kept():
    boxes = jnp.asarray([[0, 0, 10, 10], [20, 20, 30, 30], [50, 0, 60, 10]],
                        jnp.float32)
    keep = nms_mask(boxes, jnp.asarray([0.9, 0.8, 0.7]), jnp.ones(3, bool), 0.5)
    assert np.asarray(keep).all()


def test_nms_invalid_never_kept_never_suppress():
    # High-scoring invalid box overlaps a valid one: valid must survive.
    boxes = jnp.asarray([[0, 0, 10, 10], [1, 1, 11, 11]], jnp.float32)
    scores = jnp.asarray([0.99, 0.5])
    valid = jnp.asarray([False, True])
    keep = np.asarray(nms_mask(boxes, scores, valid, 0.5))
    assert keep.tolist() == [False, True]


def test_soft_nms_decays_overlapping():
    boxes = jnp.asarray([[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]],
                        jnp.float32)
    scores = jnp.asarray([0.9, 0.8, 0.7])
    keep, new_scores = soft_nms_mask(boxes, scores, jnp.ones(3, bool),
                                     sigma=0.5, score_th=0.001)
    new_scores = np.asarray(new_scores)
    assert new_scores[0] == pytest.approx(0.9)       # top box untouched
    assert new_scores[1] < 0.8                        # overlapped: decayed
    assert new_scores[2] == pytest.approx(0.7, abs=1e-4)  # far box ~untouched
    assert np.asarray(keep).all()                     # all above 0.001


def test_soft_nms_kills_duplicates():
    boxes = jnp.asarray([[0, 0, 100, 100]] * 2, jnp.float32)
    scores = jnp.asarray([0.9, 0.85])
    keep, new_scores = soft_nms_mask(boxes, scores, jnp.ones(2, bool),
                                     sigma=0.5, score_th=0.2)
    assert np.asarray(keep).tolist() == [True, False]


def _np_soft_nms(boxes, scores, sigma=0.5, thresh=0.001):
    """Sequential oracle mirroring the reference's swap-based Soft-NMS
    (ref evaluate.py:184-243): at round i the max-scoring remaining box is
    swapped into slot i, then every later box is decayed by
    exp(-iou^2/sigma) using the +1 inclusive-coordinate IoU; survivors are
    final score > thresh. Returns (keep index set, final scores by ORIGINAL
    index)."""
    boxes = np.asarray(boxes, np.float64).copy()
    scores = np.asarray(scores, np.float64).copy()
    n = len(boxes)
    idx = np.arange(n)
    for i in range(n):
        if i < n - 1:
            m = i + 1 + int(np.argmax(scores[i + 1:]))
            if scores[i] < scores[m]:
                for arr in (boxes, scores, idx):
                    arr[[i, m]] = arr[[m, i]]
        rest = np.arange(i + 1, n)
        if rest.size == 0:
            break
        xx1 = np.maximum(boxes[i, 0], boxes[rest, 0])
        yy1 = np.maximum(boxes[i, 1], boxes[rest, 1])
        xx2 = np.minimum(boxes[i, 2], boxes[rest, 2])
        yy2 = np.minimum(boxes[i, 3], boxes[rest, 3])
        inter = np.maximum(0.0, xx2 - xx1 + 1) * np.maximum(0.0, yy2 - yy1 + 1)
        area_i = (boxes[i, 2] - boxes[i, 0] + 1) * (boxes[i, 3] - boxes[i, 1] + 1)
        area_r = (boxes[rest, 2] - boxes[rest, 0] + 1) \
            * (boxes[rest, 3] - boxes[rest, 1] + 1)
        iou = inter / (area_i + area_r - inter)
        scores[rest] *= np.exp(-(iou ** 2) / sigma)
    final = np.empty(n)
    final[idx] = scores
    return set(idx[scores > thresh].tolist()), final


@pytest.mark.parametrize("seed", range(4))
def test_soft_nms_matches_reference_oracle(seed):
    """The fixed-iteration masked formulation must reproduce the reference's
    sequential swap-based loop: same survivor set AND same decayed scores
    (round-2 verdict missing #5 — the hard-NMS path had an oracle, the soft
    path did not)."""
    rng = np.random.RandomState(seed)
    n = 40
    # clustered boxes so overlaps (and multi-step decay chains) are common
    centers = rng.uniform(20, 80, (8, 2))
    xy = centers[rng.randint(0, 8, n)] + rng.uniform(-8, 8, (n, 2))
    wh = rng.uniform(10, 30, (n, 2))
    boxes = np.concatenate([xy, xy + wh], 1).astype(np.float32)
    scores = rng.uniform(0.05, 1.0, n).astype(np.float32)

    thresh = 0.3  # a floor that actually drops some decayed boxes
    ref_keep, ref_scores = _np_soft_nms(boxes, scores, sigma=0.5,
                                        thresh=thresh)
    keep, new_scores = soft_nms_mask(jnp.asarray(boxes), jnp.asarray(scores),
                                     jnp.ones(n, bool), sigma=0.5,
                                     score_th=thresh)
    assert set(np.nonzero(np.asarray(keep))[0].tolist()) == ref_keep
    np.testing.assert_allclose(np.asarray(new_scores), ref_scores,
                               rtol=1e-4, atol=1e-5)


def test_soft_nms_invalid_entries_ignored_vs_oracle():
    """Masked entries must neither decay others nor be kept; the valid
    subset must behave exactly as the oracle run on that subset alone."""
    rng = np.random.RandomState(7)
    n = 24
    xy = rng.uniform(10, 60, (n, 2))
    wh = rng.uniform(15, 40, (n, 2))
    boxes = np.concatenate([xy, xy + wh], 1).astype(np.float32)
    scores = rng.uniform(0.05, 1.0, n).astype(np.float32)
    valid = rng.rand(n) < 0.6

    ref_keep_sub, ref_scores_sub = _np_soft_nms(
        boxes[valid], scores[valid], sigma=0.5, thresh=0.2)
    sub_to_full = np.nonzero(valid)[0]
    ref_keep = {int(sub_to_full[i]) for i in ref_keep_sub}

    keep, new_scores = soft_nms_mask(jnp.asarray(boxes), jnp.asarray(scores),
                                     jnp.asarray(valid), sigma=0.5,
                                     score_th=0.2)
    assert set(np.nonzero(np.asarray(keep))[0].tolist()) == ref_keep
    np.testing.assert_allclose(np.asarray(new_scores)[valid], ref_scores_sub,
                               rtol=1e-4, atol=1e-5)
    # invalid entries keep their input scores (decay never touches them)
    np.testing.assert_allclose(np.asarray(new_scores)[~valid],
                               scores[~valid], rtol=1e-6)


def _iou_matrix_np(boxes, plus_one):
    """`_iou_matrix`'s arithmetic in numpy's float32, one rounding an
    operation. XLA's CPU backend contracts `area_i + area_j` inside that
    (N, N) fusion into a fused multiply-add, one rounding fewer in some
    entries; the TPU's does not (there the index form over the package's
    matrix and the mask form read bit for bit alike)."""
    b = np.asarray(boxes, np.float32)
    e = np.float32(1.0 if plus_one else 0.0)
    x1, y1, x2, y2 = (b[..., k] for k in range(4))
    area = (x2 - x1 + e) * (y2 - y1 + e)
    w = np.maximum(np.float32(0), np.minimum(x2[..., :, None], x2[..., None, :])
                   - np.maximum(x1[..., :, None], x1[..., None, :]) + e)
    h = np.maximum(np.float32(0), np.minimum(y2[..., :, None], y2[..., None, :])
                   - np.maximum(y1[..., :, None], y1[..., None, :]) + e)
    inter = w * h
    with np.errstate(invalid="ignore", divide="ignore"):
        return inter / (area[..., :, None] + area[..., None, :] - inter)


@jax.jit
def _gather_soft_nms(iou, scores, valid, sigma=0.5, score_th=0.001):
    """The index-form body `soft_nms_mask` had before it selected by mask:
    a precomputed (N, N) IoU matrix read at the round's argmax, the
    selected entries set by index (under `vmap`, a gather and two scatters
    a round). Kept as the oracle its answers must equal bit for bit."""
    n = iou.shape[0]

    def body(_, state):
        cur_scores, processed = state
        cand = jnp.where(processed | ~valid, _NEG, cur_scores)
        i = jnp.argmax(cand)
        has_cand = cand[i] > _NEG / 2
        weight = jnp.exp(-(iou[i] ** 2) / sigma)
        decayed = jnp.where(processed | ~valid, cur_scores, cur_scores * weight)
        decayed = decayed.at[i].set(cur_scores[i])
        cur_scores = jnp.where(has_cand, decayed, cur_scores)
        processed = processed.at[i].set(True) | processed
        return cur_scores, processed

    final_scores, _ = jax.lax.fori_loop(0, n, body,
                                        (scores, jnp.zeros((n,), bool)))
    return (final_scores > score_th) & valid, final_scores


def _soft_nms_rows(seed, b=8, n=200):
    """A batch of rows shaped as predict hands soft-NMS (2 stacks x top-100),
    each row a hard case for the selection: scores on a grid of 1/32, so
    exact ties everywhere and at the maximum; duplicates; invalid entries
    scattered (with the highest scores); one row with nothing valid."""
    rng = np.random.RandomState(seed)
    centers = rng.uniform(40, 470, (12, 2))
    xy = centers[rng.randint(0, 12, (b, n))] + rng.uniform(-10, 10, (b, n, 2))
    wh = rng.uniform(8, 80, (b, n, 2))
    boxes = np.concatenate([xy - wh / 2, xy + wh / 2], -1)
    scores = rng.randint(1, 33, (b, n)) / 32.0
    valid = np.ones((b, n), bool)
    scores[0, rng.choice(n, 6, replace=False)] = 1.25      # ties at the top
    src, dst = rng.choice(n, 60), rng.choice(n, 60)
    boxes[1, dst] = boxes[1, src]                          # IoU 1 ...
    scores[1, dst[:30]] = scores[1, src[:30]]              # ... tied scores
    valid[2] = rng.rand(n) < 0.6                           # scattered invalid,
    scores[2, ~valid[2]] = 1.0                             # outscoring valid
    valid[3] = False                                       # nothing valid
    scores[4] = 0.5                                        # every score tied
    boxes[5] = boxes[5, 0]                                 # every box the same
    scores[6] = rng.uniform(0.01, 1.0, n)                  # mixed, continuous
    valid[6] = rng.rand(n) < 0.8
    boxes[6, :50] = boxes[6, 50:100]
    boxes[7, :20, 2:] = boxes[7, :20, :2]                  # zero area
    boxes[7, 20:40] -= 60.0                                # off the image
    boxes[7, 40:60] = 0.0                                  # zeros
    return (boxes.astype(np.float32), scores.astype(np.float32), valid)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("plus_one", [True, False])
@pytest.mark.parametrize("seed,score_th", [(0, 0.0), (1, 0.001), (2, 0.3)])
def test_soft_nms_masked_body_equals_index_body_bit_for_bit(seed, score_th,
                                                            plus_one):
    """Selecting by mask is the same arithmetic in another loop order: the
    batched and the unbatched call answer exactly what the index form
    answered, ties, duplicates and invalid entries included."""
    boxes, scores, valid = (jnp.asarray(a) for a in _soft_nms_rows(seed))
    kw = dict(sigma=0.5, score_th=score_th)
    iou = jnp.asarray(_iou_matrix_np(boxes, plus_one))
    oracle = jax.vmap(partial(_gather_soft_nms, **kw))
    want_keep, want = oracle(iou, scores, valid)
    kw["plus_one"] = plus_one
    keep, got = jax.vmap(partial(soft_nms_mask, **kw))(boxes, scores, valid)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want_keep))
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the rows do exercise the recurrence: scores decayed, some not kept
    assert (np.asarray(got) != np.asarray(scores)).any(axis=1)[
        [0, 1, 2, 4, 5, 6, 7]].all()
    assert not np.asarray(keep)[3].any()
    for r in (0, 2, 3, 5):
        k1, s1 = soft_nms_mask(boxes[r], scores[r], valid[r], **kw)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(want_keep[r]))
        np.testing.assert_array_equal(_bits(s1), _bits(want[r]))
    # over the package's own matrix, as the index form ran: on the CPU its
    # contracted entries move a score by a few units in the last place
    old_keep, old = oracle(
        jax.vmap(partial(_iou_matrix, plus_one=plus_one))(boxes), scores, valid)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(old_keep))
    np.testing.assert_allclose(np.asarray(got), np.asarray(old), rtol=1e-5)


_INDEXED = ("gather", "scatter", "dynamic_slice", "dynamic_update_slice")


def _index_form(boxes, scores, valid):
    return _gather_soft_nms(_iou_matrix(boxes, plus_one=True), scores, valid)


@pytest.mark.parametrize("fn,indexes", [(soft_nms_mask, False),
                                        (_index_form, True)])
def test_soft_nms_loop_body_indexes_nothing_under_vmap(fn, indexes):
    """What keeps a later edit from bringing the per-image gather back
    unseen on the CPU: at predict's shape (256 images x 200 boxes) the
    loop body of the vmapped call holds no gather, scatter or dynamic
    slice, and no (N, N) IoU matrix is formed. The index form is the
    check's own control: it must be caught."""
    b, n = 256, 200
    closed = jax.make_jaxpr(jax.vmap(fn))(
        jax.ShapeDtypeStruct((b, n, 4), jnp.float32),
        jax.ShapeDtypeStruct((b, n), jnp.float32),
        jax.ShapeDtypeStruct((b, n), jnp.bool_))
    jaxprs = _walk_jaxprs(closed.jaxpr)
    loops = [e for j in jaxprs for e in j.eqns
             if e.primitive.name in ("while", "scan")]
    assert len(loops) == 1
    body = loops[0].params.get("body_jaxpr") or loops[0].params["jaxpr"]
    found = {e.primitive.name for j in _walk_jaxprs(body.jaxpr)
             for e in j.eqns if e.primitive.name.startswith(_INDEXED)}
    square = [v.aval.shape for j in jaxprs for e in j.eqns for v in e.outvars
              if v.aval.shape[-2:] == (n, n)]
    assert bool(found) == indexes, found
    assert bool(square) == indexes, square


def _clustered_boxes(seed, n, ncl, jitter, wlo, whi, extent=512.0):
    rng = np.random.RandomState(seed)
    centers = rng.uniform(60, extent - 60, (ncl, 2))
    xy = centers[rng.randint(0, ncl, n)] + rng.uniform(-jitter, jitter,
                                                       (n, 2))
    wh = rng.uniform(wlo, whi, (n, 2))
    boxes = np.clip(np.concatenate([xy - wh / 2, xy + wh / 2], 1),
                    0, extent).astype(np.float32)
    scores = rng.uniform(0.1, 1.0, n).astype(np.float32)
    return boxes, scores


def test_maxpool_nms_collapses_duplicates():
    boxes = jnp.asarray([[100, 100, 160, 160]] * 5, jnp.float32)
    scores = jnp.asarray([0.5, 0.9, 0.7, 0.6, 0.8])
    keep = np.asarray(maxpool_nms_mask(boxes, scores, jnp.ones(5, bool),
                                       extent=512.0))
    assert keep.tolist() == [False, True, False, False, False]


def test_maxpool_nms_disjoint_kept():
    boxes = jnp.asarray([[0, 0, 60, 60], [200, 200, 260, 260],
                         [400, 0, 460, 60]], jnp.float32)
    keep = np.asarray(maxpool_nms_mask(boxes, jnp.asarray([0.9, 0.8, 0.7]),
                                       jnp.ones(3, bool), extent=512.0))
    assert keep.all()


def test_maxpool_nms_invalid_never_kept():
    boxes = jnp.asarray([[0, 0, 60, 60], [300, 300, 360, 360]], jnp.float32)
    keep = np.asarray(maxpool_nms_mask(boxes, jnp.asarray([0.9, 0.8]),
                                       jnp.asarray([False, True]),
                                       extent=512.0))
    assert keep.tolist() == [False, True]


def test_maxpool_nms_agreement_rate_vs_greedy():
    """The documented parity contract: per-box keep agreement RATE vs
    `nms_mask`, not exactness (adjacent-octave pairs and cell-quantized
    borderline pairs legitimately differ). Bounds are calibrated on these
    exact generators (mean measured ~0.96 duplicate-heavy / ~0.74 mixed;
    asserted with margin so only a real regression trips)."""
    def rate(boxes, scores):
        n = len(scores)
        k_greedy = np.asarray(nms_mask(jnp.asarray(boxes),
                                       jnp.asarray(scores),
                                       jnp.ones(n, bool), 0.5))
        k_pool = np.asarray(maxpool_nms_mask(jnp.asarray(boxes),
                                             jnp.asarray(scores),
                                             jnp.ones(n, bool),
                                             extent=512.0))
        return float((k_greedy == k_pool).mean())

    # duplicate-heavy, one size octave: the deployment regime (many
    # near-identical candidates per object) — high agreement expected
    dup = [rate(*_clustered_boxes(s, 48, 12, 4, 40, 60)) for s in range(6)]
    # mixed sizes + looser clusters: the adversarial regime for a
    # scale-binned method — agreement degrades but stays well above chance
    mixed = [rate(*_clustered_boxes(s, 48, 12, 10, 40, 70))
             for s in range(6)]
    assert np.mean(dup) >= 0.9 and min(dup) >= 0.85, dup
    assert np.mean(mixed) >= 0.6, mixed


def test_maxpool_nms_through_predict_fn():
    """`--nms maxpool` must thread end-to-end through make_predict_fn."""
    import jax

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn

    cfg = Config(num_stack=1, hourglass_inch=16, num_cls=2, topk=10,
                 conf_th=0.1, nms_th=0.5, imsize=64, nms="maxpool")
    model = build_model(cfg)
    imgs = jnp.zeros((1, 64, 64, 3), jnp.float32)
    variables = model.init(jax.random.key(0), imgs, train=False)
    dets = jax.device_get(make_predict_fn(model, cfg)(variables, imgs))
    assert dets.boxes.shape == (1, cfg.num_stack * cfg.topk, 4)
    assert dets.valid.dtype == bool


def test_nms_three_hundred_near_duplicates_keep_one():
    """The classic deployment probe: hundreds of near-identical boxes in,
    one survivor out."""
    rng = np.random.default_rng(0)
    base = np.array([50.0, 50.0, 150.0, 150.0], np.float32)
    boxes = base + rng.uniform(-1.5, 1.5, (300, 4)).astype(np.float32)
    scores = rng.uniform(0.5, 1.0, 300).astype(np.float32)
    keep = np.asarray(nms_mask(jnp.asarray(boxes), jnp.asarray(scores),
                               jnp.ones(300, bool), 0.5))
    assert keep.sum() == 1
    assert keep[np.argmax(scores)]
