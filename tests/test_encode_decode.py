"""Encode/decode numerics tests.

Mirrors the reference's only numerics check — the encode->decode round trip
in /root/reference/transform.py:112-131 — and extends it into a real test
pyramid: exact golden values, windowing, normalization, ordering, the
on-device encoder vs the host encoder, and fixed-shape decode semantics.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from real_time_helmet_detection_tpu.ops import (
    encode_boxes, encode_boxes_batch, encode_boxes_jax, decode_heatmap,
    decode_peak_scores, peak_mask)
from real_time_helmet_detection_tpu.analysis.trace_audit import _walk_jaxprs
from real_time_helmet_detection_tpu.ops.decode import chunk_length, top_k_exact


def test_encode_shapes_channels_last():
    heat, off, size, mask = encode_boxes([[10, 20, 100, 200]], [1], (512, 512))
    assert heat.shape == (128, 128, 2)
    assert off.shape == (128, 128, 2)
    assert size.shape == (128, 128, 2)
    assert mask.shape == (128, 128, 1)


def test_encode_empty():
    heat, off, size, mask = encode_boxes(None, None, (512, 512))
    assert heat.sum() == 0 and mask.sum() == 0


def test_encode_golden_center_values():
    # Box [10,20,100,200] at 512^2: map-scale box [2.5,5,25,50], center
    # (13.75, 27.5) -> index (13, 27), offset (0.75, 0.5), size (22.5, 45).
    heat, off, size, mask = encode_boxes([[10, 20, 100, 200]], [1], (512, 512))
    assert mask[27, 13, 0] == 1.0
    assert np.allclose(off[27, 13], [0.75, 0.5])
    assert np.allclose(size[27, 13], [22.5, 45.0])
    assert heat[27, 13, 1] == pytest.approx(1.0)
    assert heat[27, 13, 0] == 0.0  # other class untouched


def test_encode_normalized_golden():
    heat, off, size, mask = encode_boxes([[10, 20, 100, 200]], [1], (512, 512),
                                         normalized=True)
    assert np.allclose(off[27, 13], [0.75 / 4, 0.5 / 4])
    assert np.allclose(size[27, 13], [22.5 / 128, 45.0 / 128])


def test_encode_gaussian_window_and_sigma():
    heat, *_ = encode_boxes([[10, 20, 100, 200]], [1], (512, 512))
    # radius = hypot(13.75-2.5, 27.5-5) = hypot(11.25, 22.5); int window
    radius = np.hypot(11.25, 22.5)
    ri = int(radius)
    sigma = radius / 3
    # value one pixel right of center
    expected = np.exp(-1.0 / (2 * sigma * sigma))
    assert heat[27, 14, 1] == pytest.approx(expected, rel=1e-5)
    # window edge: inside at distance ri, zero beyond
    assert heat[27, 13 + ri, 1] > 0
    assert heat[27, min(13 + ri + 1, 127), 1] == 0.0
    assert heat[27 - ri, 13, 1] > 0


def test_encode_overlap_max_merge():
    # Two same-class boxes with the same center: heatmap merges via max (=1),
    # scatter maps take the later box's values.
    boxes = [[0, 0, 40, 40], [10, 10, 30, 30]]
    heat, off, size, mask = encode_boxes(boxes, [0, 0], (128, 128))
    assert heat[5, 5, 0] == pytest.approx(1.0)
    assert np.allclose(size[5, 5], [5.0, 5.0])  # second (smaller) box wins
    assert mask.sum() == 1.0


def test_encode_jax_matches_numpy():
    boxes = np.array([[10, 20, 100, 200], [50, 60, 90, 120], [0, 0, 0, 0]],
                     np.float32)
    labels = np.array([1, 0, 0], np.int32)
    valid = np.array([True, True, False])
    h_np, o_np, s_np, m_np = encode_boxes(boxes[:2], labels[:2], (512, 512))
    h_j, o_j, s_j, m_j = encode_boxes_jax(jnp.asarray(boxes), jnp.asarray(labels),
                                          jnp.asarray(valid), height=128, width=128)
    assert np.allclose(h_np, np.asarray(h_j), atol=1e-6)
    assert np.allclose(o_np, np.asarray(o_j), atol=1e-6)
    assert np.allclose(s_np, np.asarray(s_j), atol=1e-6)
    assert np.allclose(m_np, np.asarray(m_j))


def test_round_trip():
    """The reference's transform.py:112-131 round-trip, as a real assertion."""
    boxes = [[10, 20, 100, 200]]
    labels = [1]
    for normalized in (False, True):
        heat, off, size, _ = encode_boxes(boxes, labels, (512, 512),
                                          normalized=normalized)
        det = decode_heatmap(jnp.asarray(heat), jnp.asarray(off), jnp.asarray(size),
                             topk=10, normalized=normalized)
        # Best peak reconstructs the box exactly (center snapped to its cell).
        assert int(det.classes[0]) == 1
        assert float(det.scores[0]) == pytest.approx(1.0)
        np.testing.assert_allclose(np.asarray(det.boxes[0]), [10, 20, 100, 200],
                                   atol=1e-4)


def test_round_trip_multi_box_multi_class():
    boxes = [[32, 32, 96, 96], [200, 220, 280, 300], [400, 40, 480, 120]]
    labels = [0, 1, 0]
    heat, off, size, _ = encode_boxes(boxes, labels, (512, 512))
    det = decode_heatmap(jnp.asarray(heat), jnp.asarray(off), jnp.asarray(size),
                         topk=20)
    got = {(int(c), tuple(np.round(np.asarray(b)).astype(int)))
           for b, c, s in zip(det.boxes, det.classes, det.scores)
           if float(s) > 0.99}
    want = {(l, tuple(b)) for b, l in zip(boxes, labels)}
    assert want <= got


def test_decode_fixed_shapes_and_valid_mask():
    heat, off, size, _ = encode_boxes([[10, 20, 100, 200]], [1], (512, 512))
    det = decode_heatmap(jnp.asarray(heat), jnp.asarray(off), jnp.asarray(size),
                         topk=100, conf_th=0.5)
    assert det.boxes.shape == (100, 4)
    assert det.classes.shape == (100,)
    assert det.scores.shape == (100,)
    assert det.valid.shape == (100,)
    assert int(det.valid.sum()) == 1  # only the true center survives 0.5


def test_peak_mask_batched():
    hm = jnp.zeros((2, 3, 8, 8, 2)).at[1, 2, 4, 4, 1].set(0.9)
    pm = peak_mask(hm)
    assert pm.shape == hm.shape
    assert bool(pm[1, 2, 4, 4, 1])


def test_peak_mask_plateau_ties_count_as_peaks():
    hm = jnp.zeros((8, 8, 1)).at[3:5, 3:5, 0].set(0.7)
    pm = peak_mask(hm)
    assert bool(pm[3, 3, 0]) and bool(pm[4, 4, 0])


def test_decode_class_major_index_layout():
    # A peak in class 0 and a peak in class 1 at different cells: class ids
    # must come out right (flat index layout is class-major like the ref).
    heat = np.zeros((16, 16, 2), np.float32)
    heat[2, 3, 0] = 0.9
    heat[10, 12, 1] = 0.8
    off = np.zeros((16, 16, 2), np.float32)
    size = np.full((16, 16, 2), 2.0, np.float32)
    det = decode_heatmap(jnp.asarray(heat), jnp.asarray(off), jnp.asarray(size),
                         topk=2)
    assert int(det.classes[0]) == 0 and int(det.classes[1]) == 1
    np.testing.assert_allclose(np.asarray(det.boxes[0]),
                               [(3 - 1) * 4, (2 - 1) * 4, (3 + 1) * 4, (2 + 1) * 4])


def test_encode_batch_stacks():
    h, o, s, m = encode_boxes_batch([[[10, 20, 100, 200]], []], [[1], []],
                                    (256, 256))
    assert h.shape == (2, 64, 64, 2)
    assert m[1].sum() == 0


def test_encode_zero_area_box_no_nan():
    """A degenerate (zero-area) box must not produce NaNs or a zero sigma
    blowup — the radius/sigma clamp handles it."""
    boxes = np.array([[10.0, 10.0, 10.0, 10.0]], np.float32)
    labels = np.array([0], np.int32)
    heat, off, wh, mask = encode_boxes(boxes, labels, (64, 64), 4, 2, False)
    assert np.isfinite(heat).all() and np.isfinite(off).all()
    assert np.isfinite(wh).all()
    assert heat.max() <= 1.0


def test_encode_box_on_image_edge_clips_indices():
    """Centers at/over the image border must clip into the map, not wrap
    or crash (ref transform.py center-index int division)."""
    boxes = np.array([[56.0, 56.0, 64.0, 64.0],   # touches bottom-right
                      [0.0, 0.0, 4.0, 4.0]], np.float32)
    labels = np.array([0, 1], np.int32)
    heat, off, wh, mask = encode_boxes(boxes, labels, (64, 64), 4, 2, False)
    assert mask.sum() == 2
    assert np.isfinite(heat).all()


def test_decode_conf_above_all_scores_fixed_shape():
    """conf_th above every score: fixed shapes with valid all-False (the
    eval path then writes no detections) — never a shape change."""
    heat = jnp.zeros((16, 16, 2)) + 0.3
    off = jnp.zeros((16, 16, 2))
    wh = jnp.ones((16, 16, 2))
    dets = decode_heatmap(heat, off, wh, scale_factor=4, topk=10,
                          conf_th=0.99, normalized=False)
    assert dets.boxes.shape == (10, 4)
    assert not bool(np.asarray(dets.valid).any())


# -- the two-level top-k against lax.top_k, values AND indices ---------------

PUBLISHED = 128 * 128 * 2  # one stack's flat map at 512^2: 32,768 scores


def _scores(case: str, n: int) -> np.ndarray:
    rng = np.random.default_rng(sum(map(ord, case)) + n)
    if case == "distinct":
        return rng.permutation(n).astype(np.float32) / n
    if case in ("ties4", "ties8"):
        return rng.integers(0, int(case[4:]), n).astype(np.float32) / 8
    flat = np.zeros(n, np.float32)
    if case == "few_nonzero":  # fewer peaks than k: zeros fill, lowest index first
        flat[rng.choice(n, 37, replace=False)] = rng.random(37, np.float32)
    elif case == "max_across_chunks":  # one maximum repeated over a chunk boundary
        edge = 5 * chunk_length(PUBLISHED, 100)
        flat[rng.choice(n, 300, replace=False)] = 0.5
        flat[edge - 3:edge + 3] = 0.9
    else:
        assert case == "all_zero"
    return flat


@pytest.mark.parametrize("case,n,k", [
    ("distinct", PUBLISHED, 100),
    ("ties4", PUBLISHED, 100),
    ("ties8", PUBLISHED, 100),
    ("few_nonzero", PUBLISHED, 100),
    ("all_zero", PUBLISHED, 100),
    ("max_across_chunks", PUBLISHED, 100),
    ("ties8", 64 * 64 * 2, 100),   # a 256^2 image's map: chunks of 8
    ("ties4", 3 * 4096, 7),        # a chunk count that is no power of two
    ("ties4", 16 * 16 * 2, 100),   # the suite's toy map: the direct call
    ("distinct", 16 * 16 * 2, 100),
])
def test_top_k_exact_is_lax_top_k(case, n, k):
    flat = jnp.asarray(_scores(case, n))
    want_scores, want_at = jax.lax.top_k(flat, k)
    scores, at = top_k_exact(flat, k)
    np.testing.assert_array_equal(np.asarray(scores), np.asarray(want_scores))
    np.testing.assert_array_equal(np.asarray(at), np.asarray(want_at))


def test_top_k_exact_under_vmap_vmap_as_predict_calls_it():
    flat = jnp.asarray(np.stack([
        _scores(case, PUBLISHED) for case in
        ("distinct", "ties4", "few_nonzero", "all_zero", "ties8",
         "max_across_chunks")]).reshape(3, 2, PUBLISHED))
    want = jax.vmap(jax.vmap(lambda f: jax.lax.top_k(f, 100)))(flat)
    got = jax.vmap(jax.vmap(lambda f: top_k_exact(f, 100)))(flat)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("n,k,chunk", [
    (PUBLISHED, 100, 16), (64 * 64 * 2, 100, 8), (32 * 32 * 2, 100, 4),
    (16 * 16 * 2, 100, 0), (PUBLISHED, 200, 8), (1599, 10, 0), (50, 100, 0)])
def test_chunk_length_follows_the_shape(n, k, chunk):
    assert chunk_length(n, k) == chunk


def test_decode_holds_no_whole_map_sort():
    """What keeps a later edit from bringing the 32,768-key sort back unseen
    on the CPU suite: the published map selects through sorts of at most
    2,048 keys, the toy map through exactly the one direct call."""
    def selections(side):
        maps = [jax.ShapeDtypeStruct((side, side, 2), jnp.float32)] * 3
        closed = jax.make_jaxpr(
            lambda p, o, w: decode_peak_scores(p, o, w, topk=100))(*maps)
        return sorted((e.primitive.name, e.invars[0].aval.shape[-1])
                      for j in _walk_jaxprs(closed.jaxpr) for e in j.eqns
                      if e.primitive.name in ("top_k", "sort"))

    assert selections(128) == [("sort", 100), ("top_k", 1600), ("top_k", 2048)]
    assert selections(16) == [("top_k", 512)]
