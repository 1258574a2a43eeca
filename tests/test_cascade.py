"""Cascade serving primitives (ISSUE 16): the in-jit confidence signal,
the calibrated-threshold promotion record (`config.cascade_overrides`),
and the bench-line cascade fields — all CPU, no chip.

The fleet-level routing behavior (edge-first dispatch, escalation hop,
degraded answers) lives in tests/test_fleet.py; the two-hop trace
integrity proof in tests/test_trace.py; seeded escalation-site chaos in
tests/test_chaos.py. This file covers the pieces those build on.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from real_time_helmet_detection_tpu import config as config_mod
from real_time_helmet_detection_tpu.ops.decode import (MARGIN_K,
                                                       CascadeDetections,
                                                       Detections,
                                                       confidence_summary)


# ---------------------------------------------------------------------------
# confidence_summary: the signal definition every calibrated threshold
# artifact refers to


def test_confidence_summary_empty_image_is_least_confident():
    """No valid detections: top1 = margin = frac = 0 -> confidence 0,
    the floor for non-negative scores (an empty image never outranks one
    with a confident peak)."""
    scores = jnp.zeros((32,), jnp.float32)
    valid = jnp.zeros((32,), bool)
    assert float(confidence_summary(scores, valid)) == 0.0


def test_confidence_summary_monotone_in_each_signal():
    topk = 32

    def conf(score_list, n_valid):
        scores = np.zeros((topk,), np.float32)
        scores[:len(score_list)] = score_list
        valid = np.zeros((topk,), bool)
        valid[:n_valid] = True
        return float(confidence_summary(jnp.asarray(scores),
                                        jnp.asarray(valid)))

    # higher top1, same margin structure -> more confident
    assert conf([0.9], 1) > conf([0.5], 1)
    # many near-tied peaks (small margin) -> less confident than one
    # dominant peak at the same top1
    lone = conf([0.9], 1)
    tied = conf([0.9] * MARGIN_K, MARGIN_K)
    assert lone > tied
    # busier scene (higher valid fraction) at identical scores -> less
    # confident
    assert conf([0.9, 0.8], 2) > conf([0.9, 0.8] + [0.1] * 20, 22)


def test_confidence_summary_masks_invalid_scores():
    """Invalid rows must not leak into the signal (masks, never
    filtering): a huge score behind valid=False changes nothing."""
    scores = np.zeros((32,), np.float32)
    scores[0], scores[1] = 0.7, 99.0
    valid = np.zeros((32,), bool)
    valid[0] = True
    a = float(confidence_summary(jnp.asarray(scores), jnp.asarray(valid)))
    scores[1] = 0.0
    b = float(confidence_summary(jnp.asarray(scores), jnp.asarray(valid)))
    assert a == b


def test_confidence_summary_batched_matches_per_image():
    rng = np.random.default_rng(0)
    scores = rng.uniform(0.0, 1.0, size=(4, 32)).astype(np.float32)
    valid = rng.uniform(size=(4, 32)) < 0.4
    batched = np.asarray(confidence_summary(jnp.asarray(scores),
                                            jnp.asarray(valid)))
    assert batched.shape == (4,) and batched.dtype == np.float32
    for i in range(4):
        one = float(confidence_summary(jnp.asarray(scores[i]),
                                       jnp.asarray(valid[i])))
        assert batched[i] == pytest.approx(one)


def test_cascade_detections_view_drops_only_the_scalar():
    det = CascadeDetections(
        boxes=jnp.zeros((8, 4)), classes=jnp.zeros((8,), jnp.int32),
        scores=jnp.zeros((8,)), valid=jnp.zeros((8,), bool),
        confidence=jnp.float32(0.5))
    plain = det.detections()
    assert isinstance(plain, Detections)
    assert plain._fields == ("boxes", "classes", "scores", "valid")
    for name in plain._fields:
        assert getattr(plain, name) is getattr(det, name)


# ---------------------------------------------------------------------------
# cascade_overrides: the committed calibration artifact IS the promotion
# record


def _write_calib(root, rnd, threshold):
    d = os.path.join(root, "artifacts", rnd)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "cascade.json"), "w") as f:
        json.dump({"schema": "cascade-calibration-v1",
                   "selected": {"threshold": threshold}}, f)


def test_cascade_overrides_highest_round_wins(tmp_path):
    root = str(tmp_path)
    _write_calib(root, "r09", 0.11)
    _write_calib(root, "r16", 0.29)
    over = config_mod.cascade_overrides(repo_root=root)
    assert over["cascade_threshold"] == 0.29
    assert "r16" in over["_source"]


def test_cascade_overrides_missing_artifact_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        config_mod.cascade_overrides(repo_root=str(tmp_path))


def test_cascade_overrides_tolerates_junk_artifacts(tmp_path):
    root = str(tmp_path)
    d = os.path.join(root, "artifacts", "r20")
    os.makedirs(d)
    with open(os.path.join(d, "cascade.json"), "w") as f:
        f.write("{torn")
    _write_calib(root, "r10", 0.2)
    assert config_mod.cascade_overrides(
        repo_root=root)["cascade_threshold"] == 0.2


def test_apply_cascade_noop_when_off_or_explicit():
    cfg = config_mod.Config(cascade=False)
    assert config_mod.apply_cascade(cfg) is cfg
    cfg = config_mod.Config(cascade=True, cascade_threshold=0.5)
    assert config_mod.apply_cascade(cfg) is cfg


def test_committed_calibration_artifact_resolves():
    """The repo's own committed artifact must satisfy the loader (the
    acceptance evidence for the calibration workflow)."""
    over = config_mod.cascade_overrides()
    assert isinstance(over["cascade_threshold"], float)


# ---------------------------------------------------------------------------
# bench-line cascade fields: pre-cascade lines parse as cascade-off
# (regression-tested exactly like the tier/arch fields)


def test_bench_cascade_of_pre_cascade_lines_parse_as_off():
    import bench
    assert bench.bench_cascade_of({}) == {
        "cascade": False, "escalation_rate": None}
    line = {"cascade": True, "escalation_rate": 0.031}
    assert bench.bench_cascade_of(line) == line
    # a cascade-on line that never measured a rate keeps the null
    assert bench.bench_cascade_of({"cascade": True}) == {
        "cascade": True, "escalation_rate": None}


def test_find_last_tpu_result_carries_cascade_fields(tmp_path):
    import bench
    root = str(tmp_path)
    d = os.path.join(root, "artifacts", "r16")
    os.makedirs(d)
    rec = {"platform": "tpu", "metric": "inference_fps_512",
           "value": 900.0, "cascade": True, "escalation_rate": 0.031}
    with open(os.path.join(d, "BENCH_r16_local.json"), "w") as f:
        f.write(json.dumps(rec) + "\n")
    got = bench.find_last_tpu_result(root)
    assert bench.bench_cascade_of(got) == {
        "cascade": True, "escalation_rate": 0.031}


def test_predict_cascade_summary_only_adds_a_leaf(count_device_get):
    """cascade_summary=True returns CascadeDetections whose det leaves
    are bit-identical to the plain program's (the cascade-off program is
    untouched; the summary only ADDS the scalar), and the summary RIDES
    the one box-block fetch — the device_get count is identical to the
    plain program's (the zero-extra-D2H law, pinned by the shared
    conftest counter exactly like the telemetry/sentinel contracts)."""
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables
    cfg = config_mod.Config(imsize=64, variant="ghost", num_stack=1,
                            hourglass_inch=8, stem_width=8)
    model = build_model(cfg)
    rng = np.random.default_rng(3)
    images = jnp.asarray(rng.standard_normal((2, 64, 64, 3),
                                             ).astype(np.float32))
    params, batch_stats = init_variables(model, jax.random.key(0), 64)
    variables = {"params": params, "batch_stats": batch_stats}
    with count_device_get() as c_plain:
        plain = jax.device_get(
            make_predict_fn(model, cfg)(variables, images))
    with count_device_get() as c_casc:
        casc = jax.device_get(make_predict_fn(
            model, cfg, cascade_summary=True)(variables, images))
    assert c_plain.count == c_casc.count == 1  # ONE fetch, summary rides it
    assert isinstance(casc, CascadeDetections)
    for name in ("boxes", "classes", "scores", "valid"):
        assert np.array_equal(getattr(plain, name), getattr(casc, name))
    assert casc.confidence.shape == (2,)
    assert np.all(np.isfinite(casc.confidence))
