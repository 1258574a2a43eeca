"""The yardstick itself, on the CPU at toy size: the control (the reference in
fp8, the nearest precision below the bfloat16 the configurations state) fails
the comparison a sound run passes; the traffic generator is reproducible and
hands out due instants; the analytic work counts; the trace reduction on the
small trace recorded on the chip and committed with it."""

import json
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import calibrate, compare, traffic, trace_reduce  # noqa: E402
from benchmark import weights as wts  # noqa: E402
from benchmark.reference import model as ref  # noqa: E402
from benchmark.work import count  # noqa: E402

REPO = bench_toy.REPO


def _fields(config):
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        return json.load(f)["fields"]


def _limits(mix):
    with open(os.path.join(REPO, "benchmark", "workloads",
                           mix + ".json")) as f:
        return json.load(f)["limits"]


# ---- the control ------------------------------------------------------------

@pytest.mark.parametrize("config,mix", [("flagship-s1-w128", "serve-bulk"),
                                        ("quality-s2-w128", "serve-bulk-soft"),
                                        ("quality-s2-w128", "serve-live")])
def test_serving_control_is_not_correct(config, mix):
    """Same frames, same weights: the reference's own answers pass against
    itself at every limit; computed in fp8 they fail."""
    import jax
    import jax.numpy as jnp
    cfg = bench_toy.toy_fields(config)
    spec = ref.param_spec(cfg)
    frames = traffic.frame_pool(3, 4, 64)
    weights = wts.with_running_statistics(
        cfg, wts.make_weights(spec, 3), frames)

    def maps(quant):
        out = jax.device_get(jax.jit(lambda w, x: ref.dense_maps(
            cfg, w, x, quant))(weights, jnp.asarray(frames)))
        return [{k: v[i] for k, v in out.items()} for i in range(len(frames))]

    want = maps("f32")
    sound = compare.judge(compare.serve_numbers(
        cfg, calibrate.control_answers(cfg, want), want), _limits(mix))
    assert sound["correct"], sound["checked"]
    control = compare.judge(compare.serve_numbers(
        cfg, calibrate.control_answers(cfg, maps("fp8")), want), _limits(mix))
    assert not control["correct"], control["checked"]


def test_training_control_and_half_batch_are_not_correct():
    import jax
    import jax.numpy as jnp
    cfg = bench_toy.toy_fields("flagship-s1-w128")
    spec = ref.param_spec(cfg)
    weights = wts.make_weights(spec, 5)
    batches = [tuple(jnp.asarray(a) for a in (b.image, b.heatmap, b.offset,
                                              b.wh, b.mask))
               for b in traffic.train_batches(5, 3, 8, 64, 2, 0.01)]

    def side(quant="f32", rows=None):
        losses, grads, change = ref.train_steps(cfg, weights, batches, spec,
                                                quant, rows)
        norm = lambda t: {k: float(jnp.linalg.norm(v.ravel()))  # noqa: E731
                          for k, v in t.items()}
        return {"losses": [float(x) for x in losses],
                "grad_norms": norm(grads), "change_norms": norm(change)}

    want, limits = side(), _limits("train-b32")
    assert compare.judge(compare.train_numbers(want, want), limits)["correct"]
    for broken in (side(quant="fp8"), side(quant="bf16", rows=slice(0, 4))):
        verdict = compare.judge(compare.train_numbers(broken, want), limits)
        assert not verdict["correct"], verdict["checked"]
    # a step that returns its state unchanged: the change reads 1
    still = dict(want, change_norms={k: 0.0 for k in want["change_norms"]})
    assert compare.train_numbers(still, want)["change_norm_gap"] == 1.0


def test_zero_gradient_leaves_are_left_out():
    norms = {"a": 1.0, "b": 2.0, "c": 3.0, "stem_bias": 1e-7}
    assert compare.moved_leaves(norms) == ["a", "b", "c"]


def test_judge_needs_every_number_and_every_answer():
    limits = {"x": 1.0, "y": 1.0}
    assert compare.judge({"x": 0.5, "y": 1.0}, limits)["correct"]
    assert not compare.judge({"x": 0.5}, limits)["correct"]
    assert not compare.judge({"x": 0.5, "y": float("nan")}, limits)["correct"]
    assert not compare.judge({"x": 0.5, "y": 0.5}, limits, 1)["correct"]


# ---- weights and the program's tree ------------------------------------------

@pytest.mark.parametrize("stacks", [1, 2])
def test_reference_lists_exactly_the_programs_parameters(stacks):
    import jax
    import jax.numpy as jnp
    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    cfg = dict(num_stack=stacks, hourglass_inch=16, num_cls=2)
    spec = ref.param_spec(cfg)
    model = build_model(Config(**cfg))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 64, 64, 3)), train=False))
    wts.check_tree(shapes, spec)
    with pytest.raises(ValueError):
        wts.check_tree(shapes, {k: v for k, v in list(spec.items())[1:]})
    a, b = wts.make_weights(spec, 2 ** 31 + 5), wts.make_weights(spec,
                                                                 2 ** 31 + 5)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["Head_0/Convolution_0/Conv_0/kernel"],
                              wts.make_weights(spec, 6)[
                                  "Head_0/Convolution_0/Conv_0/kernel"])


# ---- traffic ------------------------------------------------------------------

def test_open_schedule_is_reproducible_and_offers_the_same_work():
    a = traffic.open_schedule(2 ** 31 + 1, 20.0, 228.0, 1, 16, 24)
    b = traffic.open_schedule(2 ** 31 + 1, 20.0, 228.0, 1, 16, 24)
    c = traffic.open_schedule(7, 20.0, 228.0, 1, 16, 24)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == round(228.0 * 20.0)
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0) and a[-1] < 20.0
    # the same multiset of burst sizes, in another order
    sizes = lambda d: sorted(np.unique(d, return_counts=True)[1])  # noqa
    assert sizes(a) == sizes(c) and max(sizes(a)) <= 16


def test_open_loop_times_from_the_due_instant():
    """A request answered 10 ms after it was submitted, but submitted 50 ms
    late, waited 60 ms."""
    from benchmark.drivers import serve_open
    cell = serve_open.Cell(types.SimpleNamespace(
        traffic={"rate_per_s": 50.0, "burst": [1, 1], "schedule_seed": 1},
        seed=1, span=__import__("contextlib").nullcontext))
    cell.counters = lambda: {"batches_total": 0}

    class Fut:
        def result(self, timeout=None):
            return None

    def submit(due, block):
        cell.due.append(due)
        cell.sub.append(due + 0.050)
        cell.done.append(due + 0.060)
        cell.futs.append(Fut())
    cell.submit = submit
    cell.ctx.span = lambda name: __import__("contextlib").nullcontext()
    window = cell.run(0.2)
    assert window["attempted"] == 10 and window["failed"] == 0
    assert np.allclose(window["latency_ms"], 60.0)
    assert np.allclose(window["late_ms"], 50.0)
    assert abs(window["e2e"]["serve_p95_ms"] - 60.0) < 1e-6


def test_batches_and_frames_come_from_the_seed():
    a = traffic.train_batches(9, 2, 4, 64, 2, 0.01)
    b = traffic.train_batches(9, 2, 4, 64, 2, 0.01)
    assert np.array_equal(a[1].image, b[1].image)
    rows = np.concatenate([x.image.reshape(4, -1) for x in a])
    assert len(np.unique(rows[:, :8], axis=0)) == 8  # rows all differ
    f = traffic.frame_pool(9, 4, 64)
    assert f.dtype == np.uint8 and f.shape == (4, 64, 64, 3)
    assert np.array_equal(f, traffic.frame_pool(9, 4, 64))


# ---- work ----------------------------------------------------------------------

@pytest.mark.parametrize("config,fwd,train", [
    ("flagship-s1-w128", 80.14, 239.19), ("quality-s2-w128", 110.22, 329.42)])
def test_analytic_flops_at_the_published_sizes(config, fwd, train):
    """GFLOP per 512^2 image; XLA's own cost analysis of the compiled
    programs read 79.8 / 239.7 and 109.6 / 329.5 (ISSUE 24): a cross-check,
    within 1%."""
    cfg = _fields(config)
    assert abs(count.conv_flops_per_image(cfg, 512, False) / 1e9 - fwd) < 0.01
    assert abs(count.conv_flops_per_image(cfg, 512, True) / 1e9 - train) < 0.01


def test_bn_tail_bytes_against_a_hand_count_of_the_flagships_tails():
    """The train step's 37 tails (PERF.md section 3): 8 bf16 activation
    transfers a tail without a skip, 12 with one. Per level, elements of one
    activation x (tails without, tails with a skip): the stem at 256^2 (one
    64-channel tail, two 128-channel ones, one with the skip), 128^2 (5, 4),
    and the hourglass's four levels 64^2 .. 8^2 (3, 3 each)."""
    assert count.bn_tail_transfers(add=False) == 8
    assert count.bn_tail_transfers(add=True) == 12
    plain = 256 * 256 * 64 + 2 * 256 * 256 * 128 + 5 * 128 * 128 * 128 \
        + 3 * 128 * (64 * 64 + 32 * 32 + 16 * 16 + 8 * 8)
    skip = 256 * 256 * 128 + 4 * 128 * 128 * 128 \
        + 3 * 128 * (64 * 64 + 32 * 32 + 16 * 16 + 8 * 8)
    assert count.bn_tail_bytes_per_image(_fields("flagship-s1-w128"), 512) \
        == 2 * (8 * plain + 12 * skip) == 989528064


# ---- the trace reduction ----------------------------------------------------------

def test_reduce_events_by_hand():
    ops = {"/device:TPU:0": [("%bn_act_fwd.1 = x", 10, 20),
                             ("%fusion.2 = y", 15, 30),
                             ("%bn_act_fwd.3 = x", 50, 60),
                             ("%late = z", 120, 130)]}
    spans = [("bench:stage", 30, 50), ("bench:fetch", 58, 100)]
    r = trace_reduce.reduce_events(ops, spans, (0, 100))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)  # [10,30] + [50,60]
    assert r["op_ms"]["bn_act_fwd"] == pytest.approx(20e-6)
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["bench:stage"] == pytest.approx(20e-9)
    assert gaps["bench:fetch"] == pytest.approx(40e-9)
    assert gaps["no-span"] == pytest.approx(10e-9)
    assert trace_reduce.reduce_events({"/device:TPU:0": []}, []) is None
    assert trace_reduce.clock_offset_ns([1e9, 3e9], [10.0, 12.0]) == -9e9


def test_reduce_the_committed_chip_trace(tmp_path):
    """Two toy-size train steps recorded on the TPU v5e (build notes in
    benchmark/testdata/README.md); the expected numbers were taken from the
    raw events at recording time by a separate sort-and-sweep."""
    data = os.path.join(REPO, "benchmark", "testdata")
    with open(os.path.join(data, "train2.expected.json")) as f:
        rec = json.load(f)
    import gzip
    with gzip.open(os.path.join(data, "train2.xplane.pb.gz")) as src, \
            open(tmp_path / "train2.xplane.pb", "wb") as dst:
        dst.write(src.read())
    r = trace_reduce.reduce_trace(
        str(tmp_path / "train2.xplane.pb"),
        [tuple(s) for s in rec["host_spans"]], rec["marks_host_s"],
        tuple(rec["window_host_s"]))
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    for kernel, ms in want["kernel_ms"].items():
        assert r["op_ms"][kernel] == pytest.approx(ms, rel=1e-6)
    assert any(k.startswith("bn_act_") for k in want["kernel_ms"])
    assert {name for name, _ in r["idle_gaps"]} & {
        "bench:stage", "bench:step", "bench:fetch", "no-span"}
