"""The per-layer metrics that read the program's own flight recorder (ISSUE
25): the toy train and serve cells run traced, as test_bench_cells.py runs
them, and every new `program_span` metric is in the line; the two new
`device_trace` readers, which find nothing on the CPU, are fed a record by
hand; `scripts/layer_trace.py` drives a cell and keeps its scope map."""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import run as bench_run  # noqa: E402

SPAN_METRICS = {
    "flagship-train-b32": {"train_dispatch_ms_per_step",
                           "train_h2d_ms_per_step", "setup_trace_lower_s",
                           "setup_backend_compile_s"},
    "flagship-serve-bulk": {"engine_dispatch_ms_per_batch.hostbound",
                            "engine_device_wait_ms_per_batch.hostbound",
                            "setup_trace_lower_s",
                            "setup_backend_compile_s"},
    "quality-serve-bulk": {"engine_dispatch_ms_per_batch.bulk",
                           "engine_device_wait_ms_per_batch.bulk",
                           "setup_trace_lower_s",
                           "setup_backend_compile_s"},
}
TRACE_METRICS = {"peak_kernel_ms_per_img.bulk", "peak_kernel_ms_per_img.hostbound",
                 "loss_kernel_ms_per_step"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


def test_the_manifest_declares_the_new_metrics():
    manifest = bench_run.load_manifest(bench_toy.REPO)
    source = {m["name"]: m["source"] for m in manifest["per_layer"]}
    for name in set().union(*SPAN_METRICS.values()):
        assert source[name] == "program_span"
    for name in TRACE_METRICS:
        assert source[name] == "device_trace"


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_traced_toy_cell_reports_every_new_span_metric(root, name):
    from real_time_helmet_detection_tpu.obs.spans import reset_ring
    reset_ring()  # a long test session may have filled the process's ring
    result = bench_run.run_cell(name, 2 ** 31 + 25, 1.0, 1, root=root,
                                allow_cpu=True)
    line = json.loads(json.dumps(result))
    assert line["correct"] is True, line["checked"]
    assert SPAN_METRICS[name] <= set(line["metrics"]), sorted(line["metrics"])
    for metric in SPAN_METRICS[name]:
        assert line["metrics"][metric]["value"] > 0
    # no device trace on the CPU: nothing to read, so left out, never 0
    assert not TRACE_METRICS & set(line["metrics"])
    # set-up really is mostly compile at toy size, and bounded by it
    setup = sum(line["metrics"][m]["value"] for m in
                ("setup_trace_lower_s", "setup_backend_compile_s"))
    assert setup < 600


def _reader(metric):
    return bench_run.load_reader(os.path.join(bench_toy.REPO, "benchmark"),
                                 metric)


def test_kernel_readers_match_names_jax_may_decorate():
    rec = types.SimpleNamespace(
        trace={"op_ms": {"peak_scores": 6.0, "bn_act_fwd": 9.0,
                         "detection_loss_fwd": 1.0,
                         "detection_loss_bwd": 3.0}},
        window={"images": 300, "steps": 8})
    assert _reader("peak_kernel_ms_per_img.bulk")(rec) == 6.0 / 300
    assert _reader("loss_kernel_ms_per_step")(rec) == 4.0 / 8
    rec.trace["op_ms"] = {"vmap_peak_scores_": 3.0,  # decorated: still read
                          "jvp_detection_loss_fwd_": 1.0}  # PR 24's: not
    assert _reader("peak_kernel_ms_per_img.bulk")(rec) == 3.0 / 300
    assert _reader("loss_kernel_ms_per_step")(rec) is None
    rec.trace["op_ms"] = {"bn_act_fwd": 9.0}
    assert _reader("peak_kernel_ms_per_img.bulk")(rec) is None


@pytest.mark.parametrize("quantity", [
    "engine_batch_fill", "engine_device_wait_ms_per_batch",
    "engine_dispatch_ms_per_batch", "peak_kernel_ms_per_img",
    "predict_device_ms_per_img", "predict_mfu"])
def test_a_hostbound_twin_reads_what_its_bulk_sibling_reads(quantity):
    """`<quantity>.hostbound` is `<quantity>.bulk` in the cell that reports
    `serve_img_per_s.hostbound`: one arithmetic under two names."""
    rec = types.SimpleNamespace(
        config=bench_toy.toy_fields("flagship-s1-w128"),
        e2e={"serve_img_per_s": 1000.0, "setup_s": 30.0},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"busy_s": 15.0, "op_ms": {"peak_scores": 6.0}},
        window={"images": 300, "counters": {"batch_slots": 320,
                                            "padded_slots": 20}},
        engine_spans=[("serve:batch-form", 0.01), ("serve:h2d", 0.002),
                      ("serve:dispatch", 0.001), ("serve:device-wait", 0.25)])
    value = _reader(quantity + ".hostbound")(rec)
    assert value is not None and value > 0
    assert value == _reader(quantity + ".bulk")(rec)


def test_span_readers_return_none_where_there_is_nothing_to_read():
    """A parent commit's engine (no `serve:dispatch`), a window the ring
    no longer covers, a program without the ring: None, never 0."""
    from benchmark import program_spans
    from real_time_helmet_detection_tpu.obs import spans
    rec = types.SimpleNamespace(
        engine_spans=[("serve:batch-form", 0.01), ("serve:h2d", 0.002),
                      ("serve:compute", 0.001), ("serve:d2h", 0.3)],
        window={"t0": 5.0, "window_s": 1.0, "steps": 4})
    assert _reader("engine_dispatch_ms_per_batch.bulk")(rec) is None
    assert _reader("engine_device_wait_ms_per_batch.bulk")(rec) is None
    rec.engine_spans += [("serve:dispatch", 0.001),
                         ("serve:device-wait", 0.25)]
    assert _reader("engine_dispatch_ms_per_batch.bulk")(rec) \
        == pytest.approx(13.0)
    assert _reader("engine_device_wait_ms_per_batch.bulk")(rec) \
        == pytest.approx(250.0)

    spans.reset_ring()
    tracer = spans.default_tracer()
    for i in range(4):
        tracer.record("dispatch", 0.002, t0=5.0 + 0.25 * i, step=i)
    tracer.record("dispatch", 0.5, t0=2.0)      # before the window
    tracer.record("compile", 2.0, t0=1.0, stage="trace")
    tracer.record("compile", 0.5, t0=1.5, stage="trace")   # nested
    tracer.record("compile", 1.0, t0=3.0, stage="lower")
    tracer.record("compile", 0.7, t0=4.0, stage="backend", cache_hit=True)
    tracer.record("compile", 9.0, t0=5.5, stage="backend")  # in the window
    assert _reader("train_dispatch_ms_per_step")(rec) == pytest.approx(2.0)
    assert _reader("train_h2d_ms_per_step")(rec) is None
    assert _reader("setup_trace_lower_s")(rec) == pytest.approx(3.0)
    assert _reader("setup_backend_compile_s")(rec) == pytest.approx(0.7)
    # the ring's start is gone: no partial sums
    small = spans.SpanTracer(None, ring=spans.SpanRing(2))
    for i in range(4):
        small.record("dispatch", 0.002, t0=5.0 + 0.25 * i)
    old = program_spans.ring_spans
    program_spans.ring_spans = lambda since: small.snapshot(since=since)
    try:
        assert _reader("train_dispatch_ms_per_step")(rec) is None
        assert _reader("setup_backend_compile_s")(rec) is None
    finally:
        program_spans.ring_spans = old
    spans.reset_ring()


def test_layer_trace_keeps_the_scope_map_of_a_cell(root, tmp_path):
    sys.path.insert(0, os.path.join(bench_toy.REPO, "scripts"))
    import layer_trace
    out = str(tmp_path / "layers")
    got = layer_trace.record("flagship-serve-bulk", 2 ** 31 + 26, 1.0, out,
                             root=root, allow_cpu=True)
    # (the CPU's trace has no device plane: the table is empty here)
    assert got["trace"] and os.path.exists(
        os.path.join(out, "flagship-serve-bulk.layers.txt"))
    with open(os.path.join(out, "flagship-serve-bulk.scopes.json")) as f:
        scopes = json.load(f)
    assert sorted(scopes) == ["bucket-2", "bucket-4"]
    layers = set(scopes["bucket-4"].values())
    assert {"stem", "hourglass", "neck", "head", "decode", "nms"} <= layers
    with open(os.path.join(out, "flagship-serve-bulk.window.json")) as f:
        note = json.load(f)
    assert note["images"] > 0 and note["compile_spans_in_window"] == 0
