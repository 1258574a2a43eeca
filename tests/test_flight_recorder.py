"""The flight recorder inside the program (ISSUE 25): the always-on ring of
obs/spans.py, the spans the step runner and the serving engine record from
where they work, the one compile listener, and device operations mapped to
layers through the compiled HLO (obs/hlo_scopes.py + trace_summary).
All CPU, toy sizes."""

import gzip
import json
import os
import shutil
import sys
import time
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.obs import hlo_scopes  # noqa: E402
from real_time_helmet_detection_tpu.obs.spans import (  # noqa: E402
    RING_CAPACITY, SpanRing, SpanTracer, default_tracer, maybe_tracer,
    read_spans, reset_ring)
from real_time_helmet_detection_tpu.obs.telemetry import (  # noqa: E402
    install_compile_listener, install_recompile_counter)
from real_time_helmet_detection_tpu.optim import build_optimizer  # noqa: E402
from real_time_helmet_detection_tpu.parallel import (  # noqa: E402
    make_mesh, replicated)
from real_time_helmet_detection_tpu.predict import \
    make_predict_fn  # noqa: E402
from real_time_helmet_detection_tpu.serving import ServingEngine  # noqa: E402
from real_time_helmet_detection_tpu.train import (  # noqa: E402
    create_train_state, init_variables, make_step_runner, train_epoch)

IMSIZE = 64
LAYERS_TRAIN = {"stem", "hourglass", "neck", "head", "loss", "optimizer",
                "stem/bwd", "hourglass/bwd", "neck/bwd", "head/bwd",
                "loss/bwd"}
LAYERS_PREDICT = {"normalize", "stem", "hourglass", "neck", "head",
                  "decode", "nms"}


@pytest.fixture
def own_compiles():
    """Compile without the persistent cache: its key leaves metadata out,
    so a hit returns the executable of whichever checkout filled the
    entry, with that checkout's scope names."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# the ring


def test_ring_is_bounded_and_counts_what_it_overwrote():
    ring = SpanRing(8)
    t = SpanTracer(None, ring=ring)
    for i in range(20):
        t.record("step", 0.001, it=i)
    spans = t.snapshot()
    assert len(spans) == 8 and t.dropped == 12
    assert [m["it"] for _, _, _, m in spans] == list(range(12, 20))
    assert RING_CAPACITY == 1 << 17


def test_ring_spans_sit_on_the_monotonic_clock():
    t = SpanTracer(None, ring=SpanRing(16))
    before = time.monotonic()
    with t.span("h2d", b=2) as sp:
        time.sleep(0.002)
    t.record("fetch", 0.5)              # measured by the caller: ends now
    t.record("compile", 0.25, t0=12.5)  # an explicit start wins
    t.event("beat")
    after = time.monotonic()
    (n0, s0, d0, m0), (n1, s1, d1, _), (n2, s2, d2, _), (n3, s3, d3, m3) = \
        t.snapshot()
    assert (n0, m0) == ("h2d", {"b": 2}) and d0 == sp.dur_s >= 0.002
    assert before <= s0 <= s0 + d0 <= after
    assert n1 == "fetch" and d1 == 0.5
    assert before - 0.5 <= s1 <= after - 0.5   # t0 = now - dur_s
    assert (n2, s2, d2) == ("compile", 12.5, 0.25)
    assert (n3, d3, m3) == ("beat", 0.0, None) and before <= s3 <= after


def test_snapshot_since_never_returns_a_partial_window():
    t = SpanTracer(None, ring=SpanRing(4))
    for i in range(3):
        t.record("step", 1.0, t0=10.0 * i)        # [0,1] [10,11] [20,21]
    assert [s for _, s, _, _ in t.snapshot(since=10.0)] == [10.0, 20.0]
    assert t.snapshot(since=30.0) == []
    for i in range(3, 6):
        t.record("step", 1.0, t0=10.0 * i)        # ring keeps 20..50
    assert t.dropped == 2
    # everything that ended after 21 is still there: a whole window
    assert [s for _, s, _, _ in t.snapshot(since=21.0)] == [30.0, 40.0, 50.0]
    # the oldest kept span ended at 21 > 15: spans from 15 on may be gone
    assert t.snapshot(since=15.0) is None
    assert t.snapshot(since=0.0) is None
    assert len(t.snapshot()) == 4  # no `since`: what there is, with dropped


def test_no_file_is_touched_without_a_path(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_SPAN_LOG", raising=False)
    monkeypatch.chdir(tmp_path)
    t = maybe_tracer()
    assert t is default_tracer() and not t.enabled and t.path is None
    with t.span("dispatch", step=0):
        pass
    t.record("loader-wait", 0.1)
    t.event("recover:skip-step")
    t.context(phase="test")
    t.close()
    assert list(tmp_path.iterdir()) == []
    names = [n for n, _, _, _ in t.snapshot()]
    assert names[-4:] == ["dispatch", "loader-wait", "recover:skip-step",
                          "context"]


def test_a_file_tracer_feeds_the_ring_and_the_log(tmp_path):
    """`maybe_tracer(path)`: the same process-wide ring, the JSONL as
    well, in the file format obs_report reads (wall `t`, `t0` on traced
    records)."""
    path = str(tmp_path / "spans.jsonl")
    t = maybe_tracer(path)
    assert t.enabled and t is not default_tracer()
    with t.span("checkpoint", epoch=3):
        pass
    t.record("serve:e2e", 0.02)
    t.close()
    ring = [(n, m) for n, _, _, m in default_tracer().snapshot()][-2:]
    assert ring == [("checkpoint", {"epoch": 3}), ("serve:e2e", None)]
    recs = [r for r in read_spans(path) if r.get("kind") == "span"]
    assert [r["name"] for r in recs] == ["checkpoint", "serve:e2e"]
    assert recs[0]["meta"] == {"epoch": 3}
    assert abs(recs[0]["t"] - time.time()) < 60  # wall clock in the file


# ---------------------------------------------------------------------------
# the step runner records from inside


def _tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=2,
                lr=1e-3, imsize=IMSIZE)
    base.update(kw)
    return Config(**base)


def _host_batch(b=2, seed=0):
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.data.pipeline import Batch
    im, hm, off, wh, mask = synthetic_target_batch(b, IMSIZE, seed=seed)
    empty = np.zeros((b, 0), np.float32)
    return Batch(image=im, heatmap=hm, offset=off, wh=wh, mask=mask,
                 boxes=empty, labels=empty, valid=empty, infos=[{}] * b)


def _raw_batch(b=2):
    from real_time_helmet_detection_tpu.data.pipeline import Batch
    rng = np.random.default_rng(0)
    boxes = np.zeros((b, 8, 4), np.float32)
    valid = np.zeros((b, 8), bool)
    boxes[:, 0] = [8, 8, 40, 40]
    valid[:, 0] = True
    empty = np.zeros((b, 0, 0, 0), np.float32)
    return Batch(image=rng.integers(0, 255, (b, IMSIZE, IMSIZE, 3)
                                    ).astype(np.uint8),
                 heatmap=empty, offset=empty, wh=empty, mask=empty,
                 boxes=boxes, labels=np.zeros((b, 8), np.int32),
                 valid=valid, infos=[{}] * b)


def _runner_parts(cfg):
    model = build_model(cfg)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    return model, tx, state


@pytest.mark.parametrize("path", ["host", "device-augment", "cached"])
def test_step_runner_emits_dispatch_and_h2d(path):
    """One `dispatch` a step in all three input paths, one `h2d` a step
    in the two that stage, whoever calls the runner."""
    device = path != "host"
    cfg = _tiny_cfg(device_augment=device, multiscale=[64, 64, 64],
                    multiscale_flag=False)
    model, tx, state = _runner_parts(cfg)
    mesh = make_mesh(1)
    cache = None
    if path == "cached":
        raw = _raw_batch(4)
        cache = types.SimpleNamespace(**{
            k: jax.device_put(getattr(raw, k), replicated(mesh))
            for k in ("images", "boxes", "labels", "valid")
            if k != "images"}, images=jax.device_put(raw.image,
                                                     replicated(mesh)))
    runner = make_step_runner(cfg, mesh, model, tx, cache=cache)
    batch = {"host": _host_batch(), "device-augment": _raw_batch(),
             "cached": np.array([0, 1], np.int32)}[path]
    t_start = time.monotonic()
    for i in range(3):
        state, losses = runner(state, batch, i)
    assert np.isfinite(float(losses["total"]))
    spans = default_tracer().snapshot(since=t_start)
    assert spans is not None
    dispatch = [m["step"] for n, _, _, m in spans if n == "dispatch"]
    assert dispatch == [0, 1, 2]
    assert sum(n == "h2d" for n, _, _, _ in spans) == \
        (0 if path == "cached" else 3)
    assert hasattr(runner, "stage") == (path != "cached")


def test_train_epoch_records_each_transfer_once(tmp_path):
    """`train_epoch` over the runner with `--device-prefetch`: the
    prefetcher calls `stage`, `stage` records `h2d` — one record a
    transfer, beside the loop's own `loader-wait`, `step`, `fetch`."""
    from real_time_helmet_detection_tpu.ops.loss import LossLog
    cfg = _tiny_cfg(device_prefetch=1, print_interval=2,
                    save_path=str(tmp_path))
    model, tx, state = _runner_parts(cfg)
    mesh = make_mesh(1)
    runner = make_step_runner(cfg, mesh, model, tx)

    class Loader:
        def set_epoch(self, e):
            pass

        def __len__(self):
            return 4

        def __iter__(self):
            return iter([_host_batch(seed=i) for i in range(4)])

    state = jax.device_put(state, replicated(mesh))
    t_start = time.monotonic()
    train_epoch(cfg, 0, Loader(), runner, state, mesh, LossLog(),
                is_chief=False)
    names = [n for n, _, _, _ in default_tracer().snapshot(since=t_start)]
    assert names.count("h2d") == 4 and names.count("dispatch") == 4
    assert names.count("loader-wait") == 4 and names.count("step") == 4
    assert names.count("fetch") >= 2


# ---------------------------------------------------------------------------
# the engine records from inside, through the narrow tracer surface


class NarrowTracer:
    """Only what the benchmark's stand-in implements: the engine may call
    nothing else of its tracer."""
    enabled = False

    def __init__(self):
        self.names = []

    def span(self, name, ctx=None, links=None, **meta):
        tracer = self

        class _CM:
            dur_s = None

            def __enter__(self):
                self.t0 = time.monotonic()
                return self

            def __exit__(self, *exc):
                self.dur_s = time.monotonic() - self.t0
                tracer.names.append(name)

        return _CM()

    def record(self, name, dur_s, ctx=None, links=None, **meta):
        self.names.append(name)

    def event(self, name, ctx=None, links=None, **meta):
        pass


@pytest.fixture(scope="module")
def serve_parts():
    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, topk=16,
                 conf_th=0.0, nms_th=0.5, imsize=IMSIZE)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0), IMSIZE)
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    rng = np.random.default_rng(3)
    pool = [rng.integers(0, 256, (IMSIZE, IMSIZE, 3), dtype=np.uint8)
            for _ in range(4)]
    return predict, {"params": params, "batch_stats": batch_stats}, pool


def test_engine_serves_through_a_narrow_tracer(serve_parts):
    from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
    predict, variables, pool = serve_parts
    tracer, registry = NarrowTracer(), MetricsRegistry()
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(2,), max_wait_ms=1.0, tracer=tracer,
                        metrics=registry)
    got = eng.predict_many(pool[:2])
    eng.close()
    assert len(got) == 2
    assert {"serve:lower", "serve:compile", "serve:queue-wait",
            "serve:batch-form", "serve:h2d", "serve:dispatch",
            "serve:device-wait", "serve:d2h", "serve:deliver",
            "serve:e2e"} <= set(tracer.names)
    assert "serve:compute" not in tracer.names
    assert "serve:inflight-wait" not in tracer.names
    # the wait for the device comes before the copy of a finished batch
    assert tracer.names.index("serve:device-wait") \
        < tracer.names.index("serve:d2h") \
        < tracer.names.index("serve:deliver")
    # one histogram left: the five per-stage ones repeated the spans
    snap = registry.snapshot()
    assert list(snap["histograms"]) == ["serve.e2e_ms"]
    assert snap["histograms"]["serve.e2e_ms"]["count"] == 2
    assert snap["counters"]["serve.batch_slots"] == 2
    assert snap["counters"]["serve.padded_slots"] == 0
    assert snap["counters"]["serve.completed"] == 2


def test_collector_stays_out_of_the_delivery_loop(serve_parts):
    """A batch's answers are delivered with the cyclic GC held off (a
    full collection starting mid-loop held half a batch 60-130 ms on the
    chip's host), and it is back on afterwards."""
    import gc
    predict, variables, pool = serve_parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(2,), max_wait_ms=50.0)
    seen = []
    futs = [eng.submit(img) for img in pool[:2]]
    for f in futs:
        f.add_done_callback(lambda _f: seen.append(gc.isenabled()))
    for f in futs:
        f.result(timeout=60)
    eng.close()
    assert seen == [False, False] and gc.isenabled()


def test_engine_scope_maps_name_every_predict_layer(serve_parts,
                                                    own_compiles):
    predict, variables, _ = serve_parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1, 2), start=False)
    maps = eng.scope_maps()
    eng.close()
    assert sorted(maps) == [1, 2]
    for scopes in maps.values():
        shares = hlo_scopes.layer_shares(scopes)
        assert LAYERS_PREDICT <= set(shares), sorted(shares)
        assert shares.get(hlo_scopes.UNATTRIBUTED, 0.0) < 0.05, shares


# ---------------------------------------------------------------------------
# the compile listener


def test_compile_listener_is_installed_once(serve_parts):
    """However many runners and engines are built, one listener: a forced
    compile lands as ONE `compile` span with `stage=backend`, with a
    time, and the counters are views that count from their call on."""
    predict, variables, _ = serve_parts
    listener = install_compile_listener()
    cfg = _tiny_cfg()
    model, tx, _ = _runner_parts(cfg)
    for _ in range(2):
        make_step_runner(cfg, make_mesh(1), model, tx)
    ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                  buckets=(1,), start=False).close()
    assert install_compile_listener() is listener
    ours = [cb for cb in
            jax._src.monitoring.get_event_duration_listeners()
            if getattr(cb, "__self__", None) is listener]
    assert len(ours) == 1

    x = jnp.ones((7,))  # made before the counters: its own small compile
    first, second = install_recompile_counter(), None
    t_start = time.monotonic()

    @jax.jit
    def listener_probe(v):  # a name no other test compiles
        return v * 5.0 - 2.0

    listener_probe(x).block_until_ready()
    second = install_recompile_counter()
    assert first.count >= 1 and second.count == 0
    assert first.last_dur_s is not None and second.last_dur_s is None
    spans = [(s, d, m) for n, s, d, m in
             default_tracer().snapshot(since=t_start - 60.0)
             if n == "compile" and m
             and "listener_probe" in str(m.get("fun"))]
    stages = [m["stage"] for _, _, m in spans]
    assert stages.count("backend") == 1
    assert {"trace", "lower", "backend"} <= set(stages)
    s, d, m = next(x for x in spans if x[2]["stage"] == "backend")
    assert t_start <= s and s + d <= time.monotonic() + 1e-3
    assert m["cache_hit"] in (True, False)


# ---------------------------------------------------------------------------
# instruction -> layer


def test_layer_of_reads_scope_paths():
    f = hlo_scopes.layer_of
    assert f("jit(step)/jvp(StackedHourglass)/PreLayer_0/Conv_0/conv") \
        == "stem"
    assert f("jit(step)/transpose(jvp(StackedHourglass))/Hourglass_0/"
             "Residual_1/add") == "hourglass/bwd"
    assert f("jit(step)/jvp(loss)/detection_loss_fwd/pallas_call") == "loss"
    assert f("jit(step)/transpose(jvp(loss))/mul") == "loss/bwd"
    assert f("jit(step)/optimizer/sqrt") == "optimizer"
    assert f("jit(predict_impl)/peak/vmap(vmap(jit(_fused_chw)))/"
             "peak_scores/pallas_call") == "peak"
    assert f("jit(predict_impl)/StackedHourglass/Convolution_0/Conv_0/x") \
        == "merge"
    assert f("jit(predict_impl)/nms/vmap(while)/body/sub") == "nms"
    assert f("jit(step)/add") == "other"
    assert f("jit(predict_impl)/StackedHourglass/convert_element_type") \
        == "other"


def test_scope_map_follows_unnamed_copies_to_a_layer():
    text = """HloModule m

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(f)/jvp(StackedHourglass)/Head_0/neg"}
}

ENTRY %main (w: f32[4], x: f32[4]) -> (f32[4], f32[4]) {
  %w = f32[4]{0} parameter(0), metadata={op_name="state.params[\\'Neck_0\\'][\\'kernel\\']"}
  %x = f32[4]{0} parameter(1), metadata={op_name="images"}
  %copy.1 = f32[4]{0} copy(%w)
  %copy.2 = f32[4]{0} copy(%x)
  %fusion.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation
  %add.4 = f32[4]{0} add(%copy.1, %fusion.3), metadata={op_name="jit(f)/jvp(StackedHourglass)/Neck_0/add"}
  %mul.5 = f32[4]{0} multiply(%copy.1, %copy.1), metadata={op_name="jit(f)/optimizer/mul"}
  %copy.6 = f32[4]{0} copy(%add.4)
  ROOT %tuple.7 = (f32[4]{0}, f32[4]{0}) tuple(%copy.6, %mul.5)
}
"""
    assert hlo_scopes.scope_map(text) == {
        # read by the neck and by the optimizer: the weight's own module
        "copy.1": "neck",
        "copy.2": "head",     # feeds one fusion, which has its root's name
        "fusion.3": "head",
        "add.4": "neck",
        "mul.5": "optimizer",
        "copy.6": "neck",     # feeds only the result tuple: reads `add.4`
        "tuple.7": "unattributed",
    }


def test_scope_map_names_every_layer_of_the_toy_train_step(own_compiles):
    cfg = _tiny_cfg()
    model, tx, state = _runner_parts(cfg)
    mesh = make_mesh(1)
    runner = make_step_runner(cfg, mesh, model, tx)
    state = jax.device_put(state, replicated(mesh))
    scopes = runner.scope_map(state, runner.stage(_host_batch()))
    shares = hlo_scopes.layer_shares(scopes)
    assert LAYERS_TRAIN <= set(shares), sorted(shares)
    assert shares.get(hlo_scopes.UNATTRIBUTED, 0.0) < 0.05, shares
    assert len(scopes) > 200


# ---------------------------------------------------------------------------
# trace + scope map -> device seconds by layer


def test_self_times_count_no_while_body_twice():
    import trace_summary
    ops = [("%while.11 = ...", 0, 100), ("%a.1 = ...", 10, 30),
           ("%b.2 = ...", 40, 60), ("%c.3 = ...", 45, 50),
           ("%d.4 = ...", 120, 130)]
    got = dict(trace_summary.self_times(ops))
    assert got == {"%while.11 = ...": 60, "%a.1 = ...": 20,
                   "%b.2 = ...": 15, "%c.3 = ...": 5, "%d.4 = ...": 10}
    assert sum(got.values()) == 110  # the union of the intervals
    summary = trace_summary.by_layer(
        {"/device:TPU:0": ops}, {"while.11": "nms", "a.1": "nms",
                                 "b.2": "decode", "c.3": "decode"})
    assert summary["layers"] == pytest.approx(
        {"nms": 80e-9, "decode": 20e-9, "unattributed": 10e-9})
    text = trace_summary.render(summary, {}, top=3)
    assert text.splitlines()[-5].split()[0] == "unattributed"


def test_trace_summary_reproduces_the_recorded_kernel_times(tmp_path):
    """The recorded chip trace with a hand-made map (each Pallas kernel
    its own layer): per-kernel milliseconds as recorded beside it."""
    import trace_summary
    from benchmark import trace_reduce
    data = os.path.join(REPO, "benchmark", "testdata")
    with open(os.path.join(data, "train2.expected.json")) as f:
        want = json.load(f)
    pb = str(tmp_path / "train2.xplane.pb")
    with gzip.open(os.path.join(data, "train2.xplane.pb.gz")) as src, \
            open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    devices, marks = trace_reduce.read_planes(pb)
    offset = trace_reduce.clock_offset_ns(marks, want["marks_host_s"])
    window = tuple(t * 1e9 + offset for t in want["window_host_s"])
    kernels = want["expected"]["kernel_ms"]
    scopes = {}
    for events in devices.values():
        for name, _, _ in events:
            inst = trace_reduce.op_instance(name)
            kind = trace_reduce.op_name(name)
            scopes[inst] = kind if kind in kernels else "rest"
    scopes_path = str(tmp_path / "scopes.json")
    with open(scopes_path, "w") as f:
        json.dump({"step": scopes}, f)
    summary = trace_summary.by_layer(
        trace_summary.open_trace(pb),
        trace_summary.load_scopes(scopes_path), window)
    for kind, ms in kernels.items():
        assert summary["layers"][kind] * 1e3 == pytest.approx(ms, rel=1e-9)
    # nothing nests in this trace: self time adds up to busy time
    assert summary["busy_s"] == pytest.approx(want["expected"]["busy_s"],
                                              rel=1e-9)
    assert trace_summary.main([pb, "--scopes", scopes_path, "--window-ns",
                               str(window[0]), str(window[1])]) == 0


def test_reset_ring_empties_the_process_ring():
    default_tracer().record("step", 0.1)
    reset_ring()
    assert default_tracer().snapshot() == [] and default_tracer().dropped == 0
