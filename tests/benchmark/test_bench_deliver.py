"""The readers of the program's one record a served batch (`serve:deliver`,
PR 38: benchmark/deliver_records.py) and the four metrics that read it, on
hand-built rings and on toy runs of two cells on the CPU: a window's batches
are the records that start inside it, their rows sum to the window's
requests, and a program without the record (a parent) leaves every metric
out. (The reference has no benchmark: no analogue.)"""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import deliver_records, program_spans  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.work import attn_fused as work  # noqa: E402
from real_time_helmet_detection_tpu.obs import spans  # noqa: E402

READERS = ("attn_fused_roofline.gen", "q_block_live_share.gen",
           "attn_fused_block_share.gen", "engine_deliver_ms_per_batch.bulk")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(metric):
    return bench_run.load_reader(os.path.join(bench_toy.REPO, "benchmark"),
                                 metric)


def _config(name):
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)["fields"]


def _counts(rows, run=2, total=3, fused=1, visits=4):
    return {"gen.requests": rows, "gen.q_blocks_run": run * rows,
            "gen.q_blocks_total": total * rows,
            "gen.q_blocks_fused": fused * rows,
            "gen.attn_fused_visits": visits * rows}


def _ring(batches, others=True):
    """A fresh process ring holding `serve:deliver` records `(start, seconds,
    rows, counters)` and, around them, what else an engine writes."""
    spans.reset_ring()
    tracer = spans.default_tracer()
    for start, dur, rows, counters in batches:
        if others:
            tracer.record("serve:d2h", 0.001, t0=start - 0.001, b=4, n=rows)
        tracer.record("serve:deliver", dur, t0=start, b=4, n=rows,
                      counters=counters)
        if others:
            for i in range(rows):
                tracer.record("serve:e2e", 1.0, t0=start + dur - 1.0, b=4)


def _rec(t0=10.0, t1=14.0, **more):
    return types.SimpleNamespace(
        window=dict({"t0": t0, "t1": t1, "window_s": t1 - t0,
                     "counters": {}, "images": 0}, **more.pop("window", {})),
        **more)


@pytest.fixture(autouse=True)
def _clean_ring():
    spans.reset_ring()
    yield
    spans.reset_ring()


# ---- the window's batches --------------------------------------------------------

def test_the_windows_batches_are_the_records_that_start_inside_it():
    """The lead-in's batch (its last answer is t0) started before t0 and is
    out; the last whole batch (its last answer is t1) started before t1 and
    is in; the next one starts after t1 and is out."""
    _ring([(9.95, 0.02, 4, _counts(4)),    # lead-in: ends at t0
           (11.0, 0.02, 4, _counts(4)),
           (12.0, 0.03, 3, _counts(3)),
           (13.99, 0.02, 4, _counts(4)),   # its last answer is t1
           (14.01, 0.02, 4, _counts(4))])  # the next batch
    got = deliver_records.window_batches(_rec())
    assert [s for s, _, _ in got] == [11.0, 12.0, 13.99]
    assert sum(meta["n"] for _, _, meta in got) == 11
    assert deliver_records.counter_sums(_rec(), ("gen.requests",)) \
        == {"gen.requests": 11}


@pytest.mark.parametrize("case", ["no record", "no start", "start lost",
                                  "no batch inside", "counters missing",
                                  "a name missing"])
def test_window_batches_finds_nothing_where_there_is_nothing_to_read(case):
    rec = _rec()
    if case == "no record":  # a parent: its engine writes no such record
        tracer = spans.default_tracer()
        for i in range(4):
            tracer.record("serve:d2h", 0.01, t0=11.0 + i, b=4, n=4)
        assert deliver_records.window_batches(rec) is None
    elif case == "no start":
        _ring([(11.0, 0.02, 4, _counts(4))])
        rec.window.pop("t0")
        assert deliver_records.window_batches(rec) is None
    elif case == "start lost":
        small = spans.SpanTracer(None, ring=spans.SpanRing(2))
        for i in range(4):
            small.record("serve:deliver", 0.02, t0=10.5 + i, n=4,
                         counters=_counts(4))
        old = program_spans.ring_spans
        program_spans.ring_spans = lambda since: small.snapshot(since=since)
        try:
            assert deliver_records.window_batches(rec) is None
        finally:
            program_spans.ring_spans = old
    elif case == "no batch inside":
        _ring([(9.0, 0.02, 4, _counts(4)), (15.0, 0.02, 4, _counts(4))])
        assert deliver_records.window_batches(rec) == []
        assert deliver_records.counter_sums(rec, ("gen.requests",)) is None
        assert deliver_records.median_ms(rec) is None
    elif case == "counters missing":  # an engine without row_counters
        _ring([(11.0, 0.02, 4, None), (12.0, 0.02, 4, _counts(4))])
        assert len(deliver_records.window_batches(rec)) == 2
        assert deliver_records.counter_sums(rec, ("gen.requests",)) is None
        assert deliver_records.median_ms(rec) == pytest.approx(20.0)
    else:  # a program that counts no visits
        counts = _counts(4)
        counts.pop("gen.attn_fused_visits")
        _ring([(11.0, 0.02, 4, counts), (12.0, 0.02, 4, _counts(4))])
        assert deliver_records.counter_sums(
            rec, ("gen.attn_fused_visits",)) is None
        assert deliver_records.share(rec, "gen.q_blocks_run",
                                     "gen.q_blocks_total") \
            == pytest.approx(100 * 2 / 3)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.mark.parametrize("cell", ["quality-serve-bulk", "dots3-ep8-l5-gen8k"])
def test_the_rows_of_a_toy_windows_batches_are_its_requests(root, cell):
    """A toy window run as `run_cell` runs it: the records that start in it
    hold exactly the requests it counted, and (where the program feeds
    counters) `gen.requests` says the same."""
    manifest = bench_run.load_manifest(root)
    parts = bench_run.resolve_cell(root, manifest, cell)
    ctx = bench_run.Context(2 ** 31 + 41, parts["config"], parts["traffic"],
                            True)
    driver = parts["driver"].Cell(ctx)
    driver.setup()
    try:
        window = driver.run(1.0)
    finally:
        driver.free()
    rec = types.SimpleNamespace(window=window)
    batches = deliver_records.window_batches(rec)
    assert batches and window["images"] > 0
    assert sum(meta["n"] for _, _, meta in batches) == window["images"]
    assert all(meta["b"] >= meta["n"] for _, _, meta in batches)
    if cell.startswith("dots3"):
        assert deliver_records.counter_sums(rec, ("gen.requests",)) \
            == {"gen.requests": window["images"]}
        for metric in ("q_block_live_share.gen",
                       "attn_fused_block_share.gen"):
            assert 0 <= _reader(metric)(rec) <= 100
    assert _reader("engine_deliver_ms_per_batch.bulk")(rec) > 0


# ---- the four readers ------------------------------------------------------------

def _full_rec(config, p_max, ms=1000.0):
    return _rec(config=_config(config), traffic={"p_max": p_max},
                peaks=PEAKS, trace={"op_ms": {"attn_fused": ms}})


def test_each_reader_reads_the_windows_batches():
    _ring([(9.9, 0.05, 4, _counts(4, visits=100)),
           (11.0, 0.02, 4, _counts(4, visits=50)),
           (12.0, 0.04, 4, _counts(4, visits=25))])
    rec = _full_rec("dots3-note-prev-ep8-l5", 8192)
    flops = 4 * 75 * 2 * 128 * (128 + 64 + 128) * 512 * 1024
    assert _reader("attn_fused_roofline.gen")(rec) \
        == pytest.approx(100 * flops / 197e12 / 1.0)
    assert _reader("q_block_live_share.gen")(rec) == pytest.approx(200 / 3)
    assert _reader("attn_fused_block_share.gen")(rec) == pytest.approx(50.0)
    assert _reader("engine_deliver_ms_per_batch.bulk")(rec) \
        == pytest.approx(30.0)


def test_a_visit_is_one_q_block_by_the_kernels_key_block():
    dots3 = _config("dots3-note-prev-ep8-l5")
    assert work.key_block(8192) == 1024 and work.key_block(512) == 512
    assert work.key_block(16) == 16
    assert work.visit_flops(dots3, 8192, 512) \
        == 2 * 128 * 320 * 512 * 1024


def test_where_a_prompt_is_one_q_block_the_visits_read_as_the_blocks_do():
    """A.X-K1's prompts are one q block of 512 slots: one visit a fused
    block, so `.gen` reads what `attn_fused_roofline.latent` reads."""
    _ring([(11.0, 0.02, 32, _counts(32, run=5, total=5, fused=5,
                                    visits=5))])
    rec = _full_rec("axk1-ep8-l5", 512, ms=40.0)
    rec.window["counters"] = {"gen.q_blocks_fused": 5 * 32}
    got = _reader("attn_fused_roofline.gen")(rec)
    assert got == pytest.approx(_reader("attn_fused_roofline.latent")(rec))
    assert 0 < got < 100


@pytest.mark.parametrize("metric", READERS)
def test_each_reader_leaves_its_metric_out_on_a_parent(metric):
    """A parent's ring holds the engine's other spans (in an untraced run)
    or nothing of it (traced: its engine wrote only the benchmark's tracer),
    and its counters come through the driver's list alone: None, never a
    raise, never 0."""
    rec = _full_rec("dots3-note-prev-ep8-l5", 8192)
    rec.window["counters"] = _counts(16)
    rec.engine_spans = [("serve:d2h", 0.01), ("serve:device-wait", 1.2)]
    assert _reader(metric)(rec) is None
    tracer = spans.default_tracer()
    for i in range(4):
        tracer.record("serve:d2h", 0.01, t0=11.0 + i, b=4, n=4)
    assert _reader(metric)(rec) is None
