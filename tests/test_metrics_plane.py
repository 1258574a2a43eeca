"""Live metrics plane + SLO watchdog + perf gate tests (ISSUE 10).

Pins the tentpole contracts: registry thread-safety, histogram merge
associativity (the roll-up law), crash-safe snapshot export (kill -9
tears at most the final JSONL line; the .latest sidecar is always one
complete snapshot), SLO alert determinism under a canned FaultSchedule
replay, the metrics-OFF acceptance (identical D2H fetch counts and
bit-identical results with $OBS_METRICS set or unset — the plane is
host bookkeeping, never a program change), and the perfgate ratchet
(real-subprocess --selfcheck incl. the seeded +20% step-time regression
FAILING, plus the committed ledger gating clean at HEAD).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.obs.metrics import (  # noqa: E402
    Histogram, MetricsRegistry, MetricsWriter, latest_path, read_latest,
    read_metrics, snapshot_digest)
from real_time_helmet_detection_tpu.obs.slo import (  # noqa: E402
    DriftDetector, ErrorBurnRule, LatencyBurnRule, SloWatchdog,
    default_serving_rules, default_train_rules)
from real_time_helmet_detection_tpu.predict import \
    make_predict_fn  # noqa: E402
from real_time_helmet_detection_tpu.runtime import (  # noqa: E402
    ChaosInjector, FaultSchedule)
from real_time_helmet_detection_tpu.serving import (  # noqa: E402
    DEGRADED, SERVING, ServingEngine)
from real_time_helmet_detection_tpu.train import init_variables  # noqa: E402

IMSIZE = 64


# ---------------------------------------------------------------------------
# registry primitives


def test_counter_and_histogram_thread_safety():
    """8 writer threads hammering one counter + one histogram lose
    nothing: totals are exact (the serving engine increments from its
    dispatcher, fetcher AND client threads)."""
    reg = MetricsRegistry()
    c = reg.counter("t.hits")
    h = reg.histogram("t.lat_ms")
    n_threads, n_each = 8, 500

    def worker(tid):
        for i in range(n_each):
            c.inc()
            h.observe(1.0 + (tid * n_each + i) % 100)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_each
    assert h.count == n_threads * n_each
    snap = h.snapshot()
    assert sum(snap["buckets"]) == n_threads * n_each


def test_histogram_merge_associative_and_commutative():
    """The roll-up law: per-thread/per-phase histograms merge into one
    digest regardless of grouping or order (integer bucket addition)."""
    rng = np.random.default_rng(7)
    parts = []
    for i in range(3):
        h = Histogram("p%d" % i)
        for v in rng.lognormal(mean=2.0, sigma=1.5, size=200):
            h.observe(float(v))
        parts.append(h)
    a, b, c = parts

    def merged(*hs):
        out = Histogram.from_snapshot("m", hs[0].snapshot())
        for h in hs[1:]:
            out.merge(h)
        return out.snapshot()

    left = merged(a, b, c)        # (a + b) + c
    right = merged(b, c, a)       # (b + c) + a
    for key in ("count", "buckets", "min", "max"):
        assert left[key] == right[key]
    assert abs(left["total"] - right["total"]) < 1e-6
    with pytest.raises(ValueError):
        Histogram("x", sub=4).merge(Histogram("y", sub=8))


def test_histogram_quantiles_and_fixed_snapshot_size():
    h = Histogram("q")
    vals = list(range(1, 101))  # 1..100
    for v in vals:
        h.observe(v)
    # ~9% bucket resolution at sub=8: p50 near 50, p99 near 99
    assert abs(h.quantile(0.50) - 50) <= 5
    assert abs(h.quantile(0.99) - 99) <= 9
    assert h.quantile(0.0) >= h.min and h.quantile(1.0) <= h.max
    assert h.mean == pytest.approx(np.mean(vals))
    # constant-size snapshots: bucket layout independent of traffic
    empty = Histogram("e")
    assert len(h.snapshot()["buckets"]) == len(empty.snapshot()["buckets"])
    assert empty.quantile(0.5) is None
    # roundtrip preserves digesting
    back = Histogram.from_snapshot("q2", h.snapshot())
    assert back.quantile(0.5) == h.quantile(0.5)
    assert snapshot_digest({"histograms": {"q": h.snapshot()}})[
        "histograms"]["q"]["count"] == 100


def test_registry_snapshot_and_digest_prefix():
    reg = MetricsRegistry()
    reg.counter("serve.completed").inc(3)
    reg.counter("train.steps").inc(5)
    reg.gauge("serve.queue_depth").set(2)
    reg.histogram("serve.e2e_ms").observe(10.0)
    snap = reg.snapshot()
    assert snap["schema"] == "obs-metrics-v1"
    assert snap["counters"] == {"serve.completed": 3, "train.steps": 5}
    d = reg.digest(prefix="serve.")
    assert set(d["counters"]) == {"serve.completed"}
    assert d["histograms"]["serve.e2e_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# crash-safe export


def test_writer_appends_lines_and_latest_sidecar(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry()
    w = MetricsWriter(reg, path, period_s=0.0)
    reg.counter("a").inc()
    assert w.maybe_flush(force=True)
    reg.counter("a").inc()
    w.close()  # close forces the final snapshot
    snaps = read_metrics(path)
    assert [s["counters"]["a"] for s in snaps] == [1, 2]
    assert read_latest(path)["counters"]["a"] == 2
    assert os.path.exists(latest_path(path))
    # disabled writer: no file, no error
    w2 = MetricsWriter(reg, None)
    assert not w2.maybe_flush(force=True)
    w2.close()


def test_writer_period_gates_flushes(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    w = MetricsWriter(MetricsRegistry(), path, period_s=3600.0)
    assert w.maybe_flush()            # first flush always lands
    assert not w.maybe_flush()        # inside the period: gated
    assert w.maybe_flush(force=True)  # force overrides
    w.close()


def test_writer_first_flush_lands_on_a_fresh_host(tmp_path, monkeypatch):
    """The first flush does not wait for the host's monotonic clock (its
    uptime, on Linux) to pass one period: a machine up for ten seconds
    exports at once, then gates."""
    import types

    from real_time_helmet_detection_tpu.obs import metrics
    clock = types.SimpleNamespace(monotonic=lambda: clock.now,
                                  time=time.time, now=10.0)
    monkeypatch.setattr(metrics, "time", clock)  # the module's view only
    w = MetricsWriter(MetricsRegistry(), str(tmp_path / "metrics.jsonl"),
                      period_s=3600.0)
    assert w.maybe_flush()            # at 10 s of uptime
    clock.now = 11.0
    assert not w.maybe_flush()        # one second later: gated
    clock.now = 3611.0
    assert w.maybe_flush()            # one period after the first
    w.close()


_KILL9_WRITER = """
import os, sys
sys.path.insert(0, %r)
from real_time_helmet_detection_tpu.obs.metrics import (MetricsRegistry,
                                                        MetricsWriter)
reg = MetricsRegistry()
w = MetricsWriter(reg, sys.argv[1], period_s=0.0)
i = 0
while True:
    reg.counter("spin").inc()
    w.maybe_flush(force=True)
    i += 1
    if i == 5:
        print("ready", flush=True)
""" % REPO


def test_kill9_tears_at_most_final_line(tmp_path):
    """Acceptance: a snapshot writer killed -9 mid-export leaves a
    readable timeline (torn tail dropped) and a complete .latest
    sidecar (tmp+replace can only swap whole files)."""
    path = str(tmp_path / "metrics.jsonl")
    proc = subprocess.Popen([sys.executable, "-c", _KILL9_WRITER, path],
                            stdout=subprocess.PIPE, text=True)
    assert proc.stdout.readline().strip() == "ready"
    time.sleep(0.05)  # let it race ahead mid-write
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=10)
    snaps = read_metrics(path)
    assert len(snaps) >= 5
    # every parsed snapshot is complete and monotonic
    counts = [s["counters"]["spin"] for s in snaps]
    assert counts == sorted(counts)
    latest = read_latest(path)
    assert latest is not None and latest["counters"]["spin"] >= counts[0]


# ---------------------------------------------------------------------------
# SLO watchdog determinism


def test_drift_detector_deterministic_and_rearming():
    series = [100.0] * 30 + [180.0] + [100.0] * 10 + [175.0]

    def run():
        rule_set = default_train_rules(z_thresh=4.0, warmup=10)
        wd = SloWatchdog(rule_set)
        for v in series:
            wd.observe("train.step_ms", v)
        return [(a["rule"], round(a["value"], 1)) for a in wd.alerts]

    first, second = run(), run()
    assert first == second  # replay-deterministic
    assert [r for r, _ in first] == ["train-step-drift",
                                    "train-step-drift"]
    assert [v for _, v in first] == [180.0, 175.0]


def test_drift_detector_flat_series_never_divides_by_zero():
    d = DriftDetector(warmup=5, z_thresh=4.0)
    for _ in range(50):
        assert d.observe(10.0) is None  # flat series: no alert, no inf


def test_error_burn_rule_windows_and_rearms():
    reg = MetricsRegistry()
    rule = ErrorBurnRule("r", err="e", total="t", objective=0.1, burn=2.0)
    wd = SloWatchdog([rule], registry=reg)
    reg.counter("t").inc(10)
    assert wd.check() == []                # 0/10: clean
    reg.counter("e").inc(5)
    reg.counter("t").inc(10)
    assert [a["rule"] for a in wd.check()] == ["r"]  # 5/10 > 0.2
    reg.counter("e").inc(5)
    reg.counter("t").inc(10)
    assert wd.check() == []                # still bad: armed, no re-alert
    reg.counter("t").inc(10)
    assert wd.check() == []                # clean window: re-arms
    reg.counter("e").inc(9)
    reg.counter("t").inc(10)
    assert [a["rule"] for a in wd.check()] == ["r"]  # fires again


def test_latency_burn_rule_over_histogram_window():
    reg = MetricsRegistry()
    rule = LatencyBurnRule("lat", hist="h", threshold=100.0,
                           objective=0.05, burn=2.0, min_count=8)
    wd = SloWatchdog([rule], registry=reg)
    h = reg.histogram("h")
    for _ in range(10):
        h.observe(10.0)
    assert wd.check() == []
    for _ in range(5):
        h.observe(10.0)
    for _ in range(5):
        h.observe(500.0)  # half the new window over budget
    assert [a["rule"] for a in wd.check()] == ["lat"]


# ---------------------------------------------------------------------------
# engine integration: metrics-off acceptance + deterministic alerts


@pytest.fixture(scope="module")
def parts():
    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, topk=16,
                 conf_th=0.0, nms_th=0.5, imsize=IMSIZE)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0), IMSIZE)
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    rng = np.random.default_rng(3)
    pool = [rng.integers(0, 256, (IMSIZE, IMSIZE, 3), dtype=np.uint8)
            for _ in range(8)]
    return predict, variables, pool


def _run_stream(predict, variables, pool, monkeypatch, count_device_get,
                export_path):
    """One deterministic request stream; returns (device_get count,
    detection bytes, final stats)."""
    if export_path:
        monkeypatch.setenv("OBS_METRICS", export_path)
    else:
        monkeypatch.delenv("OBS_METRICS", raising=False)
    with count_device_get() as counter:
        eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3),
                            np.uint8, buckets=(1, 2), max_wait_ms=0.0,
                            depth=1, queue_capacity=16,
                            metrics=MetricsRegistry())
        rows = []
        for i in range(6):
            rows.append(eng.submit(pool[i % len(pool)]).result(timeout=30))
        eng.close()
    blob = b"".join(np.asarray(r.boxes).tobytes() + np.asarray(
        r.scores).tobytes() for r in rows)
    return counter.count, blob, eng.stats()


def test_metrics_off_same_fetches_and_bits(parts, monkeypatch, tmp_path,
                                           count_device_get):
    """Acceptance: $OBS_METRICS unset runs the exact same programs — the
    engine performs the SAME number of device_get calls and returns
    bit-identical detections as with export armed (the metrics plane is
    host bookkeeping riding existing completion points, count-pinned
    like the PR 6 telemetry and PR 9 sentinel contracts)."""
    predict, variables, pool = parts
    export = str(tmp_path / "metrics.jsonl")
    n_on, blob_on, st_on = _run_stream(predict, variables, pool,
                                       monkeypatch, count_device_get,
                                       export)
    n_off, blob_off, st_off = _run_stream(predict, variables, pool,
                                          monkeypatch, count_device_get,
                                          None)
    assert n_on == n_off            # zero extra D2H fetches
    assert blob_on == blob_off      # bit-identical results
    assert st_on["completed"] == st_off["completed"] == 6
    # and the armed run actually exported
    assert read_metrics(export), "export armed but no snapshot written"
    assert not os.path.exists(str(tmp_path / "never.jsonl"))


def test_slo_alerts_deterministic_under_fault_replay(parts):
    """Acceptance: the watchdog's alerts derive from the deterministic
    batch-outcome sequence — replaying the SAME FaultSchedule over the
    SAME sequential stream yields the SAME alert list, and the alert
    flips the engine to DEGRADED before retries exhaust anything."""
    predict, variables, pool = parts
    spec = "serve:dispatch=device-loss@2,serve:dispatch=device-loss@5"

    def run():
        reg = MetricsRegistry()
        wd = SloWatchdog(default_serving_rules(objective=0.05, burn=2.0),
                         registry=reg)
        inj = ChaosInjector(FaultSchedule.parse(spec))
        eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3),
                            np.uint8, buckets=(1, 2), max_wait_ms=0.0,
                            depth=1, queue_capacity=16, max_retries=3,
                            metrics=reg, watchdog=wd, injector=inj)
        states = []
        for i in range(6):
            eng.submit(pool[i % len(pool)]).result(timeout=30)
            states.append(eng.state)
        eng.close()
        return [a["rule"] for a in wd.alerts], states, eng.stats()

    alerts_a, states_a, st_a = run()
    alerts_b, states_b, st_b = run()
    assert alerts_a == alerts_b                      # replay-identical
    assert "serve-error-burn" in alerts_a            # the burn fired
    assert DEGRADED in states_a                      # watchdog flipped it
    assert st_a["failed"] == st_b["failed"] == 0     # zero lost acks
    assert st_a["retried"] == st_b["retried"] >= 2


def test_engine_degrade_api_recovers_after_healthy_batches(parts):
    predict, variables, pool = parts
    eng = ServingEngine(predict, variables, (IMSIZE, IMSIZE, 3), np.uint8,
                        buckets=(1,), max_wait_ms=0.0, depth=1,
                        queue_capacity=8, recover_after=2,
                        metrics=MetricsRegistry())
    try:
        eng.submit(pool[0]).result(timeout=30)
        assert eng.state == SERVING
        eng.degrade("test alert")
        assert eng.state == DEGRADED
        assert "degraded: test alert" in eng.health()["last_error"]
        for i in range(3):
            eng.submit(pool[i % len(pool)]).result(timeout=30)
        time.sleep(0.05)  # recovery bookkeeping rides the fetcher thread
        assert eng.state == SERVING
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# train_epoch count-pin: metrics/SLO ride the existing flush


def test_train_epoch_metrics_do_not_change_fetch_count(
        count_device_get, tmp_path):
    """The loop-level acceptance twin: train_epoch with the metrics
    writer + SLO watchdog armed performs EXACTLY the same device_get
    calls (the deferred flush barrier) as with both absent, and logs
    bit-identical losses."""
    from real_time_helmet_detection_tpu.obs.metrics import (
        MetricsWriter, default_registry)
    from real_time_helmet_detection_tpu.ops.loss import LossLog
    from real_time_helmet_detection_tpu.train import train_epoch

    cfg = Config(num_stack=1, hourglass_inch=8, num_cls=2, batch_size=2,
                 print_interval=2, save_path=str(tmp_path))

    class FakeLoader:
        def __init__(self, n):
            self.n = n

        def set_epoch(self, e):
            pass

        def __len__(self):
            return self.n

        def __iter__(self):
            for i in range(self.n):
                yield i

    def runner(state, batch, idx):
        v = jnp.float32(0.25) * (state + 1)
        return state + 1, {"hm": v, "offset": v, "size": v, "total": v}

    def run(mwriter, slo):
        loss_log = LossLog()
        with count_device_get() as counter:
            train_epoch(cfg, 0, FakeLoader(5), runner, 0, None, loss_log,
                        is_chief=True, mwriter=mwriter, slo=slo)
        return counter.count, loss_log.log["total"]

    export = str(tmp_path / "metrics.jsonl")
    reg = default_registry()
    steps_before = reg.histogram("train.step_ms").count
    wd = SloWatchdog(default_train_rules(), registry=reg)
    n_on, tot_on = run(MetricsWriter(reg, export, period_s=0.0), wd)
    n_off, tot_off = run(None, None)
    assert n_on == n_off          # flush barrier count unchanged
    assert tot_on == tot_off      # bit-identical loss history
    assert reg.histogram("train.step_ms").count - steps_before == 10
    assert read_metrics(export)   # armed run exported at the barrier


# ---------------------------------------------------------------------------
# perfgate: the ratchet proven end-to-end


def _load_perfgate():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perfgate", os.path.join(REPO, "scripts", "perfgate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perfgate_gate_function_fails_20pct_tpu_regression():
    """Acceptance (in-process twin of the selfcheck fixture): a +20%
    chip step time against a committed reference fails at the 10% TPU
    tolerance; a +20% CPU step time passes at the 50% box-noise
    tolerance; bytes regress at 2%."""
    pg = _load_perfgate()
    ledger = {"entries": {
        "bench[tpu,512,b16].train_step_ms": {
            "value": 36.8, "direction": "lower", "class": "time",
            "platform": "tpu"},
        "bench[cpu,128,b2].train_step_ms": {
            "value": 3000.0, "direction": "lower", "class": "time",
            "platform": "cpu"},
        "roofline[tpu].bytes.conv": {
            "value": 2.0e10, "direction": "lower", "class": "bytes",
            "platform": "tpu"},
    }}

    def obs(key, value):
        return pg.Obs(key, value, ledger["entries"][key]["direction"],
                      ledger["entries"][key]["class"],
                      ledger["entries"][key]["platform"], 99, "test")

    d = pg.gate({"bench[tpu,512,b16].train_step_ms":
                 obs("bench[tpu,512,b16].train_step_ms", 36.8 * 1.2)},
                ledger)
    assert [r["key"] for r in d["regressions"]] == [
        "bench[tpu,512,b16].train_step_ms"]
    d = pg.gate({"bench[cpu,128,b2].train_step_ms":
                 obs("bench[cpu,128,b2].train_step_ms", 3000.0 * 1.2)},
                ledger)
    assert d["regressions"] == []
    d = pg.gate({"roofline[tpu].bytes.conv":
                 obs("roofline[tpu].bytes.conv", 2.0e10 * 1.05)}, ledger)
    assert len(d["regressions"]) == 1
    d = pg.gate({"roofline[tpu].bytes.conv":
                 obs("roofline[tpu].bytes.conv", 2.0e10 * 1.01)}, ledger)
    assert d["regressions"] == []


def test_perfgate_selfcheck_subprocess():
    """The full fixture suite in a REAL subprocess (the CI twin of
    tpu_queue/graftlint --selfcheck)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perfgate.py"),
         "--selfcheck"], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["ok"] is True and rec["failures"] == []


def test_perfgate_passes_at_head_over_committed_ledger():
    """Acceptance: the committed ledger gates the committed artifacts
    clean — pure file work, deterministic, no backend."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perfgate.py")],
        capture_output=True, text=True, timeout=120)
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert r.returncode == 0, (rec, r.stderr[-2000:])
    assert rec["ok"] is True and rec["checked"] > 0
    assert rec["regressions"] == []
