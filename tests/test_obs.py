"""Flight-recorder tests (ISSUE 6): span-tracer crash safety, in-jit step
telemetry (single-fetch contract + telemetry-off bit-identity), LossLog
schema versioning, and the obs_report joiner.

The reference has no observability tooling at all (its loop prints averaged
meters, ref train.py:140-160); everything here guards new capability. The
D2H-count tests run on the fake 8-device CPU mesh — jax's transfer guards
never fire on the CPU backend (D2H is a zero-copy view), so the fetch
contract is pinned by counting `jax.device_get` calls in the bench-style
outer loop instead.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.obs.context import sample_context
from real_time_helmet_detection_tpu.obs.spans import (SpanTracer,
                                                      maybe_tracer,
                                                      read_spans)
from real_time_helmet_detection_tpu.obs.telemetry import (
    SCAN_TELEMETRY_KEYS, install_recompile_counter, ring_init, ring_push,
    ring_to_host)
from real_time_helmet_detection_tpu.ops.loss import LossLog
from real_time_helmet_detection_tpu.optim import build_optimizer
from real_time_helmet_detection_tpu.parallel import (batch_sharding,
                                                     make_mesh, replicated,
                                                     shard_batch)
from real_time_helmet_detection_tpu.train import (_optimizer_update,
                                                  create_train_state,
                                                  loss_fn,
                                                  make_scanned_train_fn,
                                                  make_train_step,
                                                  make_train_step_body)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IMSIZE = 64
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=4,
                lr=1e-3)
    base.update(kw)
    return Config(**base)


def synthetic_batch(b=4, seed=0):
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    return synthetic_target_batch(b, IMSIZE, seed=seed)


def make_state(cfg):
    model = build_model(cfg)
    tx = build_optimizer(cfg, steps_per_epoch=10)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    return model, tx, state


# ---------------------------------------------------------------------------
# span tracer


def test_tracer_roundtrip_all_record_kinds(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    t = SpanTracer(path)
    with t.span("compile", batch=16) as sp:
        time.sleep(0.01)
    assert sp.dur_s >= 0.01
    t.record("loader-wait", 0.25, it=3)
    t.event("heartbeat", label="flush 0")
    sample = t.context(phase="test")
    t.close()
    assert isinstance(sample, dict) and "loadavg" in sample

    recs = read_spans(path)
    assert recs[0]["kind"] == "meta" and recs[0]["schema"] == "obs-spans-v1"
    by_kind = {}
    for r in recs[1:]:
        by_kind.setdefault(r["kind"], []).append(r)
    assert by_kind["span"][0]["name"] == "compile"
    assert by_kind["span"][0]["dur_s"] >= 0.01
    assert by_kind["span"][0]["meta"] == {"batch": 16}
    assert by_kind["span"][1]["dur_s"] == 0.25
    assert by_kind["event"][0]["meta"]["label"] == "flush 0"
    assert by_kind["context"][0]["sample"]["loadavg"] is not None
    assert all("pid" in r for r in recs[1:])


def test_disabled_tracer_times_but_never_touches_disk(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_SPAN_LOG", raising=False)
    t = maybe_tracer()  # no path, no env -> disabled
    assert not t.enabled
    with t.span("compile") as sp:
        time.sleep(0.005)
    assert sp.dur_s >= 0.005  # callers read dur_s for their own artifacts
    fn = lambda x: x + 1  # noqa: E731
    assert t.wrap("h2d", fn) is fn  # identity: zero cost in the hot loop
    t.record("step", 0.1)
    t.event("beat")
    assert list(tmp_path.iterdir()) == []


def test_maybe_tracer_env_wiring(tmp_path, monkeypatch):
    path = str(tmp_path / "env_spans.jsonl")
    monkeypatch.setenv("OBS_SPAN_LOG", path)
    t = maybe_tracer()  # the supervisor's per-job wiring
    assert t.enabled and t.path == path
    explicit = maybe_tracer(str(tmp_path / "explicit.jsonl"))
    assert explicit.path.endswith("explicit.jsonl")  # explicit wins


def test_tracer_write_failure_disables_instead_of_raising(tmp_path):
    t = SpanTracer(str(tmp_path))  # a DIRECTORY: open() will fail
    t.record("step", 0.1)  # must not raise — tracing never kills the job
    assert not t.enabled


def test_span_records_error_class_on_exception(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    t = SpanTracer(path)
    with pytest.raises(RuntimeError):
        with t.span("checkpoint", epoch=1):
            raise RuntimeError("disk full")
    t.close()
    rec = [r for r in read_spans(path) if r.get("kind") == "span"][0]
    assert rec["meta"]["error"] == "RuntimeError"


def test_read_spans_drops_torn_tail_silently(tmp_path, capsys):
    path = str(tmp_path / "spans.jsonl")
    t = SpanTracer(path)
    for i in range(3):
        t.record("step", 0.01, it=i)
    t.close()
    with open(path, "a") as f:  # kill -9 mid-append twin: no newline
        f.write('{"kind": "span", "name": "st')
    recs = read_spans(path)
    assert len(recs) == 4  # meta + 3 steps; torn tail gone
    assert "WARNING" not in capsys.readouterr().out  # tail is EXPECTED


def test_read_spans_skips_midfile_garbage_loudly(tmp_path, capsys):
    path = str(tmp_path / "spans.jsonl")
    t = SpanTracer(path)
    t.record("step", 0.01)
    t.close()
    with open(path, "a") as f:
        f.write("NOT JSON\n")
        f.write(json.dumps({"kind": "event", "name": "late", "v": 1}) + "\n")
    recs = read_spans(path)
    assert [r.get("name") for r in recs[1:]] == ["step", "late"]
    assert "WARNING" in capsys.readouterr().out  # mid-file damage is NOT


def test_torn_tail_recovery_after_kill9(tmp_path):
    """ISSUE 6 satellite: a writer killed -9 mid-append leaves at most one
    torn final line; the reader recovers every complete record."""
    path = str(tmp_path / "spans.jsonl")
    code = (
        "import sys\n"
        "sys.path.insert(0, %r)\n"
        "from real_time_helmet_detection_tpu.obs.spans import SpanTracer\n"
        "t = SpanTracer(%r)\n"
        "print('ready', flush=True)\n"
        "i = 0\n"
        "while True:\n"
        "    t.record('step', 0.001, it=i, pad='x' * 256)\n"
        "    i += 1\n" % (REPO, path))
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE)
    try:
        proc.stdout.readline()  # writer is up
        deadline = time.time() + 30
        while time.time() < deadline:
            if os.path.exists(path) and os.path.getsize(path) > 64 * 1024:
                break
            time.sleep(0.02)
        assert os.path.getsize(path) > 64 * 1024, "writer produced no log"
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait()
    recs = read_spans(path)  # must not raise
    steps = [r for r in recs if r.get("kind") == "span"]
    assert len(steps) > 50
    # every surviving record is complete and ordered — nothing half-read
    assert [r["meta"]["it"] for r in steps] == list(range(len(steps)))


# ---------------------------------------------------------------------------
# host context + recompile counter


def test_sample_context_shape():
    s = sample_context()
    assert set(s) == {"ncpu", "loadavg"}
    assert isinstance(s["loadavg"], list) and len(s["loadavg"]) == 3


def test_recompile_counter_observes_fresh_compile():
    c = install_recompile_counter()
    before = c.count

    @jax.jit
    def fresh(x):
        return x * 3.0 + 1.0

    fresh(jnp.ones((5,))).block_until_ready()
    assert c.count > before  # a compilation-observed detector, not an
    assert c.total_s >= 0.0  # exact model-step count (see telemetry.py)
    assert c.last_dur_s is not None


def test_recompile_counter_mirrors_compiles_into_span_log(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    t = SpanTracer(path)
    c = install_recompile_counter(t)

    @jax.jit
    def fresh2(x):
        return x - 7.0

    fresh2(jnp.ones((6,))).block_until_ready()
    t.close()
    compiles = [r for r in read_spans(path) if r.get("name") == "compile"]
    assert len(compiles) == c.count and c.count >= 1


# ---------------------------------------------------------------------------
# telemetry ring


def test_ring_push_and_decode_roundtrip():
    ring = ring_init(capacity=4, nkeys=2)
    for i in range(3):
        ring = ring_push(ring, [float(i), 10.0 + i])
    host = jax.device_get(ring)
    out = ring_to_host(host, keys=("a", "b"))
    assert out["a"] == [0.0, 1.0, 2.0]
    assert out["b"] == [10.0, 11.0, 12.0]


def test_ring_wraparound_keeps_newest_chronological():
    ring = ring_init(capacity=3, nkeys=1)
    for i in range(7):
        ring = ring_push(ring, [float(i)])
    out = ring_to_host(jax.device_get(ring), keys=("v",))
    assert out["v"] == [4.0, 5.0, 6.0]  # last `capacity`, oldest first


def test_ring_empty_decodes_empty():
    out = ring_to_host(jax.device_get(ring_init(capacity=2, nkeys=1)),
                       keys=("v",))
    assert out["v"] == []


# ---------------------------------------------------------------------------
# in-jit step telemetry: the single-fetch contract + off == pre-PR


@pytest.mark.slow  # 145 s at r15 --durations: the heaviest smoke-tier
# compile (telemetry ring + scan); the D2H-count pin is a perf-hygiene
# check, not a robustness acceptance test — re-tiered to fit the 870 s
# tier-1 budget (ISSUE 13 satellite)
def test_scanned_telemetry_one_d2h_per_outer_loop(count_device_get):
    """Acceptance: telemetry-on, the bench-style outer loop performs
    exactly one D2H fetch per iteration — the SAME count as telemetry-off
    — and the ring rides that fetch as a fixed-size payload."""
    n_scan, n_outer = 2, 3
    cfg_on = tiny_cfg(telemetry=True)
    model, tx, state0 = make_state(cfg_on)
    arrs = tuple(jnp.asarray(a) for a in synthetic_batch())

    def run_loop(cfg, telemetry):
        body = make_train_step_body(model, tx, cfg)
        train_n = make_scanned_train_fn(body, n_scan, telemetry=telemetry,
                                        ring_capacity=8)
        compiled = jax.jit(train_n, donate_argnums=(0,)).lower(
            state0, *arrs).compile()
        state = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state0)
        fetched = []
        with count_device_get() as counter:
            for _ in range(n_outer):
                state, out = compiled(state, *arrs)  # async dispatch
                fetched.append(jax.device_get(out))  # THE one D2H
        return counter.count, fetched

    on_fetches, on_host = run_loop(cfg_on, telemetry=True)
    off_fetches, off_host = run_loop(tiny_cfg(), telemetry=False)
    assert on_fetches == off_fetches == n_outer

    # the ring rode the fetch: already-host numpy, fixed-size, decodable
    # without any further device access (device_get count stays n_outer)
    last, ring = on_host[-1]
    assert int(ring["n"]) == n_scan
    assert ring["buf"].nbytes == 8 * len(SCAN_TELEMETRY_KEYS) * 4
    telem = ring_to_host(ring)
    assert set(telem) == set(SCAN_TELEMETRY_KEYS)
    assert all(len(v) == n_scan for v in telem.values())
    assert all(np.isfinite(v).all() for v in telem.values())
    assert telem["grad_norm"][0] > 0.0
    # the ring's last total IS the returned loss scalar (same step, same
    # program, same fetch)
    assert telem["total"][-1] == float(np.asarray(last))
    # telemetry-off signature unchanged: out[1] is the bare scalar
    assert np.asarray(off_host[-1]).shape == ()


@pytest.mark.slow  # 51 s at r15 --durations: two scanned-step compiles
# for a bit-identity pin — re-tiered (ISSUE 13 satellite)
def test_scanned_telemetry_off_bit_identical_to_pre_pr():
    """Acceptance: telemetry off, make_scanned_train_fn is the exact
    pre-PR program — loss and updated params BIT-identical to the pre-PR
    scan body reimplemented verbatim."""
    cfg = tiny_cfg()  # telemetry=False
    model, tx, state = make_state(cfg)
    body = make_train_step_body(model, tx, cfg)
    n = 3

    def pre_pr_train_n(state, images, heat, off, wh, mask):
        # the pre-PR make_scanned_train_fn body, verbatim
        def sbody(st, _):
            st, losses = body(st, images, heat, off, wh, mask)
            return st, losses["total"]
        st, totals = jax.lax.scan(sbody, state, None, length=n)
        return st, totals[-1]

    arrs = tuple(jnp.asarray(a) for a in synthetic_batch(seed=11))
    st_a = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
    st_b = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
    sa, la = jax.jit(make_scanned_train_fn(body, n))(st_a, *arrs)
    sb, lb = jax.jit(pre_pr_train_n)(st_b, *arrs)
    assert np.asarray(la).tobytes() == np.asarray(lb).tobytes()
    for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_mesh_train_step_telemetry_off_bit_identical():
    """Acceptance: on the 8-device mesh, the production jitted step with
    telemetry off is bit-identical (losses AND params) to the pre-PR step
    — same body minus the telemetry hook, same shardings/donation."""
    cfg = tiny_cfg(batch_size=8)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(8)
    step_new = make_train_step(model, tx, cfg, mesh)

    def pre_pr_body(state, images, gt_heat, gt_off, gt_wh, mask):
        # pre-PR make_train_step_body, verbatim (no _maybe_telemetry)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, (batch_stats, losses)), grads = grad_fn(
            state.params, state.batch_stats, model, images, gt_heat,
            gt_off, gt_wh, mask, cfg)
        return _optimizer_update(state, tx, cfg, grads, batch_stats), losses

    repl = replicated(mesh)
    sh = batch_sharding(mesh, 4, spatial_dim=1)
    step_old = jax.jit(pre_pr_body,
                       in_shardings=(repl, sh, sh, sh, sh, sh),
                       out_shardings=(repl, repl), donate_argnums=(0,))
    batch = shard_batch(mesh, synthetic_batch(b=8, seed=5),
                        spatial_dims=[1] * 5)
    st_a = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
    st_b = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
    sa, la = step_new(st_a, *batch)
    sb, lb = step_old(st_b, *batch)
    la, lb = jax.device_get((la, lb))
    assert set(la) == set(lb)  # no extra keys leak in when off
    for k in lb:
        assert np.asarray(la[k]).tobytes() == np.asarray(lb[k]).tobytes()
    for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_train_step_telemetry_on_adds_finite_norms():
    cfg = tiny_cfg(telemetry=True)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    _, losses = step(state, *batch)
    losses = jax.device_get(losses)
    for k in ("grad_norm", "update_norm", "param_norm"):
        assert k in losses and np.isfinite(losses[k]) and losses[k] > 0


def test_scanned_telemetry_requires_telemetry_body():
    cfg = tiny_cfg()  # telemetry OFF: body produces no norm scalars
    model, tx, state = make_state(cfg)
    body = make_train_step_body(model, tx, cfg)
    train_n = make_scanned_train_fn(body, 2, telemetry=True)
    arrs = tuple(jnp.asarray(a) for a in synthetic_batch())
    with pytest.raises(ValueError, match="cfg.telemetry=True"):
        jax.jit(train_n).lower(state, *arrs)


# ---------------------------------------------------------------------------
# LossLog schema versioning


def test_loss_log_v2_state_dict_roundtrip():
    ll = LossLog()
    ll.append({"hm": 1.0, "offset": 0.5, "size": 0.25, "total": 1.75,
               "grad_norm": 30.0, "update_norm": 0.9, "param_norm": 50.0})
    sd = ll.state_dict()
    assert sd["schema"] == "loss-log-v2"
    assert sd["grad_norm"] == [30.0]
    restored = LossLog(sd)
    assert restored.state_dict() == sd


def test_loss_log_reads_checked_in_v1_fixture():
    """Regression: every pre-PR checkpoint's loss_log.json (untagged v1)
    keeps restoring — pinned against a checked-in fixture."""
    with open(os.path.join(FIXTURES, "loss_log_v1.json")) as f:
        v1 = json.load(f)
    assert "schema" not in v1  # the fixture IS the old format
    ll = LossLog(v1)
    assert ll.log["hm"] == v1["hm"]
    assert ll.log["total"] == v1["total"]
    assert ll.log["grad_norm"] == []  # v1 carried no telemetry
    # a v1-shaped losses dict (no telemetry scalars) appends as before
    ll.append({"hm": 1.0, "offset": 0.5, "size": 0.25, "total": 1.75})
    assert len(ll.log["hm"]) == len(v1["hm"]) + 1
    assert ll.log["grad_norm"] == []
    assert "hm" in ll.get_log(3)
    assert ll.state_dict()["schema"] == "loss-log-v2"  # upgraded on save


def test_loss_log_rejects_unknown_schema():
    with pytest.raises(ValueError, match="unknown loss-log schema"):
        LossLog({"schema": "loss-log-v99", "hm": []})


# ---------------------------------------------------------------------------
# heartbeat -> span mirroring + supervisor wiring


def test_heartbeat_beats_mirror_into_span_log(tmp_path, monkeypatch):
    log = str(tmp_path / "spans.jsonl")
    monkeypatch.setenv("OBS_SPAN_LOG", log)
    from real_time_helmet_detection_tpu.runtime.heartbeat import FileHeartbeat
    hb = FileHeartbeat(str(tmp_path / "hb.json"))
    hb.beat("section A")
    hb.beat("section B")
    events = [r for r in read_spans(log) if r.get("kind") == "event"]
    assert [e["meta"]["label"] for e in events] == ["section A", "section B"]
    # the heartbeat file itself still works (last beat only)
    assert json.load(open(str(tmp_path / "hb.json")))["label"] == "section B"


def test_heartbeat_stays_silent_without_span_log(tmp_path, monkeypatch):
    monkeypatch.delenv("OBS_SPAN_LOG", raising=False)
    from real_time_helmet_detection_tpu.runtime.heartbeat import FileHeartbeat
    hb = FileHeartbeat(str(tmp_path / "hb.json"))
    hb.beat("quiet")
    assert sorted(p.name for p in tmp_path.iterdir()) == ["hb.json"]


# ---------------------------------------------------------------------------
# obs_report: the per-round joiner


def test_obs_report_selfcheck_end_to_end():
    """`obs_report.py --selfcheck` in a child process, exactly as CI runs
    it (smoke tier, CPU-only, seconds)."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         "--selfcheck"],
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, "selfcheck failed:\n%s\n%s" % (r.stdout,
                                                             r.stderr)
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["ok"] is True and line["failures"] == []


def test_obs_report_joins_real_spool_journal(tmp_path):
    """Acceptance: the report reads a journal written by the REAL tpu_queue
    spool (not a hand-rolled fixture), plus tracer spans and a bench line,
    into one obs-report-v7 object (the ISSUE-17 schema; a round with no
    metrics export/scaling/fleet/trace/stream activity just nulls those
    sections)."""
    from real_time_helmet_detection_tpu.runtime.spool import JobSpec, Spool
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import obs_report

    sp = Spool(str(tmp_path / "queue"))
    sp.enqueue(JobSpec(job="bench", argv=["python", "bench.py"],
                       heartbeat_timeout_s=60.0))
    sp.transition("bench", "claim-wait")
    sp.transition("bench", "running")
    sp.transition("bench", "done")
    sp.close()

    span_path = str(tmp_path / "obs" / "spans.jsonl")
    t = SpanTracer(span_path)
    t.record("step", 0.5, it=0)
    t.record("step", 0.7, it=1)
    t.context(phase="test")
    t.close()

    bench_path = str(tmp_path / "BENCH_r99_local.json")
    with open(bench_path, "w") as f:
        f.write(json.dumps({"metric": "inference_fps_512", "value": 100.0,
                            "platform": "tpu", "recompile_count": 2,
                            "loadavg": [0.5, 0.5, 0.5]}) + "\n")

    import argparse
    rep = obs_report.generate(argparse.Namespace(
        round="r99", span_log=[span_path],
        queue_dir=str(tmp_path / "queue"), bench=[bench_path],
        loss_log=[], out=str(tmp_path / "out")))
    assert rep["schema"] == "obs-report-v7"
    assert rep["metrics"] is None and rep["slo"] is None  # nothing exported
    assert rep["scaling"] is None  # no scaling activity this round
    assert rep["fleet"] is None  # no fleet activity this round
    assert rep["traces"] is None  # no traced spans this round
    assert rep["streams"] is None  # no stream activity this round
    assert rep["queue"]["jobs"]["bench"]["state"] == "done"
    assert rep["spans"]["by_name"]["step"]["count"] == 2
    assert rep["bench"][0]["recompile_count"] == 2
    assert os.path.exists(str(tmp_path / "out" / "report.md"))
    md = open(str(tmp_path / "out" / "report.md")).read()
    assert "| bench | done |" in md
