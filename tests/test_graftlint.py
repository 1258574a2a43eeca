"""graftlint (ISSUE 4): per-rule positive/negative fixtures + the
repo-wide ratchet gate.

The reference has no static analysis at all (its only check is the manual
module self-test, ref /root/reference/hourglass.py:241-256); this suite
pins the auditor that replaces convention-by-memory: every AST rule class
and every trace rule class must fire on a seeded violation and stay
silent on its clean twin, and the WHOLE repo at HEAD must lint clean
against the committed analysis/baseline.json.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from real_time_helmet_detection_tpu.analysis import (  # noqa: E402
    Finding, diff_baseline, load_baseline)
from real_time_helmet_detection_tpu.analysis import ast_rules  # noqa: E402
from real_time_helmet_detection_tpu.analysis import trace_audit  # noqa: E402


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# AST rule classes: positive + negative fixture each


AST_CASES = [
    # (rule, path-to-lint-under, bad source, good source)
    ("ast/per-call-timing", "scripts/x.py",
     "import time, jax\n"
     "def f(c, x):\n"
     "    t0 = time.time()\n"
     "    r = c(x)\n"
     "    jax.block_until_ready(r)\n"
     "    return time.time() - t0\n",
     "import time, jax\n"
     "def f(c, x):\n"
     "    jax.block_until_ready(c(x))\n"
     "def g():\n"
     "    t0 = time.time()\n"
     "    return time.time() - t0\n"),
    ("ast/queue-bypass", "scripts/x.py",
     "from bench import acquire_backend\n"
     "jax, devs = acquire_backend()\n",
     "from bench import acquire_backend\n"
     "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
     "def main():\n"
     "    jax, devs = acquire_backend()\n"
     "run_as_job(main)\n"),
    ("ast/raw-artifact-write", "scripts/x.py",
     "def w(path, data):\n"
     "    with open(path, mode='wb') as f:\n"
     "        f.write(data)\n",
     "def r(path):\n"
     "    with open(path, 'rb') as f:\n"
     "        return f.read()\n"),
    ("ast/device-get-in-loop", "scripts/x.py",
     "import jax\n"
     "def run(step, s, batches):\n"
     "    while batches:\n"
     "        s, loss = step(s, batches.pop())\n"
     "        jax.device_get(loss)\n",
     "import jax\n"
     "def run(step, s, batches):\n"
     "    out = [step(s, b)[1] for b in batches]\n"
     "    return jax.device_get(out)\n"),
    ("ast/missing-ref-citation", "scripts/x.py",
     '"""Module with no provenance statement whatsoever."""\nX = 1\n',
     '"""Module citing ref evaluate.py:15 properly."""\nX = 1\n'),
    ("ast/raw-metric-aggregation", "scripts/x.py",
     # hand-rolled nearest-rank percentile + np.percentile in a module
     # that acquires a backend (ISSUE 10 satellite)
     "import numpy as np, jax\n"
     "jax.devices()\n"
     "def pctl(vals, q):\n"
     "    s = sorted(vals)\n"
     "    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]\n"
     "def digest(lats):\n"
     "    return {'p50': pctl(lats, 0.5),\n"
     "            'p99': float(np.percentile(lats, 99))}\n",
     # routed through the metrics plane instead
     "import jax\n"
     "from real_time_helmet_detection_tpu.obs.metrics import Histogram\n"
     "jax.devices()\n"
     "def digest(lats):\n"
     "    h = Histogram('lat_ms')\n"
     "    for v in lats:\n"
     "        h.observe(v)\n"
     "    return {'p50': h.quantile(0.5), 'p99': h.quantile(0.99)}\n"),
    ("ast/unbarriered-collective-start", "scripts/x.py",
     # a multi-process entry point AOT-compiling + executing with no
     # barrier between compile and the Gloo-context-creating first run
     "import jax\n"
     "from real_time_helmet_detection_tpu.parallel import "
     "init_process_group\n"
     "def main(rank, world, step, state, arrays):\n"
     "    init_process_group('127.0.0.1:29500', world, rank)\n"
     "    compiled = step.lower(state, *arrays).compile()\n"
     "    return compiled(state, *arrays)\n",
     # the barrier law via the public helper
     "import jax\n"
     "from real_time_helmet_detection_tpu.parallel import ("
     "barrier_synced_compile, init_process_group)\n"
     "def main(rank, world, step, state, arrays):\n"
     "    init_process_group('127.0.0.1:29500', world, rank)\n"
     "    compiled = barrier_synced_compile(step, (state, *arrays),\n"
     "                                      name='train_step')\n"
     "    return compiled(state, *arrays)\n"),
    ("ast/engine-bypass-in-fleet",
     "real_time_helmet_detection_tpu/serving/fleet_x.py",
     # raw engine construction + direct replica-engine submit in fleet
     # code: traffic escapes tenant/SLO/canary accounting (ISSUE 12)
     "def route(predict, variables, replicas, image):\n"
     "    spare = ServingEngine(predict, variables, (64, 64, 3),\n"
     "                          'uint8')\n"
     "    return replicas[0].engine.submit(image)\n",
     # router dispatch + factory construction — the sanctioned shape
     "def route(router, image):\n"
     "    return router.submit(image, tenant='bulk')\n"
     "def spawn(factory, rid):\n"
     "    return factory(rid, True)\n"),
    ("ast/context-free-span",
     "real_time_helmet_detection_tpu/serving/x.py",
     # a per-request span emitted without its trace context (ISSUE 14):
     # the waterfall assembler can never attach it to a request
     "def shed(tracer, req):\n"
     "    tracer.event('serve:shed', reason='deadline')\n",
     # context carried + a lifecycle span (exempt) + fan-in links
     "def shed(tracer, req, links):\n"
     "    tracer.event('serve:shed', ctx=req.ctx, reason='deadline')\n"
     "    with tracer.span('serve:d2h', b=2, links=links):\n"
     "        pass\n"
     "    tracer.event('serve:state', **{'from': 'a', 'to': 'b'})\n"),
    ("ast/unbounded-retry", "scripts/x.py",
     # the r2 probe-kill class: swallow + loop forever, no cap, no pause
     "import jax\n"
     "def wait():\n"
     "    while True:\n"
     "        try:\n"
     "            return jax.devices()\n"
     "        except Exception:\n"
     "            continue\n",
     # bounded + backed-off retry
     "import time, jax\n"
     "def wait():\n"
     "    for attempt in range(5):\n"
     "        try:\n"
     "            return jax.devices()\n"
     "        except Exception:\n"
     "            time.sleep(2.0 * (attempt + 1))\n"
     "    raise RuntimeError('never came up')\n"),
]


@pytest.mark.parametrize("rule,path,bad,good", AST_CASES,
                         ids=[c[0] for c in AST_CASES])
def test_ast_rule_fires_and_stays_silent(rule, path, bad, good):
    assert rule in rules_of(ast_rules.lint_source(bad, path))
    assert rule not in rules_of(ast_rules.lint_source(good, path))


def test_engine_bypass_in_fleet_scope_and_allowlist():
    """The rule follows fleet code, not paths alone: the same bad source
    is silent in a plain script, fires once the module references
    FleetRouter (import or name), and the sanctioned dispatch scope is
    allowlisted by qualname."""
    bad = ("def route(predict, variables, replicas, image):\n"
           "    eng = ServingEngine(predict, variables, (64, 64, 3),\n"
           "                        'uint8')\n"
           "    return replicas[0].engine.submit(image)\n")
    rule = "ast/engine-bypass-in-fleet"
    assert rule not in rules_of(
        ast_rules.lint_source(bad, "scripts/plain.py"))
    assert rule in rules_of(ast_rules.lint_source(
        "from real_time_helmet_detection_tpu.serving import FleetRouter\n"
        + bad, "scripts/plain.py"))
    # the shipped sanctioned scopes really are in the allowlist
    assert ("real_time_helmet_detection_tpu/serving/fleet.py::"
            "FleetRouter._dispatch") in ast_rules.FLEET_ENGINE_ALLOW
    assert "scripts/serve_bench.py::make_replica_factory" \
        in ast_rules.FLEET_ENGINE_ALLOW


def test_context_free_span_scoped_to_serving():
    """The trace-context rule polices the serving package only (ISSUE
    14): the same context-free emission in a script or a train-path
    module is out of scope (bench sections and train spans have their
    own taxonomy), and the shipped lifecycle allowlist really names the
    engine's construction/state spans."""
    bad = ("def shed(tracer):\n"
           "    tracer.event('fleet:lost', tenant='bulk')\n")
    rule = "ast/context-free-span"
    assert rule in rules_of(ast_rules.lint_source(
        bad, "real_time_helmet_detection_tpu/serving/fleet.py"))
    assert rule not in rules_of(ast_rules.lint_source(bad, "scripts/x.py"))
    assert rule not in rules_of(ast_rules.lint_source(
        bad, "real_time_helmet_detection_tpu/train.py"))
    assert {"serve:compile", "serve:state", "fleet:rollout",
            "fleet:rollback"} <= ast_rules.TRACE_LIFECYCLE_SPANS


def test_queue_bypass_scoped_to_chip_scripts():
    """A library module may probe jax.devices() without the job contract —
    the rule is about scripts/ (+ bench/scaling) only."""
    src = "import jax\nd = jax.devices()\n"
    assert "ast/queue-bypass" in rules_of(
        ast_rules.lint_source(src, "scripts/x.py"))
    assert "ast/queue-bypass" not in rules_of(
        ast_rules.lint_source(src, "real_time_helmet_detection_tpu/x.py"))


def test_unbarriered_collective_start_scope():
    """The rule needs BOTH markers: a single-process AOT compile (bench's
    whole idiom) never fires, a multi-process module that merely calls
    re.compile never fires, and `coordination_barrier` (the manual form
    of the law) also satisfies it."""
    single = ("import jax\n"
              "def f(step, x):\n"
              "    return step.lower(x).compile()\n")
    assert "ast/unbarriered-collective-start" not in rules_of(
        ast_rules.lint_source(single, "scripts/x.py"))
    re_only = ("import re\n"
               "from real_time_helmet_detection_tpu.parallel import "
               "init_process_group\n"
               "def f(world, rank):\n"
               "    init_process_group('h:1', world, rank)\n"
               "    return re.compile('x')\n")
    assert "ast/unbarriered-collective-start" not in rules_of(
        ast_rules.lint_source(re_only, "scripts/x.py"))
    manual = ("from real_time_helmet_detection_tpu.parallel import ("
              "coordination_barrier, init_process_group)\n"
              "def f(step, x, world, rank):\n"
              "    init_process_group('h:1', world, rank)\n"
              "    compiled = step.lower(x).compile()\n"
              "    coordination_barrier('compiled:f')\n"
              "    return compiled(x)\n")
    assert "ast/unbarriered-collective-start" not in rules_of(
        ast_rules.lint_source(manual, "scripts/x.py"))


def test_unbounded_retry_exemptions():
    """The rule must NOT flag the legitimate while-True shapes the repo
    runs on: queue-consumer loops (the serving dispatcher/fetcher, the
    shm worker — they block on `.get()` and re-attempt on NEW work) and
    backed-off reconnect loops; a handler that re-raises is bounded."""
    consumer = ("def loop(q):\n"
                "    while True:\n"
                "        task = q.get()\n"
                "        if task is None:\n"
                "            break\n"
                "        try:\n"
                "            task()\n"
                "        except Exception:\n"
                "            continue\n")
    backed_off = ("import time\n"
                  "def loop(connect):\n"
                  "    while True:\n"
                  "        try:\n"
                  "            return connect()\n"
                  "        except Exception:\n"
                  "            time.sleep(5.0)\n")
    reraises = ("def loop(connect):\n"
                "    while True:\n"
                "        try:\n"
                "            return connect()\n"
                "        except Exception:\n"
                "            raise\n")
    for src in (consumer, backed_off, reraises):
        assert "ast/unbounded-retry" not in rules_of(
            ast_rules.lint_source(src, "scripts/x.py")), src
    # and an inline suppression silences a justified exception
    bad = ("def loop(connect):\n"
           "    while True:\n"
           "        try:\n"
           "            return connect()\n"
           "        except Exception:  # graftlint: off=unbounded-retry\n"
           "            continue\n")
    assert "ast/unbounded-retry" not in rules_of(
        ast_rules.lint_source(bad, "scripts/x.py"))


def test_unbounded_retry_repo_is_clean():
    """The production tree at HEAD carries zero unbounded retry loops —
    fixed, not grandfathered (the baseline stays EMPTY)."""
    findings = [f for f in ast_rules.lint_repo(REPO)
                if f.rule == "ast/unbounded-retry"]
    assert findings == []


def test_raw_metric_aggregation_scope_and_allowlist():
    """ISSUE 10 satellite: the rule only polices chip-path scripts that
    acquire a backend (obs_report's file-work percentiles stay legal),
    Histogram.quantile() never flags itself, and the sanctioned
    dispatch-overhead median in bench.py is allowlisted."""
    bad = ("import numpy as np\n"
           "def digest(lats):\n"
           "    return float(np.percentile(lats, 99))\n")
    # no backend acquisition -> out of scope even under scripts/
    assert "ast/raw-metric-aggregation" not in rules_of(
        ast_rules.lint_source(bad, "scripts/x.py"))
    # library modules -> out of scope regardless
    assert "ast/raw-metric-aggregation" not in rules_of(
        ast_rules.lint_source("import jax\njax.devices()\n" + bad,
                              "real_time_helmet_detection_tpu/x.py"))
    # the metrics plane's own digest is not "raw aggregation"
    ok = ("import jax\njax.devices()\n"
          "def digest(h):\n"
          "    return {'p50': h.quantile(0.5)}\n")
    assert "ast/raw-metric-aggregation" not in rules_of(
        ast_rules.lint_source(ok, "scripts/x.py"))
    # bench.py at HEAD is clean (measure_dispatch_overhead allowlisted)
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "ast/raw-metric-aggregation" not in rules_of(
        ast_rules.lint_source(src, "bench.py"))
    # serve_bench at HEAD is FIXED, not grandfathered
    with open(os.path.join(REPO, "scripts", "serve_bench.py")) as f:
        src = f.read()
    assert "ast/raw-metric-aggregation" not in rules_of(
        ast_rules.lint_source(src, "scripts/serve_bench.py"))


def test_inline_suppression_and_syntax_error():
    bad = ("def w(p, d):\n"
           "    with open(p, 'w') as f:  # graftlint: off=raw-artifact-write\n"
           "        f.write(d)\n")
    assert "ast/raw-artifact-write" not in rules_of(
        ast_rules.lint_source(bad, "scripts/x.py"))
    assert "ast/syntax-error" in rules_of(
        ast_rules.lint_source("def broken(:\n", "scripts/x.py"))


def test_timing_allowlist_covers_bench_harness():
    """bench.timed_fetch IS the sanctioned implementation; the rule must
    not flag the tool it tells people to use."""
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "ast/per-call-timing" not in rules_of(
        ast_rules.lint_source(src, "bench.py"))


# ---------------------------------------------------------------------------
# trace rule classes: positive + negative fixture each


def test_trace_failure_on_boolean_filtering():
    import jax.numpy as jnp
    x = np.ones((4, 4), np.float32)
    bad = trace_audit.audit_entry(lambda v: v[v > 0], (x,), "fix")
    assert "trace/trace-failure" in rules_of(bad)
    good = trace_audit.audit_entry(lambda v: jnp.where(v > 0, v, 0.0),
                                   (x,), "fix")
    assert not good


def test_f64_leak_detected():
    import jax
    import jax.numpy as jnp
    x = np.ones((4,), np.float32)
    with jax.enable_x64(True):
        bad = trace_audit.audit_entry(
            lambda v: jnp.asarray(v, jnp.float64) * 2.0, (x,), "fix",
            lower=False)
    assert "trace/f64" in rules_of(bad)
    good = trace_audit.audit_entry(lambda v: v * 2.0, (x,), "fix",
                                   lower=False)
    assert "trace/f64" not in rules_of(good)


def test_host_callback_detected_through_scan():
    """The walk must reach primitives nested in sub-jaxprs (scan body)."""
    import jax
    import jax.numpy as jnp

    def bad(v):
        def body(c, _):
            jax.debug.print("c={}", c[0])
            return c + 1.0, ()
        out, _ = jax.lax.scan(body, v, None, length=2)
        return out

    x = np.ones((4,), np.float32)
    assert "trace/host-callback" in rules_of(
        trace_audit.audit_entry(bad, (x,), "fix", lower=False))

    def good(v):
        out, _ = jax.lax.scan(lambda c, _: (c + 1.0, ()), v, None, length=2)
        return jnp.sum(out)

    assert "trace/host-callback" not in rules_of(
        trace_audit.audit_entry(good, (x,), "fix", lower=False))


def test_donation_rule_and_donation_ok():
    import jax.numpy as jnp
    x = np.ones((4, 4), np.float32)
    bad = lambda v: jnp.sum(v)            # noqa: E731 — no aliasing target
    good = lambda v: (v + 1.0, jnp.sum(v))  # noqa: E731
    assert "trace/donation" in rules_of(
        trace_audit.audit_entry(bad, (x,), "fix", donate_argnums=(0,),
                                lower=False))
    assert "trace/donation" not in rules_of(
        trace_audit.audit_entry(good, (x,), "fix", donate_argnums=(0,),
                                lower=False))
    assert trace_audit.donation_ok(good, (0,), (x,))
    assert not trace_audit.donation_ok(bad, (0,), (x,))


def test_retrace_instability_detected():
    import random
    x = np.ones((4,), np.float32)
    assert "trace/retrace-unstable" in rules_of(
        trace_audit.audit_entry(lambda v: v + random.random(), (x,), "fix",
                                lower=False))
    assert "trace/retrace-unstable" not in rules_of(
        trace_audit.audit_entry(lambda v: v + 1.0, (x,), "fix",
                                lower=False))


def test_dynamic_shape_detected_in_stablehlo():
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export
    b = jax_export.symbolic_shape("b")[0]
    spec = jax.ShapeDtypeStruct((b, 4), jnp.float32)
    assert "trace/dynamic-shape" in rules_of(
        trace_audit.stablehlo_findings(lambda v: v * 2.0, (spec,), "fix"))
    x = np.ones((4, 4), np.float32)
    assert not trace_audit.stablehlo_findings(lambda v: v * 2.0, (x,),
                                              "fix")


def test_scanned_train_fn_donation_contract():
    """The production contract bench.py's `donation_ok` reports: the
    scanned train fn returns the FULL final state, so the donated input
    state aliases completely."""
    train_n, args = trace_audit._tiny_train_parts("none")
    assert trace_audit.donation_ok(train_n, (0,), args)
    # and the scalar-only variant (the pre-PR1 bug shape) must NOT be ok
    scalar_only = lambda *a: train_n(*a)[1]  # noqa: E731
    assert not trace_audit.donation_ok(scalar_only, (0,), args)


# ---------------------------------------------------------------------------
# baseline ratchet mechanics


def test_baseline_diff_ratchet():
    f1 = Finding(rule="r", path="a.py", message="m", context="f")
    f2 = Finding(rule="r", path="b.py", message="m", context="g")
    base = {f1.key: "justified"}
    d = diff_baseline([f1, f2], base)
    assert [f.key for f in d["new"]] == [f2.key]
    assert [f.key for f in d["baselined"]] == [f1.key]
    assert d["stale"] == []
    d2 = diff_baseline([], base)
    assert d2["stale"] == [f1.key]


# ---------------------------------------------------------------------------
# the repo-wide gates (the CI teeth)


def test_repo_ast_layer_clean_vs_baseline():
    findings = ast_rules.lint_repo(REPO)
    d = diff_baseline(findings, load_baseline())
    assert not d["new"], "new AST findings (fix or baseline with a " \
        "justification):\n" + "\n".join(
            "%s %s:%d %s" % (f.rule, f.path, f.line, f.message)
            for f in d["new"])


@pytest.mark.slow  # 88 s at r15 --durations (and growing with every
# audited entry — the tier variants added four): the full trace audit
# still gates every chip enqueue via scripts/graftlint.py itself; the
# smoke tier keeps the AST layer + CLI selfcheck (ISSUE 13 satellite)
def test_repo_trace_audit_clean_vs_baseline():
    """Every public entry point traces clean (fixed shapes, no f64, no
    callbacks, donation aliasable, deterministic retrace). Jaxpr-level
    only: the StableHLO lowering pass adds minutes of CPU for no extra
    rule the entry points could realistically trip (dynamic dims cannot
    appear without symbolic shapes, which none of the entries use)."""
    findings = trace_audit.audit_repo_entry_points(lower=False)
    d = diff_baseline(findings, load_baseline())
    assert not d["new"], "new trace findings:\n" + "\n".join(
        "%s %s %s" % (f.rule, f.context, f.message) for f in d["new"])


@pytest.mark.slow  # 88 s at PR 21 --durations (a cold interpreter + every
# layer's fixtures, models included): the tier-1 command is serial under a
# fixed window, and the rules themselves are pinned in-process by the rest
# of this file. Under jax 0.9 it had been failing fast at the removed
# `jax.experimental.enable_x64`; repaired, it is the suite's longest test.
def test_cli_selfcheck_subprocess():
    """`graftlint --selfcheck` proves every rule fires on seeded fixtures
    (mirrors tpu_queue.py --selfcheck), as a real subprocess, and keeps
    the ONE-JSON-line stdout contract."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "graftlint.py"),
         "--selfcheck"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, "ONE JSON line expected, got: %r" % lines
    rec = json.loads(lines[0])
    assert rec["ok"] is True and rec["selfcheck"] is True
    assert rec["failures"] == []


# ---------------------------------------------------------------------------
# ISSUE 8: serving-scoped rule + per-bucket trace entries


def test_serving_fetch_rule_fires_and_is_scoped():
    """ast/device-get-in-serving-loop: a per-request fetch in a serving
    loop fires; the batched-fetch twin is silent; the same bad source
    OUTSIDE serving/ is the generic rule's business, not this one's."""
    bad = ("import jax\n"
           "def fetch_all(requests, compiled, v):\n"
           "    out = []\n"
           "    for r in requests:\n"
           "        out.append(jax.device_get(compiled(v, r)))\n"
           "    return out\n")
    good = ("import jax\n"
            "def fetch_all(requests, compiled, v):\n"
            "    pending = [compiled(v, r) for r in requests]\n"
            "    return jax.device_get(pending)\n")
    spath = ast_rules.SERVING_PREFIX + "x.py"
    assert "ast/device-get-in-serving-loop" in rules_of(
        ast_rules.lint_source(bad, spath))
    assert "ast/device-get-in-serving-loop" not in rules_of(
        ast_rules.lint_source(good, spath))
    assert "ast/device-get-in-serving-loop" not in rules_of(
        ast_rules.lint_source(bad, "scripts/x.py"))


def test_serving_fetch_allowlist_names_the_engine_fetch_loop():
    """The allowlisted qualname must be the engine's real fetch loop —
    if the method moves/renames, the allowlist (and this pin) must move
    with it, not silently allowlist nothing."""
    import ast as pyast
    path = os.path.join(REPO, "real_time_helmet_detection_tpu", "serving",
                        "engine.py")
    tree = pyast.parse(open(path).read())
    quals = {"%s.%s" % (c.name, f.name)
             for c in pyast.walk(tree) if isinstance(c, pyast.ClassDef)
             for f in c.body if isinstance(f, pyast.FunctionDef)}
    for entry in ast_rules.SERVING_FETCH_ALLOW:
        assert entry.split("::")[1] in quals


def test_serve_bucket_entries_audit_clean():
    """Every serve bucket's program (the engine's per-bucket AOT surface)
    passes the trace rules — the bucket SET is the production surface,
    not just the eval batch shape."""
    for b in trace_audit.SERVE_BUCKETS_AUDIT[:2]:
        predict, variables, images = trace_audit._tiny_serve_parts(b)
        findings = trace_audit.audit_entry(
            lambda v, im: predict(v, im), (variables, images),
            "serve_predict[b=%d]" % b, lower=False)
        assert not findings, [f.message for f in findings]


# ---------------------------------------------------------------------------
# ISSUE 19: hand-picked-threshold rule + xfer findings through the CLI


def test_hand_picked_threshold_scope_and_sanctioned_shapes():
    """ast/hand-picked-threshold: a numeric-literal threshold kwarg fires
    in serving scope (path, serve_bench.py, or a FleetRouter/StreamSession
    reference); the calibrated-artifact resolution and a None argparse
    default are the sanctioned shapes."""
    bad = ("def route(router, img):\n"
           "    return router.submit(img, cascade_threshold=0.25)\n")
    good = ("def route(router, img, cfg):\n"
            "    th = cfg.cascade_overrides()['threshold']\n"
            "    return router.submit(img, cascade_threshold=th)\n")
    spath = ast_rules.SERVING_PREFIX + "x.py"
    assert "ast/hand-picked-threshold" in rules_of(
        ast_rules.lint_source(bad, spath))
    assert "ast/hand-picked-threshold" not in rules_of(
        ast_rules.lint_source(good, spath))
    # serve_bench.py is in scope by path; an unrelated script is not
    assert "ast/hand-picked-threshold" in rules_of(
        ast_rules.lint_source(bad, "scripts/serve_bench.py"))
    assert "ast/hand-picked-threshold" not in rules_of(
        ast_rules.lint_source(bad, "scripts/x.py"))
    # ...unless it references the serving classes
    assert "ast/hand-picked-threshold" in rules_of(ast_rules.lint_source(
        "from real_time_helmet_detection_tpu.serving import StreamSession\n"
        + bad, "scripts/x.py"))
    # argparse: a numeric default on a --*threshold option fires; None +
    # downstream resolution is the sanctioned CLI shape
    argp = ("def cli(p):\n"
            "    p.add_argument('--stream-threshold', type=float,"
            " default=%s)\n")
    assert "ast/hand-picked-threshold" in rules_of(ast_rules.lint_source(
        argp % "1.0", "scripts/serve_bench.py"))
    assert "ast/hand-picked-threshold" not in rules_of(
        ast_rules.lint_source(argp % "None", "scripts/serve_bench.py"))


def test_xfer_findings_render_as_github_annotations():
    """A manifest delta (no source line of its own) anchors its ::error
    annotation to the committed manifest file, so `--format github` CI
    runs show budget regressions inline like any other finding."""
    import importlib.util
    from real_time_helmet_detection_tpu.analysis import transfer_audit as xa
    spec = importlib.util.spec_from_file_location(
        "graftlint_mod", os.path.join(REPO, "scripts", "graftlint.py"))
    gl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gl)
    entry = {"d2h": {"leaves": 1, "bytes": 8, "shapes": ["float32[]"]},
             "h2d_fresh": {"leaves": 1, "bytes": 4},
             "donated": {"leaves": 1, "bytes": 400},
             "host_callbacks": 0}
    grown = json.loads(json.dumps(entry))
    grown["d2h"]["leaves"] = 2
    grown["d2h"]["shapes"] = ["float32[]", "float32[]"]
    res = xa.gate_manifest({"e": grown},
                           {"schema": xa.SCHEMA, "entries": {"e": entry}})
    assert rules_of(res["findings"]) == {"xfer/extra-fetch-leaf"}
    lines = gl.github_annotations(res["findings"])
    assert len(lines) == 1
    assert lines[0].startswith(
        "::error file=%s,line=1,title=xfer/extra-fetch-leaf"
        % xa.MANIFEST_RELPATH)
