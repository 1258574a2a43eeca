"""graftlint — trace-level jit-hygiene auditor + repo-convention linter.

The static half of the campaign-loss postmortems: every class of mistake
that cost a round (eager per-op dispatch, per-call timing, un-donated
buffers, queue-bypassing chip scripts, non-atomic artifact writes —
CLAUDE.md) is checked mechanically BEFORE a chip-second is spent. The
reference repo has nothing comparable (its only check is a manual module
self-test, ref /root/reference/hourglass.py:241-256).

Four layers (real_time_helmet_detection_tpu/analysis/):

* AST convention rules (`ast_rules.py`, stdlib-only)  — always run
* trace audit (`trace_audit.py`, jaxpr + StableHLO over the public entry
  points) — CPU-only, zero TPU contact; skip with `--ast-only`
* concurrency audit (`lock_audit.py`, stdlib-only) — lockset inference,
  lock-order cycles, blocking/callback-under-lock over the threaded
  serving plane; its dynamic twin (`interleave.py`) replays seeded
  thread schedules so flagged races are PROVABLE (the selfcheck
  reproduces the PR 12 health() torn read and the AB/BA deadlock on
  seeded schedules, and certifies the fixed shapes clean)
* transfer-budget audit (`transfer_audit.py`) — every registered jitted
  surface's D2H/H2D interface (fetched leaves, donated vs fresh inputs,
  host callbacks) ratchet-gated against the committed
  `analysis/transfer_manifest.json` (leaf counts exact, bytes 2%);
  CPU-only like the trace layer; skip with `--ast-only`. In `--changed`
  mode only the entry points whose owning modules were touched are
  re-measured.

Findings diff against the committed `analysis/baseline.json` (ratchet:
new findings fail, baselined entries are individually justified; the
baseline is EMPTY — findings get fixed or annotated, not grandfathered).
Run it before enqueueing chip jobs; CI runs it in the smoke tier
(tests/test_graftlint.py, tests/test_lock_audit.py).

Usage:

    python scripts/graftlint.py                  # full run, gate on new
    python scripts/graftlint.py --ast-only       # skip trace + transfer
    python scripts/graftlint.py --changed HEAD   # ~1 s pre-commit loop:
                                                 # AST+lock layers over
                                                 # files changed vs a ref
                                                 # (+ the transfer gate
                                                 # for touched entry-
                                                 # point modules)
    python scripts/graftlint.py --format github  # ::error annotations
                                                 # (+ the JSON line LAST)
    python scripts/graftlint.py --write-baseline # reset the ratchet
    python scripts/graftlint.py --write-manifest # adopt the measured
                                                 # transfer surfaces as
                                                 # the committed budget
                                                 # (deltas print loudly)
    python scripts/graftlint.py --selfcheck      # prove every rule fires
                                                 # on seeded fixtures
                                                 # (--ast-only skips the
                                                 # slow trace fixtures)

Prints ONE JSON line (repo convention); findings detail goes to stderr.
`--format github` is the documented exception: GitHub only parses
workflow commands from stdout, so annotation lines precede the final
JSON line there. Exit 0 = clean vs baseline, 1 = new findings (or
selfcheck failure).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from real_time_helmet_detection_tpu.analysis import (  # noqa: E402
    Finding, diff_baseline, load_baseline, write_baseline)
from real_time_helmet_detection_tpu.analysis import ast_rules  # noqa: E402
from real_time_helmet_detection_tpu.analysis import interleave  # noqa: E402
from real_time_helmet_detection_tpu.analysis import lock_audit  # noqa: E402


def log(msg: str) -> None:
    print("[graftlint] %s" % msg, file=sys.stderr, flush=True)


def changed_files(ref: str):
    """Repo-relative .py files changed vs `ref` (working tree diff,
    staged + unstaged — the pre-commit view), intersected with the lint
    scope so deleted/out-of-scope paths drop out."""
    import subprocess
    r = subprocess.run(["git", "diff", "--name-only", "-z", ref, "--"],
                       capture_output=True, text=True, cwd=REPO)
    if r.returncode != 0:
        raise SystemExit("graftlint --changed: git diff vs %r failed: %s"
                         % (ref, r.stderr.strip()[:200]))
    changed = {p for p in r.stdout.split("\0") if p.endswith(".py")}
    return sorted(changed & set(ast_rules.repo_files(REPO)))


def github_annotations(findings) -> list:
    """GitHub Actions workflow-command lines for a finding list."""
    return ["::error file=%s,line=%d,title=%s::%s"
            % (f.path, max(1, f.line), f.rule,
               f.message.replace("\n", " "))
            for f in findings]


def _force_cpu() -> None:
    """The audit NEVER touches the chip: pin the CPU platform before the
    first backend use."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from real_time_helmet_detection_tpu.runtime import use_compile_cache
    use_compile_cache()


def run_lint(args) -> int:
    t0 = time.time()
    only = None
    if args.changed:
        only = changed_files(args.changed)
        log("changed mode vs %s: %d file(s) in scope"
            % (args.changed, len(only)))
        findings = []
        for rel in only:
            with open(os.path.join(REPO, rel)) as f:
                findings += ast_rules.lint_source(f.read(), rel)
        log("ast layer: %d finding(s) over %d changed file(s)"
            % (len(findings), len(only)))
    else:
        findings = ast_rules.lint_repo(REPO)
        log("ast layer: %d finding(s) over %d file(s)"
            % (len(findings), len(ast_rules.repo_files(REPO))))
    # layer 3: concurrency audit — per-file rules follow the changed set;
    # the lock-order graph is ALWAYS global (an edge added in a changed
    # file can close a cycle through an untouched one)
    lfind = lock_audit.audit_repo(REPO, only=only)
    log("lock layer: %d finding(s)" % len(lfind))
    findings += lfind
    trace_ran = False
    if not args.ast_only and not args.changed:
        _force_cpu()
        from real_time_helmet_detection_tpu.analysis import trace_audit
        tfind = trace_audit.audit_repo_entry_points(lower=not args.no_lower)
        log("trace layer: %d finding(s)" % len(tfind))
        findings += tfind
        trace_ran = True
    elif args.changed and not args.ast_only:
        log("trace layer skipped in --changed mode (the full run stays "
            "the gate)")

    # layer 4: transfer-budget audit — full runs gate EVERY registered
    # entry point; --changed re-measures only the entries whose owning
    # modules were touched (the manifest lookup itself is cheap)
    xfer_entries = 0
    if not args.ast_only:
        from real_time_helmet_detection_tpu.analysis import transfer_audit
        xonly = None
        if args.changed:
            xonly = transfer_audit.entries_for_changed(only)
        if xonly is None or xonly:
            _force_cpu()
            xres = transfer_audit.audit_transfers(only=xonly)
            xfer_entries = len(xres["measured"])
            log("xfer layer: %d entry point(s) measured, %d finding(s)"
                % (xfer_entries, len(xres["findings"])))
            for line in xres["improved"]:
                log("xfer IMPROVED %s" % line)
            for k in xres["stale"]:
                log("xfer stale manifest entry (no longer registered — "
                    "drop via --write-manifest): %s" % k)
            findings += xres["findings"]
            if args.write_manifest:
                _print_manifest_delta(xres["measured"], transfer_audit)
                path = transfer_audit.write_manifest(xres["measured"])
                log("transfer manifest rewritten -> %s (%d entries)"
                    % (path, xfer_entries))
                # the adoption IS the new budget: re-gate against it so
                # the JSON line reports the post-adoption state
                findings = [f for f in findings
                            if not f.rule.startswith("xfer/")]
                findings += transfer_audit.gate_manifest(
                    xres["measured"],
                    transfer_audit.load_manifest())["findings"]
        else:
            log("xfer layer: no changed entry-point modules — skipped")
    elif args.write_manifest:
        raise SystemExit("graftlint --write-manifest needs the transfer "
                         "layer (drop --ast-only)")

    if args.write_baseline:
        baseline = load_baseline()
        path = write_baseline(findings, reasons=baseline)
        log("baseline rewritten -> %s (%d entries)"
            % (path, len(findings)))

    baseline = load_baseline()
    d = diff_baseline(findings, baseline)
    by_rule = {}
    for f in findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    for f in d["new"]:
        log("NEW %s %s:%d [%s] %s"
            % (f.rule, f.path, f.line, f.context, f.message))
    for f in d["baselined"]:
        log("baselined %s (%s)" % (f.key, baseline.get(f.key, "")))
    for k in d["stale"]:
        log("stale baseline entry (fixed — drop it): %s" % k)

    ok = not d["new"]
    if args.format == "github":
        # the documented ONE-JSON-line exception: GitHub parses workflow
        # commands from stdout only, so annotations precede the (LAST)
        # JSON line
        for ln in github_annotations(d["new"]):
            print(ln)
    print(json.dumps({
        "tool": "graftlint", "ok": ok, "findings": len(findings),
        "new": len(d["new"]), "baselined": len(d["baselined"]),
        "stale_baseline": len(d["stale"]), "by_rule": by_rule,
        "trace_layer": trace_ran, "xfer_entries": xfer_entries,
        "changed": args.changed or None,
        "elapsed_s": round(time.time() - t0, 1),
        "new_keys": sorted(f.key for f in d["new"])[:20],
    }))
    sys.stdout.flush()
    return 0 if ok else 1


def _print_manifest_delta(measured, transfer_audit) -> None:
    """The loud half of --write-manifest: every entry's old vs new budget
    on stderr, so an adoption is a reviewed decision, not a silent
    reset (perfgate --update's convention)."""
    old = transfer_audit.load_manifest().get("entries", {})
    for name in sorted(measured):
        m = measured[name]
        o = old.get(name)
        if o is None:
            log("manifest ADOPT %s: d2h %d leaves/%d B, fresh %d leaves, "
                "donated %d, callbacks %d"
                % (name, m["d2h"]["leaves"], m["d2h"]["bytes"],
                   m["h2d_fresh"]["leaves"], m["donated"]["leaves"],
                   m["host_callbacks"]))
        elif o != m:
            log("manifest CHANGE %s: d2h %d->%d leaves %d->%d B, fresh "
                "%d->%d leaves, donated %d->%d, callbacks %d->%d"
                % (name, o["d2h"]["leaves"], m["d2h"]["leaves"],
                   o["d2h"]["bytes"], m["d2h"]["bytes"],
                   o["h2d_fresh"]["leaves"], m["h2d_fresh"]["leaves"],
                   o["donated"]["leaves"], m["donated"]["leaves"],
                   o["host_callbacks"], m["host_callbacks"]))
    for name in sorted(set(old) - set(measured)):
        log("manifest DROP %s (entry no longer registered)" % name)


# ---------------------------------------------------------------------------
# selfcheck: every rule must fire on its seeded bad fixture and stay
# silent on the good twin (mirrors tpu_queue.py --selfcheck)

AST_FIXTURES = {
    # rule-short-name: (bad source, good source)
    "per-call-timing": (
        "import time, jax\n"
        "def f(c, x):\n"
        "    t0 = time.perf_counter()\n"
        "    jax.block_until_ready(c(x))\n"
        "    return time.perf_counter() - t0\n",
        "import time, jax\n"
        "def f(c, x):\n"
        "    out = c(x)\n"
        "    jax.block_until_ready(out)\n"
        "def g():\n"
        "    return time.perf_counter()\n",
    ),
    "queue-bypass": (
        "import jax\n"
        "devs = jax.devices()\n",
        "import jax\n"
        "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
        "def main():\n"
        "    devs = jax.devices()\n"
        "run_as_job(main)\n",
    ),
    "raw-artifact-write": (
        "import json\n"
        "def dump(path, obj):\n"
        "    with open(path, 'w') as f:\n"
        "        json.dump(obj, f)\n",
        "from real_time_helmet_detection_tpu.utils import save_json\n"
        "def dump(path, obj):\n"
        "    save_json(path, obj)\n"
        "def read(path):\n"
        "    with open(path) as f:\n"
        "        return f.read()\n",
    ),
    "device-get-in-loop": (
        "import jax\n"
        "def run(step, state, batches):\n"
        "    for b in batches:\n"
        "        state, loss = step(state, b)\n"
        "        print(jax.device_get(loss))\n",
        "import jax\n"
        "def run(step, state, batches):\n"
        "    pending = []\n"
        "    for b in batches:\n"
        "        state, loss = step(state, b)\n"
        "        pending.append(loss)\n"
        "    return jax.device_get(pending)\n",
    ),
    "missing-ref-citation": (
        '"""A public module with no provenance at all."""\n'
        "X = 1\n",
        '"""A cited module (ref train.py:86) with provenance."""\n'
        "X = 1\n",
    ),
    "unbounded-retry": (
        # the r2 probe-kill class: swallow, loop again, forever, no pause
        "import jax\n"
        "def wait_for_claim():\n"
        "    while True:\n"
        "        try:\n"
        "            return jax.devices()\n"
        "        except Exception:\n"
        "            continue\n",
        # bounded attempts + backoff (and a consumer loop stays exempt)
        "import queue, time, jax\n"
        "def wait_for_claim():\n"
        "    for attempt in range(5):\n"
        "        try:\n"
        "            return jax.devices()\n"
        "        except Exception:\n"
        "            time.sleep(2.0 * (attempt + 1))\n"
        "    raise RuntimeError('claim never cleared')\n"
        "def consume(q):\n"
        "    while True:\n"
        "        task = q.get()\n"
        "        if task is None:\n"
        "            break\n"
        "        try:\n"
        "            task()\n"
        "        except Exception:\n"
        "            continue\n",
    ),
    "raw-metric-aggregation": (
        # a chip-path script hand-rolling a nearest-rank percentile +
        # an np.percentile call over per-request latencies
        "import numpy as np, jax\n"
        "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
        "def pctl(vals, q):\n"
        "    s = sorted(vals)\n"
        "    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]\n"
        "def main():\n"
        "    jax.devices()\n"
        "    lats = [0.1, 0.2]\n"
        "    rec = {'p50': pctl(lats, 0.5),\n"
        "           'p99': float(np.percentile(lats, 99))}\n"
        "run_as_job(main)\n",
        # the same script routed through the metrics plane
        "import jax\n"
        "from real_time_helmet_detection_tpu.obs.metrics import Histogram\n"
        "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
        "def main():\n"
        "    jax.devices()\n"
        "    h = Histogram('lat_ms')\n"
        "    for v in (0.1, 0.2):\n"
        "        h.observe(v * 1e3)\n"
        "    rec = {'p50': h.quantile(0.5), 'p99': h.quantile(0.99)}\n"
        "run_as_job(main)\n",
    ),
    "unbarriered-collective-start": (
        # a multi-process entry point compiling + executing with no
        # barrier: the first execution's fresh Gloo context (30 s hard
        # KeyValue deadline) eats the per-rank compile skew
        "import jax\n"
        "from real_time_helmet_detection_tpu.parallel import "
        "init_process_group\n"
        "def main(rank, world, step, state, arrays):\n"
        "    init_process_group('127.0.0.1:29500', world, rank)\n"
        "    compiled = step.lower(state, *arrays).compile()\n"
        "    return compiled(state, *arrays)\n",
        # the barrier law: AOT-compile -> coordination barrier -> execute
        "import jax\n"
        "from real_time_helmet_detection_tpu.parallel import ("
        "barrier_synced_compile, init_process_group)\n"
        "def main(rank, world, step, state, arrays):\n"
        "    init_process_group('127.0.0.1:29500', world, rank)\n"
        "    compiled = barrier_synced_compile(step, (state, *arrays),\n"
        "                                      name='train_step')\n"
        "    return compiled(state, *arrays)\n",
    ),
    "raw-span-timing": (
        # a chip-path script (acquires a backend) timing a span by hand
        "import time\n"
        "from bench import acquire_backend\n"
        "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
        "def main():\n"
        "    jax, devs = acquire_backend()\n"
        "    t0 = time.time()\n"
        "    compiled = build()\n"
        "    rec = {'compile_s': time.time() - t0}\n"
        "run_as_job(main)\n",
        # the same script routed through the flight recorder
        "from bench import acquire_backend\n"
        "from real_time_helmet_detection_tpu.obs.spans import maybe_tracer\n"
        "from real_time_helmet_detection_tpu.runtime import run_as_job\n"
        "def main():\n"
        "    jax, devs = acquire_backend()\n"
        "    with maybe_tracer().span('compile') as sp:\n"
        "        compiled = build()\n"
        "    rec = {'compile_s': sp.dur_s}\n"
        "run_as_job(main)\n",
    ),
}


FLEET_FIXTURES = {
    # the fleet bypass rule renders at a serving/fleet path (ISSUE 12)
    "engine-bypass-in-fleet": (
        # a fleet module constructing a raw engine and submitting to a
        # replica's engine directly — the tenant/SLO/canary accounting
        # never sees that traffic
        "def route(predict, variables, replicas, image):\n"
        "    spare = ServingEngine(predict, variables, (64, 64, 3),\n"
        "                          'uint8')\n"
        "    return replicas[0].engine.submit(image)\n",
        # the sanctioned shape: construction through the factory, traffic
        # through router dispatch
        "def route(router, image):\n"
        "    return router.submit(image, tenant='bulk')\n"
        "def spawn(factory, rid):\n"
        "    return factory(rid, True)\n",
    ),
}


SERVING_FIXTURES = {
    # trace-context hygiene (ISSUE 14): a request-path span without
    # ctx=/links= in serving code is invisible to the waterfall
    # assembler; lifecycle spans and context-carrying emissions pass
    "context-free-span": (
        # serve:shed (a per-request terminal!) emitted context-free, and
        # a batch d2h span without its fan-in links
        "def shed(tracer, req):\n"
        "    tracer.event('serve:shed', reason='deadline')\n"
        "def fetch(self, b, live):\n"
        "    with self._tracer.span('serve:d2h', b=b):\n"
        "        pass\n",
        # the same sites carrying their contexts + an exempt lifecycle
        # span + a non-request span name (untraced bench section is fine)
        "def shed(tracer, req):\n"
        "    tracer.event('serve:shed', ctx=req.ctx, reason='deadline')\n"
        "def fetch(self, b, live, links):\n"
        "    with self._tracer.span('serve:d2h', b=b, links=links):\n"
        "        pass\n"
        "def lifecycle(tracer):\n"
        "    tracer.event('serve:state', **{'from': 'a', 'to': 'b'})\n"
        "    with tracer.span('serve:compile', b=4):\n"
        "        pass\n",
    ),
    # rules scoped to the serving package render at a serving/ path
    "device-get-in-serving-loop": (
        # a per-request fetch inside the batch loop — the sync the engine
        # exists to amortize
        "import jax\n"
        "def fetch_all(requests, compiled, variables):\n"
        "    out = []\n"
        "    for r in requests:\n"
        "        out.append(jax.device_get(compiled(variables, r)))\n"
        "    return out\n",
        # the engine pattern: dispatch per request, ONE batched fetch
        "import jax\n"
        "def fetch_all(requests, compiled, variables):\n"
        "    pending = [compiled(variables, r) for r in requests]\n"
        "    return jax.device_get(pending)\n",
    ),
}


THRESHOLD_FIXTURES = {
    # calibrated-artifact law (ISSUE 19 satellite): a numeric-literal
    # confidence/skip threshold reaching the serving plane drifts
    # silently when the model or data changes — the sanctioned shape
    # resolves it from the quality_matrix artifact (or derives it from
    # the data in hand)
    "hand-picked-threshold": (
        # a constant escalation threshold at a router call site, and an
        # argparse threshold option defaulting to a magic number
        "def route(router, img):\n"
        "    return router.submit(img, tenant='cam',\n"
        "                         cascade_threshold=0.25)\n"
        "def cli(p):\n"
        "    p.add_argument('--skip-threshold', type=float,"
        " default=1.0)\n",
        # the sanctioned shapes: resolved from the calibrated artifact;
        # None default + explicit resolution downstream
        "def route(router, img, cfg):\n"
        "    th = cfg.cascade_overrides()['threshold']\n"
        "    return router.submit(img, tenant='cam',\n"
        "                         cascade_threshold=th)\n"
        "def cli(p):\n"
        "    p.add_argument('--skip-threshold', type=float,"
        " default=None)\n",
    ),
}


LOCK_FIXTURES = {
    # rule-short-name: (bad source, good source) — linted standalone via
    # lock_audit.audit_source (layer 3)
    "unguarded-shared-write": (
        # the PR 12 class: state written under the lock, read outside it
        "import threading\n"
        "class Eng:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._state = 'serving'\n"
        "    def set_state(self, s):\n"
        "        with self._lock:\n"
        "            self._state = s\n"
        "    def state(self):\n"
        "        return self._state\n",
        # the fix: every touch inside a window
        "import threading\n"
        "class Eng:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._state = 'serving'\n"
        "    def set_state(self, s):\n"
        "        with self._lock:\n"
        "            self._state = s\n"
        "    def state(self):\n"
        "        with self._lock:\n"
        "            return self._state\n",
    ),
    "order-cycle": (
        # AB in one method, BA in another: deadlock potential (the
        # interleave harness drives this exact shape into the detected
        # deadlock — see the dynamic checks below)
        "import threading\n"
        "class X:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def m1(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def m2(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n",
        # ONE global order
        "import threading\n"
        "class X:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def m1(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def m2(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n",
    ),
    "blocking-call-under-lock": (
        # a batched D2H inside the mutex: every submitter stalls ~70 ms
        "import threading, jax\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.out = None\n"
        "    def flush(self, dev):\n"
        "        with self._lock:\n"
        "            self.out = jax.device_get(dev)\n",
        # fetch outside, publish under the lock
        "import threading, jax\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.out = None\n"
        "    def flush(self, dev):\n"
        "        host = jax.device_get(dev)\n"
        "        with self._lock:\n"
        "            self.out = host\n",
    ),
    "callback-under-lock": (
        # user code inside the critical section: re-entry deadlocks
        "import threading\n"
        "class F:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cb = None\n"
        "    def set_cb(self, fn):\n"
        "        with self._lock:\n"
        "            self._cb = fn\n"
        "    def fire(self):\n"
        "        with self._lock:\n"
        "            cb = self._cb\n"
        "            cb(self)\n",
        # the ServeFuture._run_callback shape: snapshot, release, fire
        "import threading\n"
        "class F:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cb = None\n"
        "    def set_cb(self, fn):\n"
        "        with self._lock:\n"
        "            self._cb = fn\n"
        "    def fire(self):\n"
        "        with self._lock:\n"
        "            cb = self._cb\n"
        "        cb(self)\n",
    ),
}


def _selfcheck_lock(check) -> None:
    spath = ast_rules.SERVING_PREFIX + "lock_fixture_%s.py"
    for short, (bad, good) in LOCK_FIXTURES.items():
        rule = "lock/" + short
        bad_f = lock_audit.audit_source(bad, spath % "bad")
        good_f = lock_audit.audit_source(good, spath % "good")
        check("%s fires on bad fixture" % rule,
              any(f.rule == rule for f in bad_f))
        check("%s silent on good fixture" % rule,
              not any(f.rule == rule for f in good_f))
    # the annotation convention: a guarded-by'd caller-holds-the-lock
    # scope and a lock-free'd intentional read both go silent
    bad, _good = LOCK_FIXTURES["unguarded-shared-write"]
    ann = bad.replace("    def state(self):",
                      "    def state(self):  # lock-free: GIL-atomic "
                      "single-field read")
    check("lock-free annotation honored",
          not lock_audit.audit_source(ann, spath % "ann"))
    guarded = (
        "import threading\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._tenants = {}\n"
        "    def _tenant(self, name):  # guarded-by: _lock\n"
        "        self._tenants[name] = 1\n"
        "    def submit(self, name):\n"
        "        with self._lock:\n"
        "            self._tenant(name)\n")
    check("guarded-by annotation honored",
          not lock_audit.audit_source(guarded, spath % "gb"))
    # thread-shared state with no lock at all (the HangWatchdog class)
    threaded = (
        "import threading\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._warned = False\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _run(self):\n"
        "        self._warned = True\n"
        "    def beat(self):\n"
        "        self._warned = False\n")
    check("lock/unguarded-shared-write fires on lockless thread share",
          any(f.rule == "lock/unguarded-shared-write"
              for f in lock_audit.audit_source(threaded, spath % "thr")))
    # graftlint: off= suppression works on the lock layer too
    sup = bad.replace("        return self._state",
                      "        return self._state  "
                      "# graftlint: off=unguarded-shared-write")
    check("lock layer honors graftlint: off=",
          not lock_audit.audit_source(sup, spath % "sup"))

    # ---- dynamic half: seeded interleaving proofs (CPU, milliseconds)
    torn = interleave.find_torn_read(fixed=False)
    check("interleave reproduces the PR 12 health() torn read",
          torn is not None)
    if torn is not None:
        sched = interleave.Scheduler(torn["seed"])
        fx = interleave.TornHealthFixture(sched, fixed=False)
        observed = []

        def reader():
            for _ in range(3):
                observed.append(fx.health())

        def writer():
            for _ in range(2):
                fx.reload()

        sched.run([reader, writer])
        check("torn-read schedule replays deterministically (seed %d)"
              % torn["seed"], sched.trace == torn["trace"])
    check("single-window health() certified clean over the seed sweep",
          interleave.find_torn_read(fixed=True) is None)
    dl = interleave.find_deadlock(ordered=False)
    check("interleave drives the AB/BA cycle into a detected deadlock",
          dl is not None and len(dl["waiting"]) == 2)
    check("single-order twin never deadlocks over the seed sweep",
          interleave.find_deadlock(ordered=True) is None)


def _selfcheck_ast(check) -> None:
    for short, (bad, good) in AST_FIXTURES.items():
        rule = "ast/" + short
        # scripts/fixture.py path so path-scoped rules (queue-bypass)
        # consider the fixture in scope
        bad_f = ast_rules.lint_source(bad, "scripts/fixture_bad.py")
        good_f = ast_rules.lint_source(good, "scripts/fixture_good.py")
        check("%s fires on bad fixture" % rule,
              any(f.rule == rule for f in bad_f))
        check("%s silent on good fixture" % rule,
              not any(f.rule == rule for f in good_f))
    for short, (bad, good) in SERVING_FIXTURES.items():
        rule = "ast/" + short
        spath = ast_rules.SERVING_PREFIX + "fixture_%s.py"
        bad_f = ast_rules.lint_source(bad, spath % "bad")
        good_f = ast_rules.lint_source(good, spath % "good")
        check("%s fires on bad fixture" % rule,
              any(f.rule == rule for f in bad_f))
        check("%s silent on good fixture" % rule,
              not any(f.rule == rule for f in good_f))
        # out-of-scope twin: the same bad source outside serving/ must not
        # fire this rule (the generic device-get-in-loop covers it there)
        check("%s scoped to serving/" % rule,
              not any(f.rule == rule for f in ast_rules.lint_source(
                  bad, "scripts/fixture_scope.py")))
    for short, (bad, good) in FLEET_FIXTURES.items():
        rule = "ast/" + short
        fpath = ast_rules.SERVING_PREFIX + "fleet_fixture_%s.py"
        bad_f = ast_rules.lint_source(bad, fpath % "bad")
        good_f = ast_rules.lint_source(good, fpath % "good")
        check("%s fires on bad fixture" % rule,
              any(f.rule == rule for f in bad_f))
        check("%s silent on good fixture" % rule,
              not any(f.rule == rule for f in good_f))
        # out-of-scope twin: the same bad source in a module that neither
        # lives at a fleet path nor references FleetRouter must not fire
        check("%s scoped to fleet code paths" % rule,
              not any(f.rule == rule for f in ast_rules.lint_source(
                  bad, "scripts/fixture_scope.py")))
        # ...but ANY module referencing FleetRouter is in scope
        check("%s follows FleetRouter references" % rule,
              any(f.rule == rule for f in ast_rules.lint_source(
                  "from real_time_helmet_detection_tpu.serving import "
                  "FleetRouter\n" + bad, "scripts/fixture_router.py")))
    for short, (bad, good) in THRESHOLD_FIXTURES.items():
        rule = "ast/" + short
        tpath = ast_rules.SERVING_PREFIX + "threshold_fixture_%s.py"
        check("%s fires on bad fixture" % rule,
              any(f.rule == rule for f in ast_rules.lint_source(
                  bad, tpath % "bad")))
        check("%s silent on good fixture" % rule,
              not any(f.rule == rule for f in ast_rules.lint_source(
                  good, tpath % "good")))
        # serve_bench.py is explicitly in scope: its SIM threshold knobs
        # are exactly the surface the rule audits
        check("%s covers scripts/serve_bench.py" % rule,
              any(f.rule == rule for f in ast_rules.lint_source(
                  bad, "scripts/serve_bench.py")))
        # out-of-scope twin: neither a serving path nor a
        # FleetRouter/StreamSession reference — must stay silent
        check("%s scoped to serving code paths" % rule,
              not any(f.rule == rule for f in ast_rules.lint_source(
                  bad, "scripts/fixture_scope.py")))
        # ...but ANY module referencing StreamSession is in scope
        check("%s follows StreamSession references" % rule,
              any(f.rule == rule for f in ast_rules.lint_source(
                  "from real_time_helmet_detection_tpu.serving import "
                  "StreamSession\n" + bad, "scripts/fixture_stream.py")))
        # inline suppression on the literal's own line goes silent
        sup = bad.replace(
            "cascade_threshold=0.25)",
            "cascade_threshold=0.25)  "
            "# graftlint: off=hand-picked-threshold").replace(
            "default=1.0)",
            "default=1.0)  # graftlint: off=hand-picked-threshold")
        check("%s honors inline suppression" % rule,
              not any(f.rule == rule for f in ast_rules.lint_source(
                  sup, tpath % "sup")))
    # suppression marker: the bad fixture plus an inline off= goes silent
    bad = AST_FIXTURES["raw-artifact-write"][0].replace(
        "'w') as f:", "'w') as f:  # graftlint: off=raw-artifact-write")
    check("inline suppression honored",
          not any(f.rule == "ast/raw-artifact-write" for f in
                  ast_rules.lint_source(bad, "scripts/fixture_sup.py")))


def _selfcheck_trace(check) -> None:
    _force_cpu()
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.analysis import trace_audit as ta

    x = np.ones((4, 4), np.float32)

    def rules_of(findings):
        return {f.rule for f in findings}

    # trace-failure: boolean filtering (dynamic result shape) dies at trace
    bad = lambda v: v[v > 0]  # noqa: E731
    good = lambda v: jnp.where(v > 0, v, 0.0)  # noqa: E731
    check("trace/trace-failure fires on boolean filtering",
          "trace/trace-failure" in rules_of(ta.audit_entry(bad, (x,),
                                                           "fix")))
    ok_f = ta.audit_entry(good, (x,), "fix")
    check("masked twin audits clean", not ok_f)

    # f64: a wide-dtype leak under x64
    with jax.enable_x64(True):
        f64 = ta.audit_entry(lambda v: jnp.asarray(v, jnp.float64) * 2.0,
                             (x,), "fix", lower=False)
    check("trace/f64 fires under x64 leak", "trace/f64" in rules_of(f64))

    # host-callback
    def with_cb(v):
        jax.debug.print("x={}", v[0, 0])
        return v * 2

    check("trace/host-callback fires on debug callback",
          "trace/host-callback" in rules_of(
              ta.audit_entry(with_cb, (x,), "fix", lower=False)))

    # donation: donated input, no aliasing output
    bad_don = lambda v: jnp.sum(v)  # noqa: E731
    good_don = lambda v: (v + 1.0, jnp.sum(v))  # noqa: E731
    check("trace/donation fires on unusable donation",
          "trace/donation" in rules_of(
              ta.audit_entry(bad_don, (x,), "fix", donate_argnums=(0,),
                             lower=False)))
    check("trace/donation silent when aliasable",
          "trace/donation" not in rules_of(
              ta.audit_entry(good_don, (x,), "fix", donate_argnums=(0,),
                             lower=False)))

    # retrace instability: trace-time RNG constant
    unstable = lambda v: v + random.random()  # noqa: E731
    check("trace/retrace-unstable fires on trace-time RNG",
          "trace/retrace-unstable" in rules_of(
              ta.audit_entry(unstable, (x,), "fix", lower=False)))

    # dynamic-shape: a symbolically-shaped export trace lowers with ? dims
    b = jax.export.symbolic_shape("b")[0]
    spec = jax.ShapeDtypeStruct((b, 4), jnp.float32)
    dyn = ta.stablehlo_findings(lambda v: v * 2.0, (spec,), "fix")
    check("trace/dynamic-shape fires on symbolic dims",
          any(f.rule == "trace/dynamic-shape" for f in dyn))

    check("trace/dynamic-shape silent on static shapes",
          not ta.stablehlo_findings(lambda v: v * 2.0, (x,), "fix"))

    # the quantized predict entry point (ISSUE 5): the int8 twin's trace
    # must pass the dynamic-shape/f64/donation rules like every other
    # production surface — the fold + round/clip/conv-int32 body is easy
    # to get wrong in exactly these ways (a np.percentile host call, an
    # f64 rsqrt, a chain that drops its carry)
    predict_q, variables_q, images_q = ta._tiny_predict_int8_parts()
    qf = ta.audit_entry(lambda v, im: predict_q(v, im),
                        (variables_q, images_q), "predict_int8")
    check("quantized predict audits clean", not qf)
    qc = ta.audit_entry(ta._predict_chain(predict_q),
                        (variables_q, images_q), "predict_int8_chain",
                        donate_argnums=(1,), lower=False)
    check("quantized predict chain donation ok",
          not any(f.rule == "trace/donation" for f in qc) and not qc)

    # the ISSUE-7 entry points: the bf16 param-policy scanned step (fp32
    # master inside the optimizer state — the donation surface every
    # mistake class loves) and the fused-epilogue predict (the fold-algebra
    # eval tail in every conv tail) must audit clean like the surfaces
    # they replace — donation/f64/dynamic-shape included (full audit_entry
    # incl. lowering)
    # the serve bucket set (ISSUE 8): every bucket the engine AOT-compiles
    # must audit clean — the bucket programs ARE the production serving
    # surface (dynamic-shape/f64/host-callback rules across the set)
    for b in ta.SERVE_BUCKETS_AUDIT[:2]:
        predict_s, variables_s, images_s = ta._tiny_serve_parts(b)
        sf = ta.audit_entry(lambda v, im: predict_s(v, im),
                            (variables_s, images_s),
                            "serve_predict[b=%d]" % b, lower=b == 1)
        check("serve bucket b=%d audits clean" % b, not sf)

    train_bf16, targs_bf16 = ta._tiny_train_parts("none", "bf16-compute")
    pf = ta.audit_entry(train_bf16, targs_bf16,
                        "train_step_scanned[param=bf16-compute]",
                        donate_argnums=(0,))
    check("bf16-policy scanned step audits clean", not pf)

    # the tier-variant entry points (ISSUE 13): smallest (edge/depthwise)
    # and largest (quality/residual stack2) tier — train step + predict
    # must audit as clean as the flagship surfaces they sit beside (the
    # repo baseline stays EMPTY: anything these raise gets FIXED)
    for tier, arch in ta.TIER_AUDIT:
        train_t, targs_t = ta._tiny_train_parts("none", arch=arch)
        tf = ta.audit_entry(train_t, targs_t,
                            "train_step_scanned[tier=%s]" % tier,
                            donate_argnums=(0,), lower=tier == "edge")
        check("tier=%s scanned step audits clean" % tier, not tf)
        predict_t, variables_t, images_t = ta._tiny_predict_parts(
            arch=arch)
        pf_t = ta.audit_entry(lambda v, im, _p=predict_t: _p(v, im),
                              (variables_t, images_t),
                              "predict[tier=%s]" % tier,
                              lower=tier == "edge")
        check("tier=%s predict audits clean" % tier, not pf_t)
    predict_e, variables_e, images_e = ta._tiny_predict_parts(
        epilogue="fused")
    ef = ta.audit_entry(lambda v, im: predict_e(v, im),
                        (variables_e, images_e), "predict_epilogue_fused")
    check("fused-epilogue predict audits clean", not ef)

    # the ISSUE-20 step-compression surfaces: the block-fused scanned
    # step (residual-tail BN+add+act custom_vjp), the int8-STE-forward
    # scanned step (per-step in-jit scale refresh), and the block-fused
    # predict — each must keep the plain step's donation/f64/dynamic-
    # shape surface (the repo baseline stays EMPTY)
    train_bf, targs_bf = ta._tiny_train_parts(block_fuse="fused")
    bff = ta.audit_entry(train_bf, targs_bf,
                         "train_step_scanned[block-fuse]",
                         donate_argnums=(0,))
    check("block-fused scanned step audits clean", not bff)
    train_i8, targs_i8 = ta._tiny_train_parts(fwd_dtype="int8")
    i8f = ta.audit_entry(train_i8, targs_i8,
                         "train_step_scanned[fwd=int8]",
                         donate_argnums=(0,))
    check("int8-forward scanned step audits clean", not i8f)
    predict_bf, variables_bf, images_bf = ta._tiny_predict_parts(
        block_fuse="fused")
    pbf = ta.audit_entry(lambda v, im: predict_bf(v, im),
                         (variables_bf, images_bf), "predict_block_fused")
    check("block-fused predict audits clean", not pbf)

    # the cascade-summary predict (ISSUE 16): the edge serving program
    # with the in-jit confidence summary — the FleetRouter escalation
    # signal rides this trace, so dynamic shapes/f64/retrace instability
    # here would recompile on the cascade hot path (baseline stays EMPTY)
    predict_c, variables_c, images_c = ta._tiny_predict_parts(
        arch=dict(ta.TIER_AUDIT[0][1]), cascade_summary=True)
    cf = ta.audit_entry(lambda v, im: predict_c(v, im),
                        (variables_c, images_c),
                        "predict_cascade_summary[tier=edge]")
    check("cascade-summary predict audits clean", not cf)

    # the streaming programs (ISSUE 17): the in-jit per-tile delta
    # summary dispatches once per frame on every stream, and the tile
    # predict the gated submits ride is the raw-uint8 serve wire —
    # both must audit clean (baseline stays EMPTY); the delta program
    # must also be retrace-stable, or every frame would recompile
    from real_time_helmet_detection_tpu.ops.delta import (
        tile_delta_summary)
    frame_st = np.zeros((2 * 64, 2 * 64, 3), np.uint8)
    df = ta.audit_entry(lambda p, c: tile_delta_summary(p, c, grid=2),
                        (frame_st, frame_st),
                        "stream_delta_summary[grid=2]")
    check("stream delta-summary audits clean", not df)
    predict_st, variables_st, images_st = ta._tiny_serve_parts(2)
    stf = ta.audit_entry(lambda v, im, _p=predict_st: _p(v, im),
                         (variables_st, images_st),
                         "stream_tile_predict[b=2]", lower=False)
    check("stream tile predict audits clean", not stf)


def _selfcheck_xfer(check) -> None:
    """Layer 4 on seeded synthetic programs: the three regression
    classes (extra fetched leaf, newly un-donated input, +10% D2H bytes)
    each FAIL the manifest gate while an in-tolerance byte wiggle
    passes — no model build, milliseconds."""
    _force_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from real_time_helmet_detection_tpu.analysis import transfer_audit as xa

    state = np.zeros((100,), np.float32)
    batch = np.zeros((100,), np.float32)

    def base(s, b):
        # the scanned-step shape: state round-trips through the donated
        # buffer, one fetched f32[100] leaf (400 B) rides out
        return s + 1.0, b * 2.0

    m0 = xa.measure_entry(base, (state, batch), (0,))
    check("measure: donated state leaf never counts as a fetch",
          m0["d2h"]["leaves"] == 1 and m0["d2h"]["bytes"] == 400
          and m0["donated"]["leaves"] == 1
          and m0["h2d_fresh"]["leaves"] == 1)
    manifest = {"schema": xa.SCHEMA, "entries": {"base": m0}}

    def rules_of(res):
        return {f.rule for f in res["findings"]}

    same = xa.gate_manifest(
        {"base": xa.measure_entry(base, (state, batch), (0,))}, manifest)
    check("identical surface gates clean",
          not same["findings"] and not same["improved"])

    def extra_leaf(s, b):
        return s + 1.0, b * 2.0, jnp.sum(b)

    check("xfer/extra-fetch-leaf FAILS on a new output leaf",
          "xfer/extra-fetch-leaf" in rules_of(xa.gate_manifest(
              {"base": xa.measure_entry(extra_leaf, (state, batch),
                                        (0,))}, manifest)))
    check("xfer/undonated-input FAILS when donation is dropped",
          "xfer/undonated-input" in rules_of(xa.gate_manifest(
              {"base": xa.measure_entry(base, (state, batch), ())},
              manifest)))

    def grown(s, b):
        return s + 1.0, jnp.concatenate([b, b[:10]]) * 2.0  # +10% bytes

    def wiggle(s, b):
        return s + 1.0, jnp.concatenate([b, b[:1]]) * 2.0   # +1% bytes

    check("xfer/d2h-bytes-grew FAILS at +10%",
          "xfer/d2h-bytes-grew" in rules_of(xa.gate_manifest(
              {"base": xa.measure_entry(grown, (state, batch), (0,))},
              manifest)))
    check("in-tolerance byte wiggle (+1%) passes",
          not xa.gate_manifest(
              {"base": xa.measure_entry(wiggle, (state, batch), (0,))},
              manifest)["findings"])
    check("xfer/unknown-entry FAILS on an unbudgeted entry",
          "xfer/unknown-entry" in rules_of(
              xa.gate_manifest({"new_surface": m0}, manifest)))
    check("xfer/entry-unmeasurable FAILS on a broken builder",
          "xfer/entry-unmeasurable" in rules_of(xa.gate_manifest(
              {"base": {"error": "ValueError: boom"}}, manifest)))

    def with_cb(s, b):
        jax.debug.print("b0={}", b[0])
        return s + 1.0, b * 2.0

    check("xfer/host-callback-grew FAILS on a new callback",
          "xfer/host-callback-grew" in rules_of(xa.gate_manifest(
              {"base": xa.measure_entry(with_cb, (state, batch), (0,))},
              manifest)))

    real = jax.device_get
    with xa.counting_device_get() as c:
        jax.device_get(np.ones(3))
        jax.device_get((np.ones(2), np.ones(2)))
    check("counting_device_get counts fetches (not leaves)",
          c.count == 2 and len(c.calls) == 2)
    check("counting_device_get restores the real fetch on exit",
          jax.device_get is real)


def selfcheck(ast_only: bool = False) -> int:
    t0 = time.time()
    failures = []

    def check(name, cond):
        print("selfcheck %-52s %s" % (name, "ok" if cond else "FAIL"),
              file=sys.stderr, flush=True)
        if not cond:
            failures.append(name)

    _selfcheck_ast(check)
    _selfcheck_lock(check)
    if not ast_only:
        _selfcheck_trace(check)
        _selfcheck_xfer(check)

    ok = not failures
    print(json.dumps({"tool": "graftlint", "selfcheck": True, "ok": ok,
                      "failures": failures, "trace_layer": not ast_only,
                      "elapsed_s": round(time.time() - t0, 1)}))
    sys.stdout.flush()
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ast-only", action="store_true",
                   help="skip the (slower) trace layer")
    p.add_argument("--no-lower", action="store_true",
                   help="trace layer: skip StableHLO lowering (jaxpr "
                        "checks only; faster)")
    p.add_argument("--write-baseline", action="store_true",
                   help="reset the ratchet: rewrite analysis/baseline.json "
                        "from the current findings (existing "
                        "justifications are carried over by key)")
    p.add_argument("--write-manifest", action="store_true",
                   help="adopt the measured transfer surfaces as the "
                        "committed analysis/transfer_manifest.json budget "
                        "(per-entry deltas print loudly; full run only)")
    p.add_argument("--selfcheck", action="store_true",
                   help="prove every rule fires on seeded fixtures "
                        "(with --ast-only: skip the slow trace fixtures "
                        "— the fast pre-commit proof)")
    p.add_argument("--changed", metavar="REF", default=None,
                   help="incremental mode: AST+lock layers over files "
                        "changed vs REF only (~1 s); the trace layer and "
                        "--write-baseline need the full run")
    p.add_argument("--format", choices=("text", "github"), default="text",
                   help="'github' emits ::error annotations for new "
                        "findings before the final JSON line")
    args = p.parse_args(argv)
    if args.selfcheck:
        return selfcheck(ast_only=args.ast_only)
    if args.changed and args.write_baseline:
        p.error("--write-baseline needs the full run, not --changed")
    if args.changed and args.write_manifest:
        p.error("--write-manifest needs the full run, not --changed (a "
                "partial measurement would silently drop budgets)")
    return run_lint(args)


if __name__ == "__main__":
    raise SystemExit(main())
