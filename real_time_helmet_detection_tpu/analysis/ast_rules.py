"""AST convention rules (graftlint layer 2) — stdlib `ast` only, no jax.

Each rule mechanizes one hard-won repo convention (CLAUDE.md "Environment
pitfalls"; the reference repo has no conventions to lint — its closest
analogue is manual code review, ref /root/reference/README.md:1):

* `per-call-timing`     — wall-clock timing bracketing a device fetch in
                          one function: one dispatch's wall is mostly
                          dispatch overhead for a small program, and the
                          rule's original premise (a transport whose
                          completion events resolved before execution)
                          is gone — ROADMAP S1 decides the rule's fate
                          once both timing methods are compared on the
                          chip. Until then: `bench.timed_fetch` /
                          `measure_dispatch_overhead` (the allowlisted
                          implementations).
* `queue-bypass`        — a chip-touching script (acquires a backend)
                          without the job-supervision contract
                          (`run_as_job` / `maybe_job_heartbeat`): ad-hoc
                          chip invocations are how r2/r3/r7 lost their
                          campaigns (scripts/tpu_queue.py is the front-end).
* `raw-artifact-write`  — `open(..., "w"/"wb")` writes outside
                          `utils.save_json`/`atomic_write_bytes`: a kill
                          mid-write leaves a truncated artifact where a
                          complete one stood, and the salvage path trusts
                          every file it finds.
* `device-get-in-loop`  — `jax.device_get` inside a per-step loop outside
                          the allowlisted modules: each materializing
                          fetch is a host<->device sync that breaks
                          async dispatch.
* `missing-ref-citation`— public module docstring without a reference
                          citation (`ref <file:line>` / `/root/reference`
                          path / an explicit no-analogue statement): the
                          parity-checkability convention (CLAUDE.md).
* `raw-span-timing`     — hand-rolled span timing (`time.X() - t0`) in a
                          chip-path script (one that acquires a backend):
                          ad-hoc wall-clock spans are invisible to the
                          flight recorder (obs/spans.py) and keep
                          re-growing the per-call-timing folklore. Use
                          `obs.spans.SpanTracer.span(...)` — it always
                          measures (read `sp.dur_s` for your JSON
                          artifact) and lands in the round's span log when
                          $OBS_SPAN_LOG is set. The sanctioned bench
                          timing harness is allowlisted.
* `device-get-in-serving-loop` — a device fetch inside a loop in the
                          serving package anywhere but the engine's ONE
                          sanctioned batched fetch point: a per-request
                          `device_get` in a serving hot loop serializes
                          the pipeline (one host<->device sync per
                          REQUEST) — exactly
                          the failure continuous batching exists to
                          amortize. Results must ride the per-BATCH D2H
                          (`ServingEngine._fetch_loop`, the allowlisted
                          completion point).
* `raw-metric-aggregation` — hand-rolled running-mean/percentile
                          arithmetic (np.percentile/median/quantile
                          calls, or the sorted-then-rank-index idiom) in
                          a chip-path script: ad-hoc statistics keep
                          re-growing incompatible latency digests that
                          neither merge nor export — route them through
                          `obs.metrics` (fixed-layout mergeable
                          histograms whose snapshots the SLO watchdog
                          and perfgate consume). The sanctioned bench
                          timing harness (median-of-dispatch-overheads)
                          is allowlisted.
* `unbarriered-collective-start` — a multi-process entry point (calls
                          `jax.distributed.initialize` /
                          `init_process_group` / `init_distributed`) that
                          AOT-compiles a program (`.lower(...).compile()`)
                          without the barrier law: every compiled
                          multi-process program creates a fresh Gloo
                          context at FIRST execution with a hard 30 s
                          KeyValue deadline, and skewed per-rank compiles
                          trip it (the flaky DEADLINE_EXCEEDED class).
                          Use `parallel.barrier_synced_compile(...)` (or
                          at least `coordination_barrier` between compile
                          and first execution).
* `engine-bypass-in-fleet` — raw ServingEngine construction or a direct
                          `<x>.engine.submit(...)` inside fleet/router
                          code paths (serving/ fleet modules + anything
                          referencing FleetRouter): traffic that skips
                          FleetRouter dispatch silently escapes tenant
                          budgets, SLO penalty boxes, the canary split
                          and the re-dispatch ack guarantee. The
                          sanctioned factory/dispatch scopes and the
                          single-engine surfaces (evaluate/demo/export-
                          style uses in modules that also drive the
                          fleet) are allowlisted.
* `context-free-span`   — span/record/event emission of a request-path
                          name (`serve:*`, `fleet:*`, `recover:*`)
                          inside the serving package without a
                          trace-context argument (`ctx=`/`links=`):
                          an untraced request-path record is invisible
                          to the waterfall assembler (obs/traceview.py)
                          — the request it belongs to reads as having
                          skipped that stage, and orphan/broken-chain
                          detection silently weakens. Module-scope /
                          process-lifecycle spans (compile, state
                          transitions, rollout arcs — the
                          TRACE_LIFECYCLE_SPANS allowlist) carry no
                          per-request causality and are exempt.
* `unbounded-retry`     — a `while True` retry loop whose except handler
                          swallows the failure and loops again with no
                          attempt cap and no backoff: the r2 probe-kill
                          mistake class (an unbounded reconnect loop
                          hammers a dead backend forever). Retries must be
                          bounded (`for attempt in range(N)`) and/or
                          backed off (`time.sleep` in the loop). Consumer
                          loops that block on a queue-style `.get()` are
                          exempt — they re-attempt on NEW work, not the
                          same failing operation.

Suppression: a `# graftlint: off=<rule>[,<rule>]` comment anywhere inside
the flagged node's line span disables that rule there — every suppression
should carry a nearby justification comment, exactly like a baseline
entry.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Iterable, List, Optional, Sequence, Tuple

from . import Finding

# ---------------------------------------------------------------------------
# scope: which files each rule applies to (paths repo-relative, "/"-sep)

EXCLUDE_DIRS = {"tests", "artifacts", "build", "cpp", "docs", ".git",
                "__pycache__", ".claude"}

# chip-touching scripts: must run under the job-supervision contract
QUEUE_RULE_PREFIXES = ("scripts/",)
QUEUE_RULE_FILES = {"bench.py", "scaling.py"}

# documented exemptions, mirrored in docs/ARCHITECTURE.md's rule table:
TIMING_ALLOW = {
    # THE sanctioned timing harness: scan-inside-one-program + scalar
    # fetch minus measured dispatch overhead (bench.py module docstring)
    "bench.py::measure_dispatch_overhead",
    "bench.py::timed_fetch",
    "bench.py::chain_timed_fetch",
}
DEVICE_GET_LOOP_ALLOW = {
    # software-pipelined eval loop: the device_get IS the designed
    # completion point for batch i while batch i+1 computes
    "real_time_helmet_detection_tpu/evaluate.py",
    # deferred loss flush every print_interval steps + epoch-boundary
    # scalar fetches — the documented alternative to a per-step sync
    "real_time_helmet_detection_tpu/train.py",
    # the serving engine's batched fetch loop is the designed completion
    # point of the in-flight pipeline; the STRICTER serving-specific rule
    # below (device-get-in-serving-loop) polices this package instead,
    # allowing only that one fetch point
    "real_time_helmet_detection_tpu/serving/engine.py",
}
# the serving package's ONE sanctioned fetch point: the depth-pipelined
# per-BATCH D2H (everything else in serving/ that fetches in a loop is a
# per-request sync bug)
SERVING_PREFIX = "real_time_helmet_detection_tpu/serving/"
SERVING_FETCH_ALLOW = {
    "real_time_helmet_detection_tpu/serving/engine.py::"
    "ServingEngine._fetch_loop",
}
# fleet/router code paths (ISSUE 12): modules under serving/ whose name
# marks them as fleet code, plus ANY module that references FleetRouter —
# in those, raw ServingEngine construction or direct replica-engine
# submits bypass the router's tenant/SLO/canary accounting. The
# sanctioned points (and the single-engine surfaces of modules that also
# drive the fleet — evaluate/demo/export-style uses) are allowlisted.
FLEET_FILE_MARKERS = ("fleet", "router")
FLEET_ENGINE_ALLOW = {
    # THE sanctioned replica construction + dispatch scopes
    "real_time_helmet_detection_tpu/serving/fleet.py::"
    "FleetRouter._spawn",
    "real_time_helmet_detection_tpu/serving/fleet.py::"
    "FleetRouter._dispatch",
    # serve_bench: the replica factory + the single-engine bench paths
    "scripts/serve_bench.py::make_replica_factory",
    "scripts/serve_bench.py::make_replica_factory.factory",
    "scripts/serve_bench.py::run_bench",
    "scripts/serve_bench.py::selfcheck",
}
RAW_WRITE_ALLOW = {
    # the atomic-write implementation itself
    "real_time_helmet_detection_tpu/utils.py",
}
# request-path span names that are NOT per-request (ISSUE 14): module
# scope / process lifecycle — construction-time compiles, state-machine
# transitions, whole-replica arcs, rollout control flow. Everything else
# under the serve:/fleet:/recover: prefixes belongs to ONE request (or a
# batch of them) and must carry ctx= or links=.
TRACE_LIFECYCLE_SPANS = {
    "serve:lower", "serve:compile", "serve:state", "serve:killed", "serve:degrade",
    "recover:reload",
    "fleet:rollout", "fleet:promote", "fleet:rollback",
    "fleet:replica-death", "fleet:respawn", "fleet:reload-timeout",
    "fleet:tenant-shed",
}
_TRACED_SPAN_PREFIXES = ("serve:", "fleet:", "recover:")
_TRACER_EMIT_FNS = {"span", "record", "event"}
RAW_SPAN_ALLOW = {
    # the sanctioned timing harness (bench.py module docstring): its
    # wall-clock arithmetic IS the documented methodology — scan inside
    # one program, scalar fetch, subtract measured dispatch overhead
    "bench.py::measure_dispatch_overhead",
    "bench.py::timed_fetch",
    "bench.py::chain_timed_fetch",
    "bench.py::chained_scan_step_samples",
}
METRIC_AGG_ALLOW = {
    # the documented dispatch-overhead probe: median-of-7 trivial
    # dispatches IS the methodology (bench.py module docstring) and its
    # output is an input to the metrics plane, not a latency digest
    "bench.py::measure_dispatch_overhead",
}

_REF_PATTERNS = (
    re.compile(r"\bref\s+\S+:\d"),             # "ref train.py:86"
    re.compile(r"/root/reference/\S+\.\w+"),   # "/root/reference/data.py"
    re.compile(r"reference\s+has\s+no", re.I),
    re.compile(r"no\s+reference\s+analogue", re.I),
)

_SUPPRESS_RE = re.compile(r"#.*graftlint:\s*off=([\w,/-]+)")

_TIMING_FNS = {"time", "perf_counter", "monotonic"}
_FETCH_ATTRS = {"device_get", "block_until_ready"}


def _suppressed(rule: str, lines: Sequence[str], lo: int, hi: int) -> bool:
    """Is `rule` switched off by a `# graftlint: off=` marker in
    source lines [lo, hi] (1-based, inclusive)?"""
    short = rule.split("/", 1)[-1]
    for ln in lines[max(0, lo - 1):hi]:
        m = _SUPPRESS_RE.search(ln)
        if m and short in m.group(1).split(","):
            return True
    return False


def _node_span(node: ast.AST) -> Tuple[int, int]:
    lo = getattr(node, "lineno", 1)
    hi = getattr(node, "end_lineno", lo)
    return lo, hi


def _call_name(node: ast.Call) -> str:
    """Dotted name of a call target, best effort ("time.perf_counter",
    "jax.device_get", "open", ...)."""
    parts: List[str] = []
    t = node.func
    while isinstance(t, ast.Attribute):
        parts.append(t.attr)
        t = t.value
    if isinstance(t, ast.Name):
        parts.append(t.id)
    return ".".join(reversed(parts))


def _iter_scopes(tree: ast.Module) -> Iterable[Tuple[str, ast.AST,
                                                     List[ast.stmt]]]:
    """(qualname, node, body) for the module and every (nested) function/
    class scope. Each function's body EXCLUDES nested function bodies, so
    a pattern split across an outer function and its closure does not
    double-report."""
    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qual = (prefix + "." + child.name) if prefix else child.name
                yield qual, child, child.body
                yield from walk(child, qual)
            else:
                yield from walk(child, prefix)

    yield "module", tree, tree.body
    yield from walk(tree, "")


def _scope_calls(body: List[ast.stmt]) -> Iterable[ast.Call]:
    """Every Call in a scope body, NOT descending into nested defs."""
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# the rules


def rule_per_call_timing(tree, lines, relpath) -> List[Finding]:
    out = []
    for qual, node, body in _iter_scopes(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if "%s::%s" % (relpath, qual) in TIMING_ALLOW \
                or "%s::%s" % (os.path.basename(relpath), qual) \
                in TIMING_ALLOW:
            continue
        timing_line = fetch_line = 0
        for call in _scope_calls(body):
            name = _call_name(call)
            if name.startswith("time.") and name.split(".")[-1] \
                    in _TIMING_FNS:
                timing_line = timing_line or call.lineno
            if name.split(".")[-1] in _FETCH_ATTRS:
                fetch_line = fetch_line or call.lineno
        if timing_line and fetch_line:
            lo, hi = _node_span(node)
            if _suppressed("per-call-timing", lines, lo, hi):
                continue
            out.append(Finding(
                rule="ast/per-call-timing", path=relpath,
                line=min(timing_line, fetch_line), context=qual,
                message="wall-clock timing and a device fetch in one "
                        "function: one dispatch's wall is mostly dispatch "
                        "overhead for a small program — use "
                        "bench.timed_fetch / a scanned program (ROADMAP "
                        "S1 decides this rule's fate)"))
    return out


def rule_queue_bypass(tree, lines, relpath) -> List[Finding]:
    if not (relpath in QUEUE_RULE_FILES
            or any(relpath.startswith(p) for p in QUEUE_RULE_PREFIXES)):
        return []
    acquire_line = 0
    supervised = False
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name.endswith("acquire_backend") or name == "jax.devices":
                acquire_line = acquire_line or node.lineno
        if isinstance(node, ast.Name) and node.id in ("run_as_job",
                                                      "maybe_job_heartbeat"):
            supervised = True
        if isinstance(node, ast.Attribute) and node.attr in (
                "run_as_job", "maybe_job_heartbeat"):
            supervised = True
    if acquire_line and not supervised:
        if _suppressed("queue-bypass", lines, 1, len(lines)):
            return []
        return [Finding(
            rule="ast/queue-bypass", path=relpath, line=acquire_line,
            context="module",
            message="script acquires a backend but never touches the job "
                    "supervision contract (run_as_job / "
                    "maybe_job_heartbeat): chip jobs go through "
                    "scripts/tpu_queue.py, which needs the heartbeat to "
                    "distinguish slow from hung")]
    return []


def rule_raw_artifact_write(tree, lines, relpath) -> List[Finding]:
    if relpath in RAW_WRITE_ALLOW:
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        if isinstance(node, ast.ClassDef):
            continue
        for call in _scope_calls(body):
            if _call_name(call) != "open":
                continue
            mode = None
            if len(call.args) >= 2 and isinstance(call.args[1],
                                                  ast.Constant):
                mode = call.args[1].value
            for kw in call.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = kw.value.value
            if not (isinstance(mode, str) and "w" in mode):
                continue
            if _suppressed("raw-artifact-write", lines, call.lineno,
                           getattr(call, "end_lineno", call.lineno)):
                continue
            out.append(Finding(
                rule="ast/raw-artifact-write", path=relpath,
                line=call.lineno, context=qual,
                message="raw open(..., %r) write: a kill mid-write leaves "
                        "a truncated file where a complete one stood — "
                        "use utils.save_json / atomic_write_bytes "
                        "(tmp + os.replace)" % mode))
    return out


def rule_device_get_in_loop(tree, lines, relpath) -> List[Finding]:
    if relpath in DEVICE_GET_LOOP_ALLOW:
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        stack: List[ast.AST] = list(body)
        loops: List[ast.AST] = []
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(n, (ast.For, ast.While)):
                loops.append(n)
            stack.extend(ast.iter_child_nodes(n))
        for loop in loops:
            for call in _scope_calls(loop.body):
                if _call_name(call).split(".")[-1] != "device_get":
                    continue
                if _suppressed("device-get-in-loop", lines, call.lineno,
                               getattr(call, "end_lineno", call.lineno)):
                    continue
                out.append(Finding(
                    rule="ast/device-get-in-loop", path=relpath,
                    line=call.lineno, context=qual,
                    message="jax.device_get inside a loop forces a "
                            "host<->device sync every iteration — batch "
                            "the fetch "
                            "(deferred flush) or pipeline it"))
    return out


def rule_missing_ref_citation(tree, lines, relpath) -> List[Finding]:
    if os.path.basename(relpath) == "__init__.py":
        return []  # namespace modules: the citation lives in the members
    doc = ast.get_docstring(tree) or ""
    if any(p.search(doc) for p in _REF_PATTERNS):
        return []
    if _suppressed("missing-ref-citation", lines, 1,
                   min(len(lines), 3)):
        return []
    return [Finding(
        rule="ast/missing-ref-citation", path=relpath, line=1,
        context="module",
        message="public module docstring has no reference citation: add "
                "`ref <file:line>` (into /root/reference) or state the "
                "reference has no analogue (CLAUDE.md convention)")]


def _acquires_backend(tree: ast.Module) -> bool:
    """Does this module take the device claim (the queue-bypass rule's
    definition of a chip-path script)?"""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            if name.endswith("acquire_backend") or name == "jax.devices":
                return True
    return False


def rule_raw_span_timing(tree, lines, relpath) -> List[Finding]:
    """`time.X() - <start>` span arithmetic in a chip-path script: route
    it through obs.spans.SpanTracer (ISSUE 6 satellite). Scope mirrors
    queue-bypass — scripts/ + the root chip scripts — narrowed to modules
    that actually acquire a backend; the flight recorder is about chip
    evidence, not generic CLI stopwatches."""
    if not (relpath in QUEUE_RULE_FILES
            or any(relpath.startswith(p) for p in QUEUE_RULE_PREFIXES)):
        return []
    if not _acquires_backend(tree):
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        if "%s::%s" % (relpath, qual) in RAW_SPAN_ALLOW \
                or "%s::%s" % (os.path.basename(relpath), qual) \
                in RAW_SPAN_ALLOW:
            continue
        stack: List[ast.AST] = list(body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(n))
            if not (isinstance(n, ast.BinOp) and isinstance(n.op, ast.Sub)):
                continue
            left = n.left
            if not isinstance(left, ast.Call):
                continue
            name = _call_name(left)
            if not (name.startswith("time.")
                    and name.split(".")[-1] in _TIMING_FNS):
                continue
            if _suppressed("raw-span-timing", lines, n.lineno,
                           getattr(n, "end_lineno", n.lineno)):
                continue
            out.append(Finding(
                rule="ast/raw-span-timing", path=relpath, line=n.lineno,
                context=qual,
                message="hand-rolled span timing (time.%s() - start) in a "
                        "chip-path script is invisible to the flight "
                        "recorder — use obs.spans.SpanTracer.span(...) "
                        "(sp.dur_s carries the value; the record lands in "
                        "the round's span log)" % name.split(".")[-1]))
    return out


def rule_device_get_in_serving_loop(tree, lines, relpath) -> List[Finding]:
    """Per-request fetches in serving hot loops (ISSUE 8 satellite). Scope
    is the serving package; the engine's single batched fetch point is
    allowlisted (SERVING_FETCH_ALLOW) — anything else that fetches inside
    a loop is syncing per request and defeats the pipeline."""
    if not relpath.startswith(SERVING_PREFIX):
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        if "%s::%s" % (relpath, qual) in SERVING_FETCH_ALLOW:
            continue
        stack: List[ast.AST] = list(body)
        loops: List[ast.AST] = []
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(n, (ast.For, ast.While)):
                loops.append(n)
            stack.extend(ast.iter_child_nodes(n))
        for loop in loops:
            for call in _scope_calls(loop.body):
                if _call_name(call).split(".")[-1] not in _FETCH_ATTRS:
                    continue
                if _suppressed("device-get-in-serving-loop", lines,
                               call.lineno,
                               getattr(call, "end_lineno", call.lineno)):
                    continue
                out.append(Finding(
                    rule="ast/device-get-in-serving-loop", path=relpath,
                    line=call.lineno, context=qual,
                    message="device fetch inside a serving loop outside "
                            "the engine's batched fetch point: a "
                            "per-request sync serializes the pipeline "
                            "— return "
                            "futures and let ServingEngine._fetch_loop's "
                            "per-batch D2H complete them"))
    return out


def rule_context_free_span(tree, lines, relpath) -> List[Finding]:
    """Trace-context hygiene in the serving package (ISSUE 14): a
    tracer span/record/event call whose name literal is a request-path
    span (`serve:*`/`fleet:*`/`recover:*`) must carry `ctx=` (its
    request's TraceContext) or `links=` (a batch's fan-in edges) —
    module-scope/process-lifecycle spans (TRACE_LIFECYCLE_SPANS) are
    exempt. Scope: serving/ modules, where every such record belongs to
    an acknowledged request whose causal chain the fleet acceptance
    gates reassemble."""
    if not relpath.startswith(SERVING_PREFIX):
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        for call in _scope_calls(body):
            name = _call_name(call)
            parts = name.split(".")
            if parts[-1] not in _TRACER_EMIT_FNS or len(parts) < 2 \
                    or "tracer" not in parts[-2].lower():
                continue
            first = call.args[0] if call.args else None
            if not (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)
                    and first.value.startswith(_TRACED_SPAN_PREFIXES)):
                continue
            if first.value in TRACE_LIFECYCLE_SPANS:
                continue
            if any(kw.arg in ("ctx", "links") for kw in call.keywords):
                continue
            if _suppressed("context-free-span", lines, call.lineno,
                           getattr(call, "end_lineno", call.lineno)):
                continue
            out.append(Finding(
                rule="ast/context-free-span", path=relpath,
                line=call.lineno, context=qual,
                message="request-path span %r emitted without a trace "
                        "context (ctx=) or fan-in links (links=): the "
                        "record is invisible to the waterfall assembler "
                        "and the request's causal chain silently loses "
                        "this stage — thread the request's TraceContext "
                        "through (obs/trace.py), or add the name to "
                        "TRACE_LIFECYCLE_SPANS if it is genuinely "
                        "process-lifecycle" % first.value))
    return out


def _references_name(tree: ast.Module, name: str) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == name:
            return True
        if isinstance(node, ast.Attribute) and node.attr == name:
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)) \
                and any(a.name == name for a in node.names):
            return True
    return False


def _references_fleet_router(tree: ast.Module) -> bool:
    return _references_name(tree, "FleetRouter")


def rule_engine_bypass_in_fleet(tree, lines, relpath) -> List[Finding]:
    """Raw ServingEngine use inside fleet/router code paths (ISSUE 12
    satellite): constructing an engine directly, or submitting to a
    replica's engine (`<x>.engine.submit/predict_many`), skips
    FleetRouter dispatch — per-tenant budgets, SLO penalty boxes, canary
    traffic splits and the re-dispatch ack guarantee all silently stop
    applying to that traffic. Scope: serving/ modules named like fleet
    code, plus any module referencing FleetRouter; the sanctioned
    construction/dispatch scopes and single-engine surfaces are
    allowlisted (FLEET_ENGINE_ALLOW)."""
    base = os.path.basename(relpath)
    fleet_file = relpath.startswith(SERVING_PREFIX) \
        and any(m in base for m in FLEET_FILE_MARKERS)
    if not fleet_file and not _references_fleet_router(tree):
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        if "%s::%s" % (relpath, qual) in FLEET_ENGINE_ALLOW:
            continue
        for call in _scope_calls(body):
            name = _call_name(call)
            parts = name.split(".")
            hit = None
            if parts[-1] == "ServingEngine":
                hit = "raw ServingEngine construction"
            elif len(parts) >= 2 and parts[-2] == "engine" \
                    and parts[-1] in ("submit", "predict_many"):
                hit = "direct replica-engine %s()" % parts[-1]
            if hit is None:
                continue
            if _suppressed("engine-bypass-in-fleet", lines, call.lineno,
                           getattr(call, "end_lineno", call.lineno)):
                continue
            out.append(Finding(
                rule="ast/engine-bypass-in-fleet", path=relpath,
                line=call.lineno, context=qual,
                message="%s in a fleet/router code path bypasses "
                        "FleetRouter dispatch — tenant budgets, SLO "
                        "penalty boxes, the canary split and the "
                        "re-dispatch ack guarantee stop applying; go "
                        "through router.submit (or the allowlisted "
                        "factory/dispatch scopes)" % hit))
    return out


_THRESHOLD_KWARGS = {"threshold", "cascade_threshold", "stream_threshold",
                     "skip_threshold"}
_THRESHOLD_REFS = ("FleetRouter", "StreamSession")
_THRESHOLD_FILES = {"scripts/serve_bench.py"}


def _numeric_literal(node) -> bool:
    """A bare numeric constant (possibly signed) — the hand-picked shape.
    None, names, attribute reads and computed expressions all pass: the
    sanctioned flows (cfg fields, calibrated-artifact lookups, values
    derived from the data in hand) are never literals."""
    if isinstance(node, ast.UnaryOp) \
            and isinstance(node.op, (ast.USub, ast.UAdd)):
        node = node.operand
    return isinstance(node, ast.Constant) \
        and isinstance(node.value, (int, float)) \
        and not isinstance(node.value, bool)


def rule_hand_picked_threshold(tree, lines, relpath) -> List[Finding]:
    """A numeric-literal confidence/skip threshold reaching the serving
    plane (ISSUE 19 satellite): the cascade escalation threshold and the
    stream tile-skip threshold are CALIBRATED ARTIFACTS
    (`quality_matrix --cascade/--streams` -> `config.cascade_overrides()`
    / `stream_overrides()`), never constants — a hand-picked value either
    over-escalates (goodput collapses to all-quality) or under-escalates
    (blended mAP silently decays), and nothing re-checks it when the
    model or data drifts. Scope: serving/ modules, scripts/serve_bench.py,
    and any module referencing FleetRouter/StreamSession. Two signatures:
    (a) a threshold-named kwarg bound to a numeric literal at any call
    site, (b) an argparse `--*threshold` option with a numeric default
    (None + explicit resolution is the sanctioned CLI shape)."""
    in_scope = relpath.startswith(SERVING_PREFIX) \
        or relpath in _THRESHOLD_FILES \
        or any(_references_name(tree, n) for n in _THRESHOLD_REFS)
    if not in_scope:
        return []
    out = []
    for qual, node, body in _iter_scopes(tree):
        for call in _scope_calls(body):
            leaf = _call_name(call).split(".")[-1]
            hits = []
            if leaf == "add_argument":
                opt = next((a.value for a in call.args
                            if isinstance(a, ast.Constant)
                            and isinstance(a.value, str)
                            and "threshold" in a.value), None)
                if opt is not None:
                    hits += ["argparse option %s with a numeric default"
                             % opt
                             for kw in call.keywords
                             if kw.arg == "default"
                             and _numeric_literal(kw.value)]
            else:
                hits += ["%s=<literal> at a call site" % kw.arg
                         for kw in call.keywords
                         if kw.arg in _THRESHOLD_KWARGS
                         and _numeric_literal(kw.value)]
            for desc in hits:
                if _suppressed("hand-picked-threshold", lines,
                               call.lineno,
                               getattr(call, "end_lineno", call.lineno)):
                    continue
                out.append(Finding(
                    rule="ast/hand-picked-threshold", path=relpath,
                    line=call.lineno, context=qual,
                    message="hand-picked threshold (%s): confidence/skip "
                            "thresholds are calibrated artifacts — "
                            "resolve via config.cascade_overrides()/"
                            "stream_overrides() (or derive from the data "
                            "in hand), never a constant" % desc))
    return out


_STAT_FNS = {"percentile", "quantile", "quantiles", "median"}


def rule_raw_metric_aggregation(tree, lines, relpath) -> List[Finding]:
    """Hand-rolled percentile/median arithmetic in a chip-path script
    (ISSUE 10 satellite): scope mirrors raw-span-timing — scripts/ + the
    root chip scripts, narrowed to modules that acquire a backend. Two
    signatures: (a) a call whose leaf name is a statistics function
    (np.percentile/median/statistics.quantiles/...), (b) the
    nearest-rank idiom — `round(q * (len(s) - 1))`-style rank
    arithmetic, or indexing directly into a `sorted(...)` call with a
    computed index. Both should be `obs.metrics.Histogram` digests."""
    if not (relpath in QUEUE_RULE_FILES
            or any(relpath.startswith(p) for p in QUEUE_RULE_PREFIXES)):
        return []
    if not _acquires_backend(tree):
        return []

    def contains_len_call(node) -> bool:
        return any(isinstance(n, ast.Call) and _call_name(n) == "len"
                   for n in ast.walk(node))

    out = []
    for qual, node, body in _iter_scopes(tree):
        if "%s::%s" % (relpath, qual) in METRIC_AGG_ALLOW \
                or "%s::%s" % (os.path.basename(relpath), qual) \
                in METRIC_AGG_ALLOW:
            continue
        for call in _scope_calls(body):
            name = _call_name(call)
            leaf = name.split(".")[-1]
            root_mod = name.split(".")[0]
            hit = None
            # stat-library calls only (np.percentile, statistics.median,
            # a bare percentile): `Histogram.quantile()` IS the sanctioned
            # digest and must not flag itself
            if leaf in _STAT_FNS and (name == leaf or root_mod in
                                      ("np", "numpy", "statistics",
                                       "scipy")):
                hit = "%s()" % name
            elif leaf == "round" and any(
                    isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult)
                    and contains_len_call(n)
                    for a in call.args for n in ast.walk(a)):
                hit = "rank arithmetic (round(q * (len(..) - 1)))"
            if hit is None:
                continue
            if _suppressed("raw-metric-aggregation", lines, call.lineno,
                           getattr(call, "end_lineno", call.lineno)):
                continue
            out.append(Finding(
                rule="ast/raw-metric-aggregation", path=relpath,
                line=call.lineno, context=qual,
                message="hand-rolled metric aggregation (%s) in a "
                        "chip-path script: ad-hoc percentiles neither "
                        "merge nor export — observe into an obs.metrics "
                        "Histogram and read quantile()/digest() (the SLO "
                        "watchdog and perfgate consume those snapshots)"
                        % hit))
        # the sorted-then-index idiom outside calls (s = sorted(v);
        # s[int(...)] on the sorted() call directly)
        stack: List[ast.AST] = list(body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(n))
            if isinstance(n, ast.Subscript) \
                    and isinstance(n.value, ast.Call) \
                    and _call_name(n.value) == "sorted" \
                    and not isinstance(n.slice, ast.Constant) \
                    and not (isinstance(n.slice, ast.UnaryOp)
                             and isinstance(n.slice.operand, ast.Constant)):
                if "%s::%s" % (relpath, qual) in METRIC_AGG_ALLOW:
                    continue
                if _suppressed("raw-metric-aggregation", lines, n.lineno,
                               getattr(n, "end_lineno", n.lineno)):
                    continue
                out.append(Finding(
                    rule="ast/raw-metric-aggregation", path=relpath,
                    line=n.lineno, context=qual,
                    message="computed index into sorted(...) (the "
                            "nearest-rank percentile idiom) in a "
                            "chip-path script — observe into an "
                            "obs.metrics Histogram instead"))
    return out


# multi-process rendezvous markers + the sanctioned barrier helpers
_MULTIPROC_INIT = ("init_process_group", "init_distributed")
_BARRIER_NAMES = {"barrier_synced_compile", "coordination_barrier",
                  "wait_at_barrier"}


def rule_unbarriered_collective_start(tree, lines, relpath) -> List[Finding]:
    """Compile-without-barrier in a multi-process entry point (ISSUE 11
    satellite): the CLAUDE.md Gloo pitfall as a mechanical check. Scope is
    any production module that initializes a process group; the finding
    lands on the first `.compile()` call when no barrier helper is
    referenced anywhere in the module."""
    init_line = 0
    barriered = False
    compile_line = 0
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)
            leaf = name.split(".")[-1]
            if name.endswith("distributed.initialize") \
                    or leaf in _MULTIPROC_INIT:
                init_line = init_line or node.lineno
            # the AOT idiom specifically — `<jitted>.lower(...).compile()`
            # — so `re.compile(...)` and friends never match
            if leaf == "compile" and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Call) \
                    and _call_name(node.func.value).split(".")[-1] \
                    == "lower":
                compile_line = compile_line or node.lineno
        if isinstance(node, ast.Name) and node.id in _BARRIER_NAMES:
            barriered = True
        if isinstance(node, ast.Attribute) and node.attr in _BARRIER_NAMES:
            barriered = True
    if not (init_line and compile_line) or barriered:
        return []
    if _suppressed("unbarriered-collective-start", lines, compile_line,
                   compile_line):
        return []
    return [Finding(
        rule="ast/unbarriered-collective-start", path=relpath,
        line=compile_line, context="module",
        message="multi-process entry point AOT-compiles without the "
                "barrier law: the compiled program's fresh Gloo context "
                "has a hard 30 s first-execution KeyValue deadline and "
                "skewed per-rank compiles trip it — use "
                "parallel.barrier_synced_compile (compile -> "
                "coordination barrier -> execute)")]


def _subtree_nodes(root) -> Iterable[ast.AST]:
    """Every node under `root` (inclusive), NOT descending into nested
    function/class defs — loop analysis must not be confused by a
    closure's control flow."""
    stack: List[ast.AST] = [root]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                continue
            stack.append(child)


def rule_unbounded_retry(tree, lines, relpath) -> List[Finding]:
    """`while True` + an except handler that swallows and loops again +
    no cap, no backoff, no queue-consume (ISSUE 9 satellite — the r2
    probe-kill mistake class; see the module docstring)."""
    out = []
    for qual, node, body in _iter_scopes(tree):
        loops = [n for stmt in body for n in _subtree_nodes(stmt)
                 if isinstance(n, ast.While)]
        for loop in loops:
            test = loop.test
            if not (isinstance(test, ast.Constant) and test.value is True):
                continue
            nodes = [n for stmt in loop.body for n in _subtree_nodes(stmt)]
            # a backoff or a blocking queue-consume anywhere in the loop
            # legitimizes it (bounded-in-time, or a consumer loop)
            slept = consumes = False
            for n in nodes:
                if isinstance(n, ast.Call):
                    name = _call_name(n)
                    leaf = name.split(".")[-1]
                    if leaf == "sleep" or "backoff" in leaf:
                        slept = True
                    if leaf == "get" and "." in name:
                        consumes = True
            if slept or consumes:
                continue
            for n in nodes:
                if not isinstance(n, ast.ExceptHandler):
                    continue
                handler_nodes = [m for stmt in n.body
                                 for m in _subtree_nodes(stmt)]
                if any(isinstance(m, (ast.Raise, ast.Return, ast.Break))
                       for m in handler_nodes):
                    continue  # the handler exits the loop: bounded
                if _suppressed("unbounded-retry", lines, n.lineno,
                               getattr(n, "end_lineno", n.lineno)):
                    continue
                out.append(Finding(
                    rule="ast/unbounded-retry", path=relpath,
                    line=n.lineno, context=qual,
                    message="while-True retry loop swallows the exception "
                            "and loops again with no attempt cap and no "
                            "backoff (the r2 probe-kill mistake class) — "
                            "bound it (for attempt in range(N)) and/or "
                            "back off (time.sleep) before re-attempting"))
                break  # one finding per loop
    return out


RULES = (rule_per_call_timing, rule_queue_bypass,
         rule_raw_artifact_write, rule_device_get_in_loop,
         rule_missing_ref_citation, rule_raw_span_timing,
         rule_device_get_in_serving_loop, rule_unbounded_retry,
         rule_raw_metric_aggregation, rule_unbarriered_collective_start,
         rule_engine_bypass_in_fleet, rule_context_free_span,
         rule_hand_picked_threshold)


# ---------------------------------------------------------------------------
# drivers


def lint_source(src: str, relpath: str,
                rules=RULES) -> List[Finding]:
    """Run `rules` over one file's source. Unparseable source is itself a
    finding (a syntax error in prod code must not pass silently)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(rule="ast/syntax-error", path=relpath,
                        line=e.lineno or 0, context="module",
                        message="unparseable: %s" % e.msg)]
    lines = src.splitlines()
    out: List[Finding] = []
    for rule in rules:
        out.extend(rule(tree, lines, relpath))
    return out


def repo_files(root: str) -> List[str]:
    """Repo-relative production .py files in lint scope (tests, committed
    artifacts, build outputs excluded — their conventions differ)."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        rel = os.path.relpath(dirpath, root)
        parts = [] if rel == "." else rel.split(os.sep)
        if parts and (parts[0] in EXCLUDE_DIRS
                      or any(p in EXCLUDE_DIRS for p in parts)):
            dirnames[:] = []
            continue
        dirnames[:] = [d for d in dirnames if d not in EXCLUDE_DIRS]
        for f in sorted(filenames):
            if f.endswith(".py"):
                p = os.path.normpath(os.path.join(rel, f)) if parts else f
                out.append(p.replace(os.sep, "/"))
    return sorted(out)


def lint_repo(root: str, rules=RULES) -> List[Finding]:
    out: List[Finding] = []
    for rel in repo_files(root):
        with open(os.path.join(root, rel)) as f:
            src = f.read()
        out.extend(lint_source(src, rel, rules))
    return out
