"""Transfer-budget audit (graftlint layer 4) — the committed D2H/H2D
manifest for every jitted surface.

Host<->device transfers and syncs are what the hot paths ration,
and every subsystem since the flight recorder ships under a "zero extra
D2H / rides the same fetch" law: the telemetry ring, the sentinel
scalars, `confidence_summary`, `tile_delta_summary` all return NEXT TO an
already-fetched leaf. The reference's eval loop is the anti-pattern this
layer exists to keep out: it fetches eagerly per batch item
(ref /root/reference/evaluate.py:66-97), paying one host round trip per
element. Until this layer, each zero-extra-D2H law was enforced by its
own hand-written `device_get`-count test pin; a new output leaf or a
newly un-donated input that slipped past one pin would silently tax every
queued chip job. This module makes the whole device<->host interface a
single versioned contract instead:

* `measure_entry`   — enumerate one program's transfer surface from
                      `jax.eval_shape` + `jax.make_jaxpr` alone (ZERO
                      device execution): fetched output leaves (those
                      with no donated-input aval to alias — the same
                      greedy matching as `trace_audit.donation_mismatches`,
                      so "aliased into a donated buffer" never counts as
                      a fetch), input leaves split donated vs fresh-H2D,
                      and host-callback primitives.
* `ENTRY_POINTS`    — the registered jitted surfaces, tiny-shape CPU
                      editions (same builders/grid as trace_audit):
                      scanned train step across telemetry / sentinel /
                      bf16-param-policy / distill modes, jitted predict +
                      the donating bench chain, the cascade summary
                      predict, the stream delta summary + tile predict,
                      every serve bucket, and the calibration step.
* `gate_manifest`   — ratchet gate against the committed
                      `transfer_manifest.json` (schema
                      `transfer-manifest-v1`): leaf counts exact (any
                      growth fails), bytes within 2% like perfgate's byte
                      class. Deltas surface as `xfer/*` findings through
                      the ordinary baseline diff (the baseline stays
                      EMPTY); improvements print loudly and are adopted
                      deliberately via `graftlint --write-manifest`.
* `counting_device_get` — the runtime twin: a context manager counting
                      actual `jax.device_get` calls, backing the shared
                      `count_device_get` test fixture (one implementation
                      behind every per-subsystem fetch-count pin).

Leaf counts are shape-independent for the production programs (the whole
TrainState aliases into the donated input, so the fetched surface is the
loss scalar + mode ring regardless of arch), which is what lets bench.py
check its in-hand timed program against the tiny-shape manifest entry
(`bench_transfer_ok`) without any device work.
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from . import Finding

SCHEMA = "transfer-manifest-v1"
BYTES_TOL = 0.02  # perfgate's byte class: 2% — counts are exact instead

MANIFEST_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "transfer_manifest.json")
# repo-relative manifest path: the `path` of every xfer finding, so
# baseline keys and --format github annotations anchor to a real file
MANIFEST_RELPATH = "real_time_helmet_detection_tpu/analysis/" \
                   "transfer_manifest.json"


# ---------------------------------------------------------------------------
# measurement — eval_shape/make_jaxpr only, zero device execution


def _leaf_key(leaf) -> Tuple[Tuple[int, ...], str]:
    return (tuple(leaf.shape), str(leaf.dtype))


def _leaf_bytes(leaf) -> int:
    import numpy as np
    n = 1
    for d in leaf.shape:
        n *= int(d)
    return n * np.dtype(leaf.dtype).itemsize


def _spec(leaf) -> str:
    return "%s%s" % (leaf.dtype, list(leaf.shape))


def _side(leaves) -> Dict:
    return {"leaves": len(leaves),
            "bytes": int(sum(_leaf_bytes(l) for l in leaves))}


def measure_entry(fn: Callable, args: Sequence,
                  donate_argnums: Sequence[int] = ()) -> Dict:
    """One program's device<->host surface, from abstract evaluation only.

    Fetched D2H leaves are the output leaves left over AFTER the donated
    input leaves greedily claim their same-(shape, dtype) aliasing
    targets — the exact aval matching XLA's donation uses
    (`trace_audit.donation_mismatches`), so a scanned train step whose
    full TrainState round-trips through a donated buffer measures ONE
    fetched leaf (the loss scalar), not ten thousand.
    """
    import jax

    out_leaves = jax.tree.leaves(jax.eval_shape(fn, *args))
    donated, fresh = [], []
    dset = set(int(i) for i in donate_argnums)
    for i, a in enumerate(args):
        leaves = jax.tree.leaves(jax.eval_shape(lambda x: x, a))
        (donated if i in dset else fresh).extend(leaves)

    pool: Dict[Tuple, List[int]] = {}
    for idx, leaf in enumerate(out_leaves):
        pool.setdefault(_leaf_key(leaf), []).append(idx)
    aliased: Set[int] = set()
    for leaf in donated:
        hit = pool.get(_leaf_key(leaf))
        if hit:
            aliased.add(hit.pop())
    fetched = [l for i, l in enumerate(out_leaves) if i not in aliased]

    from .trace_audit import _CALLBACK_PRIMS, _walk_jaxprs
    closed = jax.make_jaxpr(fn)(*args)
    callbacks = 0
    for j in _walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if any(tok in eqn.primitive.name for tok in _CALLBACK_PRIMS):
                callbacks += 1

    d2h = _side(fetched)
    d2h["shapes"] = sorted(_spec(l) for l in fetched)
    return {"d2h": d2h, "h2d_fresh": _side(fresh), "donated": _side(donated),
            "host_callbacks": callbacks}


# ---------------------------------------------------------------------------
# the registered entry points (tiny-shape CPU editions)


def _train_parts(telemetry: bool = False, sentinel: bool = False,
                 param_policy: str = "fp32", distill: bool = False,
                 block_fuse: str = "auto", fwd_dtype: str = "bf16"):
    """The scanned-train-step family at trace_audit's tiny config: the
    exact programs bench.py/scaling.py time, across the mode knobs that
    reshape the fetched surface (telemetry ring, sentinel skip counter,
    fp32-master state restructure, in-jit distill teacher) — plus the
    ISSUE-20 modes (block-fused residual tail, int8 STE forward), which
    must keep the base step's budget EXACTLY: the fused pass and the
    per-step scale refresh are both in-jit by construction."""
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..data import synthetic_target_batch
    from ..models import build_model
    from ..optim import build_optimizer
    from ..train import (Distiller, create_train_state, init_variables,
                         make_scanned_train_fn, make_train_step_body)
    from .trace_audit import _BATCH, _TINY

    cfg = Config(batch_size=_BATCH, remat="none", loss_kernel="xla",
                 amp=param_policy == "bf16-compute",
                 param_policy=param_policy, telemetry=telemetry,
                 sentinel=sentinel, block_fuse=block_fuse,
                 fwd_dtype=fwd_dtype, **_TINY)
    model = build_model(cfg, dtype=jnp.bfloat16 if cfg.amp else None)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0),
                               _TINY["imsize"], tx)
    dist = None
    if distill:
        # an in-memory teacher (same tiny arch): the teacher variables
        # are closed-over trace constants, so the measured signature is
        # the production --distill program's
        tparams, tstats = init_variables(model, jax.random.key(1),
                                         _TINY["imsize"])
        dist = Distiller(model, tparams, tstats, cfg.distill_alpha,
                         cfg.num_cls, cfg.normalized_coord)
    body = make_train_step_body(model, tx, cfg, distill=dist)
    train_n = make_scanned_train_fn(body, 2, telemetry=telemetry,
                                    sentinel=sentinel)
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        _BATCH, _TINY["imsize"], pos_rate=0.05))
    return train_n, (state,) + arrs, (0,)


def _predict_parts(cascade: bool = False):
    from .trace_audit import _tiny_predict_parts
    arch = None
    if cascade:
        from .trace_audit import TIER_AUDIT
        arch = dict(TIER_AUDIT[0][1])  # the edge tier: the cascade's
    predict, variables, images = _tiny_predict_parts(
        arch=arch, cascade_summary=cascade)
    return (lambda v, im: predict(v, im)), (variables, images), ()


def _chain_parts():
    from .trace_audit import _predict_chain, _tiny_predict_parts
    predict, variables, images = _tiny_predict_parts()
    return _predict_chain(predict), (variables, images), (1,)


def _serve_parts(bucket: int):
    from .trace_audit import _tiny_serve_parts
    predict, variables, images = _tiny_serve_parts(bucket)
    return (lambda v, im: predict(v, im)), (variables, images), ()


def _delta_parts(grid: int = 2):
    import numpy as np

    from ..ops.delta import tile_delta_summary
    from .trace_audit import _TINY
    frame = np.zeros((grid * _TINY["imsize"], grid * _TINY["imsize"], 3),
                     np.uint8)
    return (lambda p, c: tile_delta_summary(p, c, grid=grid)), \
        (frame, frame), ()


def _calib_parts():
    """The max-combine calibration step (`ops/quant.make_calib_step`) —
    the program every post-first batch of `calibrate_scales` dispatches;
    its whole output (the per-layer scalar pytree) IS the pass's single
    D2H."""
    import jax
    import numpy as np

    from ..config import Config
    from ..ops.quant import make_calib_step
    from ..train import init_variables
    from ..models import build_model
    from .trace_audit import _BATCH, _TINY

    cfg = Config(topk=16, conf_th=0.0, nms_th=0.5, infer_dtype="int8",
                 **_TINY)
    model = build_model(cfg)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         _TINY["imsize"])
    step = make_calib_step(cfg)
    images = np.zeros((_BATCH, _TINY["imsize"], _TINY["imsize"], 3),
                      np.float32)
    agg = jax.eval_shape(lambda p, b, i: step(p, b, i, None),
                         params, batch_stats, images)
    return (lambda p, b, i, a: step(p, b, i, a)), \
        (params, batch_stats, images, agg), ()


_RT = "real_time_helmet_detection_tpu/"
_TRAIN_MODS = (_RT + "train.py", _RT + "models/", _RT + "optim.py",
               _RT + "ops/")
_PREDICT_MODS = (_RT + "predict.py", _RT + "models/", _RT + "ops/")
_SERVE_MODS = _PREDICT_MODS + (_RT + "serving/engine.py",)

# name -> (builder() -> (fn, args, donate_argnums), owning module prefixes
# for `graftlint --changed`). Every registered trace-audit surface whose
# fetch budget a subsystem claims ("rides the same fetch") is pinned here.
ENTRY_POINTS: Dict[str, Tuple[Callable, Tuple[str, ...]]] = {
    "train_step_scanned": (lambda: _train_parts(), _TRAIN_MODS),
    "train_step_scanned[telemetry]": (
        lambda: _train_parts(telemetry=True),
        _TRAIN_MODS + (_RT + "obs/telemetry.py",)),
    "train_step_scanned[sentinel]": (
        lambda: _train_parts(sentinel=True), _TRAIN_MODS),
    "train_step_scanned[param=bf16-compute]": (
        lambda: _train_parts(param_policy="bf16-compute"), _TRAIN_MODS),
    "train_step_scanned[distill]": (
        lambda: _train_parts(distill=True), _TRAIN_MODS),
    "train_step_scanned[block-fuse]": (
        lambda: _train_parts(block_fuse="fused"), _TRAIN_MODS),
    "train_step_scanned[fwd=int8]": (
        lambda: _train_parts(fwd_dtype="int8"), _TRAIN_MODS),
    "predict": (lambda: _predict_parts(), _PREDICT_MODS),
    "predict_chain": (_chain_parts, _PREDICT_MODS),
    "predict_cascade_summary[tier=edge]": (
        lambda: _predict_parts(cascade=True),
        _PREDICT_MODS + (_RT + "ops/decode.py", _RT + "serving/fleet.py")),
    "stream_delta_summary[grid=2]": (
        lambda: _delta_parts(2),
        (_RT + "ops/delta.py", _RT + "serving/streams.py")),
    "stream_tile_predict[b=2]": (
        lambda: _serve_parts(2),
        _SERVE_MODS + (_RT + "serving/streams.py",)),
    "serve_predict[b=1]": (lambda: _serve_parts(1), _SERVE_MODS),
    "serve_predict[b=2]": (lambda: _serve_parts(2), _SERVE_MODS),
    "serve_predict[b=4]": (lambda: _serve_parts(4), _SERVE_MODS),
    "calibrate_scales": (
        _calib_parts, (_RT + "ops/quant.py", _RT + "models/")),
}


def entries_for_changed(changed: Sequence[str]) -> Set[str]:
    """The entry points whose owning modules intersect a changed-file
    list — `graftlint --changed`'s cheap layer-4 subset."""
    out = set()
    for name, (_, mods) in ENTRY_POINTS.items():
        if any(path.startswith(mods) for path in changed):
            out.add(name)
    return out


def measure_repo_entry_points(
        only: Optional[Set[str]] = None) -> Dict[str, Dict]:
    """name -> measurement (or {"error": ...}: a builder that no longer
    constructs can't silently pass the gate)."""
    out: Dict[str, Dict] = {}
    for name, (builder, _) in ENTRY_POINTS.items():
        if only is not None and name not in only:
            continue
        try:
            fn, args, donate = builder()
            out[name] = measure_entry(fn, args, donate)
        except Exception as e:  # noqa: BLE001 — the failure is the finding
            out[name] = {"error": "%s: %s" % (
                type(e).__name__, (str(e).splitlines() or ["?"])[0][:200])}
    return out


# ---------------------------------------------------------------------------
# manifest — load / ratchet gate / write


def load_manifest(path: Optional[str] = None) -> Dict:
    """The committed manifest, or an empty one (nothing budgeted: every
    measured entry then fails as `xfer/unknown-entry` — a missing
    manifest never silently passes)."""
    path = path or MANIFEST_PATH
    if not os.path.exists(path):
        return {"schema": SCHEMA, "entries": {}}
    with open(path) as f:
        data = json.load(f)
    if data.get("schema") != SCHEMA:
        raise ValueError("%s is not a %s manifest (schema=%r)"
                         % (path, SCHEMA, data.get("schema")))
    return data


def _finding(rule: str, entry: str, message: str) -> Finding:
    return Finding(rule=rule, path=MANIFEST_RELPATH, context=entry,
                   message=message)


def gate_manifest(measured: Dict[str, Dict], manifest: Dict,
                  tol: float = BYTES_TOL) -> Dict:
    """Ratchet diff of measured transfer surfaces against the committed
    budgets. Returns {"findings": [Finding], "improved": [str],
    "stale": [str]}: findings fail the gate (growth — leaf counts exact,
    bytes beyond `tol`); improvements and stale manifest entries print
    loudly and are adopted deliberately via --write-manifest.
    """
    findings: List[Finding] = []
    improved: List[str] = []
    entries = manifest.get("entries", {})
    for name in sorted(measured):
        m = measured[name]
        if "error" in m:
            findings.append(_finding(
                "xfer/entry-unmeasurable", name,
                "entry %r failed to measure (%s) — a surface that cannot "
                "be audited cannot keep its budget" % (name, m["error"])))
            continue
        if name not in entries:
            findings.append(_finding(
                "xfer/unknown-entry", name,
                "entry %r has no committed transfer budget — adopt it "
                "deliberately with `graftlint --write-manifest`" % name))
            continue
        want = entries[name]
        md, wd = m["d2h"], want["d2h"]
        if md["leaves"] > wd["leaves"]:
            findings.append(_finding(
                "xfer/extra-fetch-leaf", name,
                "%s fetches %d output leaves (budget %d): a new D2H leaf "
                "on the hot path (measured %s vs manifest %s) — every "
                "'rides the same fetch' claim must keep the leaf count"
                % (name, md["leaves"], wd["leaves"], md["shapes"],
                   want["d2h"].get("shapes", []))))
        elif md["leaves"] < wd["leaves"]:
            improved.append("%s: d2h leaves %d -> %d (adopt with "
                            "--write-manifest)"
                            % (name, wd["leaves"], md["leaves"]))
        if m["h2d_fresh"]["leaves"] > want["h2d_fresh"]["leaves"] \
                or m["donated"]["leaves"] < want["donated"]["leaves"]:
            findings.append(_finding(
                "xfer/undonated-input", name,
                "%s input split drifted: fresh-H2D %d leaves (budget %d), "
                "donated %d (budget %d) — a previously donated buffer is "
                "now a fresh per-call upload"
                % (name, m["h2d_fresh"]["leaves"],
                   want["h2d_fresh"]["leaves"], m["donated"]["leaves"],
                   want["donated"]["leaves"])))
        elif m["h2d_fresh"]["leaves"] < want["h2d_fresh"]["leaves"] \
                or m["donated"]["leaves"] > want["donated"]["leaves"]:
            improved.append("%s: input split improved (fresh %d -> %d, "
                            "donated %d -> %d)"
                            % (name, want["h2d_fresh"]["leaves"],
                               m["h2d_fresh"]["leaves"],
                               want["donated"]["leaves"],
                               m["donated"]["leaves"]))
        if md["bytes"] > wd["bytes"] * (1.0 + tol):
            findings.append(_finding(
                "xfer/d2h-bytes-grew", name,
                "%s D2H grew %d -> %d bytes (+%.1f%%, tolerance %.0f%%) "
                "— grow the budget deliberately with --write-manifest or "
                "shed the fetch"
                % (name, wd["bytes"], md["bytes"],
                   100.0 * (md["bytes"] / max(wd["bytes"], 1) - 1.0),
                   100.0 * tol)))
        elif md["bytes"] < wd["bytes"] * (1.0 - tol):
            improved.append("%s: d2h bytes %d -> %d"
                            % (name, wd["bytes"], md["bytes"]))
        if m["h2d_fresh"]["bytes"] > want["h2d_fresh"]["bytes"] \
                * (1.0 + tol):
            findings.append(_finding(
                "xfer/h2d-bytes-grew", name,
                "%s fresh-H2D grew %d -> %d bytes (+%.1f%%)"
                % (name, want["h2d_fresh"]["bytes"],
                   m["h2d_fresh"]["bytes"],
                   100.0 * (m["h2d_fresh"]["bytes"]
                            / max(want["h2d_fresh"]["bytes"], 1) - 1.0))))
        if m["host_callbacks"] > want.get("host_callbacks", 0):
            findings.append(_finding(
                "xfer/host-callback-grew", name,
                "%s gained a host callback (%d vs budget %d): each "
                "invocation is a host round trip per step"
                % (name, m["host_callbacks"],
                   want.get("host_callbacks", 0))))
    if set(measured) >= set(ENTRY_POINTS):
        stale = sorted(k for k in entries if k not in measured)
    else:
        stale = []  # a partial (--changed) run can't judge staleness
    return {"findings": findings, "improved": improved, "stale": stale}


def write_manifest(measured: Dict[str, Dict],
                   path: Optional[str] = None) -> str:
    """Adopt the measured surfaces as the committed budget (atomic write,
    like every artifact). Refuses to bake in an unmeasurable entry."""
    from ..utils import save_json
    path = path or MANIFEST_PATH
    bad = sorted(n for n, m in measured.items() if "error" in m)
    if bad:
        raise ValueError("refusing to write a manifest with unmeasurable "
                         "entries: %s" % ", ".join(bad))
    save_json(path, {"schema": SCHEMA, "entries": measured}, indent=1,
              sort_keys=True)
    return path


def audit_transfers(only: Optional[Set[str]] = None,
                    manifest_path: Optional[str] = None) -> Dict:
    """Measure (all registered entries, or the `only` subset) and gate
    against the committed manifest — graftlint layer 4's whole run."""
    measured = measure_repo_entry_points(only=only)
    res = gate_manifest(measured, load_manifest(manifest_path))
    res["measured"] = measured
    return res


def bench_transfer_ok(fn: Callable, args: Sequence,
                      donate_argnums: Sequence[int] = (),
                      entry: str = "train_step_scanned",
                      manifest_path: Optional[str] = None) -> bool:
    """Does the IN-HAND timed program's device<->host interface fit the
    committed budget for `entry`? Shape-INDEPENDENT comparison (fetched
    leaf count, fresh-H2D leaf count, host-callback count) — the bench
    runs real archs and batch sizes while the manifest is measured at
    the audit's tiny config, so bytes are not comparable here (graftlint
    layer 4 gates them at the pinned config). eval_shape/make_jaxpr
    only: zero device work, safe next to `donation_ok` in bench.py's
    ONE-JSON-line path. Raises KeyError when the manifest carries no
    budget for `entry` (the caller's try/except reports "unavailable"
    rather than a fake verdict)."""
    budget = load_manifest(manifest_path)["entries"].get(entry)
    if budget is None or "error" in budget:
        raise KeyError("no committed transfer budget for entry %r"
                       % entry)
    m = measure_entry(fn, args, donate_argnums=donate_argnums)
    return (m["d2h"]["leaves"] <= budget["d2h"]["leaves"]
            and m["h2d_fresh"]["leaves"] <= budget["h2d_fresh"]["leaves"]
            and m["host_callbacks"] <= budget["host_callbacks"])


# ---------------------------------------------------------------------------
# the runtime twin: counted real fetches (the shared test fixture's core)


class DeviceGetCounter:
    """Collected `jax.device_get` calls while `counting_device_get` is
    active. `count` is the number of FETCHES (calls), the quantity every
    zero-extra-D2H pin asserts on; `calls` keeps the fetched trees for
    structure checks."""

    def __init__(self):
        self.calls: List = []

    @property
    def count(self) -> int:
        return len(self.calls)


@contextlib.contextmanager
def counting_device_get():
    """Count every `jax.device_get` under the context — the one
    implementation behind the per-subsystem fetch-count test pins
    (tests/conftest.py `count_device_get`). Restores the real function
    on exit even when the body raises."""
    import jax

    counter = DeviceGetCounter()
    real = jax.device_get

    def _counting(tree):
        counter.calls.append(tree)
        return real(tree)

    jax.device_get = _counting
    try:
        yield counter
    finally:
        jax.device_get = real
