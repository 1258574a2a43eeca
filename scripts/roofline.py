"""Per-fusion roofline attribution for the train step (ISSUE 2 tentpole).

The reference has no performance attribution at all (SURVEY.md §5; its
timing stops at the per-segment meters of ref train.py:92-140).

bench.py's `mfu_train` says WHAT fraction of peak the step achieves;
nothing said WHERE the rest goes. This tool grows scripts/trace_summary.py
into a roofline attributor: it compiles the production scanned train step
(the exact program bench.py times), then

1. parses the compiled HLO text (`jax.stages.Compiled.as_text()`) into a
   per-instruction table — HBM bytes (operand + result buffer sizes: what
   a fusion actually moves, ignoring VMEM-resident intra-fusion
   temporaries), analytic FLOPs (convolution/dot shape math, attributed
   through `calls=`d fused computations; elementwise/reduce ops counted at
   1 FLOP/element and labeled approximate),
2. optionally executes the program under `jax.profiler` and joins the
   device trace's per-op durations (`op_durations` below) by exact
   instruction name,
3. classifies every op against the v5e roofline: arithmetic intensity
   (FLOPs/byte) vs the ridge point peak_flops / hbm_bw (~241 FLOP/byte on
   v5e) -> bound-by "mxu" | "hbm", plus each op's % of step time and of
   step bytes,

and writes `artifacts/<round>/roofline/` (round from bench.graft_round())
as machine-readable JSON (schema "roofline-v1", guarded by
tests/test_roofline.py) plus a human markdown table — so every future perf
PR starts from measured targets instead of vibes.

`--ab-loss-kernel` additionally compiles the --loss-kernel xla/fused
variants of the same config and records the cost-analysis byte/FLOP deltas
(full step AND loss-only subprogram) — the ISSUE-2 acceptance evidence.

Off-chip honesty: on the CPU backend the per-op BYTES reflect the CPU
pipeline's fusion/layout choices (a proxy for TPU's — r5's analytic
roofline showed CPU bytes can overestimate chip traffic severely for
convolutions), and times are host times; the artifact labels its platform
and the v5e constants it classifies against. That holds for the fused BN
tails too: off the chip a `--epilogue fused` / `--block-fuse fused` step
compiles the jnp twins of ops/pallas/epilogue.py, and their rows are
counted as every other row is (the CPU pipeline's bytes, not the 8 / 12
activation-sized transfers the kernels make on the chip). When the chip is reachable,
run exactly the same command behind the single claim waiter (CLAUDE.md).

`--diff baseline.json candidate.json` (ISSUE 7) is the attribution
counterpart for step-compression A/Bs: it joins two roofline-v1 artifacts
into per-op-class (conv / convert / elementwise / reduce-window / dot)
and per-fusion byte+FLOP delta tables (schema "roofline-diff-v1"), pure
file work — no backend is acquired. The acceptance workflow for any
conv-path change: run the tool at the same config before and after, then
diff; the class table says which traffic actually moved (CLAUDE.md points
conv-path PRs here).

Usage:
  python scripts/roofline.py [--platform cpu] [--batch N] [--imsize N]
      [--steps N] [--remat none|stacks|full] [--loss-kernel auto|fused|xla]
      [--param-policy fp32|bf16-compute] [--epilogue auto|fused|xla]
      [--num-stack N] [--top N] [--no-trace] [--ab-loss-kernel]
      [--out PATH.json] [--tag TAG]
  python scripts/roofline.py --diff BASELINE.json CANDIDATE.json
      [--out PATH.json] [--tag TAG]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (TARGET_CHIP, acquire_backend, bytes_of, chip_peaks,
                   flops_of, graft_round, log)
from real_time_helmet_detection_tpu.runtime import (maybe_job_heartbeat,
                                                    run_as_job)

SCHEMA = "roofline-v1"

# dtype -> bytes per element (HLO shape literals)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
# greedy param match: computation params can be tuple-typed (nested
# parens — while-body regions), so anchor on the LAST ') ->'
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_WINDOW_RE = re.compile(r"window={[^}]*\bsize=([0-9x]+)")
_GROUPS_RE = re.compile(r"feature_group_count=(\d+)")
_DIMLBL_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims={([0-9,]*)}")

# non-compute plumbing: never reported as roofline rows
_SKIP_OPCODES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "add-dependency",
    "partition-id", "replica-id", "rng-get-and-update-state", "domain",
    "opt-barrier", "get-dimension-size",
}

# 1-FLOP/element opcodes (the approximate elementwise/reduce estimate;
# transcendentals deliberately also 1/elem — byte-bound ops don't turn on
# their FLOP count)
_ELEMENTWISE_HINT = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "abs",
    "negate", "exponential", "log", "tanh", "logistic", "power", "sqrt",
    "rsqrt", "select", "compare", "convert", "floor", "ceil", "sign",
    "and", "or", "not", "xor", "clamp", "reduce", "reduce-window",
    "exponential-minus-one", "log-plus-one", "remainder", "atan2",
}


# the op-class taxonomy of the --diff tables (ISSUE 7): every reportable
# row lands in exactly one class, derived from opcode + the descriptive
# fusion names the optimized HLO carries ("convert_convert_fusion",
# "subtract_multiply_fusion", ...). Order matters: "convolution" must be
# tested before "convert" ("conv" is a prefix of both).
OP_CLASSES = ("conv", "convert", "reduce-window", "dot", "elementwise")


def op_class(name: str, opcode: str) -> str:
    """Roofline op class of one reportable row. Classes roll up the diff
    tables; 'elementwise' is the catch-all for the pointwise/copy/reduce
    plumbing between the compute classes (custom-calls — Pallas kernels —
    land there too: they replace exactly that traffic)."""
    n = name.lower()
    if opcode == "convolution" or "convolution" in n:
        return "conv"
    if opcode == "convert" or "convert" in n:
        return "convert"
    if opcode == "reduce-window" or "reduce-window" in n \
            or "reduce_window" in n:
        return "reduce-window"
    if opcode == "dot" or n.startswith("dot"):
        return "dot"
    return "elementwise"


def class_totals(rows) -> dict:
    """Per-class byte/FLOP rollup of a fusions table (works on any
    roofline-v1 artifact, including pre-ISSUE-7 ones whose rows carry no
    'class' field — the class is derived from name+opcode)."""
    out = {c: {"bytes": 0.0, "flops": 0.0, "ops": 0} for c in OP_CLASSES}
    for r in rows:
        c = r.get("class") or op_class(r["name"], r["opcode"])
        out[c]["bytes"] += r["bytes"]
        out[c]["flops"] += r["flops"]
        out[c]["ops"] += 1
    total = sum(v["bytes"] for v in out.values()) or 1.0
    for v in out.values():
        v["pct_bytes"] = round(100.0 * v["bytes"] / total, 2)
    return out


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype)
    if bpe is None:
        return 0  # token/opaque/tuple-internal — no buffer
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * bpe


def _shape_elems(dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


class Instr:
    __slots__ = ("name", "opcode", "out_bytes", "operand_bytes",
                 "out_elems", "flops", "calls", "line")

    def __init__(self, name, opcode, out_bytes, operand_bytes, out_elems,
                 flops, calls, line):
        self.name = name
        self.opcode = opcode
        self.out_bytes = out_bytes
        self.operand_bytes = operand_bytes
        self.out_elems = out_elems
        self.flops = flops
        self.calls = calls
        self.line = line


def _parse_rhs(rhs: str):
    """(result_part, opcode, rest) of an instruction's right-hand side."""
    rhs = rhs.strip()
    if rhs.startswith("("):  # tuple result: shapes up to the matching ')'
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        sp = rhs.find(" ")
        result, rest = rhs[:sp], rhs[sp:]
    rest = rest.strip()
    m = re.match(r"([\w\-]+)", rest)
    opcode = m.group(1) if m else "?"
    return result, opcode, rest[len(opcode):]


def _conv_flops(line: str, out_elems: int) -> float:
    """2 * out_elems * window_prod * per-group input channels."""
    win = _WINDOW_RE.search(line)
    wprod = 1
    if win:
        for s in win.group(1).split("x"):
            wprod *= int(s)
    cin = 1
    dl = _DIMLBL_RE.search(line)
    # operand 1 (the kernel) is the second shape in the call parens
    shapes = _SHAPE_RE.findall(line.split("convolution(", 1)[-1])
    if dl and len(shapes) >= 2:
        klabels = dl.group(2)
        kdims = shapes[1][1].split(",") if shapes[1][1] else []
        ipos = klabels.find("i")
        if 0 <= ipos < len(kdims):
            cin = int(kdims[ipos])
    return 2.0 * out_elems * wprod * cin


def _dot_flops(line: str, out_elems: int) -> float:
    m = _CONTRACT_RE.search(line)
    shapes = _SHAPE_RE.findall(line.split("dot(", 1)[-1])
    contract = 1
    if m and shapes:
        lhs_dims = shapes[0][1].split(",") if shapes[0][1] else []
        for idx in (m.group(1).split(",") if m.group(1) else []):
            i = int(idx)
            if i < len(lhs_dims):
                contract *= int(lhs_dims[i])
    return 2.0 * out_elems * contract


def _instr_flops(opcode: str, line: str, out_elems: int) -> float:
    if opcode == "convolution":
        return _conv_flops(line, out_elems)
    if opcode == "dot":
        return _dot_flops(line, out_elems)
    if opcode in _ELEMENTWISE_HINT:
        return float(out_elems)
    return 0.0


def parse_hlo(text: str):
    """HLO module text -> {computation_name: [Instr, ...]}, plus the sets
    of computations called as fusion bodies / scalar appliers (to roll up
    or skip when selecting reportable rows)."""
    comps = {}
    fusion_bodies = set()
    appliers = set()
    current = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" ") and "{" in line:
            m = _COMP_RE.match(line)
            # a header that fails the name parse still ends the previous
            # computation — misfiling its instructions into an excluded
            # fusion body would silently drop them from the table
            current = m.group(1) if m else "_comp_%d" % len(comps)
            comps[current] = []
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR_RE.match(line)
        if m is None or current is None:
            continue
        name, rhs = m.group(1), m.group(2)
        # cut trailing annotation blocks whose payload can contain
        # bracketed text that would pollute the operand-shape scan
        body = re.split(r",\s*(?:metadata=|backend_config=|sharding=)",
                        rhs)[0]
        result, opcode, rest = _parse_rhs(body)
        out_shapes = _SHAPE_RE.findall(result)
        out_bytes = sum(_shape_bytes(d, s) for d, s in out_shapes)
        out_elems = sum(_shape_elems(s) for _, s in out_shapes)
        opnd_bytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(rest))
        calls = None
        if opcode == "fusion":
            cm = _CALLS_RE.search(rest)
            if cm:
                calls = cm.group(1)
                fusion_bodies.add(calls)
        am = _APPLY_RE.search(rest)
        if am:
            appliers.add(am.group(1))
        flops = _instr_flops(opcode, body, out_elems)
        comps[current].append(Instr(name, opcode, out_bytes, opnd_bytes,
                                    out_elems, flops, calls, body))
    return comps, fusion_bodies, appliers


def attribute(comps, fusion_bodies, appliers):
    """Reportable per-op records: every instruction of every computation
    that is not a fusion body or scalar applier, with fusion FLOPs rolled
    up from their called computations."""
    comp_flops = {
        cname: sum(i.flops for i in instrs)
        for cname, instrs in comps.items()
    }

    rows = []
    for cname, instrs in comps.items():
        if cname in fusion_bodies or cname in appliers:
            continue
        for i in instrs:
            if i.opcode in _SKIP_OPCODES:
                continue
            flops = i.flops
            kind = i.opcode
            if i.opcode == "fusion" and i.calls:
                flops = comp_flops.get(i.calls, 0.0)
            bytes_ = i.out_bytes + i.operand_bytes
            if bytes_ == 0 and flops == 0:
                continue
            rows.append({"name": i.name, "opcode": kind,
                         "class": op_class(i.name, kind),
                         "flops": flops, "bytes": float(bytes_)})
    return rows



def find_traces(root: str):
    """The Chrome trace-event JSON files (`*.trace.json[.gz]`) the
    profiler wrote under `root`."""
    out = []
    for dirpath, _, files in os.walk(root):
        out += [os.path.join(dirpath, f) for f in files
                if f.endswith(".trace.json.gz") or f.endswith(".trace.json")]
    return out


def load_events(path: str):
    import gzip
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        data = json.load(f)
    return data.get("traceEvents", [])


def op_durations(events):
    """RAW-name per-op total durations: {name: [total_us, count]} — names
    exactly as emitted (`fusion.123`, `convolution.1293`), so they join
    the compiled HLO's instruction names. Only duration events
    (ph == 'X') count; track attribution is dropped (the join is by
    instruction name, which XLA keeps module-unique)."""
    out = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        rec = out.setdefault(e.get("name", ""), [0.0, 0])
        rec[0] += float(e.get("dur", 0.0))
        rec[1] += 1
    return out


def classify(rows, peak: float, hbm: float, durations=None, steps: int = 1):
    """Fill intensity / bound / %s into `rows`; returns summary totals."""
    ridge = peak / hbm
    matched_us = 0.0
    for r in rows:
        dur = durations.get(r["name"]) if durations else None
        if dur is not None:
            r["time_us"] = round(dur[0] / steps, 3)
            r["trace_calls"] = dur[1]
            matched_us += dur[0]
        else:
            r["time_us"] = None
        b = r["bytes"]
        f = r["flops"]
        r["intensity"] = round(f / b, 3) if b else math.inf
        r["bound"] = "mxu" if (b == 0 or f / b >= ridge) else "hbm"
        # the roofline-implied floor for this op alone, at target-chip
        # constants (µs)
        r["t_roofline_us"] = round(max(f / peak, b / hbm) * 1e6, 3)
    total_bytes = sum(r["bytes"] for r in rows) or 1.0
    total_time = sum(r["time_us"] for r in rows
                     if r["time_us"] is not None) or None
    for r in rows:
        r["pct_bytes"] = round(100.0 * r["bytes"] / total_bytes, 2)
        r["pct_time"] = (round(100.0 * r["time_us"] / total_time, 2)
                         if total_time and r["time_us"] is not None
                         else None)
    rows.sort(key=lambda r: (-(r["time_us"] or 0.0), -r["bytes"]))
    return {"total_bytes": total_bytes,
            "total_time_us_per_step": total_time,
            "ridge_flops_per_byte": round(peak / hbm, 2),
            "matched_trace_us": round(matched_us, 1)}


def _markdown(rows, meta, top: int) -> str:
    lines = ["# Roofline attribution — %s"
             % ("predict (serve wire)"
                if (meta["config"] or {}).get("mode") == "predict"
                else "train step"),
             "",
             "platform=%s  config=%s" % (meta["platform"],
                                         json.dumps(meta["config"])),
             "ridge=%.1f FLOP/byte (v5e %.0f TFLOP/s / %.0f GB/s)"
             % (meta["summary"]["ridge_flops_per_byte"],
                meta["peak_flops"] / 1e12, meta["hbm_bytes_per_s"] / 1e9),
             "",
             "| op | kind | time us/step | % time | MB | % bytes | "
             "GFLOP | FLOP/byte | bound |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows[:top]:
        lines.append(
            "| %s | %s | %s | %s | %.2f | %.1f | %.2f | %s | %s |" % (
                r["name"][:48], r["opcode"],
                "%.1f" % r["time_us"] if r["time_us"] is not None else "-",
                "%.1f" % r["pct_time"] if r["pct_time"] is not None else "-",
                r["bytes"] / 2**20, r["pct_bytes"], r["flops"] / 1e9,
                "inf" if r["intensity"] == math.inf else
                "%.1f" % r["intensity"], r["bound"]))
    return "\n".join(lines) + "\n"


def build_step(jax, args, loss_kernel: str):
    """The exact scanned train program bench.py times, at the CLI config."""
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.optim import build_optimizer
    from real_time_helmet_detection_tpu.train import (
        create_train_state, make_scanned_train_fn, make_train_step_body)

    cfg = Config(num_stack=args.num_stack, hourglass_inch=args.hourglass_inch,
                 num_cls=2, batch_size=args.batch, amp=True,
                 imsize=args.imsize, remat=args.remat,
                 loss_kernel=loss_kernel,
                 param_policy=getattr(args, "param_policy", "fp32"),
                 epilogue=getattr(args, "epilogue", "auto"),
                 block_fuse=getattr(args, "block_fuse", "auto"),
                 fwd_dtype=getattr(args, "fwd_dtype", "bf16"))
    model = build_model(cfg, dtype=jnp.bfloat16)
    tx = build_optimizer(cfg, 100)
    state = create_train_state(model, cfg, jax.random.key(0), args.imsize,
                               tx)
    body = make_train_step_body(model, tx, cfg)
    arrs = tuple(jnp.asarray(a) for a in synthetic_target_batch(
        args.batch, args.imsize, pos_rate=0.01))
    train_n = make_scanned_train_fn(body, args.steps)
    compiled = jax.jit(train_n, donate_argnums=(0,)).lower(
        state, *arrs).compile()
    remake = lambda: create_train_state(  # noqa: E731 — donation refills
        model, cfg, jax.random.key(0), args.imsize, tx)
    return compiled, state, arrs, remake


def build_predict(jax, args):
    """`--mode predict` (ISSUE 13): the serve-wire predict program — raw
    uint8 in, normalize on-device, network -> sigmoid -> decode -> NMS —
    at the CLI architecture (variant/stacks/width), ONE batch shape. The
    per-tier counting model behind the latency-tier Pareto table: the
    quality_matrix tier rows and the edge-vs-flagship `--diff` evidence
    both come from this program."""
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.config import Config
    from real_time_helmet_detection_tpu.models import build_model
    from real_time_helmet_detection_tpu.predict import make_predict_fn
    from real_time_helmet_detection_tpu.train import init_variables

    cfg = Config(num_stack=args.num_stack,
                 hourglass_inch=args.hourglass_inch, num_cls=2,
                 variant=args.variant,
                 # tier geometry: the stem follows the model width below
                 # 128 (config.TIER_PRESETS stem_width convention)
                 stem_width=min(128, args.hourglass_inch),
                 topk=100, conf_th=0.0, nms_th=0.5,
                 imsize=args.imsize, epilogue=args.epilogue,
                 block_fuse=getattr(args, "block_fuse", "auto"))
    model = build_model(cfg, dtype=jnp.bfloat16)
    params, batch_stats = init_variables(model, jax.random.key(0),
                                         args.imsize)
    variables = {"params": params, "batch_stats": batch_stats}
    predict = make_predict_fn(model, cfg, normalize="imagenet")
    images = jnp.zeros((args.batch, args.imsize, args.imsize, 3),
                       jnp.uint8)
    compiled = predict.lower(variables, images).compile()
    return compiled, (variables, images)


def loss_subprogram_cost(jax, args, kernel: str):
    """Cost record of value_and_grad of the loss ALONE over the raw stack
    output at the CLI shapes — the fusion the Pallas kernel replaces,
    isolated from the conv-dominated step.

    Returns {flops, bytes (XLA cost analysis), parsed_bytes (this file's
    operand+result model over the compiled HLO), kernel_bytes_analytic
    (fused only)}. Counting-model caveat: OFF-TPU the fused variant
    compiles the Pallas INTERPRET lowering (dynamic-update-slice
    machinery that does not exist on chip), so its compiled-artifact byte
    counts are meaningless there; `kernel_bytes_analytic` applies the
    SAME operand+result rule to the real TPU lowering's shape — fwd reads
    the five input maps, bwd reads them again and writes d(out) — and is
    the honest comparison partner for the XLA variant's parsed_bytes."""
    import jax.numpy as jnp

    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    from real_time_helmet_detection_tpu.ops.loss import (
        stacked_detection_loss)
    from real_time_helmet_detection_tpu.ops.pallas import (
        fused_detection_loss)

    _, heat, off, wh, mask = (jnp.asarray(a) for a in
                              synthetic_target_batch(args.batch,
                                                     args.imsize,
                                                     pos_rate=0.01))
    m = args.imsize // 4
    rng = np.random.default_rng(0)
    out = jnp.asarray(rng.standard_normal(
        (args.batch, args.num_stack, m, m, 6)).astype(np.float32))

    if kernel == "fused":
        fn = lambda o: fused_detection_loss(  # noqa: E731
            o, heat, off, wh, mask)["total"]
    else:
        fn = lambda o: stacked_detection_loss(  # noqa: E731
            o, heat, off, wh, mask, num_cls=2)["total"]
    c = jax.jit(jax.value_and_grad(fn)).lower(out).compile()
    comps, fb, ap = parse_hlo(c.as_text())
    rec = {"flops": flops_of(c), "bytes": bytes_of(c),
           "parsed_bytes": sum(r["bytes"]
                               for r in attribute(comps, fb, ap))}
    if kernel == "fused":
        inputs = sum(float(a.size) * a.dtype.itemsize
                     for a in (out, heat, off, wh, mask))
        # fwd pass reads + bwd pass reads + d(out) write (+ the tiny
        # epilogue re-reads mask for num_pos)
        rec["kernel_bytes_analytic"] = (
            2.0 * inputs + float(out.size) * out.dtype.itemsize
            + float(mask.size) * mask.dtype.itemsize)
    return rec


DIFF_SCHEMA = "roofline-diff-v1"


def diff_rooflines(baseline: dict, candidate: dict) -> dict:
    """Join two roofline-v1 artifacts into byte/FLOP delta tables.

    Pure dict work (tests pin it on checked-in fixture tables). Per-class
    deltas are the headline — instruction names rarely survive a program
    change, so per-fusion deltas are only reported for names present on
    BOTH sides, plus each side's top unmatched movers. Sign convention:
    positive delta_pct = the candidate REDUCED that class's bytes."""
    for side, art in (("baseline", baseline), ("candidate", candidate)):
        if art.get("schema") != SCHEMA:
            raise ValueError("--diff: %s is not a %s artifact (schema=%r)"
                             % (side, SCHEMA, art.get("schema")))
    rows_a, rows_b = baseline["fusions"], candidate["fusions"]
    cls_a, cls_b = class_totals(rows_a), class_totals(rows_b)
    total_a = sum(v["bytes"] for v in cls_a.values())
    total_b = sum(v["bytes"] for v in cls_b.values())

    def pct(delta, base):
        return round(100.0 * delta / base, 2) if base else None

    by_class = {}
    for c in OP_CLASSES:
        a, b = cls_a[c], cls_b[c]
        by_class[c] = {
            "bytes_baseline": a["bytes"], "bytes_candidate": b["bytes"],
            "bytes_delta": a["bytes"] - b["bytes"],
            "bytes_delta_pct": pct(a["bytes"] - b["bytes"], a["bytes"]),
            "flops_baseline": a["flops"], "flops_candidate": b["flops"],
            "ops_baseline": a["ops"], "ops_candidate": b["ops"],
            "pct_of_step_baseline": a["pct_bytes"],
            "pct_of_step_candidate": b["pct_bytes"],
        }
    nonconv_a = total_a - cls_a["conv"]["bytes"]
    nonconv_b = total_b - cls_b["conv"]["bytes"]
    ce_a = cls_a["convert"]["bytes"] + cls_a["elementwise"]["bytes"]
    ce_b = cls_b["convert"]["bytes"] + cls_b["elementwise"]["bytes"]

    named_a = {r["name"]: r for r in rows_a}
    named_b = {r["name"]: r for r in rows_b}
    matched = []
    for name in set(named_a) & set(named_b):
        da = named_a[name]["bytes"] - named_b[name]["bytes"]
        if da:
            matched.append({
                "name": name, "class": op_class(name,
                                                named_a[name]["opcode"]),
                "bytes_baseline": named_a[name]["bytes"],
                "bytes_candidate": named_b[name]["bytes"],
                "bytes_delta": da})
    matched.sort(key=lambda r: -abs(r["bytes_delta"]))

    def top_unmatched(rows, other_names):
        un = [r for r in rows if r["name"] not in other_names]
        un.sort(key=lambda r: -r["bytes"])
        return [{"name": r["name"],
                 "class": op_class(r["name"], r["opcode"]),
                 "bytes": r["bytes"]} for r in un[:15]]

    return {
        "schema": DIFF_SCHEMA,
        "baseline": {"config": baseline.get("config"),
                     "platform": baseline.get("platform"),
                     "total_bytes": total_a},
        "candidate": {"config": candidate.get("config"),
                      "platform": candidate.get("platform"),
                      "total_bytes": total_b},
        "platform_match": baseline.get("platform")
        == candidate.get("platform"),
        "total_bytes_delta_pct": pct(total_a - total_b, total_a),
        "nonconv_bytes_baseline": nonconv_a,
        "nonconv_bytes_candidate": nonconv_b,
        "nonconv_bytes_delta_pct": pct(nonconv_a - nonconv_b, nonconv_a),
        "convert_plus_elementwise_baseline": ce_a,
        "convert_plus_elementwise_candidate": ce_b,
        "convert_plus_elementwise_delta_pct": pct(ce_a - ce_b, ce_a),
        "conv_bytes_delta_pct": pct(
            cls_a["conv"]["bytes"] - cls_b["conv"]["bytes"],
            cls_a["conv"]["bytes"]),
        "by_class": by_class,
        "matched_fusions": matched[:30],
        "top_baseline_only": top_unmatched(rows_a, set(named_b)),
        "top_candidate_only": top_unmatched(rows_b, set(named_a)),
    }


def _diff_markdown(d: dict) -> str:
    lines = ["# Roofline diff — per-op-class HBM bytes",
             "",
             "baseline: %s  candidate: %s" % (
                 json.dumps(d["baseline"]["config"]),
                 json.dumps(d["candidate"]["config"])),
             "",
             "| class | baseline MB | candidate MB | delta MB | delta % | "
             "% of step (base -> cand) |",
             "|---|---|---|---|---|---|"]
    for c in OP_CLASSES:
        r = d["by_class"][c]
        lines.append("| %s | %.1f | %.1f | %.1f | %s | %.1f -> %.1f |" % (
            c, r["bytes_baseline"] / 2**20, r["bytes_candidate"] / 2**20,
            r["bytes_delta"] / 2**20,
            "%.1f" % r["bytes_delta_pct"]
            if r["bytes_delta_pct"] is not None else "-",
            r["pct_of_step_baseline"], r["pct_of_step_candidate"]))
    lines += ["",
              "total: %.1f%%  non-conv: %.1f%%  convert+elementwise: "
              "%.1f%%  conv: %s%%  (positive = candidate moves fewer "
              "bytes)" % (
                  d["total_bytes_delta_pct"] or 0.0,
                  d["nonconv_bytes_delta_pct"] or 0.0,
                  d["convert_plus_elementwise_delta_pct"] or 0.0,
                  d["conv_bytes_delta_pct"]),
              "",
              "## Top matched-fusion movers", "",
              "| fusion | class | baseline MB | candidate MB |",
              "|---|---|---|---|"]
    for r in d["matched_fusions"][:15]:
        lines.append("| %s | %s | %.2f | %.2f |" % (
            r["name"][:48], r["class"], r["bytes_baseline"] / 2**20,
            r["bytes_candidate"] / 2**20))
    return "\n".join(lines) + "\n"


def run_diff(args) -> None:
    """--diff entry: pure file work, NO backend acquisition (a diff must
    run on a box with no chip — that is its whole point)."""
    base_path, cand_path = args.diff
    with open(base_path) as f:
        baseline = json.load(f)
    with open(cand_path) as f:
        candidate = json.load(f)
    d = diff_rooflines(baseline, candidate)
    d["inputs"] = {"baseline": base_path, "candidate": cand_path}
    if not d["platform_match"]:
        log("WARNING: diffing across platforms (%s vs %s) — fusion "
            "choices differ by pipeline, read the class table as a trend"
            % (baseline.get("platform"), candidate.get("platform")))
    if args.out:
        out_path = args.out
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tag = ("_" + args.tag) if args.tag else ""
        out_path = os.path.join(root, "artifacts", graft_round(),
                                "roofline", "roofline_diff%s.json" % tag)
    from real_time_helmet_detection_tpu.utils import (atomic_write_bytes,
                                                      save_json)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    save_json(out_path, d, indent=1)
    atomic_write_bytes(out_path.rsplit(".", 1)[0] + ".md",
                       _diff_markdown(d).encode())
    log("wrote %s" % out_path)
    print(json.dumps({k: v for k, v in d.items()
                      if k not in ("matched_fusions", "top_baseline_only",
                                   "top_candidate_only", "by_class")}
                     | {"out": out_path}))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--platform", default="",
                    help="force a jax platform (cpu/tpu); '' = default")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--imsize", type=int, default=512)
    ap.add_argument("--num-stack", type=int, default=1)
    ap.add_argument("--hourglass-inch", type=int, default=128)
    ap.add_argument("--mode", default="train",
                    choices=["train", "predict"],
                    help="train = the scanned train step (the default, "
                         "the pre-tier behavior); predict = the serve-"
                         "wire predict program (ISSUE 13: the per-tier "
                         "counting model)")
    ap.add_argument("--variant", default="residual",
                    choices=["residual", "depthwise", "ghost"],
                    help="residual-block variant (the latency-tier axis)")
    ap.add_argument("--steps", type=int, default=2,
                    help="scan length of the traced program (train mode)")
    ap.add_argument("--remat", default="none",
                    choices=["none", "stacks", "full"])
    ap.add_argument("--loss-kernel", default="auto",
                    choices=["auto", "fused", "xla"])
    ap.add_argument("--param-policy", default="fp32",
                    choices=["fp32", "bf16-compute"])
    ap.add_argument("--epilogue", default="auto",
                    choices=["auto", "fused", "xla"])
    ap.add_argument("--block-fuse", default="auto",
                    choices=["auto", "fused", "xla"],
                    help="residual-block tail pass family (ISSUE 20): "
                         "fused = the one-pass BN+add+act custom_vjp")
    ap.add_argument("--fwd-dtype", default="bf16",
                    choices=["bf16", "int8"],
                    help="train-forward compute dtype (ISSUE 20): int8 "
                         "= STE forward, bf16 backward (train mode only)")
    ap.add_argument("--diff", nargs=2, metavar=("BASELINE", "CANDIDATE"),
                    help="join two roofline-v1 artifacts into per-class "
                         "delta tables (no backend; see module docstring)")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the profiler run (cost-only attribution)")
    ap.add_argument("--ab-loss-kernel", action="store_true",
                    help="also compile the xla/fused loss variants and "
                         "record the byte/FLOP deltas")
    ap.add_argument("--out", default="",
                    help="output JSON path (default: artifacts/<round>/"
                         "roofline/roofline_<platform>[_<tag>].json)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--cpu", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.diff:
        run_diff(args)
        return

    if args.platform:
        import jax
        from real_time_helmet_detection_tpu.runtime import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_platforms", args.platform)
        devs = jax.devices()
    else:
        # never silently CPU (an accidental CPU artifact would masquerade
        # as chip attribution): --cpu is the explicit count-only request
        jax, devs = acquire_backend()
        import jax  # noqa: F811 — name for the helpers below

    platform = devs[0].platform
    device_kind = devs[0].device_kind
    # a CPU run only counts: it classifies against the named target chip
    peak, hbm = chip_peaks(TARGET_CHIP if platform == "cpu" else device_kind)
    log("backend: %s (%s); classifying against %.0f TFLOP/s / %.0f GB/s"
        % (device_kind, platform, peak / 1e12, hbm / 1e9))

    # supervised-job contract (scripts/tpu_queue.py): beat at the slow
    # phase boundaries — first compile on a remote transport is minutes
    hb = maybe_job_heartbeat()
    hb.beat("backend up (%s)" % platform)
    predict_mode = args.mode == "predict"
    if predict_mode:
        compiled, pargs = build_predict(jax, args)
    else:
        compiled, state, arrs, remake = build_step(jax, args,
                                                   args.loss_kernel)
    hb.beat("step compiled")
    total_flops, total_bytes_ca = flops_of(compiled), bytes_of(compiled)
    comps, fusion_bodies, appliers = parse_hlo(compiled.as_text())
    rows = attribute(comps, fusion_bodies, appliers)
    log("HLO: %d computations, %d reportable ops"
        % (len(comps), len(rows)))
    durations = None
    trace_note = "disabled (--no-trace)"
    if not args.no_trace:
        import tempfile
        tdir = tempfile.mkdtemp(prefix="roofline_trace_")
        try:
            if predict_mode:
                # no donation: the same args serve warmup and traced run
                jax.tree.map(np.asarray, compiled(*pargs))  # warmup
                jax.profiler.start_trace(tdir)
                jax.tree.map(np.asarray, compiled(*pargs))
            else:
                np.asarray(compiled(state, *arrs)[1])  # warmup (donates)
                st2 = remake()
                jax.profiler.start_trace(tdir)
                np.asarray(compiled(st2, *arrs)[1])
            jax.profiler.stop_trace()
            events = []
            for t in find_traces(tdir):
                events += load_events(t)
            durations = op_durations(events)
            trace_note = "%d named trace ops" % len(durations)
        except Exception as e:  # noqa: BLE001 — plugin support varies
            trace_note = "trace failed: %s" % str(e).splitlines()[-1][:200]
            log(trace_note)

    steps = 1 if predict_mode else args.steps
    summary = classify(rows, peak, hbm, durations, steps=steps)
    # per-op-class rollup (the --diff tables join on these classes; also
    # the counting model behind bench.py's convert_bytes_pct)
    summary["by_class"] = class_totals(rows)
    meta = {
        "schema": SCHEMA,
        "platform": platform,
        "device_kind": device_kind,
        "peak_flops": peak,
        "hbm_bytes_per_s": hbm,
        "config": {"batch": args.batch, "imsize": args.imsize,
                   "num_stack": args.num_stack, "steps": steps,
                   "mode": args.mode, "variant": args.variant,
                   "width": args.hourglass_inch,
                   "remat": args.remat, "loss_kernel": args.loss_kernel,
                   "param_policy": args.param_policy,
                   "epilogue": args.epilogue,
                   "block_fuse": getattr(args, "block_fuse", "auto"),
                   "fwd_dtype": getattr(args, "fwd_dtype", "bf16"),
                   "amp": True},
        "totals": {"flops": total_flops,
                   "cost_analysis_bytes": total_bytes_ca,
                   "parsed_bytes": summary["total_bytes"]},
        "trace": trace_note,
        "summary": summary,
        "note": ("bytes are operand+result buffer sizes of the optimized "
                 "HLO's reportable ops (fusion-internal temporaries "
                 "excluded); on cpu they reflect the host pipeline's "
                 "fusion choices — a proxy for the TPU compiler's"),
    }

    if args.ab_loss_kernel and predict_mode:
        log("--ab-loss-kernel is a train-mode A/B; ignoring in "
            "--mode predict")
    if args.ab_loss_kernel and not predict_mode:
        ab = {}
        for variant in ("xla", "fused"):
            c, _, _, _ = build_step(jax, args, variant)
            ab["step_%s" % variant] = {"flops": flops_of(c),
                                       "bytes": bytes_of(c)}
            ab["loss_only_%s" % variant] = loss_subprogram_cost(
                jax, args, variant)
        # Honest pairing per platform (see loss_subprogram_cost): the XLA
        # variant's parsed bytes vs the fused kernel's — parsed on TPU
        # (the custom-call is transparent to the operand+result model),
        # analytic off-TPU (the interpret lowering is not the kernel).
        lx = ab["loss_only_xla"]["parsed_bytes"]
        fused_rec = ab["loss_only_fused"]
        lf_ = (fused_rec["parsed_bytes"] if platform == "tpu"
               else fused_rec["kernel_bytes_analytic"])
        ab["fused_bytes_basis"] = ("parsed" if platform == "tpu"
                                   else "analytic")
        if lx and lf_:
            ab["loss_bytes_delta_pct"] = round(100.0 * (lx - lf_) / lx, 2)
        # projected FULL-step reduction from the loss fusion alone, on the
        # same counting model (the conv-dominated step dilutes it hard —
        # the attribution table above is the evidence of where bytes
        # actually go)
        if lx and lf_ and summary["total_bytes"]:
            ab["step_bytes_delta_pct_projected"] = round(
                100.0 * (lx - lf_) / summary["total_bytes"], 3)
        sx, sf = ab["step_xla"]["bytes"], ab["step_fused"]["bytes"]
        if sx and sf and platform == "tpu":
            # meaningful only where the fused step compiles the real
            # kernel, not the interpret lowering
            ab["step_bytes_delta_pct_cost_analysis"] = round(
                100.0 * (sx - sf) / sx, 2)
        meta["loss_kernel_ab"] = ab
        log("loss-kernel A/B: %s" % json.dumps(
            {k: v for k, v in ab.items() if "pct" in k or "basis" in k}))

    meta["fusions"] = rows
    if args.out:
        out_path = args.out
    else:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        tag = ("_" + args.tag) if args.tag else ""
        out_path = os.path.join(
            root, "artifacts", graft_round(), "roofline",
            "roofline_%s%s%s.json"
            % (platform, "_predict" if predict_mode else "", tag))
    from real_time_helmet_detection_tpu.utils import (atomic_write_bytes,
                                                      save_json)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    save_json(out_path, meta, indent=1)  # atomic: crash-safe artifact
    md_path = out_path.rsplit(".", 1)[0] + ".md"
    atomic_write_bytes(md_path, _markdown(rows, meta, args.top).encode())
    log("wrote %s (+ %s)" % (out_path, os.path.basename(md_path)))
    # one JSON line on stdout (repo convention), without the full table
    print(json.dumps({k: v for k, v in meta.items() if k != "fusions"}
                     | {"n_ops": len(rows), "out": out_path}))


if __name__ == "__main__":
    run_as_job(main)  # status file + 0/75/1 exit contract (runtime/)
