"""HLO instruction -> layer, from a compiled executable's text.

The reference has no profiling of any kind (ref train.py:140-160 prints
averaged meters); this module is new capability. A device-only profiler
trace — the only kind the benchmark can afford (PERF.md section 6: with
the host tracer on the train step ran at 38% of its rate) — names each
device event after its HLO instruction (`%fusion.276 = ...`) and carries
nothing else: no scope, no `metadata=`. The scope names flax's modules and
the program's `jax.named_scope`s give an operation reach the trace only
through the COMPILED program: `compiled.as_text()` holds, for every
instruction, `metadata={op_name="jit(step)/jvp(StackedHourglass)/
Hourglass_0/.../conv_general_dilated"}`. Only the program holds those
executables (`ServingEngine.scope_maps()`, the step runner's
`scope_map()`); this module turns their text into `{instruction: layer}`,
which `scripts/trace_summary.py` joins with a trace.

Layers (PERF.md section 3): `stem` (PreLayer), `hourglass`, `neck`,
`head`, `merge` (the inter-stack 1x1 convolutions of a multi-stack model),
`normalize`, `loss`, `optimizer`, `peak`, `decode`, `nms`, with `/bwd`
appended where the scope path runs through `transpose(`: the backward
pass. The decoder families': `prefill/<scope>` and `decode/<scope>` for the
scopes `embed`, `attn_full`, `indexer`, `attn_window`, `attn_linear`,
`router`, `experts`, `shared_expert`, `dense_ffn`, `lm_head`, and under an
attention layer its parts `rope`, `kv_write`, `gate` (`decode/attn_full/kv_write`),
the latent attention's `absorb_q`, `cache_write`, `absorb_o`, the linear
attention's `conv`, `scan`, `state`, `out_norm` (`prefill/attn_linear/scan`:
the `kda_prefill` kernel on the chip; `decode/attn_linear/state`: the
`kda_step` kernel), and under `router` the `group_limit`. An operation the
program named but outside those (the step counter's `jit(step)/add`, the
network's own input cast) is `other`. An instruction
XLA made itself carries no metadata (`copy`, `bitcast`, `copy-start/done`
from layout assignment and memory-space assignment): it takes the layer
of the instructions it feeds when they agree, else of the instructions it
reads when they agree, else `unattributed`.

Stdlib only (obs/ rule): it parses text.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

UNATTRIBUTED = "unattributed"
OTHER = "other"

# scope-path element (flax module or jax.named_scope) -> layer; flax
# appends `_<n>` to a module's class name
_LAYER_OF_SCOPE = (
    ("PreLayer", "stem"), ("Hourglass", "hourglass"), ("Neck", "neck"),
    ("Head", "head"), ("Convolution", "merge"), ("normalize", "normalize"),
    ("loss", "loss"), ("optimizer", "optimizer"), ("peak", "peak"),
    ("decode", "decode"), ("nms", "nms"),
)
# the model's top module: its children are the layers, so it is looked
# through; an operation directly under it is `other`
_LOOKED_THROUGH = ("StackedHourglass",)
# the decoder family (models/decoder.py): its `jax.named_scope`s, the
# innermost of which is the layer, under the phase that ran it
_DECODER_SCOPES = ("embed", "attn_full", "indexer", "attn_window",
                   "attn_linear", "router", "experts", "shared_expert",
                   "dense_ffn", "lm_head")
_DECODER_PHASES = ("prefill", "decode")
# parts of an attention layer, named under the layer that holds them
# (`decode/attn_full/kv_write`), or of the router (`decode/router/group_limit`)
_DECODER_PARTS = ("rope", "kv_write", "gate", "absorb_q", "cache_write",
                  "absorb_o", "conv", "scan", "state", "out_norm",
                  "group_limit")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_REFERENCE = re.compile(r"%([\w.\-]+)")
# the opcode is the first bare word followed by "(" after the result type
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w\-]*)\(")
# computations whose instructions never run as device operations of their
# own: a fusion's body, a reduction's combiner
_INLINED = re.compile(
    r"(?:to_apply|select|scatter|comparator)=%?([\w.\-]+)")
_CALLED = re.compile(r"called_computations=\{([^}]*)\}")  # a custom call's
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAPPER = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
_TREE_KEY = re.compile(r"\[\\?'([A-Za-z_]\w*)\\?'\]")


def _split_path(op_name: str) -> List[str]:
    """'jit(step)/transpose(jvp(M))/Head_0/add' -> its elements; a '/'
    inside parentheses does not split."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(0, depth - 1)
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def layer_of(op_name: str) -> str:
    """The layer a scope path belongs to (module docstring)."""
    backward = False
    layer = None
    elements = _split_path(op_name)
    inner = [e for e in elements if e in _DECODER_SCOPES]
    if inner:
        phase = [e for e in elements if e in _DECODER_PHASES]
        part = [e for e in elements if e in _DECODER_PARTS]
        return "/".join(phase[:1] + inner[-1:] + part[-1:])
    for element in elements:
        # peel the transforms jax wrote around the scope: jvp(...),
        # transpose(jvp(...)), vmap(...), checkpoint(...)
        jitted = False
        while True:
            m = _WRAPPER.match(element)
            if not m:
                break
            backward = backward or m.group(1) == "transpose"
            jitted = jitted or m.group(1) in ("jit", "pjit")
            element = m.group(2)
        if (layer is not None or jitted
                or element.startswith(_LOOKED_THROUGH)):
            continue
        for scope, name in _LAYER_OF_SCOPE:
            if element == scope or element.startswith(scope + "_"):
                layer = name
                break
        else:
            # the first element that is neither the jit, the top module
            # nor a layer: an operation outside every layer
            layer = OTHER
    if layer is None:
        layer = OTHER
    return layer + "/bwd" if backward and layer != OTHER else layer


def _argument_layer(op_name: str) -> Optional[str]:
    """The layer of a program argument, whose op_name is its path in the
    arguments' tree (`state.params['Hourglass_0'][...]['kernel']`): the
    optimizer's state is the optimizer's, a weight its module's."""
    if "opt_state" in op_name:
        return "optimizer"
    for key in _TREE_KEY.findall(op_name):
        layer = layer_of(key)
        if layer != OTHER:
            return layer
    return None


def _agreed(layers) -> Optional[str]:
    found = {x for x in layers if x is not None}
    return found.pop() if len(found) == 1 else None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """`{instruction name: layer}` for every instruction of
    `compiled.as_text()` that can run as a device operation of its own
    (fusion bodies and reduction combiners are left out; a fusion is its
    own instruction, with its root's metadata)."""
    computation = None
    where: Dict[str, str] = {}      # instruction -> its computation
    op_names: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    called: Dict[str, str] = {}     # fusion instruction -> its body
    roots: Dict[str, str] = {}      # computation -> its ROOT instruction
    inlined, parameters = set(), set()
    for line in hlo_text.splitlines():
        if computation is None:
            m = _COMPUTATION.match(line)
            if m:
                computation = m.group(1)
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        where[name] = computation
        if line.lstrip().startswith("ROOT"):
            roots[computation] = name
        found = _OP_NAME.search(rest)
        op_names[name] = found.group(1) if found else None
        body = rest.split(", metadata=")[0]
        operands[name] = _REFERENCE.findall(body)
        inlined.update(_INLINED.findall(body))
        for group in _CALLED.findall(body):
            inlined.update(_REFERENCE.findall(group))
        opcode = _OPCODE.search(body)
        calls = _CALLS.search(body)
        if opcode and opcode.group(1) == "parameter":
            parameters.add(name)  # an argument: names a buffer, runs nothing
        if calls and opcode and opcode.group(1) == "fusion":
            inlined.add(calls.group(1))
            called[name] = calls.group(1)
    # a fusion without metadata of its own takes its root's
    for name, body in called.items():
        if op_names[name] is None and body in roots:
            op_names[name] = op_names.get(roots[body])
    # in the text's order, which within a computation is operands first
    nodes = [n for n, c in where.items() if c not in inlined]
    known = set(nodes)
    users: Dict[str, List[str]] = {n: [] for n in nodes}
    for name in nodes:
        operands[name] = [o for o in operands[name] if o in known]
        for o in operands[name]:
            users[o].append(name)
    layers: Dict[str, Optional[str]] = {}
    for name in nodes:
        op_name = op_names[name]
        if name in parameters:
            layers[name] = op_name and _argument_layer(op_name)
        else:
            layers[name] = layer_of(op_name) if op_name is not None else None
    # an unnamed instruction takes the layer its users agree on (users
    # last to first, so a copy-start sees its copy-done already settled),
    # else the layer its operands agree on (first to last): the copy of a
    # weight that forward, backward and optimizer all read is its module's
    for order, neighbours in ((reversed(nodes), users), (nodes, operands)):
        for name in order:
            if layers[name] is None and name not in parameters:
                layers[name] = _agreed(layers[x] for x in neighbours[name])
    # an argument names a buffer and runs nothing: not in the map
    return {n: layers[n] or UNATTRIBUTED for n in nodes
            if n not in parameters}


def layer_shares(scopes: Dict[str, str]) -> Dict[str, float]:
    """Share of the map's instructions in each layer (a count, for tests
    and for a first look; time by layer needs a trace)."""
    total = float(len(scopes)) or 1.0
    out: Dict[str, float] = {}
    for layer in scopes.values():
        out[layer] = out.get(layer, 0.0) + 1.0 / total
    return out
