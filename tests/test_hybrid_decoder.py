"""The linear-hybrid family (models/decoder.py `hybrid_moe_decoder` over the
Ling-3.0-flash keys: KDA linear attention on most layers, latent attention
whose query has no latent on every third here, group-limited routing with a
selection bias) at toy sizes on the CPU, held to the plain reference
(benchmark/reference/hybrid_moe_decoder.py); the chunked delta rule against
its recurrence; `kda_step` under the Pallas interpreter against its XLA twin
and the reference's one step. The toy sizes are the benchmark
configuration's own `toy` block: 4 layers (KDA dense, KDA sparse, MLA
sparse, KDA sparse), 4 heads of 16, 16 experts in 4 groups of which 2 are
kept, 4 shares, chunks of 8. (The reference repository has no language
model: no analogue.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import bench_toy  # noqa: E402

from benchmark import hybrid_check  # noqa: E402
from benchmark.reference import hybrid_moe_decoder as ref  # noqa: E402
from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.ops import linear_attention as la  # noqa
from real_time_helmet_detection_tpu.ops.pallas import kda  # noqa: E402
from real_time_helmet_detection_tpu.parallel.experts import (  # noqa: E402
    ExpertShare)
from real_time_helmet_detection_tpu.predict import (  # noqa: E402
    Generation, generation_counters, make_generate_fn)

SEED = 2 ** 31 + 29
P_MAX, NEW = 16, 13          # 12 decode steps through the states
LENGTHS = (16, 9, 12, 3)     # two chunks, mid-chunk, mid-chunk, one chunk
# what the float32 program is held to here: it reads about 1e-6 on the
# logit gaps and 0 on the rest, the least of the faults below 6e-3
F32_LIMITS = {"prefill_logit_gap": 1e-3, "decode_logit_gap": 1e-3,
              "token_gap_p99": 0.05, "expert_pairs_gap": 0.02,
              "group_hits_gap": 0.02}


@pytest.fixture(scope="module")
def fields():
    return bench_toy.toy_fields("ling3-flash-ep8-l8")


def _config(fields):
    f = dict(fields)
    return Config(family=f.pop("family"), decoder=f)


def _payload(vocab, lengths=LENGTHS, p_max=P_MAX):
    rng = np.random.default_rng(0)
    rows = np.zeros((len(lengths), p_max + 1), np.int32)
    rows[:, 0] = lengths
    for row in rows:
        row[1:1 + row[0]] = rng.integers(0, vocab, row[0])
    return rows


def _tree(fields):
    """The seed's draw with W_f eight times as large (exact in bfloat16): at
    64 wide sigma 0.02 gives W_f x of 0.16 and nearly every channel's decay
    rounds to none; at 2,560 wide the same sigma gives W_f x of order one,
    and the decays spread."""
    tree = ref.program_tree(fields, SEED)
    for layer in tree["params"].values():
        if isinstance(layer, dict) and "w_f" in layer.get("attn", {}):
            layer["attn"]["w_f"] = layer["attn"]["w_f"] * 8
    return tree


def _held(fields):
    return ref.Held(fields, ref.flatten_tree(_tree(fields)["params"]))


def _wants(fields, rows, served, quant="f32"):
    return hybrid_check.reference_answers(fields, SEED, list(rows), served,
                                          quant, _held(fields))


def _generate(fields, dtype=jnp.float32, faults=frozenset(), rows=None):
    cfg = _config(fields)
    model = build_model(cfg, dtype).clone(faults=frozenset(faults))
    tree = jax.tree.map(lambda a: a.astype(dtype), _tree(fields))
    rows = _payload(fields["vocab_size"]) if rows is None else rows
    out = jax.device_get(make_generate_fn(model, cfg, NEW)(
        tree, jnp.asarray(rows)))
    return rows, [Generation(*(leaf[i] for leaf in out))
                  for i in range(len(rows))]


@pytest.fixture(scope="module")
def exact(fields):
    """The program in float32 and the reference over what it served."""
    rows, served = _generate(fields)
    return rows, served, _wants(fields, rows, served)


def _shapes(fields):
    model = build_model(_config(fields))
    return model, jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))


def test_the_program_asks_for_exactly_the_references_parameters(fields):
    model, shapes = _shapes(fields)
    assert model.spec.family == dec.HYBRID_FAMILY
    ref.check_tree(shapes, ref.param_spec(fields))
    kinds = model.spec.kinds
    assert kinds == (dec.LINEAR, dec.LINEAR, dec.FULL, dec.LINEAR)
    # the attention by the layer's kind, and the cache entry with it
    _, cache = jax.eval_shape(
        lambda v: model.apply(v, jnp.zeros((2, 8), jnp.int32),
                              jnp.full((2,), 8, jnp.int32), 4,
                              method="prefill"), shapes)
    for kind, entry in zip(kinds, cache["layers"]):
        if kind == dec.LINEAR:
            assert entry["state"].shape == (2, 4, 16, 16)
            assert entry["state"].dtype == jnp.float32
            assert entry["conv"].shape == (2, 3, 3 * 64)
        else:
            assert set(entry) == {"c_kv", "k_r"}
            assert entry["c_kv"].shape == (2, 12, 16)


def test_the_published_spec_reads_the_sources_own_keys():
    with open(os.path.join(bench_toy.REPO, "benchmark", "configs",
                           "ling3-flash-ep8-l8.json")) as f:
        published = json.load(f)["fields"]
    f = dict(published)
    spec = dec.DecoderSpec.from_mapping(f, f.pop("family"))
    assert spec.kinds == (dec.LINEAR,) * 5 + (dec.FULL,) + (dec.LINEAR,) * 2
    assert spec.dense_layers == 2 and spec.linear_layers == 7
    a, lin = spec.full, spec.linear
    assert (a.heads, a.q_rank, a.kv_rank, a.nope, a.rope, a.v) == (
        32, 0, 512, 128, 64, 128)
    assert a.gate and not a.rescale and a.inv_freq is None
    assert abs(a.scale - 192 ** -0.5) < 1e-12 and a.theta == 6e6
    assert lin == dec.LinearSizes(32, 128, 4, -5.0)
    assert spec.share == ExpertShare(8, 0, 512, 8) and spec.share.held == 64
    assert (spec.n_group, spec.topk_group, spec.per_token) == (8, 4, 8)
    assert spec.select_bias and spec.routed_scale == 2.5
    assert spec.shared_width == 768 and spec.dense_width == 6144
    assert spec.linear_chunk == la.CHUNK


@pytest.mark.parametrize("key,value", [
    ("expert_swiglu_limit_list", [0, 0, 4]),
    ("share_expert_swiglu_limit_list", [5]),
    ("use_kda_lora", True), ("kda_safe_gate", False),
    ("value_norm", True), ("group_norm_size", 2)])
def test_a_setting_the_program_does_not_compute_is_refused(fields, key,
                                                           value):
    f = dict(fields, **{key: value})
    f.pop("family")
    with pytest.raises(ValueError, match=key):
        dec.DecoderSpec.from_mapping(f, dec.HYBRID_FAMILY)
    # a clamp in a layer this chip does not hold is no business of its own
    if key.endswith("swiglu_limit_list"):
        f[key] = [0] * fields["num_hidden_layers"] + [7]
        dec.DecoderSpec.from_mapping(f, dec.HYBRID_FAMILY)


@pytest.mark.parametrize("row", range(len(LENGTHS)))
def test_prefill_and_every_decode_step_match_the_full_forward_in_float32(
        exact, row):
    """Rows of different lengths in one batch; float32 on both sides: the
    chunked prefill (a row ending at a chunk's end, mid-chunk, in its first
    chunk), the states and tails handed to decode, each step's kernel twin
    and the latent cache hold nothing back. Every step's token is the
    reference's argmax at that position."""
    _, served, wants = exact
    s, w, n = served[row], wants[row], LENGTHS[row]
    assert np.allclose(s.logits_first, w["logits"][0], atol=2e-5)
    assert np.allclose(s.logits_last, w["logits"][-1], atol=2e-5)
    assert np.array_equal(s.tokens, np.argmax(w["logits"], -1))
    assert np.array_equal(s.expert_tokens, w["expert_pairs"])
    assert int(s.group_hits) == w["group_hits"]
    # 3 KDA layers: one state read-modify-write each a step, chunks of 8,
    # the four rows one prefill pass that runs to its longest row; the row
    # itself fills its own chunks alone
    assert int(s.linear_state_steps) == 3 * (NEW - 1)
    assert int(s.linear_chunks_run) == 3 * -(-max(LENGTHS) // 8)
    assert int(s.linear_chunks_live) == 3 * -(-n // 8)
    assert int(s.linear_chunks_total) == 3 * 2
    # one full layer: q blocks of it alone, slots and keys of its cache
    assert int(s.q_blocks_total) == 1 * 2
    assert s.cache_slots_read.tolist() == [(NEW - 1) * (P_MAX + NEW - 1), 0]


@pytest.mark.parametrize("length", [1, 5, 8, 13, 16, 23])
def test_the_chunked_prefill_is_the_recurrence(length):
    """Chunks of 8 over 24 slots, a pass of three rows: the longest of
    `length` (one token, ending mid-chunk, at a chunk's end), the others
    shorter, so that they walk chunks past their own length; every decay
    down to -5 a token (exp(+G) would overflow in a chunk of 18); padding
    writes nothing; the chunks past the longest row are not run."""
    rng = np.random.default_rng(length)
    rows, total, heads, d = 3, 24, 3, 16
    lengths = np.array([length, max(length - 7, 1), max(length // 2, 1)])
    q = la.l2_normalize(jnp.asarray(rng.standard_normal(
        (rows, total, heads, d)), jnp.float32)) * d ** -0.5
    k = la.l2_normalize(jnp.asarray(rng.standard_normal(
        (rows, total, heads, d)), jnp.float32))
    v = jnp.asarray(rng.standard_normal((rows, total, heads, d)), jnp.float32)
    g = jnp.asarray(-5 * rng.uniform(0, 1, (rows, total, heads, d)),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (rows, total, heads)), jnp.float32)
    real = np.arange(total)[None, :] < lengths[:, None]
    g, beta = g * real[..., None, None], beta * real[..., None]
    o, state, ran = la.chunked_prefill(q, k, v, g, beta,
                                       jnp.asarray(lengths, jnp.int32),
                                       chunk=8)
    for row, n in enumerate(lengths):
        s = np.zeros((heads, d, d))
        for t in range(n):
            s = s * np.exp(np.asarray(g[row, t], np.float64))[..., None]
            kt, vt, bt = (np.asarray(a[row, t], np.float64)
                          for a in (k, v, beta))
            s = s + bt[:, None, None] * kt[..., None] * (
                vt - np.einsum("hkv,hk->hv", s, kt))[:, None, :]
            assert np.allclose(o[row, t], np.einsum(
                "hkv,hk->hv", s, np.asarray(q[row, t])), atol=2e-5), (row, t)
        assert np.allclose(state[row], s, atol=2e-5), row
    assert np.isfinite(np.asarray(o)).all()
    assert not np.asarray(o)[:, -(-length // 8) * 8:].any()
    assert ran.tolist() == [-(-length // 8)] * rows


def test_kda_step_is_its_xla_twin_and_the_references_step():
    """Under the Pallas interpreter at 2 rows x 16 heads (two grid steps of
    8 heads a row) of 16 x 16 states, against the XLA twin and against the
    reference's form, S <- (I - beta k k^T) Diag(exp g) S + beta k v^T."""
    rng = np.random.default_rng(7)
    b, h, d = 2, 16, 16
    state = jnp.asarray(rng.standard_normal((b, h, d, d)), jnp.float32)
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
               for _ in range(3))
    k = la.l2_normalize(k)
    g = jnp.asarray(-5 * rng.uniform(0, 1, (b, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (b, h)), jnp.float32)
    o, after = la.state_step(state, q, k, v, g, beta, interpret=True)
    o_x, after_x = la.xla_state_step(state, q, k, v, g, beta)
    assert np.allclose(o, o_x, atol=1e-5) and np.allclose(after, after_x,
                                                          atol=1e-5)
    eye = np.eye(d)
    kk = np.einsum("bhk,bhl->bhkl", k, k)
    want = np.einsum("bhkl,bhlv->bhkv", eye - beta[..., None, None] * kk,
                     np.exp(np.asarray(g))[..., None] * np.asarray(state)) \
        + beta[..., None, None] * np.einsum("bhk,bhv->bhkv", k, v)
    assert np.allclose(after, want, atol=1e-5)
    assert np.allclose(o, np.einsum("bhkv,bhk->bhv", want, q), atol=1e-5)
    assert kda.head_block(h) == 8 and kda.head_block(4) == 4


def test_a_padded_row_leaves_the_state_tail_and_logits_of_the_row_unpadded(
        fields):
    """The same 11 tokens as a row of 11 slots and as a row padded to 24:
    the states, the tails and the first and last logits agree."""
    model = build_model(_config(fields), jnp.float32)
    tree = jax.tree.map(lambda a: a.astype(jnp.float32), _tree(fields))
    ids = np.random.default_rng(3).integers(0, fields["vocab_size"], 11)
    outs = []
    for slots in (11, 24):
        tokens = np.zeros((1, slots), np.int32)
        tokens[0, :11] = ids
        outs.append(model.apply(tree, jnp.asarray(tokens),
                                jnp.asarray([11], jnp.int32), 1,
                                method="prefill"))
    (logits_a, cache_a), (logits_b, cache_b) = outs
    assert np.allclose(logits_a, logits_b, atol=2e-5)
    for kind, a, b in zip(model.spec.kinds, cache_a["layers"],
                          cache_b["layers"]):
        if kind == dec.LINEAR:
            assert np.allclose(a["state"], b["state"], atol=2e-5)
            assert np.allclose(a["conv"], b["conv"], atol=1e-6)
    step = lambda c: model.apply(  # noqa: E731
        tree, jnp.asarray([5], jnp.int32), jnp.asarray([11], jnp.int32), c,
        method="step")
    assert np.allclose(step(cache_a)[0], step(cache_b)[0], atol=2e-5)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole_layer(
        fields):
    """The program's expert layer as eight shares (16 experts in 8 groups of
    which 4 are kept, a group a share, the selection bias entering the
    groups' scores): the routed parts summed, the shared expert counted
    once, equal the uncut reference's layer (one chip holding all 16)."""
    wide = dict(fields, n_group=8, topk_group=4)
    hn = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, fields["hidden_size"])), jnp.float32)
    whole_cfg = dict(wide, ep_size=1, num_experts=16)
    whole = ref.Reference(whole_cfg, ref.Drawn(whole_cfg, SEED))
    routed_w, shared_w, chosen, kept = whole.experts(1, hn)
    assert (np.asarray(kept).sum(-1) == 4).all()
    total, holders = 0.0, np.zeros((24,), np.int32)
    for rank in range(8):
        cut = dict(wide, ep_size=8, ep_rank=rank, num_experts=2)
        spec = dec.DecoderSpec.from_mapping(_config(cut).decoder,
                                            dec.HYBRID_FAMILY)
        assert spec.share == ExpertShare(8, rank, 16, 8)
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref.program_tree(
            cut, SEED)["params"]["layer_1"]["moe"])
        y, local, hit = dec.expert_layer_groups(p, spec, hn,
                                                jnp.ones((24,), bool))
        assert np.array_equal(hit, np.asarray(kept)[:, rank])
        holders += np.asarray(local < 2).any(-1)
        total = total + (y - shared_w)
    assert holders.max() <= 4 and holders.min() >= 1
    assert np.allclose(total + shared_w, routed_w + shared_w, atol=2e-5)


@pytest.mark.parametrize("fault", hybrid_check.FAULTS)
def test_a_planted_fault_fails_the_check_in_float32(fields, exact, fault):
    """Each fault the check knows by name, in the float32 program, fails at
    least one of the check's numbers that the sound float32 program passes
    with a thousandfold room."""
    rows, served, wants = exact
    assert not hybrid_check.judged(hybrid_check.numbers(
        list(rows), served, wants), F32_LIMITS)["over"]
    rows, served = _generate(fields, faults={fault})
    got = hybrid_check.judged(hybrid_check.numbers(
        list(rows), served, _wants(fields, rows, served)), F32_LIMITS)
    assert got["over"], got


def test_the_fp8_control_fails_the_check(fields, exact):
    rows, served, wants = exact
    low = _wants(fields, rows, served, "fp8")
    got = hybrid_check.judged(hybrid_check.numbers(
        list(rows), hybrid_check.control_answers(low), wants), F32_LIMITS)
    assert got["over"], got


def test_the_engine_feeds_the_linear_counters(fields):
    from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
    from real_time_helmet_detection_tpu.serving import ServingEngine
    cfg = _config(fields)
    generate = make_generate_fn(build_model(cfg), cfg, 3)
    rows = _payload(fields["vocab_size"])
    registry = MetricsRegistry()
    with ServingEngine(generate, ref.program_tree(fields, SEED),
                       (P_MAX + 1,), np.int32, buckets=(4,),
                       metrics=registry,
                       row_counters=generation_counters(P_MAX)) as engine:
        for f in [engine.submit(r) for r in rows[:3]]:
            f.result(timeout=300)
    count = lambda n: registry.counter(n).value  # noqa: E731
    assert count("gen.requests") == 3
    assert count("gen.linear_state_steps") == 3 * 3 * 2
    # one pass of the bucket's four rows: to its longest row, 16 tokens;
    # the three real rows' own tokens (16, 9, 12) fill 2 chunks each
    assert count("gen.linear_chunks_run") == 3 * 3 * 2
    assert count("gen.linear_chunks_live") == 3 * 3 * 2
    assert count("gen.linear_chunks_total") == 3 * 3 * 2
    assert count("gen.cache_slots.full") == 3 * 2 * (P_MAX + 2)
    assert 0 < count("gen.group_hits") < count("gen.group_slots")


@pytest.mark.parametrize("config", ["axk1-ep8-l5", "dots3-note-prev-ep8-l5",
                                    "laguna-xs2-l5"])
def test_the_families_with_one_attention_keep_it_on_every_layer(config):
    """The attention is looked up by the layer's kind: for the latent and the
    grouped-query families every kind names the family's one attention, no
    layer carries a state, and the linear counts stay zero."""
    fields = bench_toy.toy_fields(config)
    model, shapes = _shapes(fields)
    spec = model.spec
    assert len({dec.attention_of(spec, kind) for kind in spec.kinds}) == 1
    assert spec.linear is None and spec.linear_layers == 0
    cfg = _config(fields)
    if config == "laguna-xs2-l5":
        from benchmark.reference import gqa_moe_decoder as own
    elif config == "axk1-ep8-l5":
        from benchmark.reference import mla_moe_decoder as own
    else:
        from benchmark.reference import latent_moe_decoder as own
    out = jax.device_get(make_generate_fn(model, cfg, 3)(
        own.program_tree(fields, SEED),
        jnp.asarray(_payload(fields["vocab_size"]))))
    assert not out.linear_state_steps.any()
    assert not out.linear_chunks_run.any()
    assert not out.linear_chunks_live.any()
    assert not out.linear_chunks_total.any()
    assert (out.q_blocks_total == spec.layers * -(-P_MAX // spec.q_block)
            ).all()


@pytest.mark.parametrize("op_name,layer", [
    ("jit(generate)/prefill/attn_linear/while/body/scan/dot_general",
     "prefill/attn_linear/scan"),
    ("jit(generate)/while/body/decode/attn_linear/state/kda_step",
     "decode/attn_linear/state"),
    ("jit(generate)/while/body/decode/attn_linear/conv/mul",
     "decode/attn_linear/conv"),
    ("jit(generate)/prefill/attn_linear/while/body/out_norm/rsqrt",
     "prefill/attn_linear/out_norm"),
])
def test_hlo_scopes_names_the_linear_attention_parts(op_name, layer):
    from real_time_helmet_detection_tpu.obs.hlo_scopes import layer_of
    assert layer_of(op_name) == layer


def test_the_generate_program_carries_the_linear_scopes(fields):
    """The compiled program's own scope map (what scripts/layer_trace.py
    reads a trace by) names the linear attention and its parts in both
    phases, beside the latent layer's scopes."""
    from real_time_helmet_detection_tpu.obs.hlo_scopes import scope_map
    model, shapes = _shapes(fields)
    layers = set(scope_map(make_generate_fn(model, _config(fields), 3).lower(
        shapes, jax.ShapeDtypeStruct((4, P_MAX + 1), jnp.int32))
        .compile().as_text()).values())
    assert {"prefill/attn_linear/scan", "decode/attn_linear/state",
            "prefill/attn_full", "decode/attn_full"} <= layers, layers
    for phase in ("prefill", "decode"):
        assert {"%s/attn_linear/%s" % (phase, part)
                for part in ("conv", "out_norm")} & layers, layers
