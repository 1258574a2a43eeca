"""The grouped-query decoder family (models/decoder.py `gqa_moe_decoder`: the
one body behind `GroupedAttention`, ops/attention.py's grouped forms and
rotary table, the bounded expert passes, predict.py's generate program and
its new row counts) at toy sizes on the CPU, held to the plain reference
(benchmark/reference/gqa_moe_decoder.py). The toy sizes are the benchmark
configuration's own `toy` block: 3 layers (full.dense, sliding, full),
window 5, 4 and 6 query heads over 2 k/v heads, 8 experts top-2. (The
reference repository has no language model: no analogue.)"""

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

from benchmark import gqa_check  # noqa: E402
from benchmark.reference import gqa_moe_decoder as ref  # noqa: E402
from real_time_helmet_detection_tpu.config import (  # noqa: E402
    MODEL_FAMILIES, Config)
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.ops import attention as att  # noqa: E402
from real_time_helmet_detection_tpu.ops import moe  # noqa: E402
from real_time_helmet_detection_tpu.parallel.experts import (  # noqa: E402
    ExpertShare)
from real_time_helmet_detection_tpu.predict import (  # noqa: E402
    Generation, generation_counters, make_generate_fn)

SEED = 2 ** 31 + 29
P_MAX, NEW = 16, 13          # 12 decode steps: every ring of 5 wraps
LENGTHS = (16, 9, 12, 3)     # across the toy window (5)
PUBLISHED = os.path.join(bench_toy.REPO, "benchmark", "configs",
                         "laguna-xs2-l5.json")


@pytest.fixture(scope="module")
def fields():
    return bench_toy.toy_fields("laguna-xs2-l5")


def _config(fields):
    f = dict(fields)
    return Config(family=f.pop("family"), decoder=f)


def _payload(vocab):
    rng = np.random.default_rng(0)
    rows = np.zeros((len(LENGTHS), P_MAX + 1), np.int32)
    rows[:, 0] = LENGTHS
    for row in rows:
        row[1:1 + row[0]] = rng.integers(0, vocab, row[0])
    return rows


def _tree(fields):
    """The seed's draw with W_q and W_k eight times as large (exact in
    bfloat16). At 64 wide the drawn sigma 0.02 gives scores of 0.03: every
    softmax is flat and no rotary or window fault shows; at 2,048 wide the
    same sigma gives 0.8. Eight times each is 64 times the scores."""
    tree = ref.program_tree(fields, SEED)
    for layer in tree["params"].values():
        if isinstance(layer, dict) and "attn" in layer:
            layer["attn"] = dict(layer["attn"], w_q=layer["attn"]["w_q"] * 8,
                                 w_k=layer["attn"]["w_k"] * 8)
    return tree


def _wants(fields, rows, served, quant="f32"):
    held = ref.Held(fields, ref.flatten_tree(_tree(fields)["params"]))
    return gqa_check.reference_answers(fields, SEED, list(rows), served,
                                       quant, held)


def _generate(fields, dtype=None, faults=frozenset()):
    cfg = _config(fields)
    model = build_model(cfg, dtype).clone(faults=frozenset(faults))
    tree = _tree(fields)
    if dtype is not None:
        tree = jax.tree.map(lambda a: a.astype(dtype), tree)
    rows = _payload(fields["vocab_size"])
    out = jax.device_get(make_generate_fn(model, cfg, NEW)(
        tree, jnp.asarray(rows)))
    return rows, [Generation(*(leaf[i] for leaf in out))
                  for i in range(len(rows))]


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(bench_toy.REPO, "benchmark", "workloads",
                           "gen-1k-256.json")) as f:
        return json.load(f)["toy"]["limits"]


@pytest.fixture(scope="module")
def sound(fields):
    """The sound program's answers (bfloat16) and the reference's."""
    rows, served = _generate(fields)
    return rows, served, _wants(fields, rows, served)


def test_the_program_asks_for_exactly_the_references_parameters(fields):
    model = build_model(_config(fields))
    assert isinstance(model, dec.MoEDecoder)
    assert model.spec.family == dec.GQA_FAMILY
    assert model.spec.heads == (4, 6, 4)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ref.check_tree(shapes, ref.param_spec(fields))
    with pytest.raises(ValueError, match="disagree"):
        ref.check_tree(shapes, ref.param_spec(dict(
            fields, num_attention_heads_per_layer=[4, 4, 4])))


def _visits(wants, lengths):
    """Expert visits of the batch from the reference's own routing: for the
    prefill and for each step, by expert layer, the experts some row chose."""
    total = 0
    for layer in range(wants[0]["chosen"].shape[0]):
        prefill = np.zeros(wants[0]["chosen"].shape[2], bool)
        for w, n in zip(wants, lengths):
            prefill |= w["chosen"][layer, :n].any(axis=0)
        total += int(prefill.sum())
        for step in range(NEW - 1):
            hit = np.zeros_like(prefill)
            for w, n in zip(wants, lengths):
                hit |= w["chosen"][layer, n + step]
            total += int(hit.sum())
    return total


def test_prefill_and_twelve_steps_match_the_full_forward_in_float32(fields):
    """Rows of different lengths in one batch, every row decoding past the
    toy window so that every ring wraps; float32 on both sides, so the
    agreement is to rounding (2e-5: sums of a few hundred float32 products
    in another order): the k/v caches, the rings, the per-layer head counts,
    the two rotary tables and the per-row positions hold nothing back."""
    rows, served = _generate(fields, jnp.float32)
    wants = _wants(fields, rows, served)
    for s, w, n in zip(served, wants, LENGTHS):
        assert np.allclose(s.logits_first, w["logits"][0], atol=2e-5)
        assert np.allclose(s.logits_last, w["logits"][-1], atol=2e-5)
        assert np.array_equal(s.tokens, np.argmax(w["logits"], -1))
        assert np.array_equal(s.expert_tokens, w["expert_pairs"])
        assert int(s.keys_kept) == 0          # no indexer in this family
        # two full layers: the prefill's causal keys and each step's
        last = n + NEW - 1
        assert int(s.keys_causal) == 2 * (last * (last + 1) // 2)
        steps = NEW - 1
        assert s.cache_slots_read.tolist() == [
            2 * steps * (P_MAX + steps), steps * 5]
        assert s.cache_keys_real.tolist() == [
            2 * sum(n + i + 1 for i in range(steps)),
            sum(min(n + i + 1, 5) for i in range(steps))]
        assert int(s.q_blocks_run) == 3 * -(-n // 8)
    assert sum(int(s.expert_visits) for s in served) == _visits(
        wants, LENGTHS)


def test_the_bfloat16_program_is_within_the_cells_limits(sound, limits):
    rows, served, wants = sound
    numbers = gqa_check.numbers(list(rows), served, wants)
    for name, limit in limits.items():
        assert numbers[name] <= limit, (name, numbers)


@pytest.mark.parametrize("fault,caught_by", [
    ("kv_group_misassigned", "prefill_logit_gap"),
    ("full_rope_whole_head", "prefill_logit_gap"),
    ("yarn_dropped", "prefill_logit_gap"),
    ("window_off_by_one", "prefill_logit_gap"),
    ("stale_ring_row", "decode_logit_gap"),
    ("no_gate", "prefill_logit_gap"),
    ("no_routed_scale", "prefill_logit_gap"),
    ("no_shared", "prefill_logit_gap"),
])
def test_a_planted_fault_fails_its_number(fields, limits, fault, caught_by):
    assert fault in gqa_check.FAULTS
    rows, served = _generate(fields, faults={fault})
    numbers = gqa_check.numbers(list(rows), served,
                                _wants(fields, rows, served))
    assert numbers[caught_by] > limits[caught_by], numbers


def test_the_fp8_control_fails_a_limit(fields, sound, limits):
    rows, served, wants = sound
    low = _wants(fields, rows, served, "fp8")
    numbers = gqa_check.numbers(
        list(rows), gqa_check.control_answers(low), wants)
    assert any(numbers[k] > v for k, v in limits.items()), numbers


@pytest.mark.parametrize("table", [dec.rotary_table, ref.rotary_table])
def test_the_yarn_table_at_the_published_sizes(table):
    """ISSUE 33's numbers: low 5, high 16 (f_5 is still the plain
    frequency, f_16 the plain one over 64), m = 0.1 ln 64 + 1; program and
    reference each from its own code."""
    with open(PUBLISHED) as f:
        rope = json.load(f)["fields"]["rope_parameters"]
    r, f, m = table(rope["full_attention"], 128)
    assert r == 64 and len(f) == 32
    assert f[0] == 1.0
    assert abs(f[5] / 0.128687 - 1) < 1e-5
    assert abs(f[5] / 500000 ** (-10 / 64) - 1) < 1e-12    # ramp 0 at low
    assert abs(f[6] / 500000 ** (-12 / 64) - 1) > 0.05     # and not past it
    assert abs(f[16] / 2.20971e-5 - 1) < 1e-5
    assert abs(f[16] * 64 / 500000 ** (-0.5) - 1) < 1e-12   # ramp 1 at high
    assert abs(f[15] * 64 / 500000 ** (-30 / 64) - 1) > 0.05
    assert abs(f[31] / 4.70915e-8 - 1) < 1e-5
    assert m == 1.4158883083359672
    r, f, m = table(rope["sliding_attention"], 128)
    assert r == 128 and m == 1.0
    assert abs(f[1] / 10000 ** (-2 / 128) - 1) < 1e-12


def test_the_published_spec_reads_the_sources_own_keys():
    with open(PUBLISHED) as f:
        published = json.load(f)["fields"]
    f = dict(published)
    spec = dec.DecoderSpec.from_mapping(f, f.pop("family"))
    assert spec.heads == (48, 64, 64, 64, 48) and spec.dense_layers == 1
    assert spec.kinds[0] == spec.kinds[4] == dec.FULL
    assert (spec.full.kv_heads, spec.full.head_dim, spec.full.rot) == (
        8, 128, 64)
    assert spec.swa.rot == 128 and spec.window == 512
    assert spec.share == ExpertShare(1, 0, 256) and spec.per_token == 8
    assert spec.routed_scale == 2.5 and spec.shared_width == 512
    for bad in (dict(published, gating=False),
                dict(published, mlp_layer_types=["sparse", "dense"] * 3),
                dict(published, num_attention_heads_per_layer=[50] * 5)):
        bad.pop("family")
        with pytest.raises(ValueError):
            dec.DecoderSpec.from_mapping(bad, dec.GQA_FAMILY)


@pytest.mark.parametrize("layer", [1, 2])
def test_one_layers_attention_matches_the_reference(fields, layer):
    """Layer 1 (sliding, 6 query heads: 3 a k/v head) and layer 2 (full, 4:
    YaRN on half the head), a sequence longer than the window, float32."""
    spec = dec.DecoderSpec.from_mapping(_config(fields).decoder,
                                        dec.GQA_FAMILY)
    flat = {k: v.astype(jnp.float32) for k, v in ref.flatten_tree(
        ref.program_tree(fields, SEED)["params"]).items()}
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (16, spec.hidden)), jnp.float32)
    want = ref.Reference(fields, ref.Held(fields, flat)).attention(layer, x)
    pre = "layer_%d/attn/" % layer
    p = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
    p["attn_norm"] = flat["layer_%d/attn_norm" % layer]
    got, entry, counts = dec.grouped_prefill_row(
        p, spec.kinds[layer], spec, x, jnp.int32(16), 20)
    assert np.allclose(got, want, atol=2e-5)
    assert int(counts["q_blocks_run"]) == 16 // spec.q_block
    slots = 20 if spec.kinds[layer] == dec.FULL else spec.window
    assert entry["k"].shape == entry["v"].shape == (2, slots, 16)


Q_BLOCK, TOTAL = 8, 32


@pytest.mark.parametrize("length", [1, Q_BLOCK + 1, TOTAL])
@pytest.mark.parametrize("window", [None, 5])
def test_grouped_blockwise_attention_is_the_per_head_form(window, length):
    """q (G, R, T, d) against one k/v a group gives what the per-head form
    gives with the group's k/v copied a head; groups one at a time under
    `lax.map` or all at once; a q block past `length` is zeros."""
    rng = np.random.default_rng(7)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    q, k, v = draw(2, 3, TOTAL, 12), draw(2, TOTAL, 12), draw(2, TOTAL, 12)
    kw = dict(q_block=Q_BLOCK, scale=0.3, length=jnp.int32(length),
              window=window)
    want = att.blockwise_attention(
        q.reshape(6, TOTAL, 12), jnp.repeat(k, 3, axis=0),
        jnp.repeat(v, 3, axis=0), **kw).reshape(2, 3, TOTAL, 12)
    for step in (None, 1):
        got = jax.jit(lambda q, k, v: att.blockwise_attention(
            q, k, v, head_block=step, **kw))(q, k, v)
        assert got.shape == (2, 3, TOTAL, 12)
        assert np.allclose(got, want, atol=2e-5)
    ran = -(-length // Q_BLOCK) * Q_BLOCK
    assert not np.asarray(got)[:, :, ran:].any()


def test_grouped_cache_attention_reads_a_groups_kv_in_place():
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    q, k, v = draw(6, 4, 8), draw(6, 10, 8), draw(6, 10, 8)
    allowed = jnp.asarray(rng.random((6, 10)) < 0.6).at[:, 0].set(True)
    got = att.grouped_cache_attention(q, k, v, allowed, 0.35)
    s = np.einsum("nrd,nsd->nrs", q, k) * 0.35
    s = np.where(np.asarray(allowed)[:, None], s, -np.inf)
    prob = np.exp(s - s.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    assert np.allclose(got, np.einsum("nrs,nsd->nrd", prob, v), atol=2e-5)


def test_rotate_by_is_rotate_at_the_plain_table_and_scales_cos_and_sin():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((7, 3, 16)), jnp.float32)
    pos = jnp.arange(7) * 3
    freq = 100.0 ** (-jnp.arange(8, dtype=jnp.float32) / 8)
    assert np.array_equal(att.rotate_by(x, pos, freq),
                          att.rotate(x, pos, 100.0))
    assert np.allclose(att.rotate_by(x, pos, freq, 1.5),
                       1.5 * att.rotate(x, pos, 100.0), atol=1e-6)
    part = att.rotate_leading_by(x, pos, freq[:4], 1.0, 8)
    assert np.array_equal(part[..., 8:], x[..., 8:])
    assert np.array_equal(part[..., :8],
                          att.rotate_by(x[..., :8], pos, freq[:4]))


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(fields):
    """The program's expert layer as two shares (`ep_size` 2, 4 experts
    each): the routed parts summed, the shared expert counted once, equal
    the uncut reference's layer (this configuration's: one chip holding all
    8 experts)."""
    hn = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, fields["hidden_size"])), jnp.float32)
    whole = ref.Reference(fields, ref.Drawn(fields, SEED))
    routed_w, shared_w, _ = whole.experts(1, hn)
    total = 0.0
    for rank in range(2):
        cut = dict(fields, ep_size=2, ep_rank=rank, num_experts=4)
        spec = dec.DecoderSpec.from_mapping(_config(cut).decoder,
                                            dec.GQA_FAMILY)
        assert spec.share == ExpertShare(2, rank, 8)
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref.program_tree(
            cut, SEED)["params"]["layer_1"]["moe"])
        y, _ = dec.expert_layer(p, spec, hn, jnp.ones((24,), bool))
        routed = y - shared_w
        assert float(jnp.abs(routed).max()) > 0
        total = total + routed
    assert np.allclose(total + shared_w, routed_w + shared_w, atol=2e-5)


def test_the_passes_of_a_whole_layer_are_bounded_and_a_shares_are_not_moved():
    """262,144 pairs: dots3's share of 8 keeps its 65,536 rows a pass (one
    pass in all but pathological routings); a layer that holds all 256
    experts takes 131,072 at a time, so the real pairs of 32 prompts of
    512-1,024 tokens (131,072-262,144) are two passes whatever the lengths;
    a decode step's 256 pairs are one pass of 256."""
    assert moe.capacity_rows(262144, ExpertShare(8, 0, 256)) == 65536
    cap = moe.capacity_rows(262144, ExpertShare(1, 0, 256))
    assert cap == 131072
    assert {-(-32 * n * 8 // cap) for n in (513, 768, 1024)} == {2}
    assert moe.capacity_rows(256, ExpertShare(1, 0, 256)) == 256
    assert moe.capacity_rows(32, ExpertShare(8, 0, 256)) == 32


def test_several_passes_give_what_one_pass_gives(monkeypatch):
    rng = np.random.default_rng(9)
    tokens, k, experts, hidden, width = 300, 2, 8, 32, 16
    hn = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(experts)[:k]
                                for _ in range(tokens)]), jnp.int32)
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    real = jnp.asarray(rng.random(tokens) < 0.9)
    w_gate_up = jnp.asarray(0.1 * rng.standard_normal(
        (experts, hidden, 2 * width)), jnp.float32)
    w_down = jnp.asarray(0.1 * rng.standard_normal(
        (experts, width, hidden)), jnp.float32)
    share = ExpertShare(1, 0, experts)
    one, local = moe.routed_experts(hn, idx, weights, real, w_gate_up, w_down,
                                    share)
    monkeypatch.setattr(moe, "MAX_PASS_ROWS", 512)
    assert moe.capacity_rows(tokens * k, share) == 512    # two passes
    two, local2 = moe.routed_experts(hn, idx, weights, real, w_gate_up,
                                     w_down, share)
    assert np.array_equal(local, local2)
    assert np.allclose(one, two, atol=1e-5)
    assert float(jnp.abs(one).max()) > 0


def test_the_engine_feeds_the_new_row_counters(fields):
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
        answers = [f.result(timeout=300) for f in
                   [engine.submit(r) for r in rows[:3]]]
    count = lambda n: registry.counter(n).value  # noqa: E731
    assert count("gen.requests") == 3
    assert count("gen.keys_kept") == 0
    # one batch: 2 expert layers passed by the prefill and by 2 steps
    assert count("gen.expert_passes") == 2 * 3
    visits = sum(int(a.expert_visits) for a in answers)
    assert 0 < count("gen.expert_visits") == visits <= 2 * 3 * 8
    assert count("gen.cache_slots.full") == 3 * 2 * 2 * (P_MAX + 2)
    assert count("gen.cache_slots.window") == 3 * 2 * 5
    assert count("gen.cache_keys.full") == 2 * sum(
        n + 1 + n + 2 for n in LENGTHS[:3])
    assert 0 < count("gen.cache_keys.window") <= count(
        "gen.cache_slots.window")
    pairs = sum(count("gen.expert_pairs.e%02d" % e) for e in range(8))
    assert pairs == sum(int(a.expert_tokens.sum()) for a in answers) > 0


def test_the_latent_familys_answer_carries_zeros_for_what_it_has_not():
    f = bench_toy.toy_fields("dots3-note-prev-ep8-l5")
    from benchmark.reference import latent_moe_decoder as latent
    cfg = _config(f)
    out = jax.device_get(make_generate_fn(build_model(cfg), cfg, 3)(
        latent.program_tree(f, SEED), jnp.asarray(_payload(f["vocab_size"]))))
    assert not out.cache_slots_read.any() and not out.cache_keys_real.any()
    assert out.keys_kept.all() and out.expert_visits.sum() > 0


def test_the_errors_name_the_families_that_exist():
    with pytest.raises(ValueError) as e:
        build_model(Config(family="gqa_moe_decoder",
                           decoder={"hidden_size": 1}), variant="ghost")
    assert all(name in str(e.value) for name in MODEL_FAMILIES)
    with pytest.raises(ValueError) as e:
        make_generate_fn(None, Config(), 4)
    assert all(name in str(e.value) for name in MODEL_FAMILIES)
    with pytest.raises(ValueError, match="decoder"):
        build_model(Config(family="gqa_moe_decoder"))
    with pytest.raises(ValueError, match="latent_moe_decoder"):
        dec.DecoderSpec.from_mapping({}, "transformer")


@pytest.mark.parametrize("op_name,layer", [
    ("jit(generate)/while/body/decode/attn_full/kv_write/scatter",
     "decode/attn_full/kv_write"),
    ("jit(generate)/prefill/attn_window/while/body/rope/cos",
     "prefill/attn_window/rope"),
    ("jit(generate)/while/body/decode/attn_window/gate/dot_general",
     "decode/attn_window/gate"),
    ("jit(generate)/while/body/decode/attn_window/dot_general",
     "decode/attn_window"),
])
def test_hlo_scopes_names_the_attention_parts(op_name, layer):
    from real_time_helmet_detection_tpu.obs.hlo_scopes import layer_of
    assert layer_of(op_name) == layer


def test_the_generate_program_carries_the_new_scopes(fields):
    """The compiled program's own scope map (what scripts/layer_trace.py
    reads a trace by) holds both attention kinds in both phases with their
    parts, beside the scopes the body had."""
    from real_time_helmet_detection_tpu.obs.hlo_scopes import scope_map
    cfg = _config(fields)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    layers = set(scope_map(make_generate_fn(model, cfg, 3).lower(
        shapes, jax.ShapeDtypeStruct((4, P_MAX + 1), jnp.int32)).compile()
        .as_text()).values())
    # which part keeps an instruction of its own is the compiler's fusion
    # choice (a pad, a rotation of 8 numbers), so: each part somewhere
    for kind in ("attn_full", "attn_window"):
        for part in ("rope", "kv_write", "gate"):
            assert {"%s/%s/%s" % (phase, kind, part)
                    for phase in ("prefill", "decode")} & layers, (part,
                                                                   layers)
    for phase in ("prefill", "decode"):
        assert {phase + "/experts", phase + "/router", phase + "/lm_head",
                phase + "/shared_expert", phase + "/dense_ffn",
                phase + "/attn_full", phase + "/attn_window"} <= layers
