"""The decoder family (models/decoder.py, ops/attention.py, predict.py's
generate program, the engine's payloads and row counters) at toy sizes on the
CPU, held to the plain reference (benchmark/reference/latent_moe_decoder.py).
The toy sizes are the benchmark configuration's own `toy` block. (The
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
from test_bn_tail import count_primitives  # noqa: E402

from benchmark import decoder_check  # noqa: E402
from benchmark.reference import latent_moe_decoder as ref  # noqa: E402
from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.ops import attention as att  # noqa: E402
from real_time_helmet_detection_tpu.predict import (  # noqa: E402
    Generation, generation_counters, make_generate_fn)

SEED = 2 ** 31 + 29
P_MAX, NEW = 16, 13          # 12 decode steps
LENGTHS = (16, 9, 12, 3)     # across the toy window (5) and top-k (8)


@pytest.fixture(scope="module")
def fields():
    return bench_toy.toy_fields("dots3-note-prev-ep8-l5")


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


def _generate(fields, dtype=None, faults=frozenset()):
    cfg = _config(fields)
    model = dec.LatentMoEDecoder(dec.DecoderSpec.from_mapping(cfg.decoder),
                                 dtype or jnp.bfloat16, frozenset(faults))
    tree = ref.program_tree(fields, SEED)
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
                           "gen-8k-64.json")) as f:
        return json.load(f)["toy"]["limits"]


@pytest.fixture(scope="module")
def sound(fields):
    """The sound program's answers (bfloat16) and the reference's."""
    rows, served = _generate(fields)
    return rows, served, decoder_check.reference_answers(
        fields, SEED, list(rows), served)


def test_the_program_asks_for_exactly_the_references_parameters(fields):
    model = build_model(_config(fields))
    assert isinstance(model, dec.LatentMoEDecoder)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    ref.check_tree(shapes, ref.param_spec(fields))
    with pytest.raises(ValueError, match="disagree"):
        ref.check_tree(shapes, ref.param_spec(dict(fields, index_n_heads=3)))


def test_prefill_and_twelve_steps_match_the_full_forward_in_float32(fields):
    """Rows of different lengths in one batch, positions crossing the toy
    window and the toy top-k; float32 on both sides, so the agreement is to
    rounding: the cache, the ring, the absorbed decode and the per-row
    positions hold nothing back."""
    rows, served = _generate(fields, jnp.float32)
    wants = decoder_check.reference_answers(fields, SEED, list(rows), served)
    for s, w in zip(served, wants):
        assert np.allclose(s.logits_first, w["logits"][0], atol=2e-5)
        assert np.allclose(s.logits_last, w["logits"][-1], atol=2e-5)
        assert np.array_equal(s.tokens, np.argmax(w["logits"], -1))
        assert np.array_equal(s.expert_tokens, w["expert_pairs"])
        assert int(s.keys_kept) == int(w["keys_kept"])
        assert int(s.keys_causal) == int(w["keys_causal"])
        assert int(s.keys_kept) < int(s.keys_causal)


def test_the_bfloat16_program_is_within_the_cells_limits(sound, limits):
    rows, served, wants = sound
    numbers = decoder_check.numbers(list(rows), served, wants)
    for name, limit in limits.items():
        assert numbers[name] <= limit, (name, numbers)


@pytest.mark.parametrize("fault,caught_by", [
    ("window_off_by_one", "prefill_logit_gap"),
    ("no_indexer", "keys_kept_gap"),
    ("no_shared", "prefill_logit_gap"),
    ("no_select_bias", "expert_pairs_gap"),
    ("stale_ring_row", "decode_logit_gap"),
])
def test_a_planted_fault_fails_its_number(fields, limits, fault, caught_by):
    rows, served = _generate(fields, faults={fault})
    wants = decoder_check.reference_answers(fields, SEED, list(rows), served)
    numbers = decoder_check.numbers(list(rows), served, wants)
    assert numbers[caught_by] > limits[caught_by], numbers


def test_the_fp8_control_fails_a_limit(fields, sound, limits):
    rows, served, wants = sound
    low = decoder_check.reference_answers(fields, SEED, list(rows), served,
                                          "fp8")
    numbers = decoder_check.numbers(
        list(rows), decoder_check.control_answers(low), wants)
    assert any(numbers[k] > v for k, v in limits.items()), numbers


@pytest.mark.parametrize("layer", [1, 2])
def test_one_layers_attention_matches_the_reference(fields, layer):
    """Layer 1 (full, behind the indexer) and layer 2 (sliding), a sequence
    longer than window and top-k, float32."""
    spec = dec.DecoderSpec.from_mapping(_config(fields).decoder)
    flat = {k: v.astype(jnp.float32) for k, v in ref.flatten_tree(
        ref.program_tree(fields, SEED)["params"]).items()}
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (16, spec.hidden)), jnp.float32)
    want, allowed = ref.Reference(fields, ref.Held(fields, flat)).attention(
        layer, x)
    pre = "layer_%d/attn/" % layer
    p = {k[len(pre):]: v for k, v in flat.items()
         if k.startswith(pre) and "indexer" not in k}
    p["attn_norm"] = flat["layer_%d/attn_norm" % layer]
    p["indexer"] = {k[len(pre) + 8:]: v for k, v in flat.items()
                    if k.startswith(pre + "indexer/")}
    got, entry, kept, ran = dec.attention_prefill_row(
        p, spec.kinds[layer], spec, x, jnp.int32(16), 20)
    assert np.allclose(got, want, atol=2e-5)
    assert int(ran) == 16 // spec.q_block
    if spec.kinds[layer] == dec.FULL:
        assert int(kept) == int(np.sum(allowed)) < 16 * 17 // 2
        assert entry["c_kv"].shape == (20, spec.full.kv_rank)
    else:
        assert entry["c_kv"].shape == (spec.window, spec.swa.kv_rank)


Q_BLOCK, TOTAL = 8, 32


def _blockwise_case(kind):
    """A jitted (length -> array) of one sequence of TOTAL rows in q blocks
    of Q_BLOCK, float32: `blockwise_attention` plain, under a window, behind
    a chosen set (heads two at a time under `lax.map`), or `select_blocks`
    itself (its blocks padded to TOTAL keys and stacked)."""
    rng = np.random.default_rng(7)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    q, k, v = draw(4, TOTAL, 12), draw(4, TOTAL, 12), draw(4, TOTAL, 8)
    qi, ki, w = draw(TOTAL, 2, 16), draw(TOTAL, 16), draw(TOTAL, 2)

    def select(length):
        return att.select_blocks(qi, ki, w, 8, Q_BLOCK, length=length)

    def run(length):
        if kind == "select":
            return jnp.concatenate([jnp.pad(
                b, ((0, 0), (0, TOTAL - b.shape[1]))) for b in select(length)])
        return att.blockwise_attention(
            q, k, v, q_block=Q_BLOCK, scale=0.3, length=length, head_block=2,
            window=5 if kind == "window" else None,
            chosen=select(length) if kind == "chosen" else None)
    return jax.jit(run)


@pytest.mark.parametrize("length", [1, Q_BLOCK, Q_BLOCK + 1, TOTAL - 1, TOTAL])
@pytest.mark.parametrize("kind", ["plain", "window", "chosen", "select"])
def test_q_blocks_past_the_length_are_not_computed(kind, length):
    """Rows below `length` are what the full length gives; the rows of a
    block that starts at or past it are zeros (nothing chosen); no NaN."""
    run = _blockwise_case(kind)
    got = np.asarray(run(jnp.int32(length)))
    full = np.asarray(run(jnp.int32(TOTAL)))
    rows = 0 if kind == "select" else 1           # the axis of the queries
    ran = -(-length // Q_BLOCK) * Q_BLOCK         # rows of the blocks that ran
    take = lambda a, lo, hi: np.take(a, np.arange(lo, hi), rows)  # noqa: E731
    assert not np.isnan(got.astype(np.float32)).any()
    assert np.allclose(take(got, 0, length), take(full, 0, length), atol=2e-5)
    assert not take(got, ran, TOTAL).any()
    assert take(full, length, TOTAL).any() or length == TOTAL


def test_prefill_branches_once_a_q_block_and_counts_the_blocks_it_ran(fields):
    """Toy: 16 slots in q blocks of 8, top-k 8. A full layer holds one branch
    in `select_blocks` (block 0 is the causal constant) and one in
    `blockwise_attention`, a sliding layer one; a row's `q_blocks_run` is
    what its length gives, the attention layers summed."""
    cfg = _config(fields)
    spec = dec.DecoderSpec.from_mapping(cfg.decoder)
    model = build_model(cfg)
    tree = ref.program_tree(fields, SEED)
    rows = _payload(fields["vocab_size"])
    prefill = lambda v, t, n: model.apply(v, t, n, 4,  # noqa: E731
                                          method="prefill")
    blocks = P_MAX // spec.q_block
    assert count_primitives(jax.make_jaxpr(prefill)(
        tree, rows[:, 1:], rows[:, 0]).jaxpr)["cond"] == sum(
        (blocks - 1) * (2 if kind == dec.FULL else 1) for kind in spec.kinds)
    _, cache = jax.jit(prefill)(tree, rows[:, 1:], rows[:, 0])
    assert cache["counts"]["q_blocks_run"].tolist() == [
        spec.layers * -(-n // spec.q_block) for n in LENGTHS]
    assert cache["counts"]["q_blocks_total"].tolist() == [
        spec.layers * blocks] * len(LENGTHS)


def test_skipping_padded_q_blocks_changes_no_answer(fields, sound,
                                                    monkeypatch):
    """Prefill and 12 steps of the batch of mixed lengths against the same
    rows with every branch taken (every block live: the program as it was
    before a block could be skipped)."""
    _, served, _ = sound
    monkeypatch.setattr(att, "q_blocks_live", lambda total, q_block, length:
                        [True] * -(-total // q_block))
    _, every = _generate(fields)
    for s, e in zip(served, every):
        assert int(e.q_blocks_run) == int(e.q_blocks_total)
        assert int(s.q_blocks_run) <= int(s.q_blocks_total)
        for name in ("tokens", "logits_first", "logits_last", "keys_kept",
                     "expert_tokens"):
            assert np.array_equal(getattr(s, name), getattr(e, name)), name
    assert sum(int(s.q_blocks_run) for s in served) < sum(
        int(s.q_blocks_total) for s in served)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(fields):
    """For ep_rank in 0..ep_size-1 the routed parts summed, the shared
    expert once, equal the uncut reference's layer (one chip holding all
    the experts)."""
    ep = fields["ep_size"]
    whole = dict(fields, ep_size=1, ep_rank=0,
                 n_routed_experts=fields["n_routed_experts"] * ep)
    hn = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, fields["hidden_size"])), jnp.float32)
    routed_w, shared_w, _ = ref.Reference(whole, ref.Drawn(
        whole, SEED)).experts(1, hn)
    total = 0.0
    for rank in range(ep):
        cut = dict(fields, ep_rank=rank)
        routed, shared, _ = ref.Reference(cut, ref.Drawn(cut, SEED)).experts(
            1, hn)
        assert np.allclose(shared, shared_w, atol=1e-6)
        assert float(jnp.abs(routed).max()) > 0
        total = total + routed
    assert np.allclose(total + shared_w, routed_w + shared_w, atol=1e-5)


@pytest.mark.parametrize("shape,k", [((7, 40), 8), ((3, 5, 64), 17),
                                     ((4, 9), 9), ((2, 33), 1)])
def test_top_k_mask_is_the_exact_top_k(shape, k):
    rng = np.random.default_rng(4)
    x = rng.standard_normal(shape).astype(np.float32)
    x[..., ::5] = -np.inf                      # masked keys
    x[..., 1] = x[..., 2]                      # a tie
    got = np.asarray(att.top_k_mask(jnp.asarray(x), k))
    kth = np.sort(x, axis=-1)[..., -min(k, shape[-1])][..., None]
    assert np.array_equal(got, x >= kth)


def test_ring_positions():
    got = np.asarray(att.ring_positions(jnp.asarray([0, 4, 5, 11]), 5))
    assert got.tolist() == [[0, -4, -3, -2, -1], [0, 1, 2, 3, 4],
                            [5, 1, 2, 3, 4], [10, 11, 7, 8, 9]]


@pytest.mark.parametrize("name", ["flagship-s1-w128", "quality-s2-w128"])
def test_an_hourglass_config_builds_what_it_built(name):
    from real_time_helmet_detection_tpu.models import StackedHourglass
    cfg = Config(**bench_toy.toy_fields(name))
    assert cfg.family == "hourglass" and cfg.decoder == {}
    assert isinstance(build_model(cfg), StackedHourglass)


def test_an_unknown_family_is_refused():
    with pytest.raises(ValueError, match="family"):
        Config(family="transformer")
    with pytest.raises(ValueError, match="decoder"):
        build_model(Config(family="latent_moe_decoder"))


def test_the_engine_serves_int32_payloads_and_feeds_row_counters(fields):
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
        with pytest.raises(ValueError, match="payload"):
            engine.submit(rows[0].astype(np.int64))
    count = lambda n: registry.counter(n).value  # noqa: E731
    assert [int(a.prompt_len) for a in answers] == list(LENGTHS[:3])
    assert answers[0].tokens.shape == (3,)
    assert count("gen.requests") == 3          # the padded row counts nothing
    assert count("gen.prompt_tokens") == sum(LENGTHS[:3])
    assert count("gen.padded_prompt_tokens") == 3 * P_MAX - sum(LENGTHS[:3])
    assert count("gen.new_tokens") == 9
    assert count("gen.keys_kept") == sum(int(a.keys_kept) for a in answers)
    layers, q_block = fields["num_hidden_layers"], fields["attn_q_block"]
    assert count("gen.q_blocks_total") == 3 * layers * -(-P_MAX // q_block)
    assert count("gen.q_blocks_run") == layers * sum(
        -(-n // q_block) for n in LENGTHS[:3])
    pairs = sum(count("gen.expert_pairs.e%02d" % e)
                for e in range(fields["n_routed_experts"]))
    assert pairs == sum(int(a.expert_tokens.sum()) for a in answers) > 0


def test_a_row_counters_that_raises_costs_its_counts_not_the_engine():
    from typing import NamedTuple
    from real_time_helmet_detection_tpu.obs.metrics import MetricsRegistry
    from real_time_helmet_detection_tpu.serving import ServingEngine

    class Answer(NamedTuple):
        doubled: jax.Array

    calls = []

    def counters(rows):
        calls.append(len(rows.doubled))
        if len(calls) == 1:
            raise RuntimeError("the first batch's callback fails")
        return {"test.rows": len(rows.doubled)}

    registry = MetricsRegistry()
    with ServingEngine(jax.jit(lambda variables, x: Answer(x * 2)), {}, (4,),
                       np.int32, buckets=(1,), metrics=registry,
                       row_counters=counters) as engine:
        for i in range(3):  # one batch each: the first one's callback raises
            got = engine.submit(np.full((4,), i, np.int32)).result(timeout=60)
            assert np.array_equal(got.doubled, np.full((4,), 2 * i))
    assert calls == [1, 1, 1]
    assert registry.counter("serve.row_counter_errors").value == 1
    assert registry.counter("test.rows").value == 2


@pytest.mark.parametrize("op_name,layer", [
    ("jit(generate)/prefill/attn_full/while/body/indexer/dot_general",
     "prefill/indexer"),
    ("jit(generate)/while/body/decode/experts/pallas_call", "decode/experts"),
    ("jit(generate)/prefill/lm_head/dot_general", "prefill/lm_head"),
    ("jit(predict)/decode/top_k", "decode"),
])
def test_hlo_scopes_names_the_decoders_layers(op_name, layer):
    from real_time_helmet_detection_tpu.obs.hlo_scopes import layer_of
    assert layer_of(op_name) == layer
