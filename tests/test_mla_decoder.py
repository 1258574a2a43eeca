"""The latent family reading a source that holds no indexer, window, gate or
rank rescale (models/decoder.py `latent_moe_decoder` over the A.X-K1 keys:
YaRN on the rotary part, group-limited routing, one routing group a share)
at toy sizes on the CPU, held to the plain reference
(benchmark/reference/mla_moe_decoder.py). The toy sizes are the benchmark
configuration's own `toy` block: 3 full layers (dense, sparse, sparse), 4
heads, 16 experts in 4 groups of which 2 are kept, 4 shares. (The reference
repository has no language model: no analogue.)"""

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

from benchmark import latent_check  # noqa: E402
from benchmark.reference import mla_moe_decoder as ref  # noqa: E402
from real_time_helmet_detection_tpu.config import Config  # noqa: E402
from real_time_helmet_detection_tpu.models import build_model  # noqa: E402
from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.ops import moe  # noqa: E402
from real_time_helmet_detection_tpu.parallel.experts import (  # noqa: E402
    ExpertShare, expert_share)
from real_time_helmet_detection_tpu.predict import (  # noqa: E402
    Generation, generation_counters, make_generate_fn)

SEED = 2 ** 31 + 29
P_MAX, NEW = 16, 13          # 12 decode steps through the latent cache
LENGTHS = (16, 9, 12, 3)
PUBLISHED = os.path.join(bench_toy.REPO, "benchmark", "configs",
                         "axk1-ep8-l5.json")


@pytest.fixture(scope="module")
def fields():
    return bench_toy.toy_fields("axk1-ep8-l5")


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
    """The seed's draw with the queries, the nope keys and the rotary key
    eight times as large each (exact in bfloat16). At 64 wide the drawn
    sigma 0.02 gives scores of 0.01: every softmax is flat and no rotary,
    scale or cache fault shows; at 7,168 wide the same sigma gives scores of
    order one. Eight times each side is 64 times the scores."""
    tree, z = ref.program_tree(fields, SEED), ref.sizes(fields)
    for layer in tree["params"].values():
        if isinstance(layer, dict) and "attn" in layer:
            a = layer["attn"]
            ukv = a["w_ukv"].reshape(z.kv_rank, z.heads, z.nope + z.v)
            ukv = jnp.concatenate([ukv[..., :z.nope] * 8, ukv[..., z.nope:]],
                                  axis=-1)
            layer["attn"] = dict(
                a, w_uq=a["w_uq"] * 8, w_ukv=ukv.reshape(a["w_ukv"].shape),
                w_dkv=jnp.concatenate([a["w_dkv"][:, :z.kv_rank],
                                       a["w_dkv"][:, z.kv_rank:] * 8], -1))
    return tree


def _wants(fields, rows, served, quant="f32"):
    held = ref.Held(fields, ref.flatten_tree(_tree(fields)["params"]))
    return latent_check.reference_answers(fields, SEED, list(rows), served,
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
                           "gen-512-256.json")) as f:
        return json.load(f)["toy"]["limits"]


@pytest.fixture(scope="module")
def sound(fields):
    """The sound program's answers (bfloat16) and the reference's."""
    rows, served = _generate(fields)
    return rows, served, _wants(fields, rows, served)


@pytest.fixture(scope="module")
def exact(fields):
    """The program in float32 and the reference over what it served."""
    rows, served = _generate(fields, jnp.float32)
    return served, _wants(fields, rows, served)


def _shapes(fields):
    model = build_model(_config(fields))
    return model, jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))


def test_the_program_asks_for_exactly_the_references_parameters(fields):
    model, shapes = _shapes(fields)
    assert isinstance(model, dec.MoEDecoder)
    assert model.spec.family == dec.FAMILY        # no third family
    ref.check_tree(shapes, ref.param_spec(fields))
    with pytest.raises(ValueError, match="disagree"):
        ref.check_tree(shapes, ref.param_spec(dict(fields, q_lora_rank=8)))


@pytest.mark.parametrize("config,has", [("axk1-ep8-l5", False),
                                        ("dots3-note-prev-ep8-l5", True)])
def test_absent_keys_select_absent_parts(config, has):
    """One `LatentAttention`: a dots3 mapping builds an indexer, a gate, a
    selection bias and the indexer's key cache; this source's, none of
    them."""
    fields = bench_toy.toy_fields(config)
    model, shapes = _shapes(fields)
    paths = set(ref.flatten_tree(shapes["params"]))
    for leaf in ("attn/indexer/w_q", "attn/w_g"):
        assert any(p.endswith(leaf) for p in paths) is has, leaf
    assert any(p.endswith("moe/b_select") for p in paths) is has
    _, cache = jax.eval_shape(
        lambda v: model.apply(v, jnp.zeros((2, 8), jnp.int32),
                              jnp.full((2,), 8, jnp.int32), 4,
                              method="prefill"), shapes)
    assert any("k_i" in entry for entry in cache["layers"]) is has
    spec = model.spec
    assert (spec.index_topk > 0) is has and spec.full.gate is has
    assert spec.full.rescale is has and (spec.swa is not None) is has
    assert (spec.full.inv_freq is None) is has and (spec.n_group == 0) is has


@pytest.mark.parametrize("row", range(len(LENGTHS)))
def test_prefill_and_every_decode_step_match_the_full_forward_in_float32(
        exact, row):
    """Rows of different lengths in one batch; float32 on both sides, so the
    agreement is to rounding: the latent cache with W_uk / W_uv absorbed,
    the YaRN table, the score scale and the per-row positions hold nothing
    back. Every step's token is the reference's argmax at that position
    (the reference has no cache: a step that read a wrong slot would
    part)."""
    s, w, n = exact[0][row], exact[1][row], LENGTHS[row]
    assert np.allclose(s.logits_first, w["logits"][0], atol=2e-5)
    assert np.allclose(s.logits_last, w["logits"][-1], atol=2e-5)
    assert np.array_equal(s.tokens, np.argmax(w["logits"], -1))
    last, steps = n + NEW - 1, NEW - 1
    # three full layers, no indexer: every causal key is kept
    assert int(s.keys_kept) == int(s.keys_causal) == 3 * (
        last * (last + 1) // 2)
    assert s.cache_slots_read.tolist() == [3 * steps * (P_MAX + steps), 0]
    assert s.cache_keys_real.tolist() == [
        3 * sum(n + i + 1 for i in range(steps)), 0]
    assert int(s.q_blocks_run) == 3 * -(-n // 8)


@pytest.mark.parametrize("row", range(len(LENGTHS)))
def test_routing_and_group_hits_are_the_references(exact, row):
    s, w, n = exact[0][row], exact[1][row], LENGTHS[row]
    assert np.array_equal(s.expert_tokens, w["expert_pairs"])
    assert int(s.group_hits) == w["group_hits"] > 0
    # 2 expert layers, every position fed through them
    assert int(s.group_slots) == 2 * (n + NEW - 1)
    # a token comes to this share with picks only where its group was kept
    assert int(s.expert_tokens.sum()) <= 4 * int(s.group_hits)


def test_the_bfloat16_program_is_within_the_cells_limits(sound, limits):
    rows, served, wants = sound
    numbers = latent_check.numbers(list(rows), served, wants)
    for name, limit in limits.items():
        assert numbers[name] <= limit, (name, numbers)


@pytest.mark.parametrize("fault,caught_by", [
    ("no_group_limit", "group_hits_gap"),
    ("score_scale_plain", "prefill_logit_gap"),
    ("yarn_dropped", "decode_logit_gap"),
    ("rank_rescale_kept", "prefill_logit_gap"),
    ("gate_kept", "prefill_logit_gap"),
    ("no_shared", "prefill_logit_gap"),
    ("no_routed_scale", "prefill_logit_gap"),
    ("stale_cache_row", "decode_logit_gap"),
])
def test_a_planted_fault_fails_its_number(fields, limits, fault, caught_by):
    assert fault in latent_check.FAULTS
    rows, served = _generate(fields, faults={fault})
    numbers = latent_check.numbers(list(rows), served,
                                   _wants(fields, rows, served))
    assert numbers[caught_by] > limits[caught_by], numbers


def test_the_fp8_control_fails_a_limit(fields, sound, limits):
    rows, served, wants = sound
    low = _wants(fields, rows, served, "fp8")
    numbers = latent_check.numbers(
        list(rows), latent_check.control_answers(low), wants)
    assert any(numbers[k] > v for k, v in limits.items()), numbers


def _published():
    with open(PUBLISHED) as f:
        return json.load(f)["fields"]


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_yarn_table_and_the_score_scale_at_the_published_sizes(side):
    """ISSUE 35's numbers: low 10, high 23, m = 0.1 ln 32 + 1; cos and sin
    are not scaled, every score is times 192^(-1/2) m^2; program and
    reference each from its own code."""
    published = _published()
    if side == "program":
        f, m, mult = dec.latent_rotary(published["rope_scaling"], 10000.0, 64)
        scale = dec.DecoderSpec.from_mapping(
            {k: v for k, v in published.items() if k != "family"}).full.scale
    else:
        f, m, mult = ref.rotary(ref.sizes(published))
        scale = ref.score_scale(ref.sizes(published))
    assert len(f) == 32 and f[0] == 1.0 and m == 1.0
    for j, want in ((10, 0.0562341), (16, 5.52885e-3), (23, 4.16725e-5),
                    (31, 4.16725e-6)):
        assert abs(f[j] / want - 1) < 1e-5, (j, f[j])
    assert abs(f[10] / 10000 ** (-20 / 64) - 1) < 1e-12     # ramp 0 at low
    assert abs(f[23] * 32 / 10000 ** (-46 / 64) - 1) < 1e-12  # 1 at high
    assert abs(mult / 1.3465736 ** 2 - 1) < 1e-6
    assert abs(scale / 0.1308608 - 1) < 1e-6


def test_the_published_spec_reads_the_sources_own_keys():
    published = _published()
    f = dict(published)
    spec = dec.DecoderSpec.from_mapping(f, f.pop("family"))
    assert spec.kinds == (dec.FULL,) * 5 and spec.dense_layers == 1
    assert spec.heads == (64,) * 5 and spec.swa is None and spec.window == 0
    a = spec.full
    assert (a.q_rank, a.kv_rank, a.nope, a.rope, a.v) == (1536, 512, 128,
                                                          64, 128)
    assert not a.gate and not a.rescale and spec.index_topk == 0
    assert spec.share == ExpertShare(8, 0, 192, 8)
    assert list(spec.share.groups()) == [0] and spec.share.held == 24
    assert (spec.n_group, spec.topk_group, spec.per_token) == (8, 4, 8)
    assert spec.routed_scale == 2.5 and not spec.select_bias
    for bad in (dict(f, topk_group=9), dict(f, attention_gate_type="tanh"),
                dict(f, rope_scaling={"type": "linear", "factor": 2}),
                dict(f, layer_types=[dec.SLIDING] * 5)):
        with pytest.raises((ValueError, KeyError)):
            dec.DecoderSpec.from_mapping(bad)


@pytest.mark.parametrize("args,ok", [
    ((8, 0, 192, 8), True),     # a group a share
    ((4, 1, 192, 8), True),     # two whole groups a share
    ((16, 3, 192, 8), True),    # half a group a share
    ((6, 0, 192, 8), False),    # 32 experts: a group and a third
    ((64, 0, 192, 8), True),    # an eighth of a group
    ((12, 0, 192, 8), False),   # 16 experts: two thirds of a group
    ((8, 0, 192, 7), False),    # groups that do not divide the experts
])
def test_a_share_is_whole_groups_or_a_whole_fraction_of_one(args, ok):
    if not ok:
        with pytest.raises(ValueError, match="group"):
            expert_share(*args)
        return
    share = expert_share(*args)
    size = share.n_routed // share.n_group
    assert [e // size for e in (share.first, share.first + share.held - 1)
            ] == [share.groups()[0], share.groups()[-1]]


def _scores_to_router(scores):
    """(hn, w_router) whose sigmoid scores are `scores` (T, E) exactly
    enough: one-hot tokens against the logits."""
    logits = np.log(scores / (1 - scores))
    return (jnp.eye(len(scores), dtype=jnp.float32),
            jnp.asarray(logits, jnp.float32))


def test_route_with_groups_against_a_hand_written_case():
    """8 experts in 4 groups of 2, 2 groups kept, top-3. Token 0: group 0
    holds the best single score but its two sum to less than groups 1 and
    2: it is left out, and its expert with it. Token 1: groups 1 and 3 tie;
    the lower index wins, then the top-3 among the kept."""
    scores = np.array([
        [0.90, 0.10, 0.60, 0.55, 0.50, 0.52, 0.30, 0.20],
        [0.40, 0.30, 0.50, 0.25, 0.80, 0.60, 0.25, 0.50]])
    hn, w = _scores_to_router(scores)
    idx, weights, kept = moe.route_groups(hn, w, None, 3, True, 2.5, 4, 2)
    assert kept.tolist() == [[False, True, True, False],
                             [False, True, True, False]]
    assert sorted(idx[0].tolist()) == [2, 3, 5]
    assert sorted(idx[1].tolist()) == [2, 4, 5]
    picked = np.take_along_axis(scores, np.asarray(idx), axis=-1)
    assert np.allclose(weights, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    # every group kept is the plain top-k, and so is no group at all
    plain = moe.route(hn, w, None, 3, True, 2.5)
    every = moe.route(hn, w, None, 3, True, 2.5, 4, 4)
    assert np.array_equal(plain[0], every[0]) and np.allclose(plain[1],
                                                              every[1])
    assert sorted(plain[0][0].tolist()) == [0, 2, 3]
    # a bias enters the choice (the groups' too), never the weights
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.5], jnp.float32)
    idx_b, w_b, kept_b = moe.route_groups(hn, w, bias, 3, True, 2.5, 4, 2)
    assert kept_b[0].tolist() == [False, True, False, True]
    assert sorted(idx_b[0].tolist()) == [2, 6, 7]
    picked = np.take_along_axis(scores, np.asarray(idx_b), axis=-1)
    assert np.allclose(w_b, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)


def test_the_references_groups_are_the_programs(fields):
    rng = np.random.default_rng(4)
    scores = rng.uniform(0.05, 0.95, (40, 16))
    scores[:8, 4:8] = scores[:8, 0:4]               # tied groups
    hn, w = _scores_to_router(scores)
    kept = moe.kept_groups(jax.nn.sigmoid(jnp.dot(hn, w)), 4, 2)
    model = ref.Reference(fields, None)
    assert np.array_equal(kept, model.kept_groups(
        jax.nn.sigmoid(jnp.dot(hn, w))))
    assert (np.asarray(kept).sum(-1) == 2).all()
    tied = np.asarray(kept)[:8]      # a tie goes to the lower group
    assert (tied[:, 0] | ~tied[:, 1]).all() and (tied[:, 0] ^ tied[:, 1]).any()


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole_layer(
        fields):
    """The program's expert layer as eight shares (16 experts in 8 groups of
    which 4 are kept, a group a share): the routed parts summed, the shared
    expert counted once, equal the uncut reference's layer (one chip holding
    all 16); and a token has picks on at most `topk_group` shares."""
    wide = dict(fields, n_group=8, topk_group=4)
    hn = jnp.asarray(np.random.default_rng(2).standard_normal(
        (24, fields["hidden_size"])), jnp.float32)
    whole = ref.Reference(dict(wide, ep_size=1, n_routed_experts=16),
                          ref.Drawn(dict(wide, ep_size=1,
                                         n_routed_experts=16), SEED))
    routed_w, shared_w, chosen, kept = whole.experts(1, hn)
    assert (np.asarray(kept).sum(-1) == 4).all()
    total, holders = 0.0, np.zeros((24,), np.int32)
    for rank in range(8):
        cut = dict(wide, ep_size=8, ep_rank=rank, n_routed_experts=2)
        spec = dec.DecoderSpec.from_mapping(_config(cut).decoder)
        assert spec.share == ExpertShare(8, rank, 16, 8)
        assert list(spec.share.groups()) == [rank]
        p = jax.tree.map(lambda a: a.astype(jnp.float32), ref.program_tree(
            cut, SEED)["params"]["layer_1"]["moe"])
        y, local, hit = dec.expert_layer_groups(p, spec, hn,
                                                jnp.ones((24,), bool))
        assert np.array_equal(hit, np.asarray(kept)[:, rank])
        here = np.asarray(local < 2).any(-1)
        assert not (here & ~np.asarray(hit)).any()
        holders += here
        total = total + (y - shared_w)
    assert holders.max() <= 4 and holders.min() >= 1
    assert np.allclose(total + shared_w, routed_w + shared_w, atol=2e-5)


def test_one_layers_attention_and_its_absorbed_step_match_the_reference(
        fields):
    """Layer 1 over a sequence of 16 in float32: the prefill row against the
    reference's attention, and position 15 again as a decode step through
    the cache the first 15 left (W_uk / W_uv absorbed): the same numbers."""
    spec = dec.DecoderSpec.from_mapping(_config(fields).decoder)
    flat = {k: v.astype(jnp.float32) for k, v in ref.flatten_tree(
        _tree(fields)["params"]).items()}
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (16, spec.hidden)), jnp.float32)
    want = ref.Reference(fields, ref.Held(fields, flat)).attention(1, x)
    pre = "layer_1/attn/"
    p = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
    p["attn_norm"] = flat["layer_1/attn_norm"]
    got, entry, kept, ran = dec.attention_prefill_row(
        p, dec.FULL, spec, x, jnp.int32(16), 20)
    assert np.allclose(got, want, atol=2e-5)
    assert int(kept) == 16 * 17 // 2 and int(ran) == 2
    assert set(entry) == {"c_kv", "k_r"}
    assert entry["c_kv"].shape == (20, 16) and entry["k_r"].shape == (20, 8)
    _, before, _, _ = dec.attention_prefill_row(
        p, dec.FULL, spec, x.at[15].set(0.0), jnp.int32(15), 20)
    cache = {k: v.at[15].set(0.0)[None] for k, v in before.items()}
    out, after, kept = dec.attention_step(
        p, dec.FULL, spec, x[15:16], jnp.asarray([15], jnp.int32), cache)
    assert np.allclose(out[0], want[15], atol=2e-5)
    assert int(kept[0]) == 16
    assert np.allclose(after["c_kv"][0, :16], entry["c_kv"][:16], atol=1e-6)


def test_the_engine_feeds_the_group_and_cache_counters(fields):
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
    positions = sum(n + 2 for n in LENGTHS[:3])
    assert count("gen.group_slots") == 2 * positions
    assert 0 < count("gen.group_hits") == sum(
        int(a.group_hits) for a in answers) < count("gen.group_slots")
    assert count("gen.keys_kept") == count("gen.keys_causal") > 0
    # 2 steps over 3 full layers, each reading all P_MAX + 2 slots
    assert count("gen.cache_slots.full") == 3 * 3 * 2 * (P_MAX + 2)
    assert count("gen.cache_keys.full") == 3 * sum(
        n + 1 + n + 2 for n in LENGTHS[:3])
    assert count("gen.cache_slots.window") == 0
    assert count("gen.q_blocks_fused") == 0          # no Mosaic on the CPU
    assert count("gen.expert_passes") == 2 * 3


def test_a_family_without_groups_counts_no_group_slot():
    f = bench_toy.toy_fields("dots3-note-prev-ep8-l5")
    from benchmark.reference import latent_moe_decoder as latent
    cfg = _config(f)
    out = jax.device_get(make_generate_fn(build_model(cfg), cfg, 3)(
        latent.program_tree(f, SEED), jnp.asarray(_payload(f["vocab_size"]))))
    assert not out.group_hits.any() and not out.group_slots.any()
