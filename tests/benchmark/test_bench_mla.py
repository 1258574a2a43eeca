"""The plain latent-attention decoder's cell (`axk1-ep8-l5-gen512-256`) at toy
size on the CPU, through `run_cell(..., allow_cpu=True)` from a throw-away
root made by the files' own `toy` blocks; its configuration file against the
catalog's form; its work counts against hand arithmetic at the published
sizes; its readers on a program that lacks the family's counters."""

import contextlib
import io
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.work import mla_moe_decoder as work  # noqa: E402

CELL = "axk1-ep8-l5-gen512-256"
CONFIG = os.path.join(bench_toy.REPO, "benchmark", "configs",
                      "axk1-ep8-l5.json")
COUNTER_METRICS = {"expert_visit_share.latent", "group_hit_share.latent",
                   "cache_live_share.decode", "expert_load_max_over_mean.gen",
                   "prompt_padding_share.gen", "engine_batch_fill.bulk"}
NEW_READERS = ("generate_mfu.latent", "expert_gmm_roofline.latent",
               "attn_fused_roofline.latent", "expert_visit_share.latent",
               "group_hit_share.latent")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _cell(root, trace=0, sabotage=None):
    result = bench_run.run_cell(CELL, 2 ** 31 + 29, 1.0, trace, root=root,
                                allow_cpu=True, sabotage=sabotage)
    return json.loads(json.dumps(result))


def test_the_cell_runs_and_prints_the_contracts_line(root):
    line = _cell(root)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checked"]
    assert line["correct"] is True, line["checked"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_img_per_s", "setup_s"}
    assert set(line["checked"]) == {
        "prefill_logit_gap", "decode_logit_gap", "token_gap_p99",
        "expert_pairs_gap", "group_hits_gap"}


def test_a_traced_run_carries_every_counter_metric(root):
    line = _cell(root, trace=1)
    assert line["correct"] is True, line["checked"]
    assert COUNTER_METRICS <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["expert_visit_share.latent"] <= 100
    # 4 groups of which 2 are kept, one held here: about half the slots
    assert 25 < m["group_hit_share.latent"] < 75
    assert 0 < m["cache_live_share.decode"] < 100
    assert m["expert_load_max_over_mean.gen"] >= 1.0
    assert 0 < m["prompt_padding_share.gen"] < 100
    # no device trace and no peak on the CPU: a share is left out, never 0
    assert not any("roofline" in k or "mfu" in k for k in line["metrics"])


def test_an_altered_token_is_not_correct(root):
    def altered(cell):
        real = cell.engine._fetch

        def fetch(out, b):
            host = real(out, b)
            vocab = cell.ctx.config["vocab_size"]
            return host._replace(tokens=(host.tokens + 1) % vocab)
        cell.engine._fetch = fetch
    line = _cell(root, sabotage=altered)
    assert line["correct"] is False
    c = line["checked"]["token_gap_p99"]
    assert c["value"] > c["limit"], line["checked"]


@pytest.fixture(scope="module")
def readings(root):
    """`benchmark.latent_check` at toy size: a sound seed with both lower
    precisions beside it, and a seed with two planted faults."""
    from benchmark import latent_check as gqa_check
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        for extra in (["--seeds", "2147483701", "--control-seeds",
                       "2147483701", "--bf16-seeds", "2147483701"],
                      ["--fault-seed", "2147483702", "--faults",
                       "no_group_limit,rank_rescale_kept"]):
            assert gqa_check.main(["--workload", CELL, "--allow-cpu"] + extra,
                                  root=root) == 0
    lines = [json.loads(line[len("READING "):])
             for line in out.getvalue().splitlines()
             if line.startswith("READING ")]
    assert len(lines) == 3
    return {side: r[side] for r in lines for side in r if side != "seed"}


@pytest.mark.parametrize("side,correct", [
    ("program", True), ("ref_bf16", True), ("control", None),
    ("fault:no_group_limit", False), ("fault:rank_rescale_kept", False)])
def test_latent_check_puts_every_side_through_the_cells_limits(
        readings, side, correct):
    got = readings[side]
    assert got["correct"] is (not got["over"]), got
    assert correct is None or got["correct"] is correct, got
    if side == "control":  # fp8 against the program's own bfloat16
        assert got["prefill_logit_gap"] > 3 * readings["program"][
            "prefill_logit_gap"], got


def test_the_sources_keys_stand_at_the_top_level_as_they_are_run():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(bench_toy.REPO, "benchmark", "sources",
                           "a.x-k1.json")) as f:
        source = json.load(f)["widths"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "ep_size"]
    for key, value in source.items():
        assert config[key] == config["fields"][key], key
        if key not in config["reduced"]:
            assert config[key] == value, key
    # the floors: the dense layer once and four layers after it, a whole
    # routing group of the eight (>= 8 experts), an eighth of the vocabulary
    assert (source["num_hidden_layers"], config["num_hidden_layers"]) == (
        61, 1 + 4)
    assert (source["n_routed_experts"], config["n_routed_experts"]) == (
        192, 192 // 8)
    assert (source["vocab_size"], config["vocab_size"]) == (163840,
                                                            163840 // 8)
    assert (source["ep_size"], config["ep_size"]) == (1, 8)
    assert config["fields"]["ep_rank"] == 0
    assert config["fields"]["family"] == "latent_moe_decoder"
    assert config["n_group"] == 8 and config["topk_group"] == 4
    # what the source does not have is not in the file either
    for absent in ("layer_types", "index_topk", "attention_gate_type",
                   "apply_mla_qkv_lora_rescale", "sliding_window_size"):
        assert absent not in config["fields"], absent
    assert set(config["assumed"]) >= {"topk_method", "rotary", "rope_scaling",
                                      "weights", "towers", "ep_size"}
    assert "8 chips share each layer" in config["deployment"]
    assert "one routing group of 24 experts a chip" in config["deployment"]
    assert config["parameters"] == 5_605_186_560


def test_the_work_counts_at_the_published_sizes():
    with open(CONFIG) as f:
        fields = json.load(f)["fields"]
    assert work.param_count(fields) == 5_605_186_560
    # ISSUE 35: attention 101,124,096 parameters a layer less the two latent
    # norms; 2.52 GFLOP a slot outside scores and head (one routed pair a
    # position and expert layer: 8 picks over 8 shares)
    assert work.attention_flops(fields) == 2 * (101_124_096 - 1536 - 512)
    per_position = work.position_flops(fields) + 4 * work.pair_flops(fields)
    assert abs(per_position / 2.52e9 - 1) < 2e-3, per_position
    assert work.expert_bytes(fields) == 88_080_384
    assert work.key_flops(fields) == 2 * 64 * 320
    visits, passes = 18 * 4 * 255 + 24 * 4, 4 * 256
    counters = {"gen.requests": 32, "gen.prompt_tokens": 32 * 384,
                "gen.new_tokens": 32 * 256, "gen.expert_visits": visits,
                "gen.expert_passes": passes,
                "gen.keys_causal": 5 * 32 * (639 * 640 // 2),
                "gen.expert_pairs.e00": 32 * 639 * 4,
                "gen.q_blocks_fused": 5 * 32}
    flops = work.window_flops(fields, counters)
    positions = 32 * 639
    head = 32 * 256 * 2 * 7168 * 20480
    assert flops > positions * per_position + head
    # attention over <= 767 keys is a percent or two of the rest
    assert flops < 1.03 * (positions * per_position + head)
    gmm_flops, gmm_bytes = work.gmm_work(fields, counters)
    assert gmm_flops == 32 * 639 * 4 * work.pair_flops(fields)
    assert gmm_bytes > visits * 88_080_384
    assert work.expert_slots(fields, counters) == passes * 24
    # one q block a prompt: a fused block is one visit of 512 x 512
    assert work.fused_attention_flops(fields, 512, 512, counters) == (
        5 * 32 * 2 * 64 * 320 * 512 * 512)
    assert work.fused_attention_flops(fields, 1024, 512, counters) is None


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_lacks_the_family(metric):
    """A parent without the group counts answers none of them (the second
    set is what the grouped-query family's program feeds): every new
    reader returns None (the line leaves the metric out), never raises."""
    read = bench_run.load_reader(os.path.join(bench_toy.REPO, "benchmark"),
                                 metric)
    for counters in ({}, {"gen.requests": 8, "gen.prompt_tokens": 100,
                          "gen.new_tokens": 64, "batches_total": 2,
                          "gen.expert_visits": 5, "gen.expert_passes": 4}):
        rec = types.SimpleNamespace(
            window={"counters": counters, "window_s": 1.0, "images": 8},
            config={}, traffic={"new_tokens": 8, "p_max": 16},
            trace={"op_ms": {"expert_gmm": 3.0, "attn_fused": 1.0},
                   "busy_s": 1.0},
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
        assert read(rec) is None
