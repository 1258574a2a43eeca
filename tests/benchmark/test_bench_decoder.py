"""The decoder family's cell (`dots3-ep8-l5-gen8k`) at toy size on the CPU,
through `run_cell(..., allow_cpu=True)` from a throw-away root made by the
files' own `toy` blocks; its configuration file against the catalog's form;
its work counts against hand arithmetic at the published sizes."""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.drivers import generate_backlog  # noqa: E402
from benchmark.work import latent_moe_decoder as work  # noqa: E402

CELL = "dots3-ep8-l5-gen8k"
CONFIG = os.path.join(bench_toy.REPO, "benchmark", "configs",
                      "dots3-note-prev-ep8-l5.json")
NEW_METRICS = {"expert_load_max_over_mean.gen", "indexer_keys_kept_share.gen",
               "prompt_padding_share.gen", "generate_device_ms_per_req.gen"}


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
        "expert_pairs_gap", "keys_kept_gap"}


def test_a_traced_run_carries_every_new_counter_metric(root):
    line = _cell(root, trace=1)
    assert line["correct"] is True, line["checked"]
    assert NEW_METRICS <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["expert_load_max_over_mean.gen"] >= 1.0
    assert 0 < m["indexer_keys_kept_share.gen"] < 100
    assert 0 < m["prompt_padding_share.gen"] < 100
    assert m["engine_batch_fill.bulk"] > 0
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
    """`benchmark.decoder_check` at toy size: a sound seed with both lower
    precisions beside it, a seed with a planted fault, a rate-only seed."""
    import contextlib
    import io
    from benchmark import decoder_check
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        for extra in (["--seeds", "2147483701", "--control-seeds",
                       "2147483701", "--bf16-seeds", "2147483701",
                       "--rate-seeds", "2147483704"],
                      ["--fault-seeds", "2147483702", "--fault",
                       "no_shared"]):
            assert decoder_check.main(
                ["--workload", CELL, "--seconds", "0.5", "--allow-cpu"]
                + extra, root=root) == 0
    lines = [json.loads(line[len("READING "):])
             for line in out.getvalue().splitlines()
             if line.startswith("READING ")]
    return {r["seed"]: r for r in lines}


@pytest.mark.parametrize("seed,side,correct,over", [
    (2147483701, "program", True, None),
    (2147483701, "ref_bf16", True, None),
    # toy: fp8 reads about the toy limit (which requests the window's clock
    # lets the seed sample moves it across); tests/test_decoder.py holds
    # the control failing on fixed inputs, here it has to be judged
    (2147483701, "control", None, None),
    (2147483702, "fault:no_shared", False, "prefill_logit_gap"),
])
def test_decoder_check_puts_every_side_through_the_cells_limits(
        readings, seed, side, correct, over):
    got = readings[seed][side]
    assert got["correct"] is (not got["over"]), got
    assert correct is None or got["correct"] is correct, got
    assert over is None or over in got["over"], got
    if side == "control":
        sound = readings[seed]["program"]
        assert got["prefill_logit_gap"] > 3 * sound["prefill_logit_gap"], got


@pytest.mark.parametrize("fault,leaf", [("no_shared", "shared_down"),
                                        ("no_select_bias", "b_select")])
def test_a_fault_is_planted_in_the_weights_and_nowhere_else(fault, leaf):
    from benchmark import decoder_check
    from benchmark.reference import latent_moe_decoder as ref
    fields = bench_toy.toy_fields("dots3-note-prev-ep8-l5")
    sound = ref.flatten_tree(ref.program_tree(fields, 2 ** 31 + 3))
    planted = ref.flatten_tree(decoder_check.FAULTS[fault](
        ref.program_tree(fields, 2 ** 31 + 3)))
    assert sorted(planted) == sorted(sound)
    hit = [p for p in sound if p.endswith("moe/" + leaf)]
    assert len(hit) == (fields["num_hidden_layers"]
                        - fields["first_k_dense_replace"])
    for path in sound:
        if path in hit:
            assert np.any(np.asarray(sound[path], np.float32))
            assert not np.any(np.asarray(planted[path], np.float32))
        else:
            assert np.array_equal(np.asarray(planted[path], np.float32),
                                  np.asarray(sound[path], np.float32)), path


def test_a_rate_seed_runs_the_window_alone(readings):
    r = readings[2147483704]
    assert r["e2e"]["serve_img_per_s"] > 0 and r["failed"] == 0
    assert not {"program", "control"} & set(r)
    # what an even router sends this share: expert layers x choices / shares
    fields = bench_toy.toy_fields("dots3-note-prev-ep8-l5")
    even = ((fields["num_hidden_layers"] - fields["first_k_dense_replace"])
            * fields["num_experts_per_tok"] / fields["ep_size"])
    assert 0.5 * even < r["share_pairs_per_position"] < 1.5 * even


def test_the_sources_keys_stand_at_the_top_level_as_they_are_run():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(bench_toy.REPO, "benchmark", "sources",
                           "dots3-note-prev.json")) as f:
        source = json.load(f)["widths"]
    for key, value in source.items():
        assert config[key] == config["fields"][key], key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["fields"]["ep_size"] * config["fields"][
        "n_routed_experts"] == source["n_routed_experts"]
    assert config["layer_types"] == source["layer_types"][:5]
    # the floors: a whole period after the dense layer, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] >= 1 + 4
    assert config["vocab_size"] * 8 >= source["vocab_size"]


def test_the_work_counts_at_the_published_sizes():
    with open(CONFIG) as f:
        fields = json.load(f)["fields"]
    # ISSUE 29's arithmetic: 1.93 GFLOP of matrix products a prompt position
    # (the held experts' pairs, on average one a position a layer, included)
    per_position = work.position_flops(fields) + 4 * work.pair_flops(fields)
    assert abs(per_position / 1.93e9 - 1) < 0.03, per_position
    counters = {"gen.requests": 4, "gen.prompt_tokens": 4 * 6144,
                "gen.new_tokens": 256, "gen.keys_kept": 2 * 4 * 6144 * 1500,
                "gen.keys_causal": 2 * 4 * 6144 * 3072, "batches_total": 1,
                "gen.expert_pairs.e00": 4 * 6144 * 4}
    flops = work.window_flops(fields, counters)
    assert 1.5e9 * 4 * 6144 < flops < 4e9 * 4 * 6144
    gmm_flops, gmm_bytes = work.gmm_work(fields, counters, 64)
    assert gmm_flops == 4 * 6144 * 4 * work.pair_flops(fields)
    assert gmm_bytes > 32 * 4 * 3 * 5120 * 1536 * 2


def test_the_prompt_pool_is_the_seeds():
    a = generate_backlog.prompt_pool(2 ** 31 + 5, 16, 6, 16, 16, 64)
    b = generate_backlog.prompt_pool(2 ** 31 + 5, 16, 6, 16, 16, 64)
    c = generate_backlog.prompt_pool(2 ** 31 + 6, 16, 6, 16, 16, 64)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.shape == (16, 17)
    assert a[:, 0].min() >= 6 and a[:, 0].max() <= 16
    for row in a:
        assert not row[1 + row[0]:].any() and row[1:].max() < 64
