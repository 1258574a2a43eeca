"""The linear-hybrid decoder's cell (`ling3-ep8-l8-gen512-256`) at toy size on
the CPU, through `run_cell(..., allow_cpu=True)` from a throw-away root made
by the files' own `toy` blocks; `benchmark.hybrid_check` over every side and
every planted fault; the configuration file against the catalog's form; the
work counts against hand arithmetic at the published sizes; the new readers
on a program that lacks the family's counters."""

import contextlib
import io
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import hybrid_check  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark.work import hybrid_moe_decoder as work  # noqa: E402

CELL = "ling3-ep8-l8-gen512-256"
CONFIG = os.path.join(bench_toy.REPO, "benchmark", "configs",
                      "ling3-flash-ep8-l8.json")
COUNTER_METRICS = {"group_hit_share.latent", "cache_live_share.decode",
                   "expert_load_max_over_mean.gen", "prompt_padding_share.gen",
                   "engine_batch_fill.bulk", "q_block_live_share.gen",
                   "attn_fused_block_share.gen"}
NEW_READERS = ("generate_mfu.hybrid", "kda_step_roofline.hybrid",
               "expert_gmm_roofline.hybrid")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_toy.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _cell(root, trace=0, sabotage=None):
    result = bench_run.run_cell(CELL, 2 ** 31 + 41, 1.0, trace, root=root,
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
    assert 25 < m["group_hit_share.latent"] < 75
    assert 0 < m["cache_live_share.decode"] < 100
    assert 0 < m["prompt_padding_share.gen"] < 100
    # the one latent layer: its q blocks past a row's length are not run,
    # and off the chip none is the fused kernel's
    assert 0 < m["q_block_live_share.gen"] <= 100
    assert m["attn_fused_block_share.gen"] == 0
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
    """`benchmark.hybrid_check` at toy size: a sound seed with both lower
    precisions beside it, and one seed with every planted fault."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        for extra in (["--seeds", "2147483701", "--control-seeds",
                       "2147483701", "--bf16-seeds", "2147483701"],
                      ["--fault-seed", "2147483702", "--faults",
                       ",".join(hybrid_check.FAULTS)]):
            assert hybrid_check.main(["--workload", CELL, "--allow-cpu"]
                                     + extra, root=root) == 0
    lines = [json.loads(line[len("READING "):])
             for line in out.getvalue().splitlines()
             if line.startswith("READING ")]
    assert len(lines) == 1 + len(hybrid_check.FAULTS)
    return {side: r[side] for r in lines for side in r if side != "seed"}


# at 64 wide a dropped decay and a bfloat16 state read inside the bfloat16
# program's own band (tests/test_hybrid_decoder.py catches both in float32)
BLIND_AT_TOY = ("decay_dropped", "state_bf16")


@pytest.mark.parametrize("side,correct", [
    ("program", True), ("ref_bf16", True), ("control", False)]
    + [("fault:" + f, None if f in BLIND_AT_TOY else False)
       for f in hybrid_check.FAULTS])
def test_hybrid_check_puts_every_side_through_the_cells_limits(
        readings, side, correct):
    got = readings[side]
    assert got["correct"] is (not got["over"]), got
    assert correct is None or got["correct"] is correct, got


def test_the_sources_keys_stand_at_the_top_level_as_they_are_run():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(bench_toy.REPO, "benchmark", "sources",
                           "ling-3.0-flash.json")) as f:
        source = json.load(f)["widths"]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
        "num_nextn_predict_layers"]
    fields = config["fields"]
    assert set(fields) - set(source) == {"family", "ep_size", "ep_rank"}
    for key, value in source.items():
        assert config[key] == fields[key], key
        if key not in config["reduced"]:
            assert config[key] == value, key
    # the floors: a whole period of 6 after the 2 dense layers, a whole
    # routing group of the eight (>= 8 experts), an eighth of the vocabulary
    assert (source["num_hidden_layers"], config["num_hidden_layers"]) == (
        42, 8)
    assert (source["num_experts"], config["num_experts"]) == (512, 512 // 8)
    assert (source["vocab_size"], config["vocab_size"]) == (157184,
                                                            157184 // 8)
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert config[key] == source[key][:8] == [0] * 8
    assert (source["num_nextn_predict_layers"],
            config["num_nextn_predict_layers"]) == (1, 0)
    assert (fields["ep_size"], fields["ep_rank"]) == (8, 0)
    assert fields["family"] == "hybrid_moe_decoder"
    assert set(config["assumed"]) >= {
        "layer_pattern", "kda_decay", "rope_interleave", "use_qk_norm",
        "swiglu_limits", "mtp", "weights", "share_keys"}
    assert "8 chips share each layer" in config["deployment"]
    assert config["parameters"] == 2_976_509_024


def test_the_configuration_entry_fits_the_manifests_letter():
    manifest = bench_run.load_manifest(bench_toy.REPO)
    entry = next(c for c in manifest["configs"]
                 if c["file"].endswith("ling3-flash-ep8-l8.json"))
    with open(CONFIG) as f:
        config = json.load(f)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert cell["config"] == entry["name"] and cell["chips"] == 1


def test_the_work_counts_at_the_published_sizes():
    with open(CONFIG) as f:
        fields = json.load(f)["fields"]
    assert work.param_count(fields) == 2_976_509_024
    assert work.expert_bytes(fields) == 11_796_480
    # the least bytes of one row through one layer's 32 states
    assert work.kda_step_bytes(fields) == 32 * 4 * (2 * 128 * 128 + 5 * 128
                                                    + 1)
    assert work.linear_flops(fields) == 2 * (6 * 2560 * 4096 + 2560 * 32)
    assert work.latent_flops(fields) == 2 * (31_965_696 - 512)
    assert work.key_flops(fields) == 2 * 32 * 320
    assert work.step_flops(fields) == 2 * 32 * 3 * 128 * 128
    assert work.chunk_flops(fields) == 2 * 32 * (4 * 32 * 32 * 128
                                                 + 3 * 32 * 128 * 128)
    rows, length, new = 256, 384, 256
    counters = {"gen.requests": rows, "gen.prompt_tokens": rows * length,
                "gen.new_tokens": rows * new, "gen.expert_visits": 6 * 64,
                "gen.keys_causal": rows * (length * (length + 1) // 2),
                "gen.expert_pairs.e00": rows * 639 * 6,
                "gen.linear_chunks_run": rows * 7 * 16,
                "gen.linear_chunks_live": rows * 7 * 12,
                "gen.linear_state_steps": rows * 7 * 255}
    flops = work.window_flops(fields, counters)
    positions = rows * (length + new - 1)
    base = (positions * work.position_flops(fields)
            + rows * 639 * 6 * work.pair_flops(fields)
            + rows * new * 2 * 2560 * 19648)
    assert base < flops < 1.05 * base
    gmm_flops, gmm_bytes = work.gmm_work(fields, counters)
    assert gmm_flops == rows * 639 * 6 * work.pair_flops(fields)
    assert gmm_bytes > 6 * 64 * 11_796_480


@pytest.mark.parametrize("metric", NEW_READERS)
def test_a_reader_finds_nothing_where_the_program_lacks_the_family(metric):
    """A parent without the linear-attention counters answers none of
    them: every new reader returns None (the line leaves the metric out),
    never raises."""
    read = bench_run.load_reader(os.path.join(bench_toy.REPO, "benchmark"),
                                 metric)
    for counters in ({}, {"gen.requests": 8, "gen.prompt_tokens": 100,
                          "gen.new_tokens": 64, "gen.group_slots": 40,
                          "gen.group_hits": 20, "gen.expert_visits": 5,
                          "gen.expert_passes": 4}):
        rec = types.SimpleNamespace(
            window={"counters": counters, "window_s": 1.0, "images": 8},
            config={}, traffic={"new_tokens": 8, "p_max": 16},
            trace={"op_ms": {"expert_gmm": 3.0, "kda_step": 1.0},
                   "busy_s": 1.0},
            peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
        assert read(rec) is None


def test_the_kda_roofline_reads_its_bytes_over_the_kernels_time():
    with open(CONFIG) as f:
        fields = json.load(f)["fields"]
    read = bench_run.load_reader(os.path.join(bench_toy.REPO, "benchmark"),
                                 "kda_step_roofline.hybrid")
    steps = 256 * 7 * 255
    least_ms = 1e3 * steps * work.kda_step_bytes(fields) / 819e9
    rec = types.SimpleNamespace(
        window={"counters": {"gen.linear_state_steps": steps}},
        config=fields, trace={"op_ms": {"kda_step": 2 * least_ms}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert abs(read(rec) - 50.0) < 1e-9


@pytest.mark.parametrize("layer", [0, 2, 3])
def test_the_references_programs_are_one_program_for_every_seed(layer):
    """The seed's key is an argument of the reference's per-layer programs,
    not a constant: two seeds lower to the same program (one compile, found
    again in the compile cache by every later seed), and what it draws
    still follows the seed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import hybrid_moe_decoder as ref
    fields = bench_toy.toy_fields("ling3-flash-ep8-l8")
    x = jnp.asarray(np.random.default_rng(layer).standard_normal(
        (2, 12, fields["hidden_size"])), jnp.float32)
    texts, outs = [], []
    for seed in (2 ** 31 + 5, 2 ** 31 + 6):
        model = ref.Reference(fields, ref.Drawn(fields, seed))
        run = model._jit("layer_%d" % layer,
                         lambda x: model.layer(layer, x))
        texts.append(run.func.lower(*run.args, x).as_text())
        outs.append(np.asarray(run(x)[0]))
    assert texts[0] == texts[1]
    assert not np.allclose(outs[0], outs[1])


def test_layers_of_one_form_run_one_program_over_their_own_draws():
    """Layers 1 and 3 of the toy (KDA with experts) share layer 1's program,
    layer 3's leaves drawn through its shift: the shared program gives what
    layer 3's own program gives, and what the program's tree holds."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import hybrid_moe_decoder as ref
    fields = bench_toy.toy_fields("ling3-flash-ep8-l8")
    model = ref.Reference(fields, ref.Drawn(fields, 2 ** 31 + 7))
    assert [model.twin(i)[0] for i in range(4)] == [0, 1, 2, 1]
    j, shift = model.twin(3)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 12, fields["hidden_size"])), jnp.float32)
    shared = model._jit("layer_1", lambda x: model.layer(1, x), shift)(x)
    own = model._jit("own_3", lambda x: model.layer(3, x))(x)
    for a, b in zip(shared, own):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    held = ref.Reference(fields, ref.Held(fields, ref.flatten_tree(
        ref.program_tree(fields, 2 ** 31 + 7)["params"])))
    assert held.twin(3) == (3, 0)
    np.testing.assert_allclose(
        np.asarray(shared[0]), np.asarray(held.layer(3, x)[0]),
        rtol=1e-5, atol=1e-5)


def test_a_fault_compiled_ahead_lowers_to_the_engines_program(root):
    """`hybrid_check.compile_ahead` lowers each fault's program from plain
    shapes; the engine lowers it from parameters committed to the device.
    The two must be one text, else the compile cache never hands the
    engine what was compiled ahead."""
    import jax
    import numpy as np
    parts = bench_run.resolve_cell(root, bench_run.load_manifest(root), CELL)
    cell = parts["driver"].Cell(bench_run.Context(
        2 ** 31 + 43, parts["config"], parts["traffic"], 0))
    cell.faults = frozenset({"beta_one"})
    payload = jax.ShapeDtypeStruct(
        (max(cell.p["engine"]["buckets"]), int(cell.p["p_max"]) + 1),
        np.int32)
    ahead = cell.program().lower(jax.eval_shape(cell.weights), payload)
    engine = cell.program().lower(jax.device_put(cell.weights()), payload)
    assert ahead.as_text() == engine.as_text()
    assert [f.result() for f in hybrid_check.compile_ahead(
        parts, 2 ** 31 + 43, ["beta_one"], ["fp8"])] == [
        "reference fp8", "beta_one"]


def test_the_reference_compiled_ahead_is_the_program_forward_runs(root):
    """`Reference.compile` lowers each program from shapes, at the check's
    own (the sample's sequences, `p_max` + new tokens long, the new tokens'
    positions); `forward` runs it over arrays on the device, a layer's input
    the last program's output. Both must be one text a program, whatever the
    seed, else the cache never hands a seed's reference what was compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.reference import hybrid_moe_decoder as ref
    parts = bench_run.resolve_cell(root, bench_run.load_manifest(root), CELL)
    cfg, p = parts["config"], parts["traffic"]
    seqs, new = int(p["sample"]), int(p["new_tokens"])
    n = int(p["p_max"]) + new
    ahead = ref.Reference(cfg, ref.Drawn(cfg, 2 ** 31 + 9), "fp8")
    model = ref.Reference(cfg, ref.Drawn(cfg, 2 ** 31 + 10), "fp8")
    tokens = jnp.asarray(np.ones((seqs, n)), jnp.int32)
    x = model._embed()(tokens)
    at = jnp.asarray(np.zeros((seqs, new)), jnp.int32)

    def shapes(*arrays):
        return [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
    pairs = [("_embed", (), (tokens,)), ("_head", (), (x, at))] + [
        ("_layer", (i,), (x,)) for i in range(ahead.z.layers)]
    for name, index, arrays in pairs:
        a, b = getattr(ahead, name)(*index), getattr(model, name)(*index)
        assert (a.func.lower(*a.args, *shapes(*arrays)).as_text()
                == b.func.lower(*b.args, *arrays).as_text()), (name, index)
    ahead.compile(seqs, n, new)
