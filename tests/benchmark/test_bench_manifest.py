"""BENCHMARK.json against the contract's letter, and every name it holds
against a file that exists and parses. No JAX."""

import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


M = manifest()
CELLS = [c["name"] for c in M["workloads"]]
PER_LAYER = [m["name"] for m in M["per_layer"]]


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_that_parse(cell):
    entry = next(c for c in M["workloads"] if c["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    conf = next(c for c in M["configs"] if c["name"] == entry["config"])
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert any(conf["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(REPO, conf["file"])) as f:
        config = json.load(f)
    assert config["fields"]["hourglass_inch"] == 128  # published width
    assert config["fields"]["imsize"] == 512
    with open(os.path.join(REPO, "benchmark", "workloads",
                           entry["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(REPO, "benchmark", "drivers",
                                       mix["driver"] + ".py"))
    assert mix["limits"] and all(v > 0 for v in mix["limits"].values())
    reports = [m for m in M["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
    assert {"setup_s"} < {m["name"] for m in reports}
    assert any(cell in m.get("workloads", [cell]) for m in M["per_layer"])


def test_pairs_and_names_are_unique_and_well_formed():
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(names)) == len(names)
    for n in names + CELLS + [c["name"] for c in M["configs"]] \
            + [c["traffic"] for c in M["workloads"]]:
        assert NAME.match(n), n
    four = sum(c["chips"] == 4 for c in M["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", [m["name"] for m in M["end_to_end"]])
def test_end_to_end_metric_entry(metric):
    m = next(x for x in M["end_to_end"] if x["name"] == metric)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_has_a_reader_and_moves_something(metric):
    m = next(x for x in M["per_layer"] if x["name"] == metric)
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert UNIT.match(m["unit"]) and m["source"] in SOURCES
    moved = next(x for x in M["end_to_end"] if x["name"] == m["moves"])
    for cell in m.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS)
    if "roofline" in metric or "mfu" in metric:
        assert m["unit"] == "%"
    path = os.path.join(REPO, "benchmark", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.read)


def test_every_roofline_has_an_mfu_beside_it():
    for m in M["per_layer"]:
        if "roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(m["workloads"]) <= set(o["workloads"])
                       for o in M["per_layer"]), m["name"]


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in M["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(root, name), REPO)
                assert ok.match(rel), rel


def test_peaks_table_names_its_source():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
