"""BENCHMARK.json against the contract's letter, and every name it holds
against a file that exists and parses. No JAX.

Each check is a function of a manifest and the root it describes, so that
test_bench_foreign_family.py can hold the tree a later PR would make to the
same checks; the tests below apply them to the repo's own."""

import importlib.util
import json
import os
import pkgutil
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402

from benchmark import drivers  # noqa: E402  (an empty package: no JAX)

REPO = bench_toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest(root=REPO):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(m):
    return [c["name"] for c in m["workloads"]]


def _bench_dir(m, root):
    return os.path.join(root, m["paths"][0])  # as run.resolve_cell has it


# ---- the checks ---------------------------------------------------------------

def check_top_level(m, root):
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 64 * 1024
    for word in m["command"]:
        assert not word.startswith("/") and ".." not in word


def source_widths(m, root, source):
    """The widths a configuration of `source` is held to. They stand in a file
    of the source's own (benchmark/sources/*.json: `source`, `read_from`,
    `widths`), not in the configuration's: a later PR may add a configuration
    and may not edit a file that is there, so no one edit cuts a width and
    the value it is compared with."""
    at = os.path.join(_bench_dir(m, root), "sources")
    found = []
    for name in sorted(os.listdir(at)):
        with open(os.path.join(at, name)) as f:
            data = json.load(f)
        if data["source"] == source:
            found.append(data)
    assert len(found) == 1, "%d files of %s name the source %r" % (
        len(found), at, source)
    assert found[0]["widths"] and found[0]["read_from"], source
    return found[0]["widths"]


def check_cell(m, root, cell):
    entry = next(c for c in m["workloads"] if c["name"] == cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == entry["config"])
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert any(conf["file"].startswith(p + "/") for p in m["paths"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    # the source's own widths, at the source's values unless `reduced` owns up
    widths = source_widths(m, root, conf["source"])
    for key, value in widths.items():
        assert key in config["fields"], (conf["file"], key)
        if key not in conf["reduced"]:
            assert config["fields"][key] == value, (conf["file"], key)
    with open(os.path.join(_bench_dir(m, root), "workloads",
                           entry["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["driver"] in {d.name for d in
                             pkgutil.iter_modules(drivers.__path__)
                             if not d.name.startswith("_")}  # as resolve_cell
    assert mix["limits"] and all(v > 0 for v in mix["limits"].values())
    reports = [x for x in m["end_to_end"]
               if "workloads" not in x or cell in x["workloads"]]
    assert {"setup_s"} < {x["name"] for x in reports}
    assert any(cell in x.get("workloads", [cell]) for x in m["per_layer"])


def check_names(m):
    cells = _cells(m)
    pairs = [(c["config"], c["traffic"]) for c in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(names)) == len(names)
    for n in names + cells + [c["name"] for c in m["configs"]] \
            + [c["traffic"] for c in m["workloads"]]:
        assert NAME.match(n), n
    four = sum(c["chips"] == 4 for c in m["workloads"])
    assert four <= max(1, len(cells) // 4)


def check_end_to_end_metric(m, metric):
    x = next(x for x in m["end_to_end"] if x["name"] == metric)
    assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    assert x["source"] in ("host_clock", "device_trace")
    assert 0.01 <= x["bound"] <= 0.1
    assert set(x.get("workloads", _cells(m))) <= set(_cells(m))


def check_per_layer_metric(m, root, metric):
    cells = _cells(m)
    x = next(x for x in m["per_layer"] if x["name"] == metric)
    assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert UNIT.match(x["unit"]) and x["source"] in SOURCES
    moved = next(e for e in m["end_to_end"] if e["name"] == x["moves"])
    for cell in x.get("workloads", cells):
        assert cell in moved.get("workloads", cells)
    if "roofline" in metric or "mfu" in metric:
        assert x["unit"] == "%"
    path = os.path.join(_bench_dir(m, root), "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert callable(module.read)


def check_rooflines_have_an_mfu(m):
    for x in m["per_layer"]:
        if "roofline" in x["name"]:
            assert any("mfu" in o["name"] and o["moves"] == x["moves"]
                       and set(x["workloads"]) <= set(o["workloads"])
                       for o in m["per_layer"]), x["name"]


def check_file_names(m, root):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in m["paths"]:
        for at, dirs, files in os.walk(os.path.join(root, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(at, name), root)
                assert ok.match(rel), rel


def check_every_reader_is_named(m, root):
    """A reader file is there for an entry of `per_layer`, or for the cell
    bench_toy adds by entries alone: a metric that retires takes its reader
    with it."""
    later = {"workloads": [], "end_to_end": [], "per_layer": []}
    bench_toy.add_live_cell(later)
    named = {x["name"] for x in m["per_layer"] + later["per_layer"]}
    found = {n[:-3] for n in os.listdir(os.path.join(_bench_dir(m, root),
                                                     "layer_metrics"))
             if n.endswith(".py")}
    assert found <= named, sorted(found - named)


def check_all(m, root):
    check_top_level(m, root)
    check_names(m)
    check_rooflines_have_an_mfu(m)
    check_file_names(m, root)
    check_every_reader_is_named(m, root)
    for cell in _cells(m):
        check_cell(m, root, cell)
    for x in m["end_to_end"]:
        check_end_to_end_metric(m, x["name"])
    for x in m["per_layer"]:
        check_per_layer_metric(m, root, x["name"])


# ---- the repo's own manifest ----------------------------------------------------

M = manifest()
CELLS = _cells(M)
PER_LAYER = [m["name"] for m in M["per_layer"]]


def test_top_level_keys():
    check_top_level(M, REPO)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_that_parse(cell):
    check_cell(M, REPO, cell)


def test_pairs_and_names_are_unique_and_well_formed():
    check_names(M)


@pytest.mark.parametrize("metric", [m["name"] for m in M["end_to_end"]])
def test_end_to_end_metric_entry(metric):
    check_end_to_end_metric(M, metric)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_metric_has_a_reader_and_moves_something(metric):
    check_per_layer_metric(M, REPO, metric)


def test_every_roofline_has_an_mfu_beside_it():
    check_rooflines_have_an_mfu(M)


def test_files_under_paths_are_named_from_the_allowed_characters():
    check_file_names(M, REPO)


def test_every_reader_file_is_named_by_an_entry(tmp_path):
    check_every_reader_is_named(M, REPO)
    # a metric retired from the manifest alone leaves its reader behind
    bench_toy.copy_data(REPO, str(tmp_path))
    retired = dict(M, per_layer=M["per_layer"][1:])
    with pytest.raises(AssertionError, match=M["per_layer"][0]["name"]):
        check_every_reader_is_named(retired, str(tmp_path))


def _edited_copy(tmp, rel, edit):
    """The repo's data copied under `tmp` with one JSON file changed by
    `edit`; returns the copy's manifest."""
    m = bench_toy.copy_data(REPO, str(tmp))
    path = os.path.join(str(tmp), rel)
    with open(path) as f:
        data = json.load(f)
    edit(data)
    with open(path, "w") as f:
        json.dump(data, f)
    return m


def _config_of(m, cell):
    entry = next(c for c in m["workloads"] if c["name"] == cell)
    return next(c for c in m["configs"] if c["name"] == entry["config"])


@pytest.mark.parametrize("reduced,holds", [([], False), (["imsize"], True)])
def test_a_width_of_the_source_may_differ_only_where_reduced_says_so(
        tmp_path, reduced, holds):
    m = _edited_copy(tmp_path, _config_of(M, CELLS[0])["file"],
                     lambda config: config["fields"].update(imsize=256))
    _config_of(m, CELLS[0])["reduced"] = reduced
    if holds:
        check_cell(m, str(tmp_path), CELLS[0])
    else:
        with pytest.raises(AssertionError, match="imsize"):
            check_cell(m, str(tmp_path), CELLS[0])


def test_a_configuration_whose_source_has_no_file_of_widths_is_refused(
        tmp_path):
    m = bench_toy.copy_data(REPO, str(tmp_path))
    _config_of(m, CELLS[0])["source"] = "https://example.org/another"
    with pytest.raises(AssertionError, match="name the source"):
        check_cell(m, str(tmp_path), CELLS[0])


def test_a_data_file_without_a_toy_block_is_an_error_that_names_it(tmp_path):
    rel = os.path.join("benchmark", "workloads",
                       M["workloads"][0]["traffic"] + ".json")
    _edited_copy(tmp_path / "src", rel, lambda mix: mix.pop("toy"))
    with pytest.raises(ValueError,
                       match=re.escape(str(tmp_path / "src" / rel))):
        bench_toy.make_root(str(tmp_path / "toy"), src=str(tmp_path / "src"))


def test_peaks_table_names_its_source():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9 and row["source"]
