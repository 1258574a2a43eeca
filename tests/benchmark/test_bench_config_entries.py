"""Every `configs` entry of BENCHMARK.json against the letter the workloads'
entries are already held to (test_bench_manifest.py): a configuration's
`why` is 1 to 200 printable characters on one line, its `source` at most
200. A manifest whose configuration carries a longer `why` is refused before
any run, whatever its cells do; these checks refuse it here first. No JAX."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import bench_toy  # noqa: E402
import test_bench_manifest as manifest_checks  # noqa: E402

M = manifest_checks.M


def check_config_entries(m):
    for conf in m["configs"]:
        for key, least in (("why", 1), ("source", 1)):
            text = conf[key]
            assert least <= len(text) <= 200, (conf["name"], key, len(text))
            assert text.isprintable(), (conf["name"], key)


@pytest.mark.parametrize("name", [c["name"] for c in M["configs"]])
def test_a_configurations_why_and_source_fit_the_letter(name):
    check_config_entries({"configs": [c for c in M["configs"]
                                      if c["name"] == name]})


@pytest.mark.parametrize("key,text", [
    ("why", "x" * 201), ("why", ""), ("why", "two\nlines"),
    ("source", "https://example.org/" + "a" * 190)])
def test_a_copied_manifest_with_an_overlong_entry_is_refused(tmp_path, key,
                                                             text):
    m = bench_toy.copy_data(bench_toy.REPO, str(tmp_path))
    check_config_entries(m)
    m["configs"][-1][key] = text
    with pytest.raises(AssertionError, match=m["configs"][-1]["name"]):
        check_config_entries(m)
