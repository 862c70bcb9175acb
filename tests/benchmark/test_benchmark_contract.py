"""`BENCHMARK.json` and the files it names, held to the rules the driver
checks before any run, and to this repo's own: every name resolves to a file,
and the benchmark sets and reads no `HOROVOD_*` variable."""

import glob
import json
import os
import re

import pytest

from benchmark.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|n_embd|n_inner|"
                   r"head_dim|width|expansion|per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert sorted(bench) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert os.path.getsize(os.path.join(spec.REPO, "BENCHMARK.json")) \
        <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and \
        1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    assert all(one_line(word) and not word.startswith("/") and ".." not in
               word for word in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and os.path.isdir(os.path.join(spec.REPO, p))
    # a full check with all 24 cells fits the driver's 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(spec.REPO, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and key in held and not WIDTH.search(key)


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    every = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and "workloads" not in setup
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in end_to_end
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert {m["moves"] for m in cell.per_layer} <= names
        assert cell.chips == w["chips"]


def test_every_name_resolves_to_a_file(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        family = spec.load_module("families", cell.config["family"],
                                  cell.dirs)
        path = spec.load_module("paths", cell.traffic["path"], cell.dirs)
        assert callable(path.build) and callable(path.abstract_step)
        assert callable(family.flops_per_sample)
        assert one_line(cell.traffic["why"], 1000)
    for kind, entries in (("end_to_end", bench["end_to_end"]),
                          ("layer_metrics", bench["per_layer"])):
        for m in entries:
            assert callable(spec.load_module(kind, m["name"],
                                             (spec.PACKAGE_DIR,)).read)
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.load_module("paths", "no_such_path", (spec.PACKAGE_DIR,))


def test_layers_are_named_as_perf_md_lists_them(bench):
    with open(os.path.join(spec.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_the_two_lm_cells_differ_only_in_the_mesh():
    one, four = spec.load_cell("lm-1chip"), spec.load_cell("lm-dp4")
    assert one.config == four.config
    a, b = dict(one.traffic), dict(four.traffic)
    assert a.pop("mesh") == {} and b.pop("mesh") == {"dp": 4}
    a.pop("why"), b.pop("why")
    assert a == b      # same per-chip batch and sequence: weak scaling


def test_benchmark_sets_and_reads_no_horovod_variable_and_no_fallback():
    sources = glob.glob(os.path.join(spec.PACKAGE_DIR, "**", "*.py"),
                        recursive=True)
    assert len(sources) > 20
    for path in sources:
        with open(path) as f:
            text = f.read()
        assert not re.search(r"HOROVOD_[A-Z]", text), path
        assert "os.environ[" not in text and "putenv" not in text, path
        if not path.endswith("aot_check.py"):
            assert "os.environ" not in text, path
    with open(os.path.join(spec.PACKAGE_DIR, "run.py")) as f:
        run = f.read()
    assert "platform=" not in run and "cpu" not in run.lower()
