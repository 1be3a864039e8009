"""BENCHMARK.json and every file it names, held to the contract's limits."""

import functools
import glob
import importlib
import json
import os
import re

import pytest

import bench_toy

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


MANIFEST = load("BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = MANIFEST["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def cell_body(name):
    return load("benchmark", "cells", name + ".json")


@functools.cache
def runner_modules():
    """The runners there are: every module of benchmark/runners that has a ``Runner``."""
    names = [os.path.splitext(os.path.basename(p))[0]
             for p in glob.glob(os.path.join(ROOT, "benchmark", "runners", "*.py"))]
    return {n for n in names if n != "__init__"
            and hasattr(importlib.import_module("benchmark.runners." + n), "Runner")}


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    command = MANIFEST["command"]
    assert len(command) <= 32 and all(one_line(w) for w in command)
    assert command[1].startswith(MANIFEST["paths"][0] + "/")
    assert os.path.exists(os.path.join(ROOT, command[1]))


def test_the_full_check_fits_its_time_with_24_cells():
    seconds = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert len(config["reduced"]) <= 16 and all(NAME.match(k) for k in config["reduced"])
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    body = load(config["file"])
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    # the sizes its builder module declares, the reference it names, and the comparison of
    # each kind of runner that its cells use: nothing a family does not have is asked of it
    builder = importlib.import_module(body.get("builder", "benchmark.models"))
    assert callable(builder.build_model) and "vocab_size" in builder.REQUIRED_SIZES
    for key in builder.REQUIRED_SIZES:
        assert isinstance(body["model"][key], int), key
    reference = importlib.import_module("benchmark.reference." + body.get("reference", "gpt2"))
    assert callable(reference.arch) and callable(reference.train) and reference.FAULTS
    kinds = {cell_body(w["name"])["runner"] for w in MANIFEST["workloads"]
             if w["config"] == config["name"]}
    for kind in kinds:
        assert one_line(body["compare"][kind]["why"], 2000)
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(config["file"]) == 1


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    assert cell["config"] in {c["name"] for c in MANIFEST["configs"]}
    body = cell_body(cell["name"])
    assert body["runner"] in runner_modules() and body[body["runner"]]
    load("benchmark", "traffic", cell["traffic"] + ".json")
    # every cell reports setup_s, one more end-to-end metric and a per-layer metric
    mine = [m["name"] for m in MANIFEST["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in mine and len(mine) >= 2
    assert any(cell["name"] in cells_of(m) for m in PER_LAYER)


def test_cells_are_distinct_and_few_take_four_chips():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", PER_LAYER, ids=lambda m: m["name"])
def test_per_layer_metric_and_its_reader_file(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    assert one_line(metric["layer"])
    # the metric it moves is reported in every cell where this one is
    assert metric["moves"] in E2E
    assert set(cells_of(metric)) <= set(cells_of(E2E[metric["moves"]]))
    spec = load("benchmark", "layer_metrics", metric["name"] + ".json")
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert spec[key] == metric[key]
    assert spec["reader"]["reduction"] in (
        "value", "module_ms_per_call", "module_ms_per", "collective_ms_per",
        "exposed_share", "roofline", "mfu")
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"] or "roofline" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique_and_layers_are_perf_mds():
    names = [m["name"] for m in MANIFEST["end_to_end"] + PER_LAYER]
    assert len(set(names)) == len(names) and 1 <= len(PER_LAYER) <= 128
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.1 and "workloads" not in E2E["setup_s"]
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for layer in {m["layer"] for m in PER_LAYER}:
        assert f"| {layer} |" in perf, f"PERF.md section 3 does not list the layer {layer!r}"


def test_every_file_under_paths_has_a_permitted_name():
    for p in MANIFEST["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


def test_toy_manifest_is_derived_from_the_real_metrics_and_the_toy_files():
    toy = bench_toy.manifest()
    assert [m["name"] for m in toy["per_layer"]] == [m["name"] for m in PER_LAYER]
    assert [m["name"] for m in toy["end_to_end"]] == list(E2E)
    assert {w["name"] for w in toy["workloads"]} == set(bench_toy.cells())
    assert {w["config"] for w in toy["workloads"]} == {c["name"] for c in toy["configs"]}


@pytest.mark.parametrize("name", sorted(bench_toy.cells()))
def test_toy_cell_stands_for_a_committed_cell_of_its_runner(name):
    toy = bench_toy.cells()[name]
    assert toy["stands_for"] in CELLS and toy["chips"] in (1, 4)
    body = load("tests", "benchmark", "toy", "cells", name + ".json")
    assert body["runner"] == cell_body(toy["stands_for"])["runner"]
    load("tests", "benchmark", "toy", "traffic", toy["traffic"] + ".json")
    config = load("tests", "benchmark", "toy", "configs", toy["config"] + ".json")
    assert one_line(config["compare"][body["runner"]]["why"], 2000)
