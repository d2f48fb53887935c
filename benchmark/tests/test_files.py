"""Every data file and reader loads, and ``BENCHMARK.json`` agrees with
them and with the contract's limits."""

import glob
import json
import os
import re

import pytest

from conftest import BENCH, ROOT
from run import in_cell, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/configs/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert {w["config"] for w in bench["workloads"]} == configs
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["configs"] + bench["workloads"]:
        assert NAME.match(e["name"])
    assert "setup_s" in names


def test_every_cell_reports_setup_one_more_and_a_layer(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if in_cell(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(in_cell(m, w["name"]) for m in bench["per_layer"])


def test_moves_names_a_metric_of_every_cell_the_reader_is_read_in(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in bench["workloads"]:
            if in_cell(m, w["name"]):
                assert in_cell(e2e[m["moves"]], w["name"]), (m["name"], w)


def test_configs_and_traffic_load_and_resolve(bench):
    import importlib

    from common import resolve
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        c = load(path)
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert os.path.basename(path) == c["name"] + ".json"
        for key in ("builder", "sizes", "changed", "assumed", "reduced",
                    "train", "rehearse"):
            assert key in c, (path, key)
        for dotted in (c["builder"], c["train"]["data"]["loader"]):
            assert callable(resolve(dotted))
        if c.get("flops"):
            assert resolve(c["flops"])(c["sizes"]) > 0
        if c.get("reference"):
            importlib.import_module("reference." + c["reference"])
    for c in bench["configs"]:
        assert load(os.path.join(ROOT, c["file"]))["reduced"] == c["reduced"]
        assert load(os.path.join(ROOT, c["file"]))["source"] == c["source"]
    for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
        t = load(path)
        assert NAME.match(os.path.basename(path)[:-5])
        assert os.path.exists(
            os.path.join(BENCH, "runners", t["runner"] + ".py"))
    for w in bench["workloads"]:
        assert os.path.exists(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))


def test_the_parameter_counts_the_configs_state():
    import jax
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        c = load(path)
        if "parameters" not in c:
            continue
        from common import resolve
        model = resolve(c["builder"])(**c["sizes"])
        shapes = jax.eval_shape(lambda: model.init(0))["params"]
        count = sum(int(a.size) for a in jax.tree_util.tree_leaves(shapes))
        assert count == c["parameters"], path


def test_every_reader_loads_and_agrees_with_its_entry(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    files = {os.path.basename(p)[:-3] for p in
             glob.glob(os.path.join(BENCH, "layer_metrics", "*.py"))}
    assert set(entries) <= files
    for name in files:
        mod = load_module("layer_metrics", name)
        assert mod.NAME == name and callable(mod.read)
        assert NAME.match(mod.NAME) and UNIT.match(mod.UNIT)
        if name in entries:
            e = entries[name]
            assert (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
                e["unit"], e["layer"], e["moves"], e["source"])
        # a reader that finds nothing to read returns nothing
        assert mod.read({"config": {}, "trace": None}) is None


def test_an_unknown_device_has_no_peak():
    import peaks
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
