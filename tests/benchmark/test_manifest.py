"""``BENCHMARK.json`` keeps to the contract and to its own files."""

import os
import re

import pytest

from bench_util import BENCH, ROOT, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def test_top_level_keys_and_command():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_whys():
    m = manifest()
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        for key in ("source", "why"):
            assert 1 <= len(c[key]) <= 200 and "\n" not in c[key]
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        names.append(w["name"])
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
        names.append(e["name"])
    assert len(names) == len(set(names))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    assert "peak_device_gib" not in [e["name"] for e in m["end_to_end"]]


def test_one_four_chip_cell_of_four_and_configs_used():
    m = manifest()
    assert [w["chips"] for w in m["workloads"]].count(4) == 1
    assert len(m["workloads"]) == 4
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)


def cells_of(metric, m):
    return set(metric.get("workloads", [w["name"] for w in m["workloads"]]))


def test_every_moves_names_a_metric_its_cells_report():
    m = manifest()
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        assert cells_of(p, m) <= cells_of(e2e[p["moves"]], m), p
    for w in m["workloads"]:
        mine = [e for e in m["end_to_end"] if w["name"] in cells_of(e, m)]
        assert len(mine) >= 2          # setup_s and one other
        assert any(w["name"] in cells_of(p, m) for p in m["per_layer"])


def test_layers_are_spelt_one_way():
    layers = {p["layer"] for p in manifest()["per_layer"]}
    assert layers == {"serving", "runtime", "compile", "kernels",
                      "parallel", "device"}


@pytest.mark.parametrize("folder,key", [
    ("traffic", "traffic"), ("metrics", "metric")])
def test_every_name_has_its_file(folder, key):
    m = manifest()
    if key == "traffic":
        wanted = {w["traffic"] + ".json" for w in m["workloads"]}
    else:
        wanted = {e["name"] + ".py"
                  for e in m["end_to_end"] + m["per_layer"]}
    have = set(os.listdir(os.path.join(BENCH, folder)))
    assert wanted <= have, wanted - have


def test_config_files_state_what_the_issue_asks():
    import json
    for c in manifest()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in ("source", "stencil", "radius", "dtype", "domain",
                    "mode", "wf_steps", "ranks", "chips", "assumed",
                    "reduced", "why", "guarantees", "tolerance"):
            assert key in cfg, (c["name"], key)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            BENCH, "stencils", cfg["stencil"] + ".py"))


def test_peaks_table_names_its_source():
    import json
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in v5e["source"]
