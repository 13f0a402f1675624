"""The cell ``tti-r4-1chip.advance``: its configuration states upstream's
higher space order, the size of Devito's benchmark, the departures from
upstream and that nothing is reduced; its reference counts 48 B a point
and step and says what a scratch value outside the domain is; its
``--tiny --trace 1`` run on the CPU is ``correct`` in six numbers and
prints the two per-layer metrics that came with it
(``kernel.scratch_overhead``, ``kernel.fetch_overhead``, from the
program's ``compiled_plans()``); the bfloat16 control is not correct;
and the manifest keeps its rules with the cell in it."""

import importlib.util
import json
import math
import os

import pytest

from bench_util import BENCH, ROOT, manifest, result_line, run_cell
from test_manifest import cells_keep_the_rules

CELL = "tti-r4-1chip.advance"
CONFIG = "tti-r4-1chip"
NEW_METRICS = ("kernel.scratch_overhead", "kernel.fetch_overhead")
ARRAYS = ("m", "damp", "theta", "phi", "epsilon", "delta")
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    STEPS = int(json.load(_f)["steps_per_call"])


def reference():
    spec = importlib.util.spec_from_file_location(
        "tti_cell_ref", os.path.join(BENCH, "stencils", "tti.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layers(name, axis="z"):
    return CFG["consts"][name][axis]["layers"]


def test_the_configuration_states_radius_size_departures_no_reduction():
    assert (CFG["stencil"], CFG["radius"]) == ("tti", 4)
    assert (CFG["mode"], CFG["wf_steps"]) == ("pallas", 1)
    assert CFG["dtype"] == "float32" and CFG["domain"] == [512, 512, 512]
    assert CFG["ranks"] == [1, 1, 1] and CFG["chips"] == 1
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    assert {"radius", "domain", "earth", "anisotropy", "tilt", "damp",
            "dt and h", "initial state"} <= set(CFG["assumed"])
    assert "as recalled" in CFG["assumed"]["radius"]
    assert "512x512x512" in CFG["assumed"]["domain"]
    assert "GiB" in CFG["assumed"]["domain"]
    assert {"trig", "expressions", "damp", "dt and h"} \
        <= set(CFG["departures"])
    assert "along z only" in CFG["departures"]["damp"]
    assert all("as recalled" in CFG["departures"][k]
               for k in ("trig", "expressions", "damp"))
    assert set(CFG["consts"]) == set(ARRAYS)
    assert "TTIStencil.cpp" in CFG["source"]
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == [] and "TTIStencil.cpp" in row["source"]
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_earth_the_anisotropy_the_tilt_and_the_bands():
    with open(os.path.join(BENCH, "configs",
                           "awp-abc-r2-4chip.json")) as f:
        awp = json.load(f)
    # the awp cell's layering; m = 1 / vp^2 for 1.5, 2.5, 3.5 m/ms
    assert [s for s, _v in layers("m")] \
        == [s for s, _v in awp["consts"]["rho"]["z"]["layers"]]
    assert [v for _s, v in layers("m")] == pytest.approx(
        [1 / 1.5 ** 2, 1 / 2.5 ** 2, 1 / 3.5 ** 2], rel=1e-8)
    ref = reference()
    courant = 3.5 * ref.DT / ref.H
    assert courant == pytest.approx(0.155, abs=1e-3)
    assert "0.155" in CFG["assumed"]["dt and h"]
    # Thomsen: epsilon >= delta >= 0, both within 0.3, on the same rows
    eps, dlt = layers("epsilon"), layers("delta")
    assert [s for s, _v in eps] == [s for s, _v in dlt]
    assert all(0 <= d <= e <= 0.3 for (_s, e), (_t, d) in zip(eps, dlt))
    # tilt: layered in z and along a second axis, never flat
    assert set(CFG["consts"]["theta"]) == {"z", "x"}
    assert set(CFG["consts"]["phi"]) == {"z", "y"}
    for name, axis in (("theta", "x"), ("phi", "y")):
        assert len({v for _s, v in layers(name)}) >= 3
        assert len({v for _s, v in layers(name, axis)}) >= 3
    # damp: along z only, zero inside, one entry a row of the outer 32
    assert set(CFG["consts"]["damp"]) == {"z"}
    rows = layers("damp")
    n = CFG["domain"][2]
    assert [s * n for s, _v in rows] \
        == list(range(33)) + list(range(n - 32, n))
    values = [v for _s, v in rows]
    assert values[32] == 0.0 and values[:32] == values[:32:-1]
    assert values[:32] == sorted(values[:32], reverse=True)
    assert values[0] == pytest.approx(
        1.5 * math.log(1000.0) / 32 / ref.H, rel=1e-6)


def test_the_reference_declares_two_fields_six_arrays_and_48_bytes():
    ref = reference()
    assert ref.need_bytes_per_point_step(1) == 48
    assert ref.REACH == 2 * CFG["radius"] == 8
    assert ref.FIELDS == {"u": {"slots": 2, "levels": 2, "weight": 1.0},
                          "v": {"slots": 2, "levels": 2, "weight": 0.7}}
    assert ref.SOURCE == "u" and ref.ARRAYS == ARRAYS
    assert not hasattr(ref, "SCALARS")
    with open(os.path.join(BENCH, "stencils", "tti.py")) as f:
        text = f.read()
    assert "yask_tpu" not in text.split('"""', 2)[2]    # imports nothing
    assert "import numpy as np" in text and "scipy" not in text
    assert text.count("as recalled") >= 3
    assert "computed from those zero ghost cells" in text


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"]) == (1, "advance")
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items()
            if ws and "ssg-r4-1chip.advance" in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    assert mine == like | set(NEW_METRICS)
    for name in NEW_METRICS:
        assert lists[name] == [CELL]
        entry, = [p for p in m["per_layer"] if p["name"] == name]
        assert (entry["layer"], entry["moves"], entry["better"]) \
            == ("kernels", "gpts_per_s", "lower")
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "gpts_per_s")["workloads"]
    # every list that holds it is in the manifest's own order of cells,
    # whatever cells come after this one
    order = [w["name"] for w in m["workloads"]]
    for ws in list(lists.values()) + [
            e.get("workloads") for e in m["end_to_end"]]:
        if ws and CELL in ws:
            assert ws == [n for n in order if n in ws]


def test_the_manifest_keeps_its_rules_with_the_cell_in_it():
    m = manifest()
    cells_keep_the_rules(m)
    assert [w["chips"] for w in m["workloads"]].count(4) \
        <= max(1, len(m["workloads"]) // 2)
    assert len(m["configs"]) <= 24 and len(m["workloads"]) <= 24


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_in_six_numbers(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 6
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} {f}" for p in ("corner", "far", "seam")
            for f in ("u", "v")}
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_prints_the_two_new_metrics(traced):
    """From the program's ``compiled_plans()``: at the toy size the
    plan is the interpret host's, so the numbers are held to what any
    plan of this kernel keeps and to the row they are read from."""
    r, res = traced
    got = res["metrics"]
    assert set(NEW_METRICS) <= set(got)
    assert all(got[n]["unit"] == "ratio" for n in NEW_METRICS)
    assert got["kernel.margin_overhead"]["value"] == 0    # one stage
    plan, = [ln for ln in r.stdout.splitlines() if "plan: " in ln]
    said = json.loads(plan.split("plan: ", 1)[1])
    assert said["fuse_steps"] == 1 and said["interpret"] is True
    bx, by = said["block"]["x"], said["block"]["y"]
    nz, h = CFG["tiny_domain"][2], CFG["radius"]
    # six scratch vars, each on the block grown by 4 a side, z too
    assert got["kernel.scratch_overhead"]["value"] == round(
        (bx + 2 * h) * (by + 2 * h) * (nz + 2 * h) / (bx * by * nz) - 1, 4)
    assert got["kernel.fetch_overhead"]["value"] > 0
    assert got["runtime.passthrough_copy_ms_per_step"]["value"] == 0


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control")
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 6 and all("control(bf16)" in ln for ln in said)
