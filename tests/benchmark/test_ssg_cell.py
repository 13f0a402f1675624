"""The cell ``ssg-r4-1chip.advance``: its configuration states upstream's
radius, the size that was read on the chip, the departures from
upstream and that nothing is reduced; its reference counts 84 B a point
and step; its ``--tiny --trace 1`` run on the CPU is ``correct`` over
all nine fields and prints the three per-layer metrics that read the
program's ``compiled_plans()``; the bfloat16 control is not correct;
and the manifest keeps its rules with the cell in it."""

import importlib.util
import json
import os

import pytest

from bench_util import BENCH, ROOT, manifest, result_line, run_cell
from test_manifest import cells_keep_the_rules

CELL = "ssg-r4-1chip.advance"
CONFIG = "ssg-r4-1chip"
PLAN_METRICS = ("kernel.margin_overhead", "kernel.vmem_need_share",
                "runtime.passthrough_copy_ms_per_step")
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    STEPS = int(json.load(_f)["steps_per_call"])


def reference():
    spec = importlib.util.spec_from_file_location(
        "ssg_cell_ref", os.path.join(BENCH, "stencils", "ssg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_states_radius_size_departures_no_reduction():
    assert (CFG["stencil"], CFG["radius"]) == ("ssg", 4)
    assert (CFG["mode"], CFG["wf_steps"]) == ("pallas", 1)
    assert CFG["ranks"] == [1, 1, 1] and CFG["chips"] == 1
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    assert CFG["tiny_domain"] == [48, 40, 128]
    n, m, z = CFG["domain"]
    assert n == m and n % 32 == 0 and n >= 256 and z == 384
    assert {"radius", "domain", "earth", "dt/h", "initial state"} \
        <= set(CFG["assumed"])
    assert "as recalled" in CFG["assumed"]["radius"]
    assert f"{n}x{n}x384" in CFG["assumed"]["domain"]
    assert "GiB" in CFG["assumed"]["domain"]
    assert {"weights", "delta_t and spacing", "interpolation"} \
        <= set(CFG["departures"])
    assert all("as recalled" in CFG["departures"][k]
               for k in ("weights", "delta_t and spacing",
                         "interpolation"))
    assert set(CFG["consts"]) == {"rho", "lambda_", "mu"}
    assert "SSGElasticStencil.cpp" in CFG["source"]
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == []
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_earth_is_the_awp_cells_with_dt_over_h_folded_in():
    with open(os.path.join(BENCH, "configs",
                           "awp-abc-r2-4chip.json")) as f:
        awp = json.load(f)
    dt_h = awp["consts"]["h"]
    for name, fold in (("rho", 1.0 / dt_h), ("lambda_", dt_h),
                       ("mu", dt_h)):
        mine = CFG["consts"][name]["z"]["layers"]
        theirs = awp["consts"][name]["z"]["layers"]
        assert [s for s, _v in mine] == [s for s, _v in theirs]
        assert [v for _s, v in mine] == pytest.approx(
            [v * fold for _s, v in theirs], rel=1e-12)
    # Courant number in the fastest layer, from the folded arrays
    (_s, rho), (_s, lam), (_s, mu) = (
        CFG["consts"][k]["z"]["layers"][0] for k in ("rho", "lambda_",
                                                     "mu"))
    assert ((lam + 2 * mu) / rho) ** 0.5 == pytest.approx(0.3, rel=1e-3)


def test_the_reference_declares_nine_fields_and_84_bytes():
    ref = reference()
    assert ref.need_bytes_per_point_step(1) == 84
    assert ref.REACH == 2 * CFG["radius"] == 8
    assert list(ref.FIELDS)[:3] == ["v_x", "v_y", "v_z"]
    assert all(ref.FIELDS[f] == {"slots": 1, "weight": 0.0}
               for f in ("v_x", "v_y", "v_z"))
    assert [ref.FIELDS["s_" + c]["weight"]
            for c in ("xx", "yy", "zz", "xy", "xz", "yz")] \
        == [1.0, 0.9, 0.8, 0.5, 0.4, 0.3]
    assert all(ref.FIELDS[f]["slots"] == 2 and ref.FIELDS[f]["levels"] == 1
               for f in ref.FIELDS if f.startswith("s_"))
    assert ref.SOURCE == "s_xx"
    assert ref.ARRAYS == ("rho", "lambda_", "mu")
    with open(os.path.join(BENCH, "stencils", "ssg.py")) as f:
        text = f.read()
    assert "yask_tpu" not in text.split('"""', 2)[2]    # imports nothing
    assert text.count("as recalled") >= 3


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"]) == (1, "advance")
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items()
            if ws and "iso3dfd-r8-1chip.advance" in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    assert like == mine and set(PLAN_METRICS) <= mine
    advance = [w["name"] for w in m["workloads"]
               if w["traffic"] == "advance"]
    one_chip = [w["name"] for w in m["workloads"]
                if w["traffic"] == "advance" and w["chips"] == 1]
    assert lists["kernel.margin_overhead"] == advance
    assert lists["kernel.vmem_need_share"] == advance
    assert lists["runtime.passthrough_copy_ms_per_step"] == one_chip
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "gpts_per_s")["workloads"]
    for name in PLAN_METRICS:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))


def test_the_manifest_keeps_its_rules_with_the_cell_in_it():
    m = manifest()
    cells_keep_the_rules(m)
    assert [w["chips"] for w in m["workloads"]].count(4) \
        <= max(1, len(m["workloads"]) // 2)
    assert CELL == m["workloads"][-1]["name"]       # put last


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_over_all_nine_fields(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 27
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_prints_the_three_plan_metrics(traced):
    """From the program's ``compiled_plans()``: at the toy size the
    plan is the interpret host's, so the numbers are only held to what
    any plan of a two-stage radius-4 step keeps."""
    r, res = traced
    got = res["metrics"]
    assert set(PLAN_METRICS) <= set(got)
    assert got["kernel.margin_overhead"]["value"] > 0   # stage 1's margin
    assert 0 < got["kernel.vmem_need_share"]["value"] <= 200
    assert got["kernel.vmem_need_share"]["unit"] == "%"
    # no device plane on the CPU, so no copy is listed: 0, not missing
    assert got["runtime.passthrough_copy_ms_per_step"]["value"] == 0
    plan, = [ln for ln in r.stdout.splitlines() if "plan: " in ln]
    said = json.loads(plan.split("plan: ", 1)[1])
    assert said["fuse_steps"] == 1 and said["interpret"] is True
    assert said["margin_overhead"] \
        == got["kernel.margin_overhead"]["value"]


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control")
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 27 and all("control(bf16)" in ln for ln in said)
