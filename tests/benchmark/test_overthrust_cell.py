"""The cell ``overthrust-sponge-1chip.advance``: its configuration states
the grid of the SEG/EAGE Overthrust model whole (801 x 801 x 187,
nothing reduced), its source, the sponge's faces, the earth and the
departures from upstream; its reference counts 12 B a point and step
and says where the sponge multiplies; its ``--tiny --trace 1`` run on
the CPU, on a box of three odd extents, is ``correct`` and prints the
two per-layer metrics that came with it (``kernel.edge_overhead``,
``kernel.lane_fill_share``, from the program's ``compiled_plans()``);
the bfloat16 control is not correct; and the manifest keeps its rules
with the cell in it.  No position in any list is pinned: cells that
come later go after this one."""

import importlib.util
import json
import os

import pytest

from bench_util import BENCH, ROOT, manifest, result_line, run_cell
from test_manifest import cells_keep_the_rules

CELL = "overthrust-sponge-1chip.advance"
CONFIG = "overthrust-sponge-1chip"
NEW_METRICS = {"kernel.edge_overhead": ("ratio", "lower"),
               "kernel.lane_fill_share": ("%", "higher")}
PINNED = {"runtime.slow_call_share", "runtime.slow_call_worst_ms"}
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    STEPS = int(json.load(_f)["steps_per_call"])


def reference():
    spec = importlib.util.spec_from_file_location(
        "overthrust_cell_ref",
        os.path.join(BENCH, "stencils", "iso3dfd_sponge.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_states_grid_source_sponge_and_no_reduction():
    assert (CFG["stencil"], CFG["radius"]) == ("iso3dfd_sponge", 8)
    assert (CFG["mode"], CFG["wf_steps"]) == ("pallas", 2)
    assert CFG["dtype"] == "float32" and CFG["domain"] == [801, 801, 187]
    assert CFG["ranks"] == [1, 1, 1] and CFG["chips"] == 1
    assert CFG["reduced"] == [] and CFG["probe_block"] == 8
    assert all(n % 2 == 1 and n % 8 for n in CFG["tiny_domain"])
    assert {"domain", "state", "sponge", "earth", "dt and h",
            "initial state", "tolerance"} <= set(CFG["assumed"])
    assert "as recalled" in CFG["assumed"]["domain"]
    assert "801x801x187" in CFG["assumed"]["domain"]
    assert "2.82 GiB" in CFG["assumed"]["state"]
    assert "849 x 888 x 256" in CFG["assumed"]["state"]
    assert {"sponge", "faces"} <= set(CFG["departures"])
    assert "three 1-D" in CFG["departures"]["sponge"]
    assert "sponge multiplies every new value" in CFG["guarantees"]
    for part in ("Iso3dfdStencil.cpp", "iso3dfd_sponge", "Overthrust",
                 "801x801x187", "Aminzadeh"):
        assert part in CFG["source"]
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == [] and row["source"] == CFG["source"]
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_sponge_the_earth_and_the_stability_bound():
    consts = CFG["consts"]
    assert set(consts) == {"vel", "sponge"}
    # Cerjan's taper on five faces: the high end of z is the surface
    taper = {"width": 20, "alpha": 0.015}
    for axis, ends in (("x", "both"), ("y", "both"), ("z", "low")):
        assert consts["sponge"][axis] == {"cerjan": {**taper,
                                                     "ends": ends}}
    # the awp cell's sponge, face for face
    with open(os.path.join(BENCH, "configs",
                           "awp-abc-r2-4chip.json")) as f:
        assert consts["sponge"] == json.load(f)["consts"]["sponge"]
    # four layers over z for 6.0 / 4.8 / 3.6 / 2.4 km/s, a step in x
    assert set(consts["vel"]) == {"z", "x"}
    layers = consts["vel"]["z"]["layers"]
    assert [v for _s, v in layers] == pytest.approx(
        [(v * 1.6e-3 / 0.025) ** 2 for v in (6.0, 4.8, 3.6, 2.4)],
        rel=1e-9)
    assert [s for s, _v in layers] == [0.0, 0.35, 0.6, 0.8]
    assert consts["vel"]["x"]["layers"] == [[0.0, 1.0], [0.55, 0.8]]
    ref = reference()
    top = max(v for _s, v in layers) * max(
        v for _s, v in consts["vel"]["x"]["layers"])
    assert top <= 0.15 and top * ref.eigenvalue_bound(8) < 4.0
    assert f"{ref.eigenvalue_bound(8):.4f}" in CFG["assumed"]["dt and h"]


def test_the_reference_declares_one_field_two_arrays_and_12_bytes():
    ref = reference()
    assert ref.need_bytes_per_point_step(CFG["wf_steps"]) == 12
    assert ref.FIELDS == {"pressure": {"slots": 2}}
    assert ref.ARRAYS == ("vel", "sponge")
    assert not hasattr(ref, "REACH") and not hasattr(ref, "SCALARS")
    with open(os.path.join(BENCH, "stencils", "iso3dfd_sponge.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "yask_tpu" not in body and "iso3dfd" not in body  # imports nothing
    assert "import numpy as np" in text
    assert "the whole new value, after" in text


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "advance", CONFIG)
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items()
            if ws and "ssg-r4-1chip.advance" in ws
            and "tti-r4-1chip.advance" in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    # three lists an accepted test pins to the cells it knew are not
    # among them, and a file that is there is a ``benchmark`` issue's to
    # edit: ``kernel.fetch_overhead`` (``test_tti_cell.py``: the tti
    # cell alone; it reads 15.0417 at this cell's plan) and the two
    # ``runtime.slow_call_*`` (``test_slow_call_readers.py``: six cells)
    assert mine == (like - PINNED) | set(NEW_METRICS)
    for name, (unit, better) in NEW_METRICS.items():
        assert lists[name] == [CELL]
        entry, = [p for p in m["per_layer"] if p["name"] == name]
        assert (entry["layer"], entry["moves"], entry["source"]) \
            == ("kernels", "gpts_per_s", "program_counter")
        assert (entry["unit"], entry["better"]) == (unit, better)
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


def test_the_traced_tiny_run_is_correct_on_the_ragged_corner(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} pressure" for p in ("corner", "far", "seam")}
    # ``far`` ends on the last point of every dim, none of which a
    # block or a lane count divides
    far, = [ln for ln in said if " far " in ln]
    assert f"at {[n - 4 for n in CFG['tiny_domain']]}" in far
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_prints_the_two_new_metrics(traced):
    """From the program's ``compiled_plans()``: at the toy size the
    plan is the interpret host's, so the numbers are held to the plan
    line's own block and to the box."""
    r, res = traced
    got = res["metrics"]
    assert set(NEW_METRICS) <= set(got)
    assert {n: got[n]["unit"] for n in NEW_METRICS} \
        == {n: u for n, (u, _b) in NEW_METRICS.items()}
    plan, = [ln for ln in r.stdout.splitlines() if "plan: " in ln]
    said = json.loads(plan.split("plan: ", 1)[1])
    assert said["fuse_steps"] == 2 and said["interpret"] is True
    nx, ny, nz = CFG["tiny_domain"]
    bx, by = said["block"]["x"], said["block"]["y"]
    # ceil coverage; the skewed dim walks (K - 1) r further
    skewed = 8 if "y" in said["skew_dims"] else 0
    walked = -(-nx // bx) * bx * -(-(ny + skewed) // by) * by
    assert got["kernel.edge_overhead"]["value"] == round(
        walked / (nx * ny) - 1, 4) > 0
    # the pressure tile's minor extent: nz and the fused halo either
    # side, padded to whole lanes
    lanes = -(-(nz + 2 * 2 * 8) // 128) * 128
    assert got["kernel.lane_fill_share"]["value"] == pytest.approx(
        100 * round(nz / lanes, 4))
    assert got["kernel.margin_overhead"]["value"] \
        == said["margin_overhead"] > 0
    assert got["runtime.passthrough_copy_ms_per_step"]["value"] == 0


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control")
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3 and all("control(bf16)" in ln for ln in said)
