"""The real cell ``awp-abc-r2-4chip.advance``: its reference is the
fixture's file byte for byte (the fixture stays where the other tests
read it), its configuration states the size, the departures from
upstream and that nothing is reduced, and its ``--tiny --trace 1`` run
on four host devices is ``correct`` and reports what the program's
launch spans say of the exchange."""

import json
import os

import pytest

from bench_util import AWP, BENCH, ROOT, manifest, result_line, run_cell

CELL = "awp-abc-r2-4chip.advance"
CONFIG = "awp-abc-r2-4chip"
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    STEPS = int(json.load(_f)["steps_per_call"])


def load(path):
    import importlib.util
    spec = importlib.util.spec_from_file_location("awp_abc_cell_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_reference_is_the_fixtures_file_byte_for_byte():
    with open(os.path.join(BENCH, "stencils", "awp_abc.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(AWP, "stencils", "awp_abc.py"), "rb") as f:
        assert mine == f.read()


def test_the_configuration_states_size_departures_and_no_reduction():
    with open(os.path.join(AWP, "configs", "awp-abc-4chip.json")) as f:
        fixture = json.load(f)
    assert CFG["domain"] == [640, 640, 512] and CFG["ranks"] == [4, 1, 1]
    assert CFG["reduced"] == [] and CFG["radius"] is None
    assert (CFG["mode"], CFG["wf_steps"]) == ("shard_pallas", 1)
    for key in ("consts", "guarantees", "tolerance", "probe_block",
                "tiny_domain", "stencil", "dtype", "source"):
        assert CFG[key] == fixture[key], key
    assert {"sponge", "anelasticity", "material", "free surface"} \
        <= set(CFG["departures"])
    assert "640^3" in CFG["assumed"]["domain"]
    assert "RESOURCE_EXHAUSTED" in CFG["assumed"]["domain"]
    assert "throwaway" not in CFG["why"]
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"]) == (4, "advance")
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items()
            if ws and "iso3dfd-r8-4chip.advance" in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    # at K=1 there is no shell kernel: a listed metric that never
    # reports would read null for ever
    assert like - mine == {"parallel.shell_ms_per_step"}
    assert {"parallel.exchange_mib_per_step", "parallel.slabs_per_step",
            "runtime.program_temp_gib"} <= mine
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "gpts_per_s")["workloads"]


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, devices=4, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_over_all_twelve_fields(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 36
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_reports_what_the_launches_exchange(traced):
    """Span attrs are on the CPU's host plane too.  From the stencil's
    file alone: every ring slot and read-only array refreshed once, two
    faces, then after every step but the last the written fields' newest
    slot, two faces."""
    _r, res = traced
    stencil = load(os.path.join(BENCH, "stencils", "awp_abc.py"))
    slots = sum(f["slots"] for f in stencil.FIELDS.values())
    arrays = len(stencil.ARRAYS)
    slabs = 2 * (slots + arrays) + (STEPS - 1) * 2 * len(stencil.FIELDS)
    got = res["metrics"]
    assert got["parallel.slabs_per_step"]["value"] \
        == pytest.approx(slabs / STEPS, rel=1e-12)
    assert got["parallel.slabs_per_step"]["unit"] == "count"
    # MiB over slabs is one slab: six planes (the halo the program
    # takes for its four stages of radius 2, 2, 2, 0) of the padded
    # y x z, whole lane-aligned float32 rows no smaller than the box's
    slab = (got["parallel.exchange_mib_per_step"]["value"] * 2 ** 20
            / got["parallel.slabs_per_step"]["value"])
    plane = slab / 6 / 4
    ny, nz = CFG["tiny_domain"][1:]
    assert plane == pytest.approx(round(plane), abs=1e-6)
    assert round(plane) % 128 == 0
    assert ny * nz <= round(plane) <= 4 * ny * nz
    assert got["runtime.program_temp_gib"]["value"] > 0
    assert "parallel.shell_ms_per_step" not in got


def test_the_bf16_control_of_the_real_cell_is_not_correct():
    r = run_cell(CELL, "--control", devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 36 and all("control(bf16)" in ln for ln in said)
