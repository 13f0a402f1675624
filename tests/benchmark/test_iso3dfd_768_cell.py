"""The cell ``iso3dfd-r8-768-1chip.advance``: the flagship's
configuration at the largest 128-multiple cube one chip holds, where
two generations of the written ring fit and three do not.  Its
configuration states the size and the bytes behind it, nothing
reduced; the cell is listed where its readers find something to read
and in none of the lists an accepted test pins; it brings no reader of
its own (what the launch loop costs under memory pressure is what
``runtime.enqueue_ms_per_call``, ``runtime.call_gap_ms``,
``device.idle_share`` and ``runtime.peak_device_gib`` read); its
``--tiny --trace 1`` run on the CPU is ``correct`` and prints those;
and the manifest keeps its rules with the cell in it.  No position in
any list is pinned: cells that come later go after this one."""

import json
import os

import pytest

from bench_util import BENCH, ROOT, manifest, result_line, run_cell
from test_manifest import cells_keep_the_rules

CELL = "iso3dfd-r8-768-1chip.advance"
CONFIG = "iso3dfd-r8-768-1chip"
FLAGSHIP = "iso3dfd-r8-1chip"
#: lists that hold the ssg, tti and overthrust cells and that an
#: accepted test pins to the cells it knew
PINNED = {"kernel.scratch_overhead", "kernel.fetch_overhead",
          "runtime.slow_call_share", "runtime.slow_call_worst_ms",
          "kernel.edge_overhead", "kernel.lane_fill_share"}


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


CFG = config(CONFIG)


def test_the_configuration_is_the_flagships_at_768_nothing_reduced():
    flag = config(FLAGSHIP)
    assert set(CFG) == set(flag)
    for key in set(flag) - {"domain", "assumed", "why", "source"}:
        assert CFG[key] == flag[key], key
    assert CFG["domain"] == [768, 768, 768]
    assert CFG["tiny_domain"] == [64, 64, 64] and CFG["reduced"] == []
    assert (CFG["stencil"], CFG["radius"], CFG["dtype"]) \
        == ("iso3dfd", 8, "float32")
    assert (CFG["mode"], CFG["wf_steps"], CFG["chips"]) == ("pallas", 2, 1)
    assert CFG["tolerance"] == 1e-4 and CFG["consts"] == {"vel": 0.1}
    # the flagship's source, down to the part that sizes this one
    assert CFG["source"].startswith(flag["source"])
    assert CFG["source"] != flag["source"]
    assert "largest domain the memory holds" in CFG["source"]
    assert set(CFG["assumed"]) == {"domain", "vel", "initial state"}
    for part in ("768^3", "816 x 848 x 896", "2.3097 GiB", "1.9043 GiB",
                 "11.143 GiB", "15.762 GiB", "896^3"):
        assert part in CFG["assumed"]["domain"], part
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == [] and row["source"] == CFG["source"]
    assert os.path.isfile(os.path.join(ROOT, row["file"]))
    # the reference is the one the other iso3dfd configurations share
    assert os.path.isfile(os.path.join(BENCH, "stencils", "iso3dfd.py"))


def test_the_bytes_the_configuration_states_are_the_plans():
    """The slot and ``vel`` as a v5e pads them, from shapes alone."""
    dom = CFG["domain"]
    slot = (dom[0] + 48) * (dom[1] + 80) * (dom[2] + 128) * 4
    vel = (dom[0] + 32) * (dom[1] + 64) * dom[2] * 4
    gib = 2 ** 30
    assert f"{slot / gib:.4f} GiB" in CFG["assumed"]["domain"]
    assert f"{vel / gib:.4f} GiB" in CFG["assumed"]["domain"]
    assert f"{(vel + 4 * slot) / gib:.3f} GiB" in CFG["assumed"]["domain"]
    assert f"{(vel + 6 * slot) / gib:.3f} GiB" in CFG["assumed"]["domain"]
    assert vel + 4 * slot < 15.748 * gib < vel + 6 * slot


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "advance", CONFIG)
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items()
            if ws and {"ssg-r4-1chip.advance", "tti-r4-1chip.advance",
                       "overthrust-sponge-1chip.advance"} <= set(ws)}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    assert len(like) == 13 and not like & PINNED
    assert mine == like and not mine & PINNED
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
    assert len({c["source"] for c in m["configs"]}) == len(m["configs"])


# ------------------------------------------------------------ the dry run

@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} pressure" for p in ("corner", "far", "seam")}
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln for ln in said)
    assert res["metrics"]["compile.in_window"]["value"] == 0
    plan, = [ln for ln in r.stdout.splitlines() if "plan: " in ln]
    said = json.loads(plan.split("plan: ", 1)[1])
    assert said["fuse_steps"] == 2 and said["interpret"] is True


#: what the launch loop costs under memory pressure, by the readers the
#: benchmark already had: those of them the CPU's trace can feed (the
#: chip's reads ``runtime.call_gap_ms`` and ``runtime.peak_device_gib``
#: besides)
LAUNCH_LOOP = ("runtime.enqueue_ms_per_call", "device.idle_share",
               "device.longest_gap_ms", "runtime.remainder_share")


@pytest.mark.parametrize("metric", LAUNCH_LOOP)
def test_the_traced_tiny_run_reads_the_launch_loop(traced, metric):
    r, res = traced
    assert isinstance(res["metrics"][metric]["value"], (int, float))
    assert f"metric {metric} = " in r.stdout


def test_the_traced_tiny_run_copies_nothing_through(traced):
    _r, res = traced
    assert res["metrics"]["runtime.passthrough_copy_ms_per_step"][
        "value"] == 0
