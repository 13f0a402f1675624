"""The cell ``iso3dfd-r8-4chip-2x2.advance``: the flagship at the x/4
cell's size on the rank grid upstream picks for four ranks, 2x2 over x
and y.  Its configuration is the twin's but for the layout; its
``--tiny --trace 1`` run on four host devices is ``correct`` with the
seam probe across both split dims, and prints what a chip sends across
y and that the y axis' pack and unpack are told from x's."""

import json
import os

import pytest

from bench_util import BENCH, manifest, result_line, run_cell
from test_manifest import cells_keep_the_rules

CELL = "iso3dfd-r8-4chip-2x2.advance"
CONFIG = "iso3dfd-r8-4chip-2x2"
TWIN = "iso3dfd-r8-4chip"
#: the two lists an accepted test pins to the six cells it knew
#: (``test_slow_call_readers.py``)
PINNED = {"runtime.slow_call_share", "runtime.slow_call_worst_ms"}
#: name -> (unit, source), each listed for this cell alone
NEW_METRICS = {
    "parallel.y_exchange_mib_per_step": ("MiB", "program_span"),
    "parallel.y_pack_ms_per_step": ("ms", "device_trace")}


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


CFG = config(CONFIG)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    STEPS = int(json.load(_f)["steps_per_call"])


def test_the_configuration_is_the_twins_problem_in_another_layout():
    twin = config(TWIN)
    assert CFG["ranks"] == [2, 2, 1] and twin["ranks"] == [4, 1, 1]
    for key in ("stencil", "radius", "dtype", "domain", "mode", "wf_steps",
                "chips", "consts", "tolerance", "probe_block", "reduced"):
        assert CFG[key] == twin[key], key
    assert (CFG["stencil"], CFG["radius"], CFG["domain"]) \
        == ("iso3dfd", 8, [1024, 1024, 1024])
    assert (CFG["mode"], CFG["wf_steps"], CFG["chips"]) \
        == ("shard_pallas", 2, 4)
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    # every split dim's shard is halved at the toy size too
    assert CFG["tiny_domain"] == [128, 128, 64]
    assert "diagonal" in CFG["guarantees"]
    assert {"domain", "ranks"} <= set(CFG["assumed"])
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["source"] == CFG["source"] and len(row["source"]) <= 200
    assert "setup_rank" in row["source"] and row["reduced"] == []
    sources = [c["source"] for c in manifest()["configs"]]
    assert sources.count(row["source"]) == 1
    # no reference of its own: the stencil's file is the twin's
    assert os.path.isfile(os.path.join(BENCH, "stencils", "iso3dfd.py"))


def test_the_cell_is_listed_where_its_readers_find_something():
    m = manifest()
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (4, "advance", CONFIG)
    lists = {p["name"]: p.get("workloads") for p in m["per_layer"]}
    like = {n for n, ws in lists.items() if ws and TWIN + ".advance" in ws}
    mine = {n for n, ws in lists.items() if ws and CELL in ws}
    assert mine == (like - PINNED) | set(NEW_METRICS)
    assert "parallel.shell_ms_per_step" in mine
    for name, (unit, source) in NEW_METRICS.items():
        assert CELL in lists[name] and TWIN + ".advance" not in lists[name]
        entry, = [p for p in m["per_layer"] if p["name"] == name]
        assert (entry["layer"], entry["moves"], entry["better"]) \
            == ("parallel", "gpts_per_s", "lower")
        assert (entry["unit"], entry["source"]) == (unit, source)
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
    cells_keep_the_rules(m)


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, devices=4, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_across_both_split_dims(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 4 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and f"after {STEPS} steps" in ln for ln in said)
    # the seam probe's 8^3 block starts 2 short of the middle of x and
    # of y: it lies across the corner where the four shards meet
    seam, = [ln for ln in said if " seam " in ln]
    origin = json.loads(seam.split(" at ")[1].split(" after ")[0])
    for o, n, ranks in zip(origin, CFG["tiny_domain"], CFG["ranks"]):
        if ranks > 1:
            assert o < n // ranks < o + CFG["probe_block"]
    assert '"overlap_exchange": true' in next(
        ln for ln in r.stdout.splitlines() if " plan: " in ln)
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_prints_the_two_new_metrics(traced):
    """Span attrs are on the CPU's host plane too.  What one shard sends
    across y in a call: ``pressure``'s two slots and ``vel`` once, then
    after each of the five groups but the last both ``pressure`` slots
    again, ONE face each (a 2-wide axis), 16 planes of the shard's
    padded x by its lanes; ``xbytes`` counts two faces of both axes."""
    _r, res = traced
    got = res["metrics"]
    for name, (unit, _source) in NEW_METRICS.items():
        assert got[name]["unit"] == unit and got[name]["value"] > 0
    nx, _ny, nz = (n // r for n, r in zip(CFG["tiny_domain"], CFG["ranks"]))
    lanes = -(-(nz + 2 * 16) // 128) * 128
    slab_p, slab_v = (16 * (nx + pad) * lanes * 4 for pad in (48, 32))
    sent = 2 * slab_p + slab_v + (STEPS // 2 - 1) * 2 * slab_p
    assert got["parallel.y_exchange_mib_per_step"]["value"] \
        == pytest.approx(sent / STEPS / 2 ** 20, rel=1e-12)
    assert got["parallel.y_exchange_mib_per_step"]["value"] \
        < got["parallel.exchange_mib_per_step"]["value"] / 2
    assert got["parallel.slabs_per_step"]["value"] \
        == pytest.approx(4 * (3 + 4 * 2) / STEPS, rel=1e-12)


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control", devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3 and all("control(bf16)" in ln for ln in said)
