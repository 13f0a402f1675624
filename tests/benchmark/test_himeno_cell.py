"""The cell ``himeno-l-1chip.sweeps-48``: its configuration states the
Himeno benchmark's ``jacobi`` at size LARGE whole (256 x 256 x 512,
nothing reduced), its source, every value it sets and its four
departures; its traffic says why 48 sweeps a call; its ``--tiny
--trace 1`` run on the CPU is ``correct`` after 48 sweeps at the cell's
K=4 and prints the per-layer metrics a CPU run can read; the bfloat16
control is not correct; the manifest keeps its rules with the cell in
it; and the reader that came with it, ``kernel.hbm_moved_share``, gives
a number from a plan row's two byte counts, ``None`` without either
and never over 100 for bytes that take at least their time at the peak.
Neither the cell's position nor any other metric's list is pinned:
cells that come later go after this one."""

import json
import os
import sys
import types

import pytest

from bench_util import BENCH, NOT_ON_CPU, ROOT, manifest, result_line, \
    run_cell
from test_manifest import cells_keep_the_rules

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

CELL = "himeno-l-1chip.sweeps-48"
CONFIG = "himeno-l-1chip"
NEW_METRIC = "kernel.hbm_moved_share"
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "sweeps-48.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(BENCH, "peaks.json")) as _f:
    HBM = json.load(_f)["TPU v5 lite"]["hbm_bytes_per_s"]


def test_the_configuration_states_source_size_values_and_departures():
    assert (CFG["stencil"], CFG["radius"]) == ("himeno", None)
    assert (CFG["mode"], CFG["dtype"]) == ("pallas", "float32")
    assert CFG["wf_steps"] in (1, 2, 4)
    assert CFG["domain"] == [256, 256, 512]
    assert CFG["ranks"] == [1, 1, 1] and CFG["chips"] == 1
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    for part in ("Himeno", "RIKEN", "himenoBMTxps.c", "jacobi()", "LARGE",
                 "MIMAX 257", "MKMAX 513", "256x256x512", "omega 0.8"):
        assert part in CFG["source"]
    assert set(CFG["consts"]) == {
        "a0", "a1", "a2", "a3", "b0", "b1", "b2", "c0", "c1", "c2",
        "wrk1", "bnd", "omega"}
    # every value set here is listed, "as recalled" on the source's
    assumed = CFG["assumed"]
    assert {"domain", "omega", "a0 a1 a2 c0 c1 c2", "a3", "b0 b1 b2",
            "wrk1", "bnd", "growth", "initial state", "state"} \
        <= set(assumed)
    assert sum("as recalled" in v for v in assumed.values()) >= 6
    assert set(CFG["departures"]) == {
        "what", "bnd layer", "coefficient arrays", "ring", "gosa"}
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == [] and row["source"] == CFG["source"]
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_traffic_says_why_48_sweeps_a_call():
    assert (TRAFFIC["kind"], TRAFFIC["steps_per_call"]) == ("advance", 48)
    assert TRAFFIC["loop"] == "closed, one caller"
    # whole groups at every K the cell is measured at
    assert all(TRAFFIC["steps_per_call"] % k == 0 for k in (1, 2, 4))
    assert "jacobi(nn)" in TRAFFIC["why"]
    assert "cone" in TRAFFIC["reduced"]["steps_per_call"]
    cell, = [w for w in manifest()["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "sweeps-48", CONFIG)


def test_the_manifest_keeps_its_rules_with_the_cell_in_it():
    m = manifest()
    cells_keep_the_rules(m)
    assert [w["chips"] for w in m["workloads"]].count(4) \
        <= max(1, len(m["workloads"]) // 2)
    assert len(m["configs"]) <= 24 and len(m["workloads"]) <= 24
    entry, = [p for p in m["per_layer"] if p["name"] == NEW_METRIC]
    assert (entry["unit"], entry["better"], entry["layer"],
            entry["moves"]) == ("%", "higher", "kernels", "gpts_per_s")
    assert CELL in entry["workloads"]
    assert os.path.isfile(os.path.join(BENCH, "metrics",
                                       NEW_METRIC + ".py"))
    # it reports what moves: the end-to-end metric, the kernel's time
    # and both roofline shares
    mine = {e["name"] for e in m["end_to_end"] + m["per_layer"]
            if "workloads" not in e or CELL in e["workloads"]}
    assert {"gpts_per_s", "setup_s", "kernel.fused_ms_per_step",
            "kernel.hbm_need_share", NEW_METRIC, "runtime.call_gap_ms",
            "device.idle_share"} <= mine


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_after_48_sweeps(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} p" for p in ("corner", "far", "seam")}
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and "after 48 steps" in ln for ln in said)
    plan, = [ln for ln in r.stdout.splitlines() if "plan: " in ln]
    plan = json.loads(plan.split("plan: ", 1)[1])
    assert plan["fuse_steps"] == CFG["wf_steps"] and plan["interpret"]
    assert res["metrics"]["compile.in_window"]["value"] == 0


def test_the_traced_tiny_run_names_the_metrics_a_cpu_can_read(traced):
    _r, res = traced
    m = manifest()
    listed = [p["name"] for p in m["per_layer"]
              if "workloads" not in p or CELL in p["workloads"]]
    assert NEW_METRIC in listed
    readable = {n for n in listed if not n.startswith(NOT_ON_CPU)}
    assert readable <= set(res["metrics"])
    assert {"device.idle_share", "runtime.enqueue_ms_per_call",
            "runtime.remainder_share", "compile.first_call_s"} <= readable
    # no fused kernel is traced where the kernel is interpreted: the
    # new reader finds no time to divide by and the line leaves it out
    assert NEW_METRIC not in res["metrics"]


def test_the_bf16_control_is_not_correct():
    r = run_cell(CELL, "--control")
    assert r.returncode == 0, r.stdout + r.stderr
    assert result_line(r.stdout)["correct"] is False
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert len(said) == 3 and all("control(bf16)" in ln for ln in said)


# -- the reader, on stand-in rows ---------------------------------------

#: the rows the program plans for the cell on a v5e at K = 1, 2, 4
#: (``tests/test_compiled_plans.py``): bytes fetched and written a sweep
ROWS = {1: (1988100096, 167772160), 2: (1900019712, 167772160),
        4: (1233125376, 83886080)}


@pytest.fixture(scope="module")
def reader():
    return bench_run.load_module("metrics", NEW_METRIC)


@pytest.mark.parametrize("k", sorted(ROWS))
def test_the_reader_divides_the_rows_bytes_by_the_peak_and_the_time(
        reader, k):
    fetched, written = ROWS[k]
    row = {"k": k, "fetch_bytes_per_step": fetched,
           "write_bytes_per_step": written}
    at_the_roof_ms = 1e3 * (fetched + written) / HBM
    assert reader.share(row, HBM, at_the_roof_ms) == pytest.approx(100.0)
    assert reader.share(row, HBM, 2 * at_the_roof_ms) \
        == pytest.approx(50.0)
    # never over 100 for bytes that take at least their time at the peak
    for slower in (1.0, 1.01, 1.5, 4.0, 100.0):
        assert 0 < reader.share(row, HBM, slower * at_the_roof_ms) \
            <= 100.0 + 1e-9


@pytest.mark.parametrize("missing", ["fetch_bytes_per_step",
                                     "write_bytes_per_step", "row",
                                     "time", "peak"])
def test_nothing_to_read_is_none_not_zero(reader, missing):
    row = {"k": 4, "fetch_bytes_per_step": ROWS[4][0],
           "write_bytes_per_step": ROWS[4][1]}
    row.pop(missing, None)
    assert reader.share(None if missing == "row" else row,
                        None if missing == "peak" else HBM,
                        None if missing == "time" else 2.0) is None


def test_the_reader_takes_the_row_with_the_largest_k(reader, monkeypatch):
    """``read(run)`` on a stand-in run: the plan rows of a context that
    holds two chunks, the peak's row, the fused kernel's time as
    ``program_spans`` would reduce it; a parent's rows (no
    ``write_bytes_per_step``) and a served cell (no context) read
    ``None``."""
    import program_spans
    rows = [{"k": 2, "fetch_bytes_per_step": 10 ** 12,
             "write_bytes_per_step": 10 ** 12},
            {"k": 4, "fetch_bytes_per_step": ROWS[4][0],
             "write_bytes_per_step": ROWS[4][1]}]
    kind = types.SimpleNamespace(
        ctx=types.SimpleNamespace(compiled_plans=lambda: rows))
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(kind=kind),
        peak={"hbm_bytes_per_s": HBM})
    monkeypatch.setattr(program_spans, "load",
                        lambda _run: {"fused_ms_per_step": 2.0})
    want = 100.0 * sum(ROWS[4]) / HBM / 2e-3
    assert reader.read(run) == pytest.approx(want) and 0 < want < 100
    for row in rows:
        del row["write_bytes_per_step"]                 # the parent
    assert reader.read(run) is None
    run.cell.kind = types.SimpleNamespace()             # a served cell
    assert reader.read(run) is None
    run.peak = None                                     # a CPU dry run
    assert reader.read(run) is None
