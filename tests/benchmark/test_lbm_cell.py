"""The cell ``lbm-d3q19-ldc-1chip.advance``: its configuration states
SPEC 470.lbm / 519.lbm_r's lid-driven cavity (D3Q19, BGK, ``OMEGA``
1.95, the lid's velocity, ``OBSTACLE`` on six faces and ``ACCEL`` in two
layers), the scale it was raised to and why, every value it sets and
its departures; its ``--tiny --trace 1`` run on the CPU is ``correct``
in all nineteen fields of all three probes after 10 steps and prints
the per-layer metrics a CPU run can read; the bfloat16 control is not
correct; the manifest keeps its rules with the cell in it; and the
reader that came with it, ``kernel.dag_gops_per_s``, gives a number
from a plan row's ``dag_ops_per_point`` and ``None`` without one.
Neither the cell's position nor any metric's list is pinned: cells
that come later go after this one."""

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

CELL = "lbm-d3q19-ldc-1chip.advance"
CONFIG = "lbm-d3q19-ldc-1chip"
NEW_METRIC = "kernel.dag_gops_per_s"
POINTS = 256 * 256 * 512
with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as _f:
    CFG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "advance.json")) as _f:
    TRAFFIC = json.load(_f)


def test_the_configuration_states_source_scale_values_and_departures():
    assert (CFG["stencil"], CFG["radius"]) == ("lbm_d3q19", None)
    assert (CFG["mode"], CFG["dtype"]) == ("pallas", "float32")
    assert CFG["wf_steps"] == 1 and CFG["domain"] == [256, 256, 512]
    assert CFG["ranks"] == [1, 1, 1] and CFG["chips"] == 1
    assert CFG["reduced"] == [] and CFG["tolerance"] == 1e-4
    for part in ("SPEC", "470.lbm", "519.lbm_r", "lbm.c",
                 "LBM_performStreamCollide",
                 "LBM_initializeSpecialCellsForLDC", "ldc",
                 "no obstacle file", "D3Q19", "BGK", "OMEGA 1.95"):
        assert part in CFG["source"], part
    assert len(CFG["source"]) <= 200
    assert set(CFG["consts"]) == {"omega", "u_lid_x", "u_lid_y", "fluid",
                                  "accel"}
    assert (CFG["consts"]["omega"], CFG["consts"]["u_lid_x"],
            CFG["consts"]["u_lid_y"]) == (1.8, 0.005, 0.002)
    # lbm.c's OMEGA 1.95 does not stay finite under the seeding law: said
    assert "1.95" in CFG["assumed"]["omega u_lid_x u_lid_y"]
    # the dry run keeps z whole, where accel's two layers land
    assert CFG["tiny_domain"][2] == CFG["domain"][2]
    # every value set here is listed, "as recalled" on the source's;
    # the scale above all
    assumed = CFG["assumed"]
    assert {"what", "domain", "omega u_lid_x u_lid_y", "masks",
            "initial state", "state", "tolerance"} <= set(assumed)
    assert sum("as recalled" in v for v in assumed.values()) >= 5
    for part in ("100 x 100 x 130", "256 x 256 x 512", "RAISED"):
        assert part in assumed["domain"], part
    assert {"what", "pull", "arrays", "float32", "z fastest"} \
        <= set(CFG["departures"])
    assert "closed" in CFG["guarantees"]
    row, = [c for c in manifest()["configs"] if c["name"] == CONFIG]
    assert row["file"] == f"benchmark/configs/{CONFIG}.json"
    assert row["reduced"] == [] and row["source"] == CFG["source"]
    assert os.path.isfile(os.path.join(ROOT, row["file"]))


def test_the_manifest_keeps_its_rules_with_the_cell_in_it():
    m = manifest()
    cells_keep_the_rules(m)
    assert [w["chips"] for w in m["workloads"]].count(4) \
        <= max(1, len(m["workloads"]) // 2)
    assert len(m["configs"]) <= 24 and len(m["workloads"]) <= 24
    cell, = [w for w in m["workloads"] if w["name"] == CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) \
        == (1, "advance", CONFIG)
    assert (TRAFFIC["kind"], TRAFFIC["steps_per_call"]) == ("advance", 10)
    entry, = [p for p in m["per_layer"] if p["name"] == NEW_METRIC]
    assert (entry["unit"], entry["better"], entry["layer"],
            entry["moves"], entry["source"]) \
        == ("Gop/s", "higher", "kernels", "gpts_per_s", "device_trace")
    assert CELL in entry["workloads"]
    assert os.path.isfile(os.path.join(BENCH, "metrics",
                                       NEW_METRIC + ".py"))
    # it reports what moves: the end-to-end metric, the kernel's time,
    # both shares of the HBM roof and the rate beside them; and it is
    # in the lists that tests hold to every one-chip `advance` cell
    mine = {e["name"] for e in m["end_to_end"] + m["per_layer"]
            if "workloads" not in e or CELL in e["workloads"]}
    assert {"gpts_per_s", "setup_s", "kernel.fused_ms_per_step",
            "kernel.hbm_need_share", "kernel.hbm_moved_share", NEW_METRIC,
            "kernel.margin_overhead", "kernel.vmem_need_share",
            "runtime.passthrough_copy_ms_per_step", "runtime.call_gap_ms",
            "runtime.peak_device_gib", "device.idle_share"} <= mine


@pytest.fixture(scope="module")
def traced():
    r = run_cell(CELL, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    return r, result_line(r.stdout)


def test_the_traced_tiny_run_is_correct_in_all_nineteen_fields(traced):
    r, res = traced
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 1 and res["dry_run"] is True
    said = [ln for ln in r.stdout.splitlines() if " check " in ln]
    assert {ln.split(" check ")[1].split(" at ")[0] for ln in said} \
        == {f"{p} f{i}" for p in ("corner", "far", "seam")
            for i in range(19)}
    assert len(said) == 57
    assert all(f"limit {float(CFG['tolerance']):.3e}" in ln
               and "after 10 steps" in ln for ln in said)
    # every gap at least ten times inside the limit
    assert max(float(ln.split(" error ")[1].split()[0]) for ln in said) \
        <= CFG["tolerance"] / 10
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
    assert len(said) == 57 and all("control(bf16)" in ln for ln in said)
    # not by one field of one probe: bfloat16 fails every one
    assert min(float(ln.split(" error ")[1].split()[0]) for ln in said) \
        > 10 * CFG["tolerance"]


# -- the reader, on stand-in rows ---------------------------------------

@pytest.fixture(scope="module")
def reader():
    return bench_run.load_module("metrics", NEW_METRIC)


@pytest.mark.parametrize("ops,ms", [(280, 11.0), (280, 5.5), (48, 2.0)])
def test_the_reader_multiplies_the_rows_operations_by_the_points_a_second(
        reader, ops, ms):
    row = {"k": 1, "dag_ops_per_point": ops, "ops_per_point": 6599}
    assert reader.rate(row, POINTS, ms) \
        == pytest.approx(ops * POINTS / (ms * 1e-3) / 1e9)
    # half the time, twice the rate; the trees' count plays no part
    assert reader.rate(row, POINTS, ms / 2) \
        == pytest.approx(2 * reader.rate(row, POINTS, ms))
    row["ops_per_point"] = 1
    assert reader.rate(row, POINTS, ms) \
        == pytest.approx(ops * POINTS / (ms * 1e-3) / 1e9)


@pytest.mark.parametrize("missing", ["dag_ops_per_point", "row", "time"])
def test_nothing_to_read_is_none_not_zero(reader, missing):
    row = {"k": 1, "dag_ops_per_point": 280}
    row.pop(missing, None)
    assert reader.rate(None if missing == "row" else row, POINTS,
                       None if missing == "time" else 11.0) is None


def test_the_reader_takes_the_row_with_the_largest_k(reader, monkeypatch):
    """``read(run)`` on a stand-in run: the plan rows of a context that
    holds two chunks, one device's points, the fused kernel's time as
    ``program_spans`` would reduce it; a parent's rows (no
    ``dag_ops_per_point``), a run whose trace holds no fused kernel and
    a served cell (no context) read ``None``."""
    import program_spans
    rows = [{"k": 1, "dag_ops_per_point": 10 ** 6},
            {"k": 2, "dag_ops_per_point": 280}]
    kind = types.SimpleNamespace(
        ctx=types.SimpleNamespace(compiled_plans=lambda: rows))
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(kind=kind), points=float(POINTS),
        trace={"devices": 1})
    spans = {"fused_ms_per_step": 11.0}
    monkeypatch.setattr(program_spans, "load", lambda _run: spans)
    assert reader.read(run) == pytest.approx(280 * POINTS / 11e-3 / 1e9)
    run.trace = {"devices": 4}              # a shard's points a device
    assert reader.read(run) \
        == pytest.approx(280 * POINTS / 4 / 11e-3 / 1e9)
    spans.clear()                           # no fused kernel traced
    assert reader.read(run) is None
    spans["fused_ms_per_step"] = 11.0
    for row in rows:
        del row["dag_ops_per_point"]                    # the parent
    assert reader.read(run) is None
    run.cell.kind = types.SimpleNamespace()             # a served cell
    assert reader.read(run) is None
