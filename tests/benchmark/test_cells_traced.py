"""The traced run of a direct, the four-device and the served cell on
the CPU: per-layer metrics of the cell and no others, ``busy_s`` and
``window_s`` in ``device``, a breakdown under the names it prints."""

import pytest

from bench_util import manifest, result_line, run_cell

#: what a CPU dry run cannot read: there is no device plane, no Pallas
#: custom call (the kernel is interpreted) and no memory statistic
NOT_ON_CPU = ("kernel.", "parallel.", "runtime.call_gap_ms",
              "runtime.peak_device_gib")


@pytest.mark.parametrize("name,chips", [
    ("cube-r1-1chip.advance", 1),
    ("iso3dfd-r8-4chip.advance", 4),
    ("iso3dfd-r8-1chip.snapshots", 1)])
def test_traced_run_reports_the_cells_per_layer_metrics(name, chips):
    r = run_cell(name, devices=chips, trace=1)
    assert r.returncode == 0, r.stdout + r.stderr
    res = result_line(r.stdout)
    assert res["correct"] is True and res["failed"] == 0
    mine = {m["name"] for m in manifest()["per_layer"]
            if name in m.get("workloads", [name])}
    assert set(res["metrics"]) <= mine
    assert {n for n in mine if not n.startswith(NOT_ON_CPU)} \
        <= set(res["metrics"])
    assert res["metrics"]["compile.in_window"]["value"] == 0
    dev = res["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    bd = res["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(n.startswith("bench.") for n, _s in bd["idle_gaps"])
