"""Every cell runs ``--tiny`` end to end on the CPU, a child process
each, and prints a line that keeps the contract."""

import pytest

from bench_util import manifest, result_line, run_cell

CELLS = [(w["name"], w["chips"]) for w in manifest()["workloads"]]


def reported(kind, name):
    return {m["name"] for m in manifest()[kind]
            if name in m.get("workloads", [name])}


@pytest.mark.parametrize("name,chips", CELLS)
def test_cell_runs_tiny_and_prints_a_contract_line(name, chips):
    r = run_cell(name, devices=chips)
    assert r.returncode == 0, r.stdout + r.stderr
    res = result_line(r.stdout)
    assert res is not None, r.stdout
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["dry_run"] is True
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips
    assert set(res["metrics"]) == reported("end_to_end", name)
    for m in res["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    # every other line of ours says what it is: never a device number
    ours = [ln for ln in r.stdout.splitlines()[:-1]
            if "check" in ln or "metric" in ln or "window" in ln]
    assert ours and all(ln.startswith("cpu dry-run: ") for ln in ours)
    assert r.stdout.count("limit 1.000e-04") >= 3   # numbers beside limits
    assert "compilations in window 0" in r.stdout
