"""Where a run must fail -- exit code, no result line -- and where
``correct`` must come out false."""

import os
import shutil
import sys
import json

import pytest

from bench_util import BENCH, ROOT, manifest, result_line, run_cell

CELL = "cube-r1-1chip.advance"


def test_no_tpu_and_no_tiny_is_no_result():
    r = run_cell(CELL, tiny=False)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "does not fall back" in r.stderr
    assert result_line(r.stdout) is None


def test_tiny_is_refused_unless_the_cpu_is_named():
    r = run_cell(CELL, env_over={"JAX_PLATFORMS": ""})
    assert r.returncode != 0 and "JAX_PLATFORMS=cpu" in r.stderr
    assert result_line(r.stdout) is None


def test_fewer_devices_than_the_cell_asks_for_is_no_result():
    r = run_cell("iso3dfd-r8-4chip.advance", devices=2)
    assert r.returncode != 0 and "asks for 4 chip(s)" in r.stderr
    assert result_line(r.stdout) is None


def test_unknown_workload_is_no_result():
    r = run_cell("no-such.cell")
    assert r.returncode != 0 and result_line(r.stdout) is None


def test_benchmark_alone_without_the_program_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: there is no system under test."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_cell(CELL, run=str(tmp_path / "benchmark" / "run.py"),
                 cwd=str(tmp_path), env_over={"PYTHONPATH": ""})
    assert r.returncode != 0 and result_line(r.stdout) is None


def test_unknown_device_kind_is_an_error(monkeypatch):
    sys.path.insert(0, BENCH)
    import run as bench_run
    import types
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    cell = types.SimpleNamespace(tiny=False, chips=1)
    with pytest.raises(SystemExit) as exc:
        bench_run.find_device(cell)
    assert "not in benchmark/peaks.json" in str(exc.value)


def test_the_bf16_control_prints_correct_false():
    r = run_cell("iso3dfd-r8-1chip.advance", "--control")
    assert r.returncode == 0, r.stdout + r.stderr
    res = result_line(r.stdout)
    assert res["correct"] is False
    assert r.stdout.count("control(bf16)") == 3


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    """The rest of a run, in this process, with the timed path broken
    underneath: the fused chunk hands its state back untouched."""
    sys.path.insert(0, BENCH)
    import run as bench_run
    from yask_tpu.runtime.context import StencilContext
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(StencilContext, "_get_pallas_chunk",
                        lambda self, k: (lambda state, t: state))
    monkeypatch.setattr(bench_run, "_label", "")
    rc = bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                         "0.2", "--trace", "0", "--tiny"])
    out = capsys.readouterr().out
    res = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is False and res["failed"] == 0
    assert res["attempted"] >= 1


def test_a_cell_is_added_as_files_alone(tmp_path):
    """A later PR's view: a throwaway configuration, traffic mix and
    per-layer metric arrive as new files and new entries; no file that
    was there is edited."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "cube-r1-1chip.json").read_text())
    cfg.update(radius=2, wf_steps=2, tiny_domain=[48, 48, 64])
    (b / "configs" / "cube-r2-throwaway.json").write_text(json.dumps(cfg))
    (b / "traffic" / "short-calls.json").write_text(json.dumps(
        {"kind": "advance", "steps_per_call": 4, "why": "throwaway"}))
    (b / "metrics" / "runtime.units.py").write_text(
        "def read(run):\n    return len(run.units)\n")
    m = manifest()
    m["configs"].append({"name": "cube-r2-throwaway", "source": "test",
                         "file": "benchmark/configs/cube-r2-throwaway.json",
                         "reduced": [], "why": "throwaway"})
    name = "cube-r2-throwaway.short-calls"
    m["workloads"].append({"name": name, "config": "cube-r2-throwaway",
                           "traffic": "short-calls", "chips": 1,
                           "why": "throwaway"})
    m["end_to_end"][0]["workloads"].append(name)
    m["per_layer"].append({"name": "runtime.units", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "runtime", "moves": "gpts_per_s",
                           "workloads": [name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    for trace in (0, 1):
        r = run_cell(name, run=str(b / "run.py"), cwd=str(tmp_path),
                     trace=trace, env_over={"PYTHONPATH": ROOT})
        assert r.returncode == 0, r.stdout + r.stderr
        res = result_line(r.stdout)
        assert res["correct"] is True, r.stdout
        assert "after 4 steps" in r.stdout
        if trace:
            assert res["metrics"]["runtime.units"]["value"] >= 3
        else:
            assert set(res["metrics"]) == {"gpts_per_s", "setup_s"}
    assert all(p.read_bytes() == was for p, was in before.items())
    # nothing was written into the copy beside the new files
    assert {p.name for p in tmp_path.iterdir()} == {"benchmark",
                                                   "BENCHMARK.json"}
