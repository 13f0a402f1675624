"""Harness CLI + tools tests (the analog of the reference's api-tests for
yask_main and the log-scraper)."""

import io
import os
import subprocess
import sys

import pytest

from yask_tpu.main import run_harness
from yask_tpu.tools.log_to_csv import scrape


def run_cli(args):
    out = io.StringIO()
    rc = run_harness(args, out=out)
    return rc, out.getvalue()


def test_list():
    rc, text = run_cli(["-list"])
    assert rc == 0
    assert "iso3dfd" in text and "ssg" in text


def test_missing_stencil_is_error():
    rc, text = run_cli([])
    assert rc == 2
    assert "-stencil" in text


@pytest.mark.parametrize("extra", [
    ["-bogus", "1"],
    ["-ledger"],      # the harness writes no perf record (PR 29)
])
def test_unknown_option_is_error(extra):
    from yask_tpu.utils.exceptions import YaskException
    with pytest.raises(YaskException, match="unrecognized options"):
        run_cli(["-stencil", "3axis", "-g", "8", *extra])


def test_perf_flow_log_keys():
    rc, text = run_cli(["-stencil", "3axis", "-g", "12",
                        "-trial_steps", "2", "-num_trials", "2"])
    assert rc == 0
    assert "mid-throughput (num-points/sec):" in text
    assert "best-throughput (num-points/sec):" in text
    # the log scraper reads its own harness output
    row = scrape(text)
    assert float(row["mid-throughput (num-points/sec)"]) > 0
    assert "elapsed-time (sec)" in row
    # the stats block carries the roofline lines
    assert "hbm-bytes-per-point (read+write):" in text
    assert "achieved-HBM (GB/s):" in text


def test_validate_flow():
    rc, text = run_cli(["-stencil", "test_scratch_1d", "-g", "16",
                        "-validate"])
    assert rc == 0
    assert "validation passed" in text


def test_validate_multi_stage():
    rc, text = run_cli(["-stencil", "test_stages_2d", "-g", "12",
                        "-validate"])
    assert rc == 0, text
    assert "validation passed" in text


def test_help():
    rc, text = run_cli(["-help"])
    assert rc == 0
    assert "-validate" in text


def test_examples_run():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for script, args in (("examples/swe_main.py", ["-g", "24", "-steps", "8"]),
                         ("examples/wave_eq_main.py",
                          ["-g", "24", "-steps", "8"])):
        p = subprocess.run([sys.executable, os.path.join(root, script)]
                           + args, capture_output=True, text=True, env=env,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-800:]
        assert "PASS" in p.stdout


def test_profile_flag(tmp_path):
    """-profile wraps the timed trials in a jax.profiler trace
    (SURVEY §5 tracing row: XLA-op-level profiling integration)."""
    import os
    d = str(tmp_path / "prof")
    rc, text = run_cli(["-stencil", "3axis", "-g", "16",
                        "-trial_steps", "2", "-num_trials", "1",
                        "-profile", d])
    assert rc == 0, text
    assert "profiling trials into" in text
    assert os.path.isdir(os.path.join(d, "plugins", "profile"))


def test_distributed_example_runs():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable,
         os.path.join(root, "examples", "distributed_iso3dfd_main.py"),
         "-g", "32", "-steps", "8"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-800:]
    assert "self-check passed" in p.stdout
