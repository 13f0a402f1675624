"""Shared by the benchmark's tests: where things are, how a cell is
run as a child process on the CPU."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RUN = os.path.join(BENCH, "run.py")


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(workload, *extra, run=RUN, devices=1, cwd=None, env_over=None,
             tiny=True, seed=2147483777, trace=0):
    """One run of ``run.py`` in a child of its own, on the CPU by name;
    ``devices`` virtual CPU devices in the child's own ``XLA_FLAGS``.
    The child is niced and single-threaded: tier-1 runs six workers on
    few cores, and some of its older tests read the wall clock."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        "--xla_cpu_multi_thread_eigen=false "
        "intra_op_parallelism_threads=1")
    env["OMP_NUM_THREADS"] = env["OPENBLAS_NUM_THREADS"] = "1"
    env.pop("BENCH_KEEP_TRACE", None)
    env.update(env_over or {})
    nice = ["nice", "-n", "19"] if shutil.which("nice") else []
    cmd = [*nice, sys.executable, run, "--workload", workload, "--seed",
           str(seed), "--seconds", "1", "--trace", str(trace), *extra]
    if tiny:
        cmd.append("--tiny")
    return subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=900)


def result_line(stdout):
    """The contract's last line, parsed; None where there is none."""
    lines = stdout.strip().splitlines()
    if not lines or not lines[-1].startswith("{"):
        return None
    return json.loads(lines[-1])
