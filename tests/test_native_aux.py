"""Native library, tracing, trace-diff, and checkpoint/resume tests."""

import os

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu import native


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


# ---------------------------------------------------------------------------
# native library (built on demand by the loader; g++ is present in CI)
# ---------------------------------------------------------------------------


def test_native_builds_and_loads():
    assert native.available(), "native host library failed to build"
    assert native.get_lib().yt_version() >= 1


def test_native_layout_roundtrip():
    sizes = [3, 4, 5]
    pts = np.array([[0, 0, 0], [2, 3, 4], [1, 2, 3]], dtype=np.int64)
    offs = native.layout(sizes, pts)
    assert offs.tolist() == [0, 59, 33]
    back = native.unlayout(sizes, offs)
    np.testing.assert_array_equal(back, pts)
    with pytest.raises(ValueError):
        native.layout(sizes, np.array([[3, 0, 0]], dtype=np.int64))


def test_native_matches_python_fd():
    # the native path is used by get_center_fd_coefficients when available
    from yask_tpu.utils.fd_coeff import get_center_fd_coefficients
    c = get_center_fd_coefficients(2, 2)
    assert c == pytest.approx([-1 / 12, 4 / 3, -5 / 2, 4 / 3, -1 / 12])
    w = native.fd_weights(1, 0.0, [-1.0, 0.0, 1.0])
    assert w == pytest.approx([-0.5, 0.0, 0.5])


def test_native_compact_factors():
    assert sorted(native.compact_factors(12, 2)) == [3, 4]
    assert sorted(native.compact_factors(8, 3)) == [2, 2, 2]


def test_native_divergence_scan():
    a = np.zeros(100, dtype=np.float32)
    b = a.copy()
    assert native.first_divergence(a, b) == -1
    b[42] = 1.0
    assert native.first_divergence(a, b) == 42
    assert native.count_divergence(a, b) == 1
    b[7] = np.nan
    assert native.first_divergence(a, b) == 7


# ---------------------------------------------------------------------------
# tracing + analyze_trace
# ---------------------------------------------------------------------------


def _run_traced(env, tmp, tag, poison_step=None):
    ctx = yk_factory().new_solution(env, stencil="test_2d")
    ctx.apply_command_line_options("-g 12")
    ctx.prepare_solution()
    ctx.get_var("u").set_elements_in_seq(0.1)
    d = os.path.join(tmp, tag)
    ctx.set_trace_dir(d)
    ctx.run_solution(0, 3)
    if poison_step is not None:
        # corrupt one written value in the dump to emulate a divergence
        p = os.path.join(d, f"step_{poison_step}.npz")
        data = dict(np.load(p))
        data["u"][5, 6] += 1.0
        np.savez(p, **data)
    return d


def test_trace_and_analyze(env, tmp_path):
    from yask_tpu.tools.analyze_trace import compare_traces
    da = _run_traced(env, str(tmp_path), "a")
    db = _run_traced(env, str(tmp_path), "b")
    assert sorted(os.listdir(da)) == [f"step_{t}.npz" for t in range(1, 5)]
    assert compare_traces(da, db) is None
    dc = _run_traced(env, str(tmp_path), "c", poison_step=3)
    res = compare_traces(da, dc)
    assert res is not None
    t, var, coords, va, vb = res
    assert (t, var, coords) == (3, "u", (5, 6))


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------


def test_checkpoint_resume(env, tmp_path):
    def fresh():
        c = yk_factory().new_solution(env, stencil="3axis", radius=1)
        c.apply_command_line_options("-g 12")
        c.prepare_solution()
        c.get_var("A").set_elements_in_seq(0.1)
        return c

    a = fresh()
    a.run_solution(0, 5)

    b = fresh()
    b.run_solution(0, 2)
    ck = str(tmp_path / "ck.npz")
    b.save_checkpoint(ck)

    c = fresh()  # different history; restore overwrites it
    c.run_solution(0, 0)
    c.load_checkpoint(ck)
    assert c._cur_step == b._cur_step
    c.run_solution(3, 5)
    assert c.compare_data(a) == 0


def test_checkpoint_shape_mismatch(env, tmp_path):
    from yask_tpu.utils.exceptions import YaskException
    a = yk_factory().new_solution(env, stencil="3axis", radius=1)
    a.apply_command_line_options("-g 12")
    a.prepare_solution()
    ck = str(tmp_path / "ck.npz")
    a.save_checkpoint(ck)
    b = yk_factory().new_solution(env, stencil="3axis", radius=1)
    b.apply_command_line_options("-g 16")
    b.prepare_solution()
    with pytest.raises(YaskException):
        b.load_checkpoint(ck)


# ---------------------------------------------------------------------------
# C/C++ kernel API (embedded-interpreter front end, reference yk_* C++ API)
# ---------------------------------------------------------------------------


def test_cpp_api_demo(tmp_path):
    """Build the C API library + demo app and run it end to end: the
    C++ front end must drive the same runtime (build, configure, seed,
    run, oracle-compare) — the analog of the reference's C++ kernel API
    test (``yask_kernel_api_test.cpp``)."""
    import shutil
    import subprocess
    import sys
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    # embed THIS interpreter (the one with jax installed), not whatever
    # python3-config happens to be on PATH
    cfg = sys.executable + "-config"
    if not os.path.exists(cfg):
        cfg = os.path.join(os.path.dirname(sys.executable),
                           "python3-config")
    if not os.path.exists(cfg):
        cfg = shutil.which("python3-config")
    if cfg is None:
        pytest.skip("no python3-config for embedding")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ndir = os.path.join(repo, "yask_tpu", "native")
    r = subprocess.run(["make", "-C", ndir, "capi", f"PYCFG={cfg}"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    env_ = dict(os.environ)
    env_["JAX_PLATFORMS"] = "cpu"
    env_["PYTHONPATH"] = os.pathsep.join(
        [repo] + [p for p in env_.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    r = subprocess.run([os.path.join(ndir, "capi_demo")],
                       capture_output=True, text=True, timeout=300,
                       env=env_)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "capi demo passed" in r.stdout
