"""chip_smoke.py on the CPU: the dry run passes every stage, and the
command fails — exit code, no result line — where it must: no TPU, a
failing stage, an unknown device kind, ``--tiny`` without CPU named."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
sys.path.insert(0, ROOT)


def _run(args, tmp_path, **env_over):
    env = dict(os.environ)
    env.pop("YT_FAULT_PLAN", None)
    for k, v in env_over.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_tiny_passes_all_five_stages(tmp_path):
    r = _run(["--tiny"], tmp_path, JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert r.returncode == 0, r.stdout + r.stderr
    lines = r.stdout.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "dry_run": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    # every other line says what it is: never a device number
    assert all(ln.startswith("cpu dry-run: ") for ln in lines[:-1])
    assert "GPts/s" not in r.stdout
    for marker in ("jit vs oracle: 0 mismatches",
                   "pallas-K2 vs oracle: 0 mismatches",
                   '"interpret": true',
                   "served vs direct run over steps 0..3: 0 mismatches",
                   "x4 64^3 vs oracle: 0 mismatches",
                   "vs shard_map 96^3: 0 mismatches over 8 slabs"):
        assert marker in r.stdout, marker
    # the journal went to the output directory, not the repo root
    assert (tmp_path / "out" / "SERVE_JOURNAL.jsonl").exists()


def test_default_invocation_fails_without_a_tpu(tmp_path):
    r = _run([], tmp_path, JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "does not fall back" in r.stderr
    assert not _result_lines(r.stdout)


def test_tiny_refused_unless_cpu_named(tmp_path):
    r = _run(["--tiny"], tmp_path, JAX_PLATFORMS=None)
    assert r.returncode != 0
    assert "JAX_PLATFORMS=cpu" in r.stderr
    assert not _result_lines(r.stdout)


def test_failing_stage_ends_the_run_nonzero(tmp_path, monkeypatch, capsys):
    import chip_smoke
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(
        chip_smoke, "stage_jit",
        lambda *a, **k: chip_smoke.fail("injected stage failure"))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--tiny", "--out", str(tmp_path)])
    assert exc.value.code not in (0, None)      # sys.exit(str) → 1
    assert not _result_lines(capsys.readouterr().out)

    # nothing between a stage and the exit swallows its exception
    def boom(*a, **k):
        raise RuntimeError("stage raised")
    monkeypatch.setattr(chip_smoke, "stage_jit", boom)
    with pytest.raises(RuntimeError, match="stage raised"):
        chip_smoke.main(["--tiny", "--out", str(tmp_path)])
    assert not _result_lines(capsys.readouterr().out)


def test_unknown_device_kind_is_an_error(monkeypatch):
    from yask_tpu.backend import capability_for_platform
    from yask_tpu.runtime.env import yk_env
    from yask_tpu.utils.exceptions import YaskException
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(YaskException, match="TPU v9"):
        yk_env(devices=[dev]).get_hbm_peak_bytes_per_sec()
    with pytest.raises(KeyError, match="TPU v9"):
        capability_for_platform("tpu", "TPU v9")
    assert capability_for_platform("tpu", "TPU v5 lite").name == "tpu:v5e"

    # ... and chip_smoke's stage 1 does not get past it
    import jax
    import chip_smoke
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    args = types.SimpleNamespace(tiny=False, chips=None)
    with pytest.raises(YaskException, match="TPU v9"):
        chip_smoke.stage_device(args)


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch, tmp_path):
    import jax
    from yask_tpu.runtime import env as yenv
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert yenv.place_compile_cache() == os.path.join(ROOT,
                                                          ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
        # placed from outside: the program sets nothing in code
        jax.config.update("jax_compilation_cache_dir", "untouched")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert yenv.place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
