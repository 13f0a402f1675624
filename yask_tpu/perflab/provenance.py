"""Measurement provenance: the machine/load context attached to every
perf row.

The round-5 verdict found the CPU proxy regressed −24 % across the board
with *no investigation possible* because nothing recorded load context —
"possibly machine load, but that is exactly the point".  Every ledger row
now carries:

* fresh load average + CPU count (the noise axis on a shared host);
* static machine identity (CPU model, frequency governor, jax/jaxlib
  versions, platform/device kind, git SHA, env fingerprint) — cached per
  process, it cannot change mid-run;
* a calibration micro-kernel rate: a fixed pure-numpy 3-point stencil
  sweep whose throughput tracks the host's effective memory/compute
  speed, so two rows for the same key are comparable even across hosts
  ("same config, calib 0.9× → the 0.9× headline delta is the machine").

Tests stub the ``/proc``/``/sys`` roots; nothing here imports jax (the
version lookup uses importlib.metadata) so capture works even when the
backend is unusable.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import time
from typing import Dict, Optional

#: env vars whose values change jax/XLA behavior enough to make perf
#: rows non-comparable — fingerprinted (hashed) into every row.
_ENV_KEYS = ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_ENABLE_X64",
             "OMP_NUM_THREADS",
             "XLA_PYTHON_CLIENT_PREALLOCATE")

_CALIB_PTS = 1 << 20       # 1 Mi points per calibration sweep
_CALIB_REPS = 3

_static_cache: Dict[str, dict] = {}


def _read_first_line(path: str) -> str:
    try:
        with open(path) as f:
            return f.readline().strip()
    except OSError:
        return ""


def cpu_model(proc_root: str = "/proc") -> str:
    """`model name` from cpuinfo (first hit), '' when unavailable."""
    fallback = ""
    try:
        with open(os.path.join(proc_root, "cpuinfo")) as f:
            for line in f:
                low = line.lower()
                if ":" not in line:
                    continue
                val = line.split(":", 1)[1].strip()
                if low.startswith("model name"):
                    return val
                # ARM /proc/cpuinfo has no "model name"
                if low.startswith(("hardware", "cpu implementer")) \
                        and not fallback:
                    fallback = val
    except OSError:
        pass
    return fallback


def cpu_governor(sys_root: str = "/sys") -> str:
    return _read_first_line(os.path.join(
        sys_root, "devices/system/cpu/cpu0/cpufreq/scaling_governor"))


def loadavg(proc_root: str = "/proc") -> list:
    """[1m, 5m, 15m] load averages (prefers the stubbable proc file)."""
    line = _read_first_line(os.path.join(proc_root, "loadavg"))
    if line:
        try:
            return [float(x) for x in line.split()[:3]]
        except ValueError:
            pass
    try:
        return list(os.getloadavg())
    except (OSError, AttributeError):
        return [0.0, 0.0, 0.0]


def git_sha(repo_root: Optional[str] = None) -> str:
    """Short HEAD SHA (+ '-dirty' when the tree differs), '' off-repo."""
    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=10).stdout.strip()
        if not sha:
            return ""
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=root, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=10).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return ""


def _pkg_version(name: str) -> str:
    try:
        from importlib.metadata import version
        return version(name)
    except Exception:
        return ""


def env_fingerprint() -> str:
    """Stable digest of the perf-relevant environment variables."""
    blob = "\n".join(f"{k}={os.environ.get(k, '')}" for k in _ENV_KEYS)
    return hashlib.sha1(blob.encode()).hexdigest()[:12]


def calibration_gpts(reps: int = _CALIB_REPS) -> float:
    """Median throughput (GPts/s) of a fixed pure-numpy 1-D 3-point
    stencil sweep — the per-row yardstick for host speed under the load
    actually present at measurement time.  Pure numpy: independent of
    jax/XLA state, a few milliseconds total."""
    import numpy as np
    a = np.linspace(0.0, 1.0, _CALIB_PTS, dtype=np.float32)
    out = np.empty_like(a)
    rates = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        np.add(a[:-2], a[2:], out=out[1:-1])
        np.add(out[1:-1], a[1:-1], out=out[1:-1])
        out[1:-1] *= np.float32(1.0 / 3.0)
        dt = time.perf_counter() - t0
        rates.append(_CALIB_PTS / max(dt, 1e-12) / 1e9)
    rates.sort()
    return round(rates[len(rates) // 2], 4)


def _static_context(proc_root: str, sys_root: str) -> dict:
    key = f"{proc_root}|{sys_root}"
    if key not in _static_cache:
        _static_cache[key] = {
            "cpu_model": cpu_model(proc_root),
            "ncpu": os.cpu_count() or 0,
            "governor": cpu_governor(sys_root),
            "jax": _pkg_version("jax"),
            "jaxlib": _pkg_version("jaxlib"),
            "git_sha": git_sha(),
            "env_fp": env_fingerprint(),
        }
    return dict(_static_cache[key])


def capture_provenance(platform: str = "", device_kind: str = "",
                       calibrate: bool = True,
                       proc_root: str = "/proc",
                       sys_root: str = "/sys") -> dict:
    """One provenance dict for a row measured *now*: static machine
    identity (cached per process) + fresh load + calibration rate.

    ``platform``/``device_kind`` come from the producer's ``yk_env``
    (this module never imports jax, so it never opens the backend).
    ``calibrate=False`` skips the micro-kernel (e.g. per-row refresh
    where the suite-level calibration already stands).
    """
    prov = _static_context(proc_root, sys_root)
    prov["loadavg"] = loadavg(proc_root)
    if platform:
        prov["platform"] = platform
    if device_kind:
        prov["device_kind"] = device_kind
    if calibrate:
        prov["calib_gpts"] = calibration_gpts()
    return prov
