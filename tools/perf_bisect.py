"""Replay one perf-ledger row-key across a git revision range.

The round-5 verdict's unanswerable question — "is the −24 % slide the
code or the machine?" — becomes a command: take the row key exactly as
it appears in ``PERF_LEDGER.jsonl``, check out each candidate revision
into a throwaway worktree under ``.perf_bisect/``, re-measure the SAME
configuration in each, and print one table.  All replays run back to
back on the same host with fresh load + calibration context per row, so
a value that moves only with the revision is code, and one that moves
with ``calib_gpts`` is the machine.

Row keys understood (the suite/bench naming scheme):

* ``<stencil> r=<R> <G>^3 <plat> <mode>[-K<k>][ bf16]`` — throughput
  replay (``iso3dfd r=8 128^3 fp32 cpu throughput (jit)`` and the
  harness' ``... harness (jit)`` spellings are parsed too);
* ``<stencil> <tag> <G>^3 <plat> wavefront-speedup`` — fused K=4 over
  K=1 pallas ratio (the cube residue row).

Each replay result is appended to the ledger with ``source="bisect"``
and the revision in ``extra`` (the sentinel excludes bisect rows from
guard baselines — historical revisions must not shift the median).

Usage::

    python tools/perf_bisect.py "iso3dfd r=8 128^3 fp32 cpu throughput (jit)" \
        47f415b HEAD [-trials 3] [-steps 4] [--keep]
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_WT_DIR = os.path.join(_ROOT, ".perf_bisect")

#: the per-revision replay, run with cwd=<worktree> so it imports THAT
#: revision's yask_tpu.  Only the oldest-stable API surface is used
#: (yk_factory / apply_command_line_options / run_solution), so specs
#: replay across every round boundary.
_REPLAY = r"""
import json, sys, time
spec = json.loads(sys.argv[1])

from yask_tpu import yk_factory
from yask_tpu.runtime.init_utils import init_solution_vars

fac = yk_factory()
env = fac.new_env()

def build(mode, wf):
    ctx = fac.new_solution(env, stencil=spec["stencil"],
                           radius=spec["radius"] or None)
    ctx.apply_command_line_options(f"-g {spec['g']} -wf_steps {wf}")
    ctx.get_settings().mode = mode
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx

def measure(ctx):
    g, steps, trials = spec["g"], spec["steps"], spec["trials"]
    npts = g ** len(ctx.get_domain_dim_names())
    t = 0
    ctx.run_solution(t, t + steps - 1)   # warm (compile)
    t += steps
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        ctx.run_solution(t, t + steps - 1)
        dt = time.perf_counter() - t0
        t += steps
        rates.append(npts * steps / dt / 1e9)
    rates.sort()
    return rates[len(rates) // 2]

if spec["kind"] == "wavefront-speedup":
    base = measure(build("pallas", 1))
    fused = measure(build("pallas", 4))
    out = {"value": round(fused / max(base, 1e-12), 4), "unit": "x",
           "k1_gpts": round(base, 4), "k4_gpts": round(fused, 4)}
else:
    out = {"value": round(measure(build(spec["mode"], spec["wf"])), 4),
           "unit": "GPts/s"}
print("PERF_BISECT_RESULT " + json.dumps(out))
"""


def parse_key(key: str) -> dict:
    """Row key → replay spec; raises ValueError on an unknown shape."""
    m = re.search(r"(\d+)\^3", key)
    if m:
        g = int(m.group(1))
    else:
        # the harness' cube spelling: g=64x64x64
        hm = re.search(r"g=(\d+(?:x\d+)+)", key)
        if not hm or len(set(hm.group(1).split("x"))) != 1:
            raise ValueError(f"no cubic domain size in row key: {key!r}")
        g = int(hm.group(1).split("x")[0])
    stencil = key.split()[0]
    rm = re.search(r"\br=(\d+)", key)
    radius = int(rm.group(1)) if rm else 0
    if "wavefront-speedup" in key:
        return {"kind": "wavefront-speedup", "stencil": stencil,
                "radius": radius, "g": g}
    # mode: "(jit)" / "(pallas-K2)" contract+harness style, or the
    # suite's trailing "jit" / "pallas-K2" token
    mode, wf = "jit", 1
    pm = re.search(r"\(?\b(jit|pallas(?:-K(\d+))?)\)?(?:\s+bf16)?\s*$",
                   key) or re.search(r"\((jit|pallas(?:-K(\d+))?)\)", key)
    if pm:
        mode = "pallas" if pm.group(1).startswith("pallas") else "jit"
        wf = int(pm.group(2)) if pm.group(2) else 1
    return {"kind": "throughput", "stencil": stencil, "radius": radius,
            "g": g, "mode": mode, "wf": wf}


def _git(*args: str, cwd: str = _ROOT) -> str:
    return subprocess.run(["git", *args], cwd=cwd, text=True,
                          stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT).stdout.strip()


def replay_at(rev: str, spec: dict, timeout: float = 600.0) -> dict:
    """Measure the spec at one revision (throwaway worktree)."""
    sha = _git("rev-parse", "--short", rev)
    wt = os.path.join(_WT_DIR, sha)
    if not os.path.isdir(wt):
        out = _git("worktree", "add", "--detach", wt, rev)
        if not os.path.isdir(wt):
            return {"rev": rev, "error": f"worktree add failed: {out[:200]}"}
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": wt})
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _REPLAY, json.dumps(spec)],
            cwd=wt, env=env, text=True, timeout=timeout,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    except subprocess.TimeoutExpired:
        return {"rev": rev, "sha": sha, "error": "timeout"}
    for ln in proc.stdout.splitlines():
        if ln.startswith("PERF_BISECT_RESULT "):
            res = json.loads(ln[len("PERF_BISECT_RESULT "):])
            return {"rev": rev, "sha": sha, **res}
    return {"rev": rev, "sha": sha,
            "error": (proc.stderr.strip().splitlines() or ["no output"])
            [-1][:200]}


def cleanup() -> None:
    if not os.path.isdir(_WT_DIR):
        return
    for name in os.listdir(_WT_DIR):
        _git("worktree", "remove", "--force",
             os.path.join(_WT_DIR, name))
    _git("worktree", "prune")
    shutil.rmtree(_WT_DIR, ignore_errors=True)


def bisect(key: str, revs, trials: int = 3, steps: int = 4,
           keep: bool = False, ledger: bool = True, out=None):
    out = out or sys.stdout
    spec = dict(parse_key(key), trials=trials, steps=steps)
    out.write(f"replaying {spec} at {len(revs)} revision(s)\n")
    results = []
    try:
        for rev in revs:
            from yask_tpu.perflab import capture_provenance
            res = replay_at(rev, spec)
            # per-replay calibration: same-host noise yardstick riding
            # next to each value in the table AND the ledger row
            prov = capture_provenance(platform="cpu", device_kind="cpu")
            res["calib_gpts"] = prov.get("calib_gpts")
            results.append(res)
            out.write(json.dumps(res) + "\n")
            if ledger and "error" not in res:
                from yask_tpu.perflab.sentinel import guard_and_append
                guard_and_append(
                    key, res["value"], res["unit"], "cpu", "bisect",
                    prov, extra={"rev": res.get("sha", rev),
                                 **{k: v for k, v in res.items()
                                    if k in ("k1_gpts", "k4_gpts")}})
    finally:
        if not keep:
            cleanup()
    ok = [r for r in results if "error" not in r]
    if len(ok) >= 2:
        first, last = ok[0], ok[-1]
        ratio = last["value"] / max(first["value"], 1e-12)
        out.write(f"{first.get('sha')} -> {last.get('sha')}: "
                  f"{first['value']} -> {last['value']} {last['unit']} "
                  f"({ratio:.3f}x; calib "
                  f"{first['calib_gpts']} -> {last['calib_gpts']})\n")
    return results


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    trials, steps, keep, ledger = 3, 4, False, True
    pos = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "-trials":
            trials = int(argv[i + 1]); i += 2
        elif a == "-steps":
            steps = int(argv[i + 1]); i += 2
        elif a == "--keep":
            keep = True; i += 1
        elif a == "--no-ledger":
            ledger = False; i += 1
        else:
            pos.append(a); i += 1
    if len(pos) < 3:
        sys.stderr.write(__doc__ + "\n")
        return 2
    key, revs = pos[0], pos[1:]
    results = bisect(key, revs, trials=trials, steps=steps, keep=keep,
                     ledger=ledger)
    return 0 if all("error" not in r for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
