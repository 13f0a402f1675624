#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls, at the full width of upstream's flagship (``iso3dfd``, radius 8 =
order 16, fp32) with the planner's DEFAULT settings, and checks what
comes out against the numpy oracle:

1. device: a TPU whose ``device_kind`` is in the HBM-peak and the
   capability tables (anything else is an error, never a fallback);
2. direct path, XLA (``-mode jit``, 512³) vs the ``ref`` oracle;
3. direct path, fused Pallas on Mosaic (``-mode pallas -wf_steps 2``)
   vs the same oracle, compiled — not interpreted;
4. served path: an in-process ``StencilServer`` session, three ``run``
   requests, outputs vs the direct run;
5. four chips (when ≥ 4 devices are visible; ``--chips 4`` requires
   them): ``shard_pallas`` K=2 at 512³ vs the oracle, and at 1024³ vs
   ``shard_map`` on the same mesh, slab by slab, with per-device
   ``bytes_in_use``.

A stage that fails raises: the run ends non-zero and prints no result.
Rates are printed for information only.  ``--tiny`` (g=64, Pallas
interpret) is the same command for debugging on a CPU; it is accepted
only with ``JAX_PLATFORMS=cpu`` set by name and labels every line.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

RADIUS = 8
STEPS = 4            # two K=2 groups: ring rotation, skew carry and the
#                      parity-staged write-back cross a group boundary
WF = 2
EPS, ABS_EPS = 1e-3, 1e-4
TRIALS, TRIAL_STEPS = 3, 10
SEED = 0.05          # init_solution_vars' law for 'pressure'
MAX_BYTES_RATIO = 1.5

_label = ""


def say(msg: str) -> None:
    print(_label + msg, flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def build(fac, env, g: int, mode: str, wf: int = 0, ranks: int = 1):
    """README's own flow, then the smoke's initial state: a dense
    position-dependent pressure field (every tile computes something —
    a lone impulse leaves 99.9 % of a 512³ domain at 0 == 0) with a
    point source on top, and ``vel = 0.1``."""
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=RADIUS)
    ctx.apply_command_line_options(
        f"-g {g} -mode {mode}" + (f" -wf_steps {wf}" if wf else ""))
    if ranks > 1:
        ctx.set_num_ranks("x", ranks)
    ctx.prepare_solution()
    ctx.get_var("vel").set_all_elements_same(0.1)
    p = ctx.get_var("pressure")
    p.set_elements_in_seq(SEED)
    p.set_element(1.0, [0, g // 2, g // 2, g // 2])
    return ctx


def mismatches(x, y) -> int:
    """``compare_data``'s point test on two host arrays."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    tol = ABS_EPS + EPS * np.maximum(np.abs(x), np.abs(y))
    return int((np.abs(x - y) > tol).sum()) + int(
        x.size - np.isfinite(x).sum())


def must_match(what: str, ctx, oracle) -> None:
    t0 = time.perf_counter()
    bad = ctx.compare_data(oracle, epsilon=EPS, abs_epsilon=ABS_EPS)
    say(f"  {what}: {bad} mismatches "
        f"(compare {time.perf_counter() - t0:.1f} s)")
    if bad:
        fail(f"{what}: {bad} mismatches")


def newest(ctx, g: int, x0: int = 0, x1: int = None):
    """Newest pressure interior, rows ``[x0, x1)`` of x (default all)."""
    t = ctx.get_var("pressure").get_last_valid_step_index()
    return ctx.get_var("pressure").get_elements_in_slice(
        [t, x0, 0, 0], [t, (g if x1 is None else x1) - 1, g - 1, g - 1])


def time_trials(ctx, g: int, dev: str, what: str, timed: bool) -> None:
    """Warm-up + TRIALS × TRIAL_STEPS steps; ``run_solution`` ends in
    ``block_until_ready``.  A rate is printed only for a chip."""
    t = ctx.get_var("pressure").get_last_valid_step_index()
    n = TRIAL_STEPS if timed else WF
    ctx.run_solution(t, t + n - 1)          # warm-up (compiles length n)
    t += n
    rates = []
    for _ in range(TRIALS if timed else 1):
        t0 = time.perf_counter()
        ctx.run_solution(t, t + n - 1)
        rates.append(g ** 3 * n / (time.perf_counter() - t0) / 1e9)
        t += n
    for i in range(4):                     # slab by slab, as stage 5
        if not np.isfinite(newest(ctx, g, i * g // 4,
                                  (i + 1) * g // 4)).all():
            fail(f"{what}: non-finite field after the trials")
    if timed:
        rates.sort()
        say(f"  {what}: {rates[len(rates) // 2]:.3f} GPts/s median of "
            f"{TRIALS} x {n} steps at {g}^3 on {dev} "
            f"(min {rates[0]:.3f}, max {rates[-1]:.3f}; information only)")


PLAN_KEYS = ("interpret", "fuse_steps", "block", "skew", "skew_dims",
             "pipeline_dmas", "pipeline_out", "tile_bytes",
             "margin_overhead", "overlap_exchange")


def say_plan(ctx, env) -> None:
    """Print the plan that RAN (the tiling record of the built kernel)
    and hold it to compiled-on-TPU / interpreted-elsewhere."""
    til = ctx._built_pallas_tiling()
    if til is None:
        fail("no pallas tiling record: the kernel was not built")
    say("  plan: " + json.dumps({k: til[k] for k in PLAN_KEYS
                                 if k in til}))
    for r in til["reasons"] + til.get("overlap_reasons", []):
        say(f"    reason: {json.dumps(r, default=str)}")
    if til["interpret"] != (env.get_platform() != "tpu"):
        fail(f"pallas kernel interpret={til['interpret']} on "
             f"platform {env.get_platform()}")


def device_bytes(devs, when: str, check: bool) -> None:
    import jax
    for d in devs:      # a device runs in order: drain what was queued
        (jax.device_put(np.float32(0), d) + 1).block_until_ready()
    stats = [d.memory_stats() for d in devs]
    if any(s is None for s in stats):
        say(f"  bytes_in_use {when}: not reported by this backend")
        return
    used = [int(s["bytes_in_use"]) for s in stats]
    peak = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    say(f"  bytes_in_use {when}: {used} (peak {peak})")
    if check and max(used) > MAX_BYTES_RATIO * max(min(used), 1):
        fail(f"bytes_in_use {when} unbalanced: {used}")


def stage_device(args):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    say(f"[1] device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)}")
    from importlib import metadata
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            vers[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            vers[pkg] = "absent"
    say(f"  versions: {vers}")
    if args.tiny:
        if d0.platform != "cpu":
            fail("--tiny is the CPU dry run, but JAX found "
                 f"platform '{d0.platform}'")
    elif d0.platform != "tpu":
        fail(f"no TPU: JAX found platform '{d0.platform}' "
             f"({d0.device_kind}); this check does not fall back")
    if args.chips == 4 and len(devs) < 4:
        fail(f"--chips 4 but only {len(devs)} device(s) visible")

    from yask_tpu import yk_factory
    from yask_tpu.backend import capability_for_platform
    fac = yk_factory()
    env = fac.new_env()
    # a kind in neither table raises here
    peak = env.get_hbm_peak_bytes_per_sec()
    cap = capability_for_platform(env.get_platform(),
                                  env.get_device_kind())
    say(f"  tables: hbm peak {peak / 1e9:.0f} GB/s, capability "
        f"'{cap.name}' (plan budget at K=2, one stage "
        f"{cap.plan_budget_bytes(2, 1) >> 20} MiB, "
        f"vmem limit cap {cap.vmem_limit_cap_mib} MiB)")
    say(f"  compile cache: JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '(unset)')!r}, "
        f"jax_compilation_cache_dir="
        f"{jax.config.jax_compilation_cache_dir!r}")

    # built from committed files: the native library builds on first
    # use or the Python Fornberg path answers — same coefficients
    from yask_tpu import native
    from yask_tpu.utils.fd_coeff import (_fornberg_weights_py,
                                         get_center_fd_coefficients)
    pts = [float(i) for i in range(-RADIUS, RADIUS + 1)]
    used = get_center_fd_coefficients(2, RADIUS)
    if used != _fornberg_weights_py(2, 0.0, pts):
        fail("native and Python r=8 FD coefficients differ")
    say(f"  fd coefficients: "
        f"{'native library' if native.available() else 'python path'}"
        f", identical to the Python Fornberg path")
    return fac, env, devs


def stage_jit(fac, env, g, dev, timed):
    say(f"[2] direct path, XLA: -mode jit, {g}^3, {STEPS} steps vs "
        f"the numpy oracle")
    ref = build(fac, env, g, "ref")
    direct = build(fac, env, g, "jit")
    with ThreadPoolExecutor(1) as pool:       # numpy drops the GIL
        t0 = time.perf_counter()
        oracle = pool.submit(ref.run_solution, 0, STEPS - 1)
        direct.run_solution(0, STEPS - 1)
        say(f"  compile {direct._compile_secs:.1f} s")
        oracle.result()
        say(f"  oracle {time.perf_counter() - t0:.1f} s")
    must_match("jit vs oracle", direct, ref)
    direct_p = newest(direct, g)
    time_trials(direct, g, dev, "jit", timed)
    return ref, direct_p


def stage_pallas(fac, env, g, ref, dev, timed):
    say(f"[3] direct path, fused Pallas: -mode pallas -wf_steps {WF}, "
        f"{g}^3, {STEPS} steps vs the numpy oracle")
    ctx = build(fac, env, g, "pallas", wf=WF)
    ctx.run_solution(0, STEPS - 1)
    say(f"  compile {ctx._compile_secs:.1f} s")
    say_plan(ctx, env)
    must_match("pallas-K2 vs oracle", ctx, ref)
    time_trials(ctx, g, dev, "pallas-K2", timed)


def stage_served(fac, env, g, direct_p, out_dir):
    say(f"[4] served path: StencilServer, one session iso3dfd r=8 "
        f"g={g} mode=jit, three run requests")
    from yask_tpu.serve import StencilServer
    srv = StencilServer(env=env, factory=fac, journal_path=os.path.join(
        out_dir, "SERVE_JOURNAL.jsonl"))
    try:
        sid = srv.open_session(stencil="iso3dfd", radius=RADIUS, g=g)
        srv.init_vars(sid)                     # pressure: seq(SEED)
        srv.set_var(sid, "vel", 0.1)
        c = g // 2
        srv.set_var_slice(sid, "pressure", np.ones((1, 1, 1, 1)),
                          [0, c, c, c], [0, c, c, c])
        resp = None
        for first, last in ((0, 1), (2, 2), (3, STEPS - 1)):
            resp = srv.run(sid, first, last, outputs=("pressure",))
            say(f"  run {first}..{last}: status={resp.status} "
                f"mode={resp.mode} batch={resp.batch} "
                f"compile {resp.compile_secs:.1f} s "
                f"cache_hit={resp.cache_hit} error={resp.error!r}")
            if resp.status != "ok":
                fail(f"served run {first}..{last}: {resp.status} "
                     f"{resp.error} {resp.anomaly}")
        got = resp.outputs["pressure"]
        if got.shape != direct_p.shape:
            fail(f"served output shape {got.shape} != {direct_p.shape}")
        bad = mismatches(got, direct_p)
        say(f"  served vs direct run over steps 0..{STEPS - 1}: {bad} "
            f"mismatches, bit-identical={np.array_equal(got, direct_p)}")
        if bad:
            fail(f"served outputs differ from the direct run: {bad}")
    finally:
        srv.shutdown()


def stage_four_chips(fac, env, devs, g, g_big, ref, dev, timed):
    say(f"[5] four chips: -mode shard_pallas -wf_steps {WF}, x over 4")
    # ground truth at the one-chip size: four chips vs the oracle that
    # stages 2 and 3 were held to
    sp = build(fac, env, g, "shard_pallas", wf=WF, ranks=4)
    sp.run_solution(0, STEPS - 1)
    say(f"  {g}^3 compile {sp._compile_secs:.1f} s")
    say_plan(sp, env)
    must_match(f"shard_pallas-K2 x4 {g}^3 vs oracle", sp, ref)
    sp.end_solution()
    del sp
    gc.collect()

    # the four-chip size: y whole so distributed skew can engage; the
    # reference is shard_map on the same mesh, compared slab by slab
    # through the slice API — no global array on the host or a chip
    say(f"  {g_big}^3: shard_pallas-K2 vs shard_map, {STEPS} steps")
    device_bytes(devs[:4], "before prepare", False)
    slab = g_big // 4

    def slabs(ctx):
        v = ctx.get_var("pressure")
        for t in range(v.get_first_valid_step_index(),
                       v.get_last_valid_step_index() + 1):
            for i in range(4):
                yield (t, i), v.get_elements_in_slice(
                    [t, i * slab, 0, 0],
                    [t, (i + 1) * slab - 1, g_big - 1, g_big - 1])

    sm = build(fac, env, g_big, "shard_map", ranks=4)
    device_bytes(devs[:4], "after prepare (shard_map)", True)
    sm.run_solution(0, STEPS - 1)   # (its jit compiles inside the run)
    device_bytes(devs[:4], "after run (shard_map)", True)
    want = dict(slabs(sm))
    sm.end_solution()
    del sm
    gc.collect()

    sp = build(fac, env, g_big, "shard_pallas", wf=WF, ranks=4)
    device_bytes(devs[:4], "after prepare (shard_pallas)", True)
    sp.run_solution(0, STEPS - 1)
    say(f"  shard_pallas compile {sp._compile_secs:.1f} s")
    device_bytes(devs[:4], "after run (shard_pallas)", True)
    say_plan(sp, env)
    bad = 0
    for key, got in slabs(sp):
        bad += mismatches(got, want.pop(key))
    say(f"  shard_pallas-K2 vs shard_map {g_big}^3: {bad} mismatches "
        f"over {2 * 4} slabs")
    if bad or want:
        fail(f"shard_pallas vs shard_map at {g_big}^3: {bad} mismatches")
    time_trials(sp, g_big, dev, "shard_pallas-K2 x4", timed)


def main(argv=None) -> int:
    global _label
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=None,
                    help="4: require four devices and run stage 5; "
                         "1: stages 1-4 only (default: stage 5 runs "
                         "when >= 4 devices are visible)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at g=64 (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out",
        "chip_smoke"), help="directory for the serve journal")
    args = ap.parse_args(argv)
    if args.tiny:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            fail("--tiny is refused unless JAX_PLATFORMS=cpu is set "
                 "by name")
        _label = "cpu dry-run: "
    g, g_big = (64, 96) if args.tiny else (512, 1024)
    timed = not args.tiny
    os.makedirs(args.out, exist_ok=True)

    t_all = time.perf_counter()
    fac, env, devs = stage_device(args)
    dev = f"1x {devs[0].device_kind}"       # stages 2-4 use one device
    ref, direct_p = stage_jit(fac, env, g, dev, timed)
    stage_pallas(fac, env, g, ref, dev, timed)
    stage_served(fac, env, g, direct_p, args.out)
    del direct_p
    gc.collect()
    if args.chips != 1 and len(devs) >= 4:
        stage_four_chips(fac, env, devs, g, g_big, ref,
                         f"4x {devs[0].device_kind}", timed)
    else:
        say(f"[5] four chips: not run ({len(devs)} device(s) visible)")
    say(f"all stages passed in {time.perf_counter() - t_all:.0f} s")
    result = {"ok": True,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind,
                         "count": len(devs)}}
    if args.tiny:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
