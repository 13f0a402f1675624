#!/usr/bin/env python
"""Headline benchmark: iso3dfd order-16 (radius 8) single-device throughput.

Mirrors the reference harness' trial protocol (``yask_main.cpp:53-66``):
warmup (excluded, covers XLA compile), then N timed trials; report the
"mid" (median) throughput in GPts/s — the reference's primary fitness
metric (``context.cpp:449-460``, ``YaskUtils.pm:40``).

After the XLA-path measurement it tries the fused Pallas path (temporal
fusion, K=wf_steps): each candidate is first validated against the XLA
path on a small domain, then timed; the best mode wins.  A candidate
that fails to build, run or match fails the size — it never silently
gives way to the XLA number.

This measures on a TPU.  Everything runs in this one process (a chip
belongs to one process at a time): no probe, no child that opens the
backend.  With no TPU it exits non-zero; it never falls back.  A CPU
run happens only when CPU was asked for by name (``JAX_PLATFORMS=cpu``)
— a dry run of the plumbing whose numbers are not device speed.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GPts/s", "vs_baseline": N}
vs_baseline is measured against the BASELINE.md target of 500 GPts/s/chip.
"""

import json
import os
import sys
import time
import traceback

from yask_tpu.resilience import (CompilerOOM, anomaly_fields,
                                 check_output, classify, guarded_call,
                                 maybe_corrupt)


def build(fac, env, g, mode="jit", wf=0, radius=8):
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=radius)
    ctx.apply_command_line_options(f"-g {g}")
    ctx.get_settings().mode = mode
    ctx.get_settings().wf_steps = wf
    # static preflight (default-on, -no-preflight to skip): surfaces
    # Mosaic/VMEM/race findings up front but never blocks the bench —
    # the contract line must survive even a checker bug
    from yask_tpu.checker import preflight
    if not preflight(ctx):
        print(f"bench: preflight found errors for mode={mode} "
              f"(see above); attempting the run anyway", file=sys.stderr)
    ctx.prepare_solution()
    ctx.get_var("pressure").set_element(1.0, [0, g // 2, g // 2, g // 2])
    ctx.get_var("vel").set_all_elements_same(0.1)
    return ctx


def measure(ctx, g, steps_per_trial, trials, sanity=None):
    # warmup (compile)
    ctx.run_solution(0, steps_per_trial - 1)
    rates = []
    t = steps_per_trial
    for _ in range(trials):
        t0 = time.perf_counter()
        ctx.run_solution(t, t + steps_per_trial - 1)
        dt = time.perf_counter() - t0
        t += steps_per_trial
        rates.append(g ** 3 * steps_per_trial / dt / 1e9)
    # result-sanity guard on the interior slice around the impulse
    # (nonzero after any step on a live device): all-zero / NaN fields
    # must never yield a clean throughput number.  With a ``sanity``
    # dict the verdict is returned for the caller to quarantine the row
    # (the contract line still prints, labeled ANOMALY); without one a
    # bad verdict raises, so pallas candidates and re-measures reject.
    s = ctx.get_var("pressure").get_elements_in_slice(
        [t, g // 2 - 1, g // 2 - 1, g // 2 - 1],
        [t, g // 2 + 1, g // 2 + 1, g // 2 + 1])
    s = maybe_corrupt("bench.result", s)
    verdict = check_output(s)
    if sanity is not None:
        sanity.clear()
        sanity.update(verdict)
    elif not verdict["ok"]:
        raise RuntimeError("result anomaly: "
                           + ",".join(verdict["anomalies"]))
    rates.sort()
    return rates[len(rates) // 2]


def _ckpt_ab(fac, env, g, steps_per_trial, trials, base_rate, platform,
             ddl):
    """Checkpoint-cadence overhead A/B on the jit headline config: the
    SAME build re-measured with the supervision cadence on (snapshots
    to a throwaway dir).  The ratio rides the ledger under the
    sentinel, so a hot-path regression — ``-ckpt_every 0`` must stay a
    true no-op, and the cadence cost is one device→host snapshot pull
    per N steps — is caught in the artifact, never the contract line
    (the caller isolates this whole probe)."""
    import tempfile
    from yask_tpu.perflab import capture_provenance
    from yask_tpu.perflab.sentinel import guard_and_append
    with tempfile.TemporaryDirectory(prefix="yt_ckpt_ab_") as td:
        ctx = build(fac, env, g, "jit")
        o = ctx.get_settings()
        o.ckpt_every = max(1, steps_per_trial // 2)
        o.ckpt_dir = td
        rate = guarded_call(measure, ctx, g, steps_per_trial, trials,
                            site="bench.ckpt_ab", deadline_secs=ddl)
        cadence = o.ckpt_every
        del ctx
    overhead = max(0.0, 1.0 - rate / base_rate) if base_rate > 0 else 0.0
    prov = capture_provenance(platform=platform,
                              device_kind=env.get_device_kind())
    guard_and_append(
        f"iso3dfd r=8 {g}^3 fp32 {platform} jit ckpt-cadence A/B",
        round(rate, 3), "GPts/s", platform, "bench", prov,
        extra={"ckpt_every": cadence,
               "baseline_gpts": round(base_rate, 3),
               "overhead_frac": round(overhead, 4)})
    return overhead


def try_pallas(fac, env, g, steps_per_trial, trials, candidates=(2, 4)):
    """Validated + timed fused-Pallas attempt; returns
    (rate, K, bytes_pp, compile_ms, cache_hit) of the fastest candidate.
    A candidate whose plan does not fit the chip (a classified
    ``CompilerOOM`` — on a v5e the default K=4 plan at 512³ needs
    149.99M of 128.00M VMEM) is infeasible, said so on stderr, and
    skipped; any other failure, a mismatch with the XLA path, or no
    feasible candidate at all propagates."""
    best = None
    small = 64
    nval = 2 * max(candidates)
    # correctness gate on a small domain first (one shared jit ref)
    ref = build(fac, env, small, "jit")
    ref.run_solution(0, nval - 1)
    for K in candidates:
        b = build(fac, env, small, "pallas", wf=K)
        b.run_solution(0, nval - 1)
        bad = ref.compare_data(b, epsilon=1e-3, abs_epsilon=1e-4)
        if bad:
            raise RuntimeError(f"pallas K={K} mismatches the XLA path "
                               f"at {small}^3 ({bad} points)")
        ctx = build(fac, env, g, "pallas", wf=K)
        try:
            rate = measure(ctx, g, steps_per_trial, trials)
        except Exception as e:  # noqa: BLE001 - re-raised unless OOM
            if not isinstance(classify(e), CompilerOOM):
                raise
            print(f"bench: pallas K={K} at {g}^3 does not fit the chip, "
                  f"candidate skipped: {str(e).splitlines()[0][:240]}",
                  file=sys.stderr)
            continue
        if best is None or rate > best[0]:
            # traffic model + compile cost of the kernel actually
            # benchmarked (cache_hit tells cold vs memory vs disk)
            best = (rate, K, sum(ctx.hbm_model_bytes_pp()),
                    round(ctx._compile_secs * 1000.0, 1),
                    ctx._last_cache_hit or "cold")
    if best is None:
        raise RuntimeError(f"no pallas candidate of {candidates} fits "
                           f"the chip at {g}^3")
    return best


def main():
    from yask_tpu import yk_factory

    fac = yk_factory()
    env = fac.new_env()
    platform = env.get_platform()
    on_tpu = platform == "tpu"
    if not on_tpu and not (platform == "cpu" and os.environ.get(
            "JAX_PLATFORMS", "").startswith("cpu")):
        print(f"bench: no TPU (JAX found platform '{platform}') and CPU "
              f"was not requested by name (JAX_PLATFORMS=cpu); not "
              f"falling back", file=sys.stderr)
        return 1

    sizes = [512, 384, 256] if on_tpu else [128]
    steps_per_trial = 10 if on_tpu else 2
    trials = 3

    for g in sizes:
        try:
            sanity = {}
            ctx = build(fac, env, g, "jit")
            # deadline around the in-process device work: a device that
            # stops answering would otherwise hang run_solution inside
            # this process with nothing to kill it
            try:
                ddl = float(os.environ.get("YT_BENCH_MEASURE_DEADLINE",
                                           "900"))
            except ValueError:
                ddl = 900.0
            rate = guarded_call(measure, ctx, g, steps_per_trial, trials,
                                site="bench.measure", deadline_secs=ddl,
                                sanity=sanity)
            mode = "jit"
            bytes_pp = sum(ctx.hbm_model_bytes_pp())
            hbm_peak = env.get_hbm_peak_bytes_per_sec()
            compile_ms = round(ctx._compile_secs * 1000.0, 1)
            cache_hit = ctx._last_cache_hit or "cold"
            del ctx
            # checkpoint-cadence overhead A/B (acceptance: ≤5% on the
            # jit headline); telemetry only — never the contract line
            try:
                _ckpt_ab(fac, env, g, steps_per_trial, trials, rate,
                         platform, ddl)
            except Exception as e:  # noqa: BLE001
                print(f"bench: ckpt A/B failed ({str(e)[:120]})",
                      file=sys.stderr)
            # interpret-mode Pallas can never beat XLA off-TPU: only try
            # the fused path on real hardware (override via env for tests)
            want_pallas = os.environ.get(
                "YT_BENCH_PALLAS", "1" if on_tpu else "0")
            if want_pallas == "1":
                p = guarded_call(try_pallas, fac, env, g,
                                 steps_per_trial, trials,
                                 site="bench.pallas")
                if p[0] > rate:
                    rate, mode = p[0], f"pallas-K{p[1]}"
                    bytes_pp = p[2]   # model of the winning kernel
                    compile_ms, cache_hit = p[3], p[4]
            metric = (f"iso3dfd r=8 {g}^3 fp32 {platform} "
                      f"throughput ({mode})")
            # roofline context (VERDICT r2 item 8) via the shared
            # perflab model; provenance + sentinel verdict make the
            # contract line self-explaining (an r5-style slide reads as
            # "noise" or "regression" in the artifact itself)
            from yask_tpu.perflab import capture_provenance
            from yask_tpu.perflab.roofline import roofline as _roofline
            from yask_tpu.perflab.sentinel import guard_and_append
            roof = _roofline(rate, bytes_pp, hbm_peak)
            prov = capture_provenance(
                platform=platform, device_kind=env.get_device_kind())
            # re-measure hook (breach → noise-vs-regression verdict):
            # rebuild the winning configuration from scratch so the
            # second sample shares nothing with the first
            if mode == "jit":
                remeasure = lambda: measure(  # noqa: E731
                    build(fac, env, g, mode="jit"), g,
                    steps_per_trial, trials)
            else:
                K = int(mode.rsplit("K", 1)[-1])
                remeasure = lambda: measure(  # noqa: E731
                    build(fac, env, g, mode="pallas", wf=K), g,
                    steps_per_trial, trials)
            guard = {"status": "unrecorded"}
            try:
                lrow = guard_and_append(
                    metric, round(rate, 3), "GPts/s", platform, "bench",
                    prov, roofline=roof,
                    extra={"mode": mode,
                           "vs_baseline": round(rate / 500.0, 4),
                           "compile_ms": compile_ms,
                           "cache_hit": cache_hit},
                    remeasure=remeasure, sanity=sanity)
                guard = lrow["guard"]
            except Exception:
                pass  # ledger I/O must never cost the contract line
            line = {
                "metric": metric,
                "value": round(rate, 3),
                "unit": "GPts/s",
                # platform as a FIELD, not only in the metric string: a
                # CPU dry run's vs_baseline of ~0.0001 must be readable
                # as "not a device number", not a perf collapse
                "platform": platform,
                "vs_baseline": round(rate / 500.0, 4),
                "hbm_bytes_pp": roof["hbm_bytes_pp"],
                "hbm_gbps": roof["hbm_gbps"],
                "provenance": prov,
                "guard": guard,
                # compile amortization telemetry: cold = fresh Mosaic/XLA
                # build, disk = the persistent cache paid it in an
                # earlier process (see docs/performance.md)
                "compile_ms": compile_ms,
                "cache_hit": cache_hit,
            }
            if roof.get("roofline_frac") is not None:
                line["hbm_roofline"] = roof["roofline_frac"]
            if sanity and not sanity.get("ok", True):
                # the contract line survives but labeled: an all-zero /
                # NaN field is an ANOMALY row, quarantined everywhere
                # (excluded from sentinel baselines)
                line.update(anomaly_fields(sanity))
            print(json.dumps(line))
            return 0
        except Exception:  # noqa: BLE001 - try a smaller domain
            print(f"bench: {g}^3 failed:", file=sys.stderr)
            traceback.print_exc()
    print("bench: every size failed", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
