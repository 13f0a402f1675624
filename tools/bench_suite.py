"""BASELINE-table benchmark suite: one JSON line per headline config.

Covers the target rows in BASELINE.md beyond the single-number contract
of ``bench.py``:

* iso3dfd order-16, single device (jit vs validated fused pallas);
* cube 27-point with temporal wave-front fusion (wavefront speedup =
  fused K=4 over K=1);
* ssg staggered elastic (multi-var);
* iso3dfd in bf16 on the validated pallas path (HBM roofline lever);
* iso3dfd small-radius trapezoid-vs-skew A/B (the two-phase
  parallel-grid tiling, correctness-gated, TPU-scoped sentinel floor);
* awp, domain-decomposed with measured halo fraction (multi-device);
* ensemble batched-vs-sequential A/B (N instances as one vmapped
  program vs N fresh contexts each paying its own compile — the
  parameter-sweep regime; bit-identity gated per member);
* serving-layer A/Bs: same-geometry micro-batching, cross-profile
  shape-bucket co-batching (mixed geometries on one ladder rung,
  masked sub-domain runs bit-identical to solo), and the
  streaming/preemption short-request p99 win under mixed traffic.

Every section is independent (a failure emits an error line and the
suite continues), pallas numbers are correctness-gated against the jit
path first. It runs on whatever backend JAX finds, in one process; the
platform rides every row. Sizes shrink automatically off-TPU so the
suite stays runnable on the virtual CPU mesh (those rows are not
device speed).

Every row goes through ``yask_tpu.perflab``: it carries measurement
provenance (load average, CPU model, git SHA, calibration rate — the
context whose absence made the r5 across-the-board proxy slide
uninvestigable), a sentinel guard verdict (trailing clean median +
absolute floors, one automatic re-measure on breach deciding
noise-vs-regression), roofline context where a traffic model exists,
and is appended to ``PERF_LEDGER.jsonl``.  There are no ad-hoc guards
left here — the old cube-wavefront floor is now a sentinel rule.

Run: ``python tools/bench_suite.py``
"""

from __future__ import annotations

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from yask_tpu.resilience import (Fault, anomaly_fields,  # noqa: E402
                                 check_output, guarded_call,
                                 maybe_corrupt)

#: sanity verdicts accumulated by measure() since the last emit() —
#: a row built from several measurements (speedup ratios) is
#: quarantined when ANY of them failed the guards.
_SANITY: list = []


def measure(ctx, g_pts, steps, trials=3):
    rates = []
    t = ctx._cur_step
    ctx.run_solution(t, t + steps - 1)   # warm
    t += steps
    for _ in range(trials):
        t0 = time.perf_counter()
        ctx.run_solution(t, t + steps - 1)
        dt = time.perf_counter() - t0
        t += steps
        rates.append(g_pts * steps / dt / 1e9)
    # result-sanity gate: wall-clock throughput of a diverged or
    # all-zero field is noise.  The interior slice around the domain
    # center (seeded nonzero by init_solution_vars) goes through the
    # shared guards; the verdict is accumulated for emit() to
    # quarantine the row rather than raising — the measurement is
    # recorded as a structured ANOMALY, not lost.
    name = ctx.get_var_names()[0]
    v = ctx.get_var(name)
    mid = [s // 2 for s in
           (ctx.get_settings().global_domain_sizes[d]
            for d in ctx.get_domain_dim_names())]
    s = v.get_elements_in_slice([t] + [c - 1 for c in mid],
                                [t] + [c + 1 for c in mid])
    s = maybe_corrupt("suite.result", s)
    verdict = check_output(s)
    if not verdict["ok"]:
        _SANITY.append(verdict)
    rates.sort()
    return rates[len(rates) // 2]


def build(fac, env, name, radius, g, mode, wf=0, ranks=(),
          measure_halo=False, elem_bytes=None, extra_opts=""):
    from yask_tpu.runtime.init_utils import init_solution_vars
    if elem_bytes:
        from yask_tpu.compiler.solution_base import create_solution
        sb = create_solution(name, radius=radius)
        sb.get_soln().set_element_bytes(elem_bytes)
        ctx = fac.new_solution(env, sb)
    else:
        ctx = fac.new_solution(env, stencil=name, radius=radius)
    opts = f"-g {g} -wf_steps {wf}"
    if measure_halo:
        opts += " -measure_halo"
    if extra_opts:
        opts += " " + extra_opts
    ctx.apply_command_line_options(opts)
    ctx.get_settings().mode = mode
    for d, r in ranks:
        ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


def validated_pallas(fac, env, name, radius, wf, gv=24, steps=4,
                     elem_bytes=None, epsilon=1e-3, abs_epsilon=1e-4):
    """Correctness gate: the fused path must match jit on a small domain
    before any timing is trusted (same policy as bench.py)."""
    ref = build(fac, env, name, radius, gv, "jit", elem_bytes=elem_bytes)
    ref.run_solution(0, steps - 1)
    p = build(fac, env, name, radius, gv, "pallas", wf=wf,
              elem_bytes=elem_bytes)
    p.run_solution(0, steps - 1)
    bad = p.compare_data(ref, epsilon=epsilon, abs_epsilon=abs_epsilon)
    if bad:
        raise RuntimeError(f"pallas K={wf} mismatches jit at {gv}^3: {bad}")


#: rows emitted by the current run_suite invocation (bench.py persists
#: them into the round artifact alongside its contract line).
ROWS = []

#: set by run_suite: (platform, device_kind) for per-row provenance.
_ENV_INFO = {"platform": "", "device_kind": ""}


def emit(metric, value, unit, remeasure=None, roofline=None, **extra):
    """Record one suite row: provenance + sentinel verdict + ledger
    append, then the legacy-shaped JSON line (bench.py re-prints these
    and the driver parser reads them — `metric`/`value`/`unit` keys stay
    stable, provenance/guard ride along as extra fields).

    Sanity verdicts accumulated by measure() since the previous emit
    quarantine the row: it still prints and lands in the ledger, but as
    a structured ANOMALY the sentinel never baselines on."""
    from yask_tpu.perflab import capture_provenance, guard_and_append
    value = round(value, 4)
    sanity = None
    if _SANITY:
        sanity = {"ok": False,
                  "anomalies": sorted({a for v in _SANITY
                                       for a in v["anomalies"]}),
                  **{k: _SANITY[-1][k]
                     for k in ("zero_frac", "nonfinite_frac", "max_abs")
                     if k in _SANITY[-1]}}
        _SANITY.clear()
    prov = capture_provenance(platform=_ENV_INFO["platform"],
                              device_kind=_ENV_INFO["device_kind"])
    try:
        lrow = guard_and_append(metric, value, unit,
                                _ENV_INFO["platform"] or "cpu", "suite",
                                prov, remeasure=remeasure,
                                roofline=roofline, extra=extra or None,
                                sanity=sanity)
        guard = lrow["guard"]
    except Exception as e:  # ledger I/O must never kill a bench section
        guard = {"status": "unrecorded", "error": str(e)[:120]}
    row = {"metric": metric, "value": value, "unit": unit, **extra,
           "provenance": prov, "guard": guard}
    if sanity is not None:
        row.update(anomaly_fields(sanity))
    if roofline:
        row.update({k: v for k, v in roofline.items() if v is not None})
    ROWS.append(row)
    print(json.dumps(row), flush=True)


def section(fn, budget_t0=None, budget_secs=None):
    """Run one headline row; a failure emits an error line, not a crash.
    Sections past the time budget are skipped (bench.py embeds the suite
    under the driver's overall timeout — a partial suite beats no
    contract line at all).  Sections run through guarded_call, so
    injected faults fire at ``suite.<name>`` and real backend failures
    are recorded with their classified kind."""
    if budget_t0 is not None and budget_secs is not None \
            and time.perf_counter() - budget_t0 > budget_secs:
        emit(fn.__name__, 0.0, "skipped", reason="suite time budget")
        return
    try:
        guarded_call(fn, site=f"suite.{fn.__name__}")
    except Fault as f:
        _SANITY.clear()   # a failed section's verdicts die with it
        emit(fn.__name__, 0.0, "error", error=str(f)[:160],
             fault=f.kind)
    except Exception as e:
        _SANITY.clear()
        emit(fn.__name__, 0.0, "error", error=str(e)[:160])


def run_suite(fac, env, budget_secs=None):
    """All BASELINE rows (beyond bench.py's single contract line) for
    the given environment; returns the emitted row dicts. Importable by
    bench.py so the round artifact records the suite, not one number
    (VERDICT r2 weak 6)."""
    plat = env.get_platform()
    on_tpu = plat == "tpu"
    ndev = env.get_num_ranks()
    ROWS.clear()
    _ENV_INFO["platform"] = plat
    _ENV_INFO["device_kind"] = env.get_device_kind()
    t0 = time.perf_counter()

    steps = 12 if on_tpu else 4   # multiple of 4: clean K=4 fusion groups

    from yask_tpu.perflab.roofline import ctx_roofline

    def iso3dfd_jit():
        for g in ((512, 384, 256) if on_tpu else (48,)):
            try:
                ctx = build(fac, env, "iso3dfd", 8, g, "jit")
                rate = measure(ctx, g ** 3, steps)
                emit(f"iso3dfd r=8 {g}^3 {plat} jit", rate, "GPts/s",
                     remeasure=lambda: measure(ctx, g ** 3, steps),
                     roofline=ctx_roofline(ctx, env, rate))
                del ctx
                return
            except Exception:
                if g == (256 if on_tpu else 48):
                    raise

    def _tiling_of(ctx):
        """The tiling the built kernel ACTUALLY chose, for row
        provenance (skew / pipelining can auto-fall-back)."""
        for t in ctx._pallas_tiling.values():
            if t:
                return {k: t[k] for k in ("skew", "skew_dims",
                                          "trapezoid", "trap_dims",
                                          "pipeline_dmas",
                                          "pipeline_out",
                                          "overlap_exchange",
                                          "overlap_core",
                                          "margin_overhead") if k in t}
        return {}

    def _comm_of(ctx):
        """Comm-schedule row fields (mesh shape, per-axis kB, collective
        rounds — measured when halo-cal ran); {} on single-device
        paths or any plan failure (row fields must not kill a
        section)."""
        from yask_tpu.parallel.comm_plan import comm_ledger_fields
        try:
            return comm_ledger_fields(ctx)
        except Exception:
            return {}

    def iso3dfd_pallas():
        validated_pallas(fac, env, "iso3dfd", 8, wf=2)
        g = 512 if on_tpu else 48
        ctx = build(fac, env, "iso3dfd", 8, g, "pallas", wf=2)
        rate = measure(ctx, g ** 3, steps)
        emit(f"iso3dfd r=8 {g}^3 {plat} pallas-K2", rate, "GPts/s",
             remeasure=lambda: measure(ctx, g ** 3, steps),
             roofline=ctx_roofline(ctx, env, rate), **_tiling_of(ctx))
        del ctx

    def cube_wavefront():
        # The K=4-over-K=1 fusion speedup.  The old ad-hoc 1.5× floor
        # (VERDICT r4 item 3: the r4 proxy silently halved when skew
        # mis-engaged at r=1) is now the sentinel's cube-wavefront rule;
        # on a breach the guard re-measures the ratio once and records
        # noise-vs-regression in the row itself.
        validated_pallas(fac, env, "cube", 1, wf=4)
        gc = 256 if on_tpu else 32
        c1 = build(fac, env, "cube", 1, gc, "pallas", wf=1)
        base = measure(c1, gc ** 3, steps)
        c4 = build(fac, env, "cube", 1, gc, "pallas", wf=4)
        fused = measure(c4, gc ** 3, steps)

        def remeasure_speedup():
            return (measure(c4, gc ** 3, steps)
                    / max(measure(c1, gc ** 3, steps), 1e-12))

        emit(f"cube 27pt {gc}^3 {plat} wavefront-speedup",
             fused / max(base, 1e-12), "x",
             remeasure=remeasure_speedup,
             k1_gpts=round(base, 4), k4_gpts=round(fused, 4),
             **_tiling_of(c4))
        del c1, c4

    def iso3dfd_skew2d():
        # 1-D vs 2-D skew A/B via the -skew_dims knob: the second
        # (outer-dim, E=0) carry trades its row buffer for another
        # 2·K·r → (K+1)·r margin drop — track the payoff as a ratio so
        # the sentinel sees mis-engagement (the r4 cube lesson, one
        # dim up).
        g = 512 if on_tpu else 48
        c1 = build(fac, env, "iso3dfd", 8, g, "pallas", wf=2,
                   extra_opts="-skew_dims 1")
        r1 = measure(c1, g ** 3, steps)
        c2 = build(fac, env, "iso3dfd", 8, g, "pallas", wf=2)
        r2 = measure(c2, g ** 3, steps)

        def remeasure_ratio():
            return (measure(c2, g ** 3, steps)
                    / max(measure(c1, g ** 3, steps), 1e-12))

        emit(f"iso3dfd r=8 {g}^3 {plat} skew2d-speedup",
             r2 / max(r1, 1e-12), "x", remeasure=remeasure_ratio,
             skew1d_gpts=round(r1, 4), skew2d_gpts=round(r2, 4),
             **_tiling_of(c2))
        del c1, c2

    def iso3dfd_trapezoid():
        # Trapezoid-vs-skew A/B at the config the profit gate engages
        # on (small radius, K=4 — see docs/performance.md gate table):
        # -trapezoid arms the gate (pads sized at prepare), the off arm
        # is the same config on the skew/uniform path.  The correctness
        # gate asserts BIT-equality against the uniform pallas schedule
        # (same contract as pipeline_ab: a tiling variant reorders the
        # sweep, never the per-cell arithmetic — jit is the wrong oracle
        # here since XLA's fusion reassociates and drifts ~1e-3 after a
        # few steps regardless of tiling).  The provisional 0.9
        # TRAP_SPEEDUP_FLOOR is TPU-scoped (the CPU proxy has no
        # megacore and serializes the diamond fill passes, so its ratio
        # sits below 1 by construction); the row's tiling block says
        # whether the gate actually engaged.
        # 64 is the smallest cube where the gate engages trapezoid for
        # this stencil (at 48 the planner's 16^2 blocks keep skew ahead;
        # at 64..384 trapezoid wins the cost model — see the probe table
        # in docs/performance.md).
        g = 384 if on_tpu else 64
        ref = build(fac, env, "iso3dfd", 2, 24, "pallas", wf=4)
        ref.run_solution(0, 3)
        chk = build(fac, env, "iso3dfd", 2, 24, "pallas", wf=4,
                    extra_opts="-trapezoid")
        chk.run_solution(0, 3)
        bad = chk.compare_data(ref, epsilon=0.0, abs_epsilon=0.0)
        if bad:
            raise RuntimeError(
                f"trapezoid K=4 not bit-equal to uniform pallas: {bad}")
        del ref, chk
        c_off = build(fac, env, "iso3dfd", 2, g, "pallas", wf=4)
        r_off = measure(c_off, g ** 3, steps)
        c_on = build(fac, env, "iso3dfd", 2, g, "pallas", wf=4,
                     extra_opts="-trapezoid")
        r_on = measure(c_on, g ** 3, steps)
        if not _tiling_of(c_on).get("trapezoid"):
            # both arms ran the same plan — a vacuous A/B must error
            # loudly, not bank a noise ratio as "trap-speedup" (the
            # tiling only materializes at first chunk build, hence the
            # post-measure check)
            raise RuntimeError(
                f"trapezoid gate did not engage at {g}^3: "
                f"{_tiling_of(c_on)}")

        def remeasure_ratio():
            return (measure(c_on, g ** 3, steps)
                    / max(measure(c_off, g ** 3, steps), 1e-12))

        emit(f"iso3dfd r=2 {g}^3 {plat} trap-speedup",
             r_on / max(r_off, 1e-12), "x", remeasure=remeasure_ratio,
             base_gpts=round(r_off, 4), trap_gpts=round(r_on, 4),
             base_tiling=_tiling_of(c_off), **_tiling_of(c_on))
        del c_on, c_off

    def ssg_elastic():
        gs = 256 if on_tpu else 32
        ctx = build(fac, env, "ssg", 2, gs, "jit")
        rate = measure(ctx, gs ** 3, steps)
        emit(f"ssg r=2 {gs}^3 {plat} jit", rate, "GPts/s",
             remeasure=lambda: measure(ctx, gs ** 3, steps),
             roofline=ctx_roofline(ctx, env, rate))
        del ctx

    def iso3dfd_bf16():
        # bf16 halves HBM bytes/point — the bandwidth-roofline lever on
        # TPU (reference real_bytes=4|8 builds have no half-precision
        # analog; bf16 is the TPU-native one).  Validation gate compares
        # bf16 pallas against bf16 jit with bf16-appropriate epsilons.
        validated_pallas(fac, env, "iso3dfd", 8, wf=2, elem_bytes=2,
                         epsilon=3e-2, abs_epsilon=3e-2)
        g = 512 if on_tpu else 48
        ctx = build(fac, env, "iso3dfd", 8, g, "pallas", wf=2,
                    elem_bytes=2)
        rate = measure(ctx, g ** 3, steps)
        emit(f"iso3dfd r=8 {g}^3 {plat} pallas-K2 bf16", rate, "GPts/s",
             remeasure=lambda: measure(ctx, g ** 3, steps),
             roofline=ctx_roofline(ctx, env, rate))
        del ctx

    def awp_decomposed():
        if ndev <= 1:
            return
        ga = 256 if on_tpu else 32
        ctx = build(fac, env, "awp", None, ga, "shard_map",
                    ranks=[("x", ndev)], measure_halo=True)
        rate = measure(ctx, ga ** 3, steps)
        st = ctx.get_stats()
        # twice-unstable calibration banks NO split: halo_pct is null
        # (the row still carries total throughput + halo_cal_unstable)
        halo_pct = None
        if not st.get_halo_cal_unstable():
            halo_pct = round(100.0 * st.get_halo_secs()
                             / max(st.get_elapsed_secs(), 1e-12), 2)
        emit(f"awp {ga}^3 {plat} x{ndev} shard_map", rate, "GPts/s",
             remeasure=lambda: measure(ctx, ga ** 3, steps),
             roofline=ctx_roofline(ctx, env, rate),
             halo_pct=halo_pct, **_comm_of(ctx))
        del ctx

    def sm_coalesce():
        # Message-coalescing A/B on a 2-D mesh (the shape where slabs
        # per axis multiply): one packed ppermute per (axis, direction)
        # vs one per buffer slab, same geometry — the CommPlan's
        # headline lever.  Rows carry measured collective counts
        # (comm_rounds_measured, from the traced exchange twin) so the
        # ledger shows the round reduction, not just the rate delta.
        if ndev < 4:
            return
        g = 256 if on_tpu else 32
        c_off = build(fac, env, "ssg", 2, g, "shard_map",
                      ranks=[("x", 2), ("y", 2)], measure_halo=True,
                      extra_opts="-coalesce off")
        r_off = measure(c_off, g ** 3, steps)
        c_on = build(fac, env, "ssg", 2, g, "shard_map",
                     ranks=[("x", 2), ("y", 2)], measure_halo=True,
                     extra_opts="-coalesce on")
        r_on = measure(c_on, g ** 3, steps)

        def remeasure_ratio():
            return (measure(c_on, g ** 3, steps)
                    / max(measure(c_off, g ** 3, steps), 1e-12))

        emit(f"ssg r=2 {g}^3 {plat} x2y2 sm-coalesce-speedup",
             r_on / max(r_off, 1e-12), "x", remeasure=remeasure_ratio,
             serial_gpts=round(r_off, 4), coalesced_gpts=round(r_on, 4),
             serial_rounds=_comm_of(c_off).get("comm_rounds_measured"),
             **_comm_of(c_on))
        del c_on, c_off

    def sp_overlap():
        # Overlapped halo exchange A/B on the flagship multi-chip path:
        # the core/shell split of the fused K-group (-overlap_x on)
        # against the serial chunk→exchange schedule.  Forcing "on"
        # (rather than auto) makes the ratio's meaning unconditional —
        # an infeasible geometry errors the section instead of silently
        # comparing serial to serial.  The provisional 0.95 sentinel
        # floor is TPU-scoped (the CPU proxy pays the split's extra
        # launches with no collective latency to hide, ~0.7-0.8x by
        # construction — trailing-median guards that arm); re-base on
        # hardware.
        if ndev <= 1:
            return
        g = 256 if on_tpu else 48
        rx = min(ndev, 4)
        c_off = build(fac, env, "iso3dfd", 2, g, "shard_pallas", wf=2,
                      ranks=[("x", rx)], measure_halo=True,
                      extra_opts="-overlap_x off")
        r_off = measure(c_off, g ** 3, steps)
        eff_off = c_off.get_stats().get_halo_overlap_eff()
        c_on = build(fac, env, "iso3dfd", 2, g, "shard_pallas", wf=2,
                     ranks=[("x", rx)], measure_halo=True,
                     extra_opts="-overlap_x on")
        r_on = measure(c_on, g ** 3, steps)
        eff_on = c_on.get_stats().get_halo_overlap_eff()

        def remeasure_ratio():
            return (measure(c_on, g ** 3, steps)
                    / max(measure(c_off, g ** 3, steps), 1e-12))

        emit(f"iso3dfd r=2 {g}^3 {plat} x{rx} sp-overlap-speedup",
             r_on / max(r_off, 1e-12), "x", remeasure=remeasure_ratio,
             serial_gpts=round(r_off, 4), overlap_gpts=round(r_on, 4),
             overlap_eff=round(eff_on, 4),
             serial_eff=round(eff_off, 4), **_tiling_of(c_on),
             **_comm_of(c_on))
        del c_on, c_off

    def ensemble_ab():
        # Batched-vs-sequential ensemble A/B at the parameter-sweep
        # point (N=8 at 64³ off-TPU): the sequential arm is N FRESH
        # contexts each paying its own trace+lower+compile — today's
        # aggregate cost of a sweep — with the compile-cache memo
        # cleared per member and disk persistence off, so the
        # chokepoint cannot quietly share compiles between arms.  The
        # batched arm is ONE context + new_ensemble(N): one vmapped
        # compile, one fused run.  Correctness gate: every member must
        # be BIT-identical (all vars, all ring slots) to its
        # sequential twin — vmap adds a leading axis, never changes
        # per-lane arithmetic.  The ≥2× ENSEMBLE_SPEEDUP_FLOOR is
        # CPU-scoped (compile dominates at 64³ on the proxy; re-base
        # on hardware where the chip-saturation win takes over).
        import numpy as np
        from yask_tpu import cache as ccache
        try:
            N = int(os.environ.get("YT_BENCH_ENSEMBLE", "8"))
        except ValueError:
            N = 8
        if N < 2:
            return
        g = 128 if on_tpu else 64

        def seed(ctx, i):
            rng = np.random.RandomState(1000 + i)
            arr = (rng.rand(g, g, g).astype(np.float32) - 0.5) * 0.1
            ctx.get_var("pressure").set_elements_in_slice(
                arr, [0, 0, 0, 0], [0, g - 1, g - 1, g - 1])

        # Both arms time ONLY the runs: context build + initial
        # conditions are identical per-member host work (numpy fills)
        # that would dilute the signal equally on both sides.  The
        # first run_solution/ens.run still pays trace+lower+compile —
        # that asymmetry (N compiles vs one vmapped compile) is the
        # thing being measured.
        def seq_arm():
            ctxs = []
            for i in range(N):
                ctx = build(fac, env, "iso3dfd", 8, g, "jit")
                seed(ctx, i)
                ctxs.append(ctx)
            t0s = time.perf_counter()
            for ctx in ctxs:
                # identical geometry ⇒ identical persistent key: the
                # memo would hand member 2..N member 1's executable
                # and measure a sweep that paid one compile, not N
                ccache.clear_memo()
                ctx.run_solution(0, steps - 1)
            t = time.perf_counter() - t0s
            finals = [{n: [np.asarray(a) for a in ring]
                       for n, ring in ctx._state.items()}
                      for ctx in ctxs]
            del ctxs
            return t, finals

        def bat_arm():
            from yask_tpu.runtime.init_utils import init_solution_vars
            ctx = build(fac, env, "iso3dfd", 8, g, "jit")
            ens = ctx.new_ensemble(N)
            for i in range(N):
                with ens.member(i) as c:
                    if i:   # member 0 was initialized by build();
                            # fresh members need the same baseline
                        init_solution_vars(c)
                    seed(c, i)
            ccache.clear_memo()
            t0b = time.perf_counter()
            ens.run(0, steps - 1)
            return time.perf_counter() - t0b, ctx, ens

        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            t_seq, finals = seq_arm()
            t_bat, ctx, ens = bat_arm()
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved
        for i in range(N):
            with ens.member(i) as c:
                for n, ring in finals[i].items():
                    for s, a in enumerate(ring):
                        b = np.asarray(c._state[n][s])
                        if not np.array_equal(a, b):
                            raise RuntimeError(
                                f"ensemble member {i} var {n} slot {s} "
                                "not bit-identical to its sequential "
                                f"twin (maxdiff {np.abs(a - b).max()})")

        def remeasure_ratio():
            sv = os.environ.pop("YT_COMPILE_CACHE", None)
            try:
                ts, _ = seq_arm()
                tb, c2, e2 = bat_arm()
                del c2, e2
                return ts / max(tb, 1e-12)
            finally:
                if sv is not None:
                    os.environ["YT_COMPILE_CACHE"] = sv

        emit(f"iso3dfd r=8 {g}^3 {plat} ensemble{N}-speedup",
             t_seq / max(t_bat, 1e-12), "x", remeasure=remeasure_ratio,
             ensemble=N, seq_secs=round(t_seq, 3),
             batched_secs=round(t_bat, 3),
             compile_ms=round(ctx._compile_secs * 1000.0, 1),
             cache_hit=ctx._last_cache_hit or "cold",
             batched_reason=ens.batched_reason)
        del ctx, ens

    def serve_batch_ab():
        # Serving-layer A/B at the same sweep point as ensemble_ab
        # (N=8 at 64³ off-TPU): the sequential arm is N fresh solo
        # contexts each paying its own compile (memo cleared per
        # member, disk cache off) — the no-server cost of answering N
        # tenants.  The serve arm is ONE StencilServer: N sessions on
        # one profile, submit-all-then-wait-all, so the batching
        # window groups them into one vmapped execution — PLUS the
        # server's honest overheads (worker handoff, pre-request
        # snapshots, journal rows, sanity gating).  Correctness gate:
        # every response bit-identical to its sequential twin's
        # written interiors.  The SERVE_BATCH_SPEEDUP_FLOOR (1.5×) is
        # CPU-scoped and deliberately below the 2× ensemble floor:
        # the serving machinery's per-request tax is part of what this
        # row tracks.
        import numpy as np
        from yask_tpu import cache as ccache
        from yask_tpu.serve import StencilServer
        from yask_tpu.serve.scheduler import extract_outputs
        try:
            N = int(os.environ.get("YT_BENCH_ENSEMBLE", "8"))
        except ValueError:
            N = 8
        if N < 2:
            return
        g = 128 if on_tpu else 64

        def seed_arr(i):
            rng = np.random.RandomState(1000 + i)
            return (rng.rand(1, g, g, g).astype(np.float32) - 0.5) * 0.1

        def seq_arm():
            ctxs = []
            for i in range(N):
                ctx = build(fac, env, "iso3dfd", 8, g, "jit")
                ctx.get_var("pressure").set_elements_in_slice(
                    seed_arr(i), [0, 0, 0, 0],
                    [0, g - 1, g - 1, g - 1])
                ctxs.append(ctx)
            t0s = time.perf_counter()
            for ctx in ctxs:
                ccache.clear_memo()  # N tenants, N compiles — the
                ctx.run_solution(0, steps - 1)   # cost being beaten
            t = time.perf_counter() - t0s
            outs = [extract_outputs(ctx) for ctx in ctxs]
            del ctxs
            return t, outs

        def serve_arm():
            srv = StencilServer(window_secs=0.1, max_batch=N,
                                preflight=False)
            sids = []
            for i in range(N):
                sid = srv.open_session(stencil="iso3dfd", radius=8,
                                       g=g, mode="jit", wf=2)
                srv.init_vars(sid)
                with srv.scheduler.session_ctx(sid) as c:
                    c.get_var("pressure").set_elements_in_slice(
                        seed_arr(i), [0, 0, 0, 0],
                        [0, g - 1, g - 1, g - 1])
                sids.append(sid)
            ccache.clear_memo()
            t0b = time.perf_counter()
            handles = [srv.submit_run(sid, 0, steps - 1)
                       for sid in sids]
            resps = [srv.wait(h, timeout=600) for h in handles]
            t = time.perf_counter() - t0b
            occ = max((r.batch for r in resps), default=0)
            srv.shutdown()
            for r in resps:
                if not r.ok:
                    raise RuntimeError(
                        f"serve arm request {r.rid}: {r.status} "
                        f"{r.error}")
            return t, resps, occ

        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            t_seq, seq_outs = seq_arm()
            t_srv, resps, occ = serve_arm()
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved
        for i, (want, r) in enumerate(zip(seq_outs, resps)):
            for n, a in want.items():
                b = r.outputs[n]
                if not np.array_equal(a, b):
                    raise RuntimeError(
                        f"serve tenant {i} var {n} not bit-identical "
                        "to its sequential twin "
                        f"(maxdiff {np.abs(a - b).max()})")

        def remeasure_ratio():
            sv = os.environ.pop("YT_COMPILE_CACHE", None)
            try:
                ts, _ = seq_arm()
                tb, _, _ = serve_arm()
                return ts / max(tb, 1e-12)
            finally:
                if sv is not None:
                    os.environ["YT_COMPILE_CACHE"] = sv

        emit(f"iso3dfd r=8 {g}^3 {plat} serve-batch{N}-speedup",
             t_seq / max(t_srv, 1e-12), "x", remeasure=remeasure_ratio,
             tenants=N, occupancy=occ, seq_secs=round(t_seq, 3),
             serve_secs=round(t_srv, 3))

    def serve_bucket_ab():
        # Cross-PROFILE serving A/B: N tenants on >=3 DISTINCT
        # geometries, all mapping to ONE bucket-ladder rung.  The
        # sequential arm is N fresh solo contexts — each geometry its
        # own prepared context, each member its own compile (memo
        # cleared, disk cache off): the no-server cost of a
        # mixed-geometry tenant population, and the bit-identity
        # oracle.  The serve arm opens every session with
        # ``bucket=True``: the planner hosts each tenant as a masked
        # sub-domain of the shared rung profile and the scheduler
        # rides ALL of them as one vmapped EnsembleRun — one compile,
        # occupancy N, despite no two tenants necessarily sharing a
        # geometry.  Gate: every tenant's outputs bit-identical to its
        # solo twin over its OWN domain (extract_outputs slices the
        # sub-domain back out of the bucket state).  The
        # SERVE_BUCKET_SPEEDUP_FLOOR (1.5x) sentinel rule is
        # CPU-scoped.
        import numpy as np
        from yask_tpu import cache as ccache
        from yask_tpu.serve import StencilServer, bucket_for
        from yask_tpu.serve.scheduler import extract_outputs
        try:
            N = int(os.environ.get("YT_BENCH_ENSEMBLE", "8"))
        except ValueError:
            N = 8
        if N < 2:
            return
        # three distinct geometries on one rung (24 off-TPU, 48 on):
        # the ladder's 8-multiples keep every sub-domain
        # sublane-aligned for free.
        gs_cycle = (40, 44, 48) if on_tpu else (20, 22, 24)
        gs = [gs_cycle[i % len(gs_cycle)] for i in range(N)]
        rung = bucket_for(max(gs))

        def seed_arr(i, gi):
            rng = np.random.RandomState(3000 + i)
            return (rng.rand(1, gi, gi, gi).astype(np.float32)
                    - 0.5) * 0.1

        def solo_arm():
            ctxs = []
            for i, gi in enumerate(gs):
                ctx = build(fac, env, "iso3dfd", 2, gi, "jit")
                ctx.get_var("pressure").set_elements_in_slice(
                    seed_arr(i, gi), [0, 0, 0, 0],
                    [0, gi - 1, gi - 1, gi - 1])
                ctxs.append(ctx)
            t0s = time.perf_counter()
            for ctx in ctxs:
                ccache.clear_memo()   # each geometry+member: own compile
                ctx.run_solution(0, steps - 1)
            t = time.perf_counter() - t0s
            outs = [extract_outputs(ctx) for ctx in ctxs]
            del ctxs
            return t, outs

        def bucket_arm():
            srv = StencilServer(window_secs=0.1, max_batch=N,
                                preflight=False)
            sids = []
            for i, gi in enumerate(gs):
                sid = srv.open_session(stencil="iso3dfd", radius=2,
                                       g=gi, mode="jit", wf=2,
                                       bucket=True)
                b = srv.session_bucket(sid)
                if b.get("decision") != "bucketed":
                    raise RuntimeError(
                        f"tenant {i} g={gi} did not bucket: {b}")
                srv.init_vars(sid)
                with srv.scheduler.session_ctx(sid) as c:
                    c.get_var("pressure").set_elements_in_slice(
                        seed_arr(i, gi), [0, 0, 0, 0],
                        [0, gi - 1, gi - 1, gi - 1])
                sids.append(sid)
            ccache.clear_memo()
            t0b = time.perf_counter()
            handles = [srv.submit_run(sid, 0, steps - 1)
                       for sid in sids]
            resps = [srv.wait(h, timeout=600) for h in handles]
            t = time.perf_counter() - t0b
            occ = max((r.batch for r in resps), default=0)
            srv.shutdown()
            for r in resps:
                if not r.ok:
                    raise RuntimeError(
                        f"bucket arm request {r.rid}: {r.status} "
                        f"{r.error}")
            return t, resps, occ

        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            t_solo, solo_outs = solo_arm()
            t_bkt, resps, occ = bucket_arm()
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved
        if occ < N:
            raise RuntimeError(
                f"bucketed tenants did not co-batch: occupancy {occ} "
                f"< {N} (geometries {sorted(set(gs))} on rung {rung})")
        # batch= alone is the INTENDED width; batched= proves the
        # vmapped executable really ran (a missing batching rule
        # degrades to sequential members and must not bank a speedup)
        if not all(r.batched for r in resps):
            raise RuntimeError(
                "bucket arm degraded to sequential members — "
                "speedup row withheld")
        for i, (want, r) in enumerate(zip(solo_outs, resps)):
            for n, a in want.items():
                b = r.outputs[n]
                if a.shape != b.shape or not np.array_equal(a, b):
                    raise RuntimeError(
                        f"bucketed tenant {i} (g={gs[i]}) var {n} not "
                        "bit-identical to its solo twin")

        def remeasure_ratio():
            sv = os.environ.pop("YT_COMPILE_CACHE", None)
            try:
                ts, _ = solo_arm()
                tb, _, _ = bucket_arm()
                return ts / max(tb, 1e-12)
            finally:
                if sv is not None:
                    os.environ["YT_COMPILE_CACHE"] = sv

        emit(f"iso3dfd r=2 mixed-g {plat} serve-bucket{N}-speedup",
             t_solo / max(t_bkt, 1e-12), "x",
             remeasure=remeasure_ratio, tenants=N,
             geometries=sorted(set(gs)), rung=rung, occupancy=occ,
             solo_secs=round(t_solo, 3), bucket_secs=round(t_bkt, 3))

    def serve_stream_ab():
        # Streaming/preemption A/B under MIXED traffic: one long run
        # plus a burst of 1-step requests submitted while it is in
        # flight.  Blocking arm (flush_every=0): the shorts wait out
        # the whole long run — their latency IS the long run.
        # Streaming arm (flush_every=steps): the scheduler executes
        # the long run in guarded chunks, preempts it at a chunk
        # boundary when the shorts are pending, runs them, then
        # re-queues the continuation — short-request p99 collapses to
        # about one chunk.  Both arms are pre-warmed (compile excluded
        # on both sides; the row tracks scheduling latency, not
        # amortization) and the long run's final state must be
        # BIT-identical across arms: jit chunked execution equals the
        # whole-range run exactly, preemption included.  No sentinel
        # floor — the pass criterion rides in the row.
        import numpy as np
        from yask_tpu.serve import StencilServer
        # 3axis is a pure neighbor average — unconditionally stable,
        # so the long run stays finite for hundreds of steps (iso3dfd
        # amplifies and overflows fp32 within ~40 steps).
        g = 96 if on_tpu else 64
        T = 150 * steps         # long enough to dominate the window
        nshort = 3

        srv = StencilServer(window_secs=0.02, max_batch=8,
                            preflight=False)

        def mk():
            sid = srv.open_session(stencil="3axis", radius=4, g=g,
                                   mode="jit", wf=2)
            srv.init_vars(sid)
            return sid

        # warm every chunk shape both arms will run (whole-range,
        # cadence chunks, 1-step shorts)
        srv.run(mk(), 0, T - 1, timeout=600)
        srv.run(mk(), 0, T - 1, flush_every=steps, timeout=600)
        srv.run(mk(), 0, 0, timeout=600)

        def arm(flush):
            long_sid = mk()
            shorts = [mk() for _ in range(nshort)]
            h_long = srv.submit_run(long_sid, 0, T - 1,
                                    flush_every=flush)
            time.sleep(0.05)   # window elapses; long run is in flight
            hs = [srv.submit_run(s, 0, 0) for s in shorts]
            rs = [srv.wait(h, timeout=600) for h in hs]
            r_long = srv.wait(h_long, timeout=600)
            for r in list(rs) + [r_long]:
                if not r.ok:
                    raise RuntimeError(
                        f"stream arm request {r.rid}: {r.status} "
                        f"{r.error}")
            lat = [r.queue_secs + r.run_secs for r in rs]
            return max(lat), r_long

        p99_block, r_block = arm(0)
        p99_stream, r_stream = arm(steps)
        srv.shutdown()
        if r_stream.preempted < 1:
            raise RuntimeError(
                "streaming arm was never preempted — the shorts did "
                "not interleave (long run too fast for the window?)")
        for n, a in r_block.outputs.items():
            b = r_stream.outputs[n]
            if not np.array_equal(a, b):
                raise RuntimeError(
                    f"preempted chunked long run diverged from the "
                    f"blocking run on {n}")

        def remeasure_ratio():
            pb, _ = arm(0)
            ps, _ = arm(steps)
            return pb / max(ps, 1e-12)

        emit(f"3axis r=4 {g}^3 {plat} serve-stream-p99-win",
             p99_block / max(p99_stream, 1e-12), "x",
             remeasure=remeasure_ratio,
             criterion="short-request p99 with streaming+preemption "
                       "< blocking p99",
             criterion_met=bool(p99_stream < p99_block),
             p99_block_ms=round(p99_block * 1e3, 1),
             p99_stream_ms=round(p99_stream * 1e3, 1),
             shorts=nshort, long_steps=T, flush_every=steps,
             preempts=r_stream.preempted,
             stream_events=len(r_stream.streams))

    def pipeline_fusion_ab():
        # Cross-solution pipeline-fusion A/B on the 3-stage RTM chain
        # (forward iso wave -> imaging correlation -> 3-point
        # smoothing): the fused arm is ONE merged program (bound vars
        # never round-trip HBM; the model says 2× traffic for this
        # chain), the chained arm is the host-chained oracle — per
        # step, per stage, each binding pushed through host slice
        # copies.  Correctness gate: every written var of every stage
        # BIT-identical between arms — both arms run the same jit
        # temporal schedule, where the merge is exact (the pallas K>1
        # chunked schedule is only tolerance-equal to stepwise runs,
        # a pre-existing property of temporal chunking, so the perf
        # headline for that path lives in tpu_session, not here).
        # Timing excludes the warmup/compile window on both sides:
        # unlike the ensemble row, the fusion win being tracked is
        # steady-state traffic + dispatch + push tax, not compile
        # amortization.  PIPELINE_FUSION_FLOOR (1.2×) is CPU-scoped.
        import numpy as np
        from yask_tpu.ops.pipeline import (SolutionPipeline, rtm_chain,
                                           pipeline_hbm_model)
        g = 64 if on_tpu else 32

        def mk(fuse):
            stages, bindings = rtm_chain(radius=2)
            pipe = SolutionPipeline(env, stages, bindings)
            pipe.apply_command_line_options(f"-g {g} -mode jit "
                                            "-wf_steps 2")
            pipe.prepare(fuse=fuse)
            v = pipe.get_var("fwd", "pressure")
            rng = np.random.RandomState(7)
            arr = (rng.rand(g, g, g).astype(np.float32) - 0.5) * 0.1
            for t in range(v.get_first_valid_step_index(),
                           v.get_last_valid_step_index() + 1):
                v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                        [t, g - 1, g - 1, g - 1])
            return pipe

        fused, chained = mk(True), mk(False)
        # warmup window pays trace+lower+compile on both sides AND
        # feeds the bit-equality gate
        fused.run(0, steps - 1)
        chained.run(0, steps - 1)
        bad = fused.compare(chained)
        if bad:
            raise RuntimeError(
                f"pipeline fusion not bit-identical to the "
                f"host-chained oracle ({bad} mismatching elements)")

        def arms(lo, hi):
            t0f = time.perf_counter()
            fused.run(lo, hi)
            tf = time.perf_counter() - t0f
            t0c = time.perf_counter()
            chained.run(lo, hi)
            return tf, time.perf_counter() - t0c

        t_fused = t_chain = 0.0
        trials = 3
        for i in range(trials):
            tf, tc = arms((i + 1) * steps, (i + 2) * steps - 1)
            t_fused += tf
            t_chain += tc
        bad = fused.compare(chained)
        if bad:
            raise RuntimeError(
                f"pipeline fusion diverged from the host-chained "
                f"oracle during timed steps ({bad} mismatches)")

        def remeasure_ratio():
            tf, tc = arms((trials + 1) * steps,
                          (trials + 2) * steps - 1)
            return tc / max(tf, 1e-12)

        hbm = pipeline_hbm_model(fused)
        emit(f"rtm3 r=2 {g}^3 {plat} pipeline-fusion-speedup",
             t_chain / max(t_fused, 1e-12), "x",
             remeasure=remeasure_ratio, stages=len(fused.stage_names),
             fused=fused.fused, chained_secs=round(t_chain, 3),
             fused_secs=round(t_fused, 3), hbm_bytes_model=hbm)
        fused.end()
        chained.end()

    def pipeline_push_ab():
        # Push-memory tile-graph fusion A/B on the PURE rtm chain
        # (rtm_img_pure: no img(t) self-read, so the merged image var's
        # only reader is the smoother at +step — the push flagship):
        # three arms at the same pallas K=1 temporal schedule, where
        # the merge and the push are both bit-exact vs the host-chained
        # oracle.  push = fused with the image tile consumed in-VMEM
        # (no input DMA, no write-back — the var leaves HBM entirely);
        # nopush = the r16 source-fused arm (bound reads eliminated,
        # the image still round-trips HBM); chained = the oracle.
        # Bit-equality gates run BEFORE and AFTER the timed windows on
        # both fused arms.  The headline is push vs source-fused (the
        # r16 baseline); the hbm model's chained/fused/fused_push
        # bytes-per-point ride the row with each arm's achieved
        # bandwidth so the modeled traffic drop is a ledger number.
        import numpy as np
        from yask_tpu.ops.pipeline import (SolutionPipeline, rtm_chain,
                                           pipeline_hbm_model)
        g = 64 if on_tpu else 32

        def mk(fuse, push_cli):
            stages, bindings = rtm_chain(radius=2, accumulate=False)
            pipe = SolutionPipeline(env, stages, bindings)
            pipe.apply_command_line_options(
                f"-g {g} -mode pallas -wf_steps 1 {push_cli}")
            pipe.prepare(fuse=fuse)
            v = pipe.get_var("fwd", "pressure")
            rng = np.random.RandomState(7)
            arr = (rng.rand(g, g, g).astype(np.float32) - 0.5) * 0.1
            for t in range(v.get_first_valid_step_index(),
                           v.get_last_valid_step_index() + 1):
                v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                        [t, g - 1, g - 1, g - 1])
            return pipe

        push = mk(True, "-push on")
        nopush = mk(True, "-push off")
        chained = mk(False, "-push off")
        pal = (push.plan().get("pallas") or {})
        if not pal.get("push"):
            raise RuntimeError(
                f"push arm did not engage: {push.plan()['reasons']}")

        def gate(tag):
            bad = push.compare(chained) + nopush.compare(chained)
            if bad:
                raise RuntimeError(
                    f"push fusion not bit-identical to the "
                    f"host-chained oracle {tag} ({bad} mismatches)")

        # warmup pays trace+compile on all arms AND feeds the bit gate
        push.run(0, steps - 1)
        nopush.run(0, steps - 1)
        chained.run(0, steps - 1)
        gate("before timed windows")

        def arm(pipe, lo, hi):
            t0 = time.perf_counter()
            pipe.run(lo, hi)
            return time.perf_counter() - t0

        t_push = t_nopush = t_chain = 0.0
        trials = 3
        for i in range(trials):
            lo, hi = (i + 1) * steps, (i + 2) * steps - 1
            t_push += arm(push, lo, hi)
            t_nopush += arm(nopush, lo, hi)
            t_chain += arm(chained, lo, hi)
        gate("after timed windows")

        def remeasure_ratio():
            lo = (trials + 1) * steps
            hi = (trials + 2) * steps - 1
            return arm(nopush, lo, hi) / max(arm(push, lo, hi), 1e-12)

        hbm = pipeline_hbm_model(push, push_vars=pal.get("push_vars"))
        n_steps = trials * steps
        pts = g ** 3 * n_steps

        def gbs(bpp, secs):
            return round(bpp * pts / max(secs, 1e-12) / 1e9, 3)

        emit(f"rtm3-pure r=2 {g}^3 {plat} pipeline-push-speedup",
             t_nopush / max(t_push, 1e-12), "x",
             remeasure=remeasure_ratio,
             criterion="push arm >= source-fused arm",
             criterion_met=bool(t_push <= t_nopush),
             push_vars=pal.get("push_vars"),
             hbm_bytes_model=hbm,
             push_secs=round(t_push, 3),
             fused_secs=round(t_nopush, 3),
             chained_secs=round(t_chain, 3),
             achieved_gbs_push=gbs(hbm["fused_push_bytes_pp"], t_push),
             achieved_gbs_fused=gbs(hbm["fused_bytes_pp"], t_nopush),
             achieved_gbs_chained=gbs(hbm["chained_bytes_pp"], t_chain),
             chained_over_push=round(
                 t_chain / max(t_push, 1e-12), 4))
        push.end()
        nopush.end()
        chained.end()

    def serve_resident_ab():
        # Device-resident bulk serving A/B: the SAME work list — 4
        # sessions x 4 single-step items — drained through the
        # resident executor (one device-lock hold, one end-of-queue
        # sync, one extraction per session) vs per-request dispatch
        # through the scheduler (queue + batching window + rollback
        # snapshot + host extraction per item).  Responses bit-gated
        # identical across arms before the row is trusted; profile is
        # shared and pre-warmed so neither arm pays compile.
        import numpy as np
        from yask_tpu.serve.registry import SessionRegistry
        from yask_tpu.serve.scheduler import BatchScheduler
        from yask_tpu.serve.resident import run_per_request
        g, occupancy, nsteps = 16, 4, 4
        rng = np.random.RandomState(11)
        arr = (rng.rand(g, g, g).astype(np.float32) - 0.5) * 0.1

        reg = SessionRegistry(fac, env)
        prof = reg.get_profile("iso3dfd", 2, str(g), mode="jit", wf=1)
        sched = BatchScheduler(reg, window_secs=0.0)

        def open_sessions():
            sids = []
            for i in range(occupancy):
                s = reg.open_session(prof)
                sids.append(s.sid)
                with sched.session_ctx(s.sid) as ctx:
                    v = ctx.get_var("pressure")
                    for t in range(v.get_first_valid_step_index(),
                                   v.get_last_valid_step_index() + 1):
                        v.set_elements_in_slice(
                            arr * (i + 1), [t, 0, 0, 0],
                            [t, g - 1, g - 1, g - 1])
            return sids

        def work(sids):
            return [(sid, t, t) for t in range(nsteps)
                    for sid in sids]

        # warm the shared profile's compile outside both timed arms
        warm = open_sessions()
        sched.run_resident(work(warm)[:1])
        for sid in warm:
            reg.close_session(sid)

        sids_r = open_sessions()
        t0 = time.perf_counter()
        res = sched.run_resident(work(sids_r))
        t_resident = time.perf_counter() - t0

        sids_p = open_sessions()
        t0 = time.perf_counter()
        base = run_per_request(sched, work(sids_p))
        t_per_req = time.perf_counter() - t0

        for sr, sp in zip(sids_r, sids_p):
            for name, a in res[sr]["outputs"].items():
                if not np.array_equal(a, base[sp]["outputs"][name]):
                    raise RuntimeError(
                        f"resident arm diverged from per-request "
                        f"dispatch on {name}")

        def remeasure_ratio():
            s1, s2 = open_sessions(), open_sessions()
            t0 = time.perf_counter()
            sched.run_resident(work(s1))
            tr = time.perf_counter() - t0
            t0 = time.perf_counter()
            run_per_request(sched, work(s2))
            return (time.perf_counter() - t0) / max(tr, 1e-12)

        emit(f"iso3dfd r=2 {g}^3 {plat} serve-resident-speedup",
             t_per_req / max(t_resident, 1e-12), "x",
             remeasure=remeasure_ratio,
             criterion=f"resident arm strictly faster at "
                       f"occupancy {occupancy}",
             criterion_met=bool(t_resident < t_per_req),
             occupancy=occupancy, items=occupancy * nsteps,
             resident_secs=round(t_resident, 4),
             per_request_secs=round(t_per_req, 4))
        sched.shutdown()

    # explicit section(...) calls (not a loop over a tuple): repo_lint's
    # BARE-DEVICE-CALL closure sanctions device work lexically, from
    # the names passed into the guard invokers
    section(iso3dfd_jit, t0, budget_secs)
    section(iso3dfd_pallas, t0, budget_secs)
    section(cube_wavefront, t0, budget_secs)
    section(iso3dfd_skew2d, t0, budget_secs)
    section(iso3dfd_trapezoid, t0, budget_secs)
    section(ssg_elastic, t0, budget_secs)
    section(iso3dfd_bf16, t0, budget_secs)
    section(awp_decomposed, t0, budget_secs)
    section(sm_coalesce, t0, budget_secs)
    section(sp_overlap, t0, budget_secs)
    section(ensemble_ab, t0, budget_secs)
    section(serve_batch_ab, t0, budget_secs)
    section(serve_bucket_ab, t0, budget_secs)
    section(serve_stream_ab, t0, budget_secs)
    section(pipeline_fusion_ab, t0, budget_secs)
    section(pipeline_push_ab, t0, budget_secs)
    section(serve_resident_ab, t0, budget_secs)
    return list(ROWS)


def main() -> int:
    # runs on whatever backend JAX finds, in this one process; the
    # platform rides every row and the artifact (no probe, no fallback)
    from yask_tpu import yk_factory
    fac = yk_factory()
    env = fac.new_env()
    # graceful section-skip margin: sections past the budget are
    # skipped and the artifact is still written
    try:
        budget = float(os.environ.get("YT_SUITE_BUDGET", "900"))
    except ValueError:
        budget = 900.0
    rows = run_suite(fac, env, budget_secs=max(budget - 60.0, 30.0))
    out = os.path.join(_ROOT, "BENCH_suite_latest.json")
    try:
        with open(out, "w") as f:
            json.dump({"platform": env.get_platform(), "rows": rows}, f,
                      indent=1)
    except OSError:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
