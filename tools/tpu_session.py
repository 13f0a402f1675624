"""TPU-session protocol, as one command.

Round 3 ran the Pallas backend on real Mosaic (v5e) for the first time:
22/26 validation-matrix cases matched the jit oracle and the
pipeline_dmas A/B measured 1.75× before that session was lost.  This
script is the staged validation + tuning session to run on a chip —
the remaining goals are 26/26 validation, the skew A/B, a completed
joint tune, and a tuned bench number.  (``chip_smoke.py`` is the quick
proof that the main path runs on the chip; the benchmark of ROADMAP S0
replaces this script.)  Stages:

1. smoke: iso3dfd on the XLA path (device sanity);
2. validate: the pallas equivalence matrix ON DEVICE (interpret=False,
   real Mosaic lowering) against the jit path — runs FIRST on full
   sessions, but AFTER the perf stages on ``--quick`` sessions (round
   3 lost its hardware numbers while validation compiles were still
   grinding);
3. A/B: pipeline_dmas / skew / misaligned-E_sk / bf16 chunk variants
   (bit-equality cross-checks + timing on real DMA engines) plus the
   shard_pallas overlapped-exchange arms when >1 device is attached;
4. tune: joint (K, block) auto-tuner walk on iso3dfd at the bench size;
5. report: a BENCH-style JSON line per stage (each perf row is
   appended to the perf ledger the moment it is measured);
6. compile_cache_ab: cold-vs-warm AOT compile through the persistent
   cache (the warm rebuild must show ZERO lowerings on the cache's
   trace counter — a disk round-trip of a serialized executable on the
   real backend) and ensemble_ab: N-member batched-vs-sequential run
   with per-member bit-identity; then
7. compile-time A/B of the ``max_vinstr`` tile cap on ssg/swe2d.

Every stage is crash-isolated AND journaled (yask_tpu.resilience):
each case appends its outcome to SESSION_JOURNAL.jsonl the moment it
is known, ``--resume`` completes only the cases an interrupted
session left unfinished (and, with ``YT_CKPT_DIR`` set, restarts
MID-case from the supervision cadence's last checkpoint instead of
re-running the whole case), a consecutive-fault breaker (persisted
across sessions) aborts the session loudly when the backend dies
mid-run, and every
measured row passes the result-sanity guards (an all-zero field is banked as a quarantined ANOMALY
row, never a clean number — the round-3 quick-matrix incident).

Run: ``python tools/tpu_session.py [-g 512] [--quick] [--resume |
--fresh] [--stages smoke,validate,...] [-no-trace]``
(needs the real backend: do NOT set JAX_PLATFORMS=cpu).
``YT_SESSION_MATRIX="name:radius,..."`` ("-" = default radius)
overrides the validation matrix; ``YT_SESSION_JOURNAL`` relocates the
journal; ``YT_SESSION_BANK=1`` banks rows off-TPU (tests).

Tracing is ON by default here (``-trace``/``-no-trace``; an explicit
``YT_TRACE`` env wins): chip time is the scarce resource, and a
span timeline that joins the session journal / ledger rows is exactly
the evidence a post-mortem of an interrupted session needs.  See
docs/observability.md.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from yask_tpu.resilience import (Breaker, Fault, SessionJournal,
                                 TERMINAL_OUTCOMES, anomaly_fields,
                                 check_output, guarded_call,
                                 maybe_corrupt)

MATRIX = [
    ("3axis", 1), ("cube", 1), ("iso3dfd", 2), ("iso3dfd_sponge", 2),
    ("ssg", 2), ("awp", None), ("tti", 2), ("swe2d", None),
    ("box", None), ("test_scratch_3d", None), ("test_stream_3d", None),
    ("test_boundary_3d", None), ("test_misc_2d", None),
]

STAGES = ("smoke", "validate", "chunk_abs", "tune_bench",
          "compile_cache_ab", "ensemble_ab", "pipeline_fusion_ab",
          "push_ab", "serving", "serving_bucket", "serve_resident_ab",
          "compile_time")


def matrix_cases():
    """The validation matrix, overridable via YT_SESSION_MATRIX
    ("name:radius,..." with "-" for the stencil's default radius) —
    the resume acceptance test runs a 2-stencil matrix on the CPU
    mesh instead of burning minutes on all 13."""
    raw = os.environ.get("YT_SESSION_MATRIX", "").strip()
    if not raw:
        return list(MATRIX)
    out = []
    for part in raw.split(","):
        name, _, rad = part.strip().partition(":")
        out.append((name, None if rad in ("", "-") else int(rad)))
    return out


def log(stage, **kv):
    print(json.dumps({"stage": stage, **kv}), flush=True)


def bank_row(plat, env, line, roofline=None, sanity=None):
    """Persist one measured TPU row in the unified perf ledger
    (source ``tpu_session``) with provenance + a sentinel verdict —
    every row is banked the moment it exists.  A failed ``sanity``
    verdict quarantines the row (structured ANOMALY, excluded from
    sentinel baselines)."""
    line = dict(line)
    if sanity and not sanity.get("ok", True):
        line.update(anomaly_fields(sanity))
    try:
        from yask_tpu.perflab import capture_provenance
        from yask_tpu.perflab.sentinel import guard_and_append
        prov = capture_provenance(
            platform=plat,
            device_kind=env.get_device_kind())
        extra = {k: v for k, v in line.items()
                 if k not in ("metric", "value", "unit", "platform",
                              "quarantined", "anomaly")}
        guard_and_append(line["metric"], line["value"], line["unit"],
                         plat, "tpu_session", prov,
                         roofline=roofline, extra=extra or None,
                         sanity=sanity)
    except Exception as e:  # noqa: BLE001
        log("ledger", error=str(e)[:160])
    return line


def build(fac, env, name, mode, g, radius, wf=1, block=None, tune=False,
          tune_max=None):
    from yask_tpu.runtime.init_utils import init_solution_vars
    ctx = fac.new_solution(env, stencil=name, radius=radius)
    ctx.apply_command_line_options(f"-g {g} -wf_steps {wf}")
    ctx.get_settings().mode = mode
    if tune:
        # Must be set BEFORE prepare: pallas pads are then planned for
        # tune_max_wf_steps so the joint walk can grow K, not only
        # shrink it (K-doubling candidates would otherwise all fail pad
        # validation and cache as inf).
        ctx.get_settings().do_auto_tune = True
        if tune_max:
            ctx.get_settings().tune_max_wf_steps = tune_max
    if block:
        for d, b in block.items():
            ctx.set_block_size(d, b)
    # static preflight (default-on): catch statically-infeasible configs
    # (the round-3 VMEM-spill class) BEFORE spending chip time
    # on a compile; findings are logged, the stage still proceeds so a
    # checker false-positive cannot cost a hardware window
    from yask_tpu.checker import preflight
    if not preflight(ctx):
        log("preflight", name=name, mode=mode, ok=False)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


def interior_slice(ctx):
    """A small interior slice of the first var around the domain center
    (seeded nonzero by init_solution_vars) — the sanity-guard probe."""
    name = ctx.get_var_names()[0]
    v = ctx.get_var(name)
    t = ctx._cur_step
    mid = [s // 2 for s in
           (ctx.get_settings().global_domain_sizes[d]
            for d in ctx.get_domain_dim_names())]
    return v.get_elements_in_slice([t] + [c - 1 for c in mid],
                                   [t] + [c + 1 for c in mid])


class SessionRunner:
    """Journal + breaker wiring around every stage/case: outcomes are
    durable the moment they are known, ``--resume`` skips journaled
    terminal cases, and ``breaker.threshold`` consecutive classified
    faults abort the whole session (a dead backend must end it loudly,
    not grind every remaining case against nothing)."""

    def __init__(self, journal: SessionJournal, resume: bool,
                 breaker: Breaker):
        self.journal = journal
        self.resume = resume
        self.breaker = breaker
        self.last_status = ""   # "skipped"|"fault"|terminal outcome

    def pending(self, stage, cases):
        if not self.resume:
            return list(cases)
        return self.journal.pending(stage, list(cases))

    def run_case(self, stage, case, fn):
        """One journaled case.  ``fn`` returning ``{"outcome":
        "anomaly"|"skip", ...}`` selects a non-ok terminal outcome
        (details journaled); any other return is outcome ``ok``."""
        if self.resume and self.journal.completed(stage, case):
            self.last_status = "skipped"
            log(stage, case=case, skipped="journaled complete")
            return None
        attempt = self.journal.attempts(stage, case) + 1
        self.journal.record(stage, case, "started", attempt=attempt)
        site = f"session.{stage}" + (f".{case}" if case else "")
        try:
            out = guarded_call(fn, site=site, breaker=self.breaker)
        except Fault as f:
            self.last_status = "fault"
            self.journal.record(stage, case, "fault", attempt=attempt,
                                kind=f.kind, error=str(f)[:160])
            log(stage, case=case, fault=f.kind, error=str(f)[:200])
            if self.breaker.tripped:
                self.journal.record(
                    "session", "", "aborted",
                    reason=f"{self.breaker.consecutive} consecutive "
                           f"faults (last: {f.kind})")
                raise
            return None
        except Exception as e:  # noqa: BLE001 - stage isolation
            self.last_status = "fault"
            self.journal.record(stage, case, "fault", attempt=attempt,
                                error=str(e)[:160])
            log(stage, case=case, error=str(e)[:200])
            return None
        outcome, detail = "ok", {}
        if isinstance(out, dict) and out.get("outcome") \
                in TERMINAL_OUTCOMES:
            outcome = out["outcome"]
            detail = {k: v for k, v in out.items() if k != "outcome"}
        self.last_status = outcome
        self.journal.record(stage, case, outcome, attempt=attempt,
                            **detail)
        return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    g_bench = 512
    quick = False
    resume = False
    trace = True
    stages = list(STAGES)
    journal_path = None
    i = 0
    while i < len(argv):
        if argv[i] == "-g":
            g_bench = int(argv[i + 1]); i += 2
        elif argv[i] == "--quick":
            quick = True; i += 1
        elif argv[i] == "--resume":
            resume = True; i += 1
        elif argv[i] in ("-trace", "--trace"):
            trace = True; i += 1
        elif argv[i] in ("-no-trace", "--no-trace"):
            trace = False; i += 1
        elif argv[i] == "--fresh":
            resume = False
            try:
                os.remove(SessionJournal().path)
            except OSError:
                pass
            i += 1
        elif argv[i] == "--stages":
            stages = [s.strip() for s in argv[i + 1].split(",")
                      if s.strip()]
            i += 2
        else:
            print(__doc__)
            return 2

    # span tracing defaults ON for hardware sessions (an explicit
    # YT_TRACE env wins either way; -no-trace opts out): the trace is
    # the post-mortem record of scarce chip time
    if trace:
        os.environ.setdefault("YT_TRACE", "1")

    from yask_tpu import yk_factory
    fac = yk_factory()
    env = fac.new_env()
    plat = env.get_platform()
    log("env", platform=plat, devices=env.get_num_ranks())
    if plat != "tpu" and os.environ.get("YT_TPU_SESSION_FORCE") != "1":
        log("env", error="not on TPU — this protocol needs real hardware "
            "(YT_TPU_SESSION_FORCE=1 dry-runs the logic in interpret "
            "mode)")
        return 1
    should_bank = (plat == "tpu"
                   or os.environ.get("YT_SESSION_BANK") == "1")

    journal = SessionJournal(journal_path)
    # growth bound: repeated sessions append to the same journal;
    # past YT_JOURNAL_MAX_BYTES (8 MiB default) compact at session open
    dropped = journal.compact_if_large()
    if dropped:
        log("journal", compacted_rows=dropped)
    # the breaker is PERSISTENT: a restarted session must not reset an
    # open breaker; any success resets it
    from yask_tpu.resilience import default_breaker_path
    runner = SessionRunner(
        journal, resume,
        Breaker(threshold=3, path=default_breaker_path()))
    journal.record("session", "", "started", quick=quick,
                   resume=resume, g=g_bench, stages=stages)

    def record(line, roofline=None, sanity=None):
        return bank_row(plat, env, line, roofline=roofline,
                        sanity=sanity)

    def run_span(ctx, first, last, tag):
        """Checkpointed span (forward time): with YT_CKPT_DIR set the
        supervision cadence snapshots every step into a per-case
        subdirectory, and under ``--resume`` a mid-case checkpoint
        restores and only the REMAINING steps run — an interruption
        costs the un-checkpointed tail, not the whole case."""
        base = os.environ.get("YT_CKPT_DIR", "")
        if not base:
            ctx.run_solution(first, last)
            return
        o = ctx.get_settings()
        o.ckpt_dir = os.path.join(base, tag.replace("/", "_"))
        if o.ckpt_every <= 0:
            o.ckpt_every = 1   # session cases are short; per-step
        if resume:
            from yask_tpu.resilience import restore_checkpoint
            path = os.path.join(o.ckpt_dir,
                                f"{ctx.get_name()}.ckpt.npz")
            if guarded_call(restore_checkpoint, ctx, path,
                            site="ckpt.restore"):
                done = ctx._cur_step   # next step the run continues at
                log("ckpt", case=tag, resumed_at=int(done))
                if done > last:
                    return
                first = max(first, done)
        ctx.run_solution(first, last)

    # 1) smoke
    def smoke():
        ctx = build(fac, env, "iso3dfd", "jit", 128, 2)
        run_span(ctx, 0, 4, "smoke")
        log("smoke", ok=True)

    def run_matrix():
        # on-device pallas validation matrix
        failures = []
        cases = matrix_cases()
        if quick and not os.environ.get("YT_SESSION_MATRIX"):
            cases = cases[:4]

        def one_case(name, radius):
            def body():
                ref = build(fac, env, name, "jit", 32, radius)
                run_span(ref, 0, 3, f"validate.{name}.ref")
                # oracle-sanity: an all-zero reference makes every
                # comparison vacuous (zero stays zero under the linear
                # homogeneous stencils) — the round-3 all-zero matrix
                # "matched" exactly this way
                overdict = check_output(
                    maybe_corrupt("session.validate.oracle",
                                  interior_slice(ref)))
                case_bad = 0
                anom = list(overdict["anomalies"])
                for wf in (1, 2):
                    p = build(fac, env, name, "pallas", 32, radius,
                              wf=wf)
                    run_span(p, 0, 3, f"validate.{name}.K{wf}")
                    verdict = check_output(
                        maybe_corrupt("session.validate.result",
                                      interior_slice(p)))
                    bad = p.compare_data(ref, epsilon=1e-3,
                                         abs_epsilon=1e-4)
                    log("validate", stencil=name, K=wf,
                        mismatches=int(bad),
                        **({"anomalies": verdict["anomalies"]}
                           if not verdict["ok"] else {}))
                    anom += verdict["anomalies"]
                    if bad:
                        case_bad += int(bad)
                        failures.append((name, wf, int(bad)))
                if anom:
                    failures.append((name, "anomaly",
                                     ",".join(sorted(set(anom)))))
                    return {"outcome": "anomaly",
                            "anomalies": sorted(set(anom))}
                return {"mismatches": case_bad}
            return body

        radii = dict(cases)
        for name in runner.pending("validate", [n for n, _ in cases]):
            out = runner.run_case("validate", name,
                                  one_case(name, radii[name]))
            if out is None and runner.last_status == "fault":
                failures.append((name, "fault", ""))
        if failures:
            log("validate", summary="FAILURES", detail=failures)
        else:
            log("validate", summary="all pallas cases match jit on "
                "device")

    def chunk_ab_stages() -> None:
        """Stage 3 (chunk A/Bs), setup included.  Crash-isolated from
        stages 4-5: the tune/bench build their own context, so a
        failure planning the flagship chunk must not cost the
        session's headline hardware number (round-3 failure mode)."""
        ab_cases = ["pipeline_ab", "skew_ab.K2", "skew_ab.K4",
                    "vmem_ladder", "esk_ab", "trapezoid_ab", "bf16_ab",
                    "overlap_ab"]
        if not runner.pending("chunk_abs", ab_cases):
            log("chunk_abs", skipped="all cases journaled complete")
            return
        # 3) pipeline + skew A/Bs (timing on real DMA engines).  Each stage
        #    is isolated: a Mosaic failure in one A/B must not cost the rest
        #    of the session (chip time is budgeted).
        from yask_tpu.ops.pallas_stencil import build_pallas_chunk
        from yask_tpu.utils.idx_tuple import IdxTuple
        from yask_tpu.compiler.solution_base import create_solution
        import jax
        gi = min(g_bench, 256)
        prog = create_solution("iso3dfd", radius=8).get_soln().compile().plan(
            IdxTuple(x=gi, y=gi, z=gi),
            extra_pad={"x": (32, 32), "y": (32, 32), "z": (0, 0)})

        # Seed INTERIORS (pads must stay zero — the ghost-zero invariant):
        # a zero state would make every A/B cross-check vacuous, since
        # iso3dfd is linear homogeneous and zero stays zero.
        def seeded_init(prog_=None):
            prog_ = prog_ or prog
            rng = np.random.RandomState(7)
            init = {}
            for name, g in prog_.geoms.items():
                if g.is_scratch:
                    continue
                a = np.zeros(tuple(g.shape), np.float32)
                idx = tuple(
                    slice(g.origin[dn], g.origin[dn] + prog_.sizes[dn])
                    if kind == "domain" else slice(None)
                    for dn, kind in g.axes)
                shape = a[idx].shape
                if name == "vel":
                    a[idx] = 0.0005 + rng.rand(*shape).astype(np.float32) \
                        * 0.0005
                else:
                    a[idx] = (rng.rand(*shape).astype(np.float32) - 0.5) * 0.1
                init[name] = np.asarray(a, dtype=prog_.dtype)
            return init

        state = prog.alloc_state(init=seeded_init())
        interp = plat != "tpu"   # only under YT_TPU_SESSION_FORCE
        from yask_tpu.ops.pallas_stencil import default_vmem_budget
        budget = default_vmem_budget(plat, env.get_device_kind())
        case_anomalies = []   # verdicts since the current case began

        def time_chunk(tag, prog_=None, state_=None, metric=None,
                       npts=None, **kw):
            """Time one chunk variant; returns its one-chunk output state
            (or None on failure/anomaly) so A/B stages can cross-validate.
            The default (prog, state) pair is the fp32 flagship; the bf16
            stage passes its own so the timing/recording protocol stays
            single-definition.  Outputs pass the sanity guards: an
            all-zero/NaN chunk result banks a QUARANTINED row and is
            withheld from the bit-equality cross-checks (two corrupt arms
            matching proves nothing)."""
            prog_ = prog_ or prog
            state_ = state_ if state_ is not None else state
            vb = kw.pop("vmem_budget", budget)
            time_chunk.gpts = None   # last successful rate, for ratio rows
            try:
                chunk, tb = build_pallas_chunk(prog_, interpret=interp,
                                               vmem_budget=vb, **kw)
                from yask_tpu.cache import aot_compile
                fn = chunk if interp else \
                    aot_compile(chunk, (state_, 0), platform=plat).fn
                st1 = fn(state_, 0)
                jax.block_until_ready(st1)
                st = st1
                t0 = time.perf_counter()
                for _ in range(5):
                    st = fn(st, 0)
                jax.block_until_ready(st)
                dt = (time.perf_counter() - t0) / 5
                k = kw.get("fuse_steps", 1)
                gpts = round((npts or gi ** 3) * k / dt / 1e9, 2)
                st1 = maybe_corrupt("session.chunk_result", st1)
                sanity = check_output(st1)
                log(tag, **{k2: v for k2, v in kw.items()},
                    tile_mib=round(tb / 2**20, 2),
                    secs_per_chunk=round(dt, 5), gpts=gpts,
                    **({"anomalies": sanity["anomalies"]}
                       if not sanity["ok"] else {}))
                if should_bank:
                    record({
                        "metric": metric or (f"iso3dfd r=8 {gi}^3 fp32 tpu "
                                             f"pallas chunk ({tag} {kw})"),
                        "value": gpts, "unit": "GPts/s", "platform": plat,
                        "vs_baseline": round(gpts / 500.0, 4)},
                        sanity=sanity)
                if not sanity["ok"]:
                    case_anomalies.extend(sanity["anomalies"])
                    return None
                time_chunk.gpts = gpts
                return st1
            except Exception as e:  # noqa: BLE001
                log(tag, error=str(e)[:300], **kw)
                return None

        def max_abs_diff(a, b):
            m = 0.0
            for n in a:
                for x, y in zip(a[n], b[n]):
                    m = max(m, float(jax.numpy.max(jax.numpy.abs(x - y))))
            return m

        def case_outcome():
            """Terminal-outcome dict for run_case from the verdicts the
            case's time_chunk calls accumulated."""
            if case_anomalies:
                out = {"outcome": "anomaly",
                       "anomalies": sorted(set(case_anomalies))}
                case_anomalies.clear()
                return out
            return {}

        def pipeline_case():
            unpiped = time_chunk("pipeline_ab", fuse_steps=2,
                                 pipeline_dmas=False, skew=False)
            piped = time_chunk("pipeline_ab", fuse_steps=2,
                               pipeline_dmas=True, skew=False)
            if unpiped is not None and piped is not None:
                # bit-equality promised by the protocol: double-buffering
                # must not change values (the aliasing hazard CLAUDE.md
                # documents)
                log("pipeline_ab", fuse_steps=2,
                    max_abs_diff=float(max_abs_diff(unpiped, piped)))
            return case_outcome()

        def skew_case(k):
            # skew A/B: uniform shrink vs streaming skewed wavefront,
            # growing K; the two tilings must agree numerically on real
            # Mosaic (first hardware execution of the carry machinery)
            def body():
                uni = time_chunk("skew_ab", fuse_steps=k, skew=False)
                skw = time_chunk("skew_ab", fuse_steps=k, skew=True)
                if uni is not None and skw is not None:
                    log("skew_ab", fuse_steps=k,
                        max_abs_diff=float(max_abs_diff(uni, skw)))
                # 1-D vs 2-D: force BOTH lead dims (the multi-dim carry's
                # first hardware execution) and bit-compare against the
                # 1-D arm — the second dim's row carry + diagonal corner
                # propagation must agree exactly on real Mosaic
                sk2 = time_chunk("skew2d_ab", fuse_steps=k,
                                 metric=(f"iso3dfd r=8 {gi}^3 fp32 tpu "
                                         f"pallas chunk (skew2d K{k})"),
                                 skew=["x", "y"])
                if skw is not None and sk2 is not None:
                    log("skew2d_ab", fuse_steps=k,
                        max_abs_diff=float(max_abs_diff(skw, sk2)))
                return case_outcome()
            return body

        def vmem_ladder_case():
            # 3a3) vmem-budget ladder, measured directly: the joint
            #      tuner's outer axis (64 MiB pins 8×32 blocks at the
            #      512^3 flagship; 96 MiB admits 16×32 — the r5 open
            #      item).  Each rung is its own ledger row so the sweep
            #      is comparable across sessions.
            for mb in (64, 96, 120):
                time_chunk("vmem_ladder", fuse_steps=2,
                           metric=(f"iso3dfd r=8 {gi}^3 fp32 tpu pallas "
                                   f"chunk (vmem {mb} MiB)"),
                           vmem_budget=mb * 2 ** 20)
            return case_outcome()

        def esk_case():
            # 3a2) misaligned-radius skew (E_sk window widening,
            #      r % sublane != 0): the sublane-rounded write windows +
            #      widened regions have only ever run in interpret mode —
            #      force skew on a cube r=1 K=4 chunk and bit-compare
            #      against uniform.
            gq = min(gi, 128)
            progc = create_solution("cube", radius=1).get_soln().compile() \
                .plan(IdxTuple(x=gq, y=gq, z=gq),
                      extra_pad={"x": (32, 32), "y": (32, 32), "z": (0, 0)})
            statec = progc.alloc_state(init=seeded_init(progc))
            uni_c = time_chunk(
                "esk_ab", prog_=progc, state_=statec, npts=gq ** 3,
                metric=f"cube r=1 {gq}^3 tpu pallas chunk (esk_ab uniform)",
                fuse_steps=4, skew=False)
            skw_c = time_chunk(
                "esk_ab", prog_=progc, state_=statec, npts=gq ** 3,
                metric=f"cube r=1 {gq}^3 tpu pallas chunk (esk_ab skew)",
                fuse_steps=4, skew=True)
            if uni_c is not None and skw_c is not None:
                log("esk_ab", fuse_steps=4,
                    max_abs_diff=float(max_abs_diff(uni_c, skw_c)))
            return case_outcome()

        def trapezoid_case():
            # 3a4) trapezoid/diamond two-phase A/B: first hardware
            #      execution of the parallel-grid claim (both phases run
            #      with every grid dim "parallel" — the megacore
            #      partitioning the cost model credits).  The forced
            #      trapezoid arm must be BIT-equal to the uniform arm
            #      (same contract as the bench_suite gate: a tiling
            #      variant reorders the sweep, never the per-cell
            #      arithmetic); the speedup row feeds the TPU-scoped
            #      trap-speedup sentinel floor.  r=2 K=4 is the gate's
            #      engagement regime (small radius, deep fusion).
            from yask_tpu.ops.pallas_stencil import trapezoid_pad_need
            gq = min(gi, 128)
            pad = trapezoid_pad_need(np.float32, 2, 4)
            progt = create_solution("iso3dfd", radius=2).get_soln() \
                .compile().plan(
                    IdxTuple(x=gq, y=gq, z=gq),
                    extra_pad={"x": (pad, pad), "y": (pad, pad),
                               "z": (0, 0)})
            statet = progt.alloc_state(init=seeded_init(progt))
            uni_t = time_chunk(
                "trapezoid_ab", prog_=progt, state_=statet, npts=gq ** 3,
                metric=(f"iso3dfd r=2 {gq}^3 fp32 tpu pallas chunk "
                        f"(trapezoid_ab uniform)"),
                fuse_steps=4, skew=False)
            g_off = time_chunk.gpts
            trp = time_chunk(
                "trapezoid_ab", prog_=progt, state_=statet, npts=gq ** 3,
                metric=(f"iso3dfd r=2 {gq}^3 fp32 tpu pallas chunk "
                        f"(trapezoid_ab trap)"),
                fuse_steps=4, trapezoid=True)
            g_on = time_chunk.gpts
            if uni_t is not None and trp is not None:
                mad = float(max_abs_diff(uni_t, trp))
                log("trapezoid_ab", fuse_steps=4, max_abs_diff=mad)
                if should_bank and g_off and g_on:
                    record({"metric": (f"iso3dfd r=2 {gq}^3 {plat} "
                                       f"trap-speedup"),
                            "value": round(g_on / g_off, 4), "unit": "x",
                            "platform": plat, "uniform_gpts": g_off,
                            "trap_gpts": g_on, "max_abs_diff": mad})
                if mad != 0.0:
                    case_anomalies.append(f"trapezoid-mismatch:{mad}")
            return case_outcome()

        def bf16_case():
            # 3b) bf16 A/B: the half-traffic roofline lever.  The CPU
            #     proxy inverts (bf16 is software-emulated off-TPU) so
            #     only this hardware row can confirm the >=1.5x target;
            #     sublane-16 geometry is exercised by the same chunk
            #     builder, and the timing/recording protocol is
            #     time_chunk's single definition.
            sb16 = create_solution("iso3dfd", radius=8)
            sb16.get_soln().set_element_bytes(2)
            prog16 = sb16.get_soln().compile().plan(
                IdxTuple(x=gi, y=gi, z=gi),
                extra_pad={"x": (32, 32), "y": (32, 32), "z": (0, 0)})
            state16 = prog16.alloc_state(init=seeded_init(prog16))
            time_chunk("bf16_ab", prog_=prog16, state_=state16,
                       metric=f"iso3dfd r=8 {gi}^3 bf16 tpu pallas chunk K2",
                       fuse_steps=2)
            return case_outcome()

        def overlap_ab_case():
            # 3c) overlapped halo exchange A/B: first hardware execution
            #     of the shard_pallas core/shell split.  The serial and
            #     overlapped arms must be bit-identical (corrupt arms
            #     are withheld from the comparison — two corrupt arms
            #     matching proves nothing); the speedup row feeds the
            #     TPU-scoped sp-overlap-speedup sentinel floor, and
            #     each arm's measured overlap efficiency is banked so
            #     hardware finally answers how much collective cost
            #     the split hides.
            ndev = env.get_num_ranks()
            if ndev <= 1:
                log("overlap_ab", skipped="single device")
                return {"outcome": "skip", "reason": "single device"}
            from yask_tpu.runtime.init_utils import init_solution_vars
            from yask_tpu.utils.exceptions import YaskException
            go = min(g_bench, 256)
            steps = 8

            def mk(ovx):
                c = fac.new_solution(env, stencil="iso3dfd", radius=8)
                c.apply_command_line_options(
                    f"-g {go} -wf_steps 2 -mode shard_pallas "
                    f"-measure_halo -overlap_x {ovx} -nr_x {ndev}")
                c.prepare_solution()
                init_solution_vars(c)
                return c

            def run_arm(ovx):
                try:
                    c = mk(ovx)
                    c.run_solution(0, 3)       # warmup (compiles; a
                    #   forced-on split that cannot engage raises HERE,
                    #   at the first chunk build)
                except YaskException as e:
                    return None, None, str(e)[:200]
                t0 = time.perf_counter()
                c.run_solution(4, 4 + steps - 1)
                dt = time.perf_counter() - t0
                gpts = round(go ** 3 * steps / dt / 1e9, 3)
                sanity = check_output(
                    maybe_corrupt("session.overlap.result",
                                  interior_slice(c)))
                eff = round(c.get_stats().get_halo_overlap_eff(), 4)
                log("overlap_ab", arm=ovx, gpts=gpts, overlap_eff=eff,
                    **({"anomalies": sanity["anomalies"]}
                       if not sanity["ok"] else {}))
                if should_bank:
                    record({"metric": (f"iso3dfd r=8 {go}^3 {plat} "
                                       f"x{ndev} shard_pallas "
                                       f"(overlap {ovx})"),
                            "value": gpts, "unit": "GPts/s",
                            "platform": plat, "overlap_eff": eff},
                           sanity=sanity)
                if not sanity["ok"]:
                    case_anomalies.extend(sanity["anomalies"])
                    return None, gpts, None
                return c, gpts, None

            c_off, g_off, err = run_arm("off")
            if err:
                log("overlap_ab", error=err)
                return {"outcome": "skip", "reason": err}
            c_on, g_on, err = run_arm("on")
            if err:
                # forced "on" raised: the geometry cannot split (e.g.
                # rank domains < 2·hK at this device count) — a
                # journaled skip, not a failure
                log("overlap_ab", skipped=f"overlap infeasible: {err}")
                return {"outcome": "skip", "reason": err}
            if c_off is not None and c_on is not None:
                bad = int(c_on.compare_data(c_off, epsilon=0.0,
                                            abs_epsilon=0.0))
                log("overlap_ab", mismatches=bad)
                if should_bank and g_off and g_on:
                    record({"metric": (f"iso3dfd r=8 {go}^3 {plat} "
                                       f"x{ndev} sp-overlap-speedup"),
                            "value": round(g_on / g_off, 4),
                            "unit": "x", "platform": plat,
                            "serial_gpts": g_off, "overlap_gpts": g_on,
                            "mismatches": bad})
                if bad:
                    case_anomalies.append(f"overlap-mismatch:{bad}")
            return case_outcome()

        def comm_ab_case():
            # 3d) message-coalescing A/B: first hardware execution of
            #     the packed per-(axis,direction) ppermute schedule.
            #     ppermute only moves bytes, so the arms must be
            #     bit-identical (corrupt arms withheld — two corrupt
            #     arms matching proves nothing); each arm banks its
            #     measured collectives-per-round (traced, not modeled)
            #     so the round reduction is a hardware datum.
            ndev = env.get_num_ranks()
            if ndev <= 1:
                log("comm_ab", skipped="single device")
                return {"outcome": "skip", "reason": "single device"}
            from yask_tpu.parallel.comm_plan import comm_ledger_fields
            from yask_tpu.runtime.init_utils import init_solution_vars
            from yask_tpu.utils.exceptions import YaskException
            go = min(g_bench, 256)
            steps = 8
            ranks = ("-nr_x 2 -nr_y 2" if ndev >= 4 and ndev % 4 == 0
                     else f"-nr_x {ndev}")

            def mk(coal):
                c = fac.new_solution(env, stencil="iso3dfd", radius=8)
                c.apply_command_line_options(
                    f"-g {go} -mode shard_map -measure_halo "
                    f"-coalesce {coal} {ranks}")
                c.prepare_solution()
                init_solution_vars(c)
                return c

            def run_arm(coal):
                try:
                    c = mk(coal)
                    c.run_solution(0, 3)        # warmup + compile
                except YaskException as e:
                    return None, None, str(e)[:200]
                t0 = time.perf_counter()
                c.run_solution(4, 4 + steps - 1)
                dt = time.perf_counter() - t0
                gpts = round(go ** 3 * steps / dt / 1e9, 3)
                sanity = check_output(
                    maybe_corrupt("session.comm.result",
                                  interior_slice(c)))
                comm = comm_ledger_fields(c)
                log("comm_ab", arm=coal, gpts=gpts,
                    rounds=comm.get("comm_rounds_measured"),
                    **({"anomalies": sanity["anomalies"]}
                       if not sanity["ok"] else {}))
                if should_bank:
                    record({"metric": (f"iso3dfd r=8 {go}^3 {plat} "
                                       f"shard_map (coalesce {coal})"),
                            "value": gpts, "unit": "GPts/s",
                            "platform": plat, **comm},
                           sanity=sanity)
                if not sanity["ok"]:
                    case_anomalies.extend(sanity["anomalies"])
                    return None, gpts, None
                return c, gpts, None

            c_off, g_off, err = run_arm("off")
            if err:
                log("comm_ab", error=err)
                return {"outcome": "skip", "reason": err}
            c_on, g_on, err = run_arm("on")
            if err:
                log("comm_ab", error=err)
                return {"outcome": "skip", "reason": err}
            if c_off is not None and c_on is not None:
                bad = int(c_on.compare_data(c_off, epsilon=0.0,
                                            abs_epsilon=0.0))
                rounds_on = comm_ledger_fields(c_on).get(
                    "comm_rounds_measured")
                rounds_off = comm_ledger_fields(c_off).get(
                    "comm_rounds_measured")
                log("comm_ab", mismatches=bad, rounds_on=rounds_on,
                    rounds_off=rounds_off)
                if should_bank and g_off and g_on:
                    record({"metric": (f"iso3dfd r=8 {go}^3 {plat} "
                                       "sm-coalesce-speedup"),
                            "value": round(g_on / g_off, 4),
                            "unit": "x", "platform": plat,
                            "serial_gpts": g_off,
                            "coalesced_gpts": g_on,
                            "rounds_on": rounds_on,
                            "rounds_off": rounds_off,
                            "mismatches": bad})
                if bad:
                    case_anomalies.append(f"comm-mismatch:{bad}")
            return case_outcome()

        runner.run_case("chunk_abs", "pipeline_ab", pipeline_case)
        for k in (2, 4):
            runner.run_case("chunk_abs", f"skew_ab.K{k}", skew_case(k))
        runner.run_case("chunk_abs", "vmem_ladder", vmem_ladder_case)
        runner.run_case("chunk_abs", "esk_ab", esk_case)
        runner.run_case("chunk_abs", "trapezoid_ab", trapezoid_case)
        runner.run_case("chunk_abs", "bf16_ab", bf16_case)
        runner.run_case("chunk_abs", "overlap_ab", overlap_ab_case)
        runner.run_case("chunk_abs", "comm_ab", comm_ab_case)

    def tune_bench_stages():
        """Stages 4-5 (joint tune + tuned bench): independent context,
        crash-isolated from the chunk A/Bs.  One journaled unit — a
        resumed bench without its tune would measure the untuned
        config."""
        # 4) joint auto-tune at the bench size.  tune_max_wf_steps stays
        #    small: pads are planned for radius × the cap, so 16 would
        #    inflate every state array (784^3 for 512^3 at r=8) and make
        #    each candidate compile minutes long.
        from yask_tpu.runtime.auto_tuner import AutoTuner
        ctx = build(fac, env, "iso3dfd", "pallas", g_bench, 8, wf=2,
                    tune=True, tune_max=4)
        ctx.get_settings().auto_tune_trial_secs = 0.5
        try:
            tuner = AutoTuner(ctx)
            best_k = tuner.run_auto_tuner_now()
            s = ctx.get_settings()
            log("tune", wf_steps=best_k,
                blocks={d: s.block_sizes[d] for d in ("x", "y")},
                vmem_mb=s.vmem_budget_mb,   # ladder-chosen rung (0=auto)
                candidates=len(tuner.results))
        except Exception as e:  # noqa: BLE001
            log("tune", error=str(e)[:300])

        # 5) tuned bench
        steps = 4 if quick else 20
        ctx.run_solution(0, steps - 1)   # warm
        ctx.clear_stats()
        ctx.run_solution(steps, 2 * steps - 1)
        st = ctx.get_stats()
        rate = st.get_pts_per_sec() / 1e9
        sanity = check_output(
            maybe_corrupt("session.bench_result", interior_slice(ctx)))
        # roofline fraction via the shared perflab model (the
        # MFU-style number the performance doc's table wants per
        # VERDICT r4 item 1) — one definition across the harness,
        # bench, suite, and this session
        from yask_tpu.perflab.roofline import ctx_roofline
        roof = ctx_roofline(ctx, env, rate)
        line = dict(
            metric=f"iso3dfd r=8 {g_bench}^3 fp32 tpu pallas-tuned",
            value=round(rate, 3), unit="GPts/s", platform=plat,
            hbm_bytes_pp=roof["hbm_bytes_pp"],
            roofline_frac=roof["roofline_frac"] or 0.0,
            vs_baseline=round(rate / 500.0, 4))
        log("bench", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, roofline=roof, sanity=sanity)
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        return {}

    def compile_cache_case():
        """Cold-vs-warm AOT compile through the persistent cache on the
        real backend: build+run the flagship jit config twice with the
        in-memory memo cleared in between, so the second build can ONLY
        come from a deserialized disk entry.  The warm rebuild must
        show ZERO lowerings on the cache's trace counter — the
        serialized-executable round-trip has never run against real
        Mosaic output, only CPU executables."""
        from yask_tpu import cache as ccache
        from yask_tpu.runtime.env import DEFAULT_JAX_CACHE_DIR
        saved = os.environ.get("YT_COMPILE_CACHE")
        # a fixed path (never tempfile/pid/time): the same directory
        # JAX's own cache defaults to
        cdir = saved or DEFAULT_JAX_CACHE_DIR
        os.environ["YT_COMPILE_CACHE"] = cdir
        try:
            ccache.clear_memo()
            s0 = ccache.stats()
            c1 = build(fac, env, "iso3dfd", "jit", 64, 8, wf=2)
            c1.run_solution(0, 1)
            s1 = ccache.stats()
            cold_ms = round(c1._compile_secs * 1000.0, 1)
            cold_hit = c1._last_cache_hit
            del c1
            # memo off: the warm build must round-trip through DISK
            ccache.clear_memo()
            c2 = build(fac, env, "iso3dfd", "jit", 64, 8, wf=2)
            c2.run_solution(0, 1)
            s2 = ccache.stats()
            warm_ms = round(c2._compile_secs * 1000.0, 1)
            warm_lowerings = s2["lowerings"] - s1["lowerings"]
            sanity = check_output(
                maybe_corrupt("session.cache_result",
                              interior_slice(c2)))
            line = {"metric": f"iso3dfd r=8 64^3 {plat} "
                              "compile-cache-warm-ms",
                    "value": warm_ms, "unit": "ms", "platform": plat,
                    "cold_ms": cold_ms, "cold_hit": cold_hit or "cold",
                    "warm_hit": c2._last_cache_hit,
                    "warm_lowerings": warm_lowerings,
                    "disk_hits": s2["disk_hits"] - s1["disk_hits"],
                    "stores": s1["stores"] - s0["stores"],
                    "load_failures": (s2["load_failures"]
                                      - s0["load_failures"])}
            log("compile_cache_ab", **line,
                **({"anomalies": sanity["anomalies"]}
                   if not sanity["ok"] else {}))
            if should_bank:
                record(line, sanity=sanity)
            if not sanity["ok"]:
                return {"outcome": "anomaly",
                        "anomalies": sanity["anomalies"]}
            if warm_lowerings:
                return {"outcome": "anomaly",
                        "anomalies": [f"warm-lowerings:"
                                      f"{warm_lowerings}"]}
            return {}
        finally:
            if saved is None:
                os.environ.pop("YT_COMPILE_CACHE", None)
            else:
                os.environ["YT_COMPILE_CACHE"] = saved

    def ensemble_case():
        """Batched-vs-sequential ensemble on the real backend: the
        CPU-proxy win is compile amortization; on hardware the
        chip-saturation leg (one fused program over N small domains)
        is measured for the first time.  Per-member bit-identity is
        the gate; a corrupt arm (sanity guards) is withheld from the
        comparison and banks quarantined."""
        from yask_tpu import cache as ccache
        from yask_tpu.runtime.init_utils import init_solution_vars
        N = 4
        ge = 128 if plat == "tpu" else 32
        steps_e = 4

        def seed(c, i):
            rng = np.random.RandomState(500 + i)
            arr = (rng.rand(ge, ge, ge).astype(np.float32) - 0.5) * 0.1
            c.get_var("pressure").set_elements_in_slice(
                arr, [0, 0, 0, 0], [0, ge - 1, ge - 1, ge - 1])

        # disk cache off for the A/B: a warm entry from the
        # compile_cache_ab stage would hand the sequential arm its
        # compiles for free and invert the ratio's meaning
        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            ctxs = []
            for i in range(N):
                c = build(fac, env, "iso3dfd", "jit", ge, 8, wf=2)
                seed(c, i)
                ctxs.append(c)
            t0s = time.perf_counter()
            for c in ctxs:
                ccache.clear_memo()   # identical keys: no memo sharing
                c.run_solution(0, steps_e - 1)
            t_seq = time.perf_counter() - t0s
            finals = [{n: [np.asarray(a) for a in ring]
                       for n, ring in c._state.items()} for c in ctxs]
            del ctxs

            c = build(fac, env, "iso3dfd", "jit", ge, 8, wf=2)
            ens = c.new_ensemble(N)
            for i in range(N):
                with ens.member(i) as m:
                    if i:
                        init_solution_vars(m)
                    seed(m, i)
            ccache.clear_memo()
            t0b = time.perf_counter()
            ens.run(0, steps_e - 1)
            t_bat = time.perf_counter() - t0b
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved

        with ens.member(0):
            sanity = check_output(
                maybe_corrupt("session.ensemble_result",
                              interior_slice(c)))
        mismatches = 0
        if sanity["ok"]:   # corrupt batched arm: comparison withheld
            for i in range(N):
                with ens.member(i) as m:
                    for n, ring in finals[i].items():
                        for s, a in enumerate(ring):
                            if not np.array_equal(
                                    a, np.asarray(m._state[n][s])):
                                mismatches += 1
        line = {"metric": f"iso3dfd r=8 {ge}^3 {plat} "
                          f"ensemble{N}-speedup",
                "value": round(t_seq / max(t_bat, 1e-12), 4),
                "unit": "x", "platform": plat, "ensemble": N,
                "seq_secs": round(t_seq, 3),
                "batched_secs": round(t_bat, 3),
                "compile_ms": round(c._compile_secs * 1000.0, 1),
                "cache_hit": c._last_cache_hit or "cold",
                "batched_reason": ens.batched_reason,
                "mismatches": mismatches}
        log("ensemble_ab", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        if mismatches:
            return {"outcome": "anomaly",
                    "anomalies": [f"ensemble-mismatch:{mismatches}"]}
        return {}

    def pipeline_fusion_case():
        """Cross-solution pipeline fusion on the real backend: the
        3-stage RTM chain as ONE merged pallas program vs the
        host-chained oracle.  The bit-equality gate runs BOTH arms on
        matched temporal schedules (stepwise — the repo's K>1 chunked
        schedule is only tolerance-equal to stepwise runs, a
        pre-existing FMA-reassociation property of temporal chunking,
        not a fusion defect); the perf ratio then times the fused arm
        at K=2 chunks against the per-step chained schedule — the
        composed cross-solution + temporal fusion win this PR ships.
        A corrupt arm (sanity guards) is withheld from the comparison
        and banks quarantined."""
        from yask_tpu.ops.pipeline import (SolutionPipeline, rtm_chain,
                                           pipeline_hbm_model)
        gp = 128 if plat == "tpu" else 32
        steps_p = 4

        def mk(fuse, wf):
            stages_, bindings = rtm_chain(radius=2)
            pipe = SolutionPipeline(env, stages_, bindings)
            pipe.apply_command_line_options(
                f"-g {gp} -mode pallas -wf_steps {wf}")
            pipe.prepare(fuse=fuse)
            v = pipe.get_var("fwd", "pressure")
            rng = np.random.RandomState(11)
            arr = (rng.rand(gp, gp, gp).astype(np.float32) - 0.5) * 0.1
            for t in range(v.get_first_valid_step_index(),
                           v.get_last_valid_step_index() + 1):
                v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                        [t, gp - 1, gp - 1, gp - 1])
            return pipe

        # bit-equality gate on matched schedules: fused stepwise vs
        # the (intrinsically stepwise) chained oracle
        fused1, chained = mk(True, 1), mk(False, 1)
        for t in range(steps_p):
            fused1.run(t, t)
        chained.run(0, steps_p - 1)
        vlast = fused1.get_var("smooth", "smooth")
        sanity = check_output(
            maybe_corrupt("session.pipeline_result",
                          fused1._interior(
                              "smooth", "smooth",
                              vlast.get_last_valid_step_index())))
        mismatches = 0
        if sanity["ok"]:   # corrupt arm: comparison withheld
            mismatches = int(fused1.compare(chained))
        fused1.end()

        # perf arms: fused K=2 chunks vs the per-step chained schedule
        fused2 = mk(True, 2)
        fused2.run(0, steps_p - 1)      # warm (compile)
        t0f = time.perf_counter()
        fused2.run(steps_p, 2 * steps_p - 1)
        t_fused = time.perf_counter() - t0f
        t0c = time.perf_counter()
        chained.run(steps_p, 2 * steps_p - 1)
        t_chain = time.perf_counter() - t0c

        line = {"metric": f"rtm3 r=2 {gp}^3 {plat} "
                          "pipeline-fusion-speedup",
                "value": round(t_chain / max(t_fused, 1e-12), 4),
                "unit": "x", "platform": plat,
                "stages": len(fused2.stage_names),
                "fused": fused2.fused, "wf": 2,
                "chained_secs": round(t_chain, 3),
                "fused_secs": round(t_fused, 3),
                "hbm_bytes_model": pipeline_hbm_model(fused2),
                "mismatches": mismatches}
        log("pipeline_fusion_ab", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        fused2.end()
        chained.end()
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        if mismatches:
            return {"outcome": "anomaly",
                    "anomalies": [f"pipeline-mismatch:{mismatches}"]}
        return {}

    def push_ab_case():
        """Push-memory tile-graph fusion on the real backend: the PURE
        rtm chain (img has no self-read, so the merged image var's VMEM
        tile is consumed in-grid-step and leaves BOTH HBM paths) with
        push ON vs the same fused program with push OFF.  Bit gate:
        both fused arms stepwise (K=1, exact on Mosaic) vs the
        host-chained oracle; perf ratio then times push vs source-fused
        at K=2 chunks — the HBM-traffic halving this stage exists to
        measure on hardware (the CPU proxy realizes only part of it).
        A corrupt arm is withheld from the comparison and banks
        quarantined."""
        from yask_tpu.ops.pipeline import (SolutionPipeline, rtm_chain,
                                           pipeline_hbm_model)
        gp = 128 if plat == "tpu" else 32
        steps_p = 4

        def mk(fuse, wf, push_cli):
            stages_, bindings = rtm_chain(radius=2, accumulate=False)
            pipe = SolutionPipeline(env, stages_, bindings)
            pipe.apply_command_line_options(
                f"-g {gp} -mode pallas -wf_steps {wf} {push_cli}")
            pipe.prepare(fuse=fuse)
            v = pipe.get_var("fwd", "pressure")
            rng = np.random.RandomState(11)
            arr = (rng.rand(gp, gp, gp).astype(np.float32) - 0.5) * 0.1
            for t in range(v.get_first_valid_step_index(),
                           v.get_last_valid_step_index() + 1):
                v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                        [t, gp - 1, gp - 1, gp - 1])
            return pipe

        # bit-equality gate on matched stepwise schedules
        push1, chained = mk(True, 1, "-push on"), mk(False, 1, "-push off")
        pal = (push1.plan().get("pallas") or {})
        if not pal.get("push"):
            raise RuntimeError(
                f"push did not engage on the pure chain: "
                f"{push1.plan()['reasons']}")
        for t in range(steps_p):
            push1.run(t, t)
        chained.run(0, steps_p - 1)
        vlast = push1.get_var("smooth", "smooth")
        sanity = check_output(
            maybe_corrupt("session.push_result",
                          push1._interior(
                              "smooth", "smooth",
                              vlast.get_last_valid_step_index())))
        mismatches = 0
        if sanity["ok"]:   # corrupt arm: comparison withheld
            mismatches = int(push1.compare(chained))
        push1.end()
        chained.end()

        # perf arms: push vs source-fused, both K=2 chunks
        push2 = mk(True, 2, "-push on")
        nopush2 = mk(True, 2, "-push off")
        push2.run(0, steps_p - 1)       # warm (compile)
        nopush2.run(0, steps_p - 1)
        t0p = time.perf_counter()
        push2.run(steps_p, 2 * steps_p - 1)
        t_push = time.perf_counter() - t0p
        t0n = time.perf_counter()
        nopush2.run(steps_p, 2 * steps_p - 1)
        t_nopush = time.perf_counter() - t0n

        hbm = pipeline_hbm_model(push2,
                                 push_vars=push2.pushed_vars())
        line = {"metric": f"rtm3-pure r=2 {gp}^3 {plat} "
                          "pipeline-push-speedup",
                "value": round(t_nopush / max(t_push, 1e-12), 4),
                "unit": "x", "platform": plat,
                "push_vars": sorted(push2.pushed_vars()), "wf": 2,
                "push_secs": round(t_push, 3),
                "fused_secs": round(t_nopush, 3),
                "hbm_bytes_model": hbm,
                "mismatches": mismatches}
        log("push_ab", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        push2.end()
        nopush2.end()
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        if mismatches:
            return {"outcome": "anomaly",
                    "anomalies": [f"push-mismatch:{mismatches}"]}
        return {}

    def serve_resident_case():
        """Device-resident bulk serving on the real backend: the same
        4-session x 4-item work list through ResidentExecutor.run_queue
        (one device-lock hold, one end-of-queue sync, one extraction
        per session) vs per-request scheduler dispatch.  The resident
        arm's outputs pass the sanity guards (its maybe_corrupt site is
        serve.resident, inside run_queue); a corrupt arm is withheld
        from the bit-equality gate and banks quarantined."""
        from yask_tpu.serve.registry import SessionRegistry
        from yask_tpu.serve.scheduler import BatchScheduler
        from yask_tpu.serve.resident import run_per_request
        gs = 64 if plat == "tpu" else 16
        occupancy, nsteps = 4, 4
        rng = np.random.RandomState(17)
        arr = (rng.rand(gs, gs, gs).astype(np.float32) - 0.5) * 0.1

        reg = SessionRegistry(fac, env)
        prof = reg.get_profile("iso3dfd", 2, str(gs), mode="jit", wf=1)
        sched = BatchScheduler(reg, window_secs=0.0)

        def open_sessions():
            sids = []
            for i in range(occupancy):
                s = reg.open_session(prof)
                sids.append(s.sid)
                with sched.session_ctx(s.sid) as c:
                    v = c.get_var("pressure")
                    for t in range(v.get_first_valid_step_index(),
                                   v.get_last_valid_step_index() + 1):
                        v.set_elements_in_slice(
                            arr * (i + 1), [t, 0, 0, 0],
                            [t, gs - 1, gs - 1, gs - 1])
            return sids

        def work(sids):
            return [(sid, t, t) for t in range(nsteps)
                    for sid in sids]

        warm = open_sessions()
        sched.run_resident(work(warm)[:1])     # compile outside timing
        for sid in warm:
            reg.close_session(sid)

        sids_r = open_sessions()
        t0r = time.perf_counter()
        res = sched.run_resident(work(sids_r))
        t_resident = time.perf_counter() - t0r

        sids_p = open_sessions()
        t0q = time.perf_counter()
        base = run_per_request(sched, work(sids_p))
        t_per_req = time.perf_counter() - t0q
        sched.shutdown()

        sanity = check_output(res[sids_r[0]]["outputs"]["pressure"])
        mismatches = 0
        if sanity["ok"]:   # corrupt resident arm: comparison withheld
            for sr, sp in zip(sids_r, sids_p):
                for name, a in res[sr]["outputs"].items():
                    if not np.array_equal(a, base[sp]["outputs"][name]):
                        mismatches += 1

        line = {"metric": f"iso3dfd r=2 {gs}^3 {plat} "
                          "serve-resident-speedup",
                "value": round(t_per_req / max(t_resident, 1e-12), 4),
                "unit": "x", "platform": plat,
                "occupancy": occupancy, "items": occupancy * nsteps,
                "resident_secs": round(t_resident, 4),
                "per_request_secs": round(t_per_req, 4),
                "mismatches": mismatches}
        log("serve_resident_ab", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        if mismatches:
            return {"outcome": "anomaly",
                    "anomalies": [f"resident-mismatch:{mismatches}"]}
        return {}

    def serving_case():
        """Serving-layer batched A/B on the real backend (the serving
        stage the round-10 ROADMAP left unwritten): N tenants through
        ONE StencilServer — submit-all-then-wait-all so the batching
        window co-batches them — vs N fresh solo contexts each paying
        its own compile.  Response bit-identity to the sequential
        twins is the gate; a corrupt serve arm is withheld from the
        comparison and banks quarantined."""
        from yask_tpu import cache as ccache
        from yask_tpu.serve import StencilServer
        from yask_tpu.serve.scheduler import extract_outputs
        N = 4
        gs = 128 if plat == "tpu" else 32
        steps_s = 4

        def seed_arr(i):
            rng = np.random.RandomState(700 + i)
            return (rng.rand(1, gs, gs, gs).astype(np.float32)
                    - 0.5) * 0.1

        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            ctxs = []
            for i in range(N):
                c = build(fac, env, "iso3dfd", "jit", gs, 8, wf=2)
                c.get_var("pressure").set_elements_in_slice(
                    seed_arr(i), [0, 0, 0, 0],
                    [0, gs - 1, gs - 1, gs - 1])
                ctxs.append(c)
            t0s = time.perf_counter()
            for c in ctxs:
                ccache.clear_memo()   # N tenants, N compiles
                c.run_solution(0, steps_s - 1)
            t_seq = time.perf_counter() - t0s
            seq_outs = [extract_outputs(c) for c in ctxs]
            del ctxs

            srv = StencilServer(window_secs=0.1, max_batch=N,
                                preflight=False)
            sids = []
            for i in range(N):
                sid = srv.open_session(stencil="iso3dfd", radius=8,
                                       g=gs, mode="jit", wf=2)
                srv.init_vars(sid)
                with srv.scheduler.session_ctx(sid) as c:
                    c.get_var("pressure").set_elements_in_slice(
                        seed_arr(i), [0, 0, 0, 0],
                        [0, gs - 1, gs - 1, gs - 1])
                sids.append(sid)
            ccache.clear_memo()
            t0b = time.perf_counter()
            handles = [srv.submit_run(sid, 0, steps_s - 1)
                       for sid in sids]
            resps = [srv.wait(h, timeout=600) for h in handles]
            t_srv = time.perf_counter() - t0b
            occ = max((r.batch for r in resps), default=0)
            srv.shutdown()
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved
        bad_resps = [r.rid for r in resps if not r.ok]
        first = next((r for r in resps if r.ok), None)
        probe = (next(iter(first.outputs.values()))
                 if first and first.outputs else np.zeros(1))
        sanity = check_output(
            maybe_corrupt("session.serve_result", np.asarray(probe)))
        mismatches = 0
        if sanity["ok"]:   # corrupt serve arm: comparison withheld
            for i, (want, r) in enumerate(zip(seq_outs, resps)):
                if not r.ok:
                    continue
                for n, a in want.items():
                    if not np.array_equal(a, r.outputs[n]):
                        mismatches += 1
        line = {"metric": f"iso3dfd r=8 {gs}^3 {plat} "
                          f"serve-batch{N}-speedup",
                "value": round(t_seq / max(t_srv, 1e-12), 4),
                "unit": "x", "platform": plat, "tenants": N,
                "occupancy": occ, "seq_secs": round(t_seq, 3),
                "serve_secs": round(t_srv, 3),
                "failed": len(bad_resps), "mismatches": mismatches}
        log("serving", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        if bad_resps or mismatches:
            return {"outcome": "anomaly",
                    "anomalies": ([f"serve-failed:{len(bad_resps)}"]
                                  if bad_resps else [])
                    + ([f"serve-mismatch:{mismatches}"]
                       if mismatches else [])}
        return {}

    def serving_bucket_case():
        """Cross-profile bucketed co-batch A/B on the real backend:
        tenants on THREE different geometries ride one ladder rung as
        masked sub-domains of a shared bucket profile (one vmapped
        ensemble execution) vs per-tenant solo contexts each paying
        their own compile.  Bit-identity of every tenant to its solo
        twin is the gate — the masked step runs as a chained pair of
        select-free executables exactly so this holds on any backend;
        this stage is that claim's first trial on real Mosaic-adjacent
        XLA:TPU.  A degrade to sequential members (batched=False)
        banks as an anomaly, never as a speedup."""
        from yask_tpu import cache as ccache
        from yask_tpu.serve import StencilServer
        from yask_tpu.serve.buckets import bucket_for
        from yask_tpu.serve.scheduler import extract_outputs
        N = 4
        # three distinct geometries that share ONE ladder rung (128 /
        # 32) — mixed rungs group into separate bucket profiles and
        # the A/B would measure two half-batches instead of one
        cycle = (120, 124, 128) if plat == "tpu" else (28, 30, 32)
        gs = [cycle[i % len(cycle)] for i in range(N)]
        rung = bucket_for(max(gs))
        steps_s = 4

        def seed_arr(i, gi):
            rng = np.random.RandomState(800 + i)
            return (rng.rand(1, gi, gi, gi).astype(np.float32)
                    - 0.5) * 0.1

        saved = os.environ.pop("YT_COMPILE_CACHE", None)
        try:
            seq_outs = []
            t0s = time.perf_counter()
            for i, gi in enumerate(gs):
                c = build(fac, env, "iso3dfd", "jit", gi, 2, wf=2)
                c.get_var("pressure").set_elements_in_slice(
                    seed_arr(i, gi), [0, 0, 0, 0],
                    [0, gi - 1, gi - 1, gi - 1])
                ccache.clear_memo()   # each geometry = its own compile
                c.run_solution(0, steps_s - 1)
                seq_outs.append(extract_outputs(c))
                del c
            t_seq = time.perf_counter() - t0s

            srv = StencilServer(window_secs=0.1, max_batch=N,
                                preflight=False)
            sids = []
            for i, gi in enumerate(gs):
                sid = srv.open_session(stencil="iso3dfd", radius=2,
                                       g=gi, mode="jit", wf=2,
                                       bucket=True)
                b = srv.session_bucket(sid)
                if b["decision"] != "bucketed":
                    raise RuntimeError(
                        f"g={gi} not bucketed: {b}")
                srv.init_vars(sid)
                with srv.scheduler.session_ctx(sid) as c:
                    c.get_var("pressure").set_elements_in_slice(
                        seed_arr(i, gi), [0, 0, 0, 0],
                        [0, gi - 1, gi - 1, gi - 1])
                sids.append(sid)
            ccache.clear_memo()
            t0b = time.perf_counter()
            handles = [srv.submit_run(sid, 0, steps_s - 1)
                       for sid in sids]
            resps = [srv.wait(h, timeout=600) for h in handles]
            t_bkt = time.perf_counter() - t0b
            occ = max((r.batch for r in resps), default=0)
            degraded = sum(1 for r in resps
                           if r.ok and r.batch > 1 and not r.batched)
            srv.shutdown()
        finally:
            if saved is not None:
                os.environ["YT_COMPILE_CACHE"] = saved
        bad_resps = [r.rid for r in resps if not r.ok]
        first = next((r for r in resps if r.ok), None)
        probe = (next(iter(first.outputs.values()))
                 if first and first.outputs else np.zeros(1))
        sanity = check_output(
            maybe_corrupt("session.serve_bucket_result",
                          np.asarray(probe)))
        mismatches = 0
        if sanity["ok"]:   # corrupt serve arm: comparison withheld
            for want, r in zip(seq_outs, resps):
                if not r.ok:
                    continue
                for n, a in want.items():
                    if (a.shape != r.outputs[n].shape
                            or not np.array_equal(a, r.outputs[n])):
                        mismatches += 1
        line = {"metric": f"iso3dfd r=2 mixed-g {plat} "
                          f"serve-bucket{N}-speedup",
                "value": round(t_seq / max(t_bkt, 1e-12), 4),
                "unit": "x", "platform": plat, "tenants": N,
                "geometries": sorted(set(gs)), "rung": rung,
                "occupancy": occ, "degraded": degraded,
                "seq_secs": round(t_seq, 3),
                "bucket_secs": round(t_bkt, 3),
                "failed": len(bad_resps), "mismatches": mismatches}
        log("serving_bucket", **line,
            **({"anomalies": sanity["anomalies"]}
               if not sanity["ok"] else {}))
        if should_bank:
            record(line, sanity=sanity)
        if not sanity["ok"]:
            return {"outcome": "anomaly",
                    "anomalies": sanity["anomalies"]}
        anomalies = []
        if bad_resps:
            anomalies.append(f"serve-failed:{len(bad_resps)}")
        if mismatches:
            anomalies.append(f"bucket-mismatch:{mismatches}")
        if occ < N:
            anomalies.append(f"no-cobatch:occupancy-{occ}")
        if degraded:
            anomalies.append(f"degraded-sequential:{degraded}")
        if anomalies:
            return {"outcome": "anomaly", "anomalies": anomalies}
        return {}

    rc = 0
    try:
        if "smoke" in stages:
            runner.run_case("smoke", "", smoke)

        # 2) validation matrix ordering: on a --quick session the PERF
        #    stages run first — round 3 lost its hardware numbers
        #    while validation compiles were still grinding; the A/B
        #    cross-checks below give internal consistency and the
        #    matrix still runs afterwards if time allows.  Full
        #    sessions validate first.
        if not quick and "validate" in stages:
            run_matrix()

        if "chunk_abs" in stages:
            try:
                chunk_ab_stages()
            except Fault:
                raise
            except Exception as e:  # noqa: BLE001
                log("chunk_abs", error=str(e)[:300])
                rc = 1
        if "tune_bench" in stages:
            runner.run_case("tune_bench", "", tune_bench_stages)
            if runner.last_status == "fault":
                rc = 1

        # 6) persistent-cache + ensemble A/Bs: cheap (64³/128³ jit) and
        #    banked before the quick-session validation matrix can
        #    use up the chip time
        if "compile_cache_ab" in stages:
            runner.run_case("compile_cache_ab", "", compile_cache_case)
        if "ensemble_ab" in stages:
            runner.run_case("ensemble_ab", "", ensemble_case)
        # 6b) pipeline fusion + serving A/Bs: same cheap-and-banked
        #     policy as the cache/ensemble rows
        if "pipeline_fusion_ab" in stages:
            runner.run_case("pipeline_fusion_ab", "",
                            pipeline_fusion_case)
        if "push_ab" in stages:
            runner.run_case("push_ab", "", push_ab_case)
        if "serving" in stages:
            runner.run_case("serving", "", serving_case)
        if "serving_bucket" in stages:
            runner.run_case("serving_bucket", "", serving_bucket_case)
        if "serve_resident_ab" in stages:
            runner.run_case("serve_resident_ab", "",
                            serve_resident_case)

        # 5b) quick sessions validate AFTER the perf stages are banked
        if quick and "validate" in stages:
            run_matrix()

        # 6) Mosaic compile-time pathology check (LAST: mid-r3 saw
        #    ssg-K2 / swe2d compiles >15 min; a hang here must not cost
        #    the session).  A/B the default tile-planner vinstr cap
        #    against a tight one so the r5 `max_vinstr` knob is
        #    validated on real Mosaic.
        if "compile_time" in stages:
            def ct_case(name, radius, cap):
                def body():
                    t0 = time.perf_counter()
                    c = build(fac, env, name, "pallas", 32, radius, wf=2)
                    c.get_settings().max_tile_vinstr = cap
                    c.run_solution(0, 1)
                    log("compile_time", stencil=name, max_vinstr=cap,
                        secs=round(time.perf_counter() - t0, 1))
                return body
            for name, radius in (("ssg", 2), ("swe2d", None)):
                for cap in (300_000, 64_000):
                    runner.run_case("compile_time", f"{name}.{cap}",
                                    ct_case(name, radius, cap))
    except Fault as f:
        # breaker tripped inside run_case: the session is over — the
        # journal already holds the abort marker and every banked case
        log("session", aborted=True, fault=f.kind, error=str(f)[:200])
        return 1

    journal.record("session", "", "ok", rc=rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
