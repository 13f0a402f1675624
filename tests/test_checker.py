"""Tests for yask_tpu.checker: seeded-violation fixtures (each rule id
must fire), the round-3 VMEM-OOM regression shape, planner reason
recording, and the zero-false-error sweep over known-good configs."""

import io
import types

import pytest

from yask_tpu import yk_factory, YaskException
from yask_tpu.checker import run_checks, preflight
from yask_tpu.checker.diagnostics import CheckReport, Diagnostic
from yask_tpu.checker.races import check_races
from yask_tpu.checker.mosaic import check_mosaic
from yask_tpu.compiler.solution import yc_factory


def build_ctx(stencil="iso3dfd", radius=8, args="-g 48"):
    fac = yk_factory()
    env = fac.new_env()
    ctx = fac.new_solution(env, stencil=stencil, radius=radius or None)
    ctx.apply_command_line_options(args)
    return ctx


def rules(report):
    return set(report.rules_fired())


def error_rules(report):
    return {d.rule for d in report.errors}


# ---- diagnostics model ----------------------------------------------------

def test_diagnostic_model():
    rep = CheckReport(config={"stencil": "s"})
    rep.add("A-RULE", "error", "broken", var="u", detail={"k": 1})
    rep.add("B-RULE", "info", "fyi")
    assert not rep.ok()
    assert [d.rule for d in rep.errors] == ["A-RULE"]
    j = rep.to_json()
    assert j["schema"] == "yask_tpu.checker/1"
    assert j["summary"] == {"error": 1, "warn": 0, "info": 1}
    assert j["diagnostics"][0]["var"] == "u"
    with pytest.raises(ValueError):
        Diagnostic(rule="X", severity="fatal", message="nope")


# ---- seeded violations: one fixture per rule class ------------------------

def test_mosaic_lane_align_fires_on_unaligned_plan():
    # Plan WITHOUT Mosaic alignment: 48 + 2*8 = 64-wide lane extents are
    # not 128-multiples, so a full-extent window is an unaligned slice
    # (physical tiled layout != logical extent — the probed v5e rule).
    ctx = build_ctx(args="-g 48 -mode pallas -wf_steps 2")
    ctx._plan_geometry()   # resolves ctx._mode = "pallas"
    prog = ctx._csol.plan(ctx._opts.global_domain_sizes,
                          mosaic_align=False)
    rep = CheckReport()
    check_mosaic(rep, ctx, prog)
    fired = error_rules(rep)
    assert "MOSAIC-ALIGN-OFF" in fired
    assert "MOSAIC-LANE-ALIGN" in fired


def test_mosaic_clean_on_aligned_plan():
    ctx = build_ctx(args="-g 48 -mode pallas -wf_steps 2")
    prog = ctx._plan_geometry()
    rep = CheckReport()
    check_mosaic(rep, ctx, prog)
    assert not rep.errors


def test_vmem_over_budget_plan():
    # Explicit blocks fail fast in the planner (the auto-tuner relies on
    # the raise); the checker classifies the message as a rule id.
    ctx = build_ctx(args="-g 128 -mode pallas -wf_steps 2 -b 128 "
                         "-vmem_mb 16")
    rep = run_checks(ctx)
    assert "VMEM-TILE-OVER-BUDGET" in error_rules(rep)


def test_race_missing_dim():
    # u has no y-extent but the RHS varies along y: every y point would
    # demand a different value of the single stored slab.
    soln = yc_factory().new_solution("racy")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    u = soln.new_var("u", [t, x])
    v = soln.new_var("v", [t, x, y])
    u(t + 1, x).EQUALS(v(t, x, y + 1))
    fake = types.SimpleNamespace(_csol=None, _soln=soln, _ana=None)
    rep = CheckReport()
    check_races(rep, fake)
    fired = [d for d in rep.errors if d.rule == "RACE-MISSING-DIM"]
    assert fired and fired[0].var == "u" and fired[0].dim == "y"


def test_race_same_point():
    soln = yc_factory().new_solution("selfread")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t + 1, x + 1, y) * 0.5)
    fake = types.SimpleNamespace(_csol=None, _soln=soln, _ana=None)
    rep = CheckReport()
    check_races(rep, fake)
    assert "RACE-SAME-POINT" in error_rules(rep)


def test_race_waw_order_info():
    soln = yc_factory().new_solution("waw")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t, x, y))
    u(t + 1, x, y).EQUALS(u(t, x + 1, y))
    fake = types.SimpleNamespace(_csol=None, _soln=soln, _ana=None)
    rep = CheckReport()
    check_races(rep, fake)
    assert not rep.errors          # WAW is legal, ordered — info only
    assert "RACE-WAW-ORDER" in rules(rep)


def test_ring_depth_underflow():
    soln = yc_factory().new_solution("ring")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    u = soln.new_var("u", [t, x, y])
    # the t-1 read carries a spatial halo, so the write-back
    # optimization cannot drop its slot: the floor is a full 3-ring
    u(t + 1, x, y).EQUALS(u(t, x, y) + u(t - 1, x + 1, y))
    soln.analyze()                 # populates step_offsets_used
    assert u.min_step_alloc_size() == 3
    u.set_step_alloc_size(2)       # a live level would be evicted
    fake = types.SimpleNamespace(_csol=None, _soln=soln, _ana=None)
    rep = CheckReport()
    check_races(rep, fake)
    fired = [d for d in rep.errors if d.rule == "RING-DEPTH"]
    assert fired and fired[0].detail == {"manual": 2, "needed": 3}


def test_scratch_halo_catches_mutated_analysis():
    # The analysis fixpoint is consistent by construction -> clean;
    # shrink a computed write-halo by hand and the re-derived demand
    # must catch the drift.
    ctx = build_ctx(stencil="test_scratch_2d", radius=2, args="-g 32")
    rep = run_checks(ctx)
    assert not rep.errors
    swh = ctx._ana.scratch_write_halo
    name = next(iter(swh))
    d = next(iter(swh[name]))
    swh[name][d] = (0, 0)
    rep2 = CheckReport()
    check_races(rep2, ctx)
    assert "SCRATCH-HALO" in error_rules(rep2)


def test_dist_ghost_pad_insufficient():
    # local domain 96/8 = 12 passes the per-step halo validation (12 >=
    # 8) but cannot hold the radius*K = 32 fused ghosts: one exchange
    # cannot feed 4 fused steps.
    ctx = build_ctx(args="-g 96 -mode shard_pallas -wf_steps 4 "
                         "-nr_x 8 -nr_y 1 -nr_z 1")
    rep = run_checks(ctx)
    fired = [d for d in rep.errors if d.rule == "DIST-GHOST-PAD"]
    assert fired and fired[0].dim == "x"
    assert fired[0].detail == {"rank_domain": 12, "ghost": 32}


# ---- cache pass: compile-cache hygiene + ensemble feasibility -------------

def test_ensemble_infeasible_fires_on_sharded_mode():
    ctx = build_ctx(args="-g 64 -mode shard_map -ensemble 4 "
                         "-nr_x 2 -nr_y 1 -nr_z 1")
    rep = run_checks(ctx, passes=["cache"])
    fired = [d for d in rep.errors if d.rule == "ENSEMBLE-INFEASIBLE"]
    assert fired and fired[0].detail["ensemble"] == 4
    assert "mesh" in fired[0].message


def test_ensemble_feasible_is_info_and_off_at_one():
    ctx = build_ctx(args="-g 32 -mode jit -ensemble 4")
    rep = run_checks(ctx, passes=["cache"])
    assert rep.ok()
    infos = [d for d in rep.by_severity("info")
             if d.rule == "ENSEMBLE-INFEASIBLE"]
    assert infos and infos[0].detail["mode"] == "jit"
    # ensemble=1 (the default) emits nothing at all
    ctx = build_ctx(args="-g 32 -mode ref")
    rep = run_checks(ctx, passes=["cache"])
    assert "ENSEMBLE-INFEASIBLE" not in rules(rep)


def test_cache_stale_scan(tmp_path, monkeypatch):
    import pickle
    from yask_tpu.cache import backend_fingerprint
    from yask_tpu.cache.compile_cache import SCHEMA as CSCHEMA
    cur = backend_fingerprint("cpu")
    stale_fp = dict(cur, jax="0.0.0-other")
    (tmp_path / "aaaa.aotc").write_bytes(pickle.dumps(
        {"schema": CSCHEMA, "key": "k1", "fingerprint": stale_fp,
         "payload": b"", "in_tree": b"", "out_tree": b""}))
    (tmp_path / "bbbb.aotc").write_bytes(pickle.dumps(
        {"schema": CSCHEMA, "key": "k2", "fingerprint": cur,
         "payload": b"", "in_tree": b"", "out_tree": b""}))
    (tmp_path / "cccc.aotc").write_bytes(b"not a pickle at all")
    monkeypatch.setenv("YT_COMPILE_CACHE", str(tmp_path))
    ctx = build_ctx(args="-g 32")
    rep = run_checks(ctx, passes=["cache"])
    assert rep.ok()   # hygiene findings are warnings, never errors
    warns = [d for d in rep.warnings if d.rule == "CACHE-STALE"]
    assert len(warns) == 2
    stale = next(d for d in warns if "fingerprint" in d.message)
    assert stale.detail["stale_count"] == 1
    corrupt = next(d for d in warns if "unreadable" in d.message)
    assert corrupt.detail["unreadable_count"] == 1


def test_cache_pass_silent_without_cache_dir(monkeypatch):
    monkeypatch.delenv("YT_COMPILE_CACHE", raising=False)
    ctx = build_ctx(args="-g 32")
    rep = run_checks(ctx, passes=["cache"])
    assert rep.diagnostics == [] and rep.passes == ["cache"]


def test_ckpt_pass_silent_when_supervision_off(monkeypatch):
    # -ckpt_every 0 is a true no-op: no knobs, no diagnostics
    monkeypatch.delenv("YT_CKPT_DIR", raising=False)
    ctx = build_ctx(args="-g 32")
    rep = run_checks(ctx, passes=["ckpt"])
    assert rep.diagnostics == [] and rep.passes == ["ckpt"]


def test_ckpt_dir_cadence_and_ladder_rules(monkeypatch, tmp_path):
    monkeypatch.delenv("YT_CKPT_DIR", raising=False)
    # cadence 3 splits the K=2 fused groups; no dir resolves
    ctx = build_ctx(args="-g 48 -mode pallas -wf_steps 2 -ckpt_every 3")
    rep = run_checks(ctx, passes=["ckpt"])
    assert {"CKPT-DIR", "CKPT-CADENCE", "CKPT-LADDER"} <= rules(rep)
    assert rep.ok()   # both findings are warnings
    lad = next(d for d in rep.diagnostics if d.rule == "CKPT-LADDER")
    assert lad.detail["ladder"] == ["jit"]
    # a writable dir + K-aligned cadence: only the ladder note remains
    ctx2 = build_ctx(args="-g 48 -mode pallas -wf_steps 2 -ckpt_every 4"
                     f" -ckpt_dir {tmp_path}")
    rep2 = run_checks(ctx2, passes=["ckpt"])
    assert rules(rep2) == {"CKPT-LADDER"}


def test_ckpt_unwritable_dir_is_error(tmp_path, monkeypatch):
    # root ignores permission bits, so force the access answer instead
    # of chmod-ing a fixture dir
    import os
    ctx = build_ctx(args=f"-g 32 -ckpt_every 2 -ckpt_dir {tmp_path}")
    monkeypatch.setattr(os, "access", lambda p, m: False)
    rep = run_checks(ctx, passes=["ckpt"])
    assert "CKPT-DIR" in {d.rule for d in rep.errors}


def test_ckpt_deadline_without_cadence_warns():
    ctx = build_ctx(args="-g 32 -run_deadline 60")
    rep = run_checks(ctx, passes=["ckpt"])
    assert "CKPT-DEADLINE" in {d.rule for d in rep.warnings}


# ---- the round-3 regression shape -----------------------------------------

def test_round3_vmem_spill_oom_flagged_statically():
    """512^3 r=8 K=4 with explicit 8x8 blocks at -vmem_mb 120: tiles
    pass the 120 MiB planning budget but the live-value model (the
    capability table's: tiles + 8.7 result tiles at single-stage K=4;
    the chip said 'Used 149.99M of 128.00M', PR 21) exceeds the 128
    MiB scoped Mosaic limit — the register-spill OOM that crashed the
    round-3 joint tune.  Must be an error, found WITHOUT allocating the
    512^3 state.  (Until PR 51 the case was K=2 at 64x64: the strip
    kernel declares 63.4 MiB for that and Mosaic holds 0.03 more, so
    the re-read row passes it.)"""
    ctx = build_ctx(args="-g 512 -mode pallas -wf_steps 4 -b 8 "
                         "-vmem_mb 120")
    rep = run_checks(ctx)
    spills = [d for d in rep.errors if d.rule == "VMEM-SPILL"]
    assert spills, rep.render(verbose=True)
    det = spills[0].detail
    assert det["tile_bytes"] <= 120 * 2 ** 20      # inside the budget
    assert det["live_model_bytes"] > det["vmem_limit"]
    from yask_tpu.backend import get_capability
    assert det["live_model_bytes"] == get_capability().vmem_need_bytes(
        4, 1, det["tile_bytes"], det["result_bytes"])
    assert ctx._state is None                      # nothing allocated
    assert not ctx.is_prepared()


def test_default_budget_is_spill_free():
    # The TPU default budget of the class (the capability table's row
    # for single-stage K=2) and the build's room test keep the scoped
    # need under the limit by construction; the flagship at 512^3 must
    # check clean.
    ctx = build_ctx(args="-g 512 -mode pallas -wf_steps 2")
    rep = run_checks(ctx)
    assert rep.ok(), rep.render(verbose=True)
    ok = [d for d in rep.diagnostics if d.rule == "VMEM-OK"]
    assert ok and ok[0].detail["live_model_bytes"] \
        <= ok[0].detail["vmem_limit"]


def test_vmem_limit_single_definition():
    # The checker imports the SAME function CompilerParams uses.
    from yask_tpu.checker.vmem import vmem_limit_bytes as a
    from yask_tpu.ops.pallas_stencil import vmem_limit_bytes as b
    assert a is b
    assert b(64 * 2 ** 20) == 128 * 2 ** 20
    assert b(120 * 2 ** 20) == 128 * 2 ** 20       # capped
    assert b(16 * 2 ** 20) == 32 * 2 ** 20


# ---- planner reason recording (the no-silent-fallback satellite) ----------

def test_reasons_one_per_ladder_step():
    """16^3 r=8 K=2: skew engages in the stream dim, the carry floor
    fails -> uniform shrink, and the step records a structured reason;
    forced, the same floor raises instead."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = build_ctx(args="-g 16 -mode pallas -wf_steps 2")
    prog = ctx._plan_geometry()
    reasons = []
    build_pallas_chunk(prog, fuse_steps=2, vmem_budget=ctx.vmem_budget(),
                       plan_only=True, reasons=reasons)
    codes = [r["code"] for r in reasons]
    falls = [r for r in reasons if r["code"] == "skew_fallback"]
    assert [(f["from_dims"], f["to"]) for f in falls] == [
        (["y"], "uniform shrink")]
    assert all(f["cause"] for f in falls)
    assert codes.index("skew_engaged") < codes.index("skew_fallback")
    assert "skew_disabled" in codes                # ladder bottom
    assert "pipe_in_off" in codes and "pipe_out_off" in codes
    with pytest.raises(YaskException, match="skewed wavefront needs block"):
        build_pallas_chunk(prog, fuse_steps=2, skew=True, plan_only=True,
                           vmem_budget=ctx.vmem_budget())


def test_reasons_in_built_chunk_tiling():
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = build_ctx(args="-g 48 -mode pallas -wf_steps 2")
    prog = ctx._plan_geometry()
    chunk, _tb = build_pallas_chunk(prog, fuse_steps=2, interpret=True,
                                    vmem_budget=ctx.vmem_budget())
    codes = [r["code"] for r in chunk.tiling["reasons"]]
    assert "skew_engaged" in codes
    assert "pipe_in_on" in codes and "pipe_out_on" in codes


def test_plan_only_matches_built_tiling():
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = build_ctx(args="-g 48 -mode pallas -wf_steps 2")
    prog = ctx._plan_geometry()
    plan = build_pallas_chunk(prog, fuse_steps=2,
                              vmem_budget=ctx.vmem_budget(),
                              plan_only=True)
    chunk, _tb = build_pallas_chunk(prog, fuse_steps=2, interpret=True,
                                    vmem_budget=ctx.vmem_budget())
    for k in ("block", "fuse_steps", "skew", "skew_dims"):
        assert plan[k] == chunk.tiling[k], k


# ---- run_checks / preflight plumbing --------------------------------------

def test_unknown_pass_rejected():
    from yask_tpu.utils.exceptions import YaskException
    ctx = build_ctx(args="-g 32")
    with pytest.raises(YaskException):
        run_checks(ctx, passes=["mosaic", "nope"])


def test_preflight_honors_setting_and_returns_status():
    ctx = build_ctx(args="-g 512 -mode pallas -wf_steps 4 -b 8 "
                         "-vmem_mb 120")
    buf = io.StringIO()
    assert preflight(ctx, out=buf) is False
    assert "VMEM-SPILL" in buf.getvalue()
    ctx._opts.preflight = False
    assert preflight(ctx, out=io.StringIO()) is True


def test_preflight_never_raises_on_internal_failure():
    broken = types.SimpleNamespace(_opts=types.SimpleNamespace(
        preflight=True))
    buf = io.StringIO()
    assert preflight(broken, out=buf) is True
    assert "internal failure" in buf.getvalue()


# ---- zero false errors on known-good configs ------------------------------

QUICK_GOOD = ["iso3dfd", "ssg", "tti", "wave2d", "test_misc_2d",
              "test_scratch_3d", "test_stages_2d", "test_reverse_2d"]


@pytest.mark.parametrize("name", QUICK_GOOD)
def test_no_false_errors_quick(name):
    from yask_tpu.ops.pallas_stencil import pallas_applicable
    for mode in ("jit", "pallas"):
        ctx = build_ctx(stencil=name, radius=0, args="-g 32")
        if mode == "pallas":
            ok, _ = pallas_applicable(ctx._csol)
            if not ok:
                continue
            ctx.get_settings().wf_steps = 2
        ctx.get_settings().mode = mode
        rep = run_checks(ctx)
        assert rep.ok(), f"{name}/{mode}: " + rep.render(verbose=True)


@pytest.mark.slow
def test_no_false_errors_all_stencils():
    """Every registered stencil x (jit, pallas-when-applicable) checks
    clean — the CLI sweep the Makefile `check` target also runs."""
    from yask_tpu.checker.__main__ import run_checker
    buf = io.StringIO()
    assert run_checker(["-all_stencils"], out=buf) == 0, buf.getvalue()


# ---- CLI ------------------------------------------------------------------

def test_cli_json_and_exit_codes():
    from yask_tpu.checker.__main__ import run_checker
    buf = io.StringIO()
    rc = run_checker(["-stencil", "iso3dfd", "-radius", "8", "-json",
                      "-g", "48", "-mode", "pallas", "-wf_steps", "2"],
                     out=buf)
    assert rc == 0
    import json
    j = json.loads(buf.getvalue())
    assert j["schema"] == "yask_tpu.checker/1"
    assert j["summary"]["error"] == 0

    buf = io.StringIO()
    rc = run_checker(["-stencil", "iso3dfd", "-radius", "8", "-g", "512",
                      "-mode", "pallas", "-wf_steps", "4", "-b", "8",
                      "-vmem_mb", "120"], out=buf)
    assert rc == 1 and "VMEM-SPILL" in buf.getvalue()

    assert run_checker([], out=io.StringIO()) == 2   # no stencil
