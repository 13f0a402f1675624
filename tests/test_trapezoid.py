"""Trapezoidal/diamond two-phase Pallas tiling tests.

The trapezoid mode decomposes each fused K-group along the tiled lead
dims into carry-free upright trapezoids (per-level write windows shrink
by r per side) running on a PARALLEL Pallas grid, plus an
inverted-trapezoid (diamond) fill pass that recomputes the inter-tile
gap bands from level-0 state — the TPU-native counterpart of the
reference's two-phase trapezoid blocking (``setup.cpp:863``,
``context.cpp:838``), trading the skew mode's sequential carry for
core-parallel tiles.  Every case must agree exactly with the uniform
tiling on the same state and with the XLA oracle end to end; all
tiling decisions must come off the TilePlan with recorded reasons.
"""

import numpy as np
import pytest

from yask_tpu import yk_factory, YaskException


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


def make(env, mode, name, r=8, g=48, wf=1, block=None, trap=True):
    ctx = yk_factory().new_solution(env, stencil=name, radius=r)
    ctx.apply_command_line_options(f"-g {g}")
    ctx.get_settings().mode = mode
    ctx.get_settings().wf_steps = wf
    ctx.get_settings().trapezoid_tiling = trap
    if block:
        for d, b in block.items():
            ctx.set_block_size(d, b)
    ctx.prepare_solution()
    from yask_tpu.runtime.init_utils import init_solution_vars
    init_solution_vars(ctx)
    return ctx


def _chunk_vs_uniform(env, name, r, g, wf, blk, trap_arg=True):
    """Forced trapezoid chunk must agree with the uniform tiling on the
    same state, on a parallel grid."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", name, r=r, g=g, wf=wf,
               block=dict(zip(("x", "y"), blk)))
    prog = ctx._program
    tp, _ = build_pallas_chunk(prog, fuse_steps=wf, block=blk,
                               interpret=True, trapezoid=trap_arg)
    assert tp.tiling["trapezoid"] is True
    assert tp.tiling["skew"] is False     # parallel grid: no carries
    # the emitted grid spec must be parallel in every dim, never
    # "arbitrary" (sequential) — the whole point of the two-phase split
    assert all(s == "parallel" for s in tp.tiling["dimension_semantics"])
    un, _ = build_pallas_chunk(prog, fuse_steps=wf, block=blk,
                               interpret=True, skew=False)
    st = {k: list(v) for k, v in ctx._state.items()}
    a = tp(st, 0)
    b = un(st, 0)
    for n in a:
        for x, y in zip(a[n], b[n]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-5, atol=1e-6)
    return tp.tiling


def test_trapezoid_forced_matches_uniform_r8(env):
    til = _chunk_vs_uniform(env, "iso3dfd", 8, 48, 2, (24, 24))
    assert sorted(til["trap_dims"]) == ["x", "y"]
    assert til["diamond"] and all(d["nbounds"] >= 2
                                  for d in til["diamond"])


def test_trapezoid_forced_matches_uniform_r1_k4(env):
    """Misaligned radius (r=1, sublane rounding active) at K=4."""
    til = _chunk_vs_uniform(env, "cube", 1, 32, 4, (16, 32))
    assert sorted(til["trap_dims"]) == ["x", "y"]


def test_trapezoid_forced_matches_uniform_r2_k3(env):
    _chunk_vs_uniform(env, "iso3dfd", 2, 32, 3, (16, 32))


def test_trapezoid_1d_dim_list(env):
    """trapezoid=["x"]: only the named dim decomposes."""
    til = _chunk_vs_uniform(env, "iso3dfd", 8, 48, 2, (24, 24),
                            trap_arg=["x"])
    assert til["trap_dims"] == ["x"]
    assert len(til["diamond"]) == 1 and til["diamond"][0]["dim"] == "x"


@pytest.mark.slow   # 30 s: tier-1 headroom (ROADMAP D2); the single-stage
#                     trapezoid cases above stay in tier-1
def test_trapezoid_multi_stage_and_scratch(env):
    """ssg's staged chain (per-step halo 2r) and tti's scratch-var
    chain through the diamond fill pass."""
    _chunk_vs_uniform(env, "ssg", 4, 48, 2, (24, 48))
    _chunk_vs_uniform(env, "tti", 2, 48, 2, (24, 48))


def test_trapezoid_e2e_matches_jit(env):
    """End-to-end forced trapezoid vs the XLA oracle, with a remainder
    step group (steps % K != 0)."""
    ref = make(env, "jit", "iso3dfd", r=8, g=48, trap=False)
    ref.run_solution(0, 4)
    p = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
             block={"x": 24, "y": 24})
    p.run_solution(0, 4)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_trapezoid_e2e_sponge_conditions(env):
    """IF_DOMAIN sponge conditions under the band recompute (global-
    coordinate masks must hold in the diamond pass too)."""
    ref = make(env, "jit", "iso3dfd_sponge", r=8, g=48, trap=False)
    ref.run_solution(0, 3)
    p = make(env, "pallas", "iso3dfd_sponge", r=8, g=48, wf=2,
             block={"x": 24, "y": 24})
    p.run_solution(0, 3)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_trapezoid_e2e_2d_solution(env):
    """2-D solution: a single lead dim decomposes."""
    ref = make(env, "jit", "wave2d", r=8, g=64, trap=False)
    ref.run_solution(0, 5)
    p = make(env, "pallas", "wave2d", r=8, g=64, wf=2,
             block={"x": 32})
    p.run_solution(0, 5)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_trapezoid_auto_engages_and_matches_jit(env):
    """cube r=1 K=4 at g=48: the per-variant-block profit gate engages
    trapezoid on its own (trapezoid=None), the run matches the oracle,
    and the recorded tiling is the parallel two-phase plan."""
    ref = make(env, "jit", "cube", r=1, g=48, trap=False)
    ref.run_solution(0, 5)
    p = make(env, "pallas", "cube", r=1, g=48, wf=4)
    p.run_solution(0, 5)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    til = p.get_stats().get_tiling()
    assert til["trapezoid"] is True
    assert all(s == "parallel" for s in til["dimension_semantics"])
    codes = [r["code"] for r in til["reasons"]]
    assert "trapezoid_engaged" in codes
    det = next(r["detail"] for r in til["reasons"]
               if r["code"] == "trapezoid_engaged")
    # the profit-gate numbers are in the record
    assert "vs uniform" in det and "skew" in det


def test_trapezoid_full_span_block_bit_equals_uniform(env):
    """iso3dfd r=2 K=4 at g=48: the profit gate engages with block ==
    full span (degenerate single tile — nbounds=2, only the two domain
    edges bound the diamond passes, and the sublane floor zeroes every
    y write-shrink).  The trapezoid schedule must stay BIT-equal to the
    uniform pallas schedule through the runtime path — jit is the wrong
    oracle at this size (XLA reassociation drifts ~1e-3 in a few
    steps), which is exactly why this test compares pallas schedules,
    not modes.  (At g=24, where this ran until PR 42, the uniform
    variant's blocks stopped at 8 because 16 does not divide 24; they
    now reach the whole span as well, and one uniform tile beats one
    trapezoid tile and its fill passes: 2.29 against 2.65.)"""
    p = make(env, "pallas", "iso3dfd", r=2, g=48, wf=4)
    p.run_solution(0, 3)
    til = p.get_stats().get_tiling()
    assert til["trapezoid"] is True
    assert til["block"] == {"x": 48, "y": 48}   # degenerate: full span
    u = make(env, "pallas", "iso3dfd", r=2, g=48, wf=4, trap=False)
    u.run_solution(0, 3)
    assert p.compare_data(u, epsilon=0.0, abs_epsilon=0.0) == 0


def test_trapezoid_gate_rejects_where_skew_wins(env):
    """iso3dfd r=8 K=2: phase-1 compute equals uniform at K=2, so the
    diamond overhead loses the gate — skew keeps the flagship, the
    rejection (with its cost numbers) is recorded, and the build is the
    skew one."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
               block={"x": 24, "y": 24})
    plan = build_pallas_chunk(ctx._program, fuse_steps=2, block=(24, 24),
                              interpret=True, trapezoid=None,
                              plan_only=True)
    assert plan["trapezoid"] is False and plan["trap_dims"] == []
    assert plan["skew"] is True
    rej = [r for r in plan["reasons"]
           if r["code"] == "trapezoid_gate_rejected"]
    assert rej and all("vs uniform" in r["detail"] for r in rej)


def test_trapezoid_fallback_without_pads(env):
    """Auto trapezoid on a program prepared WITHOUT the diamond-band
    pads must fall back cleanly (reason recorded); forcing raises."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "cube", r=1, g=48, wf=4, trap=False)
    ch, _ = build_pallas_chunk(ctx._program, fuse_steps=4,
                               interpret=True, trapezoid=None)
    assert ch.tiling["trapezoid"] is False
    with pytest.raises(YaskException):
        build_pallas_chunk(ctx._program, fuse_steps=4, interpret=True,
                           trapezoid=True)


def test_trapezoid_band_floor_fallback(env):
    """A block below the diamond-band floor (2·cl(K)+unit) falls back
    in auto mode with the cause recorded, and raises when forced."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "cube", r=1, g=48, wf=4)
    # y floor = 2·ceil(3, 8) + 8 = 24 > 16
    blk = (16, 16)
    with pytest.raises(YaskException, match="band floor"):
        build_pallas_chunk(ctx._program, fuse_steps=4, block=blk,
                           interpret=True, trapezoid=True)
    ch, _ = build_pallas_chunk(ctx._program, fuse_steps=4, block=blk,
                               interpret=True, trapezoid=None)
    assert ch.tiling["trapezoid"] is False


def test_trapezoid_cli_knob(env):
    """-trapezoid parses into settings.trapezoid_tiling."""
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options("-g 24 -trapezoid")
    assert ctx.get_settings().trapezoid_tiling is True
    ctx.apply_command_line_options("-no-trapezoid")
    assert ctx.get_settings().trapezoid_tiling is False


# ---- TilePlan unit coverage ---------------------------------------------


def test_tileplan_margins_and_windows(env):
    """THE dataflow-plan object: margins, write windows, diamond
    geometry and block floors for each per-dim mode."""
    from yask_tpu.ops.tile_planner import TilePlan
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2)
    prog = ctx._program
    lead = prog.ana.domain_dims[:-1]

    tp = TilePlan(prog, 2, trap_dims=list(lead))
    mL, mR = tp.margins()
    for d in lead:
        # upright trapezoids read one step radius per side
        assert mL[d] == mR[d] == 8
        assert tp.halo(d) == 16                       # radius × K
        assert tp.write_shrink(d, 1) == 0
        assert tp.write_shrink(d, 2) == 8             # (lvl−1)·r
        dia = tp.diamond(d)
        assert dia["half"] == tp.cl(d, 2) == 8
        assert dia["band"] == 16 and dia["margin"] == 16
    # band floor: 2·cl(K) + unit (sublane unit on the sublane axis)
    assert tp.min_block()[lead[-1]] == 2 * 8 + 8
    assert tp.min_block()[lead[0]] == 2 * 8 + 1
    assert tp.margin_override() == {d: 16 for d in lead}

    un = TilePlan(prog, 2)
    umL, umR = un.margins()
    assert umL == umR == {d: 16 for d in lead}        # uniform 2·r·K/2

    sk = TilePlan(prog, 2, skew_dims=[lead[-1]], e_sk={lead[-1]: 0})
    smL, smR = sk.margins()
    assert smL[lead[-1]] == 16 and smR[lead[-1]] == 8  # K·r left, r+E right


def test_tileplan_sublane_rounding(env):
    """Misaligned radius: cl ceils to the sublane tile on the sublane
    axis (write-back DMA alignment), write_shrink floors — exact on
    non-sublane dims."""
    from yask_tpu.ops.tile_planner import TilePlan
    ctx = make(env, "pallas", "cube", r=1, g=48, wf=4)
    prog = ctx._program
    lead = prog.ana.domain_dims[:-1]
    tp = TilePlan(prog, 4, trap_dims=list(lead))
    outer, subl = lead[0], lead[-1]
    assert tp.cl(outer, 4) == 3                       # exact (lvl−1)·r
    assert tp.cl(subl, 4) == 8                        # ceil(3, 8)
    assert tp.write_shrink(outer, 4) == 3
    assert tp.write_shrink(subl, 4) == 0              # floor(3, 8)


def test_tileplan_dataflow_nesting(env):
    """dataflow(): each level's read window covers the next level's
    write window expanded by the step radius — the correctness
    invariant the whole phase-1 kernel hangs on."""
    from yask_tpu.ops.tile_planner import TilePlan
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2)
    prog = ctx._program
    lead = prog.ana.domain_dims[:-1]
    tp = TilePlan(prog, 2, trap_dims=list(lead))
    steps = tp.dataflow({d: 24 for d in lead})
    assert len(steps) == 2
    for lvl0, lvl1 in zip(steps, steps[1:]):
        for d in lead:
            wlo, whi = lvl1["write"][d]
            rlo, rhi = lvl0["write"][d]
            # level l+1 writes only cells level l wrote r-coverage for
            assert rlo <= wlo - 8 + 8 and whi <= rhi + 8


def test_tileplan_volumes_model(env):
    """volumes(): trapezoid fetch is strictly below uniform fetch (2r
    vs 2rK margins) and the diamond overhead is accounted."""
    from yask_tpu.ops.tile_planner import TilePlan
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2)
    prog = ctx._program
    lead = prog.ana.domain_dims[:-1]
    blk = {d: 24 for d in lead}
    u_use, u_comp, u_fetch = TilePlan(prog, 2).volumes(blk)
    t_use, t_comp, t_fetch = TilePlan(prog, 2,
                                      trap_dims=list(lead)).volumes(blk)
    assert u_use == t_use
    assert t_comp > u_comp            # diamond recompute overhead
    # trapezoid per-lead fetch (B+2r)² < uniform (B+2rK)², but the
    # diamond bands add their own fetch; at this size the sum stays
    # below uniform's margin fetch plus half the band fetch
    assert t_fetch != u_fetch


# ---- checker integration -------------------------------------------------


def test_checker_trapezoid_rules(env):
    """The vmem pass proves the two-phase residency and write-window
    alignment statically when the plan engages trapezoid."""
    from yask_tpu.checker import run_checks
    ctx = make(env, "pallas", "cube", r=1, g=48, wf=4)
    rep = run_checks(ctx, passes=["vmem", "explain"])
    rules = {d.rule for d in rep.diagnostics}
    assert "TRAPEZOID-RESIDENCY-OK" in rules
    assert "TRAPEZOID-WRITE-ALIGN-OK" in rules
    assert "TRAPEZOID-WRITE-ALIGN" not in rules
    assert "TRAPEZOID-VMEM-SPILL" not in rules
    # the explain pass republishes the gate decision
    assert "EXPLAIN-TRAPEZOID-ENGAGED" in rules


def test_checker_trapezoid_infeasible_classified(env):
    """A forced-trapezoid plan failure classifies as
    TRAPEZOID-INFEASIBLE (not the generic PLAN-FAILED)."""
    from yask_tpu.checker.vmem import _classify_plan_error
    assert _classify_plan_error(
        "trapezoid tiling infeasible: block 16 < band floor 33 in 'x'"
    ) == "TRAPEZOID-INFEASIBLE"
    assert _classify_plan_error(
        "trapezoid tiling infeasible (fill pass): pallas diamond band "
        "in dim 'x' exceeds the planned pads"
    ) == "TRAPEZOID-INFEASIBLE"
