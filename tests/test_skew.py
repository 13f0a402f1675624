"""Skewed-wavefront (streaming) Pallas tiling tests.

The skew mode slides each fused sub-step's compute region left by the
step radius along the innermost (sequential) grid dim, patching the
inter-tile boundary strips from a parity-double-buffered VMEM carry —
zero redundant compute in that dim.  It is the TPU-native counterpart
of the reference's two-phase trapezoid blocking
(``/root/reference/src/kernel/lib/setup.cpp:863``,
``context.cpp:838``): the reference colors phases to create *thread*
parallelism, while a sequential Pallas grid only needs the dependency
carry.  Every case here must agree exactly with the XLA path, with
blocks small enough that several stream tiles (and therefore the
carry) are exercised."""

import numpy as np
import pytest

import strip_cases

from yask_tpu import yk_factory, YaskException


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


def make(env, mode, name, r=8, g=48, wf=1, block=None, skew=None,
        steps_init=None):
    ctx = yk_factory().new_solution(env, stencil=name, radius=r)
    ctx.apply_command_line_options(f"-g {g}")
    ctx.get_settings().mode = mode
    ctx.get_settings().wf_steps = wf
    if skew is not None:
        ctx.get_settings().skew_wavefront = skew
    if block:
        for d, b in block.items():
            ctx.set_block_size(d, b)
    ctx.prepare_solution()
    from yask_tpu.runtime.init_utils import init_solution_vars
    init_solution_vars(ctx)
    return ctx


def _compare(env, name, r=8, g=48, wf=2, block=None, steps=6,
             field_epsilon=0.0):
    ref = make(env, "jit", name, r=r, g=g)
    ref.run_solution(0, steps - 1)
    p = make(env, "pallas", name, r=r, g=g, wf=wf, block=block)
    p.run_solution(0, steps - 1)
    return p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4,
                          field_epsilon=field_epsilon)


def test_skew_engages_for_aligned_radius(env):
    """Direct chunk build with skew=True must not raise (eligibility)
    and must agree with the uniform tiling."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
               block={"x": 24, "y": 24})
    prog = ctx._program
    sk, _ = build_pallas_chunk(prog, fuse_steps=2, block=(24, 24),
                               interpret=True, skew=True)
    un, _ = build_pallas_chunk(prog, fuse_steps=2, block=(24, 24),
                               interpret=True, skew=False)
    st = {k: list(v) for k, v in ctx._state.items()}
    a = sk(st, 0)
    b = un(st, 0)
    for n in a:
        for x, y in zip(a[n], b[n]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-5, atol=1e-6)


def test_skew_engages_for_unaligned_radius(env):
    """r=2 (not a sublane multiple): the write-window shift rounds down
    to the sublane tile with a widened window; E_sk extra computed
    width keeps the overlap valid (round-4 eligibility lift).  The
    chunk must ENGAGE skew (not silently fall back) and agree with the
    uniform tiling."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "iso3dfd", r=2, g=32, wf=2,
               block={"x": 16, "y": 16})
    prog = ctx._program
    sk, _ = build_pallas_chunk(prog, fuse_steps=2, block=(16, 16),
                               interpret=True, skew=True)
    assert sk.tiling["skew"] is True
    un, _ = build_pallas_chunk(prog, fuse_steps=2, block=(16, 16),
                               interpret=True, skew=False)
    st = {k: list(v) for k, v in ctx._state.items()}
    a = sk(st, 0)
    b = un(st, 0)
    for n in a:
        for x, y in zip(a[n], b[n]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-5, atol=1e-6)


# The two truly-misaligned cases (shift (lvl-1)·r % 8 != 0) take the
# widened-window path; its different reduction grouping leaves a
# handful of field-ulp differences vs the jit oracle (triaged r21:
# 3/4 isolated points, |Δ| at the f32 ulp of the field scale — not a
# dropped band; a carry-geometry bug shows O(field) banded errors and
# fails field_epsilon=1e-4 by thousands of points).  r=1 (shift rounds
# to 0) is exact and stays a hard zero-tolerance assert.
@pytest.mark.parametrize("r,wf,block,fe", [
    (1, 2, {"x": 16, "y": 16}, 0.0),  # shift 1: rounds to 0, exact
    (2, 3, {"x": 16, "y": 16}, 1e-4),  # shifts 2,4: both misaligned
    (4, 2, {"x": 16, "y": 16}, 1e-4),  # shift 4: half a sublane tile
])
def test_skew_misaligned_radius_matches_jit(env, r, wf, block, fe):
    assert _compare(env, "iso3dfd", r=r, g=32, wf=wf, block=block,
                    steps=wf * 2, field_epsilon=fe) == 0


def test_skew_misaligned_radius_cube_r1(env):
    """27-point radius-1 stencil (every shift misaligned, ring 1)."""
    assert _compare(env, "cube", r=1, g=32, wf=4,
                    block={"x": 16, "y": 16}, steps=8) == 0


@pytest.mark.parametrize("wf,block", [
    (2, {"x": 24, "y": 24}),   # 2 stream tiles per row: carry active
    (3, {"x": 48, "y": 32}),
    (4, {"x": 24, "y": 32}),   # 4 sub-steps, deeper carry levels
])
def test_skew_iso3dfd_two_slot_ring(env, wf, block):
    assert _compare(env, "iso3dfd", wf=wf, block=block) == 0


def test_skew_sponge_conditions(env):
    """IF_DOMAIN sponge conditions under skewed regions."""
    assert _compare(env, "iso3dfd_sponge", wf=2,
                    block={"x": 24, "y": 24}) == 0


def test_skew_multi_stage(env):
    """ssg's staged chain: stage margins consume within each skewed
    sub-step; cross-tile strips must still line up.  The fused chain
    reassociates the staggered sums (see test_pallas_multi_stage_ssg),
    so a few field-ulp points ride field_epsilon; strip misalignment
    would fail it by orders of magnitude."""
    assert _compare(env, "ssg", r=8, g=32, wf=2,
                    block={"x": 16, "y": 16}, steps=4,
                    field_epsilon=1e-4) == 0


def test_skew_same_point_carry(env):
    """Regression (r21, awp's anelastic mem_* vars): a var written AND
    read only at zero spatial offset never appears in
    stage_read_widths — but a later sub-step still consumes the slid
    strip from the neighboring tile, so it MUST ride the skew carry
    (analysis.read_var_names).  Pre-fix this corrupted a radius-wide
    band.  Shown at an aligned stream radius (E_sk = 0): a misaligned
    one (awp's own reach of 4) hides it, the neighbor's widened write
    window covering the strip."""
    from yask_tpu.compiler.solution import yc_factory
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    from yask_tpu.runtime.init_utils import init_solution_vars

    soln = yc_factory().new_solution("same_point_memory")
    t = soln.new_step_index("t")
    x, y, z = (soln.new_domain_index(d) for d in "xyz")
    a = soln.new_var("A", [t, x, y, z])
    m = soln.new_var("M", [t, x, y, z])
    rhs = a(t, x, y, z) * 0.5
    for i in range(1, 9):
        rhs = rhs + (a(t, x, y - i, z) + a(t, x, y + i, z)) * (0.03 / i)
    a(t + 1, x, y, z).EQUALS(rhs)
    m(t + 1, x, y, z).EQUALS(m(t, x, y, z) * 0.9 + a(t + 1, x, y, z))
    ctx = yk_factory().new_solution(env, soln)
    ctx.apply_command_line_options("-g 48")
    ctx.get_settings().mode = "pallas"
    ctx.get_settings().wf_steps = 2
    ctx.prepare_solution()
    init_solution_vars(ctx)
    sk, _ = build_pallas_chunk(ctx._program, fuse_steps=2,
                               block=(24, 24), interpret=True)
    assert sk.tiling["skew_dims"] == ["y"] and sk.tiling["grid"] == [2, 3]
    un, _ = build_pallas_chunk(ctx._program, fuse_steps=2,
                               block=(24, 24), interpret=True, skew=False)
    st_sk = st_un = {k: list(v) for k, v in ctx._state.items()}
    for t0 in (0, 2):
        st_sk, st_un = sk(st_sk, t0), un(st_un, t0)
    for n in ("A", "M"):
        for got, want in zip(st_sk[n], st_un[n]):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=1e-6)


def test_skew_scratch_chain(env):
    """tti evaluates scratch vars over write-halo-expanded skewed
    regions."""
    assert _compare(env, "tti", r=8, g=32, wf=2,
                    block={"x": 16, "y": 16}, steps=4) == 0


def test_skew_2d_stream_only_dim(env):
    """2-D solution: the single lead dim is the stream dim."""
    assert _compare(env, "wave2d", r=8, g=64, wf=2,
                    block={"x": 32}, steps=6) == 0


class _Reverse3dR8:
    """Ad-hoc reverse-time radius-8 stencil (writes t−1 from t)."""

    def build(self):
        from yask_tpu.compiler.solution_base import yc_solution_base

        class R(yc_solution_base):
            def __init__(self):
                super().__init__("rev3d_r8")

            def define(self):
                t = self.new_step_index("t")
                x = self.new_domain_index("x")
                y = self.new_domain_index("y")
                z = self.new_domain_index("z")
                u = self.new_var("A", [t, x, y, z])
                e = u(t, x, y, z)
                for o in (-8, 8):
                    e = e + u(t, x + o, y, z) + u(t, x, y + o, z) \
                        + u(t, x, y, z + o)
                u(t - 1, x, y, z).EQUALS(e / 7.0)
        return R()


def test_skew_reverse_time(env):
    def mk(mode, wf=1, block=None):
        ctx = yk_factory().new_solution(env, _Reverse3dR8().build())
        ctx.apply_command_line_options("-g 48")
        ctx.get_settings().mode = mode
        ctx.get_settings().wf_steps = wf
        if block:
            for d, b in block.items():
                ctx.set_block_size(d, b)
        ctx.prepare_solution()
        from yask_tpu.runtime.init_utils import init_solution_vars
        init_solution_vars(ctx)
        return ctx

    ref = mk("jit")
    ref.run_solution(5, 0)
    p = mk("pallas", wf=2, block={"x": 24, "y": 24})
    p.run_solution(5, 0)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_skew_off_knob(env):
    """-skew false forces the uniform tiling and still matches."""
    ref = make(env, "jit", "iso3dfd", r=8, g=48)
    ref.run_solution(0, 5)
    p = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
             block={"x": 24, "y": 24}, skew=False)
    p.run_solution(0, 5)
    assert p.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_skew_auto_engage_is_profit_gated(env):
    """skew=None auto-engages the stream dim only when its skew margin
    beats uniform shrink: (K+1)·r + E_sk < 2·K·r.  Misaligned small
    stream radii (cube r=1) must keep it uniform — auto-engaging it
    regressed the round-4 cube-wavefront proxy 2.07× → 1.26× (E_sk=16
    extra width per 32-wide tile) — and no other dim takes its place:
    the tiling is fully uniform.  Explicit skew=True still forces the
    stream-dim path."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk

    # r=8 aligned, K=2: profitable (24 vs 32) → auto-skew ON, and the
    # stream dim is among the engaged dims
    iso = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
               block={"x": 24, "y": 24})
    ch, _ = build_pallas_chunk(iso._program, fuse_steps=2,
                               block=(24, 24), interpret=True)
    assert ch.tiling["skew"] is True
    iso_lead = iso._program.ana.domain_dims[:-1]
    assert iso_lead[-1] in ch.tiling["skew_dims"]

    # r=1 misaligned, K=4: E_sk=16 ⇒ 21 vs 8 → the stream dim stays
    # uniform, and the outer dim never swaps in
    cube = make(env, "pallas", "cube", r=1, g=32, wf=4)
    lead = cube._program.ana.domain_dims[:-1]
    ch, _ = build_pallas_chunk(cube._program, fuse_steps=4,
                               interpret=True)
    assert ch.tiling["skew"] is False
    assert ch.tiling["skew_dims"] == []

    # …but an explicit skew=True still builds (stream dim forced) and
    # matches the oracle
    sk, _ = build_pallas_chunk(cube._program, fuse_steps=4,
                               interpret=True, skew=True)
    assert sk.tiling["skew"] is True
    assert sk.tiling["skew_dims"] == [lead[-1]]


def test_skew_distributed_stream_unsharded(env):
    """shard_pallas engages the skewed wavefront when the stream dim is
    not mesh-decomposed (the carry never crosses a shard boundary):
    oracle equivalence on a 2-shard mesh plus a strictly smaller
    modeled margin overhead than the uniform distributed tiling — the
    distributed temporal-blocking analog of the reference's
    update_tb_info (setup.cpp:863)."""
    from yask_tpu.runtime.init_utils import init_solution_vars

    def mk(mode, ranks=(), skew=True):
        ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=8)
        ctx.apply_command_line_options("-g 48")
        ctx.get_settings().mode = mode
        ctx.get_settings().wf_steps = 2
        ctx.get_settings().skew_wavefront = skew
        for d, r in ranks:
            ctx.set_num_ranks(d, r)
        ctx.prepare_solution()
        init_solution_vars(ctx)
        return ctx

    ref = mk("jit")
    ref.run_solution(0, 3)

    sp = mk("shard_pallas", ranks=[("x", 2)])
    sp.run_solution(0, 3)
    assert sp.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    til = [t for k, t in sp._pallas_tiling.items()
           if k[0] == "shard_pallas"]
    assert til and til[0]["skew"] is True
    # x is mesh-decomposed → only the (unsharded) stream dim engages
    assert til[0]["skew_dims"] == ["y"]

    un = mk("shard_pallas", ranks=[("x", 2)], skew=False)
    un.run_solution(0, 3)
    assert un.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    til_u = [t for k, t in un._pallas_tiling.items()
             if k[0] == "shard_pallas"]
    assert til_u and til_u[0]["skew"] is False
    assert til[0]["margin_overhead"] < til_u[0]["margin_overhead"]

    # stream dim decomposed -> it must not engage (its carry would
    # cross the shard boundary), and nothing else does
    sy = mk("shard_pallas", ranks=[("y", 2)])
    sy.run_solution(0, 3)
    assert sy.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    til_y = [t for k, t in sy._pallas_tiling.items()
             if k[0] == "shard_pallas"]
    assert til_y and til_y[0]["skew_dims"] == []


# ---- the stream dim or nothing -------------------------------------------


def test_skew_per_dim_gate_and_widths(env):
    """Unit coverage for THE shared decision helpers: only the stream
    dim is eligible, it pays E_sk where its radius is misaligned, the
    profit gate decides whether it engages, and ``unsharded`` drops it
    where the mesh decomposes it."""
    from yask_tpu.ops.pallas_stencil import (skew_eligible_dims,
                                             skew_engaged_dims,
                                             skew_extra_widths)

    cube = make(env, "pallas", "cube", r=1, g=32, wf=4)
    prog = cube._program
    lead = prog.ana.domain_dims[:-1]
    assert skew_eligible_dims(prog, 4) == [lead[-1]]
    # r=1 misaligned: 2·sub_t widening, and no entry for any other dim
    assert skew_extra_widths(prog, 4) == {lead[-1]: 16}
    # the gate fails ((K+1)·1+16 ≥ 2·4·1)
    assert skew_engaged_dims(prog, 4) == []

    iso = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2)
    ip = iso._program
    il = ip.ana.domain_dims[:-1]
    assert skew_eligible_dims(ip, 1) == []          # K < 2
    assert skew_extra_widths(ip, 2) == {il[-1]: 0}  # r=8 aligned
    assert skew_engaged_dims(ip, 2) == [il[-1]]
    assert skew_engaged_dims(ip, 2, unsharded=[il[-1]]) == [il[-1]]
    assert skew_engaged_dims(ip, 2, unsharded=[il[-2]]) == []
    assert skew_engaged_dims(ip, 2, unsharded=[]) == []


def test_skew_plan_hints_per_dim(env):
    """Planner hints carry the carry floor ((ring+1)·r) and the skew
    margin ((K+1)·r + E_sk) of exactly the engaged dim."""
    from yask_tpu.ops.pallas_stencil import skew_plan_hints

    iso = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2)
    il = iso._program.ana.domain_dims[:-1]
    smin, smarg = skew_plan_hints(iso._program, 2)
    assert smarg == {il[-1]: 3 * 8}         # (K+1)·r, E=0 aligned
    assert smin == {il[-1]: 3 * 8}          # (ring+1)·r, a ring of two

    cube = make(env, "pallas", "cube", r=1, g=32, wf=4)
    cl = cube._program.ana.domain_dims[:-1]
    # forced: the stream dim's margin pays its E_sk
    _, sm1 = skew_plan_hints(cube._program, 4, engaged=True)
    assert sm1 == {cl[-1]: 5 * 1 + 16}
    # auto: the gate keeps the stream dim uniform, nothing engages
    assert skew_plan_hints(cube._program, 4) == (None, None)
    # explicitly disengaged
    assert skew_plan_hints(cube._program, 4, engaged=False) == (None, None)


# 3-D solutions, each at a depth its stream dim is eligible at: what a
# forced list may name, and what the gate engages with no window given
_STREAM_ONLY = {
    "forced-x": ("iso3dfd", 8, 48, 2, ["x"]),
    "forced-x-and-y": ("iso3dfd", 8, 48, 2, ["x", "y"]),
    "iso3dfd-r8-k4": ("iso3dfd", 8, 48, 4, None),
    "cube-r1-k4": ("cube", 1, 32, 4, None),
}


@pytest.mark.parametrize("case", _STREAM_ONLY)
def test_only_the_stream_dim_can_skew(env, case):
    """A forced list that names a lead dim other than the stream dim
    raises, alone or beside it; the gate's answer is the stream dim or
    nothing, and takes no window."""
    from yask_tpu.ops.pallas_stencil import (build_pallas_chunk,
                                             skew_engaged_dims)
    name, r, g, wf, forced = _STREAM_ONLY[case]
    prog = make(env, "pallas", name, r=r, g=g, wf=wf)._program
    if forced is not None:
        with pytest.raises(YaskException, match="only lead.-1. can skew"):
            build_pallas_chunk(prog, fuse_steps=wf, interpret=True,
                               plan_only=True, skew=forced)
        return
    engaged = skew_engaged_dims(prog, wf)
    assert engaged == (["y"] if name == "iso3dfd" else [])
    with pytest.raises(TypeError):
        skew_engaged_dims(prog, wf, max_dims=2)
    plan = build_pallas_chunk(prog, fuse_steps=wf, interpret=True,
                              plan_only=True)
    assert plan["skew_dims"] == engaged


def test_the_plan_record_names_skew_and_uniform_alone(env):
    """iso3dfd r=8 K=2, where the skew wins: the stream dim engages
    with its profit arithmetic recorded, the other lead dim is told
    why it cannot, every reason code is in the registry, and the plan
    and the tiling record carry no key of a tiling that is not built."""
    from yask_tpu.checker.rules import PLAN_REASON_CODES
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(env, "pallas", "iso3dfd", r=8, g=48, wf=2,
               block={"x": 24, "y": 24})
    plan = build_pallas_chunk(ctx._program, fuse_steps=2, block=(24, 24),
                              interpret=True, plan_only=True)
    assert plan["skew"] is True and plan["skew_dims"] == ["y"]
    why = {r["dim"]: r for r in plan["reasons"] if "dim" in r
           and r["code"].startswith("skew_")}
    assert why["y"]["code"] == "skew_engaged"
    assert why["y"]["detail"] == "profit gate (2+1)*8+0 < 2*2*8"
    assert (why["x"]["code"], why["x"]["detail"]) == (
        "skew_ineligible", "not the stream dim")
    assert {r["code"] for r in plan["reasons"]} <= set(PLAN_REASON_CODES)
    chunk, _ = build_pallas_chunk(ctx._program, fuse_steps=2,
                                  block=(24, 24), interpret=True)
    for rec in (plan, chunk.tiling):
        assert not {"trapezoid", "trap_dims", "diamond",
                    "dimension_semantics"} & set(rec)
    assert chunk.tiling["kernel"] == "yt_iso3dfd_r8_k2"


#: the bool option PR 58 took away, in two halves: a search of the tree
#: for its name then finds only code that still carries it
GONE_58 = "measure" "_halo"


@pytest.mark.parametrize("opt,rest,attr", [
    ("-skew_dims 2", ["-skew_dims", "2"], "skew_dims_max"),
    ("-trapezoid", ["-trapezoid"], "trapezoid_tiling"),
    (f"-{GONE_58}", [f"-{GONE_58}"], f"{GONE_58}_time"),
    (f"-no-{GONE_58}", [f"-no-{GONE_58}"], f"{GONE_58}_time"),
])
def test_the_removed_options_come_back_unparsed(env, opt, rest, attr):
    """Like any unknown option: in the remainder, setting nothing."""
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=2)
    before = dict(vars(ctx.get_settings()))
    assert ctx.apply_command_line_options(
        f"-g 16 {opt} -wf_steps 2") == rest
    after = dict(vars(ctx.get_settings()))
    assert attr not in after and after["wf_steps"] == 2
    changed = {k for k in after if after[k] is not before.get(k)
               and after[k] != before.get(k)}
    assert changed <= {"wf_steps", "global_domain_sizes",
                       "rank_domain_sizes"}


# ---- the strip evaluator under the skewed wavefront ----------------------


@pytest.fixture(scope="module")
def strip_results():
    return strip_cases.run_child(strip_cases.SKEW_CASES)


@pytest.mark.parametrize("case", strip_cases.SKEW_CASES)
def test_strip_evaluator_is_bit_equal_under_skew(strip_results, case):
    """The carry's patches and saves as ref-to-ref copies of their
    strips, and regions that slide instead of shrinking: bit-equal to
    the whole-tile evaluator's (``tests/strip_cases.py``) over the y
    skew at an aligned and a misaligned radius, K = 2 and 4, two
    stages, and one shard's chunk."""
    r = strip_results[case]
    assert r["evals"] == ["tile", "strip"] and r["same_plan"]
    assert r["arrays"] > 0 and r["differ"] == []
    assert r["skew_dims"] == ["y"]
