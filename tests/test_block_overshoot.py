"""A lead block need not divide its extent (PR 42): the planner's three
rules by a table of extents, the right pad prepare grants for the rows
a block walks past the edge (none where the block divides: every other
benchmark configuration keeps the array shapes it had), and every
point of odd boxes whose x is prime, interpreted, against ``-mode
jit``.  The Overthrust cell's own plan is pinned in
``test_compiled_plans.py`` and ``test_vmem_model.py``; the plain
float64 reference with its faults holds the same boxes in
``test_overthrust_reference.py``.
"""

import json
import os

import pytest

from yask_tpu import yk_factory
from yask_tpu.ops.tile_planner import (first_block, floor_block,
                                       grown_block)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every lead extent a benchmark cell, the served session or a four-chip
# shard tiles: multiples of 64 (or 32 x 5)
CELL_EXTENTS = (640, 768, 320, 512, 384, 256, 160)


def _was_first(extent, guess):
    b = min(guess, extent)
    while extent % b:
        b -= 1
    return b


def _was_floor(extent, floor):
    b = min(floor, extent)
    while extent % b and b < extent:
        b += 1
    return b


def _was_grown(extent, b):
    nb = 2 * b
    while nb <= extent and extent % nb:
        nb *= 2
    return nb if nb <= extent else None


@pytest.mark.parametrize("extent,guess,unit,want", [
    (801, 8, 1, 8),      # 3^2 x 89: the divisor 3 is not within a factor 2
    (801, 8, 8, 8),
    (676, 8, 1, 4),      # 2^2 x 13^2: 4 is, and stays
    (800, 8, 1, 8),
    (37, 8, 1, 8),       # prime: 5 tiles of 8
    (89, 8, 1, 8),
    (9, 8, 1, 5),        # two tiles either way: the balanced one
    (12, 8, 1, 6), (20, 8, 1, 5), (4, 8, 1, 4),
] + [(n, 8, 1, 8) for n in CELL_EXTENTS])
def test_the_first_guess_keeps_a_near_divisor(extent, guess, unit, want):
    got = first_block(extent, guess, unit)
    assert got == want
    if extent in CELL_EXTENTS or 2 * _was_first(extent, guess) >= guess:
        assert got == _was_first(extent, guess)


@pytest.mark.parametrize("extent,floor,unit,want", [
    (801, 24, 8, 24),    # the next divisor is 89
    (676, 24, 8, 26), (800, 24, 8, 25),
    (37, 24, 8, 37),     # the whole extent is within a factor two
    (89, 24, 8, 24), (89, 20, 8, 24), (89, 20, 1, 20),
    (40, 16, 8, 20), (40, 64, 8, 40),
    (640, 24, 8, 32), (768, 24, 8, 24), (320, 24, 8, 32),
    (512, 24, 8, 32), (384, 24, 8, 24), (256, 24, 8, 32),
    (160, 24, 8, 32),
])
def test_a_floor_keeps_a_near_divisor_and_is_never_undercut(
        extent, floor, unit, want):
    got = floor_block(extent, floor, unit)
    assert got == want >= min(floor, extent)
    if extent in CELL_EXTENTS or _was_floor(extent, floor) <= 2 * floor:
        assert got == _was_floor(extent, floor)


@pytest.mark.parametrize("extent,b,unit,want", [
    (801, 8, 1, 16), (801, 16, 1, 31), (801, 31, 1, 62),
    (801, 32, 1, 62), (801, 62, 1, 115),
    (801, 24, 8, 48), (801, 48, 8, 96),
    (676, 4, 1, 8), (676, 8, 1, 16), (800, 32, 1, 62),
    (37, 8, 1, 13), (37, 32, 1, 37), (37, 37, 1, None),
    (37, 16, 8, 24), (37, 24, 8, None),
    (89, 8, 1, 15),
    (48, 16, 1, 24),     # a divisor the doubling never met
    (160, 32, 1, 54),    # was: no candidate
] + [(n, b, 1, 2 * b) for n in CELL_EXTENTS for b in (8, 16)])
def test_a_round_of_growth_doubles_where_that_divides(
        extent, b, unit, want):
    got = grown_block(extent, b, unit)
    assert got == want
    if got is not None:
        assert got > b and got % unit == 0
        assert -(-extent // got) < -(-extent // b)
    if _was_grown(extent, b) is not None:
        assert got == _was_grown(extent, b)


# ---------------------------------------------------------------------
# the pad


def _as_v5e(ctx):
    """Plan as the chip's host would: the budget a block is planned
    with is the platform's (a CPU host's is the interpreter's)."""
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    return ctx


def _ctx(stencil, radius, dom, k, mode="pallas", extra=""):
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode {mode} "
        f"-wf_steps {k} {extra}")
    return ctx


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _shapes(prog):
    return {n: list(g.shape) for n, g in prog.geoms.items()
            if not g.is_scratch and g.shape}


# the parent's array shapes (commit 68df85a, read from _plan_geometry):
# the seven configurations whose lead extents their blocks divide
PARENT_SHAPES = {
    "iso3dfd-r8-1chip": {"pressure": [688, 720, 768],
                         "vel": [672, 704, 640]},
    "cube-r1-1chip": {"A": [778, 848, 896]},
    "ssg-r4-1chip": {
        "v_x": [343, 384, 512], "v_y": [343, 384, 512],
        "v_z": [343, 384, 512], "s_xx": [343, 368, 384],
        "s_yy": [336, 384, 384], "s_zz": [336, 368, 512],
        "s_xy": [343, 384, 384], "s_xz": [343, 368, 512],
        "s_yz": [336, 384, 512], "rho": [337, 376, 512],
        "lambda_": [336, 368, 384], "mu": [337, 376, 384]},
    "tti-r4-1chip": {
        "u": [544, 576, 640], "v": [544, 576, 640],
        "m": [528, 560, 512], "damp": [528, 560, 512],
        "phi": [536, 576, 640], "theta": [536, 576, 640],
        "delta": [528, 560, 512], "epsilon": [528, 560, 512],
        # the hoisted scratch vars' arrays (PR 49), padded as the two
        # arrays they are computed from
        "ti0": [536, 576, 640], "ti1": [536, 576, 640],
        "ti2": [536, 576, 640], "ti3": [536, 576, 640]},
    # the served session of iso3dfd-r8-1chip.snapshots (384^3, wf 2)
    "snapshots": {"pressure": [432, 464, 512], "vel": [416, 448, 384]},
}


def _cell_ctx(name):
    if name == "snapshots":
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               "snapshots.json")) as f:
            dom = json.load(f)["domain"]
        return _ctx("iso3dfd", 8, dom, 2)
    cfg = _config(name)
    return _ctx(cfg["stencil"], cfg["radius"], cfg["domain"],
                int(cfg["wf_steps"]), mode=cfg["mode"])


@pytest.mark.parametrize("name", sorted(PARENT_SHAPES))
def test_a_cell_whose_blocks_divide_keeps_the_shapes_it_had(name):
    ctx = _as_v5e(_cell_ctx(name))
    prog = ctx._plan_geometry()
    assert _shapes(prog) == PARENT_SHAPES[name]
    k = max(ctx._opts.wf_steps, 1)
    needs = ctx._pallas_pad_needs(k)
    rad = ctx._ana.fused_step_radius()
    assert all(left == rad[d] * k for d, (left, _r) in needs.items())
    assert ctx._state is None          # nothing allocated


@pytest.mark.parametrize("name", ["iso3dfd-r8-4chip", "awp-abc-r2-4chip"])
def test_a_shard_program_plans_its_own_pads(name):
    """``shard_step._prep_shard_pallas`` pads each shard itself; the
    global geometry carries no Pallas pad at all, then and now."""
    cfg = _config(name)
    ctx = _ctx(cfg["stencil"], cfg["radius"], cfg["domain"],
               int(cfg["wf_steps"]), mode=cfg["mode"])
    for dim, n in zip("xyz", cfg["ranks"]):
        if int(n) > 1:
            ctx.set_num_ranks(dim, int(n))
    prog = ctx._plan_geometry()
    halo = {n: g.var.halo for n, g in prog.geoms.items()}
    for n, g in prog.geoms.items():
        if g.is_scratch or "x" not in g.domain_dims:
            continue
        hl, hr = halo[n].get("x", (0, 0))
        assert g.shape[g.axis_of("x")] == cfg["domain"][0] + hl + hr


@pytest.mark.parametrize("extra,block,grid,rows", [
    ("", None, None, None),               # the planner's own
    ("-b_x 32 -b_y 48", (32, 48), [26, 17], 31),
    ("-b_x 31 -b_y 48", (31, 48), [26, 17], 5),
    ("-b_x 64 -b_y 48", (64, 48), [13, 17], 31),
    ("-b_x 27 -b_y 64", (27, 64), [30, 13], 9),
    ("-b_x 89 -b_y 64", (89, 64), [9, 13], 0),       # 89 divides
])
def test_the_overthrust_grid_is_padded_for_its_blocks_overshoot(
        extra, block, grid, rows):
    """801 x 801 x 187: the right pad of x grows by the rows the block
    prepare expects walks past the edge, the planner's or an explicit
    one, and by none where it divides; y's pad already held its own."""
    from yask_tpu.checker.vmem import checker_budget, plan_pallas
    ctx = _as_v5e(_ctx("iso3dfd_sponge", 8, (801, 801, 187), 2,
                       extra=extra))
    prog = ctx._plan_geometry()
    plan = plan_pallas(ctx, prog, checker_budget(ctx))
    if block is None:
        block = (plan["block"]["x"], plan["block"]["y"])
        grid = [-(-801 // block[0]), -(-(801 + 8) // block[1])]
        rows = grid[0] * block[0] - 801
        assert 801 % block[0] and rows > 0
    assert (plan["block"]["x"], plan["block"]["y"]) == block
    assert plan["grid"] == grid
    assert not [r for r in plan["reasons"] if r["code"] == "block_fitted"]
    assert plan["overshoot"]["x"] == rows
    assert _shapes(prog) == {
        "pressure": [849 + rows, 888, 256], "vel": [833 + rows, 872, 256],
        "sponge": [833 + rows, 872, 256]}
    over = {r["dim"]: r for r in plan["reasons"]
            if r["code"] == "block_overshoot"}
    assert set(over) == ({"x", "y"} if rows else {"y"})
    for d, r in over.items():
        assert r["block"] == plan["block"][d]
        assert r["overshoot"] == plan["overshoot"][d] <= r["pad"]
    if rows:
        assert over["x"]["pad"] == rows          # to the row


def test_without_the_pad_the_fit_says_what_it_took():
    """The half that does not show alone: a block planned for pads the
    arrays do not have is shrunk until its last window lies inside
    them, and the reason says by how many rows."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = _as_v5e(_ctx("iso3dfd_sponge", 8, (801, 801, 187), 2))
    ctx._block_overshoot_pad = lambda k, needs: {}
    prog = ctx._plan_geometry()
    assert _shapes(prog)["pressure"] == [849, 888, 256]
    plan = build_pallas_chunk(prog, vmem_budget=ctx.vmem_budget(2),
                              plan_only=True, **ctx._pallas_build_args(2))
    fit, = [r for r in plan["reasons"] if r["code"] == "block_fitted"]
    assert fit["to"]["x"] == 9 == plan["block"]["x"]       # 89 x 9
    assert fit["shrunk"] == {"x": fit["from"]["x"] - 9}
    assert plan["overshoot"]["x"] == 0


# ---------------------------------------------------------------------
# every point, interpreted, against -mode jit

BOXES = ((37, 41, 24), (29, 33, 23))       # x prime; 23 no multiple of 17


def _ran(stencil, radius, dom, mode, k, steps, extra=""):
    ctx = _ctx(stencil, radius, dom, k, mode=mode, extra=extra)
    ctx.prepare_solution()
    for v in ctx.get_vars():
        name = v.get_name()
        if v.get_num_dims() == 0:
            v.set_element(0.5, [])
        elif "t" in v.get_dim_names():
            v.set_elements_in_seq(0.01)
        elif name == "sponge":
            v.set_elements_in_seq(0.0001)
        else:
            v.set_all_elements_same(0.05)
    ctx.run_solution(0, steps - 1)
    return ctx


@pytest.fixture(scope="module")
def jit_runs():
    return {dom: _ran("iso3dfd_sponge", 2, dom, "jit", 2, 6)
            for dom in BOXES}


@pytest.mark.parametrize("block", [None, (8, 16), (7, 16), (10, 8)])
@pytest.mark.parametrize("dom", BOXES)
def test_the_block_asked_for_is_the_block_run(dom, block, jit_runs):
    """``iso3dfd_sponge`` r=2, K=2, six steps: an explicit block that
    divides neither lead extent ran at 1 x 16 before the pad."""
    # the default plan under a budget that keeps it from taking the
    # whole box in one tile (8 x 8: five tiles of x for 37)
    extra = ("-vmem_mb 1" if block is None
             else f"-b_x {block[0]} -b_y {block[1]}")
    ctx = _ran("iso3dfd_sponge", 2, dom, "pallas", 2, 6, extra=extra)
    row, = ctx.compiled_plans()
    til = ctx._built_pallas_tiling()
    assert not [r for r in til["reasons"] if r["code"] == "block_fitted"]
    if block is not None:
        assert (row["block"]["x"], row["block"]["y"]) == block
    assert dom[0] % row["block"]["x"]              # x is prime
    assert row["overshoot"]["x"] \
        == row["grid"][0] * row["block"]["x"] - dom[0] > 0
    over = {r["dim"]: r for r in til["reasons"]
            if r["code"] == "block_overshoot"}
    assert row["overshoot_pad"]["x"] == over["x"]["pad"] \
        >= over["x"]["overshoot"] == row["overshoot"]["x"]
    assert ctx.compare_data(jit_runs[dom], epsilon=1e-4,
                            abs_epsilon=1e-6) == 0
    ctx.end_solution()


@pytest.mark.parametrize("block", [(8, 8), (10, 16)])
def test_two_stages_on_an_x_overshoot(block):
    """``ssg`` r=2 on 37 x 40 x 24: the multi-stage evaluator's masks
    on a lead dim whose last block hangs over the edge."""
    dom = (37, 40, 24)
    ctx = _ran("ssg", 2, dom, "pallas", 1, 3,
               extra=f"-b_x {block[0]} -b_y {block[1]}")
    row, = ctx.compiled_plans()
    assert row["stages"] == 2
    assert (row["block"]["x"], row["block"]["y"]) == block
    assert row["overshoot"]["x"] == -(-37 // block[0]) * block[0] - 37 > 0
    ref = _ran("ssg", 2, dom, "jit", 1, 3)
    assert ctx.compare_data(ref, epsilon=1e-4, abs_epsilon=1e-6) == 0
    ctx.end_solution()
    ref.end_solution()


# ---------------------------------------------------------------------
# prepare and the tuner's re-plan share one definition


def test_replan_and_prepare_agree_on_an_extent_no_block_divides():
    dom = (37, 41, 24)

    def mk(tune):
        # 8 x 8 under this budget: five tiles of x walk 3 rows past 37
        ctx = _ctx("iso3dfd_sponge", 2, dom, 2, extra="-vmem_mb 1")
        if tune:
            ctx.get_settings().do_auto_tune = True
            ctx.get_settings().tune_max_wf_steps = 8
        ctx.prepare_solution()
        return ctx

    plain = mk(False)
    prog = plain._program
    plain._replan_pallas_pads(2)
    assert plain._program is prog              # nothing to re-plan
    pads = {n: dict(g.pads) for n, g in prog.geoms.items()}
    assert pads["pressure"]["x"] == (2 + 4, 2 + 4 + 3)

    tuned = mk(True)
    assert tuned._program.geoms["pressure"].pads["x"][0] == 2 + 16
    tuned.get_settings().wf_steps = 2
    tuned._tuned = True
    tuned._replan_pallas_pads(2)
    assert {n: dict(g.pads) for n, g in tuned._program.geoms.items()} \
        == pads
    assert _shapes(tuned._program) == _shapes(prog)


# ---------------------------------------------------------------------
# the dataflow plan the build reads its margins, floors and hints from


def _planned(stencil, radius, g, k):
    ctx = _ctx(stencil, radius, (g, g, g), k)
    return ctx._plan_geometry()


def test_tileplan_margins_and_windows():
    """THE dataflow-plan object: margins, write shifts, block floors
    and margin models of the two tilings a dim can take."""
    from yask_tpu.ops.tile_planner import TilePlan
    prog = _planned("iso3dfd", 8, 48, 2)
    lead = prog.ana.domain_dims[:-1]

    un = TilePlan(prog, 2)
    assert un.margins() == ({d: 16 for d in lead},) * 2   # radius × K
    assert un.min_block() is None and un.margin_override() is None
    assert all(un.halo(d) == 16 and un.write_shift(d, 2) == 0
               for d in lead)

    y = lead[-1]
    sk = TilePlan(prog, 2, skew_dims=[y], e_sk={y: 0})
    mL, mR = sk.margins()
    assert (mL[y], mR[y]) == (16, 8)          # K·r left, r + E_sk right
    assert (mL[lead[0]], mR[lead[0]]) == (16, 16)
    assert sk.write_shift(y, 1) == 0 and sk.write_shift(y, 2) == 8
    assert sk.write_shift(lead[0], 2) == 0
    assert sk.min_block() == {y: 3 * 8}       # (ring + 1)·r
    assert sk.margin_override() == {y: 3 * 8}  # (K + 1)·r + E_sk


def test_tileplan_sublane_rounding():
    """A misaligned radius: the skewed dim's E_sk widens its right
    margin and its margin model; the write shift stays the exact
    (lvl − 1)·r (the build rounds the DMA window, not the plan)."""
    from yask_tpu.ops.pallas_stencil import skew_extra_width
    from yask_tpu.ops.tile_planner import TilePlan
    prog = _planned("cube", 1, 48, 4)
    y = prog.ana.domain_dims[-2]
    e = skew_extra_width(prog.dtype, 1)
    assert e == 16                                    # 2 · sub_t
    tp = TilePlan(prog, 4, skew_dims=[y], e_sk={y: e})
    mL, mR = tp.margins()
    assert (mL[y], mR[y]) == (4, 1 + 16)
    assert tp.margin_override() == {y: 5 * 1 + 16}
    assert [tp.write_shift(y, lvl) for lvl in (1, 2, 3, 4)] == [0, 1, 2, 3]


@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skew"])
def test_tileplan_dataflow_nesting(skewed):
    """dataflow(): what level l+1 reads, level l wrote -- but for the
    skewed dim's left strips, which the carry holds."""
    from yask_tpu.ops.tile_planner import TilePlan
    prog = _planned("iso3dfd", 8, 48, 2)
    lead = prog.ana.domain_dims[:-1]
    y = lead[-1]
    tp = TilePlan(prog, 2, skew_dims=[y] if skewed else [],
                  e_sk={y: 0})
    steps = tp.dataflow({d: 24 for d in lead})
    assert [s["level"] for s in steps] == [1, 2]
    for lvl0, lvl1 in zip(steps, steps[1:]):
        for d in lead:
            wlo, whi = lvl0["write"][d]
            rlo, rhi = lvl1["read"][d]
            assert rhi <= whi
            if skewed and d == y:
                assert 0 < wlo - rlo <= lvl1["carry"][d] == 3 * 8
            else:
                assert rlo >= wlo and d not in lvl1["carry"]


@pytest.mark.parametrize("stencil,radius,k,skew", [
    ("iso3dfd", 8, 2, ["y"]), ("cube", 1, 4, [])])
def test_tileplan_margins_are_the_builds(stencil, radius, k, skew):
    """The plan the build records carries the TilePlan's margins for
    the tiling it resolved to."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    from yask_tpu.ops.tile_planner import TilePlan
    ctx = _ctx(stencil, radius, (48, 48, 48), k)
    plan = build_pallas_chunk(ctx._plan_geometry(), fuse_steps=k,
                              plan_only=True)
    assert plan["skew_dims"] == skew
    tp = TilePlan(ctx._plan_geometry(), k, skew_dims=plan["skew_dims"],
                  e_sk=plan["E"])
    assert tp.margins() == (plan["mL"], plan["mR"])
    for d, floor in (tp.min_block() or {}).items():
        assert plan["block"][d] >= floor
