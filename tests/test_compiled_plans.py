"""``StencilContext.compiled_plans()``: one row a Pallas chunk the
context holds, with the plan the build ACTUALLY chose and what the
compile cost; no row for a mode that builds no chunk.  And the plan the
``ssg-r4-1chip`` cell runs, planned here for the v5e at the cell's own
size (nothing allocated, nothing compiled): what the per-layer metrics
``kernel.margin_overhead`` and ``kernel.vmem_need_share`` will read;
and the plan the ``tti-r4-1chip`` cell runs, whose scratch chain the
record counts as ``scratch_overhead`` (``kernel.scratch_overhead``),
planned since PR 35 on the class's own ``vmem_live`` row, with the
instruction estimate the cap is held against in the record
(``vinstr_est``); and the plan the ``overthrust-sponge-1chip`` cell runs
(801 x 801 x 187: blocks 3 x 64, since no doubling divides 801), with
the two counters of a shape no block divides and no lane count fills,
``edge_overhead`` and ``lane_fill`` (``kernel.edge_overhead``,
``kernel.lane_fill_share``), for it and for every other cell; and
the three plans of the ``himeno-l-1chip`` cell (256 x 256 x 512 at K =
1, 2 and 4: the cell whose bytes set its pace), with the bytes a
launch's DMAs move in both directions (``fetch_bytes_per_step`` and,
since PR 50, ``write_bytes_per_step``: what ``kernel.hbm_moved_share``
reads); and the plans of the ``lbm-d3q19-ldc-1chip`` cell (nineteen
written vars: blocks 4 x 8 at K = 1 until PR 55), with the two
operation counters of a row (``ops_per_point``, the trees' sum, and
``dag_ops_per_point``, what the evaluation memo traces and, since PR
55, ``vinstr_est`` multiplies: what ``kernel.dag_gops_per_s`` reads),
the order in which ``plan_blocks`` tries its doublings and the reading
that ended the growth (``growth_ended``), and every other cell's plan
held to its parent's."""

import json
import math
import os

import pytest

from yask_tpu import yk_factory
from yask_tpu.backend import get_capability
from yask_tpu.ops.pallas_stencil import build_pallas_chunk, plan_attrs
from yask_tpu.stencils.lbm import DIRECTIONS as LBM_DIRECTIONS

MIB = 2 ** 20
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROW_KEYS = {"k", "kernel", "stages", "reach", "stage_consumed", "block",
            "grid", "tile_bytes",
            "result_bytes", "budget", "live_factor", "scoped_need_bytes",
            "vinstr_est", "growth_ended", "ops_per_point",
            "dag_ops_per_point", "eval",
            "strip", "strips", "strip_vregs",
            "margin_overhead", "fetch_overhead", "fetch_windows",
            "fetch_skipped", "fetch_bytes_per_step",
            "write_bytes_per_step", "scratch_overhead",
            "edge_overhead", "overshoot", "overshoot_pad", "lane_fill",
            "pipeline_dmas", "pipeline_out", "compile_secs", "cache_hit",
            "overlap", "loop", "hoisted", "hoist_kept"}


def _cell(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


SSG_CELL = _cell("ssg-r4-1chip")
TTI_CELL = _cell("tti-r4-1chip")
OVERTHRUST_CELL = _cell("overthrust-sponge-1chip")


def _ctx(stencil, radius, dom, mode, k, ranks=()):
    """``ranks``: a rank count a dim, x first (``(4,)``: x split four
    ways; ``(2, 2, 1)``: a 2x2 grid over x and y)."""
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode {mode} "
        f"-wf_steps {k}")
    for d, r in zip(("x", "y", "z"), ranks):
        if r > 1:
            ctx.set_num_ranks(d, r)
    return ctx


def _ran(stencil, radius, dom, mode, k, steps, ranks=()):
    ctx = _ctx(stencil, radius, dom, mode, k, ranks)
    ctx.prepare_solution()
    ctx.run_solution(0, steps - 1)
    return ctx


def test_no_row_before_a_build_and_none_for_jit():
    ctx = _ctx("ssg", 4, (32, 24, 128), "pallas", 1)
    ctx.prepare_solution()
    assert ctx.compiled_plans() == []
    ctx.end_solution()
    ctx = _ran("ssg", 4, (32, 24, 128), "jit", 1, 2)
    assert ctx.compiled_plans() == []
    ctx.end_solution()


def test_one_row_for_the_two_stage_chunk():
    ctx = _ran("ssg", 4, (32, 24, 128), "pallas", 1, 2)
    row, = ctx.compiled_plans()
    assert set(row) == ROW_KEYS
    assert (row["k"], row["stages"]) == (1, 2)
    assert row["kernel"] == "yt_ssg_r8_k1"
    assert set(row["block"]) == {"x", "y"} and len(row["grid"]) == 2
    assert 0 < row["result_bytes"] < row["tile_bytes"] <= row["budget"]
    assert row["scoped_need_bytes"] == pytest.approx(
        row["live_factor"] * row["tile_bytes"], rel=1e-3)
    # a true chain: the stresses read the new velocities, which read
    # the old stresses, so the step reaches the sum of the two
    assert row["reach"] == {"x": 8, "y": 8}
    assert row["stage_consumed"] == [{"x": 4, "y": 4}, {"x": 8, "y": 8}]
    # stage 1 is computed a radius wider than the block on every side
    bx, by = row["block"]["x"], row["block"]["y"]
    assert row["margin_overhead"] == pytest.approx(
        ((bx + 8) * (by + 8) + bx * by) / (2 * bx * by) - 1, abs=1e-4)
    assert row["fetch_overhead"] > 0
    # the six stresses' evicted slots are written, never read: no DMA;
    # a launch fetches what its windows hold, every grid step
    assert row["fetch_skipped"] == [f"s_{c}/0" for c in
                                    ("xx", "xy", "xz", "yy", "yz", "zz")]
    assert len(row["fetch_windows"]) == 12
    assert set(row["fetch_windows"]["v_x/0"]) == {"x", "y"}
    assert 0 < row["fetch_bytes_per_step"] < 2 * row["tile_bytes"] \
        * row["grid"][0] * row["grid"][1]
    # interpreted: traced at the first call, nothing compiled ahead
    assert row["cache_hit"] is None and row["compile_secs"] >= 0
    # it is the record the stats and the span already read
    built = ctx._built_pallas_tiling()
    assert all(built[k] == row[k]
               for k in ROW_KEYS - {"k", "overlap", "loop"})
    # no shard program's row
    assert row["overlap"] is None and row["loop"] is None
    attrs = plan_attrs(built)
    assert attrs["stages"] == 2
    assert attrs["fetch_skipped"] == 6
    assert attrs["fetch_bytes_per_step"] == row["fetch_bytes_per_step"]
    # out, a grid step: the block's rows of the nine produced slots
    # (three velocities, the newer slot of six stresses), z whole
    lanes = {n: ctx._program.geoms[n].shape[-1]
             for n in ("v_x", "v_y", "v_z", "s_xx", "s_xy", "s_xz",
                       "s_yy", "s_yz", "s_zz")}
    assert attrs["write_bytes_per_step"] == row["write_bytes_per_step"] \
        == 4 * bx * by * sum(lanes.values()) \
        * row["grid"][0] * row["grid"][1]
    assert attrs["fetch_windows"].count(":") == 12
    assert attrs["scoped_need_mib"] == round(
        row["scoped_need_bytes"] / MIB, 2)
    ctx.end_solution()
    assert ctx.compiled_plans() == []


def test_a_call_with_a_shorter_last_group_holds_two_rows():
    ctx = _ran("cube", 1, (32, 32, 128), "pallas", 4, 10)
    rows = ctx.compiled_plans()
    assert [r["k"] for r in rows] == [4, 2]
    assert [r["kernel"] for r in rows] == ["yt_cube_r1_k4",
                                           "yt_cube_r1_k2"]
    assert all(r["stages"] == 1 for r in rows)
    assert max(rows, key=lambda r: r["k"])["margin_overhead"] \
        > rows[1]["margin_overhead"]
    ctx.end_solution()


def test_a_shard_program_has_its_per_shard_chunks_row():
    ctx = _ran("iso3dfd", 2, (64, 32, 128), "shard_pallas", 2, 4,
               ranks=(4,))
    row, = ctx.compiled_plans()
    assert set(row) == ROW_KEYS
    # hK = 4 a face of a 16-wide shard: the split is taken in x, the
    # one sharded axis, and the span says so (a one-chip row: None)
    assert row["overlap"] == {"x": {"taken": True, "core": [4, 12]}}
    assert plan_attrs(ctx._built_pallas_tiling())["overlap"] == "x:4-12"
    # four steps at K=2: group 0 ahead of the scan, one group in it
    # (nothing to pair it with), none behind; K=2 reads both slots of
    # the ring, so no output is written onto an evicted one (PR 48)
    assert row["loop"] == {"loop_groups": 1, "loop_iters": 1,
                           "peeled_before": 1, "peeled_after": 0,
                           "reused": 0}
    assert (row["k"], row["stages"]) == (2, 1)
    assert row["kernel"].startswith("yt_iso3dfd_r2_k2")
    assert row["cache_hit"] is None and row["compile_secs"] > 0
    ctx.end_solution()


def _v5e_tiling(stencil, radius, dom, k, block=None, budget=None):
    """The tiling record of the chunk a v5e would build by default (or
    with ``block`` and ``budget`` forced, as ``-b_*`` / ``-vmem_mb``
    would)."""
    ctx = _ctx(stencil, radius, dom, "pallas", k)
    # prepare pads a lead dim for the overshoot of the block it
    # expects, planned with the platform's budget: the chip's, here
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    prog = ctx._plan_geometry()
    if budget is None:
        budget = get_capability("tpu:v5e").plan_budget_bytes(
            k, len(ctx._ana.stages), len(ctx._ana.tile_scratch))
    chunk, _tb = build_pallas_chunk(
        prog, fuse_steps=k, interpret=False, vmem_budget=budget,
        block=block, vinstr_cap=ctx._opts.max_tile_vinstr)
    assert ctx._state is None          # nothing allocated
    return chunk.tiling


def test_the_ssg_cells_plan_on_a_v5e():
    """320x320x384 (and whatever size the configuration states): the
    class (K=1, two stages) has its ``vmem_live`` row since PR 31 (0.6
    result tiles on top of the tiles, budget 112 MiB), so blocks 16x16
    with the input pipeline: tiles of 32x32 and stage 1 computed on
    24x24, where the unmeasured guess planned 8x8 (tiles of 24x24, and
    16x16).  Mosaic takes this plan (``test_mosaic_compiles.py``) and
    the chip ran it 1.8 times as fast (``PERF.md`` section 6).  Since
    PR 45 each slot's DMA copies the window the two stages read of it
    (tile rows, x by y; y, the sublane axis, rounded out to 8 rows): a
    stress's evicted slot nothing, ``lambda_`` the block (stage 2 at
    the point), ``mu`` a row more either way (its two-point average),
    the velocities and the stresses stage 1 differences along y or z
    the 24 rows stage 1 walks, the three it differences along x 31,
    ``rho`` 25 (averaged along x): three points fetched a block point
    of the slots that are fetched where the slabs fetched four of
    every slot's, half the bytes."""
    cap = get_capability("tpu:v5e")
    assert cap.vmem_live_row(1, 2).tiles == 0.6
    assert cap.plan_budget_bytes(1, 2) == 112 * MIB
    for dom in ((320, 320, 384), tuple(SSG_CELL["domain"])):
        til = _v5e_tiling("ssg", SSG_CELL["radius"], dom,
                          SSG_CELL["wf_steps"])
        assert til["block"] == {"x": 16, "y": 16}
        assert til["grid"] == [dom[0] // 16, dom[1] // 16]
        assert (til["stages"], til["kernel"]) == (2, "yt_ssg_r8_k1")
        assert til["margin_overhead"] == 0.625      # (24^2 + 16^2) / 2 / 16^2
        win = {slot: [hi - lo for lo, hi in (w["x"], w["y"])]
               for slot, w in til["fetch_windows"].items()}
        assert win == {
            "lambda_/0": [16, 16], "mu/0": [17, 24], "rho/0": [25, 32],
            "s_xx/1": [31, 32], "s_xy/1": [31, 32], "s_xz/1": [31, 32],
            "s_yy/1": [24, 32], "s_yz/1": [24, 32], "s_zz/1": [24, 32],
            "v_x/0": [24, 32], "v_y/0": [24, 32], "v_z/0": [24, 32]}
        assert til["fetch_skipped"] == [
            f"s_{c}/0" for c in ("xx", "xy", "xz", "yy", "yz", "zz")]
        # rows x rows x lanes: five of the fetched slots ride 384 lanes
        # (lambda_, mu, s_xx, s_xy, s_yy), seven 512
        fetched = 384 * (16 * 16 + 17 * 24 + 2 * 31 * 32 + 24 * 32) \
            + 512 * (25 * 32 + 31 * 32 + 5 * 24 * 32)
        core = 16 * 16 * (5 * 384 + 7 * 512)
        assert til["fetch_overhead"] == 1.9775 \
            == round(fetched / core - 1, 4)   # was 32^2 / 16^2 - 1 = 3.0
        steps = (dom[0] // 16) * (dom[1] // 16)
        assert til["fetch_bytes_per_step"] == 4 * fetched * steps
        # written (PR 50): the block's 16 x 16 rows of the nine
        # produced slots, three of 384 lanes (s_xx, s_xy, s_yy) and six
        # of 512: a quarter of what is fetched at 320 x 320 x 384 (1.73
        # GB a step for 6.71)
        assert til["write_bytes_per_step"] \
            == 4 * 16 * 16 * (3 * 384 + 6 * 512) * steps
        # the whole slabs of all 18 slots (10 of 512 lanes, 8 of 384)
        assert 4 * fetched / (4 * 32 * 32 * (10 * 512 + 8 * 384)) \
            == pytest.approx(0.5001, abs=1e-4)
        assert til["pipeline_dmas"] and not til["pipeline_out"]
        assert til["tile_bytes"] == 84410368 <= til["budget"] == 112 * MIB
        assert til["result_bytes"] == 17301504
        assert til["scoped_need_bytes"] == til["tile_bytes"] \
            + int(0.6 * til["result_bytes"]) <= int(0.9 * 128 * MIB)


def test_the_awp_cells_shard_plan_on_a_v5e():
    """One shard of ``awp-abc-r2-4chip`` (160 x 640 x 512), planned as
    a chunk of its own: four stages, of which both stress stages read
    the new velocities and each other at the same point only, so a step
    reaches 4 (PR 37; the sum of every stage's widest read said 6).
    Stage 0 is evaluated on the block grown by 2 a side, the others on
    the block: ``margin_overhead`` (12^2 + 3 * 8^2) / (4 * 8^2) - 1.
    The tiles are small enough for the planner's own rule to turn the
    input pipeline on under the unmeasured class's 64 MiB."""
    til = _v5e_tiling("awp_abc", None, (160, 640, 512), 1)
    assert (til["stages"], til["kernel"]) == (4, "yt_awp_abc_r4_k1")
    assert til["reach"] == {"x": 4, "y": 4}
    assert til["stage_consumed"] == [{"x": 2, "y": 2}] \
        + 3 * [{"x": 4, "y": 4}]
    assert til["block"] == {"x": 8, "y": 8} and til["grid"] == [20, 80]
    assert til["margin_overhead"] == 0.3125
    # tiles of 16 x 24 rows for a block of 8 x 8 (y's slab starts 4
    # rows off the sublane tile).  Fetched (PR 45): the six arrays the
    # last stages read at the point (``lambda_``, ``mu``, ``qp``, the
    # memory variables) 8 x 8; what stage 0 reads on its 12 x 12
    # (velocities, ``rho``, ``sponge``, the stresses it differences
    # along y or z) 12 x 24, y rounded out to the whole slab, the three
    # it differences along x 15 x 24; the six stresses' evicted slots
    # nothing -- ``stress_zz``, ``_xz``, ``_yz`` are written under a
    # condition each side of the free surface, and the two together
    # cover the domain.  11 of the 17 fetched slots ride 512 lanes, 6
    # (the velocities, ``stress_xz``, ``_yz``, ``_zz``) 640
    assert til["fetch_skipped"] == [
        f"stress_{c}/0" for c in ("xx", "xy", "xz", "yy", "yz", "zz")]
    assert til["fetch_windows"]["stress_zz/1"] == {"x": [2, 14],
                                                   "y": [-4, 20]}
    assert til["fetch_windows"]["mem_zz/0"] == {"x": [4, 12],
                                                "y": [4, 12]}
    fetched = 512 * (6 * 8 * 8 + 3 * 12 * 24 + 2 * 15 * 24) \
        + 640 * (15 * 24 + 5 * 12 * 24)
    assert til["fetch_overhead"] == 2.5625 == round(
        fetched / (8 * 8 * (11 * 512 + 6 * 640)) - 1, 4)  # was 16 * 24 / 8^2 - 1
    assert til["fetch_bytes_per_step"] == 4 * fetched * 20 * 80
    assert til["pipeline_dmas"] and not til["pipeline_out"]
    assert til["budget"] == 64 * MIB and til["live_factor"] == 2.0
    # by the DAG since PR 55: 260 operations a point where the trees
    # sum 364 (16 832 then)
    assert (til["ops_per_point"], til["dag_ops_per_point"]) == (364, 260)
    assert til["vinstr_est"] == 13184
    assert til["growth_ended"] == "budget"
    attrs = plan_attrs(til)
    assert attrs["reach"] == "4x4"
    assert attrs["stage_consumed"] == "2x2,4x4,4x4,4x4"
    assert attrs["margin_overhead"] == 0.3125


def _v5e_shard(cell):
    """``(local program, build arguments)`` of one shard's whole chunk
    of a four-chip cell on a v5e, as ``_prep_shard_pallas`` builds it:
    the per-shard program with its radius x K ghost pads, the class's
    default budget, the skew only along dims the mesh does not
    split."""
    cfg = _cell(cell)
    k = int(cfg["wf_steps"])
    ctx = _ctx(cfg["stencil"], cfg["radius"], cfg["domain"], cfg["mode"],
               k, ranks=cfg["ranks"])
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    ctx._plan_geometry()
    rad = ctx._ana.fused_step_radius()
    dims = ctx._ana.domain_dims
    local = ctx._csol.plan(
        ctx._opts.rank_domain_sizes,
        global_sizes=ctx._opts.global_domain_sizes,
        extra_pad={d: (rad.get(d, 0) * k,) * 2 for d in dims})
    assert ctx._state is None
    return local, dict(
        fuse_steps=k, interpret=False, distributed=True,
        vmem_budget=ctx.vmem_budget(k),
        vinstr_cap=ctx._opts.max_tile_vinstr,
        unsharded_dims=tuple(d for d in dims[:-1]
                             if ctx._opts.num_ranks[d] == 1))


def test_the_x4_cells_shard_plan_on_a_v5e():
    """One shard of ``iso3dfd-r8-4chip`` (256 x 1024 x 1024 of 1024^3,
    K=2, y and z whole), the plan the program gives it by default since
    PR 51: **16 x 24 skewed in y**, what its one-chip twin runs, where
    it ran 16 x 8 uniform and evaluated 3.5 points for each it kept.
    The skew engaged before too and was dropped (``skew_fallback``):
    the carry floor of 24 rows of y was priced at 102.7 of the class's
    88 MiB by a term for K result tiles that the strip kernel never
    holds, and the room check added 5.7 more.  Priced by what the strip
    kernel declares (``VmemLive.declared``: two pressure slots and
    ``vel`` double-buffered, the carry, the output staging; no result
    tile, ``pressure`` being written into the slot it evicts) the floor
    fits, x doubles once (32 x 24 reads 140 544 instructions of the
    cap's 100 000) and both pipelines fit: 106.3 MiB, 113.9 of the
    room's 115.2 by the row's 0.75 result tiles (Mosaic: the declared
    buffers and 4.04 MiB).  The ghost pads (16 rows a side of y) hold
    24 and not 32: the planner asks for 32, the divisor of 1024, and
    the build fits it.  43 tiles of 24 cover 1024 + 8: the last hangs
    8 rows over.  The chip ran this plan 2.27 x as fast as 16 x 8 on
    one chip at the shard's size (``PERF.md`` section 6)."""
    from yask_tpu.ops.pallas_stencil import block_sizer
    cap = get_capability("tpu:v5e")
    row = cap.vmem_live_row(2, 1)
    assert (row.tiles, row.budget_mib, row.declared) == (0.75, 112, True)
    assert not any(r.declared for r in cap.vmem_live if r is not row)
    local, args = _v5e_shard("iso3dfd-r8-4chip")
    assert args["unsharded_dims"] == ("y",)
    chunk, _tb = build_pallas_chunk(local, reuse_evicted=True, **args)
    til = chunk.tiling
    assert til["block"] == {"x": 16, "y": 24} and til["grid"] == [16, 43]
    assert til["skew"] and til["skew_dims"] == ["y"]
    assert til["margin_overhead"] == 0.5            # (32 + 16) / (2 * 16)
    codes = [r["code"] for r in til["reasons"]]
    assert "skew_engaged" in codes and "skew_fallback" not in codes
    assert [r for r in til["reasons"] if r["code"] == "block_fitted"] == [
        {"code": "block_fitted", "from": {"x": 16, "y": 32},
         "to": {"x": 16, "y": 24}, "shrunk": {"y": 8},
         "detail": "sublane/overshoot alignment fit"}]
    assert til["overshoot"] == {"x": 0, "y": 8}
    assert til["pipeline_dmas"] and til["pipeline_out"]
    assert til["eval"] == "strip"
    assert (til["strip"], til["strips"], til["strip_vregs"]) \
        == ([4, 24], 12, 96)
    # tiles of 48 x 48 rows (x: 16 + 16 + 16; y, skewed: 16 + 24 + 8)
    # on 1152 lanes; every slot's DMA its whole y, ``pressure(t)`` its
    # whole x, the two read at the point a radius narrower
    assert til["result_bytes"] == 48 * 48 * 1152 * 4 == 10616832
    assert til["fetch_windows"] == {
        "pressure/0": {"x": [8, 40], "y": [0, 48]},
        "pressure/1": {"x": [0, 48], "y": [0, 48]},
        "vel/0": {"x": [8, 40], "y": [0, 48]}}
    assert til["fetch_overhead"] == 3.6667 == round(
        (48 + 2 * 32) * 48 / (3 * 16 * 24) - 1, 4)      # 8.0 at 16 x 8
    assert til["fetch_bytes_per_step"] == 8521777152    # 16.31 GB then
    assert til["tile_bytes"] == 111476736 <= til["budget"] == 112 * MIB
    assert til["scoped_need_bytes"] == til["tile_bytes"] \
        + int(0.75 * til["result_bytes"]) <= int(0.9 * 128 * MIB)
    assert til["vinstr_est"] == 70272 <= 100_000
    # the planner's price of the block IS the build's count of the plan
    plan = build_pallas_chunk(local, plan_only=True, **args)
    price = block_sizer(local, 2, skew=["y"], distributed=True,
                        unsharded_dims=("y",))(dict(til["block"]))
    assert price.declared
    assert (price.in_bytes, price.work_bytes, price.vinstr) == (
        plan["in_tile_bytes"], plan["work_bytes"], plan["vinstr_est"])
    assert plan["work_bytes"] == plan["carry_bytes"]    # no result tile
    assert 2 * price.in_bytes + price.work_bytes + plan["ostage_bytes"] \
        == plan["tile_bytes"] == til["tile_bytes"]
    # ... and the floor it grew from was held to one copy of the input
    floor = block_sizer(local, 2, skew=["y"], distributed=True,
                        unsharded_dims=("y",))({"x": 8, "y": 32})
    assert floor.in_bytes + floor.work_bytes < 112 * MIB


def test_the_2x2_cells_shard_plan_on_a_v5e():
    """One shard of ``iso3dfd-r8-4chip-2x2`` (512 x 512 x 1024): y is
    split by the mesh, so the skew is ineligible by nature and the
    tiling stays uniform; priced as declared it grows to 16 x 16 with
    both pipelines (16 x 8 before): 2.5 points evaluated a useful one
    where 3.5 were.  What the y split costs on this chip is the
    difference to the x/4 twin's 1.5 (``PERF.md`` section 7)."""
    local, args = _v5e_shard("iso3dfd-r8-4chip-2x2")
    assert args["unsharded_dims"] == ()
    til = build_pallas_chunk(local, reuse_evicted=True, **args)[0].tiling
    assert til["block"] == {"x": 16, "y": 16} and til["grid"] == [32, 32]
    assert not til["skew"] and til["skew_dims"] == []
    assert [r["detail"] for r in til["reasons"]
            if r["code"] == "skew_ineligible"] == [
        "not the stream dim",
        "mesh-decomposed (carry cannot cross shards)"]
    assert til["margin_overhead"] == 1.5     # (32 * 32 + 16 * 16) / 512 - 1
    assert til["fetch_overhead"] == 4.6667
    assert til["fetch_bytes_per_step"] == 10267656192   # 16.31 GB then
    assert til["pipeline_dmas"] and til["pipeline_out"]
    assert til["tile_bytes"] == 106168320 <= 112 * MIB
    assert til["scoped_need_bytes"] <= int(0.9 * 128 * MIB)


@pytest.mark.parametrize(
    "stencil,radius,dom,k,block,margin,tiles,skipped", [
        # PR 51, the (K <= 2, one stage) class priced as declared: 32 x
        # 32 with both pipelines, 106.0 MiB (16 x 32, 55.9 counted)
        ("iso3dfd", 8, (640, 640, 640), 2, {"x": 32, "y": 32}, 0.25,
         111149056, []),
        ("cube", 1, (768, 768, 768), 4, {"x": 32, "y": 16}, None,
         41287680, ["A/0"]),
        # ... and cube's K=2 (the cell's last group): 64 x 32, both
        # pipelines, 89.25 MiB (32 x 32, 53.2 counted)
        ("cube", 1, (768, 768, 768), 2, {"x": 64, "y": 32}, None,
         93585408, ["A/0"]),
        # outside the benchmark, the other user of the (K=1, one stage)
        # class, priced as declared since PR 55 (the 7.4-tile row, read
        # off the whole-tile kernel, is gone): 64 x 32 with both
        # pipelines, 86.25 MiB (32 x 32, 58.5 counted, need 108.4 by 7.4
        # result tiles); the cap ends its growth (128 x 32: 156 160)
        ("iso3dfd", 8, (640, 640, 640), 1, {"x": 64, "y": 32}, 0.0,
         90439680, []),
    ])
def test_the_other_one_chip_cells_plans_are_what_they_were(
        stencil, radius, dom, k, block, margin, tiles, skipped):
    """Priced by the build's own count since PR 35, and, the K=4
    row's to the byte, the plan the old estimate gave; the (K <= 2, one
    stage) row's are PR 51's, priced by what the strip kernel declares
    (``VmemLive.declared``), which the chip ran 29 % and 0.6 % faster
    than their parents' (``PERF.md`` section 6); no instruction
    estimate over the cap.  The slots no DMA is started
    for (PR 45): the flagship reads ``p(t-1)`` at the point, ``cube``
    only writes into the slot it evicts -- half its slabs' bytes."""
    til = _v5e_tiling(stencil, radius, dom, k)
    assert til["block"] == block and til["stages"] == 1
    assert til["tile_bytes"] == tiles
    assert til["fetch_skipped"] == skipped
    whole = til["fetch_bytes_per_step"] * (1 + len(skipped))
    if stencil == "cube":
        # its one fetched slot is read over the whole slab: a radius
        # either side at every sub-step
        grid = til["grid"][0] * til["grid"][1]
        assert whole == 2 * math.prod(
            hi - lo for lo, hi in til["fetch_windows"]["A/1"].values()
        ) * 896 * 4 * grid // k
    if margin is not None:
        assert til["margin_overhead"] == margin
    # written (PR 50): the block's rows of the min(K, 2) newest levels,
    # the padded minor extent whole, every grid step -- the flagship's
    # skewed y walks a 21st tile past the edge and its second level's
    # window, shifted left by the radius, is a whole sublane tile off:
    # 32 rows, no wider
    lanes = {"iso3dfd": 768, "cube": 896}[stencil]
    assert til["write_bytes_per_step"] == 4 * block["x"] * block["y"] \
        * lanes * min(k, 2) * til["grid"][0] * til["grid"][1] // k
    if (stencil, k) == ("iso3dfd", 2):
        assert til["skew_dims"] == ["y"] and til["grid"] == [20, 21]
        assert til["write_bytes_per_step"] == 1321205760
    if k <= 2:
        assert til["pipeline_dmas"] and til["pipeline_out"]
        assert til["budget"] == 112 * MIB
        assert til["scoped_need_bytes"] == til["tile_bytes"] \
            + int(0.75 * til["result_bytes"]) <= int(0.9 * 128 * MIB)
        assert til["growth_ended"] == "cap"
    assert til["scoped_need_bytes"] <= 128 * MIB
    assert 0 < til["vinstr_est"] <= 100_000


@pytest.mark.parametrize("stencil,radius,dom,k", [
    ("iso3dfd", 8, (640, 640, 640), 2),
    ("cube", 1, (768, 768, 768), 4),
    ("ssg", 4, (320, 320, 384), 1),
    ("awp_abc", None, (160, 640, 512), 1),
])
def test_a_program_without_scratch_vars_reads_no_scratch_overhead(
        stencil, radius, dom, k):
    """The cells the benchmark had before ``tti``: whatever their
    stages and fused steps, the record says 0.0."""
    assert _v5e_tiling(stencil, radius, dom, k)["scratch_overhead"] == 0.0


def test_the_tti_cells_plan_on_a_v5e():
    """512^3 at radius 4, the plan the program gives it by default
    since PR 35: a kernel that keeps scratch vars in-tile has its own
    ``vmem_live`` row (4.8 result tiles, budget 96 MiB; ``iso3dfd``'s
    row of 7.4 and 64 held it at 8x8), the planner prices a candidate
    by the build's own count (a scratch tile once, not slots + 1 of
    them doubled) and the instruction cap is held against the regions
    the equations are evaluated on.  So blocks 16x16 with both
    pipelines.  Since PR 49 the four trig scratch vars (``ti0..ti3``:
    sin/cos of two read-only arrays) are HOISTED: read-only arrays
    filled once, four more inputs of the kernel, and ``theta`` and
    ``phi``, which nothing else reads, no operands at all.  Two scratch
    vars are left in-tile (``gu``, ``gv``, read 4 away), each evaluated
    on 24 x 24 x 520 points for a block's 16 x 16 x 512, which
    ``margin_overhead`` (one region a stage) reads as 0.0.  The tiles
    are the parent's to the byte: four single scratch tiles leave, four
    double-buffered input tiles come and two go.
    ``vinstr_est`` by hand, in registers of 8 x 128, by the operations
    the evaluation memo emits (PR 55): ``u`` and ``v``, one part under
    one memo (184 + 11 operations a point: ``v`` shares all but eleven
    of its 189 with ``u``'s 192), on the block's own region, the
    scratch vars, a memo each (56 + 56 of their trees' 58 + 58), on
    theirs:
    8x8    195 * (8 * 1 * 4)  + 112 * (16 * 2 * 5) =  24 160
    16x16  195 * (16 * 2 * 4) + 112 * (24 * 3 * 5) =  65 280
    (by the trees, until PR 55: 381 and 116, 30 752 and 90 528; the
    estimate charges a sin one operation and
    never saw what the trig cost: Mosaic's bundles do,
    ``test_mosaic_compiles.py``).  Mosaic takes this plan there; the
    chip ran 8x8 at 1.05 GPts/s and 16x16 1.6 times as fast (``PERF.md``
    section 6)."""
    cap = get_capability("tpu:v5e")
    row = cap.vmem_live_row(1, 1, 2)
    assert (row.tiles, row.budget_mib, row.scratch) == (4.8, 96, True)
    assert cap.plan_budget_bytes(1, 1, 2) == 96 * MIB
    # a K=1 kernel without scratch vars is priced as declared (PR 55)
    assert cap.vmem_live_row(1, 1).declared
    dom, r, k = (tuple(TTI_CELL["domain"]), TTI_CELL["radius"],
                 TTI_CELL["wf_steps"])
    assert (dom, r, k) == ((512, 512, 512), 4, 1)
    til = _v5e_tiling("tti", r, dom, k)
    assert til["block"] == {"x": 16, "y": 16} and til["grid"] == [32, 32]
    assert (til["stages"], til["kernel"]) == (1, "yt_tti_r8_k1")
    assert til["hoisted"] == ["ti0", "ti1", "ti2", "ti3"]
    assert til["hoist_kept"] == {}
    assert til["pipeline_dmas"] and til["pipeline_out"]
    assert til["margin_overhead"] == 0.0
    # tiles of 32 x 32.  Fetched (PR 45): ``u(t)``, ``v(t)`` whole (the
    # scratch chain differences them 8 away), the four trig arrays on
    # the chain's 24 x 24 (24 x 32: y rounded out to the sublane tile),
    # ``u(t-1)``, ``v(t-1)`` and the four arrays read at the point
    # 16 x 16; four of the twelve slots ride 512 lanes, eight 640.
    # ``theta`` and ``phi`` have no slot: not fetched, not skipped
    assert til["fetch_skipped"] == []
    assert {s: [hi - lo for lo, hi in (w["x"], w["y"])]
            for s, w in til["fetch_windows"].items()} == {
        "damp/0": [16, 16], "delta/0": [16, 16], "epsilon/0": [16, 16],
        "m/0": [16, 16], "ti0/0": [24, 32], "ti1/0": [24, 32],
        "ti2/0": [24, 32], "ti3/0": [24, 32],
        "u/0": [16, 16], "u/1": [32, 32], "v/0": [16, 16],
        "v/1": [32, 32]}
    fetched = 512 * 4 * 16 * 16 \
        + 640 * (4 * 24 * 32 + 2 * 16 * 16 + 2 * 32 * 32)
    assert til["fetch_overhead"] == 1.25 == round(
        fetched / (16 * 16 * (4 * 512 + 8 * 640)) - 1, 4)  # 1.087 with theta, phi
    assert til["fetch_bytes_per_step"] == 4 * fetched * 32 * 32 \
        == 16911433728                                 # 12 884 901 888 then
    assert til["scratch_overhead"] == 1.2852    # 24^2 520 / (16^2 512)
    assert til["tile_bytes"] == 79691776 <= til["budget"] == 96 * MIB
    assert til["result_bytes"] == 5242880
    assert til["scoped_need_bytes"] == til["tile_bytes"] \
        + int(4.8 * til["result_bytes"]) <= int(0.9 * 128 * MIB)
    assert (til["ops_per_point"], til["dag_ops_per_point"]) == (497, 307)
    assert til["vinstr_est"] == 65280 <= 100_000
    # 32 x 16 next: over the budget's two copies (and 117 120 by the
    # estimate: over the cap besides)
    assert til["growth_ended"] == "budget"
    attrs = plan_attrs(til)
    assert attrs["scratch_overhead"] == 1.2852
    assert attrs["growth_ended"] == "budget"
    assert attrs["vinstr_est"] == 65280 and attrs["budget_mib"] == 96.0
    assert (attrs["hoisted"], attrs["hoist_kept"]) == \
        ("ti0,ti1,ti2,ti3", "")


HIMENO_CELL = _cell("himeno-l-1chip")
#: K -> block, budget MiB, a coefficient's window (x, y rows), p's,
#: fetch_overhead, fetch_bytes_per_step, margin_overhead, tile bytes
HIMENO_PLANS = {
    1: ((16, 64), 112, (16, 64), (18, 80), 0.0383, 1846542336, 0.0,
        92897280),
    2: ((32, 32), 112, (34, 48), (36, 48), 0.6026, 1425014784, 0.0645,
        102629376),
    4: ((16, 16), 64, (22, 32), (24, 32), 1.7736, 1233125376, 0.4297,
        55443456),
}


@pytest.mark.parametrize("k", sorted(HIMENO_PLANS))
def test_the_himeno_cells_plans_on_a_v5e(k):
    """256 x 256 x 512 at K = 1, 2 and 4 (the cell states 4; its K
    curve is measured at all three, ``PERF.md`` section 6): one field
    of radius 1 in a ring of two of which the newest is read, twelve
    read-only arrays read at the point.  Fourteen tiles compete for the
    budget, so the blocks stay small, and every coefficient's window
    is the first sub-step's region: the block grown by K - 1 a side in
    x, and in y, the sublane axis, rounded out to 8 rows -- 16 rows
    become 32 as soon as K > 1.  By the program's own count, fusing
    two sweeps moved as many bytes a sweep as fusing none until PR 51
    (1.90 GB for 1.99 at 32 x 16 where the need halves, 0.94 for
    1.88); priced as declared the K=2 plan is 32 x 32 with the input
    pipeline alone and moves 1.43 GB; four move 1.23 GB for a need of
    0.47.  K = 1, outside the benchmark, is priced as declared too
    since PR 55 (the (K=1, one stage) row read off the whole-tile
    kernel is gone) and grows y first, where ``p``'s window of 18 rows
    rounds out to 32: 16 x 64 under 112 MiB where it ran 16 x 16 under
    64 (1.85 GB fetched a sweep for 1.99; 11.78 GPts/s for 10.99 in
    one 15 s pair on the chip, ``PERF.md`` section 6).  ``p``'s write
    target has no DMA; both pipelines are on at
    K = 1 and 4; the minor dim's 512 + 2K ride 640
    lanes.  Written (PR 50): the block's rows of the min(K, 2) newest
    levels on 640 lanes, every grid step."""
    block, budget, coeff, field, fetch, moved, margin, tiles = \
        HIMENO_PLANS[k]
    assert HIMENO_CELL["domain"] == [256, 256, 512]
    assert HIMENO_CELL["wf_steps"] in HIMENO_PLANS
    til = _v5e_tiling("himeno", None, (256, 256, 512), k)
    bx, by = block
    assert til["block"] == {"x": bx, "y": by}
    assert til["grid"] == [256 // bx, 256 // by]
    assert (til["stages"], til["kernel"]) == (1, f"yt_himeno_r1_k{k}")
    assert til["eval"] == "strip" and not til["skew"]
    assert til["budget"] == budget * MIB
    assert til["pipeline_dmas"] and til["pipeline_out"] == (k != 2)
    assert til["fetch_skipped"] == ["p/0"]
    win = {slot: tuple(hi - lo for lo, hi in (w["x"], w["y"]))
           for slot, w in til["fetch_windows"].items()}
    assert win.pop("p/1") == field == (bx + 2 * k, by + 16)
    assert len(win) == 12 and set(win.values()) == {coeff}
    assert coeff == (bx + 2 * (k - 1), by if k == 1 else by + 16)
    fetched = 512 * 12 * coeff[0] * coeff[1] + 640 * field[0] * field[1]
    steps = til["grid"][0] * til["grid"][1]
    assert til["fetch_overhead"] == fetch == round(
        fetched / (bx * by * (12 * 512 + 640)) - 1, 4)
    assert til["fetch_bytes_per_step"] == moved \
        == 4 * fetched * steps // k
    assert til["write_bytes_per_step"] \
        == 4 * bx * by * 640 * min(k, 2) * steps // k \
        == {1: 167772160, 2: 167772160, 4: 83886080}[k]
    # what the algorithm needs a sweep: every array once a group
    need = (13 + 1) * 4 * 256 * 256 * 512 // k
    assert moved + til["write_bytes_per_step"] > need
    assert round((moved + til["write_bytes_per_step"]) / need, 1) \
        == {1: 1.1, 2: 1.7, 4: 2.8}[k]
    assert til["margin_overhead"] == margin
    assert til["edge_overhead"] == 0.0 and til["lane_fill"] == 0.8
    assert til["scratch_overhead"] == 0.0 and til["hoisted"] == []
    assert til["tile_bytes"] == tiles <= til["budget"]
    assert til["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert 0 < til["vinstr_est"] <= 40_000
    attrs = plan_attrs(til)
    assert attrs["write_bytes_per_step"] == til["write_bytes_per_step"]
    assert attrs["fetch_bytes_per_step"] == moved
    assert (attrs["block"], attrs["lane_fill"]) == (f"{bx}x{by}", 0.8)


LBM_CELL = _cell("lbm-d3q19-ldc-1chip")
#: lbm.c's nineteen directions, in the solution's own order
LBM_VEC = tuple(c for _name, c in LBM_DIRECTIONS)
#: K -> block, budget MiB, fetch_overhead, fetched and written bytes a
#: step, margin_overhead, tile bytes, modelled need, vinstr_est, the
#: output staging, what ended the growth
LBM_PLANS = {
    1: ((8, 32), 112, 0.1809, 3724541952, 2885681152, 0.0, 87490560,
        103342080, 35840, False, "budget"),
    2: ((8, 16), 112, 1.617, 4127195136, 2818572288, 0.2031, 69992448,
        82673664, 51520, False, "room"),
}


@pytest.mark.parametrize("k", sorted(LBM_PLANS))
def test_the_lbm_cells_plans_on_a_v5e(k):
    """256 x 256 x 512 at K = 1 (the cell's) and K = 2 (its A/B,
    ``PERF.md`` section 6): nineteen stepped vars and two masks, every
    stepped var written every step.  Until PR 55 the build ended at
    blocks 4 x 8, the smallest there are, held there twice over by
    readings that were not the kernel's: the instruction estimate
    multiplied every equation's TREE (6 599 operations a point, where
    the evaluation memo traces 280: the nineteen equations share their
    density, velocity and equilibrium terms) and read 105 584 at the
    smallest block against the cap of 100 000; and the class's
    ``vmem_live`` row (7.4 result tiles, read off the whole-tile
    kernel on the flagship's one written var) priced 42.9 MiB of tiles
    at 87.6 and shrank 8 x 8 back.  Now the estimate multiplies what
    the memo emits (4 480 at 4 x 8), the class is priced as declared
    (the (K <= 2, one stage) row: the buffers the strip kernel
    allocates, of the written vars a result tile for ``f0`` alone, and
    0.75 result tiles), and y, the sublane dim, grows first, where a
    window of ``b_y + 1`` rows is fetched as ``b_y + 8``: 8 x 8 -> 8 x
    16 -> 8 x 32, and 16 x 32 is over the budget twice.  The kernel's
    DMAs move 6.61 GB a step where they moved 8.02 and the algorithm
    needs 5.37 (160 B a point): an 8-row block fetches 9 rows of x of a
    population that moves along x, y's 32 rows round out to 40 where it
    moves along y, and z's 512 ride 640 lanes where it moves along z.
    The eighteen moving populations' write targets have no DMA; ``f0``,
    read at the point alone, is a ring of ONE slot (written where it
    was read), which is fetched.  K = 2 grows x first (a fused kernel
    evaluates its halo: the plain model, a tie to the outer dim), and
    the build halves 16 x 16 back under the class's room: 8 x 16, 20 %
    more points computed, 6.95 GB a step."""
    (bx, by), budget, fetch, moved, wrote, margin, tiles, need, vinstr, \
        staged, ended = LBM_PLANS[k]
    assert LBM_CELL["domain"] == [256, 256, 512]
    assert LBM_CELL["wf_steps"] == 1
    til = _v5e_tiling("lbm_d3q19", None, (256, 256, 512), k)
    assert til["block"] == {"x": bx, "y": by}
    assert til["grid"] == [256 // bx, 256 // by]
    assert (til["stages"], til["kernel"]) == (1, f"yt_lbm_d3q19_r1_k{k}")
    assert til["eval"] == "strip" and not til["skew"]
    assert til["budget"] == budget * MIB
    assert til["pipeline_dmas"] and til["pipeline_out"] == staged
    # both op counters: the trees' sum, held to nothing, and the
    # distinct operations of the one part under the evaluation memo,
    # which the estimate multiplies
    assert (til["ops_per_point"], til["dag_ops_per_point"]) == (6599, 280)
    assert til["vinstr_est"] == vinstr <= 100_000
    assert til["growth_ended"] == ended
    shrunk = [r for r in til["reasons"] if r["code"] == "block_shrunk"]
    if k == 1:
        # lead rows x sublane tiles x 4 registers of 128 lanes, x 280
        assert vinstr == 280 * bx * (by // 8) * 4
        assert shrunk == []
    else:
        assert shrunk == [{"code": "block_shrunk",
                           "from": {"x": 16, "y": 16},
                           "to": {"x": 8, "y": 16},
                           "detail": "tile model over VMEM budget or room"}]
    moving = [f"f{i}" for i in range(1, 19)]
    assert til["fetch_skipped"] == sorted(f"{n}/0" for n in moving)
    assert set(til["fetch_windows"]) == (
        {f"{n}/1" for n in moving} | {"f0/0", "fluid/0", "accel/0"})
    win = {slot: tuple(hi - lo for lo, hi in (w["x"], w["y"]))
           for slot, w in til["fetch_windows"].items()}
    if k == 1:
        fetched = 0
        for i, (cx, cy, cz) in enumerate(LBM_VEC):
            rows = (bx + abs(cx), by + 8 * abs(cy))
            assert win[f"f{i}/{1 if i else 0}"] == rows, i
            fetched += rows[0] * rows[1] * (640 if cz else 512)
        assert win["fluid/0"] == win["accel/0"] == (bx, by)
        fetched += 2 * bx * by * 512
        assert til["fetch_bytes_per_step"] == moved \
            == 4 * fetched * til["grid"][0] * til["grid"][1]
        assert til["fetch_overhead"] == fetch == round(
            fetched / (bx * by * (11 * 512 + 10 * 640)) - 1, 4)
    else:
        # the first sub-step's region and a point more: every window
        # the block grown by two rows of x (three where the population
        # moves along x) and by a sublane tile either side of y
        assert set(win.values()) == {(bx + 2, by + 16), (bx + 3, by + 16)}
        assert (til["fetch_bytes_per_step"], til["fetch_overhead"]) \
            == (moved, fetch)
    # written: the block's rows of all nineteen, on 512 lanes or 640
    lanes = 9 * 512 + 10 * 640
    assert til["write_bytes_per_step"] == wrote
    if k == 1:
        assert wrote == 4 * lanes * 256 * 256
    # what the algorithm needs a step: 19 + 2 read, 19 written
    need_bytes = 160 * 256 * 256 * 512 // k
    assert round((moved + wrote) / need_bytes, 2) == {1: 1.23, 2: 2.59}[k]
    assert til["margin_overhead"] == margin
    assert til["edge_overhead"] == 0.0 and til["lane_fill"] == 0.8
    assert til["scratch_overhead"] == 0.0 and til["hoisted"] == []
    assert til["tile_bytes"] == tiles <= til["budget"]
    assert til["scoped_need_bytes"] == need == tiles \
        + int(0.75 * til["result_bytes"]) <= int(0.9 * 128 * MIB)
    attrs = plan_attrs(til)
    assert (attrs["ops_per_point"], attrs["dag_ops_per_point"]) \
        == (6599, 280)
    assert attrs["growth_ended"] == ended
    assert attrs["fetch_skipped"] == 18
    assert (attrs["block"], attrs["lane_fill"]) == (f"{bx}x{by}", 0.8)


def test_the_estimate_at_the_lbm_cells_old_block_reads_the_dag():
    """4 x 8 forced, the plan until PR 55: 16 registers (4 lead rows of
    one sublane tile on 512 lanes) x the 280 operations the memo emits
    is 4 480; by the trees' 6 599 it read 105 584, over the cap of
    100 000 at the smallest block there is."""
    til = _v5e_tiling("lbm_d3q19", None, (256, 256, 512), 1, block=(4, 8))
    assert til["block"] == {"x": 4, "y": 8}
    assert til["vinstr_est"] == 4480 == 16 * til["dag_ops_per_point"]
    assert 16 * til["ops_per_point"] == 105584 > 100_000
    assert til["growth_ended"] is None
    assert til["pipeline_dmas"] and til["pipeline_out"]
    assert (til["fetch_bytes_per_step"], til["write_bytes_per_step"]) \
        == (5133828096, 2885681152)


def _trail(monkeypatch, stencil, radius, dom, k):
    """``(tiling, candidates)``: the default build of a one-chip kernel
    on a v5e, and every candidate its own call of ``plan_blocks``
    priced after the first guess, in order, with its verdict (the
    ``trail`` list the build hands in)."""
    from yask_tpu.ops import tile_planner
    real, seen = tile_planner.plan_blocks, []

    def spy(*args, **kw):
        seen.append(kw["trail"])
        return real(*args, **kw)
    monkeypatch.setattr(tile_planner, "plan_blocks", spy)
    til = _v5e_tiling(stencil, radius, dom, k)
    # the last call's: a skew that falls back plans a second time
    return til, [(*t["block"].values(), t["verdict"]) for t in seen[-1]]


@pytest.mark.parametrize("stencil,radius,dom,k,tried", [
    # K=1, one stage, no scratch: the halo is fetched and never
    # evaluated, and the sublane dim's windows round out to the tile,
    # so y grows first (8 x 16 before 16 x 8: 7.00 GB a step with both
    # pipelines for 7.64 with one) ...
    ("lbm_d3q19", None, (256, 256, 512), 1,
     [(8, 16, "taken"), (8, 32, "taken"), (16, 32, "budget")]),
    ("himeno", None, (256, 256, 512), 1,
     [(8, 16, "taken"), (8, 32, "taken"), (16, 32, "taken"),
      (16, 64, "taken"), (32, 64, "budget")]),
    # ... and where the margins are multiples of the tile already
    # (radius 8) the rounding changes nothing: x first, as ever
    ("iso3dfd", 8, (640, 640, 640), 1,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "taken"),
      (32, 32, "taken"), (64, 32, "taken"), (64, 64, "cap")]),
    # every other class evaluates its halo and is ordered by the plain
    # one, a tie to the outer dim: the parent's doublings, one by one
    # (the flagship's skewed y starts at its carry floor of 32)
    ("iso3dfd", 8, (640, 640, 640), 2,
     [(16, 32, "taken"), (32, 32, "taken"), (64, 32, "cap")]),
    ("cube", 1, (768, 768, 768), 4,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "taken"),
      (32, 32, "budget")]),
    ("cube", 1, (768, 768, 768), 2,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "taken"),
      (32, 32, "taken"), (64, 32, "taken"), (64, 64, "cap")]),
    ("tti", 4, (512, 512, 512), 1,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "budget")]),
    ("ssg", 4, (320, 320, 384), 1,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "budget")]),
    ("himeno", None, (256, 256, 512), 4,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "budget")]),
    ("lbm_d3q19", None, (256, 256, 512), 2,
     [(16, 8, "taken"), (16, 16, "taken"), (32, 16, "budget")]),
])
def test_the_order_in_which_the_planner_tries_its_doublings(
        monkeypatch, stencil, radius, dom, k, tried):
    """``plan_blocks(trail=)``: from the first guess of 8 x 8 (the
    flagship's skew: 8 x 32), each round's candidate and what became
    of it; the last entry is the reading that ended the growth, which
    the build's row carries as ``growth_ended`` unless it shrinks the
    block back itself (``room``)."""
    til, trail = _trail(monkeypatch, stencil, radius, dom, k)
    assert trail == tried
    taken = [t for t in trail if t[2] == "taken"]
    assert trail[-1][2] in ("cap", "budget")
    assert til["growth_ended"] in (trail[-1][2], "room")
    assert (til["growth_ended"] == "room") == (
        tuple(til["block"].values()) != taken[-1][:2])


def test_growth_that_runs_out_of_extent_says_so(monkeypatch):
    """A span the blocks cover whole: nothing larger covers it in fewer
    tiles, and the trail ends on the plan itself."""
    til, trail = _trail(monkeypatch, "iso3dfd", 2, (16, 16, 128), 1)
    assert til["block"] == {"x": 16, "y": 16}
    assert trail[-1] == (16, 16, "extent")
    assert til["growth_ended"] == "extent"


#: every other cell's plan as the parent (PR 54, ceb7603) gives it on a
#: v5e: block, both pipelines, tile bytes -- what its ``plan:`` line
#: and ``kernel.vmem_need_share`` read.  ``shard``: one shard's chunk
#: of a four-chip cell; ``k``: the cube call's K=2 tail and the served
#: cell's sessions (384^3) beside the cells' own
PARENT_PLANS = [
    ("iso3dfd-r8-1chip", None, None, (32, 32), True, True, 111149056),
    ("iso3dfd-r8-1chip", (384, 384, 384), None, (64, 24), True, True,
     94371840),
    ("iso3dfd-r8-768-1chip", None, None, (32, 24), True, True, 112459776),
    ("cube-r1-1chip", None, None, (32, 16), True, True, 41287680),
    ("cube-r1-1chip", None, 2, (64, 32), True, True, 93585408),
    ("ssg-r4-1chip", None, None, (16, 16), True, False, 84410368),
    ("tti-r4-1chip", None, None, (16, 16), True, True, 79691776),
    ("overthrust-sponge-1chip", None, None, (62, 24), True, True,
     57753600),
    ("himeno-l-1chip", None, None, (16, 16), True, True, 55443456),
    ("iso3dfd-r8-4chip", "shard", None, (16, 24), True, True, 111476736),
    ("iso3dfd-r8-4chip-2x2", "shard", None, (16, 16), True, True,
     106168320),
    ("awp-abc-r2-4chip", "shard", None, (8, 8), True, False, 57016320),
]


@pytest.mark.parametrize(
    "cell,dom,k,block,pipe_in,pipe_out,tiles", PARENT_PLANS,
    ids=[f"{c[0]}{'-k2' if c[2] else ''}{'-384' if c[1] and c[1] != 'shard' else ''}"
         for c in PARENT_PLANS])
def test_no_other_cells_plan_moves_with_the_lbm_cells(
        cell, dom, k, block, pipe_in, pipe_out, tiles):
    """PR 55 changes what a candidate block costs and the order the
    candidates are tried in for ONE class, (K=1, one stage, no
    scratch), and the instruction estimate for every kernel: every
    other cell keeps its blocks, pipelines and tile bytes to the byte
    (only ``vinstr_est`` moves: tti 90 528 -> 65 280, ssg 63 072 ->
    50 520, awp 16 832 -> 13 184; a one-equation kernel's trees are
    its DAG)."""
    cfg = _cell(cell)
    if dom == "shard":
        local, args = _v5e_shard(cell)
        til = build_pallas_chunk(local, reuse_evicted=True,
                                 **args)[0].tiling
    else:
        til = _v5e_tiling(cfg["stencil"], cfg["radius"],
                          dom or tuple(cfg["domain"]),
                          k or int(cfg["wf_steps"]))
    assert tuple(til["block"].values()) == block
    assert (til["pipeline_dmas"], til["pipeline_out"]) \
        == (pipe_in, pipe_out)
    assert til["tile_bytes"] == tiles
    assert 0 < til["vinstr_est"] <= 100_000
    assert til["growth_ended"] in ("cap", "budget")


@pytest.mark.parametrize("block,said,trees,was", [
    ((8, 8), 24160, 30752, 179640), ((16, 16), 65280, 90528, 319360),
    ((16, 32), 117120, 167136, 479040),
    ((32, 16), 117120, 167136, 479040)])
def test_the_instruction_estimate_reads_the_evaluated_regions(
        block, said, trees, was):
    """``vinstr_est`` of the tti kernel at forced blocks: the
    operations the evaluation memo emits for each equation (PR 55; each
    equation's whole tree until then, ``trees``) times the registers of
    the region it is evaluated on, where the estimate before PR 35
    (``was``) charged every operation the input tile's.  The last two
    compiled in 95 and 108 s on the chip's host (builder's, PR 33)."""
    til = _v5e_tiling("tti", 4, (512, 512, 512), 1, block=block,
                      budget=130 * MIB)
    bx, by = block
    assert til["growth_ended"] is None              # a forced block
    assert til["vinstr_est"] == said == (
        195 * bx * (by // 8) * 4
        + 112 * (bx + 8) * ((by + 8) // 8) * 5)
    # (116 since PR 49: the trig's 1 + 0 + 0 + 1 are not a step's)
    assert trees == (381 * bx * (by // 8) * 4
                     + 116 * (bx + 8) * ((by + 8) // 8) * 5)
    assert was == 499 * (bx + 16) * (by + 16) * 640 // 1024
    assert (was > 300_000) == (block != (8, 8))      # the cap then
    # the cap since PR 42, about a minute of Mosaic: the two that took
    # 95 and 108 s are over it, the default plan (16x16, 45 s) is not
    assert (til["vinstr_est"] <= 100_000) == (bx * by <= 256)


@pytest.mark.parametrize("block,said", [
    ((16, 8), 2.0469), ((8, 16), 2.0469), ((16, 16), 1.2852)])
def test_scratch_overhead_falls_as_blocks_grow(block, said):
    """Forced blocks, under the budget PR 33's A/B gave them
    (``-vmem_mb 130``): the evaluated extent is the block grown by the
    write halo of 4 a side, the minor dim's too."""
    til = _v5e_tiling("tti", 4, (512, 512, 512), 1, block=block,
                      budget=130 * MIB)
    bx, by = block
    assert til["block"] == {"x": bx, "y": by}
    assert til["scratch_overhead"] == said == round(
        (bx + 8) * (by + 8) * 520 / (bx * by * 512) - 1, 4)
    assert til["scratch_overhead"] < 3.0625


def test_the_overthrust_cells_plan_on_a_v5e():
    """801 x 801 x 187 at radius 8, K=2, the plan the program gives it
    since PR 42: a lead block need not divide its extent.  801 = 3^2 x
    89, so by divisors alone the planner ran 3 x 64 (its first guess of
    8 snapped down to 3, no doubling ever dividing: a tile of 35 x 88 x
    256 for a block of 3 x 64, 16 points fetched a block point, 3.67 x
    computed a useful x).  Now x starts at 8, grows 16 -> 31 -> 62 (the
    balanced block of half as many tiles: 13 x 62 = 806, 5 rows past
    the edge, where 13 x 64 walks 31) and the skewed y dim starts at
    its carry floor of 24 (34 x 24 = 816 for 801 + 8); the next round,
    62 x 48, reads 104 160 instructions of the cap's 100 000 (Mosaic
    took 64 s over it on the chip's host, and without the output
    staging it ran 22 % slower than this plan: ``PERF.md`` section 6).  So a tile of 94 x 48 x 256 a
    slot for a block of 62 x 24: three points fetched a block point,
    1.129 x computed a useful x, both DMA pipelines.  prepare grants x
    the 5 rows of right pad the last tile walks past the edge (y's pads
    held its 15 already), and the record says so.  Mosaic takes this
    plan (``test_mosaic_compiles.py``)."""
    cfg = OVERTHRUST_CELL
    dom, r, k = tuple(cfg["domain"]), cfg["radius"], cfg["wf_steps"]
    assert (cfg["stencil"], dom, r, k) \
        == ("iso3dfd_sponge", (801, 801, 187), 8, 2)
    til = _v5e_tiling(cfg["stencil"], r, dom, k)
    assert til["block"] == {"x": 62, "y": 24} and til["grid"] == [13, 34]
    assert 801 % 62 and 801 % 24
    assert (til["stages"], til["kernel"]) \
        == (1, "yt_iso3dfd_sponge_r8_k2")
    assert til["skew_dims"] == ["y"]
    assert til["pipeline_dmas"] and til["pipeline_out"]
    # (PR 51: counted as the strip kernel declares it, ``pressure``
    # written in place: a result tile less than the 62373888 of PR 42)
    assert til["tile_bytes"] == 57753600 <= til["budget"] == 112 * MIB
    assert til["result_bytes"] == 94 * 48 * 256 * 4 == 4620288
    assert til["scoped_need_bytes"] == til["tile_bytes"] \
        + int(0.75 * til["result_bytes"]) == 61218816
    assert til["vinstr_est"] == 52080 <= 100_000
    # x takes the window (PR 45): ``pressure(t)`` its whole 94 rows,
    # ``pressure(t-1)``, ``vel`` and ``sponge``, read at the point of
    # sub-steps whose regions are a radius narrower, 78; the skewed y
    # keeps its whole 48 (was 94 * 48 / (62 * 24) - 1 = 2.0323)
    assert til["fetch_windows"]["pressure/1"] == {"x": [0, 94],
                                                  "y": [0, 48]}
    assert all(til["fetch_windows"][s] == {"x": [8, 86], "y": [0, 48]}
               for s in ("pressure/0", "vel/0", "sponge/0"))
    assert {"code": "fetch_whole", "dim": "y"}.items() <= [
        r for r in til["reasons"] if r["code"] == "fetch_whole"][0].items()
    assert til["fetch_overhead"] == 1.6452 == round(
        (94 + 3 * 78) * 48 / (4 * 62 * 24) - 1, 4)
    assert til["fetch_bytes_per_step"] \
        == 4 * (94 + 3 * 78) * 48 * 256 * 13 * 34 // 2
    assert til["margin_overhead"] == 0.129 == round((78 + 62) / 124 - 1, 4)
    assert til["overshoot"] == {"x": 13 * 62 - 801, "y": 34 * 24 - 801} \
        == {"x": 5, "y": 15}
    assert til["edge_overhead"] == 0.0251 == round(
        13 * 62 * 34 * 24 / 801 ** 2 - 1, 4)
    assert til["lane_fill"] == 0.7305 == round(187 / 256, 4)
    assert til["scratch_overhead"] == 0.0
    assert [r for r in til["reasons"] if r["code"].startswith("block")] == [
        {"code": "block_overshoot", "dim": "x", "block": 62, "grid": 13,
         "overshoot": 5, "pad": 5},
        {"code": "block_overshoot", "dim": "y", "block": 24, "grid": 34,
         "overshoot": 15, "pad": 47}]
    assert til["overshoot_pad"] == {"x": 5, "y": 47}
    attrs = plan_attrs(til)
    assert (attrs["block"], attrs["edge_overhead"], attrs["lane_fill"]) \
        == ("62x24", 0.0251, 0.7305)
    assert (attrs["overshoot"], attrs["overshoot_pad"]) == ("5x15", "5x47")


@pytest.mark.parametrize("stencil,radius,dom,k,edge,lanes", [
    # the y skew walks one tile more: 21 x 32 = 672 for 640 + 8
    ("iso3dfd", 8, (640, 640, 640), 2, 0.05, (640, 768)),
    ("cube", 1, (768, 768, 768), 4, 0.0, (768, 896)),
    ("cube", 1, (768, 768, 768), 2, 0.0, (768, 896)),
    ("ssg", 4, (320, 320, 384), 1, 0.0, (384, 512)),
    ("tti", 4, (512, 512, 512), 1, 0.0, (512, 640)),
    ("awp_abc", None, (160, 640, 512), 1, 0.0, (512, 640)),
    # SEG/EAGE Salt by the same rule (PR 42): 676 = 2^2 x 13^2 ran
    # block x = 4 by divisors alone; now 62 x 24 on 11 x 29
    ("iso3dfd_sponge", 8, (676, 676, 210), 2, 0.0387, (210, 256)),
])
def test_edge_overhead_and_lane_fill_of_the_other_cells(
        stencil, radius, dom, k, edge, lanes):
    """Every cell the benchmark had is a box of multiples of 64: its
    blocks divide its lead extents (``edge_overhead`` 0.0, but for the
    skewed dim's extra tile; no ``block_overshoot`` reason), and its
    minor extent plus the halo pads to the next 128 lanes."""
    til = _v5e_tiling(stencil, radius, dom, k)
    over = [r["dim"] for r in til["reasons"]
            if r["code"] == "block_overshoot"]
    assert over == (["x", "y"] if stencil == "iso3dfd_sponge" else [])
    assert all(g * b - n == til["overshoot"][d] for g, (d, b), n in zip(
        til["grid"], til["block"].items(), dom))
    walked = 1
    for g, b in zip(til["grid"], til["block"].values()):
        walked *= g * b
    assert til["edge_overhead"] == edge == round(
        walked / (dom[0] * dom[1]) - 1, 4)
    assert til["lane_fill"] == round(lanes[0] / lanes[1], 4)
    assert lanes[0] == dom[2] and lanes[1] % 128 == 0


@pytest.mark.parametrize("cell,shard,strip,strips,vregs", [
    # the flagship, by hand: K=2 under the y skew, blocks 32 x 32 (PR
    # 51; 16 x 32 and 12 strips before).  The first sub-step's region
    # is 48 lead rows (32 + 2 * 16 less the 8 its read has eaten a
    # side) by 32 sublane rows (a skewed dim's region keeps the block's
    # width), the second's 32 by 32.  640 lanes are 5 registers and 32
    # sublane rows 4 register tiles: 20 registers a lead row, so 4 lead
    # rows (the power of two within 96) by the whole 32 sublane rows
    # are a strip of 80: 48 / 4 + 32 / 4 = 20 strips a grid step.
    ("iso3dfd-r8-1chip", None, [4, 32], 20, 80),
    # 768^3: blocks 32 x 24, the same two regions 24 sublane rows wide
    # on 768 lanes: 18 registers a lead row, 4 lead rows a strip
    ("iso3dfd-r8-768-1chip", None, [4, 24], 20, 72),
    # cube reads its 27 points on the diagonals: a window shifted along
    # y or z is read at three lead rows, so a strip is at least 8 lead
    # rows (18 registers a row: 4 would fit 96), its cover windows
    # shifted once: regions of 38, 36, 34, 32 lead rows in strips of 8
    # (5 + 5 + 5 + 4), the whole sublane extent
    ("cube-r1-1chip", None, [8, 24], 19, 144),
    # two stages: 24 lead rows by 24 sublane rows and 16 by 16 on 384
    # lanes: 9 registers a row, 8 lead rows a strip: 3 + 2
    ("ssg-r4-1chip", None, [8, 24], 5, 72),
    # two walks (three until PR 49 hoisted the four trig scratch vars:
    # 16 strips): the two rotated derivatives together on the block
    # grown by their write halo (24 lead rows, 520 lanes: 15 registers
    # a row), then the two wavefields on the block: (24 + 16) / 4 strips
    ("tti-r4-1chip", None, [4, 24], 10, 60),
    # 62 x 24 under the y skew on 256 lanes (6 registers a row):
    # regions of 78 and 62 lead rows in strips of 16: 5 + 4
    ("overthrust-sponge-1chip", None, [16, 24], 9, 96),
    # one shard of awp (160 x 640 x 512, K=1, four stages on 8 x 8):
    # regions of 12 x 12 and three of 8 x 8 rows, 8 registers a row,
    # 8 lead rows a strip: 2 + 3 * 1
    ("awp-abc-r2-4chip", (160, 640, 512), [8, 16], 5, 64),
    # himeno (K=4, blocks 16 x 16) reads eight of its twelve diagonals
    # at two lead rows, as cube does: strips of 8 lead rows by the
    # whole sublane extent, 24 rows of 512 lanes (12 registers a lead
    # row, 8 rows fill the 96), regions of 22, 20, 18, 16 lead rows:
    # 3 + 3 + 3 + 2
    ("himeno-l-1chip", None, [8, 24], 11, 96),
    # lbm (K=1, blocks 8 x 32 since PR 55; 4 x 8 and one strip of 16
    # registers before): 32 sublane rows on 512 lanes are 16 registers
    # a lead row, so 4 lead rows by the whole 32 sublane rows are a
    # strip of 64, and the block two strips
    ("lbm-d3q19-ldc-1chip", None, [4, 32], 2, 64),
])
def test_every_cells_kernel_is_evaluated_in_strips_on_a_v5e(
        cell, shard, strip, strips, vregs):
    """The evaluator each cell's chunk gets by default on a v5e and
    the walk it makes of a grid step (the record's ``eval``, ``strip``,
    ``strips``, ``strip_vregs``; the same in ``plan_attrs`` for the
    ``yt.compile.chunk`` span): strips everywhere, the shape from the
    lanes of the minor extent, the region's sublane extent and whether
    an equation reads a shifted window at several lead rows -- never
    from a stencil's name."""
    cfg = _cell(cell)
    til = _v5e_tiling(cfg["stencil"], cfg["radius"],
                      shard or tuple(cfg["domain"]), int(cfg["wf_steps"]))
    assert til["eval"] == "strip"
    assert (til["strip"], til["strips"], til["strip_vregs"]) == \
        (strip, strips, vregs)
    attrs = plan_attrs(til)
    assert attrs["eval"] == "strip"
    assert attrs["strip"] == "x".join(str(n) for n in strip)
    assert (attrs["strips"], attrs["strip_vregs"]) == (strips, vregs)
    assert {"code": "eval_strip"} in til["reasons"]


# ---- every cell's allocation (the pad plan of ``_pallas_pad_needs``; a
# ---- shard's, of ``_prep_shard_pallas``) -----------------------------

# padded shape of every array, by cell (read from the geometry at
# commit fdc8627, PR 45): what decides whether a size fits the chip
CELL_SHAPES = {
    "iso3dfd-r8-1chip.advance": {
        ("pressure",): [688, 720, 768], ("vel",): [672, 704, 640]},
    "cube-r1-1chip.advance": {("A",): [778, 848, 896]},
    # one shard of four, with its radius x K ghost pads
    "iso3dfd-r8-4chip.advance": {
        ("pressure",): [304, 1088, 1152], ("vel",): [288, 1072, 1152]},
    # one shard of the 2x2 grid over x and y (512 x 512 x 1024), with
    # its ghost pads in both split dims (read from the geometry at
    # commit b761893, PR 47)
    "iso3dfd-r8-4chip-2x2.advance": {
        ("pressure",): [560, 576, 1152], ("vel",): [544, 560, 1152]},
    # the served session (384^3)
    "iso3dfd-r8-1chip.snapshots": {
        ("pressure",): [432, 464, 512], ("vel",): [416, 448, 384]},
    "awp-abc-r2-4chip.advance": {
        ("lambda_", "mem_xx", "mem_yy", "mem_zz", "mu", "qp", "rho",
         "sponge", "stress_yy", "stress_yz", "stress_zz"): [168, 672, 640],
        ("stress_xx", "stress_xy", "stress_xz", "vel_x", "vel_y",
         "vel_z"): [171, 672, 640]},
    "ssg-r4-1chip.advance": {
        ("lambda_",): [336, 368, 384], ("mu",): [337, 376, 384],
        ("rho",): [337, 376, 512], ("s_xx",): [343, 368, 384],
        ("s_xy",): [343, 384, 384], ("s_xz",): [343, 368, 512],
        ("s_yy",): [336, 384, 384], ("s_yz",): [336, 384, 512],
        ("s_zz",): [336, 368, 512],
        ("v_x", "v_y", "v_z"): [343, 384, 512]},
    # (PR 49: ``ti0..ti3``, the hoisted scratch vars, are arrays of the
    # state, padded by their write halo as ``phi``/``theta`` are by it)
    "tti-r4-1chip.advance": {
        ("damp", "delta", "epsilon", "m"): [528, 560, 512],
        ("phi", "theta", "ti0", "ti1", "ti2", "ti3"): [536, 576, 640],
        ("u", "v"): [544, 576, 640]},
    "overthrust-sponge-1chip.advance": {
        ("pressure",): [854, 888, 256],
        ("sponge", "vel"): [838, 872, 256]},
    "iso3dfd-r8-768-1chip.advance": {
        ("pressure",): [816, 848, 896], ("vel",): [800, 832, 768]},
    # (PR 50) the fused reach of 4 either side of x; y's pads rounded
    # out to the sublane tile and to the block's overshoot room; 512 +
    # 8 rides 640 lanes, the arrays read at the point their own 512
    "himeno-l-1chip.sweeps-48": {
        ("p",): [266, 336, 640],
        ("a0", "a1", "a2", "a3", "b0", "b1", "b2", "bnd", "c0", "c1",
         "c2", "wrk1"): [264, 336, 512]},
    # (PR 54) a ghost row of x for a population that moves along x,
    # y's pads rounded out to the sublane tile, 640 lanes for one that
    # moves along z; f0 and the masks are read at the point
    "lbm-d3q19-ldc-1chip.advance": {
        ("accel", "f0", "f1", "f2", "fluid"): [258, 336, 512],
        ("f3", "f4", "f7", "f8", "f9", "f10"): [259, 336, 512],
        ("f5", "f6", "f11", "f12", "f13", "f14"): [258, 336, 640],
        ("f15", "f16", "f17", "f18"): [259, 336, 640]},
    # (PR 56) one shard of four (128 x 512 x 512): the shard program
    # pads every dim by the step's reach, so every array rides 640
    # lanes; a second ghost row of x for a population that moves along x
    "lbm-d3q19-ldc-4chip.advance": {
        ("accel", "f0", "f1", "f2", "f5", "f6", "f11", "f12", "f13",
         "f14", "fluid"): [130, 544, 640],
        ("f3", "f4", "f7", "f8", "f9", "f10", "f15", "f16", "f17",
         "f18"): [131, 544, 640]},
}
# bytes of all ring slots as padded (3.906 and 9.661 GiB: ``PERF.md``
# section 4; tti's 6.716 GiB and its four derived arrays' 2.945;
# himeno's fourteen arrays 2.456 GiB; lbm's thirty-nine 7.289, a shard's
# thirty-nine at 512^3 over four chips 6.602)
CELL_BYTES = {"ssg-r4-1chip.advance": 4193996800,
              "tti-r4-1chip.advance": 7211581440
              + 4 * 4 * 536 * 576 * 640,
              "himeno-l-1chip.sweeps-48": 2637594624,
              # thirty-nine arrays: f0 a ring of one, eighteen of two
              "lbm-d3q19-ldc-1chip.advance": 7826767872,
              # a shard's thirty-nine as the program pads them: 6.60 GiB
              # for 4.875 of interiors
              "lbm-d3q19-ldc-4chip.advance": 7088537600}


def test_the_table_holds_every_cell_of_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert sorted(cells) == sorted(CELL_SHAPES)


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_every_cells_allocation_is_what_it_was(cell):
    """From the geometry alone, planned as the chip's host would plan
    it: nothing allocated."""
    config, traffic = cell.split(".")
    cfg = _cell(config)
    dom = cfg["domain"]
    if traffic == "snapshots":
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               "snapshots.json")) as f:
            dom = json.load(f)["domain"]
    k = int(cfg["wf_steps"])
    ctx = _ctx(cfg["stencil"], cfg["radius"], dom, cfg["mode"], k,
               ranks=cfg["ranks"])
    assert cfg["ranks"][2] == 1         # z, the lane dim, is whole
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    prog = ctx._plan_geometry()
    if cfg["mode"] == "shard_pallas":
        rad = ctx._ana.fused_step_radius()
        prog = ctx._csol.plan(
            ctx._opts.rank_domain_sizes,
            global_sizes=ctx._opts.global_domain_sizes,
            extra_pad={d: (rad.get(d, 0) * k,) * 2
                       for d in ctx._ana.domain_dims})
    assert ctx._state is None
    geoms = {n: g for n, g in prog.geoms.items()
             if not g.is_scratch and g.shape}
    assert {n: list(g.shape) for n, g in geoms.items()} == {
        n: shape for names, shape in CELL_SHAPES[cell].items()
        for n in names}
    if cell in CELL_BYTES:
        assert sum(4 * g.num_slots * math.prod(g.shape)
                   for g in geoms.values()) == CELL_BYTES[cell]
