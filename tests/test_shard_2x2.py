"""The flagship on a 2x2 rank grid over x and y (the layout upstream's
``setup_rank`` picks for four ranks, and the ``iso3dfd-r8-4chip-2x2``
cell's): ``iso3dfd`` radius 8, K=2, ``shard_pallas``, Pallas
interpreted on four of the virtual CPU devices.

A y slab crosses the sublane dim, and a fused group of two steps
reaches the diagonal shard through the corner (x +-8 then y +-8), so
the second axis' slabs must carry the first axis' fresh ghosts.  The
field is dense and seeded, with a point source on the corner where the
four shards meet; every point is held to ``run_ref``, with the
core/shell overlap taken and declined, and to the x/4 layout of the
same problem.  The launch span's per-axis counts are reckoned from the
geometry: in a 2-wide axis a shard has one neighbour.
"""

import json

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.obs import tracer
from yask_tpu.ops.tile_planner import TilePlan

RADIUS, K, STEPS = 8, 2, 10
#: a shard of 48 x 48: hK = 16 a face leaves a core of 16 x 16 (y, the
#: sublane dim, snapped to the tile); x/4 gives shards of 24 >= hK
DOMAIN = (96, 96, 32)
CORNER = (48, 48, 16)


def make(ranks, mode="shard_pallas", overlap="auto"):
    fac = yk_factory()
    env = fac.new_env()
    if env.get_num_ranks() < 4:
        pytest.skip("needs four of the virtual CPU devices")
    ctx = fac.new_solution(env, stencil="iso3dfd", radius=RADIUS)
    ctx.apply_command_line_options(
        "-g_x {} -g_y {} -g_z {} ".format(*DOMAIN)
        + f"-mode {mode} -wf_steps {K}")
    if mode == "shard_pallas":
        ctx.get_settings().overlap_exchange = overlap
        for d, r in zip(("x", "y", "z"), ranks):
            ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    # dense and seeded, never a lone impulse: a tiling fault that a
    # sparse field hides shows at every point
    rng = np.random.RandomState(47)
    pressure = ctx.get_var("pressure")
    last = pressure.get_last_valid_step_index()
    for t in range(pressure.get_first_valid_step_index(), last + 1):
        pressure.set_elements_in_slice(
            rng.rand(*DOMAIN).astype(np.float32) * 0.1,
            [t, 0, 0, 0], [t] + [n - 1 for n in DOMAIN])
    pressure.set_element(1.0, [last, *CORNER])  # where four shards meet
    ctx.get_var("vel").set_all_elements_same(0.1)
    return ctx


def field(ctx):
    t = ctx.get_var("pressure").get_last_valid_step_index()
    return np.asarray(ctx.get_var("pressure").get_elements_in_slice(
        [t, 0, 0, 0], [t] + [n - 1 for n in DOMAIN]), dtype=np.float64)


def ran(ranks, **kw):
    ctx = make(ranks, **kw)
    ctx.run_solution(0, STEPS - 1)          # one call: five K=2 groups
    return ctx


@pytest.fixture(scope="module")
def reference():
    ctx = make((1, 1, 1), mode="jit")
    ctx.run_ref(0, STEPS - 1)
    return field(ctx)


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace2x2") / "TRACE_EVENTS.jsonl"
    mp = pytest.MonkeyPatch()
    mp.setenv("YT_TRACE_EVENTS", str(path))
    mp.setenv("YT_TRACE", "1")
    mp.setattr(tracer, "_compact_checked", False)
    yield path
    mp.undo()


@pytest.fixture(scope="module")
def grid_2x2(trace_file):
    """The 2x2 run with the overlap as the planner takes it, and its
    launch span."""
    ctx = ran((2, 2, 1))
    attrs, = launches(trace_file)
    return ctx, attrs


def launches(path):
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    open(path, "w").close()
    return [r["attrs"] for r in rows if r["name"] == "run.launch"]


def worst(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_2x2_with_the_overlap_agrees_with_run_ref_at_every_point(
        grid_2x2, reference):
    ctx, _attrs = grid_2x2
    til = ctx.get_stats().get_tiling()
    assert til["overlap_exchange"] is True
    # the split is taken in both axes, y's core on the sublane tile
    assert til["overlap"] == {"x": {"taken": True, "core": [16, 32]},
                              "y": {"taken": True, "core": [16, 32]}}
    got = field(ctx)
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    assert got.shape == DOMAIN
    # float32 against the float64 oracle after ten steps
    assert worst(got, reference) < 1e-5
    # the source has crossed the corner into all four shards
    cx, cy, cz = CORNER
    for sx, sy in ((-1, -1), (-1, 0), (0, -1), (0, 0)):
        near = reference[cx + sx * 12:cx + sx * 12 + 12,
                         cy + sy * 12:cy + sy * 12 + 12, cz]
        assert np.abs(near).max() > 0.1


def test_2x2_with_the_overlap_declined_is_the_same_to_the_bit(
        grid_2x2, reference, trace_file):
    on, _attrs = grid_2x2
    off = ran((2, 2, 1), overlap="off")
    launches(trace_file)
    til = off.get_stats().get_tiling()
    assert til["overlap_exchange"] is False
    assert til["overlap"] == {
        d: {"taken": False, "why": "overlap_exchange=off"}
        for d in ("x", "y")}
    assert worst(field(off), reference) < 1e-5
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0


def test_x4_and_2x2_are_one_problem_in_two_layouts(grid_2x2, reference,
                                                   trace_file):
    """Shards of 24 leave no core beside 2 x 16 of shell, so x/4 runs
    the serial schedule here; its launch counts what it counted: both
    faces of the two slots up front and after each group but the last,
    of an interior shard."""
    grid, _attrs = grid_2x2
    slabs = ran((4, 1, 1))
    attrs, = launches(trace_file)
    assert worst(field(slabs), reference) < 1e-5
    assert grid.compare_data(slabs, epsilon=0.0, abs_epsilon=0.0) == 0
    assert attrs["mesh"] == "4x1x1"
    slab = {n: 16 * g.shape[1] * g.shape[2] * 4
            for n, g in shard_geoms(slabs).items()}
    first = 2 * (2 * slab["pressure"] + slab["vel"])
    later = 2 * 2 * slab["pressure"]
    assert (attrs["xslabs"], attrs["xbytes"]) == (
        6 + 4 * 4, first + 4 * later)
    # four wide: the middle shards have both neighbours
    assert (attrs["xslabs_x"], attrs["xbytes_x"]) == (
        attrs["xslabs"], attrs["xbytes"])
    assert "xbytes_y" not in attrs


def shard_geoms(ctx):
    """One shard's arrays as ``_prep_shard_pallas`` pads them."""
    prog = ctx._program
    plan = TilePlan(prog, K)
    rad = ctx._ana.fused_step_radius()
    pad = {d: plan.halo(d) for d in ("x", "y")}
    pad["z"] = rad["z"] * K
    local = ctx._csol.plan(
        ctx._opts.rank_domain_sizes,
        global_sizes=ctx._opts.global_domain_sizes,
        extra_pad={d: (w, w) for d, w in pad.items()})
    return {n: g for n, g in local.geoms.items() if not g.is_scratch}


def test_the_launch_says_the_mesh_and_what_a_chip_sends_an_axis(grid_2x2):
    """``pressure`` keeps two slots and ``vel`` one: every slot's two
    faces an axis up front, then after each of the four groups but the
    last both ``pressure`` slots again.  A slab is 16 planes of the
    padded other two dims.  No shard of a 2-wide axis has two
    neighbours: per axis ONE face, half of what ``xbytes`` holds."""
    ctx, attrs = grid_2x2
    assert attrs["mesh"] == "2x2x1" and attrs["k"] == STEPS
    assert (attrs["halo"], attrs["stages"], attrs["xrounds"]) == (16, 1, 5)
    geoms = shard_geoms(ctx)
    assert {n: list(g.shape) for n, g in geoms.items()} == {
        "pressure": [96, 112, 128], "vel": [80, 96, 128]}
    total = 0
    for d, ax in (("x", 0), ("y", 1)):
        slab = {n: 16 * 4 * int(np.prod(
            [e for i, e in enumerate(g.shape) if i != ax]))
            for n, g in geoms.items()}
        one_face = (2 * slab["pressure"] + slab["vel"]
                    + 4 * 2 * slab["pressure"])
        assert attrs[f"xslabs_{d}"] == 3 + 4 * 2
        assert attrs[f"xbytes_{d}"] == one_face
        total += 2 * one_face
    assert attrs["xslabs"] == 2 * (attrs["xslabs_x"] + attrs["xslabs_y"])
    assert attrs["xbytes"] == total
    assert all(isinstance(v, (int, str, float)) for v in attrs.values())
