"""Overlapped halo exchange for shard_pallas (core/shell split of the
fused K-group): the overlap arm must be BIT-identical to the serial
schedule (``compare_data(epsilon=0)``) and agree with the jit oracle in
every engaged configuration — K>1, 2-D meshes, skew-engaged, remainder
groups — while the auto gate must reject rank domains < 2·hK with the
serial fallback, and forcing ``on`` on an infeasible geometry must
raise.  Also covers the resident slice-API fast path (open item riding
this round): all-interior slice reads/writes must ride the
device-resident ring without materializing the padded state.
"""

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.utils.exceptions import YaskException


@pytest.fixture(scope="module")
def env():
    e = yk_factory().new_env()
    if e.get_num_ranks() < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    return e


def _mk(env, mode, ovx="auto", wf=2, g=(32, 8, 16), radius=2,
        ranks=(("x", 2),), spans=((0, 3),)):
    from yask_tpu.runtime.init_utils import init_solution_vars
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=radius)
    gx, gy, gz = g
    ctx.apply_command_line_options(f"-g_x {gx} -g_y {gy} -g_z {gz}")
    s = ctx.get_settings()
    s.mode = mode
    if mode in ("pallas", "shard_pallas"):
        s.wf_steps = wf
        s.overlap_exchange = ovx
        for d, n in ranks:
            ctx.set_num_ranks(d, n)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    for a, b in spans:
        ctx.run_solution(a, b)
    return ctx


def _tiling(ctx):
    til = ctx.get_stats().get_tiling()
    assert til is not None
    return til


_oracles = {}


def _oracle(env, g, radius, spans=((0, 3),)):
    key = (g, radius, spans)
    if key not in _oracles:
        _oracles[key] = _mk(env, "jit", g=g, radius=radius, spans=spans)
    return _oracles[key]


# ---- engaged configurations: bitwise on == off, both match jit ---------

def test_engaged_k2_matches_serial_and_oracle(env):
    # lsize_x = 16 ≥ 2·hK = 8 (r=2, K=2) → auto engages
    on = _mk(env, "shard_pallas", "on")
    off = _mk(env, "shard_pallas", "off")
    til = _tiling(on)
    assert til["overlap_exchange"] is True
    assert "x" in til["overlap_core"]
    assert _tiling(off)["overlap_exchange"] is False
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0
    assert on.compare_data(_oracle(env, (32, 8, 16), 2),
                           epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_auto_arm_engages_and_matches(env):
    auto = _mk(env, "shard_pallas", "auto")
    assert _tiling(auto)["overlap_exchange"] is True
    off = _mk(env, "shard_pallas", "off")
    assert auto.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0


def test_overlap_remainder_group(env):
    # 5 steps under K=2 → two full groups + a 1-step remainder group:
    # a single fused step has no core compute window, so the schedule
    # runs it whole on post-exchange state (recorded reason) and the
    # bit-equality with the serial arm must survive the mixed schedule
    spans = ((0, 4),)
    on = _mk(env, "shard_pallas", "on", spans=spans)
    off = _mk(env, "shard_pallas", "off", spans=spans)
    til = _tiling(on)
    assert any(r.get("code") == "overlap_rem_unsplit"
               for r in til["overlap_reasons"])
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0
    assert on.compare_data(_oracle(env, (32, 8, 16), 2, spans),
                           epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_overlap_split_remainder_group(env):
    # 5 steps under K=3 → one full group + a 2-step remainder group
    # that DOES re-derive the core/shell split (rem ≥ 2)
    spans = ((0, 4),)
    on = _mk(env, "shard_pallas", "on", wf=3, spans=spans)
    off = _mk(env, "shard_pallas", "off", wf=3, spans=spans)
    til = _tiling(on)
    assert til["overlap_exchange"] is True
    assert not any(r.get("code") == "overlap_rem_unsplit"
                   for r in til["overlap_reasons"])
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0


def test_overlap_2d_mesh_sublane_alignment(env):
    # y is the sublane dim: core bounds snap to 8-multiples, so the y
    # split needs lsize_y = 24 (lo=8, hi=16); x keeps unit alignment
    g, ranks = (32, 48, 16), (("x", 2), ("y", 2))
    on = _mk(env, "shard_pallas", "on", g=g, ranks=ranks)
    off = _mk(env, "shard_pallas", "off", g=g, ranks=ranks)
    til = _tiling(on)
    assert til["overlap_exchange"] is True
    assert set(til["overlap_core"]) == {"x", "y"}
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0
    assert on.compare_data(_oracle(env, g, 2),
                           epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_overlap_skew_engaged(env):
    # r=8 K=2 engages the skewed wavefront (stream radius % sublane
    # tile == 0) AND the split: lsize_x = 36 ≥ 2·hK = 32 + alignment
    g = (72, 48, 32)
    on = _mk(env, "shard_pallas", "on", g=g, radius=8)
    off = _mk(env, "shard_pallas", "off", g=g, radius=8)
    til = _tiling(on)
    assert til["skew"] is True
    assert til["overlap_exchange"] is True
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0
    assert on.compare_data(_oracle(env, g, 8),
                           epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_overlap_skew_four_shards_paired_loop_and_short_last_group(env):
    """What the x/4 cell runs since PR 51, at a toy size and every
    point held to the numpy oracle: ``iso3dfd`` radius 8 at K=2, x
    split four ways, blocks that engage the y skew inside each shard
    (y whole: the carry never crosses a shard), the core/shell overlap
    on, and PR 48's loop of two groups a scan iteration -- seven steps
    are group 0 ahead of the scan, one iteration of two groups, and a
    last group of one step.  ``test_overlap_skew_engaged`` has the skew
    under the split on two shards and no loop to speak of;
    ``test_skew.py::test_skew_distributed_stream_unsharded`` the skew
    in a shard and no split; nothing had all of it."""
    from yask_tpu.runtime.init_utils import init_solution_vars
    g, spans = (160, 48, 32), ((0, 6),)

    def mk(skew, ovx="on"):
        ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=8)
        ctx.apply_command_line_options(
            f"-g_x {g[0]} -g_y {g[1]} -g_z {g[2]} -b_x 8 -b_y 24")
        s = ctx.get_settings()
        s.mode, s.wf_steps, s.overlap_exchange = "shard_pallas", 2, ovx
        s.skew_wavefront = skew
        ctx.set_num_ranks("x", 4)
        ctx.prepare_solution()
        init_solution_vars(ctx)
        # the benchmark's velocity: with the seeded one near 1 the
        # oracle and every compiled mode, jit included, part by more
        # than the tolerance after seven steps (conditioning)
        ctx.get_var("vel").set_all_elements_same(0.1)
        return ctx

    ref = mk(True)
    ref.run_ref(*spans[0])
    on = mk(True)
    on.run_solution(*spans[0])
    til = _tiling(on)
    assert til["skew"] is True and til["skew_dims"] == ["y"]
    assert til["block"] == {"x": 8, "y": 24}
    assert til["overlap_exchange"] is True and "x" in til["overlap_core"]
    # the last, one-step group has no core window: run whole
    assert any(r.get("code") == "overlap_rem_unsplit"
               for r in til["overlap_reasons"])
    row, = on.compiled_plans()
    # (behind the scan: the last group of one step)
    assert row["loop"] == {"loop_groups": 2, "loop_iters": 1,
                           "peeled_before": 1, "peeled_after": 1,
                           "reused": 0}
    assert on.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    # the same run under the uniform tiling, and under the serial
    # schedule: the skew and the split each change no value
    flat = mk(False)
    flat.run_solution(*spans[0])
    assert _tiling(flat)["skew"] is False
    assert _tiling(flat)["margin_overhead"] > til["margin_overhead"]
    assert flat.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    assert on.compare_data(flat, epsilon=1e-6, abs_epsilon=1e-7) == 0
    off = mk(True, ovx="off")
    off.run_solution(*spans[0])
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0


# ---- the auto gate: small rank domains must reject, not corrupt --------

def test_auto_gate_rejects_small_domain(env):
    # lsize_x = 6 < 2·hK = 8: auto must fall back to the serial
    # schedule (and say why), and the answer must still be right
    g, ranks = (24, 8, 16), (("x", 4),)
    auto = _mk(env, "shard_pallas", "auto", g=g, ranks=ranks)
    til = _tiling(auto)
    assert til["overlap_exchange"] is False
    assert any("overlap" in r.get("code", "")
               for r in til["overlap_reasons"])
    assert auto.compare_data(_oracle(env, g, 2),
                             epsilon=1e-3, abs_epsilon=1e-4) == 0


def test_forced_on_infeasible_raises(env):
    with pytest.raises(YaskException, match="overlap"):
        _mk(env, "shard_pallas", "on", g=(24, 8, 16), ranks=(("x", 4),))


def test_single_step_groups_never_split(env):
    # K=1 groups are one fused step: nothing to hide an exchange
    # under — auto stays serial (with a reason), forcing "on" raises
    auto = _mk(env, "shard_pallas", "auto", wf=1)
    til = _tiling(auto)
    assert til["overlap_exchange"] is False
    assert any("single-step" in r.get("cause", "")
               for r in til["overlap_reasons"])
    with pytest.raises(YaskException, match="overlap"):
        _mk(env, "shard_pallas", "on", wf=1)


# ---- resident slice fast path (device-resident shard state) ------------

def test_resident_slice_fast_path(env):
    ctx = _mk(env, "shard_pallas", "auto")
    v = ctx.get_var("pressure")
    assert ctx._resident is not None
    # all-interior box: must ride the resident ring, no materialize
    box = ([3, 4, 0, 2], [3, 27, 7, 13])
    a_fast = v.get_elements_in_slice(*box)
    assert ctx._resident is not None
    # interior write stays resident too
    v.set_elements_in_slice(a_fast * 2.0, *box)
    assert ctx._resident is not None
    b_fast = v.get_elements_in_slice(*box)
    assert np.array_equal(b_fast, a_fast * 2.0)
    v.set_elements_in_slice(a_fast, *box)
    # pad-touching box: falls back to the strict materializing path
    pad = v.get_elements_in_slice([3, -1, 0, 0], [3, 0, 0, 0])
    assert ctx._resident is None
    assert pad[0].item() == 0.0   # ghost pads are identically zero
    # the strict path must agree with what the fast path returned
    a_strict = v.get_elements_in_slice(*box)
    assert np.array_equal(a_strict, a_fast)


# ---- region= builds under the pipelined write-back (r10 shell slabs) ----
#
# The overlap schedule's core/shell chunks are region-restricted builds;
# the output-DMA pipeline (use_pipe_out) stages their writes through
# parity-doubled VMEM tiles that retire two grid steps later.  A
# region build changes the grid span and the write windows, so the
# combination gets direct bit-equality coverage here: the region cells
# of a restricted chunk must match the full build EXACTLY, with the
# pipeline engaged on both sides.

def _mk_single(env, g=(32, 48, 16), radius=2, wf=2):
    from yask_tpu.runtime.init_utils import init_solution_vars
    ctx = yk_factory().new_solution(env, stencil="iso3dfd", radius=radius)
    gx, gy, gz = g
    ctx.apply_command_line_options(f"-g_x {gx} -g_y {gy} -g_z {gz}")
    s = ctx.get_settings()
    s.mode = "pallas"
    s.wf_steps = wf
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


def _region_bit_equal(prog, out_full, out_reg, region, extent, wf):
    """Region-interior cells of every written ring slot must agree to
    the last bit (cells outside the region are contract-unwritten)."""
    checked = 0
    for k, g in prog.geoms.items():
        if not g.is_written:
            continue
        L = len(out_full[k])
        for s in range(L - min(wf, L), L):
            a = np.asarray(out_full[k][s])
            b = np.asarray(out_reg[k][s])
            idx = [slice(None)] * a.ndim
            for d in g.domain_dims:
                lo, hi = region.get(d, (0, extent[d]))
                idx[g.axis_of(d)] = slice(g.origin[d] + lo,
                                          g.origin[d] + hi)
            np.testing.assert_array_equal(a[tuple(idx)], b[tuple(idx)])
            checked += 1
    assert checked


def test_region_core_box_pipe_out_bit_equal(env):
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    g = (32, 48, 16)
    ctx = _mk_single(env, g=g)
    prog = ctx._program
    blk = (8, 16)
    region = {"x": (4, 28), "y": (8, 40)}     # core box (y lo 8-aligned)
    full, _ = build_pallas_chunk(prog, fuse_steps=2, block=blk,
                                 interpret=True, pipeline_dmas=True)
    part, _ = build_pallas_chunk(prog, fuse_steps=2, block=blk,
                                 interpret=True, pipeline_dmas=True,
                                 region=region)
    # the pipelined write-back must actually be engaged on both arms
    assert full.tiling["pipeline_out"] is True
    assert part.tiling["pipeline_out"] is True
    assert part.tiling["region"] == {d: list(v)
                                     for d, v in region.items()}
    st = {k: list(v) for k, v in ctx._state.items()}
    _region_bit_equal(prog, full(st, 0), part(st, 0), region,
                      dict(zip(("x", "y", "z"), g)), 2)


@pytest.mark.parametrize("region", [{"x": (0, 4)}, {"x": (28, 32)},
                                    {"y": (0, 8)}, {"y": (40, 48)}],
                         ids=["x-lo", "x-hi", "y-lo", "y-hi"])
def test_region_shell_slab_pipe_out_bit_equal(env, region):
    # the exact shape the overlap scheduler builds: one thin slab per
    # split-dim boundary (width hK = r·K = 4, y slabs 8-aligned), with
    # the output pipeline staging through parity tiles
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    g = (32, 48, 16)
    ctx = _mk_single(env, g=g)
    prog = ctx._program
    blk = (8, 16)
    full, _ = build_pallas_chunk(prog, fuse_steps=2, block=blk,
                                 interpret=True, pipeline_dmas=True)
    slab, _ = build_pallas_chunk(prog, fuse_steps=2, block=blk,
                                 interpret=True, pipeline_dmas=True,
                                 region=region)
    assert slab.tiling["pipeline_out"] is True
    st = {k: list(v) for k, v in ctx._state.items()}
    _region_bit_equal(prog, full(st, 0), slab(st, 0), region,
                      dict(zip(("x", "y", "z"), g)), 2)


def test_region_pipe_arms_bit_equal(env):
    """The output pipeline must never change values: the same region
    build with the pipeline off agrees to the last bit."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    g = (32, 48, 16)
    ctx = _mk_single(env, g=g)
    prog = ctx._program
    region = {"x": (8, 24)}
    kw = dict(fuse_steps=2, block=(8, 16), interpret=True, region=region)
    on, _ = build_pallas_chunk(prog, pipeline_dmas=True, **kw)
    off, _ = build_pallas_chunk(prog, pipeline_dmas=False, **kw)
    assert on.tiling["pipeline_out"] is True
    assert off.tiling["pipeline_out"] is False
    st = {k: list(v) for k, v in ctx._state.items()}
    _region_bit_equal(prog, on(st, 0), off(st, 0), region,
                      dict(zip(("x", "y", "z"), g)), 2)


def test_region_sublane_misaligned_lo_raises(env):
    # y is the sublane axis: a region lo that is not an 8-multiple would
    # be an unaligned Mosaic output window — the planner must refuse
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = _mk_single(env)
    with pytest.raises(YaskException, match="align"):
        build_pallas_chunk(ctx._program, fuse_steps=2, block=(8, 16),
                           interpret=True, region={"y": (4, 20)})
