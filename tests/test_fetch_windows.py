"""The input DMA of a ``(var, slot)`` copies the window the kernel's
stages read of it (PR 45), and a slot no stage reads is not fetched.

* The property, over the plans a v5e gives the six solutions the
  benchmark's cells run and one shard's whole, core and shell chunks:
  every read of every equation, worked out here from the equations'
  own points (not from ``analysis.stage_ring_reads``, which the build
  uses), lies inside the window of the slot it falls on, and a slot in
  ``fetch_skipped`` has no read.
* The flagship's and cube's every-point comparison against the
  benchmark's plain references, over several grid steps a dim with the
  fetch double-buffered and the unfetched rows of every buffer NaN at
  every grid step (``tests/poison.py``; the other four solutions'
  are in their own ``test_*_reference.py``) -- and the proof that the
  poison is seen: windows cut a row short read NaN.
* Which vars keep what the slot they are written into held
  (``analysis.kept_vars``), and what proves that they do not.
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

from poison import poison_unfetched_rows
from test_mosaic_compiles import cell_config, shard_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402  (the benchmark's seeding law)

#: cell -> fuse depth of its main chunk, the slots no DMA is started
#: for; ``cube``'s 10-step calls end in a K=2 chunk
ONE_CHIP = [
    ("iso3dfd-r8-1chip", None, []),                 # K=2, y skewed
    ("overthrust-sponge-1chip", None, []),          # a block that overshoots
    ("cube-r1-1chip", None, ["A/0"]),
    ("cube-r1-1chip", 2, ["A/0"]),
    ("ssg-r4-1chip", None,
     [f"s_{c}/0" for c in ("xx", "xy", "xz", "yy", "yz", "zz")]),
    ("tti-r4-1chip", None, []),
]
STRESS_0 = [f"stress_{c}/0" for c in ("xx", "xy", "xz", "yy", "yz", "zz")]


def v5e_chunk(cell, k=None):
    """``(program, tiling)`` of the chunk a v5e builds for a one-chip
    cell at its own size: nothing allocated, nothing traced."""
    from yask_tpu import yk_factory
    from yask_tpu.backend import get_capability
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    cfg = cell_config(cell)
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=cfg["stencil"],
                           radius=cfg["radius"])
    dom, wf = cfg["domain"], int(cfg["wf_steps"])
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode pallas "
        f"-wf_steps {wf}")
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    prog = ctx._plan_geometry()
    k = k or wf
    budget = get_capability("tpu:v5e").plan_budget_bytes(
        k, len(ctx._ana.stages), len(ctx._ana.tile_scratch))
    chunk, _tb = build_pallas_chunk(
        prog, fuse_steps=k, interpret=False, vmem_budget=budget,
        vinstr_cap=ctx._opts.max_tile_vinstr)
    return prog, chunk.tiling


def reads_outside_their_windows(prog, til):
    """Every read of a fetched slot that does not lie inside the slot's
    window, and every read of a slot no DMA is started for: ``[]`` is
    the property.  A stage's region from the record (block, reach,
    ``stage_consumed``), a scratch equation's grown by its write halo;
    a read's slot as the kernel resolves it (the ring has turned once a
    fused sub-step; past its last slot lies a level the kernel computed
    itself)."""
    from yask_tpu.ops.pallas_stencil import _eq_points
    ana = prog.ana
    k_fused, block, reach = til["fuse_steps"], til["block"], til["reach"]
    windows, skipped = til["fetch_windows"], set(til["fetch_skipped"])
    lead = list(block)
    bad = []
    for k in range(k_fused):
        for si, stage in enumerate(ana.stages):
            region = {}
            for d in lead:
                if d in til["skew_dims"]:
                    continue        # whole slabs: nothing to hold it to
                lo = reach[d] * k + til["stage_consumed"][si][d]
                region[d] = (lo, block[d] + 2 * reach[d] * k_fused - lo)
            for part in stage.parts:
                for eq in part.eqs:
                    grown = ana.scratch_write_halo.get(
                        eq.lhs.var_name(), {}) if part.is_scratch else {}
                    for pt in _eq_points(eq):
                        g = prog.geoms[pt.var_name()]
                        so = pt.step_offset()
                        if g.is_scratch or not set(lead) & set(g.domain_dims):
                            continue
                        if g.is_written and so == ana.step_dir:
                            continue            # this step's value
                        j = g.num_slots - 1
                        if g.is_written and so is not None:
                            j += so * ana.step_dir + k
                        elif g.is_written:
                            j += k
                        if j >= g.num_slots:
                            continue            # a level it computed
                        slot = f"{pt.var_name()}/{j}"
                        if slot in skipped or slot not in windows:
                            bad.append((k, si, slot, "not fetched"))
                            continue
                        for d, (lo, hi) in region.items():
                            if d not in g.domain_dims:
                                continue
                            wl, wr = grown.get(d, (0, 0))
                            o = pt.domain_offsets().get(d, 0)
                            wlo, whi = windows[slot][d]
                            if lo - wl + o < wlo or hi + wr + o > whi:
                                bad.append((k, si, slot, d,
                                            (lo - wl + o, hi + wr + o),
                                            (wlo, whi)))
    return bad


@pytest.mark.parametrize("cell,k,skipped", ONE_CHIP)
def test_every_read_of_a_one_chip_cells_kernel_lies_in_its_slots_window(
        cell, k, skipped):
    prog, til = v5e_chunk(cell, k)
    assert til["eval"] == "strip" and til["fetch_skipped"] == skipped
    assert reads_outside_their_windows(prog, til) == []
    # every DMA'd slot is in one list or the other
    # (an array only a hoisted scratch var's fill reads, tti's theta
    # and phi, is no operand: in neither)
    assert len(til["fetch_windows"]) + len(skipped) == sum(
        g.num_slots for n, g in prog.geoms.items()
        if not g.is_scratch and g.domain_dims
        and n not in prog.ana.derive_only)
    if cell == "tti-r4-1chip":
        assert prog.ana.derive_only == {"theta", "phi"}
        assert {s for s in til["fetch_windows"] if s.startswith("ti")} \
            == {f"ti{i}/0" for i in range(4)}


@pytest.mark.parametrize("cell", ["iso3dfd-r8-4chip", "awp-abc-r2-4chip"])
def test_every_read_of_a_shards_kernels_lies_in_its_slots_window(cell):
    """One shard's whole chunk and, where the exchange overlaps
    (iso3dfd), its core and a shell: distributed offsets and region
    restriction change no window."""
    prog, arms = shard_kernels(cell_config(cell))
    assert [a for a, _c in arms] == (
        ["", "core", "shell"] if cell.startswith("iso3dfd") else [""])
    for _arm, chunk in arms:
        til = chunk.tiling
        assert til["eval"] == "strip"
        assert reads_outside_their_windows(prog, til) == []
        assert til["fetch_skipped"] == (
            [] if cell.startswith("iso3dfd") else STRESS_0)
    whole = arms[0][1].tiling
    if len(arms) > 1:
        # x is the sharded dim: the arms differ in their grids alone
        assert all(c.tiling["fetch_windows"] == whole["fetch_windows"]
                   for _a, c in arms)


def test_a_read_outside_a_window_is_reported():
    """The checker above can fail: a window a row short is seen."""
    prog, til = v5e_chunk("ssg-r4-1chip")
    lo, hi = til["fetch_windows"]["v_x/0"]["x"]
    til["fetch_windows"]["v_x/0"]["x"] = [lo + 1, hi]
    til["fetch_skipped"].append("rho/0")
    bad = reads_outside_their_windows(prog, til)
    assert {b[2] for b in bad} == {"v_x/0", "rho/0"}


# ---- every point, with what no DMA copies poisoned ----------------------

DOMAIN, STEPS, TOLERANCE = (40, 32, 48), 4, 1e-5


def reference_stencil(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, os.path.join(BENCH, "stencils", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def every_point(cell, k, options, domain=DOMAIN):
    """``({field: (program, reference)}, plans)`` over the whole box
    after ``STEPS`` steps: the solution and constants of the cell's
    configuration on the program's one-device Pallas path at
    ``options``, seeded by ``check``'s law, against the benchmark's
    plain float64 reference."""
    from yask_tpu import yk_factory
    cfg = cell_config(cell)
    mod = reference_stencil(cfg["stencil"])
    domain = list(domain)
    last = [n - 1 for n in domain]
    fill = check.draw_fill(2147483777, domain, [1, 1, 1], 4)
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=cfg["stencil"],
                           radius=cfg["radius"])
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} "
        f"-mode pallas -wf_steps {k} {options}")
    ctx.prepare_solution()
    for name, c in check.coefficients(mod, cfg, domain).items():
        var = ctx.get_var(name)
        if name in check.scalars(mod):
            var.set_element(c, [])
        else:
            var.set_elements_in_slice(
                check.coefficient_box(check.as_profiles(c, domain),
                                      [0, 0, 0], domain), [0, 0, 0], last)
    for name, levels in check.initial_state(
            mod, domain, [0, 0, 0], domain, fill).items():
        var = ctx.get_var(name)
        newest = var.get_last_valid_step_index()
        for back, level in enumerate(reversed(levels)):
            var.set_elements_in_slice(level, [newest - back, 0, 0, 0],
                                      [newest - back] + last)
    ctx.run_solution(0, STEPS - 1)
    want = check.reference_block(mod, cfg, domain, [0, 0, 0],
                                 max(domain), STEPS, fill)
    got = {}
    for name in want:
        var = ctx.get_var(name)
        t = var.get_last_valid_step_index()
        got[name] = np.asarray(var.get_elements_in_slice(
            [t, 0, 0, 0], [t] + last))
    plans = ctx.compiled_plans()
    ctx.end_solution()
    return {name: (got[name], want[name]) for name in want}, plans


FLAGSHIP, CUBE = "iso3dfd-r8-1chip", "cube-r1-1chip"


@pytest.mark.parametrize("cell,k,options,domain,skipped", [
    # y skewed (its carry floor is 24 rows): 5 x 3 tiles
    (FLAGSHIP, 2, "-b_x 8 -b_y 24", (40, 64, 48), []),
    (FLAGSHIP, 2, "-b_x 8 -b_y 8 -no-skew", DOMAIN, []),  # both windowed
    (FLAGSHIP, 1, "-b_x 8 -b_y 8", DOMAIN, []),
    (CUBE, 4, "-b_x 8 -b_y 8", DOMAIN, ["A/0"]),        # 5 x 4 tiles
    (CUBE, 2, "-b_x 16 -b_y 8", (48, 32, 48), ["A/0"]),
    # a block that overshoots (37 = 4 x 8 + 5), no extent a multiple of 8
    ("overthrust-sponge-1chip", 2, "-b_x 8 -b_y 8", (37, 27, 43), []),
    ("ssg-r4-1chip", 1, "-b_x 8 -b_y 8", DOMAIN,
     [f"s_{c}/0" for c in ("xx", "xy", "xz", "yy", "yz", "zz")]),
    ("awp-abc-r2-4chip", 1, "-b_x 8 -b_y 8", DOMAIN, STRESS_0),
    ("tti-r4-1chip", 1, "-b_x 8 -b_y 8", DOMAIN, []),
])
def test_every_point_agrees_with_the_unfetched_rows_poisoned(
        cell, k, options, domain, skipped, monkeypatch):
    """Every solution the cells run, the fetch double-buffered over at
    least three grid steps a dim, every input buffer NaN before a grid
    step's own copies land in it: a stage that read a row outside a
    window, or a slot no DMA filled, would hand the comparison NaN."""
    poisoned = poison_unfetched_rows(monkeypatch)
    fields, plans = every_point(cell, k, options, domain)
    assert poisoned and all(n >= 2 for n in poisoned)
    row = max(plans, key=lambda r: r["k"])
    assert row["eval"] == "strip" and row["pipeline_dmas"]
    assert all(n >= 3 for n in row["grid"])
    assert row["fetch_skipped"] == skipped
    if cell == FLAGSHIP and k == 2:
        # p(t-1) and vel are read at the point: a radius narrower than
        # the slab in a dim that takes the window; a skewed dim keeps
        # its whole slab
        win = row["fetch_windows"]
        assert win["vel/0"]["x"] == win["pressure/0"]["x"] == [8, 32]
        assert win["pressure/1"]["x"] == [0, 40]
        skewed = row["block"]["y"] == 24
        assert skewed == ("-no-skew" not in options)
        assert win["vel/0"]["y"] == ([0, 48] if skewed else [8, 32])
    for name, (got, want) in fields.items():
        assert np.isfinite(got).all(), name
        assert check.block_error(got, want) <= TOLERANCE, name


@pytest.mark.parametrize("cell", [FLAGSHIP, CUBE, "ssg-r4-1chip"])
def test_a_window_cut_short_reads_the_poison(cell, monkeypatch):
    """The comparison above can fail: with every read's widths
    forgotten (the windows then hold the regions alone), the stages
    read rows no DMA copied, and the poison shows in the result."""
    from yask_tpu.compiler.analysis import SolutionAnalysis
    honest = SolutionAnalysis.stage_ring_reads

    def at_the_point(self, kept=None):
        return [[(name, so, {}) for name, so, _widths in reads]
                for reads in honest(self, kept)]

    monkeypatch.setattr(SolutionAnalysis, "stage_ring_reads", at_the_point)
    poison_unfetched_rows(monkeypatch)
    fields, _plans = every_point(cell, 1, "-b_x 8 -b_y 8")
    assert any(not np.isfinite(got).all() for got, _w in fields.values())


# ---- what keeps the evicted slot's values --------------------------------

def analysis_of(stencil, options="-g 24", radius=None):
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(options + " -mode pallas")
    prog = ctx._plan_geometry()
    return ctx._ana, prog


def test_conditions_that_cover_the_domain_keep_nothing():
    """``awp_abc`` writes ``stress_zz`` below the free surface and on
    it, ``stress_xz`` and ``_yz`` likewise: each pair of conditions
    selects every point, no stage reads the half-written var, so the
    evicted slot's values show nowhere and the slot is not fetched.
    Nothing proved, they are kept."""
    from yask_tpu.compiler.analysis import EVICTED
    from yask_tpu.ops.pallas_stencil import conds_cover_domain
    ana, prog = analysis_of("awp_abc")
    conditional = {"stress_zz", "stress_xz", "stress_yz"}
    assert ana.kept_vars() == conditional
    assert ana.kept_vars(lambda c: conds_cover_domain(prog, c)) == set()
    assert ana.kept_vars(lambda c: False) == conditional
    evicted = [(si, name) for si, reads in enumerate(ana.stage_ring_reads())
               for name, so, _w in reads if so == EVICTED]
    # in the stage of the var's first equation
    assert sorted(evicted) == [(1, "stress_xz"), (1, "stress_yz"),
                               (2, "stress_zz")]
    assert not [1 for reads in ana.stage_ring_reads(set())
                for _n, so, _w in reads if so == EVICTED]


def test_a_condition_that_leaves_points_out_keeps_the_slot():
    """``P(t+1, y)`` is written where ``y >= 4`` and nowhere else:
    below, it holds what the evicted slot held, which has to be
    fetched.  ``test_boundary_3d`` writes a box and its complement:
    nothing kept."""
    from strip_cases import partial_written_cond
    from yask_tpu import yk_factory
    from yask_tpu.ops.pallas_stencil import (build_pallas_chunk,
                                             conds_cover_domain)
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), partial_written_cond())
    ctx.apply_command_line_options("-g 32 -mode pallas")
    prog = ctx._plan_geometry()
    covers = lambda c: conds_cover_domain(prog, c)      # noqa: E731
    assert ctx._ana.kept_vars(covers) == {"P"}
    til = build_pallas_chunk(prog, interpret=True)[0].tiling
    assert til["eval"] == "strip" and "P/0" in til["fetch_windows"]
    ana, prog = analysis_of("test_boundary_3d")
    assert ana.kept_vars() == {"A"}
    assert ana.kept_vars(lambda c: conds_cover_domain(prog, c)) == set()


def test_conds_cover_domain_reads_index_conditions_alone():
    from yask_tpu.ops.pallas_stencil import conds_cover_domain
    ana, prog = analysis_of("awp_abc")
    by_var = {}
    for eq in ana.eqs:
        if eq.cond is not None:
            by_var.setdefault(eq.lhs.var_name(), []).append(eq.cond)
    for conds in by_var.values():
        assert len(conds) == 2
        assert conds_cover_domain(prog, conds)
        assert not conds_cover_domain(prog, conds[:1])
        assert not conds_cover_domain(prog, conds[1:])
        assert conds_cover_domain(prog, conds[:1] + [None])
    assert conds_cover_domain(prog, [None])


def test_a_var_written_a_misc_plane_at_a_time_keeps_nothing_where_all_are():
    """``test_misc_value_2d`` writes three planes of ``A``, each by an
    unconditional equation of its own: every plane's first equation
    starts from zeros, none from the evicted slot."""
    ana, _prog = analysis_of("test_misc_value_2d", "-g 32")
    assert ana.kept_vars() == set()
