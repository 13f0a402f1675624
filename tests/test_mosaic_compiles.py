"""Mosaic's own word, at no chip time: the kernels of the main path
compiled at their cells' sizes for a v5e that is described, not
attached (``on-chip-measurement`` guide, section 2: the TPU's compiler
is installed here).  What interpret mode cannot see -- a slice off the
tiling, more scoped VMEM than a kernel may hold -- is refused here as
the chip would refuse it.  Nothing runs: no result, no time.

The topology is described inside a fixture of this file (never at
import: one process at a time may load the TPU's library, and under
several workers only the one given this file does), the compile is in
the test's own process, and the whole file skips where no topology can
be described.  Keep such tests in this one file.
"""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 2 ** 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to JAX's persistent
    # cache and cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def cell_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def compile_cell_kernel(cfg, one_chip, block=None, budget_mib=None):
    """The executable ``_get_pallas_chunk`` would hold for the cell on
    a v5e (planner defaults, or ``block`` and ``budget_mib`` forced as
    ``-b_*`` and ``-vmem_mb`` would; ``chunk.written`` built ``onto``:
    the slots the kernel writes, handed the kernel's operands and no
    other array of the state, and ``base``, the given-up slots it
    writes them onto, donated), lowered on shapes alone and
    compiled."""
    import jax
    import jax.numpy as jnp
    from yask_tpu import yk_factory
    from yask_tpu.backend import get_capability
    from yask_tpu.ops.pallas_stencil import (build_pallas_chunk,
                                             program_state_slots)
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=cfg["stencil"],
                           radius=cfg["radius"])
    dom, k = cfg["domain"], int(cfg["wf_steps"])
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode {cfg['mode']} "
        f"-wf_steps {k}")
    # prepare pads a lead dim for the overshoot of the block it
    # expects, planned with the platform's budget: the chip's, here
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    prog = ctx._plan_geometry()
    budget = budget_mib * MIB if budget_mib else get_capability(
        "tpu:v5e").plan_budget_bytes(
        k, len(ctx._ana.stages), len(ctx._ana.tile_scratch))
    chunk, _tb = build_pallas_chunk(
        prog, fuse_steps=k, interpret=False, vmem_budget=budget,
        block=block, vinstr_cap=ctx._opts.max_tile_vinstr, onto=True)

    def padded(name, count):
        return [jax.ShapeDtypeStruct(tuple(prog.geoms[name].shape),
                                     prog.dtype, sharding=one_chip)
                for _ in range(count)]

    state = {name: padded(name, len(program_state_slots(prog, name)))
             for name in chunk.written.operands}
    base = {name: padded(name, count)
            for name, count in chunk.written.writes.items()}
    t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    # the program's own compile chokepoint, unkeyed: nothing persisted
    from yask_tpu.cache import aot_compile
    from yask_tpu.runtime.context import _LAUNCH_DONATES, _launch_exe
    return chunk.tiling, aot_compile(
        _launch_exe(chunk.written), (state, t0, base),
        donate_argnums=_LAUNCH_DONATES).fn


def written_onto_what_was_donated(memory) -> bool:
    """Every output takes the memory of a donated argument (the outputs
    are those and a table of pointers), and XLA holds nothing of its
    own beside them: the launch allocates nothing."""
    return 0 <= memory.output_size_in_bytes \
        - memory.alias_size_in_bytes < 4096 \
        and memory.temp_size_in_bytes < 64 * MIB


@pytest.mark.slow   # the 16x16 kernel's Mosaic compile alone is ~50 s here;
# its plan (blocks, tiles, modelled need) is held in tier-1 by
# test_compiled_plans.py::test_the_ssg_cells_plan_on_a_v5e
def test_mosaic_takes_the_ssg_r4_kernel_at_the_cells_size(one_chip):
    """K=1, two stages, the input DMA pipeline on: blocks 16x16, 80.5
    MiB of tiles, 90.4 of 128 MiB by the class's ``vmem_live`` row.  A
    planner change that makes the cell's plan one Mosaic refuses fails
    here, not on the chip."""
    cfg = cell_config("ssg-r4-1chip")
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == "yt_ssg_r8_k1" and not tiling["interpret"]
    assert tiling["stages"] == 2 and tiling["pipeline_dmas"]
    assert tiling["scoped_need_bytes"] <= 128 * MIB
    # PR 45: twelve input DMAs a grid step, each into a window of its
    # buffer (``lambda_`` 16 x 16 of 32 x 32), six slots with none
    assert len(tiling["fetch_windows"]) == 12 \
        and len(tiling["fetch_skipped"]) == 6
    assert tiling["fetch_windows"]["lambda_/0"] == {"x": [8, 24],
                                                    "y": [8, 24]}
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_ssg_r8_k1")
    memory = compiled.memory_analysis()
    # 18 padded arrays in and the 9 given-up slots, donated; out, the
    # 9 the kernel writes onto those
    # (three velocities, the newer slot of six stresses) and no other:
    # no array is copied from an input to an output (a ``copy`` of
    # three dimensions or more), and the kernel itself leaves XLA
    # nothing to hold
    n, m, z = cfg["domain"]
    assert memory.argument_size_in_bytes >= 18 * 4 * n * m * z
    assert 9 * 4 * n * m * z <= memory.output_size_in_bytes \
        < 0.55 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert written_onto_what_was_donated(memory)


def test_mosaic_takes_the_tti_r4_kernel_at_the_cells_size(
        one_chip, monkeypatch):
    """K=1, one stage, two scratch vars in-tile and the four trig ones
    hoisted (PR 49: read-only arrays filled once, inputs of the
    kernel), the plan the program gives the ``tti-r4-1chip`` cell by
    default since PR 35: blocks 16x16 with both DMA pipelines, 76.0 MiB
    of tiles, 100.0 of 128 MiB by the class's ``vmem_live`` row (4.8
    result tiles).  A planner change that makes the cell's plan one
    Mosaic refuses (32x16 with the input pipeline: 'Used 134.80M of
    128.00M') fails here, not on the chip.  What Mosaic holds of the
    strip kernel on top of its tiles is under a MiB: it takes the plan
    under a limit of 77 MiB too (no 'Used X of 77.00M'; at 32 it
    refuses the first tile, 'Scoped allocation with size 71.00M and
    limit 32.00M'), so the row errs to the safe side here.

    Mosaic's bundles a grid step for the described v5e (read by hand
    from ``*-yt_tti_r8_k1.1-*-final_bundles.txt``, the verify skill's
    recipe: static total + sum of (trips - 1) x loop length, the trips
    from each loop's exit test; PR 49): **58 326** with the trig
    hoisted (two walks: ``gu``/``gv`` 6 strips x 5 953, ``u``/``v`` 4 x
    5 404) against **80 038** with it in-tile on the same tree (three
    walks: trig 6 x 5 284, ``gu``/``gv`` 6 x 4 664, ``u``/``v`` 4 x
    4 849; PR 44 read 82 677 of its own kernel, before PR 45's fetch
    windows).  The trig walk goes whole; each walk left loads four
    ``ti`` windows 4 rows off the register tile."""
    cfg = cell_config("tti-r4-1chip")
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == "yt_tti_r8_k1" and not tiling["interpret"]
    assert tiling["block"] == {"x": 16, "y": 16} and tiling["stages"] == 1
    assert tiling["hoisted"] == ["ti0", "ti1", "ti2", "ti3"]
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    assert tiling["tile_bytes"] == 79691776
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert tiling["vinstr_est"] <= 100_000
    # PR 45: six of the twelve input DMAs land in the block's 16 x 16
    # of their 32 x 32 buffers, the four hoisted arrays' in 24 x 32
    assert tiling["fetch_overhead"] == 1.25
    assert tiling["fetch_windows"]["u/0"] == {"x": [8, 24], "y": [8, 24]}
    assert tiling["fetch_windows"]["ti0/0"] == {"x": [4, 28],
                                                "y": [0, 32]}
    assert not {s for s in tiling["fetch_windows"]
                if s.startswith(("theta", "phi"))}
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_tti_r8_k1")
    memory = compiled.memory_analysis()
    # 12 padded arrays in (two wavefields in rings of two, four
    # read-only and the four hoisted; theta and phi are no arguments)
    # and the 2 given-up slots, donated; out, the 2 the kernel writes
    # onto those and no other: no array is copied from an input to an
    # output
    n, m, z = cfg["domain"]
    arrays = 4 * 4 * (528 * 560 * 512 + 536 * 576 * 640 + 544 * 576 * 640)
    slots = 2 * 4 * 544 * 576 * 640
    assert arrays + slots <= memory.argument_size_in_bytes \
        < arrays + slots + 4096
    assert 2 * 4 * n * m * z <= slots <= memory.output_size_in_bytes \
        < 0.3 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert written_onto_what_was_donated(memory)
    # the same plan under a limit a MiB over its tiles
    from yask_tpu.ops import pallas_stencil
    monkeypatch.setattr(pallas_stencil, "vmem_limit_bytes",
                        lambda _budget: 77 * MIB)
    compile_cell_kernel(cfg, one_chip)


def test_mosaic_takes_the_overthrust_kernel_at_the_cells_size(one_chip):
    """K=2, one stage, the flagship's kernel with a fourth array, at
    801 x 801 x 187, the plan the program gives it since PR 42: blocks
    62 x 24 under the 1-D y skew, both DMA pipelines, tiles of 94 x 48
    x 256 (55.1 MiB together as the strip kernel declares them, PR 51:
    ``pressure`` is written into the slot it evicts, so no result tile
    is among them; 59.5 counted with one).  No block divides its extent: x is
    covered by 13 blocks of 62, the last 5 rows over the edge and the
    arrays padded for them, y by 34 of 24 of which the last hangs 15
    rows (and the skew's 8) over it, and the minor dim's 187 + 16 rows
    ride 256 lanes.  What interpret mode cannot see of such a shape --
    a DMA window off the (8, 128) tiling at the ragged edge, or past
    an allocation -- Mosaic refuses here, not on the chip (~45 s here,
    24 on the chip's host: in tier-1)."""
    cfg = cell_config("overthrust-sponge-1chip")
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == "yt_iso3dfd_sponge_r8_k2"
    assert not tiling["interpret"]
    assert tiling["block"] == {"x": 62, "y": 24}
    assert tiling["grid"] == [13, 34] and tiling["skew_dims"] == ["y"]
    assert tiling["overshoot"] == {"x": 5, "y": 15}
    assert not [r for r in tiling["reasons"]
                if r["code"] == "block_fitted"]
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    assert tiling["tile_bytes"] == 57753600
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert tiling["vinstr_est"] <= 100_000
    # PR 45: x takes the window (rows 8..86 of the 94), also where the
    # last tile hangs over the ragged edge; the skewed y rides whole
    assert tiling["fetch_windows"]["vel/0"] == {"x": [8, 86], "y": [0, 48]}
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_iso3dfd_sponge_r8_k2")
    memory = compiled.memory_analysis()
    # four padded arrays in (pressure in a ring of two, vel, sponge:
    # 2.82 GiB, the minor dim padded from 187 to 256) and the two
    # given-up slots of pressure, donated; out, the two slots the fused
    # pair writes onto those and no other: no array is copied from an
    # input to an output
    n, m, z = cfg["domain"]
    assert z == 187
    assert memory.argument_size_in_bytes \
        >= 4 * 4 * (n + 32 + 5) * (m + 32) * 256
    assert 2 * 4 * (849 + 5) * 888 * 256 <= memory.output_size_in_bytes \
        < 0.52 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert written_onto_what_was_donated(memory)


def test_mosaic_takes_the_himeno_kernel_at_the_cells_size(one_chip):
    """K=4, one stage, 256 x 256 x 512 (PR 50): the kernel with the
    most operands of any one-chip cell -- ``p`` and the twelve arrays
    Himeno's ``jacobi`` reads at the point, thirteen input DMAs a grid
    step (``p``'s write target has none) into windows of 22 x 32 of the
    24 x 32 buffers, blocks 16 x 16 with both DMA pipelines, 52.9 MiB
    of tiles.  Out, both slots of ``p``: a group of four sweeps leaves
    its two newest levels (the ring is two deep), of which the next
    group reads one.  Mosaic takes it in ~8 s here (K=2 in 2, K=1 in
    under 1)."""
    cfg = cell_config("himeno-l-1chip")
    assert (cfg["stencil"], cfg["domain"]) == ("himeno", [256, 256, 512])
    cfg = {**cfg, "wf_steps": 4}
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == "yt_himeno_r1_k4"
    assert not tiling["interpret"] and tiling["eval"] == "strip"
    assert tiling["block"] == {"x": 16, "y": 16}
    assert tiling["grid"] == [16, 16] and tiling["stages"] == 1
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    assert tiling["tile_bytes"] == 55443456
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert len(tiling["fetch_windows"]) == 13
    assert tiling["fetch_skipped"] == ["p/0"]
    # tile coordinates: y's slab starts 4 rows off the sublane tile
    assert tiling["fetch_windows"]["bnd/0"] == {"x": [1, 23],
                                                "y": [-4, 28]}
    assert (tiling["fetch_bytes_per_step"],
            tiling["write_bytes_per_step"]) == (1233125376, 83886080)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_himeno_r1_k4")
    memory = compiled.memory_analysis()
    # thirteen vars in fourteen padded arrays (2.64 GB: ``p``'s ring of
    # two on 640 lanes, twelve arrays on 512) and the two given-up
    # slots of ``p``, donated; out, the two slots of ``p`` written onto
    # those and no other: no array is copied from an input to an
    # output, and the kernel leaves XLA nothing to hold
    arrays = 4 * (2 * 266 * 336 * 640 + 12 * 264 * 336 * 512)
    assert arrays == 2637594624
    slots = 2 * 4 * 266 * 336 * 640
    assert arrays + slots <= memory.argument_size_in_bytes \
        < arrays + slots + 4096
    assert slots <= memory.output_size_in_bytes < slots + 4096
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert written_onto_what_was_donated(memory)


def test_mosaic_takes_the_lbm_kernel_at_the_cells_size(
        one_chip, monkeypatch):
    """K=1, one stage, 256 x 256 x 512 (PR 54): the kernel with the
    most operands and the most outputs of any cell -- nineteen
    populations and two masks in (twenty-one input DMAs a grid step:
    the eighteen moving populations' write targets have none), all
    nineteen populations out, a division in the tile -- at the plan the
    program gives it since PR 55: blocks 8 x 32 with the input
    pipeline, 83.4 MiB of declared buffers that the class's row prices
    at 98.6 (0.75 result tiles of nineteen on top).  Mosaic takes it in
    ~3 s here under a scoped limit just over that price, and its own
    total is **92.51 MiB** ('Scoped allocation with size 92.51M' under
    92): the buffers and 9.1 MiB, 0.45 result tiles.  Until PR 55 the
    plan was 4 x 8 (42.9 MiB of tiles by a count with a result tile a
    written var, priced at 87.6 by a row of 7.4 result tiles read off
    the whole-tile kernel; Mosaic's own total 27.86)."""
    import yask_tpu.ops.pallas_stencil as ps
    cfg = cell_config("lbm-d3q19-ldc-1chip")
    assert (cfg["stencil"], cfg["domain"], cfg["wf_steps"]) \
        == ("lbm_d3q19", [256, 256, 512], 1)
    monkeypatch.setattr(ps, "vmem_limit_bytes", lambda budget: 99 * MIB)
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    monkeypatch.undo()
    assert tiling["kernel"] == "yt_lbm_d3q19_r1_k1"
    assert not tiling["interpret"] and tiling["eval"] == "strip"
    assert tiling["block"] == {"x": 8, "y": 32}
    assert tiling["grid"] == [32, 8] and tiling["stages"] == 1
    assert tiling["pipeline_dmas"] and not tiling["pipeline_out"]
    assert tiling["budget"] == 112 * MIB
    assert tiling["tile_bytes"] == 87490560
    assert tiling["scoped_need_bytes"] == 103342080 <= 99 * MIB
    assert tiling["growth_ended"] == "budget"
    assert len(tiling["fetch_windows"]) == 21
    assert tiling["fetch_skipped"] == sorted(
        f"f{i}/0" for i in range(1, 19))
    assert (tiling["fetch_bytes_per_step"],
            tiling["write_bytes_per_step"]) == (3724541952, 2885681152)
    assert (tiling["ops_per_point"], tiling["dag_ops_per_point"]) \
        == (6599, 280)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_lbm_d3q19_r1_k1")
    memory = compiled.memory_analysis()
    # twenty-one vars in thirty-nine padded arrays (7.83 GB: f0 a ring
    # of one, eighteen rings of two, two masks) and a given-up slot of
    # every population, donated: 11.65 GB, what the device holds through
    # a loop of launches; out, a new slot of every population written
    # onto those and no other: no array is copied from an input to an
    # output, and the kernel leaves XLA nothing to hold
    arrays = 7826767872
    slots = 4 * 336 * (3 * 258 * 512 + 6 * 259 * 512 + 6 * 258 * 640
                       + 4 * 259 * 640)
    assert slots == 3824615424
    assert arrays + slots <= memory.argument_size_in_bytes \
        < arrays + slots + 4096
    assert slots <= memory.output_size_in_bytes < slots + 4096
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert written_onto_what_was_donated(memory)
    # Mosaic's own count of what the kernel holds, read by giving it
    # less
    assert scoped_total(monkeypatch,
                        lambda: compile_cell_kernel(cfg, one_chip),
                        under=92) == 92.51


def scoped_total(monkeypatch, compile_it, under):
    """Mosaic's own total for a kernel, in MiB as its refusal says it:
    compiled under a scoped limit of ``under`` MiB, just below the
    total, it is refused with 'Scoped allocation with size X'."""
    import yask_tpu.ops.pallas_stencil as ps
    monkeypatch.setattr(ps, "vmem_limit_bytes",
                        lambda budget: int(under * MIB))
    with pytest.raises(Exception) as refusal:
        compile_it()
    monkeypatch.undo()
    return float(re.search(r"Scoped allocation with size ([0-9.]+)M",
                           str(refusal.value)).group(1))


@pytest.mark.parametrize("block,budget,pipes,tiles,total", [
    # the four arms of ISSUE 55, forced as ``-b_x -b_y`` would
    ((8, 8), 112, (True, True), 61.88, 47.45),
    ((8, 16), 112, (True, True), 82.50, 65.93),
    ((16, 8), 112, (True, False), 75.09, 85.78),
    ((16, 16), 112, (False, False), 50.62, 60.29),
    # ... and what ``-vmem_mb 64`` plans: 8 x 16 without the staging
    (None, 64, (True, False), 55.62, 65.30),
])
def test_mosaics_own_total_for_the_lbm_candidates(
        one_chip, monkeypatch, block, budget, pipes, tiles, total):
    """The (K=1, one stage) class is priced since PR 55 by the row read
    off the strip kernel (``VmemLive.declared``): the buffers the
    kernel declares and 0.75 result tiles.  Mosaic's own total for each
    candidate of the lbm cell, read by giving it a little less
    (``tests/test_vmem_model.py DECLARED_K1`` holds the row to them):
    with the output staging on it is UNDER the declared buffers, without
    it 9.7-10.7 MiB over, 0.40-0.72 result tiles of nineteen."""
    cfg = cell_config("lbm-d3q19-ldc-1chip")
    said = scoped_total(
        monkeypatch,
        lambda: compile_cell_kernel(cfg, one_chip, block, budget),
        under=total - 1)
    assert said == total
    tiling, _compiled = compile_cell_kernel(cfg, one_chip, block, budget)
    assert (tiling["pipeline_dmas"], tiling["pipeline_out"]) == pipes
    assert round(tiling["tile_bytes"] / MIB, 2) == tiles
    assert total <= tiling["scoped_need_bytes"] / MIB \
        <= 0.9 * 128
    if block is None:
        assert tiling["block"] == {"x": 8, "y": 16}


@pytest.mark.parametrize("stencil,radius,dom,kernel,block,tiles,total", [
    ("iso3dfd", 8, [640, 640, 640], "yt_iso3dfd_r8_k1", (64, 32),
     86.25, 90.75),
    ("himeno", None, [256, 256, 512], "yt_himeno_r1_k1", (16, 64),
     88.59, 97.38),
])
def test_the_one_written_var_kernels_of_the_k1_class(
        one_chip, monkeypatch, stencil, radius, dom, kernel, block,
        tiles, total):
    """Outside the benchmark, the flagship and himeno at ``-wf_steps
    1`` change class with the lbm cell (PR 55): priced as declared,
    under 112 MiB, both pipelines on.  Mosaic takes both plans under
    the limit the build asks for.  The flagship's total is the
    declared buffers and 4.5 MiB, as at K=2, within the row's 0.75
    result tiles; himeno's, whose strip is 8 lead rows by the whole 64
    sublane rows, 256 registers, is 8.8 MiB over its buffers, 2.5
    result tiles of its one written var: over the row's price (91.2
    MiB) by 6.2, and inside the 12.8 the room leaves free."""
    cfg = {"stencil": stencil, "radius": radius, "domain": dom,
           "wf_steps": 1, "mode": "pallas"}
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == kernel and tiling["eval"] == "strip"
    assert tiling["block"] == dict(zip("xy", block))
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    assert tiling["budget"] == 112 * MIB
    assert round(tiling["tile_bytes"] / MIB, 2) == tiles
    assert "tpu_custom_call" in compiled.as_text()
    said = scoped_total(monkeypatch,
                        lambda: compile_cell_kernel(cfg, one_chip),
                        under=total - 1)
    assert said == total
    need = tiling["scoped_need_bytes"] / MIB
    assert need == pytest.approx(
        tiles + 0.75 * tiling["result_bytes"] / MIB, abs=0.01)
    assert (total <= need) == (stencil == "iso3dfd")
    assert total <= need + 6.2 <= 0.9 * 128 + 6.2 < 128


# ---- the strip evaluator (PR 44): every cell's kernel, and what Mosaic
# ---- holds for the flagship's beyond its buffers


def shard_kernels(cfg):
    """``[(arm, chunk)]`` of one shard of a four-chip cell on a v5e:
    the whole-shard chunk and, where the exchange overlaps, the core
    and each shell -- built as ``_prep_shard_pallas`` builds them (the
    per-shard program with its radius x K ghost pads; the skew only
    along dims the mesh does not split)."""
    from yask_tpu import yk_factory
    from yask_tpu.backend import get_capability
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    from yask_tpu.parallel.shard_step import overlap_decision
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=cfg["stencil"],
                           radius=cfg["radius"])
    dom, k = cfg["domain"], int(cfg["wf_steps"])
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode {cfg['mode']} "
        f"-wf_steps {k}")
    for d, r in zip(("x", "y", "z"), cfg["ranks"]):
        ctx.set_num_ranks(d, r)
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    ctx._program = ctx._plan_geometry()     # what overlap_decision reads
    opts, ana = ctx._opts, ctx._ana
    dims = ana.domain_dims
    rad = ana.fused_step_radius()
    local = ctx._csol.plan(
        opts.rank_domain_sizes, global_sizes=opts.global_domain_sizes,
        extra_pad={d: (rad.get(d, 0) * k,) * 2 for d in dims})
    budget = get_capability("tpu:v5e").plan_budget_bytes(
        k, len(ana.stages), len(ana.tile_scratch))
    args = dict(fuse_steps=k, interpret=False, distributed=True,
                vmem_budget=budget, vinstr_cap=opts.max_tile_vinstr,
                unsharded_dims=tuple(d for d in dims[:-1]
                                     if opts.num_ranks[d] == 1))
    # the whole-shard chunk takes a rotating ring's new level in the
    # slot it evicts (PR 48)
    arms = [("", build_pallas_chunk(local, reuse_evicted=True, **args)[0])]
    engage, core, shells, _why = overlap_decision(ctx, k, local_prog=local)
    if engage:
        arms.append(("core", build_pallas_chunk(
            local, region=core, arm="core", **args)[0]))
        # the low shell of every split dim, written onto the core's
        # output (the high one is the same kernel at another offset)
        arms += [("shell", build_pallas_chunk(
            local, region={d: (a, b)}, arm="shell", onto=True,
            **args)[0]) for d, a, b in shells[::2]]
    return local, arms


def compile_chunk(prog, chunk, one_chip, distributed=False, onto=False):
    import jax
    import jax.numpy as jnp
    from yask_tpu.cache import aot_compile
    from yask_tpu.ops.pallas_stencil import program_state_slots
    state = {
        name: [jax.ShapeDtypeStruct(tuple(g.shape), prog.dtype,
                                    sharding=one_chip)
               for _ in program_state_slots(prog, name)]
        for name, g in prog.geoms.items() if not g.is_scratch}
    args = (state, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    if distributed:
        args += (jax.ShapeDtypeStruct(
            (len(prog.ana.domain_dims),), jnp.int32, sharding=one_chip),)
    if onto:
        # what another arm's ``written`` returned: the newest
        # min(K, slots) slots of every written var
        k = chunk.tiling["fuse_steps"]
        args += ({name: ring[-k:] for name, ring in state.items()
                  if prog.geoms[name].is_written},)
    return aot_compile(chunk.written, args).fn


@pytest.mark.parametrize("cell,kernel,strip", [
    ("iso3dfd-r8-1chip", "yt_iso3dfd_r8_k2", [4, 32]),
    ("iso3dfd-r8-768-1chip", "yt_iso3dfd_r8_k2", [4, 24]),
    ("cube-r1-1chip", "yt_cube_r1_k4", [8, 24]),
])
def test_mosaic_takes_the_strip_kernel_of_the_other_one_chip_cells(
        one_chip, cell, kernel, strip):
    """The three one-chip cells no test above compiles (their whole-tile
    kernels took Mosaic 70-120 s here; a strip's body takes 6-20), at
    the plan the program gives each by default (the two iso3dfd ones
    32 x 32 and 32 x 24 with both pipelines since PR 51; their strips
    are what they were at 16 x 32 and 16 x 24)."""
    tiling, compiled = compile_cell_kernel(cell_config(cell), one_chip)
    assert tiling["kernel"] == kernel and not tiling["interpret"]
    assert tiling["eval"] == "strip" and tiling["strip"] == strip
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    # PR 45: a sub-window destination (``vel``, a radius narrower in x)
    # or a slot with no DMA at all (cube's evicted one)
    assert tiling["fetch_skipped"] == (["A/0"] if "cube" in cell else [])
    assert "cube" in cell or tiling["fetch_windows"]["vel/0"]["x"][0] == 8
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", ["iso3dfd-r8-4chip", "awp-abc-r2-4chip",
                                  "iso3dfd-r8-4chip-2x2"])
def test_mosaic_takes_a_four_chip_cells_shard_kernels(one_chip, cell):
    """One shard's chunk of each four-chip cell, and the core and a
    shell a split dim where the exchange overlaps (iso3dfd: one at x/4,
    all three arms under the y skew since PR 51; an x and a y shell on
    the 2x2 grid, whose output windows start at sublane offsets; awp at
    K=1 has no split), compiled for one described chip: the arms the strip evaluator shares with the
    one-chip kernels, distributed offsets, region restriction and the
    shells' aliased outputs included."""
    prog, arms = shard_kernels(cell_config(cell))
    assert [a for a, _c in arms] == {
        "iso3dfd-r8-4chip": ["", "core", "shell"],
        "iso3dfd-r8-4chip-2x2": ["", "core", "shell", "shell"],
        "awp-abc-r2-4chip": [""]}[cell]
    for arm, chunk in arms:
        assert chunk.tiling["eval"] == "strip"
        assert chunk.tiling["kernel"].endswith(arm)
        # PR 45: sub-window destinations in every arm; awp's six
        # evicted stress slots have no DMA
        assert len(chunk.tiling["fetch_skipped"]) == (
            0 if cell.startswith("iso3dfd") else 6)
        # PR 51, iso3dfd's (K <= 2, one stage) class priced by what the
        # strip kernel declares: x/4 runs 16 x 24 skewed in y in every
        # arm (tiles of 48 x 48), the 2x2 grid, where the mesh splits y,
        # 16 x 16 uniform (tiles of 48 x 48); both ran 16 x 8 on tiles
        # of 48 x 40 (8.0).  awp: tiles of 16 x 24 for 8 x 8
        assert chunk.tiling["fetch_overhead"] == {
            "iso3dfd-r8-4chip": 3.6667, "iso3dfd-r8-4chip-2x2": 4.6667,
            "awp-abc-r2-4chip": 2.4632}[cell]
        assert chunk.tiling["block"] == {
            "iso3dfd-r8-4chip": {"x": 16, "y": 24},
            "iso3dfd-r8-4chip-2x2": {"x": 16, "y": 16},
            "awp-abc-r2-4chip": {"x": 8, "y": 8}}[cell]
        assert chunk.tiling["skew_dims"] == (
            ["y"] if cell == "iso3dfd-r8-4chip" else [])
        assert "skew_fallback" not in [
            r["code"] for r in chunk.tiling["reasons"]]
        text = compile_chunk(prog, chunk, one_chip, distributed=True,
                             onto=arm == "shell").as_text()
        assert "tpu_custom_call" in text
        # a shell lands in the arrays it is handed: no output of its
        # own; awp's chunk writes each stress's new level onto the slot
        # the ring gives up, which no DMA of it reads (PR 48); iso3dfd
        # at K=2 reads both slots of its ring and re-uses none
        reused = chunk.tiling["reused"]
        assert reused == (chunk.tiling["fetch_skipped"] if not arm else [])
        assert ("output_to_operand_aliasing" in text) \
            == (arm == "shell" or bool(reused))


def test_the_flagships_strip_kernel_holds_its_buffers_and_little_else(
        one_chip, monkeypatch):
    """Mosaic's own count of the flagship kernel's scoped VMEM, read by
    giving it less than it needs: the reading the (K <= 2, one stage)
    row of the capability table rests on since PR 51.  The plan is 32 x
    32 with both pipelines, and the kernel declares 106.0 MiB of
    buffers (two pressure slots and ``vel`` double-buffered, the skew's
    carry, the output staging; no result tile: ``pressure`` is written
    into the slot it evicts).  Mosaic holds those and 4.52 MiB, the
    spills of a strip of 80 registers, whatever the block (16 x 32:
    48 MiB declared and under 8 more, PR 44).  The whole-tile kernel,
    gone with PR 44, took 92.13 MiB for 48 declared: 5.6 result tiles
    of live values, the row's 5.7 until this PR."""
    import yask_tpu.ops.pallas_stencil as ps
    monkeypatch.setattr(ps, "vmem_limit_bytes", lambda budget: 111 * MIB)
    tiling, compiled = compile_cell_kernel(
        cell_config("iso3dfd-r8-1chip"), one_chip)
    assert tiling["eval"] == "strip"
    assert tiling["block"] == {"x": 32, "y": 32}
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    # the plan counts what the kernel declares, and the model 0.75
    # result tiles on top: 113.9 MiB for Mosaic's 110.52
    assert tiling["tile_bytes"] == 106 * MIB
    assert tiling["scoped_need_bytes"] == tiling["tile_bytes"] \
        + int(0.75 * tiling["result_bytes"]) <= int(0.9 * 128 * MIB)
    assert "tpu_custom_call" in compiled.as_text()
    # ... which compiled under 111 MiB, and does not under 110
    monkeypatch.setattr(ps, "vmem_limit_bytes", lambda budget: 110 * MIB)
    with pytest.raises(Exception,
                       match="Scoped allocation with size 110.5"):
        compile_cell_kernel(cell_config("iso3dfd-r8-1chip"), one_chip)


def test_mosaic_takes_the_lbm_four_chip_cells_shard_kernel(one_chip):
    """PR 56: one shard (128 x 512 x 512, the one-chip cell's points) of
    ``lbm-d3q19-ldc-4chip``: the one-chip cell's strip kernel with
    distributed offsets and the eighteen rings' new levels written onto
    the slots they evict; K=1 has no core/shell split.  Its DMAs fetch
    one-sided windows: the ghost rows a round no longer refreshes (a
    population's far side, the rest population's and the masks' both)
    lie outside every window."""
    prog, arms = shard_kernels(cell_config("lbm-d3q19-ldc-4chip"))
    (arm, chunk), = arms
    tiling = chunk.tiling
    assert arm == "" and tiling["eval"] == "strip"
    assert tiling["kernel"] == "yt_lbm_d3q19_r1_k1"
    assert tiling["block"] == {"x": 8, "y": 32}
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert tiling["reused"] == tiling["fetch_skipped"] \
        == sorted(f"f{i}/0" for i in range(1, 19))
    # f3 moves towards +x: read at x - 1 alone, so its window starts a
    # row before the block and ends with it; f0 is read at the point
    wins = tiling["fetch_windows"]
    assert wins["f3/1"]["x"][1] - wins["f3/1"]["x"][0] == 8 + 1
    assert wins["f0/0"]["x"][1] - wins["f0/0"]["x"][0] == 8
    text = compile_chunk(prog, chunk, one_chip, distributed=True).as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing" in text
