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


def compile_cell_kernel(cfg, one_chip):
    """The executable ``_get_pallas_chunk`` would hold for the cell on
    a v5e (planner defaults; ``chunk.written``: the slots the kernel
    writes), lowered on shapes alone and compiled."""
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
    budget = get_capability("tpu:v5e").plan_budget_bytes(
        k, len(ctx._ana.stages), len(ctx._ana.scratch_write_halo))
    chunk, _tb = build_pallas_chunk(
        prog, fuse_steps=k, interpret=False, vmem_budget=budget,
        vinstr_cap=ctx._opts.max_tile_vinstr,
        max_skew_dims=ctx._opts.skew_dims_max, trapezoid=False)
    state = {
        name: [jax.ShapeDtypeStruct(tuple(g.shape), prog.dtype,
                                    sharding=one_chip)
               for _ in program_state_slots(prog, name)]
        for name, g in prog.geoms.items() if not g.is_scratch}
    t0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    # the program's own compile chokepoint, unkeyed: nothing persisted
    from yask_tpu.cache import aot_compile
    return chunk.tiling, aot_compile(chunk.written, (state, t0)).fn


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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_ssg_r8_k1")
    memory = compiled.memory_analysis()
    # 18 padded arrays in, none donated; out, the 9 the kernel writes
    # (three velocities, the newer slot of six stresses) and no other:
    # no array is copied from an input to an output (a ``copy`` of
    # three dimensions or more), and the kernel itself leaves XLA
    # nothing to hold
    n, m, z = cfg["domain"]
    assert memory.argument_size_in_bytes >= 18 * 4 * n * m * z
    assert 9 * 4 * n * m * z <= memory.output_size_in_bytes \
        < 0.55 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert memory.alias_size_in_bytes == 0
    assert memory.temp_size_in_bytes < 64 * MIB


def test_mosaic_takes_the_tti_r4_kernel_at_the_cells_size(one_chip):
    """K=1, one stage, six scratch vars in-tile, the plan the program
    gives the ``tti-r4-1chip`` cell by default since PR 35: blocks
    16x16 with both DMA pipelines, 76.0 MiB of tiles, 100.0 of 128 MiB
    by the class's ``vmem_live`` row (4.8 result tiles).  A planner
    change that makes the cell's plan one Mosaic refuses (32x16 with
    the input pipeline: 'Used 134.80M of 128.00M') fails here, not on
    the chip."""
    cfg = cell_config("tti-r4-1chip")
    tiling, compiled = compile_cell_kernel(cfg, one_chip)
    assert tiling["kernel"] == "yt_tti_r8_k1" and not tiling["interpret"]
    assert tiling["block"] == {"x": 16, "y": 16} and tiling["stages"] == 1
    assert tiling["pipeline_dmas"] and tiling["pipeline_out"]
    assert tiling["tile_bytes"] == 79691776
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert tiling["vinstr_est"] <= 100_000
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_tti_r8_k1")
    memory = compiled.memory_analysis()
    # 10 padded arrays in (two wavefields in rings of two, six
    # read-only), none donated; out, the 2 the kernel writes and no
    # other: no array is copied from an input to an output
    n, m, z = cfg["domain"]
    assert memory.argument_size_in_bytes >= 10 * 4 * n * m * z
    assert 2 * 4 * n * m * z <= memory.output_size_in_bytes \
        < 0.3 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert memory.alias_size_in_bytes == 0


def test_mosaic_takes_the_overthrust_kernel_at_the_cells_size(one_chip):
    """K=2, one stage, the flagship's kernel with a fourth array, at
    801 x 801 x 187, the plan the program gives it since PR 42: blocks
    62 x 24 under the 1-D y skew, both DMA pipelines, tiles of 94 x 48
    x 256 (59.5 MiB together).  No block divides its extent: x is
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
    assert tiling["tile_bytes"] == 62373888
    assert tiling["scoped_need_bytes"] <= int(0.9 * 128 * MIB)
    assert tiling["vinstr_est"] <= 100_000
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.split(None, 2)[1].startswith("jit_yt_iso3dfd_sponge_r8_k2")
    memory = compiled.memory_analysis()
    # four padded arrays in (pressure in a ring of two, vel, sponge:
    # 2.82 GiB, the minor dim padded from 187 to 256), none donated; out,
    # the two slots of pressure the fused pair writes and no other: no
    # array is copied from an input to an output
    n, m, z = cfg["domain"]
    assert z == 187
    assert memory.argument_size_in_bytes \
        >= 4 * 4 * (n + 32 + 5) * (m + 32) * 256
    assert 2 * 4 * (849 + 5) * 888 * 256 <= memory.output_size_in_bytes \
        < 0.52 * memory.argument_size_in_bytes
    assert not re.findall(r"= f32\[\d+,\d+,[\d,]+\]\S* copy\(", text)
    assert memory.alias_size_in_bytes == 0
