"""Config-matrix sweep: block sizes × wf_steps × modes × element bytes.

The runnable analog of the reference's Makefile validation matrix
(``/root/reference/src/kernel/Makefile:1033-1079``): ~50 stencil×config
combos with varied folds/block sizes/temporal tiling plus MPI arg-sets
(``test_args0-4``, incl. ``-min_exterior 0``).  Here every case runs a
short 2-step trial (the reference's ``-trial_steps 2`` validation
stance) and must agree with a jit twin — and the jit twin itself with
the numpy oracle — on the 8-device virtual CPU mesh.

The ``overlap False`` rows are the ``-min_exterior 0`` analog: the
interior/exterior overlap split is disabled so the exchange runs on the
sequential path, exercising the other exchange schedule.
"""

import pytest

from yask_tpu import yk_factory


@pytest.fixture(scope="module")
def env():
    return yk_factory().new_env()


def _build(env, name, radius, mode, wf=1, blk=None, eb=4, ranks=(),
           overlap=True, ovx=None, coalesce=None, comm_order=None):
    from yask_tpu.runtime.init_utils import init_solution_vars
    from yask_tpu.compiler.solution_base import create_solution
    fac = yk_factory()
    if eb != 4:
        sb = create_solution(name, radius=radius)
        sb.get_soln().set_element_bytes(eb)
        ctx = fac.new_solution(env, sb)
    else:
        ctx = fac.new_solution(env, stencil=name, radius=radius)
    ctx.apply_command_line_options("-g 24")
    s = ctx.get_settings()
    s.mode = mode
    s.wf_steps = wf
    s.overlap_comms = overlap
    if ovx is not None:
        s.overlap_exchange = ovx
    if coalesce is not None:
        s.coalesce = coalesce
    if comm_order is not None:
        s.comm_order = comm_order
    for d, b in (blk or {}).items():
        ctx.set_block_size(d, b)
    for d, r in ranks:
        ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


_jit_ref_cache = {}


def _check(env, name, radius, mode, wf=1, blk=None, eb=4, ranks=(),
           overlap=True, ovx=None, coalesce=None, comm_order=None):
    eps = (1e-3, 1e-4) if eb == 4 else (3e-2, 3e-2)
    key = (name, radius, eb)
    if key not in _jit_ref_cache:
        ref = _build(env, name, radius, "jit", eb=eb)
        ref.run_solution(0, 1)
        if eb == 4:
            # anchor the jit twin itself to the numpy oracle once
            oracle = _build(env, name, radius, "ref")
            oracle.run_solution(0, 1)
            assert ref.compare_data(oracle, epsilon=eps[0],
                                    abs_epsilon=eps[1]) == 0
        _jit_ref_cache[key] = ref
    ctx = _build(env, name, radius, mode, wf=wf, blk=blk, eb=eb,
                 ranks=ranks, overlap=overlap, ovx=ovx,
                 coalesce=coalesce, comm_order=comm_order)
    ctx.run_solution(0, 1)
    assert ctx.compare_data(_jit_ref_cache[key], epsilon=eps[0],
                            abs_epsilon=eps[1]) == 0


# ---- single-device: modes × wf × blocks × element bytes -----------------

@pytest.mark.parametrize("mode", ["pallas"])
@pytest.mark.parametrize("wf", [1, 2])
@pytest.mark.parametrize("blk", [None, {"x": 8, "y": 8}],
                         ids=["autoblk", "b8"])
@pytest.mark.parametrize("eb", [4, 2], ids=["fp32", "bf16"])
def test_matrix_iso3dfd_pallas(env, mode, wf, blk, eb):
    _check(env, "iso3dfd", 2, mode, wf=wf, blk=blk, eb=eb)


@pytest.mark.parametrize("blk", [None, {"x": 8, "y": 8}, {"x": 12, "y": 4}],
                         ids=["autoblk", "b8", "b12x4"])
def test_matrix_iso3dfd_jit_blocks(env, blk):
    # jit path ignores blocks today; the sweep pins that stance (a
    # future tiled-jit emitter must keep these green)
    _check(env, "iso3dfd", 2, "jit", blk=blk)


@pytest.mark.parametrize("name,radius,wf", [
    ("cube", 1, 2), ("ssg", 1, 2), ("awp", None, 1),
    ("test_scratch_3d", None, 2), ("tti", 1, 1),
])
def test_matrix_families_pallas(env, name, radius, wf):
    _check(env, name, radius, "pallas", wf=wf)


# ---- distributed: modes × wf × mesh × overlap (min_exterior analog) -----

@pytest.mark.parametrize("mode", ["sharded", "shard_map", "shard_pallas"])
@pytest.mark.parametrize("wf", [1, 2])
@pytest.mark.parametrize("ranks", [[("x", 4)], [("x", 2), ("y", 2)]],
                         ids=["x4", "x2y2"])
def test_matrix_iso3dfd_distributed(env, mode, wf, ranks):
    if mode == "sharded" and wf > 1:
        pytest.skip("sharded mode has no temporal fusion")
    _check(env, "iso3dfd", 2, mode, wf=wf, ranks=ranks)


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlap", "min_ext0"])
@pytest.mark.parametrize("name,radius", [("iso3dfd", 2), ("ssg", 1)])
def test_matrix_overlap_split(env, overlap, name, radius):
    _check(env, name, radius, "shard_map", ranks=[("x", 2), ("y", 2)],
           overlap=overlap)


@pytest.mark.parametrize("eb", [4, 2], ids=["fp32", "bf16"])
def test_matrix_distributed_dtypes(env, eb):
    _check(env, "iso3dfd", 2, "shard_map", eb=eb, ranks=[("x", 4)])


@pytest.mark.parametrize("name,radius,wf", [("iso3dfd", 2, 2),
                                            ("cube", 1, 4)])
def test_matrix_uniform_fused_groups(env, name, radius, wf):
    # K-groups on the planner's defaults where the skew's profit gate
    # keeps the stream dim uniform (misaligned radii 2 and 1): bit-exact
    # against the jit twin
    _check(env, name, radius, "pallas", wf=wf)


@pytest.mark.parametrize("coalesce", ["on", "off"])
@pytest.mark.parametrize("ranks",
                         [[("x", 4)], [("x", 2), ("y", 2)],
                          [("x", 2), ("y", 2), ("z", 2)]],
                         ids=["x4", "x2y2", "x2y2z2"])
@pytest.mark.parametrize("mode", ["shard_map", "shard_pallas"])
def test_matrix_comm_schedule(env, mode, ranks, coalesce):
    # mesh-shape × coalescing axis: the packed per-(axis,direction)
    # ppermute schedule across 1-D/2-D/3-D meshes.  shard_pallas keeps
    # K=1 here (the minor dim is sharded in the 3-D row); the K>1
    # coalesce arm lives in tests/test_comm_schedule.py
    _check(env, "iso3dfd", 2, mode, wf=1, ranks=ranks,
           coalesce=coalesce)


def test_matrix_comm_order_permutation(env):
    # explicit exchange-order permutation must agree with the oracle
    # like every other row (bit-equality between orders is proved in
    # tests/test_comm_schedule.py)
    _check(env, "iso3dfd", 2, "shard_map",
           ranks=[("x", 2), ("y", 2)], comm_order="y,x")


@pytest.mark.parametrize("ovx", ["on", "off", "auto"])
@pytest.mark.parametrize("name,radius", [("iso3dfd", 2), ("cube", 1)])
def test_matrix_overlap_exchange(env, ovx, name, radius):
    # overlapped halo exchange (core/shell split of the fused K-group)
    # as a matrix axis: x2 ranks on g=24 give lsize 12 ≥ 2·hK, so "on"
    # genuinely splits (the forced arm errors rather than silently
    # comparing serial to serial)
    _check(env, name, radius, "shard_pallas", wf=2, ranks=[("x", 2)],
           ovx=ovx)


@pytest.mark.parametrize("mode,wf", [("jit", 1), ("jit", 2),
                                     ("pallas", 1), ("pallas", 2)])
@pytest.mark.parametrize("radius", [1, 2])
def test_matrix_pipeline_fusion(env, mode, wf, radius):
    # cross-solution pipeline fusion as a matrix axis: the 3-stage RTM
    # chain fused into one program must agree with the host-chained
    # oracle on every mode × wf × radius row (bit-equality per schedule
    # lives in tests/test_pipeline.py; this sweep uses the standard
    # cross-config tolerance like every other matrix row)
    import numpy as np
    from yask_tpu.ops.pipeline import SolutionPipeline, rtm_chain

    def mk(fuse):
        pipe = SolutionPipeline(env, *rtm_chain(radius=radius))
        pipe.apply_command_line_options(
            f"-g 16 -mode {mode} -wf_steps {wf}")
        pipe.prepare(fuse=fuse)
        v = pipe.get_var("fwd", "pressure")
        rng = np.random.RandomState(3)
        arr = (rng.rand(16, 16, 16).astype(np.float32) - 0.5) * 0.1
        for t in range(v.get_first_valid_step_index(),
                       v.get_last_valid_step_index() + 1):
            v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                    [t, 15, 15, 15])
        return pipe

    fused, chained = mk(True), mk(False)
    assert fused.fused and not chained.fused
    fused.run(0, 1)
    chained.run(0, 1)
    assert fused.compare(chained, epsilon=1e-3, abs_epsilon=1e-4) == 0


@pytest.mark.parametrize("push", ["on", "off", "auto"])
@pytest.mark.parametrize("mode,wf", [("jit", 2), ("pallas", 1),
                                     ("pallas", 2)])
def test_matrix_pipeline_push(env, push, mode, wf):
    # push-memory tile-graph fusion as a matrix axis: the PURE rtm
    # chain (pushable image var) with the -push knob swept against the
    # host-chained oracle on every mode × wf row.  Engagement is
    # asserted where the gate must engage (pallas + on/auto) and must
    # NOT (jit, or -push off) — a row that silently runs the wrong DMA
    # partition cannot pass (bit/tolerance equality per schedule lives
    # in tests/test_pipeline.py).
    import numpy as np
    from yask_tpu.ops.pipeline import SolutionPipeline, rtm_chain

    def mk(fuse, push_cli):
        pipe = SolutionPipeline(
            env, *rtm_chain(radius=2, accumulate=False))
        pipe.apply_command_line_options(
            f"-g 16 -mode {mode} -wf_steps {wf} {push_cli}")
        pipe.prepare(fuse=fuse)
        v = pipe.get_var("fwd", "pressure")
        rng = np.random.RandomState(3)
        arr = (rng.rand(16, 16, 16).astype(np.float32) - 0.5) * 0.1
        for t in range(v.get_first_valid_step_index(),
                       v.get_last_valid_step_index() + 1):
            v.set_elements_in_slice(arr, [t, 0, 0, 0],
                                    [t, 15, 15, 15])
        return pipe

    fused = mk(True, f"-push {push}")
    chained = mk(False, "")
    want_push = mode == "pallas" and push in ("on", "auto")
    assert (fused.pushed_vars() == {"img__img"}) == want_push
    fused.run(0, 1)
    chained.run(0, 1)
    assert fused.compare(chained, epsilon=1e-3, abs_epsilon=1e-4) == 0
