"""Communication-pattern scheduling (CommPlan): ordering, coalescing,
corner composition, measured collective rounds, checker rules.

The coalesced schedule packs every buffer's ghost slab for one
(axis, direction) into a single ppermute payload; ppermute only moves
bytes, so the packed schedule must be BIT-identical to the serial
per-buffer one (compare_data at zero tolerance), and axis-order
permutations must be too (either order sources the same diagonal
device's interior corner cells).  Against the jit oracle the shard
modes use the same mixed tolerance as the existing 3-D mesh test —
sharding the minor (lane) dim changes XLA's fusion layout enough for
fp32 contraction noise above the strict default epsilon.
"""

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.runtime.init_utils import init_solution_vars
from yask_tpu.utils.exceptions import YaskException


@pytest.fixture(scope="module")
def env():
    e = yk_factory().new_env()
    if e.get_num_ranks() < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    return e


def build(env, stencil, radius, g, mode, ranks=(), wf=0, opts="",
          steps=3):
    ctx = yk_factory().new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g} -wf_steps {wf} " + opts)
    ctx.get_settings().mode = mode
    for d, n in ranks:
        ctx.set_num_ranks(d, n)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    if steps:
        ctx.run_solution(0, steps - 1)
    return ctx


# ---- plan construction ----------------------------------------------------

def test_plan_fields_reasons_and_key(env):
    ctx = build(env, "ssg", 2, 24, "shard_map",
                ranks=[("x", 2), ("y", 2)], steps=0)
    plan = ctx.comm_plan()
    assert set(plan.order) == {"x", "y"}
    assert plan.mesh_shape == {"x": 2, "y": 2}
    # ssg moves many buffers: coalescing auto-engages and the modeled
    # round count drops to 2 per axis
    assert plan.coalesce is True
    assert plan.rounds == 2 * len(plan.order)
    assert plan.rounds_serial > plan.rounds
    codes = {r["code"] for r in plan.reasons}
    assert {"comm_axis", "comm_order",
            "comm_coalesce_engaged"} <= codes
    assert plan.errors == []
    # per-axis model fields are complete and JSON-clean
    for d in plan.order:
        a = plan.axes[d]
        assert a["kind"] in ("ici", "dcn")
        assert a["items"] > 0 and a["bytes"] > 0 and a["secs"] > 0
    import json
    json.dumps(plan.record())
    # the cache-key suffix bakes in exactly order + coalesce
    assert plan.key() == (",".join(plan.order), True)


def test_plan_explicit_order_and_append(env):
    ctx = build(env, "iso3dfd", 2, 24, "shard_map",
                ranks=[("x", 2), ("y", 2)], opts="-comm_order y",
                steps=0)
    plan = ctx.comm_plan()
    # explicit prefix honored, omitted exchanged axis appended
    assert plan.order[0] == "y" and set(plan.order) == {"x", "y"}
    assert any(r["code"] == "comm_order_appended" for r in plan.reasons)
    assert plan.errors == []


def test_invalid_comm_order_raises_at_run(env):
    ctx = build(env, "iso3dfd", 2, 24, "shard_map", ranks=[("x", 2)],
                opts="-comm_order q", steps=0)
    plan = ctx.comm_plan()
    assert plan.errors
    with pytest.raises(YaskException):
        ctx.run_solution(0, 1)


# ---- bit-equality across schedules ---------------------------------------

def test_coalesce_and_order_bitwise_2d(env):
    base = build(env, "iso3dfd", 2, 24, "shard_map",
                 ranks=[("x", 2), ("y", 2)], opts="-coalesce off")
    coal = build(env, "iso3dfd", 2, 24, "shard_map",
                 ranks=[("x", 2), ("y", 2)], opts="-coalesce on")
    perm = build(env, "iso3dfd", 2, 24, "shard_map",
                 ranks=[("x", 2), ("y", 2)],
                 opts="-coalesce on -comm_order y,x")
    assert coal.compare_data(base, epsilon=0.0, abs_epsilon=0.0) == 0
    assert perm.compare_data(base, epsilon=0.0, abs_epsilon=0.0) == 0
    ref = build(env, "iso3dfd", 2, 24, "jit")
    assert coal.compare_data(ref) == 0


def test_corner_composition_cube(env):
    """Diagonal ghosts as composed axis exchanges: the 27-point cube
    stencil reads corner neighbors, so a 2-D mesh shard needs the
    diagonal device's cells — which arrive because the y slab spans
    x's freshly filled ghosts.  No dedicated diagonal collectives:
    the plan orders {x,y} only, and the packed schedule stays
    bit-identical."""
    ref = build(env, "cube", 2, 16, "jit", steps=2)
    off = build(env, "cube", 2, 16, "shard_map",
                ranks=[("x", 2), ("y", 2)], opts="-coalesce off",
                steps=2)
    on = build(env, "cube", 2, 16, "shard_map",
               ranks=[("x", 2), ("y", 2)], opts="-coalesce on",
               steps=2)
    assert set(on.comm_plan().order) == {"x", "y"}  # no diagonal axis
    assert on.compare_data(off, epsilon=0.0, abs_epsilon=0.0) == 0
    assert on.compare_data(ref) == 0


def test_3d_mesh_sweep(env):
    """3-D virtual-mesh equivalence: shard_map (K=1) and shard_pallas
    (K=1 3-D / K=2 2-D — the minor dim may not shard at K>1) against
    the jit oracle, coalescing on and off, overlap on and off.  The
    minor-sharded cases use the mixed tolerance of the existing 3-D
    mesh test (fp32 layout noise, see module docstring); schedule
    pairs stay bitwise."""
    ref = build(env, "iso3dfd", 2, 16, "jit", steps=3)
    prev = {}
    for coal in ("off", "on"):
        for ov in ("", "-no-overlap_comms"):
            c = build(env, "iso3dfd", 2, 16, "shard_map",
                      ranks=[("x", 2), ("y", 2), ("z", 2)],
                      opts=f"-coalesce {coal} {ov}", steps=3)
            assert c.compare_data(ref, epsilon=1e-3,
                                  abs_epsilon=1e-4) == 0
            if ov in prev:
                assert c.compare_data(prev[ov], epsilon=0.0,
                                      abs_epsilon=0.0) == 0
            prev[ov] = c
    sp = build(env, "iso3dfd", 2, 16, "shard_pallas",
               ranks=[("x", 2), ("y", 2), ("z", 2)], wf=1, steps=3)
    assert sp.compare_data(ref, epsilon=1e-3, abs_epsilon=1e-4) == 0
    spk = build(env, "iso3dfd", 2, 32, "shard_pallas",
                ranks=[("x", 2), ("y", 2)], wf=2, steps=4)
    refk = build(env, "iso3dfd", 2, 32, "jit", steps=4)
    assert spk.compare_data(refk, epsilon=1e-3, abs_epsilon=1e-4) == 0
    # the K-group exchange batched through the plan stays bitwise with
    # the serial schedule
    spk2 = build(env, "iso3dfd", 2, 32, "shard_pallas",
                 ranks=[("x", 2), ("y", 2)], wf=2, steps=4,
                 opts="-coalesce on")
    assert spk2.compare_data(spk, epsilon=0.0, abs_epsilon=0.0) == 0


# ---- measured collective rounds ------------------------------------------

@pytest.mark.parametrize("mode,g,wf,more,n_on", [
    ("shard_map", 24, 0, "", 8),
    ("shard_map", 24, 0, "-no-overlap_comms", 8),
    ("shard_pallas", 32, 2, "", 8)])
def test_the_compiled_schedule_has_fewer_collectives_coalesced(
        env, mode, g, wf, more, n_on):
    """The acceptance criterion: on a 2-D mesh, the schedule that
    compiled issues strictly fewer collectives with coalescing on —
    counted while the program was traced (``_trace_stats.nperm`` around
    the first run), not modeled."""
    from yask_tpu.parallel import shard_step

    def traced(coal):
        ctx = build(env, "iso3dfd", 2, g, mode,
                    ranks=[("x", 2), ("y", 2)], wf=wf,
                    opts=f"-coalesce {coal} {more}", steps=0)
        n0 = shard_step._trace_stats.nperm
        ctx.run_solution(0, 3)
        return shard_step._trace_stats.nperm - n0
    off, on = traced("off"), traced("on")
    assert 0 < on < off
    # the up-front refresh and the later rounds (traced once, in the
    # loop's body) each hit the packed schedule's floor: one collective
    # a mesh axis and direction
    assert on == n_on


def test_plan_record(env):
    ctx = build(env, "iso3dfd", 2, 24, "shard_map",
                ranks=[("x", 2), ("y", 2)], steps=4)
    f = ctx.comm_plan().record()
    assert f["mesh"] == {"x": 2, "y": 2}
    assert set(f["order"]) == {"x", "y"}
    assert f["rounds"] <= f["rounds_serial"]
    assert set(f["axes"]) == {"x", "y"}
    assert all(a["bytes"] > 0 for a in f["axes"].values())


# ---- checker rules --------------------------------------------------------

def test_checker_comm_rules(env):
    from yask_tpu.checker import run_checks
    ctx = build(env, "ssg", 2, 24, "shard_map",
                ranks=[("x", 2), ("y", 2)], steps=0)
    rep = run_checks(ctx, passes=["races", "distributed"])
    rules = {d.rule for d in rep.diagnostics}
    assert "COMM-PLAN" in rules
    bad = build(env, "ssg", 2, 24, "shard_map", ranks=[("x", 2)],
                opts="-comm_order nope", steps=0)
    rep2 = run_checks(bad, passes=["races", "distributed"])
    assert any(d.rule == "COMM-ORDER" and d.severity == "error"
               for d in rep2.diagnostics)
    ser = build(env, "ssg", 2, 24, "shard_map", ranks=[("x", 2), ("y", 2)],
                opts="-coalesce off", steps=0)
    rep3 = run_checks(ser, passes=["races", "distributed"])
    assert any(d.rule == "COMM-SERIAL" for d in rep3.diagnostics)


def test_launch_multihost_single_process(env, tmp_path, capsys):
    """The multi-process launcher's single-process path runs end to end
    on the CPU mesh and prints the comm plan + stats."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import launch_multihost as lm
    rc = lm.main(["-stencil", "iso3dfd", "-radius", "2", "-g", "24",
                  "-mode", "shard_map", "-ranks", "x=2,y=2",
                  "-steps", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "comm plan:" in out and "num-steps-done: 2" in out


def test_mesh_factory_multihost_shape(env):
    """make_mesh is the single construction site: an explicit device
    list (the jax.distributed global-list pattern) lays out the
    requested axis grid."""
    from yask_tpu.parallel.mesh import make_mesh
    devs = env.get_devices()
    m = make_mesh(devs, [("x", 2), ("y", 2), ("z", 2)])
    assert m.axis_names == ("x", "y", "z")
    assert dict(zip(m.axis_names, m.devices.shape)) == \
        {"x": 2, "y": 2, "z": 2}
    with pytest.raises(YaskException):
        make_mesh(devs[:4], [("x", 4), ("y", 2)])
