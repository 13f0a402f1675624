"""A one-chip launch writes onto the arrays the launch before it gave up.

The launch loop keeps the ring slots each launch evicts
(``RunState.spare``) and hands them, donated, to the next launch as the
buffers its outputs are written onto (``build_pallas_chunk(onto=True)``
over the whole interior, ``_PallasLaunch``).  A given-up slot's lead-dim
pad bands are zero, as every state array's are; the launch writes every
interior cell, and every window cell outside the global problem as the
zero the kernel masked it to, so nothing zeroes a band after the kernel
and nothing but a run state's first launch makes an array.

What must hold for that to be the same work with the same result: the
bands ARE zero after any number of launches (ceil overshoot in either
lead dim, a skewed level's shift); nothing of what a given-up slot held
in its interior survives; and whatever puts another state in place
drops the pool."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from yask_tpu.obs.metrics import get_registry
from yask_tpu.ops import pallas_stencil
from yask_tpu.runtime.init_utils import init_solution_vars

from test_launch_outputs import (FIELD_EPS, group_sizes, lower_launch,
                                 make, onto_of, written_slots)

#: stencil, radius, domain and block options, wf_steps, steps a call
CASES = [
    pytest.param("iso3dfd", 2, "-g 16", 2, 4, id="iso3dfd-k2"),
    pytest.param("iso3dfd", 2, "-g 16", 1, 2, id="iso3dfd-k1"),
    pytest.param("ssg", 2, "-g 24", 1, 2, id="ssg-r2-k1"),
    pytest.param("cube", 1, "-g 16", 4, 10, id="cube-4-4-2"),
    pytest.param("awp_abc", None, "-g 20", 1, 2, id="awp_abc-k1"),
    # the overthrust kind: no block divides its extent, in either lead
    # dim (grid 3 x 4, the last tiles 3 and 5 rows past the edge)
    pytest.param("iso3dfd_sponge", 2,
                 "-g_x 21 -g_y 27 -g_z 19 -b_x 8 -b_y 8", 2, 4,
                 id="sponge-ragged-k2"),
    # the flagship's kind: y skewed, its last level written shifted
    # left into the pad and one tile walked past the edge
    pytest.param("iso3dfd", 8, "-g_x 24 -g_y 40 -g_z 128", 2, 4,
                 id="iso3dfd-r8-skewed-k2"),
]


def interior(ctx, name):
    g = ctx._program.geoms[name]
    sizes = ctx._opts.global_domain_sizes
    return tuple(slice(g.origin[dn], g.origin[dn] + sizes[dn])
                 if kind == "domain" else slice(None)
                 for dn, kind in g.axes)


def outside_interior(ctx, name, a):
    """Every cell of ``a`` that is not an interior cell: the pad bands
    of all its domain dims, the lane pads among them."""
    a = np.asarray(a)
    out = np.ones(a.shape, bool)
    out[interior(ctx, name)] = False
    return a[out]


def assert_bands_zero(ctx):
    """Of every array of the state and every given-up slot."""
    held = [(name, a) for name, ring in ctx._state.items()
            if not ctx._program.geoms[name].is_derived for a in ring]
    held += [(name, a) for name, ring in ctx._run.spare.items()
             for a in ring]
    assert any(ctx._run.spare.values())
    for name, a in held:
        band = outside_interior(ctx, name, a)
        assert not band.any(), name


def nan_interiors(ctx):
    """Every given-up slot <- NaN in every interior cell, its bands the
    zeros they were: what a launch may find where it writes."""
    for name, ring in ctx._run.spare.items():
        for i, a in enumerate(ring):
            b = np.zeros(a.shape, a.dtype)
            b[interior(ctx, name)] = math.nan
            ring[i] = jnp.asarray(b)
    return sum(len(ring) for ring in ctx._run.spare.values())


def test_the_cases_walk_past_the_edge_and_skew():
    """The two cases the others lack are what their names say."""
    ctx = make("iso3dfd_sponge", 2,
               "-g_x 21 -g_y 27 -g_z 19 -b_x 8 -b_y 8", "pallas", 2)
    ctx.run_solution(0, 1)
    til = ctx._built_pallas_tiling()
    assert til["grid"] == [3, 4] and not til["skew_dims"]
    assert til["overshoot"] == {"x": 3, "y": 5}
    ctx = make("iso3dfd", 8, "-g_x 24 -g_y 40 -g_z 128", "pallas", 2)
    ctx.run_solution(0, 1)
    til = ctx._built_pallas_tiling()
    assert til["skew_dims"] == ["y"] and til["overshoot"]["y"] > 0


# ------------------------------------------- (a), (b): bands and values

@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["as-given-up", "nan-interiors"])
@pytest.mark.parametrize("stencil,radius,dom,wf,n", CASES)
def test_bands_stay_zero_and_every_interior_cell_is_written(
        stencil, radius, dom, wf, n, poisoned, monkeypatch):
    """After three calls (N >= 3 launches) every pad band of every state
    array and of every given-up slot is exactly zero and the interiors
    are the XLA path's (the numpy oracle reassociates: the two differ
    from each other over as many steps); and the same when each launch
    finds NaN in every interior cell of what it writes onto: a
    whole-interior ``onto`` launch writes every one of them.  Then
    also with NaN in every row of a tile buffer that no input DMA of
    the grid step fills (``tests/poison.py``; stale rows on the chip):
    a row a window walks past the edge with is written as the kernel's
    masked zero, not as what the buffer held."""
    if poisoned:
        from poison import poison_unfetched_rows
        buffers = poison_unfetched_rows(monkeypatch)
    ctx = make(stencil, radius, dom, "pallas", wf)
    ref = make(stencil, radius, dom, "jit", 0)
    launches = 0
    for first in (0, n, 2 * n):
        if poisoned and first:
            assert nan_interiors(ctx) == sum(
                written_slots(ctx, min(wf, n)).values())
        ctx.run_solution(first, first + n - 1)
        launches += len(group_sizes(wf, n))
        assert_bands_zero(ctx)
    assert launches >= 3 and (not poisoned or all(buffers))
    ref.run_solution(0, 3 * n - 1)
    assert ctx.compare_data(
        ref, field_epsilon=FIELD_EPS.get(stencil, 0.0)) == 0
    for name, ring in ctx._state.items():
        assert not any(np.isnan(np.asarray(a)).any() for a in ring), name
    ctx.end_solution()
    ref.end_solution()


# ------------------------------------------------- (c): the lowered text

@pytest.mark.parametrize("stencil,radius,dom,wf,n", CASES)
def test_a_launch_zeroes_no_band_and_is_donated_what_it_writes_onto(
        stencil, radius, dom, wf, n):
    """The executable the runtime holds: no ``yt_zero_pads`` scope
    (a plain ``chunk``, and a shard program, still have it:
    ``tests/test_obs.py``), the arrays of ``base`` each donated to the
    output it is aliased with, and no other argument donated."""
    ctx = make(stencil, radius, dom, "pallas", wf)
    ctx._state_to_device()
    fn = ctx._get_pallas_chunk(min(wf, n))
    lowered = lower_launch(fn, ctx._state)
    text = lowered.as_text(debug_info=True)
    assert pallas_stencil.SCOPE_ZERO_PADS not in text
    assert f"module @jit_{ctx._built_pallas_tiling()['kernel']} " in text
    args = jax.tree_util.tree_leaves(lowered.args_info)
    operands = sum(len(ctx._state[v]) for v in fn.operands)
    assert len(args) == operands + 1 + fn.written
    assert [a.donated for a in args] \
        == [False] * (operands + 1) + [True] * fn.written
    # each paired with an output of its own shape: none left over for
    # the compiler to find a use for, or to drop
    assert text.count("tf.aliasing_output") == fn.written
    assert "jax.buffer_donor" not in text
    # while a plain chunk of the same plan zeroes its bands
    chunk, _ = pallas_stencil.build_pallas_chunk(
        ctx._program, interpret=True,
        vmem_budget=ctx.vmem_budget(min(wf, n)),
        **ctx._pallas_build_args(min(wf, n)))
    assert pallas_stencil.SCOPE_ZERO_PADS in jax.jit(chunk.written).lower(
        fn.takes(ctx._state), 0).as_text(debug_info=True)
    ctx.end_solution()


def test_onto_inside_a_region_keeps_its_rules():
    """A shell's block must divide its span and every written var have
    the restricted dims; the whole interior asks neither."""
    from yask_tpu.utils.exceptions import YaskException
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 1)
    with pytest.raises(YaskException, match="outside the interior"):
        pallas_stencil.build_pallas_chunk(
            ctx._program, fuse_steps=1, interpret=True, onto=True,
            region={"x": (0, 17)})
    shell, _ = pallas_stencil.build_pallas_chunk(
        ctx._program, fuse_steps=1, interpret=True, onto=True,
        region={"x": (8, 16)})
    whole, _ = pallas_stencil.build_pallas_chunk(
        ctx._program, fuse_steps=1, interpret=True, onto=True)
    assert shell.written.writes == whole.written.writes == {"pressure": 1}
    assert shell.tiling["grid"][0] * shell.tiling["block"]["x"] == 8
    ctx.end_solution()


# ------------------------------------- (d): the pool is what was evicted

@pytest.mark.parametrize("stencil,radius,dom,wf,n", CASES)
def test_the_pool_is_what_the_last_launch_evicted(stencil, radius, dom,
                                                  wf, n, tmp_path,
                                                  monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    import json
    ctx = make(stencil, radius, dom, "pallas", wf)
    ctx.run_solution(0, n - 1)
    sizes = group_sizes(wf, n)
    # a call of one launch: what it evicts is what the state held
    k = sizes[0]
    before = {name: list(ring) for name, ring in ctx._state.items()}
    pool = {name: list(ring) for name, ring in ctx._run.spare.items()}
    ctx.run_solution(n, n + k - 1)
    back = written_slots(ctx, k)
    assert set(ctx._run.spare) == set(back)
    for name, m in back.items():
        spare = ctx._run.spare[name]
        assert all(a is b for a, b in zip(spare[len(spare) - m:],
                                          before[name][:m]))
        # and what it was written onto is consumed, what it did not
        # need (after a longer last group) still waits
        assert all(a.is_deleted() for a in pool[name][:m])
        assert all(a is b and not a.is_deleted() for a, b in
                   zip(spare[:len(spare) - m], pool[name][m:]))
    with open(path) as f:
        attrs = [json.loads(ln)["attrs"] for ln in f
                 if '"run.launch"' in ln]
    assert [a["onto"] for a in attrs] == onto_of(ctx, sizes) + [
        sum(back.values())]
    assert attrs[-1]["onto"] == attrs[-1]["written"]
    ctx.end_solution()


# --------------------- (e): what else happens between and around calls

def test_a_host_write_between_two_calls():
    """A public fill puts another array in a ring slot; the pool is
    untouched by it and the next call is the oracle's."""
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 2)
    ref = make("iso3dfd", 2, "-g 16", "jit", 0)
    made = get_registry().counter("run.spare_made")
    for c in (ctx, ref):
        c.run_solution(0, 3)
    pool = list(ctx._run.spare["pressure"])
    was = made.value
    box = np.arange(27, dtype=np.float32).reshape(3, 3, 3) / 27
    for c in (ctx, ref):
        p = c.get_var("pressure")
        p.set_element(0.5, [4, 8, 8, 8])
        p.set_elements_in_slice(box, [4, 2, 3, 4], [4, 4, 5, 6])
        c.get_var("vel").set_element(0.002, [5, 5, 5])
    assert all(a is b for a, b in zip(ctx._run.spare["pressure"], pool))
    for c in (ctx, ref):
        c.run_solution(4, 7)
    assert made.value == was
    assert ctx.compare_data(ref) == 0
    assert_bands_zero(ctx)


def test_a_change_of_wf_steps_between_calls():
    """K = 2, then 1, then 2 again on the same pads: a launch takes of
    the pool what it writes and leaves the rest, and only the first
    ever makes an array."""
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 2)
    ref = make("iso3dfd", 2, "-g 16", "jit", 0)
    made = get_registry().counter("run.spare_made")
    was = made.value
    t = 0
    for wf, n in ((2, 4), (1, 3), (2, 4)):
        ctx.apply_command_line_options(f"-wf_steps {wf}")
        ctx.run_solution(t, t + n - 1)
        ref.run_solution(t, t + n - 1)
        t += n
        assert len(ctx._run.spare["pressure"]) == 2
    assert made.value - was == 2
    assert [r["onto"] for r in ctx.call_log()] \
        == [[0, 2], [1, 1, 1], [2, 2]]
    assert ctx.compare_data(ref) == 0
    assert_bands_zero(ctx)


@pytest.mark.parametrize("stencil,radius,dom,wf,n", [
    ("iso3dfd", 2, "-g 16", 2, 5), ("cube", 1, "-g 16", 4, 7),
    ("iso3dfd", 8, "-g_x 24 -g_y 40 -g_z 128", 2, 3)])
def test_calls_whose_steps_no_group_divides(stencil, radius, dom, wf, n):
    ctx = make(stencil, radius, dom, "pallas", wf)
    ref = make(stencil, radius, dom, "jit", 0)
    for first in (0, n, 2 * n):
        ctx.run_solution(first, first + n - 1)
        ref.run_solution(first, first + n - 1)
    sizes = group_sizes(wf, n)
    assert n % wf and [r["onto"] for r in ctx.call_log()] == [
        onto_of(ctx, sizes),
        *[onto_of(ctx, sizes, written_slots(ctx, wf))] * 2]
    assert ctx.compare_data(ref) == 0
    assert_bands_zero(ctx)


# --------------------------------------- the pool goes with the state

@pytest.mark.parametrize("how", ["to_host", "end_solution", "prepare",
                                 "restore", "new_run_state", "reset"])
def test_what_puts_another_state_in_place_drops_the_pool(how, tmp_path):
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 2)
    ref = make("iso3dfd", 2, "-g 16", "jit", 0)
    ctx.save_checkpoint(str(tmp_path / "c"))
    ctx.run_solution(0, 3)
    run = ctx._run
    assert len(run.spare["pressure"]) == 2
    made = get_registry().counter("run.spare_made")
    was = made.value
    steps = (4, 7)
    if how == "to_host":
        ctx.copy_vars_from_device()
        ref.run_solution(0, 3)
    elif how == "end_solution":
        ctx.end_solution()
        steps = None
    elif how == "prepare":
        ctx.prepare_solution()
        init_solution_vars(ctx)
        steps = (0, 3)
    elif how == "restore":
        ctx.load_checkpoint(str(tmp_path / "c"))
        steps = (0, 3)
    elif how == "new_run_state":
        fresh = ctx.new_run_state()
        assert fresh.spare == {} and len(run.spare["pressure"]) == 2
        ctx.set_run_state(fresh)            # the old run keeps its own
        init_solution_vars(ctx)
        steps = (0, 3)
    else:
        run.reset()
        steps = None
    assert ctx._run.spare == {}
    if steps:
        ctx.run_solution(*steps)
        ref.run_solution(*steps)
        assert made.value - was == 2
        assert ctx.compare_data(ref) == 0
        assert_bands_zero(ctx)


def test_a_launch_whose_executable_raises_loses_what_it_took():
    """Only the pool's arrays are donated: the state a launch read
    outlives it, state and step position agree, and the next launch
    makes the arrays the failed one took with it."""
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 2)
    ref = make("iso3dfd", 2, "-g 16", "jit", 0)
    ctx.run_solution(0, 3)
    fn = ctx._get_pallas_chunk(2)
    real = fn.exe
    made = get_registry().counter("run.spare_made")
    was = made.value

    def consumed_then_fails(state, t, base):
        real(state, t, base)
        raise RuntimeError("after the donation")

    fn.exe = consumed_then_fails
    held = {name: list(ring) for name, ring in ctx._state.items()}
    with pytest.raises(RuntimeError, match="after the donation"):
        ctx.run_solution(4, 7)
    fn.exe = real
    assert ctx._cur_step == ctx._steps_done == 4
    assert all(a is b and not a.is_deleted() for name in held
               for a, b in zip(ctx._state[name], held[name]))
    assert ctx._run.spare["pressure"] == []
    ctx.run_solution(4, 7)
    ref.run_solution(0, 7)
    assert made.value - was == 2
    assert ctx.call_log()[-1]["onto"] == [0, 2]
    assert ctx.compare_data(ref) == 0
    assert_bands_zero(ctx)


def test_two_run_states_of_one_solution_keep_their_own_pools():
    """A swapped run (a served session, an ensemble's member) is
    written onto its own given-up slots and never another's."""
    ctx = make("iso3dfd", 2, "-g 16", "pallas", 2)
    ref = make("iso3dfd", 2, "-g 16", "jit", 0)
    one = ctx.get_run_state()
    two = ctx.new_run_state()
    ctx.set_run_state(two)
    init_solution_vars(ctx)
    ctx.set_run_state(one)
    for first in (0, 4):
        for run in (one, two):
            ctx.set_run_state(run)
            ctx.run_solution(first, first + 3)
    assert not {id(a) for a in one.spare["pressure"]} \
        & {id(a) for a in two.spare["pressure"]}
    ref.run_solution(0, 7)
    for run in (one, two):
        ctx.set_run_state(run)
        assert ctx.compare_data(ref) == 0
        assert_bands_zero(ctx)
    assert [r["onto"] for r in one.calls] == [[0, 2], [2, 2]] \
        == [r["onto"] for r in two.calls]
