"""What a one-chip call holds while its launches run.

``_run_groups`` lets the context's state follow the launches: once a
launch is enqueued its outputs are the state.  Those outputs are
written onto the ring slots the launch before it evicted
(``RunState.spare``, donated: ``_PallasLaunch``), and the slots it
evicts itself wait there for the next, so through a whole loop the
device holds the state and ONE given-up generation of the written
slots and no launch allocates (the parent made new outputs at every
launch and let the generation it read go: at iso3dfd 768^3 a third
generation of the pressure ring was asked for while two were in
flight, which a v5e has no room for, and the enqueue waited in the
allocator).  The launches, their order, their spans and the single
wait are what they were; a call that raises leaves state and step
position agreeing."""

import math
import weakref

import numpy as np
import pytest

from yask_tpu.obs import tracer

from test_compiled_plans import _ctx, _v5e_tiling
from test_launch_outputs import FIELD_EPS, group_sizes, make

GIB = 2 ** 30
#: ``bytes_limit`` of one v5e (``memory_stats()`` on the chip, PR 43)
V5E_LIMIT = 16909336064

#: stencil, radius, -g, wf_steps, steps a call
CASES = [
    pytest.param("iso3dfd", 2, 16, 2, 10, id="iso3dfd-k2"),
    pytest.param("iso3dfd", 2, 16, 1, 4, id="iso3dfd-k1"),
    pytest.param("cube", 1, 16, 4, 10, id="cube-4-4-2"),
    pytest.param("ssg", 2, 24, 1, 3, id="ssg-r2-k1"),
]


def plan_bytes(program, k):
    """``(state, written)`` by the plan's shapes alone: bytes of every
    array of the state, and of the ring slots a ``k``-step Pallas
    launch returns, ``min(k, slots)`` of each var a step writes."""
    item = np.dtype(program.dtype).itemsize
    state = written = 0
    for g in program.geoms.values():
        if g.is_scratch:
            continue
        slot = item * math.prod(g.shape)
        state += g.num_slots * slot
        if g.is_written:
            written += min(k, g.num_slots) * slot
    return state, written


def arrays_of(ctx):
    return {name: [np.asarray(a) for a in ring]
            for name, ring in ctx._state.items()}


def same(ctx, ref):
    a, b = arrays_of(ctx), arrays_of(ref)
    return all((x == y).all() for name in a
               for x, y in zip(a[name], b[name]))


def spans_of(path, name):
    return [s for s in tracer.read_spans(str(path)) if s["name"] == name]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    return path


# ------------------------------------------- the bytes, from shapes alone

@pytest.mark.parametrize("stencil,radius,dom,k,state,written,three", [
    # vel and three generations of the pressure ring: 15.762 of 15.748
    ("iso3dfd", 8, (768, 768, 768), 2, 6.5237, 4.6194, False),
    ("iso3dfd", 8, (640, 640, 640), 2, 3.9624, 2.8345, True),
    ("cube", 1, (768, 768, 768), 4, 4.4043, 4.4043, True),
    ("ssg", 4, (320, 320, 384), 1, 3.9060, 2.0299, True),
    # (6.7163 until PR 49: four hoisted scratch vars are arrays now)
    ("tti", 4, (512, 512, 512), 1, 9.6606, 1.4941, True),
    ("iso3dfd_sponge", 8, (801, 801, 187), 2, 2.8402, 1.4464, True),
])
def test_what_each_one_chip_cell_holds_on_a_v5e(
        stencil, radius, dom, k, state, written, three):
    """Every one-chip cell of the benchmark as a v5e would pad it: the
    state and one more generation of the slots a launch writes -- what
    a loop of launches holds, the given-up slots each is written onto
    -- fit everywhere; two more everywhere but at 768^3.  Nothing is
    allocated."""
    ctx = _ctx(stencil, radius, dom, "pallas", k)
    ctx._env.get_platform = lambda: "tpu"
    ctx._env.get_device_kind = lambda: "TPU v5 lite"
    got = plan_bytes(ctx._plan_geometry(), k)
    assert ctx._state is None
    assert [round(b / GIB, 4) for b in got] == [state, written]
    assert got[0] + got[1] < V5E_LIMIT
    assert (got[0] + 2 * got[1] <= V5E_LIMIT) is three


def test_the_768_cells_plan_on_a_v5e():
    """The flagship's kernel class at the deployment size: a block of
    32 x 24 (24 does not divide 768: the skewed y walks one tile past
    the edge) with both pipelines since PR 51, priced by what the strip
    kernel declares (16 x 24 and the input pipeline alone before; the
    chip ran this plan 30 % faster, ``PERF.md`` section 6).  The state
    it is padded for is the same to the byte (the case above)."""
    til = _v5e_tiling("iso3dfd", 8, (768, 768, 768), 2)
    assert til["block"] == {"x": 32, "y": 24} and til["grid"] == [24, 33]
    assert til["kernel"] == "yt_iso3dfd_r8_k2" and til["skew_dims"] == ["y"]
    assert til["pipeline_dmas"] and til["pipeline_out"]
    assert til["tile_bytes"] == 112459776           # 107.25 MiB
    # tiles of 64 x 48 for the block of 32 x 24.  Fetched (PR 45):
    # ``pressure(t)`` whole, ``pressure(t-1)`` and ``vel`` (read at the
    # point) 48 of x's 64 rows, y (skewed) whole; ``vel`` rides 768
    # lanes, the pressures 896 (3.7 at 16 x 24)
    assert (til["margin_overhead"], til["fetch_overhead"]) == (0.25, 2.35) \
        == (0.25, round(48 * (896 * (64 + 48) + 768 * 48)
                        / (32 * 24 * (2 * 896 + 768)) - 1, 4))
    assert til["fetch_skipped"] == []
    assert (til["edge_overhead"], til["lane_fill"]) == (0.0312, 0.8571)
    assert til["vinstr_est"] == 87840 < 100_000
    # 115.1 of the room's 115.2 MiB by the row's 0.75 result tiles
    # (Mosaic's own count of this kernel: the buffers and ~3 MiB)
    assert til["scoped_need_bytes"] == til["tile_bytes"] \
        + int(0.75 * til["result_bytes"]) == 120717312 \
        <= int(0.9 * 128 * 2 ** 20)


# ------------------------------------------------- what a call holds

class Watched:
    """A held launch that notes, at each enqueue, which arrays of
    earlier generations are still alive."""

    def __init__(self, fn, log, ctx):
        self.fn, self.log, self.ctx = fn, log, ctx
        self.written, self.onto = fn.written, fn.onto

    def __call__(self, state, t):
        seen = {id(a) for ring in state.values() for a in ring}
        pool = {id(a) for ring in self.ctx._run.spare.values()
                for a in ring}
        alive = [r() for refs in self.log for r in refs]
        self.log.append([weakref.ref(a) for ring in state.values()
                         for a in ring])
        # of everything earlier launches read, only what this one
        # reads too (a kept array, a ring slot that survives) lives,
        # and the slots the last launch gave up: what this one is
        # written onto, no more of them than it writes
        stale = [a for a in alive if a is not None and id(a) not in seen]
        assert len({id(a) for a in stale}) <= self.written, len(self.log)
        assert all(id(a) in pool and not a.is_deleted() for a in stale)
        assert len(pool) == self.written == self.onto()
        del alive
        new = self.fn(state, t)
        # consumed: the outputs are in their memory
        assert all(a.is_deleted() for a in stale)
        return new


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_a_loop_of_launches_holds_the_state_and_one_given_up_generation(
        stencil, radius, g, wf, n):
    """At the enqueue of launch i + 1 the only arrays alive that it
    does not read are the slots launch i evicted, which it is donated:
    the state follows the launches, and the memory of what they give up
    goes round."""
    from yask_tpu.obs.metrics import get_registry
    ctx = make(stencil, radius, g, "pallas", wf)
    ctx.run_solution(0, n - 1)          # compile, and leave warm-up
    # the plan's shapes are the arrays' as allocated
    state_bytes, written_bytes = plan_bytes(ctx._program, wf)
    assert state_bytes == sum(
        a.nbytes for ring in ctx._state.values() for a in ring)
    assert written_bytes == sum(
        a.nbytes for ring in ctx._run.spare.values() for a in ring)
    log = []
    held = {k: Watched(ctx._get_pallas_chunk(k), log, ctx)
            for k in set(group_sizes(wf, n))}
    ctx._get_pallas_chunk = held.__getitem__
    made = get_registry().counter("run.spare_made").value
    # whole groups alone: a shorter last group leaves the pool larger
    # than the next launch needs
    whole = n - n % wf
    ctx.run_solution(n, n + whole - 1)
    assert len(log) == whole // wf
    assert get_registry().counter("run.spare_made").value == made
    ctx.end_solution()
    assert ctx._run.spare == {}


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_the_call_is_the_oracles(stencil, radius, g, wf, n):
    ctx = make(stencil, radius, g, "pallas", wf)
    ref = make(stencil, radius, g, "jit", 0)
    for first in (0, n):
        for c in (ctx, ref):
            c.run_solution(first, first + n - 1)
    assert ctx.compare_data(
        ref, field_epsilon=FIELD_EPS.get(stencil, 0.0)) == 0
    assert ctx._cur_step == ref._cur_step == 2 * n
    assert ctx._steps_done == ref._steps_done == 2 * n
    ctx.end_solution()
    ref.end_solution()


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_launches_spans_and_the_single_wait_are_what_they_were(
        stencil, radius, g, wf, n, traced):
    """Every launch enqueued before the one wait, each inside the
    call's span, with the attrs the launch span had."""
    ctx = make(stencil, radius, g, "pallas", wf)
    ctx.run_solution(0, n - 1)
    sizes = group_sizes(wf, n)
    call, = spans_of(traced, "run.call")
    launches = spans_of(traced, "run.launch")
    wait, = spans_of(traced, "run.wait")
    assert all(s["parent"] == call["span"] for s in launches + [wait])
    assert [s["attrs"]["k"] for s in launches] == sizes
    assert all(set(s["attrs"]) == {"k", "written", "onto", "kept"}
               for s in launches)
    assert max(s["ts"] for s in launches) < wait["ts"]
    # nothing else of the runtime's under the call: no wait between
    # launches
    assert {s["name"] for s in tracer.read_spans(str(traced))
            if s.get("parent") == call["span"]
            and s["name"].startswith("run.")} == {"run.launch", "run.wait"}
    row, = ctx.call_log()
    assert [k for k, _s in row["launches"]] == sizes
    ctx.end_solution()


# ----------------------------------------------- a call that raises

class Boom(RuntimeError):
    pass


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_a_launch_that_raises_leaves_the_context_at_a_group_boundary(
        stencil, radius, g, wf, n):
    """State, step position and steps done agree: the groups before the
    launch that raised are done, and the next call goes on from there
    to what an unbroken run gives."""
    ctx = make(stencil, radius, g, "pallas", wf)
    ref = make(stencil, radius, g, "pallas", wf)
    sizes = group_sizes(wf, n)
    real = ctx._get_pallas_chunk
    fails_at = len(sizes) - 1
    seen = []

    def flaky(k):
        fn = real(k)

        def launch(state, t):
            seen.append(t)
            if len(seen) == fails_at + 1:
                raise Boom(f"launch {fails_at}")
            return fn(state, t)
        launch.written = fn.written
        return launch

    ctx._get_pallas_chunk = flaky
    with pytest.raises(Boom):
        ctx.run_solution(0, n - 1)
    done = sum(sizes[:fails_at])
    assert ctx._cur_step == ctx._steps_done == done
    assert seen == [sum(sizes[:i]) for i in range(fails_at + 1)]
    assert ctx.call_log() == []         # a failed call leaves no row
    ref.run_solution(0, done - 1)
    assert same(ctx, ref)
    # the caller goes on from the boundary
    ctx._get_pallas_chunk = real
    ctx.run_solution(done, n - 1)
    ref.run_solution(done, n - 1)
    assert ctx._cur_step == ctx._steps_done == n
    assert same(ctx, ref)
    ctx.end_solution()
    ref.end_solution()


def test_a_fault_seen_only_in_the_wait_leaves_the_call_at_its_end(
        monkeypatch):
    """The device reports a failed launch when the host waits for it:
    by then the state is the last launch's outputs, and step position
    and steps done say so."""
    import jax
    ctx = make("iso3dfd", 2, 16, "pallas", 2)
    ctx.run_solution(0, 9)

    def fails(_tree):
        raise Boom("the device says so late")
    monkeypatch.setattr(jax, "block_until_ready", fails)
    with pytest.raises(Boom):
        ctx.run_solution(10, 19)
    monkeypatch.undo()
    assert ctx._cur_step == ctx._steps_done == 20
    assert len(ctx.call_log()) == 1
    ref = make("iso3dfd", 2, 16, "pallas", 2)
    ref.run_solution(0, 19)
    assert same(ctx, ref)
    ctx.end_solution()
    ref.end_solution()
