"""A one-chip Pallas launch returns only what its kernel wrote, written
onto the ring slots the launch before it gave up.

``build_pallas_chunk``'s chunk splits into ``chunk.written`` (the
``pallas_call``: the ring slots the kernel writes, ``min(K, slots)`` a
written var; built ``onto``, written onto the arrays of its ``base``
argument) and ``chunk.merge`` (host side, references alone).
``_get_pallas_chunk`` compiles the first with ``base`` donated and
holds ``fn(state, t) -> merge(state, exe(state, t, base))``, ``base``
taken from the run's pool of given-up slots (``RunState.spare``) and
the slots the launch evicts left there.  So no array the kernel does
not write is an output of the executable: a read-only array, or a ring
slot that survives the K steps, is carried over as the object it was,
never copied; and no launch but a run state's first makes an array.
``chunk(state, t0)`` itself returns what it always did (the shard
programs, the ensemble and the pipeline call it inside programs of
their own)."""

import json

import jax
import pytest

from yask_tpu import yk_factory
from yask_tpu.runtime.init_utils import init_solution_vars

# Fused in-tile sums reassociate against XLA's fusion in the staggered
# families: ulps of the field scale (tests/test_pallas.py _FP_REASSOC)
FIELD_EPS = {"ssg": 1e-4, "awp_abc": 1e-4}

#: stencil, radius, -g, wf_steps, steps a call -> the call's groups
CASES = [
    pytest.param("iso3dfd", 2, 16, 2, 4, id="iso3dfd-k2"),
    pytest.param("iso3dfd", 2, 16, 1, 2, id="iso3dfd-k1"),
    pytest.param("ssg", 2, 24, 1, 2, id="ssg-r2-k1"),
    pytest.param("cube", 1, 16, 4, 10, id="cube-4-4-2"),
    pytest.param("awp_abc", None, 20, 1, 2, id="awp_abc-k1"),
]


def make(stencil, radius, g, mode, wf):
    """``g``: the cube's edge, or the domain and block options."""
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    dom = g if isinstance(g, str) else f"-g {g}"
    ctx.apply_command_line_options(f"{dom} -mode {mode} -wf_steps {wf}")
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


def written_slots(ctx, k):
    """``{var: min(k, slots)}`` over the vars a step writes out."""
    return {name: min(k, g.num_slots)
            for name, g in ctx._program.geoms.items()
            if g.is_written and not g.is_scratch}


def group_sizes(wf, n):
    k = min(wf, n)
    return [k] * (n // k) + ([n % k] if n % k else [])


def onto_of(ctx, sizes, pool=None):
    """What each launch of ``sizes`` steps finds in the pool, which
    starts as ``pool`` (``{var: arrays}``; empty: a run state's first
    call): of the slots it writes of a var, as many as the largest
    launch before it gave up."""
    pool = dict(pool or {})
    out = []
    for k in sizes:
        need = written_slots(ctx, k)
        out.append(sum(min(n, pool.get(v, 0)) for v, n in need.items()))
        pool = {v: max(n, pool.get(v, 0)) for v, n in need.items()}
    return out


def lower_launch(fn, state, t=0):
    """The held launch's executable, lowered as the runtime compiles
    it: handed the kernel's operands, and ``base`` donated."""
    from yask_tpu.runtime.context import _LAUNCH_DONATES
    assert _LAUNCH_DONATES == (2,)
    return fn.exe.lower(fn.takes(state), t, fn.evicts(state))


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_a_launch_returns_the_written_slots_and_keeps_the_rest(
        stencil, radius, g, wf, n):
    ctx = make(stencil, radius, g, "pallas", wf)
    ctx._state_to_device()
    state = ctx._state
    arrays = sum(len(ring) for ring in state.values())
    t = 0
    for k in group_sizes(wf, n):
        fn = ctx._get_pallas_chunk(k)
        nback = written_slots(ctx, k)
        # the executable's outputs, read from the lowering: what the
        # compiled path returns too
        out_info = lower_launch(fn, state, t).out_info
        assert set(out_info) == set(nback) and fn.writes == nback
        assert {v: len(ring) for v, ring in out_info.items()} == nback
        assert fn.written == len(jax.tree_util.tree_leaves(out_info)) \
            == sum(nback.values())
        rings = {name: (ring, list(ring)) for name, ring in state.items()}
        new = fn(state, t)
        # what the launch evicted is the run's to write onto next: the
        # very objects, oldest first, behind what it did not need of
        # what the launches before it gave up
        for name, back in nback.items():
            spare = ctx._run.spare[name]
            assert all(a is b for a, b in zip(spare[len(spare) - back:],
                                              rings[name][1][:back]))
        assert set(new) == set(state)
        kept = 0
        for name, (ring, held) in rings.items():
            # the input's lists are the peer's too (fuse_vars)
            assert state[name] is ring and ring == held
            assert all(a is b for a, b in zip(ring, held))
            back = nback.get(name, 0)
            assert len(new[name]) == len(ring)
            for i, a in enumerate(new[name][:len(ring) - back]):
                assert a is ring[i + back], (name, i)
                kept += 1
            for a in new[name][len(ring) - back:]:
                assert not any(a is b for b in ring), name
            if back:
                assert new[name] is not ring
        assert kept == arrays - fn.written
        state, t = new, t + k
    # cube rewrites its whole ring in every group: nothing is kept,
    # and its executable is the one it always had
    if stencil == "cube":
        assert all(ctx._get_pallas_chunk(k).written == arrays
                   for k in group_sizes(wf, n))
    # the launches above were a call's: the state they left is the
    # context's (the one it held is consumed), and the values are the
    # oracle's, another call on
    ctx._run.state, ctx._cur_step = state, t
    ctx.run_solution(n, 2 * n - 1)
    ref = make(stencil, radius, g, "jit", 0)
    ref.run_solution(0, 2 * n - 1)
    assert ctx.compare_data(
        ref, field_epsilon=FIELD_EPS.get(stencil, 0.0)) == 0
    ctx.end_solution()
    ref.end_solution()


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_chunk_whole_is_merge_of_written(stencil, radius, g, wf, n):
    """``chunk(state, t0)`` -- what a shard program, the ensemble and
    the pipeline trace inside programs of their own -- is still the
    whole state, bit for bit its two halves'."""
    from yask_tpu.ops.pallas_stencil import build_pallas_chunk
    ctx = make(stencil, radius, g, "pallas", wf)
    ctx._state_to_device()
    k = group_sizes(wf, n)[0]
    chunk, _ = build_pallas_chunk(ctx._program, fuse_steps=k,
                                  interpret=True,
                                  vmem_budget=ctx.vmem_budget(k))
    assert chunk.__name__ == chunk.written.__name__ \
        == chunk.tiling["kernel"]
    whole = jax.jit(chunk)(ctx._state, 0)
    halves = chunk.merge(ctx._state,
                         jax.jit(chunk.written)(ctx._state, 0))
    assert {v: len(r) for v, r in whole.items()} \
        == {v: len(r) for v, r in halves.items()} \
        == {v: len(r) for v, r in ctx._state.items()}
    for name, ring in whole.items():
        for a, b in zip(ring, halves[name]):
            assert (a == b).all(), name
    ctx.end_solution()


@pytest.mark.parametrize("stencil,radius,g,wf,n", CASES)
def test_launch_span_says_written_onto_and_kept(stencil, radius, g, wf, n,
                                                tmp_path, monkeypatch):
    """``onto``: the outputs written onto a given-up slot.  A run
    state's first launch finds none and makes its arrays
    (``run.spare_made``); from the second call on every launch finds
    all it needs and the counter stands still."""
    from yask_tpu.obs.metrics import get_registry
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    made = get_registry().counter("run.spare_made")
    ctx = make(stencil, radius, g, "pallas", wf)
    was = made.value
    ctx.run_solution(0, n - 1)
    sizes = group_sizes(wf, n)
    first = onto_of(ctx, sizes)
    assert first[0] == 0
    assert made.value - was == sum(written_slots(ctx, sizes[0]).values())
    ctx.run_solution(n, 2 * n - 1)
    assert made.value - was == sum(written_slots(ctx, sizes[0]).values())
    arrays = sum(len(ring) for ring in ctx._state.values())
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    attrs = [r["attrs"] for r in rows if r["name"] == "run.launch"]
    assert [a["k"] for a in attrs] == sizes * 2
    assert [a["onto"] for a in attrs[:len(sizes)]] == first
    assert [r["onto"] for r in ctx.call_log()] \
        == [first, [a["written"] for a in attrs[len(sizes):]]]
    for a in attrs:
        assert a["written"] == sum(written_slots(ctx, a["k"]).values())
        assert a["written"] + a["kept"] == arrays
    attrs = attrs[len(sizes):]
    assert all(a["onto"] == a["written"] for a in attrs)
    if stencil == "cube":
        assert [a["kept"] for a in attrs] == [0] * len(sizes)
    if stencil == "ssg":
        assert [(a["written"], a["kept"]) for a in attrs] == [(9, 9)] * n
    if stencil == "iso3dfd":
        assert {(a["written"], a["kept"]) for a in attrs} \
            == {(min(wf, 2), 3 - min(wf, 2))}
    ctx.end_solution()


def test_a_jit_launch_writes_the_whole_state(tmp_path, monkeypatch):
    """The XLA chunk is donated, and returns, every array: each output
    written onto what the launch itself gives up."""
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    ctx = make("iso3dfd", 2, 16, "jit", 2)
    ctx.run_solution(0, 3)
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    attrs = [r["attrs"] for r in rows if r["name"] == "run.launch"]
    assert [(a["k"], a["written"], a["onto"], a["kept"])
            for a in attrs] == [(2, 3, 3, 0)] * 2
    assert ctx._run.spare == {}
    ctx.end_solution()


class _StubExecutable:
    """What a compiled chunk answers, with nothing compiled."""

    class _Memory:
        temp_size_in_bytes = 1
        argument_size_in_bytes = 18
        output_size_in_bytes = 9
        alias_size_in_bytes = 0
        generated_code_size_in_bytes = 4

    def __call__(self, state, t, base):
        return {}

    def as_text(self):
        return "HloModule jit_yt_stub_r1_k1, entry_computation_layout={}"

    def memory_analysis(self):
        return self._Memory()


def test_the_held_launch_answers_for_its_executable():
    """``compiled_texts()`` and ``compiled_memory()`` read the holder in
    ``_jit_cache`` as they read an executable; an interpreted chunk
    (a plain function) still has neither."""
    from yask_tpu.runtime.context import _PallasLaunch
    ctx = make("iso3dfd", 2, 16, "pallas", 2)
    ctx.run_solution(0, 1)
    held, = ctx._jit_cache.values()
    assert isinstance(held, _PallasLaunch)
    assert ctx.compiled_texts() == [] and ctx.compiled_memory() == []
    assert not hasattr(held, "as_text")
    ctx._jit_cache[("pallas", 1, None)] = _PallasLaunch(
        _StubExecutable(), held.merge, writes={},
        operands=held.operands, ctx=ctx)
    assert ctx.compiled_texts() == [_StubExecutable().as_text()]
    assert ctx.compiled_memory() == [{
        "kind": "pallas", "temp_bytes": 1, "argument_bytes": 18,
        "output_bytes": 9, "alias_bytes": 0, "generated_code_bytes": 4}]
    # the stub writes nothing: the launch is the input's references
    state = ctx._state
    new = ctx._jit_cache[("pallas", 1, None)](state, 0)
    assert new is not state
    assert all(a is b for name in state
               for a, b in zip(state[name], new[name]))
    ctx.end_solution()


def test_fused_contexts_share_what_no_step_writes_across_pallas_runs():
    """``fuse_vars`` shares references, and its caveat holds for
    ``pallas`` as for ``jit``: the slots of a written var that a launch
    evicts are donated to the next, so the peer that still holds them
    finds them consumed, and re-fuses after a run.  An array no step
    writes is never donated: it stays one live object in both."""
    a = make("iso3dfd", 2, 16, "pallas", 2)
    b = make("iso3dfd", 2, 16, "pallas", 2)
    ref = make("iso3dfd", 2, 16, "jit", 0)
    b.get_var("pressure").set_all_elements_same(0.0)   # then adopts a's
    b.fuse_vars(a)
    assert all(x is y for n in a._state
               for x, y in zip(a._state[n], b._state[n]))
    vel = a._state["vel"][0]
    shared = list(b._state["pressure"])
    ref.run_solution(0, 3)
    a.run_solution(0, 1)
    # one launch on: a gave the shared slots up, and they wait, whole,
    # for its next launch
    assert all(x is y for x, y in zip(a._run.spare["pressure"], shared))
    assert not any(x.is_deleted() for x in shared)
    a.run_solution(2, 3)
    assert a.compare_data(ref) == 0
    assert all(x.is_deleted() for x in shared)
    with pytest.raises(RuntimeError, match="deleted"):
        b.compare_data(ref)
    assert a._state["vel"][0] is vel and b._state["vel"][0] is vel
    assert not vel.is_deleted()
    # re-fused, b reads what a holds now and runs on from there
    b.fuse_vars(a)
    b._cur_step = a._cur_step
    assert b.compare_data(ref) == 0
    ref.run_solution(4, 7)
    b.run_solution(4, 7)
    assert b.compare_data(ref) == 0
    assert b._state["vel"][0] is vel
    for ctx in (a, b, ref):
        ctx.end_solution()
