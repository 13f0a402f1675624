"""A one-chip Pallas launch returns only what its kernel wrote.

``build_pallas_chunk``'s chunk splits into ``chunk.written`` (the
``pallas_call`` and what is stitched onto its outputs: the ring slots
the kernel writes, ``min(K, slots)`` a written var) and ``chunk.merge``
(host side, references alone).  ``_get_pallas_chunk`` compiles the
first and holds ``fn(state, t) -> merge(state, exe(state, t))``, so no
array the kernel does not write is an output of the executable: a
read-only array, or a ring slot that survives the K steps, is carried
over as the object it was, never copied.  ``chunk(state, t0)`` itself
returns what it always did (the shard programs, the ensemble and the
pipeline call it inside programs of their own)."""

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
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(f"-g {g} -mode {mode} -wf_steps {wf}")
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
        out_info = jax.jit(fn.exe).lower(state, t).out_info
        assert set(out_info) == set(nback)
        assert {v: len(ring) for v, ring in out_info.items()} == nback
        assert fn.written == len(jax.tree_util.tree_leaves(out_info)) \
            == sum(nback.values())
        rings = {name: (ring, list(ring)) for name, ring in state.items()}
        new = fn(state, t)
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
    # and the values are the oracle's, two calls on
    ref = make(stencil, radius, g, "jit", 0)
    for first in (0, n):
        ctx.run_solution(first, first + n - 1)
        ref.run_solution(first, first + n - 1)
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
def test_launch_span_says_written_and_kept(stencil, radius, g, wf, n,
                                           tmp_path, monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    ctx = make(stencil, radius, g, "pallas", wf)
    ctx.run_solution(0, n - 1)
    arrays = sum(len(ring) for ring in ctx._state.values())
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    attrs = [r["attrs"] for r in rows if r["name"] == "run.launch"]
    sizes = group_sizes(wf, n)
    assert [a["k"] for a in attrs] == sizes
    for a in attrs:
        assert a["written"] == sum(written_slots(ctx, a["k"]).values())
        assert a["written"] + a["kept"] == arrays
    if stencil == "cube":
        assert [a["kept"] for a in attrs] == [0] * len(sizes)
    if stencil == "ssg":
        assert [(a["written"], a["kept"]) for a in attrs] == [(9, 9)] * n
    if stencil == "iso3dfd":
        assert {(a["written"], a["kept"]) for a in attrs} \
            == {(min(wf, 2), 3 - min(wf, 2))}
    ctx.end_solution()


def test_a_jit_launch_writes_the_whole_state(tmp_path, monkeypatch):
    """The XLA chunk is donated, and returns, every array."""
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    ctx = make("iso3dfd", 2, 16, "jit", 2)
    ctx.run_solution(0, 3)
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    attrs = [r["attrs"] for r in rows if r["name"] == "run.launch"]
    assert [(a["k"], a["written"], a["kept"]) for a in attrs] \
        == [(2, 3, 0)] * 2
    ctx.end_solution()


class _StubExecutable:
    """What a compiled chunk answers, with nothing compiled."""

    class _Memory:
        temp_size_in_bytes = 1
        argument_size_in_bytes = 18
        output_size_in_bytes = 9
        alias_size_in_bytes = 0
        generated_code_size_in_bytes = 4

    def __call__(self, state, t):
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
        _StubExecutable(), held.merge, written=0,
        operands=held.operands)
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


def test_fused_contexts_read_each_others_values_across_pallas_runs():
    """``fuse_vars`` shares references; a Pallas launch donates nothing
    and mutates no list, so after either context runs the peer still
    reads what it held, and the array neither wrote stays one object."""
    a = make("iso3dfd", 2, 16, "pallas", 2)
    b = make("iso3dfd", 2, 16, "pallas", 2)
    ref = make("iso3dfd", 2, 16, "jit", 0)
    start = make("iso3dfd", 2, 16, "jit", 0)
    b.get_var("pressure").set_all_elements_same(0.0)   # then adopts a's
    b.fuse_vars(a)
    assert all(x is y for n in a._state
               for x, y in zip(a._state[n], b._state[n]))
    vel = a._state["vel"][0]
    ref.run_solution(0, 3)
    a.run_solution(0, 3)
    # a moved on; b reads the values both started from, all of them live
    assert a.compare_data(ref) == 0
    assert b.compare_data(start) == 0
    b.run_solution(0, 3)
    assert b.compare_data(ref) == 0 and a.compare_data(ref) == 0
    assert a._state["vel"][0] is vel and b._state["vel"][0] is vel
    for ctx in (a, b, ref, start):
        ctx.end_solution()
