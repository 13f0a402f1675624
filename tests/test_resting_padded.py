"""A sharded state rests padded between calls (``RunState.padded``,
``parallel/shard_step.py RestGeom`` / ``rest_padded`` / ``strip_rest``):
the shard program takes the padded shards the last call left and hands
them back, and the one pad and the one strip of a shard state are
programs of their own that run only when something other than the next
launch asks for the state.

Held here, in interpret mode on four of the virtual CPU devices, for
iso3dfd at K=2 on x/4 and on 2x2, ``awp_abc`` at K=1 on x/4 and
``lbm_d3q19`` at K=1 on x/4 with its one-way exchange: (a) calls in a
row are, to the bit, the same calls with a strip and a re-pad forced
between them, and agree with the ``ref`` oracle; (b) the program's
optimised HLO has no pad, no slice and no copy of a shard-sized array
in its entry computation; (c) the launch spans and the counters say
which launch padded; (d) a host write through each public route lands
in the next call and leaves the physical pads zero; (e) another
``-wf_steps`` converts once; (f) a launch that fails after donation
loses the state, one that fails before it does not; (g) ghost rows made
NaN at rest show nowhere.
"""

import json
import re

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.obs import tracer
from yask_tpu.obs.metrics import get_registry
from yask_tpu.utils.exceptions import YaskException

#: stencil, radius, -wf_steps, domain, ranks
CASES = {
    "iso3dfd-k2-x4": ("iso3dfd", 2, 2, (64, 16, 128), (4, 1, 1)),
    "iso3dfd-k2-2x2": ("iso3dfd", 2, 2, (32, 48, 128), (2, 2, 1)),
    "awp_abc-k1-x4": ("awp_abc", None, 1, (32, 16, 128), (4, 1, 1)),
    # z of 40 rests as 128 lanes: the pad that is not a ghost row
    "lbm_d3q19-k1-x4": ("lbm_d3q19", None, 1, (16, 32, 40), (4, 1, 1)),
}
N = 10          # steps a call
every_case = pytest.mark.parametrize("case", sorted(CASES))


def make(case, mode="shard_pallas"):
    """Dense and seeded, never a lone impulse."""
    stencil, radius, K, domain, ranks = CASES[case]
    fac = yk_factory()
    env = fac.new_env()
    if env.get_num_ranks() < 4:
        pytest.skip("needs four of the virtual CPU devices")
    ctx = fac.new_solution(env, stencil=stencil, radius=radius)
    ctx.apply_command_line_options(
        "-g_x {} -g_y {} -g_z {} ".format(*domain)
        + f"-mode {mode} -wf_steps {K}")
    if mode != "ref":
        for d, r in zip("xyz", ranks):
            if r > 1:
                ctx.set_num_ranks(d, r)
    ctx.prepare_solution()
    rng = np.random.RandomState(57)
    for name in ctx.get_var_names():
        var = ctx.get_var(name)
        if not var.get_domain_dim_names():
            var.set_element(0.05, [])
        elif var.get_step_dim_name():
            for t in range(var.get_first_valid_step_index(),
                           var.get_last_valid_step_index() + 1):
                var.set_elements_in_slice(
                    rng.rand(*domain).astype(np.float32) * 0.1,
                    [t, 0, 0, 0], [t] + [n - 1 for n in domain])
        else:
            var.set_all_elements_same(0.1)
    return ctx


def calls(ctx, count, first=0, between=None):
    for i in range(first, first + count):
        if between is not None and i > first:
            between(ctx)
        ctx.run_solution(i * N, (i + 1) * N - 1)
    return ctx


def same_bits(a, b):
    return a.compare_data(b, epsilon=0.0, abs_epsilon=0.0) == 0


def counts():
    reg = get_registry()
    return np.array([reg.counter("run.state_pads").value,
                     reg.counter("run.state_strips").value])


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    path = tmp_path / "TRACE_EVENTS.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setattr(tracer, "_compact_checked", False)
    return path


def rows(path, name):
    with open(path) as f:
        return [r["attrs"] for r in map(json.loads, f)
                if r["name"] == name]


# ---- (a) a call boundary is one more hand-over -------------------------

@every_case
def test_calls_in_a_row_are_the_calls_with_a_strip_and_a_pad_between(case):
    at_rest = calls(make(case), 3)
    assert at_rest._run.padded is not None
    assert at_rest._run.resident is None and at_rest._run.state is None
    forced = calls(make(case), 3,
                   between=lambda ctx: ctx._materialize_state())
    assert same_bits(at_rest, forced)
    # reading it made it the global padded state, and the only one
    assert at_rest._run.state is not None
    assert at_rest._run.padded is None and at_rest._run.resident is None
    oracle = calls(make(case, mode="ref"), 3)
    assert at_rest.compare_data(oracle, epsilon=1e-3,
                                abs_epsilon=1e-4) == 0


# ---- (b) what the program still holds of the conversion ----------------

def entry_computation(text):
    """The instruction lines of an HLO module's ENTRY computation."""
    lines, inside = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            lines.append(line.strip())
    return lines


def shard_shapes(ctx):
    """One shard's arrays as HLO writes their shapes, padded as the
    program takes them and as interiors."""
    geom = ctx._run.padded_geom
    lsz = ctx._opts.rank_domain_sizes
    fmt = "f32[{}]".format
    padded = {fmt(",".join(map(str, geom.local[k]))) for k in geom.names
              if len(geom.local[k]) == 3}
    return padded, {fmt(",".join(str(lsz[d]) for d in "xyz"))}


@every_case
def test_the_program_neither_pads_nor_strips_nor_copies_a_shard(case):
    ctx = calls(make(case), 2)
    text, = [t for t in ctx.compiled_texts()
             if t.startswith("HloModule jit_yt_shard_pallas,")]
    padded, interior = shard_shapes(ctx)
    entry = entry_computation(text)
    # (interpret mode makes a kernel's output buffer from a broadcast
    # and the CPU compiler may copy that: no array of the state)
    made = [(m.group(1), m.group(2)) for m in (
        re.match(r"(?:ROOT )?%?[\w.\-]+ = (f32\[[\d,]+\])\S* "
                 r"(pad|slice|copy)\((?!%?(?:wrapped_)?broadcast)", ln)
        for ln in entry) if m]
    # the shards do go through it: the shapes are the right ones
    assert any(shape in ln for ln in entry for shape in padded)
    assert not [op for shape, op in made
                if shape in padded and op in ("pad", "copy")], made
    assert not [op for shape, op in made
                if shape in interior and op == "slice"], made
    assert "yt_shard_pad" not in text and "yt_shard_strip" not in text
    # the pad and the strip are programs of their own, one a shape,
    # built with the program; each holds its scope and nothing else
    kinds = [key[0] for key in ctx._jit_cache]
    assert kinds.count("shard_pad") == kinds.count("shard_strip") >= 1
    for scope in ("yt_shard_pad", "yt_shard_strip"):
        own = [t for t in ctx.compiled_texts()
               if t.startswith(f"HloModule jit_{scope},")]
        assert len(own) == kinds.count("shard_pad")
        assert all(f"/{scope}/" in t for t in own)


# ---- (c) the launch says how it found the state ------------------------

@every_case
def test_launches_say_interior_then_padded_and_the_counters_stand(
        case, trace_file):
    ctx = make(case)
    c0 = counts()
    calls(ctx, 3)
    assert [a["rest"] for a in rows(trace_file, "run.launch")] == [
        "interior", "padded", "padded"]
    assert list(counts() - c0) == [1, 0]
    assert [a["strip"] for a in rows(trace_file, "run.repad")] == [False]
    # any reader but the next launch asks for interiors: one strip
    name = next(n for n in ctx.get_var_names()
                if ctx.get_var(n).get_step_dim_name())
    var = ctx.get_var(name)
    var.get_element([var.get_last_valid_step_index(), 1, 1, 1])
    var.get_element([var.get_last_valid_step_index(), 2, 2, 2])
    assert list(counts() - c0) == [1, 1]
    assert ctx._run.resident is not None and ctx._run.padded is None
    calls(ctx, 2, first=3)
    assert [a["rest"] for a in rows(trace_file, "run.launch")][3:] == [
        "interior", "padded"]
    assert list(counts() - c0) == [2, 1]
    assert [a["strip"] for a in rows(trace_file, "run.repad")] == [
        False, True, False]


# ---- (d) a host write between two calls --------------------------------

def write(ctx):
    """One write through each public route, all of interiors."""
    stepped = [n for n in ctx.get_var_names()
               if ctx.get_var(n).get_step_dim_name()]
    fixed = [n for n in ctx.get_var_names()
             if ctx.get_var(n).get_domain_dim_names()
             and not ctx.get_var(n).get_step_dim_name()]
    var = ctx.get_var(stepped[0])
    t = var.get_last_valid_step_index()
    var.set_element(0.75, [t, 3, 5, 7])
    var = ctx.get_var(stepped[-1])
    t = var.get_last_valid_step_index()
    var.set_elements_in_slice(np.full((4, 6, 8), 0.5, np.float32),
                              [t, 2, 3, 4], [t, 5, 8, 11])
    ctx.get_var(fixed[0]).set_all_elements_same(0.11)


def physical_pads_are_zero(ctx):
    """Every pad row of the padded shards at rest that faces no
    neighbour: all of an unsplit dim's, and the outer band of the
    first and the last shard in a split one."""
    geom = ctx._run.padded_geom
    ranks = dict(geom.mesh.shape)
    for name in geom.names:
        for a in ctx._run.padded[name]:
            a = np.asarray(a)
            for ax, dim in enumerate(geom.specs[name]):
                n, cut = geom.local[name][ax], geom.cuts[name][ax]
                if cut == slice(None):
                    continue
                shards = ranks[dim] if dim else 1
                lo = np.take(a, range(0, cut.start), axis=ax)
                hi = np.take(a, range((shards - 1) * n + cut.stop,
                                      shards * n), axis=ax)
                if lo.any() or hi.any():
                    return False
    return True


@every_case
def test_a_host_write_lands_in_the_next_call_and_the_pads_stay_zero(case):
    ctx = calls(make(case), 1)
    write(ctx)
    calls(ctx, 1, first=1)
    assert physical_pads_are_zero(ctx)
    twin = calls(make(case), 1)
    twin._materialize_state()
    write(twin)
    calls(twin, 1, first=1)
    assert same_bits(ctx, twin)
    plain = calls(make(case), 2)            # and the write was seen
    assert not same_bits(ctx, plain)


# ---- (e) another K between two calls -----------------------------------

@every_case
def test_another_wf_steps_converts_once_and_matches(case, trace_file):
    ctx = calls(make(case), 2)
    was = ctx._run.padded_geom
    ctx.apply_command_line_options(f"-wf_steps {3 - CASES[case][2]}")
    c0 = counts()
    calls(ctx, 2, first=2)
    # the ghost width follows K: the state was stripped and padded anew
    assert ctx._run.padded_geom.key != was.key
    assert [a["rest"] for a in rows(trace_file, "run.launch")] == [
        "interior", "padded", "interior", "padded"]
    assert list(counts() - c0) == [1, 1]
    oracle = calls(make(case, mode="ref"), 4)
    assert ctx.compare_data(oracle, epsilon=1e-3, abs_epsilon=1e-4) == 0


# ---- (f) a launch that fails -------------------------------------------

@every_case
def test_a_failure_after_donation_loses_the_state_one_before_does_not(
        case, monkeypatch):
    from yask_tpu.parallel import shard_step
    ctx = calls(make(case), 1)

    def refuses(*a, **kw):
        raise YaskException("no such plan")

    # before: the program of another length cannot be built
    with monkeypatch.context() as m:
        m.setattr(shard_step, "_prep_shard_pallas", refuses)
        with pytest.raises(YaskException, match="no such plan"):
            ctx.run_solution(N, N + 6)
    assert ctx._run.padded is not None
    calls(ctx, 1, first=1)
    assert same_bits(ctx, calls(make(case), 2))

    # after: the launch itself dies with the state in its hands
    def dies(state, t0):
        raise RuntimeError("device lost")

    key, = [k for k in ctx._jit_cache if k[0] == "shard_pallas"]
    ctx._jit_cache[key] = dies
    with pytest.raises(RuntimeError, match="device lost"):
        ctx.run_solution(2 * N, 3 * N - 1)
    assert ctx._run.padded is None and ctx._run.resident is None
    with pytest.raises(YaskException, match="solution state was lost"):
        ctx.compare_data(ctx)
    with pytest.raises(YaskException, match="solution state was lost"):
        ctx.run_solution(2 * N, 3 * N - 1)


# ---- (g) what the ghost rows hold at rest is never read ----------------

@every_case
def test_ghost_rows_made_nan_at_rest_show_nowhere(case):
    from poison import poison_resting_ghosts
    clean = calls(make(case), 2)
    dirty = calls(make(case), 1)
    bands = poison_resting_ghosts(dirty)
    ranks = CASES[case][4]
    # a band either side of every cut of a split dim (a band runs the
    # whole length of the other dims)
    faces = sum(2 * (r - 1) for r in ranks if r > 1)
    arrays = sum(len(ring) for k, ring in dirty._run.padded.items()
                 if dirty._run.padded_geom.local[k])
    assert bands == faces * arrays > 0
    calls(dirty, 1, first=1)
    assert same_bits(dirty, clean)
    for name in dirty.get_var_names():
        var = dirty.get_var(name)
        if var.get_step_dim_name():
            t = var.get_last_valid_step_index()
            sizes = CASES[case][3]
            assert np.isfinite(np.asarray(var.get_elements_in_slice(
                [t, 0, 0, 0], [t] + [s - 1 for s in sizes]))).all()
