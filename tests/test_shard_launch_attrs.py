"""What a shard program's ``run.launch`` span says about its exchange
(``parallel/shard_step.py _launch_attrs``), the registry counters that
accumulate the same totals, and ``StencilContext.compiled_memory()``.

The program counts slabs and bytes off the exchange schedule as it is
traced; these tests reckon the same numbers from the geometry alone --
ring slots, faces, rounds, the padded shape a slab is cut from -- so a
schedule that starts to skip, repeat or resize a slab shows here.
"""

import json

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.obs import tracer
from yask_tpu.obs.metrics import get_registry
from yask_tpu.ops.tile_planner import TilePlan

X_RANKS = 4
#: stencil, radius, -wf_steps, domain, steps a call
CASES = {"iso3dfd-k2": ("iso3dfd", 2, 2, (64, 16, 128), 10),
         "awp_abc-k1": ("awp_abc", None, 1, (32, 16, 128), 10)}


def make(stencil, radius, K, domain, mode="shard_pallas"):
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil=stencil, radius=radius)
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} "
        f"-mode {mode}" + (f" -wf_steps {K}" if K else ""))
    ctx.set_num_ranks("x", X_RANKS)
    ctx.prepare_solution()
    rng = np.random.RandomState(11)
    for name in ctx.get_var_names():
        var = ctx.get_var(name)
        if not var.get_domain_dim_names():
            var.set_element(0.05, [])
        elif var.get_step_dim_name():
            t = var.get_last_valid_step_index()
            var.set_elements_in_slice(
                rng.rand(*domain).astype(np.float32) * 0.1,
                [t, 0, 0, 0], [t] + [n - 1 for n in domain])
        else:
            var.set_all_elements_same(0.1)
    return ctx


def launches(path):
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    return [r["attrs"] for r in rows if r["name"] == "run.launch"]


def reckoned(ctx, K, n):
    """``(halo, rounds, slabs, bytes)`` of one ``n``-step shard_pallas
    launch, from the geometry: an interior shard sends two faces of
    every refreshed slot; a slab is ``halo`` planes of the padded
    per-shard array; the launch refreshes every slot of every array
    once, then after each K-group but the last the ``min(K, slots)``
    newest slots of the written fields."""
    prog = ctx._program
    halo = TilePlan(prog, K).halo("x")
    rad = ctx._ana.fused_step_radius()
    pad = {d: TilePlan(prog, K).halo(d) for d in ("x", "y")}
    pad["z"] = rad["z"] * K
    local = ctx._csol.plan(
        ctx._opts.rank_domain_sizes,
        global_sizes=ctx._opts.global_domain_sizes,
        extra_pad={d: (w, w) for d, w in pad.items()})
    first = later = first_bytes = later_bytes = 0
    for name, g in local.geoms.items():
        if g.is_scratch or "x" not in g.domain_dims:
            continue
        shape = list(g.shape)
        shape[g.axis_of("x")] = halo
        slab_bytes = int(np.prod(shape)) * np.dtype(local.dtype).itemsize
        slots = prog.geoms[name].num_slots
        first += 2 * slots
        first_bytes += 2 * slots * slab_bytes
        if g.is_written:
            later += 2 * min(K, slots)
            later_bytes += 2 * min(K, slots) * slab_bytes
    rounds = -(-n // K) - 1
    return (halo, 1 + rounds, first + rounds * later,
            first_bytes + rounds * later_bytes)


@pytest.fixture()
def trace_file(tmp_path, monkeypatch):
    path = tmp_path / "TRACE_EVENTS.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    monkeypatch.setattr(tracer, "_compact_checked", False)
    return path


@pytest.mark.parametrize("case", sorted(CASES))
def test_launch_attrs_are_what_the_geometry_gives(case, trace_file,
                                                  monkeypatch):
    stencil, radius, K, domain, n = CASES[case]
    ctx = make(stencil, radius, K, domain)
    counters = get_registry()
    before = (counters.counter("run.exchange_slabs").value,
              counters.counter("run.exchange_bytes").value)
    ctx.run_solution(0, n - 1)
    ctx.run_solution(n, 2 * n - 1)          # the cached key: same attrs
    first, second = launches(trace_file)
    # how a launch found the state is the launch's own; the rest is
    # the program's, and the cached key's are the same
    assert (first.pop("rest"), second.pop("rest")) == ("interior",
                                                       "padded")
    assert first == second and first["k"] == n
    halo, rounds, slabs, nbytes = reckoned(ctx, K, n)
    assert first["stages"] == len(ctx._ana.stages)
    assert first["stages"] == {"iso3dfd": 1, "awp_abc": 4}[stencil]
    assert first["halo"] == halo == ctx._ana.fused_step_radius()["x"] * K
    assert first["xrounds"] == rounds
    assert first["xslabs"] == slabs > 0
    assert first["xbytes"] == nbytes > 0
    # the same totals, accumulated beside the run's other counters
    assert counters.counter("run.exchange_slabs").value - before[0] \
        == 2 * slabs
    assert counters.counter("run.exchange_bytes").value - before[1] \
        == 2 * nbytes
    # every attr is a scalar: it reaches the profiler's host plane too
    assert all(isinstance(v, (int, str, float)) for v in first.values())
    # and without YT_TRACE the run leaves the same bits
    monkeypatch.delenv("YT_TRACE")
    twin = make(stencil, radius, K, domain)
    twin.run_solution(0, n - 1)
    twin.run_solution(n, 2 * n - 1)
    assert len(launches(trace_file)) == 2           # nothing was added
    assert ctx.compare_data(twin, epsilon=0.0, abs_epsilon=0.0) == 0


def test_awp_abc_slab_counts_spelt_out():
    """The cell's own case, by hand: 18 ring slots and 5 read-only
    arrays refreshed once, then 9 rounds of the 12 written fields'
    newest slot, two faces each.  A slab is 4 planes, the step's
    longest chain (old stress -> velocity -> stress), of the padded
    48 x 256: two thirds of the bytes that the 6 planes of every
    stage's widest read added up (PR 37) sent."""
    stencil, radius, K, domain, n = CASES["awp_abc-k1"]
    ctx = make(stencil, radius, K, domain)
    halo, rounds, slabs, nbytes = reckoned(ctx, K, n)
    assert (rounds, slabs) == (10, 2 * (18 + 5) + 9 * 2 * 12)
    assert halo == 4
    assert nbytes == slabs * 4 * 48 * 256 * 4
    assert 3 * nbytes == 2 * 77266944       # slabs * 6 * 48 * 256 * 4


def test_shard_map_launches_carry_the_attrs_from_the_first_on(trace_file):
    """The lazily jitted ``shard_map`` program is traced before its
    first launch, so that one's span already knows: every slot of
    ``pressure`` (the one array with a halo) once, then its newest slot
    every step, two faces."""
    ctx = make("iso3dfd", 2, None, (64, 16, 128), mode="shard_map")
    ctx.run_solution(0, 3)
    ctx.run_solution(4, 7)
    first, second = launches(trace_file)
    assert (first.pop("rest"), second.pop("rest")) == ("interior",
                                                       "padded")
    assert first == second
    slots = ctx._program.geoms["pressure"].num_slots
    assert (first["stages"], first["halo"], first["xrounds"]) == (1, 2, 5)
    assert first["xslabs"] == 2 * slots + 4 * 2
    plane = (16 + 4) * (128 + 4) * 4            # y and z with their halos
    assert first["xbytes"] == first["xslabs"] * 2 * plane


class _NoAnalysis:
    def memory_analysis(self):
        return None


class _Refuses:
    def memory_analysis(self):
        raise NotImplementedError("this backend gives none")


def test_compiled_memory_has_a_row_per_analysed_executable():
    stencil, radius, K, domain, n = CASES["iso3dfd-k2"]
    ctx = make(stencil, radius, K, domain)
    assert ctx.compiled_memory() == []              # nothing built yet
    ctx.run_solution(0, n - 1)
    rows = ctx.compiled_memory()
    # the program, and the pad and the strip of each array shape
    # (pressure's two slots share one; vel's) that bring the state to
    # its form and back
    assert len(rows) == len(ctx.compiled_texts()) == 5
    assert sorted(r["kind"] for r in rows) == [
        "shard_pad", "shard_pad", "shard_pallas", "shard_strip",
        "shard_strip"]
    row, = [r for r in rows if r["kind"] == "shard_pallas"]
    assert set(row) == {"kind", "temp_bytes", "argument_bytes",
                        "output_bytes", "alias_bytes",
                        "generated_code_bytes"}
    # one shard's ring of padded arrays is what goes in and comes out,
    # in the buffers it came in by
    interiors = sum(g.num_slots for g in ctx._program.geoms.values()) \
        * int(np.prod(domain)) // X_RANKS * 4
    assert row["argument_bytes"] > interiors
    assert row["alias_bytes"] > interiors
    assert row["temp_bytes"] > 0
    # where the backend gives no analysis there is no row, and no error
    ctx._jit_cache.clear()
    ctx._jit_cache[("fake", 0)] = _NoAnalysis()
    ctx._jit_cache[("fake", 1)] = _Refuses()
    ctx._jit_cache[("fake", 2)] = lambda *a: None   # not compiled ahead
    assert ctx.compiled_memory() == []


def test_an_interpreted_pallas_context_has_no_compiled_memory():
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="iso3dfd", radius=2)
    ctx.apply_command_line_options("-g 16 -mode pallas -wf_steps 2")
    ctx.prepare_solution()
    ctx.get_var("vel").set_all_elements_same(0.05)
    ctx.run_solution(0, 1)
    assert ctx._jit_cache and ctx.compiled_memory() == []
