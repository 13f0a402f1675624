"""Hoisting of step-invariant scratch vars (PR 49): the rule
(``SolutionAnalysis._find_hoisted``), the fill (``StepProgram.derive``,
``StencilContext._refresh_derived``) and what carries a derived array
and what does not.

A scratch var whose value depends only on read-only arrays, constants
and domain index values is the same at step 1 and at step 10 000: where
its right-hand side holds a function node or a division it becomes a
read-only array of the step program, filled once on the device, with
``f(source's ghost)`` in its ghost cells -- what the in-tile evaluation
computes there.  Every mode must give the un-hoisted answer.
"""

import json

import numpy as np
import pytest

from yask_tpu import yk_factory
from yask_tpu.compiler.analysis import SolutionAnalysis
from yask_tpu.compiler.expr import exp, sin, sqrt
from yask_tpu.compiler.lowering import CompiledSolution
from yask_tpu.compiler.solution import yc_factory
from yask_tpu.compiler.solution_base import create_solution
from yask_tpu.obs.metrics import get_registry
from yask_tpu.runtime.context import StencilContext
from yask_tpu.runtime.init_utils import init_solution_vars

TRIG = ["ti0", "ti1", "ti2", "ti3"]


# ---- the rule ----------------------------------------------------------

def adhoc(kind):
    """A 2-D solution with two scratch vars ``s1`` -> ``s2`` -> ``A``
    over a read-only array ``C``; ``kind`` says what ``s1``/``s2``
    are."""
    soln = yc_factory().new_solution(f"hoist_{kind}")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    a = soln.new_var("A", [t, x, y])
    c = soln.new_var("C", [x, y])
    s1 = soln.new_scratch_var("s1", [x, y])
    s2 = soln.new_scratch_var("s2", [x, y])
    if kind == "chain":
        # invariant -> invariant read with a halo: hoisted whole, the
        # adds-only s2 with the chain whose function node it carries
        s1(x, y).EQUALS(exp(c(x, y)) * 0.5)
        s2(x, y).EQUALS(s1(x - 1, y) + s1(x + 1, y + 2))
    elif kind == "stepped":
        # invariant -> reads a stepped var: s1 goes, s2 stays
        s1(x, y).EQUALS(sqrt(c(x, y) + 2.0))
        s2(x, y).EQUALS(s1(x + 1, y) * a(t, x, y - 1))
    elif kind == "cheap":
        # adds and multiplies of a read-only array: reading it costs
        # what reading its sources costs
        s1(x, y).EQUALS(c(x - 1, y) + c(x + 1, y) * 0.5)
        s2(x, y).EQUALS(s1(x, y + 1) - s1(x, y))
    elif kind == "division":
        s1(x, y).EQUALS(1.0 / (c(x, y) + 2.0))
        s2(x, y).EQUALS(s1(x, y + 1) * a(t, x, y))
    elif kind == "index":
        # an index value of a domain dim is step-invariant too
        s1(x, y).EQUALS(exp(-0.05 * x) * c(x, y))
        s2(x, y).EQUALS(s1(x, y) * a(t, x + 1, y))
    elif kind == "step_index":
        s1(x, y).EQUALS(sin(c(x, y)) * t)
        s2(x, y).EQUALS(s1(x, y + 1) + s1(x, y))
    elif kind == "cond":
        # a hoisted var written under sub-domain conditions
        from yask_tpu.compiler.node_api import yc_node_factory
        inner = x > yc_node_factory().new_first_domain_index(x) + 2
        s1(x, y).EQUALS(exp(c(x, y))).IF_DOMAIN(inner)
        s1(x, y).EQUALS(-sqrt(c(x, y) + 1.0)).IF_DOMAIN(~inner)
        s2(x, y).EQUALS(s1(x - 2, y) + s1(x + 1, y + 1))
    else:
        raise AssertionError(kind)
    a(t + 1, x, y).EQUALS(a(t, x, y) * 0.5 + s2(x, y - 1) * 0.125
                          + s2(x + 2, y) * 0.25)
    return soln


def partial():
    """A step-invariant scratch var worth an array that lacks a
    domain dim: not an array like the solution's own."""
    soln = yc_factory().new_solution("hoist_partial")
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    a = soln.new_var("A", [t, x, y])
    cx = soln.new_var("CX", [x])
    s1 = soln.new_scratch_var("s1", [x])
    s1(x).EQUALS(exp(cx(x)))
    a(t + 1, x, y).EQUALS(a(t, x, y) * s1(x))
    return soln


def registered(name, radius=None):
    return create_solution(name, radius=radius).get_soln()


RULE = [
    # (solution, hoisted, hoist_kept, scratch vars left in-tile)
    (lambda: registered("tti", 4), TRIG, {}, ["gu", "gv"]),
    (lambda: registered("tti", 2), TRIG, {}, ["gu", "gv"]),
    # every bundled scratch var reads a stepped var: nothing is invariant
    # (stencils/physics2d.py: the shallow-water fluxes)
    (lambda: registered("swe2d"), [], {},
     ["f_h", "f_hu", "f_hv", "g_h", "g_hu", "g_hv"]),
    (lambda: registered("wave2d", 2), [], {}, []),
    (lambda: registered("test_scratch_1d"), [], {}, ["B"]),
    (lambda: registered("test_scratch_2d"), [], {}, ["t1", "t2", "t3"]),
    (lambda: registered("test_scratch_3d"), [], {}, ["t1", "t2", "t3"]),
    (lambda: registered("test_scratch_stages_1d"), [], {},
     ["C", "D", "E"]),
    (lambda: registered("test_scratch_boundary_1d"), [], {}, ["T1"]),
    # the other cells' solutions declare no scratch var
    (lambda: registered("iso3dfd", 8), [], {}, []),
    (lambda: registered("iso3dfd_sponge", 8), [], {}, []),
    (lambda: registered("cube", 1), [], {}, []),
    (lambda: registered("ssg", 4), [], {}, []),
    (lambda: registered("awp_abc"), [], {}, []),
    (lambda: adhoc("chain"), ["s1", "s2"], {}, []),
    (lambda: adhoc("stepped"), ["s1"], {}, ["s2"]),
    (lambda: adhoc("cheap"), [], {"s1": "cheap", "s2": "cheap"},
     ["s1", "s2"]),
    (lambda: adhoc("division"), ["s1"], {}, ["s2"]),
    (lambda: adhoc("index"), ["s1"], {}, ["s2"]),
    (lambda: adhoc("step_index"), [], {}, ["s1", "s2"]),
    (lambda: adhoc("cond"), ["s1", "s2"], {}, []),
    (partial, [], {"s1": "shape"}, ["s1"]),
]
RULE_IDS = ["tti-r4", "tti-r2", "swe2d", "wave2d", "scratch_1d", "scratch_2d",
            "scratch_3d", "scratch_stages_1d", "scratch_boundary_1d",
            "iso3dfd", "iso3dfd_sponge", "cube", "ssg", "awp_abc",
            "chain", "stepped", "cheap", "division", "index",
            "step_index", "cond", "partial"]


@pytest.mark.parametrize("build,hoisted,kept,tile", RULE, ids=RULE_IDS)
def test_the_rule(build, hoisted, kept, tile):
    soln = build()
    ana = soln.analyze()
    assert ana.hoisted == hoisted
    assert ana.hoist_kept == kept
    assert ana.tile_scratch == tile
    # the step program holds every equation but the hoisted vars'
    assert len(ana.eqs) + len(ana.derive_eqs) == len(ana.all_eqs)
    assert {eq.lhs.var_name() for eq in ana.derive_eqs} == set(hoisted)
    assert not {eq.lhs.var_name() for eq in ana.eqs} & set(hoisted)
    # no stage evaluates a hoisted var
    assert not {eq.lhs.var_name() for st in ana.stages
                for part in st.parts for eq in part.eqs} & set(hoisted)
    # declined, every scratch var is in-tile and says why where the
    # rule would have taken it
    plain = SolutionAnalysis(soln, hoist=False)
    assert plain.hoisted == [] and plain.derive_eqs == []
    assert len(plain.eqs) == len(ana.all_eqs)
    assert plain.hoist_kept == {**kept,
                                **{n: "declined" for n in hoisted}}


def test_a_chain_is_ordered_and_padded_by_its_write_halo():
    soln = adhoc("chain")
    ana = soln.analyze()
    # s1 before s2, which reads it
    assert [eq.lhs.var_name() for eq in ana.derive_eqs] == ["s1", "s2"]
    assert ana.derive_sources == ["C"]
    # C is read by nothing but the fill; s1 by nothing but s2's
    assert ana.derive_only == {"C", "s1"}
    # s2 is read at y-1 and x+2; s1 at x-1 and (x+1, y+2) of s2's region
    assert ana.scratch_write_halo["s2"] == {"x": (0, 2), "y": (1, 0)}
    assert ana.scratch_write_halo["s1"] == {"x": (1, 3), "y": (1, 2)}
    from yask_tpu.utils.idx_tuple import IdxTuple
    prog = soln.compile().plan(IdxTuple(x=12, y=10), mosaic_align=False)
    for name, halo in (("s1", {"x": (1, 3), "y": (1, 2)}),
                       ("s2", {"x": (0, 2), "y": (1, 0)})):
        g = prog.geoms[name]
        assert g.is_derived and not g.is_scratch and not g.is_written
        assert g.num_slots == 1 and not g.has_step
        assert g.pads == halo
    assert not prog.geoms["C"].is_derived
    # the first fill creates them, not the allocation; a step moves no
    # byte of C or s1
    state = prog.alloc_state()
    assert set(state) == {"A", "C"}
    assert set(prog.derive(state)) == {"s1", "s2"}
    read, _written = prog.hbm_bytes_per_point()
    assert read == 4.0 * sum(
        np.prod(prog.geoms[n].shape) * prog.geoms[n].num_slots
        for n in ("A", "s2")) / (12 * 10)


# ---- the answer, mode by mode --------------------------------------------

def make_tti(mode, dom, extra="", hoist=True, **settings):
    """``tti`` at radius 4, hoisted (the default) or with every scratch
    var in-tile (the parent's evaluation)."""
    fac = yk_factory()
    env = fac.new_env()
    if hoist:
        ctx = fac.new_solution(env, stencil="tti", radius=4)
    else:
        soln = registered("tti", 4)
        ctx = StencilContext(env, CompiledSolution(
            soln, SolutionAnalysis(soln, hoist=False)))
    ctx.apply_command_line_options(
        f"-g_x {dom[0]} -g_y {dom[1]} -g_z {dom[2]} -mode {mode} {extra}")
    for key, value in settings.items():
        setattr(ctx.get_settings(), key, value)
    ctx.prepare_solution()
    init_solution_vars(ctx)
    return ctx


def fills():
    return get_registry().counter("state.derived_fills").value


ODD = (18, 21, 37)          # no extent a multiple of 8


#: the modes that carry a derived array, and the ones that decline
#: (``StencilContext.IN_TILE_MODES``)
CARRY = [("jit", ""), ("pallas", "-wf_steps 1"), ("sharded", "-nr_x 2")]
DECLINE = [("shard_pallas", "-wf_steps 1 -nr_x 2"), ("shard_map", "-nr_x 2"),
           ("ref", "")]


@pytest.mark.parametrize("mode,extra", CARRY + DECLINE)
def test_tti_hoisted_is_the_oracle_and_the_in_tile_evaluation(mode, extra):
    """Every point of ``u`` and ``v`` after 3 steps on an odd box: the
    default context against the numpy oracle (which evaluates the trig
    in-step) and against the parent's in-tile evaluation in the same
    mode.  A ghost cell of ``ti1``/``ti2`` holds cos(0) = 1.  A mode
    that cannot carry a derived array declines from what it observes,
    the mode, and says so."""
    ctx = make_tti(mode, ODD, extra)
    carries = (mode, extra) in CARRY
    assert carries == (mode not in StencilContext.IN_TILE_MODES)
    if carries:
        assert ctx._ana.hoisted == TRIG
        assert ctx._ana.tile_scratch == ["gu", "gv"]
    else:
        assert ctx._ana.hoisted == []
        assert ctx._ana.hoist_kept == {n: "declined" for n in TRIG}
        assert ctx._ana.tile_scratch == TRIG + ["gu", "gv"]
    before = fills()
    ctx.run_solution(0, 2)
    oracle = make_tti("jit", ODD, hoist=False)
    oracle.run_ref(0, 2)
    assert ctx.compare_data(oracle) == 0
    tile = make_tti(mode, ODD, extra, hoist=False)
    assert tile._ana.tile_scratch == TRIG + ["gu", "gv"]
    tile.run_solution(0, 2)
    assert ctx.compare_data(tile) == 0
    if not carries:
        assert fills() == before
        ctx._materialize_state()
        assert not set(TRIG) & set(ctx._state)
    else:
        assert fills() == before + 1
        g = ctx._program.geoms["ti2"]
        ti2 = np.asarray(ctx._state["ti2"][0])
        assert ti2.shape == tuple(g.shape)
        assert ti2[0, 0, 0] == 1.0 and ti2[-1, -1, -1] == 1.0
        assert np.asarray(ctx._state["ti0"][0])[0, 0, 0] == 0.0  # sin(0)
        # every cell, not the halo's alone
        assert (ti2[:g.origin["x"]] == 1.0).all()
    if mode in ("pallas", "shard_pallas"):
        row = ctx.compiled_plans()[-1]
        declined = {n: "declined" for n in TRIG}
        if carries:
            assert row["hoisted"] == TRIG and row["hoist_kept"] == {}
            assert not {s for s in row["fetch_windows"]
                        if s.startswith(("theta", "phi"))}
            assert {f"ti{i}/0" for i in range(4)} <= set(
                row["fetch_windows"])
        else:
            assert row["hoisted"] == [] and row["hoist_kept"] == declined
        assert tile.compiled_plans()[-1]["hoisted"] == []
        assert tile.compiled_plans()[-1]["hoist_kept"] == declined
    for c in (ctx, oracle, tile):
        c.end_solution()


def test_a_mode_change_between_prepares_changes_who_evaluates_the_trig():
    """The decline is the prepared mode's, decided where the mode is
    resolved: the same context hoists again once it is prepared in a
    mode that carries the arrays."""
    ctx = make_tti("ref", (16, 16, 32))
    assert ctx._ana.hoisted == []
    ctx.apply_command_line_options("-mode jit")
    ctx.prepare_solution()
    assert ctx._ana.hoisted == TRIG and ctx._program.ana is ctx._ana
    assert not set(TRIG) & set(ctx._state)      # until the first run
    init_solution_vars(ctx)
    n = fills()
    ctx.run_solution(0, 0)
    assert fills() == n + 1 and set(TRIG) <= set(ctx._state)
    ctx.end_solution()


def test_the_oracle_of_a_hoisting_context_evaluates_in_step(monkeypatch):
    """``run_ref`` on a default context is independent of the fill: a
    fault planted in ``derive`` is seen by the compiled run and not
    shared by the oracle that checks it (``-validate``)."""
    from yask_tpu.compiler.lowering import StepProgram
    derive = StepProgram.derive

    def off(self, state):
        return {name: [ring[0] * 0.5] for name, ring in
                derive(self, state).items()}

    dom = (16, 16, 32)
    sound = make_tti("jit", dom, hoist=False)
    sound.run_ref(0, 2)
    monkeypatch.setattr(StepProgram, "derive", off)
    ctx, ref = make_tti("jit", dom), make_tti("jit", dom)
    assert ref._ana.hoisted == TRIG
    n = fills()
    ref.run_ref(0, 2)
    assert fills() == n and ref.compare_data(sound) == 0
    ctx.run_solution(0, 2)
    assert ctx.compare_data(ref) > 0
    # and on a state that holds (faulty) derived arrays already
    ctx.run_ref(3, 3)
    sound.run_ref(3, 3)
    ref.run_ref(3, 3)
    assert ref.compare_data(sound) == 0 and set(TRIG) <= set(ctx._state)
    for c in (ctx, ref, sound):
        c.end_solution()


@pytest.mark.parametrize("mode", ["jit", "pallas"])
def test_auto_tune_leaves_no_stale_derived_array(mode, monkeypatch):
    """``-auto_tune`` in pallas mode ends by rebuilding every array of
    the state on the tuned K's pads: a derived array is not migrated
    with zero pads, it is filled anew at the new shape, and every point
    is the in-tile evaluation's -- in the tuned call and in the next."""
    from yask_tpu.runtime.auto_tuner import AutoTuner
    walk = AutoTuner._walk_joint

    def settle_on_k1(self):
        walk(self)      # the real trials (whose winner the clock picks)
        self.ctx._opts.wf_steps = 1
        return 1

    monkeypatch.setattr(AutoTuner, "_walk_joint", settle_on_k1)
    dom = (16, 16, 32)
    ctx = make_tti(mode, dom, "-wf_steps 1", do_auto_tune=True,
                   auto_tune_trial_secs=0.001, tune_max_wf_steps=2)
    shape = tuple(ctx._program.geoms["ti2"].shape)
    n = fills()
    ctx.run_solution(0, 2)
    oracle = make_tti("jit", dom, hoist=False)
    oracle.run_ref(0, 2)
    assert ctx.compare_data(oracle) == 0
    g = ctx._program.geoms["ti2"]
    ti2 = np.asarray(ctx._state["ti2"][0])
    assert ti2.shape == tuple(g.shape)
    assert ti2[0, 0, 0] == 1.0 and ti2[-1, -1, -1] == 1.0
    if mode == "pallas":
        # the trials read one fill, the tuned shape another
        assert tuple(g.shape) != shape and fills() == n + 2
    else:
        assert fills() == n + 1
    ctx.run_solution(3, 5)
    oracle.run_ref(3, 5)
    assert ctx.compare_data(oracle) == 0
    assert fills() == n + (2 if mode == "pallas" else 1)
    # the tuner called before any run (``-pre_auto_tune``) fills them
    # for its trials itself
    cold = make_tti(mode, dom, "-wf_steps 1", tune_max_wf_steps=2)
    assert not set(TRIG) & set(cold._state)
    cold.run_auto_tuner_now(min_trial_secs=0.001)
    cold.run_solution(0, 5)
    assert cold.compare_data(oracle) == 0
    for c in (ctx, oracle, cold):
        c.end_solution()


@pytest.mark.parametrize("mode", ["jit", "pallas"])
def test_zeroed_pads_of_a_derived_array_fail_at_the_corner(
        mode, monkeypatch):
    """The fault the cell's ``corner`` probe is there for: a derived
    array whose ghost cells are zero, not ``f(0)``."""
    from yask_tpu.compiler.lowering import StepProgram
    derive = StepProgram.derive

    def zeroed(self, state):
        out = derive(self, state)
        for name, (arr,) in out.items():
            g = self.geoms[name]
            inner = tuple(slice(g.origin[d], g.origin[d] + self.sizes[d])
                          for d in g.domain_dims)
            mask = np.zeros(arr.shape, bool)
            mask[inner] = True
            out[name] = [self.ops.where(mask, arr, 0.0)]
        return out

    monkeypatch.setattr(StepProgram, "derive", zeroed)
    ctx = make_tti(mode, ODD, "-wf_steps 1" if mode == "pallas" else "")
    ctx.run_solution(0, 2)
    oracle = make_tti("jit", ODD, hoist=False)
    oracle.run_ref(0, 2)
    assert ctx.compare_data(oracle) > 0
    t = ctx.get_var("u").get_last_valid_step_index()
    assert ctx.get_var("u").get_element([t, 0, 0, 0]) != pytest.approx(
        oracle.get_var("u").get_element([t, 0, 0, 0]), rel=1e-4)


@pytest.mark.parametrize("kind", ["chain", "index", "cond", "stepped"])
@pytest.mark.parametrize("mode", ["jit", "pallas"])
def test_a_hoisted_chain_is_the_in_tile_evaluation(kind, mode):
    """Read with a halo, an index value, sub-domain conditions: the
    ad-hoc solutions, hoisted against in-tile against the oracle."""
    def run(hoist, how):
        soln = adhoc(kind)
        ctx = StencilContext(yk_factory().new_env(), CompiledSolution(
            soln, SolutionAnalysis(soln, hoist=hoist)))
        ctx.apply_command_line_options(
            f"-g_x 19 -g_y 140 -mode {mode if how == 'run' else 'jit'} "
            "-wf_steps 1")
        ctx.prepare_solution()
        init_solution_vars(ctx)
        if how == "run":
            ctx.run_solution(0, 2)
        else:
            ctx.run_ref(0, 2)
        return ctx
    ctx, tile, oracle = run(True, "run"), run(False, "run"), \
        run(False, "ref")
    assert ctx._ana.hoisted and not tile._ana.hoisted
    assert ctx.compare_data(tile) == 0
    assert ctx.compare_data(oracle) == 0


# ---- when it is filled ---------------------------------------------------

@pytest.mark.parametrize("mode,extra", [("jit", ""),
                                        ("pallas", "-wf_steps 1")])
def test_a_write_to_a_source_is_seen_and_an_untouched_run_is_free(
        mode, extra, tmp_path, monkeypatch):
    path = tmp_path / "T.jsonl"
    monkeypatch.setenv("YT_TRACE_EVENTS", str(path))
    monkeypatch.setenv("YT_TRACE", "1")
    dom = (16, 16, 32)
    ctx = make_tti(mode, dom, extra)
    tile = make_tti(mode, dom, extra, hoist=False)
    assert [v.get_name() for v in ctx.get_vars()] == \
        [v.get_name() for v in tile.get_vars()]         # no ``ti*``
    base = fills()
    both = (ctx, tile)
    t = 0

    def advance():
        nonlocal t
        for c in both:
            c.run_solution(t, t + 1)
        t += 2
        assert ctx.compare_data(tile) == 0

    advance()
    assert fills() == base + 1              # the first run after prepare
    held = [ctx._state[n][0] for n in TRIG]
    advance()
    assert fills() == base + 1              # untouched: no refill
    # (the XLA chunk hands every array back as another object)
    assert mode == "jit" or all(
        a is b for a, b in zip(held, (ctx._state[n][0] for n in TRIG)))
    # a public write to theta after prepare
    for c in both:
        c.get_var("theta").set_element(0.7, [3, 4, 5])
    advance()
    assert fills() == base + 2
    assert np.asarray(ctx._state["ti0"][0])[
        tuple(ctx._program.geoms["ti0"].origin[d] + i
              for d, i in zip("xyz", (3, 4, 5)))] == pytest.approx(
        np.sin(np.float32(0.7)), rel=1e-6)
    # a device array put into ctx._state itself, the way the benchmark
    # seeds a one-chip cell after prepare_solution (device_state.
    # install_array): no flag is set, the object is another
    import jax.numpy as jnp
    for c in both:
        (old,) = c._state["phi"]
        g = c._program.geoms["phi"]
        new = np.zeros(old.shape, np.float32)
        new[tuple(slice(g.origin[d], g.origin[d] + n)
                  for d, n in zip("xyz", dom))] = 0.3
        c._state["phi"] = [jnp.asarray(new)]
    advance()
    assert fills() == base + 3
    advance()
    assert fills() == base + 3
    # the span of every fill: four arrays, their bytes, its seconds
    with open(path) as f:
        rows = [json.loads(ln) for ln in f]
    spans = [r["attrs"] for r in rows if r["name"] == "state.derive"]
    assert len(spans) == 3
    nbytes = 4 * int(np.prod(ctx._program.geoms["ti0"].shape)) * 4
    assert all(a["vars"] == 4 and a["bytes"] == nbytes and a["secs"] > 0
               for a in spans)
    if mode == "pallas":
        # theta and phi are not handed to the launch: u, v in rings of
        # two, four read-only arrays and the four derived ones
        launches = [r["attrs"] for r in rows if r["name"] == "run.launch"]
        assert {(a["written"], a["kept"]) for a in launches} == {
            (2, 10), (2, 8)}                # ctx, and tile with theta, phi
        fn = ctx._get_pallas_chunk(1)
        assert "theta" not in fn.operands and "phi" not in fn.operands
        assert set(TRIG) <= set(fn.operands)
    for c in both:
        c.end_solution()


def test_a_snapshot_carries_no_derived_array_and_a_restore_rebuilds_them(
        tmp_path):
    from yask_tpu.resilience import checkpoint as ckpt
    dom = (16, 16, 32)
    ctx = make_tti("jit", dom)
    ctx.run_solution(0, 1)
    snap = ckpt.extract_snapshot(ctx)
    assert not set(TRIG) & set(snap["state"])
    assert not set(TRIG) & set(snap["meta"]["rings"])
    assert set(snap["state"]) == {v.get_name() for v in ctx.get_vars()}
    ctx.save_checkpoint(str(tmp_path / "c"))
    with np.load(str(tmp_path / "c.npz")) as data:
        assert not [k for k in data if k.startswith("ti")]
    ctx.run_solution(2, 3)
    want = ckpt.extract_snapshot(ctx)
    # into a context whose sources differ: the restore leaves the
    # derived arrays stale, the next run rebuilds them
    other = make_tti("jit", dom)
    other.get_var("theta").set_all_elements_same(0.2)
    other.run_solution(0, 0)
    stale = np.asarray(other._state["ti0"][0]).copy()
    n = fills()
    assert ckpt.apply_snapshot(other, snap)
    assert set(TRIG) <= set(other._state)
    other.run_solution(2, 3)
    assert fills() == n + 1
    assert not (np.asarray(other._state["ti0"][0]) == stale).all()
    assert ckpt.snapshot_mismatches(ckpt.extract_snapshot(other), want) == 0
    # and through the context's own file round trip
    third = make_tti("jit", dom)
    third.load_checkpoint(str(tmp_path / "c"))
    third.run_solution(2, 3)
    assert ckpt.snapshot_mismatches(ckpt.extract_snapshot(third), want) == 0
    for c in (ctx, other, third):
        c.end_solution()


def test_the_ensemble_declines_and_gives_each_members_answer():
    """Stacked members run the program with every scratch var in-tile
    (``ctx._in_tile_program``): a member's ``theta`` is its own."""
    dom = (16, 16, 32)
    ctx = make_tti("jit", dom)
    ens = ctx.new_ensemble(2)
    with ens.member(1):
        init_solution_vars(ctx)
        ctx.get_var("theta").set_all_elements_same(0.4)
    n = fills()
    ens.run(0, 2)
    assert ens.batched_reason == "" and fills() == n
    assert ctx._in_tile_program().ana.hoist_kept == {
        name: "declined" for name in TRIG}
    for i, theta in ((0, None), (1, 0.4)):
        solo = make_tti("jit", dom, hoist=False)
        if theta is not None:
            solo.get_var("theta").set_all_elements_same(theta)
        solo.run_solution(0, 2)
        with ens.member(i):
            assert ctx.compare_data(solo) == 0
        solo.end_solution()
    # a member goes on alone: its derived arrays are filled then
    with ens.member(1):
        ctx.run_solution(3, 3)
    assert fills() == n + 1
    ctx.end_solution()
