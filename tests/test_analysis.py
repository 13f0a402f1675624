"""Tests for equation analysis: validity rules, dependencies, parts/stages,
halos, scratch propagation, lifespans (the behaviors of Eqs.cpp the reference
exercises through its stencil test suite)."""

import pytest

from yask_tpu.compiler.solution import yc_factory
from yask_tpu.utils.exceptions import YaskException


def new_soln(name="s"):
    soln = yc_factory().new_solution(name)
    t = soln.new_step_index("t")
    x = soln.new_domain_index("x")
    y = soln.new_domain_index("y")
    return soln, t, x, y


def test_halo_and_step_dir():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t, x - 2, y) + u(t, x + 3, y) + u(t, x, y - 1))
    ana = soln.analyze()
    assert ana.step_dir == 1
    assert u.halo["x"] == (2, 3)
    assert u.halo["y"] == (1, 0)
    assert u.get_step_alloc_size() == 2


def test_reverse_step_dir():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    u(t - 1, x, y).EQUALS(u(t, x + 1, y) * 0.5)
    ana = soln.analyze()
    assert ana.step_dir == -1


def test_mixed_step_dir_rejected():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    v = soln.new_var("v", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t, x, y))
    v(t - 1, x, y).EQUALS(v(t, x, y))
    with pytest.raises(YaskException):
        soln.analyze()


def test_lhs_rules():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x + 1, y).EQUALS(u(t, x, y))   # offset LHS domain index
    with pytest.raises(YaskException):
        soln.analyze()

    soln2, t2, x2, y2 = new_soln("s2")
    w = soln2.new_var("w", [t2, x2, y2])
    w(t2 + 2, x2, y2).EQUALS(w(t2, x2, y2))  # step offset 2
    with pytest.raises(YaskException):
        soln2.analyze()


def test_intra_step_race_rejected_and_override():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t + 1, x - 1, y) + 1.0)  # reads own new value
    with pytest.raises(YaskException):
        soln.analyze()
    # the reference allows disabling the checker
    # (set_dependency_checker_enabled, yask_compiler_api.hpp:575)
    soln._analysis = None
    soln.set_dependency_checker_enabled(False)
    soln.analyze()


def test_same_step_dependency_makes_stages():
    soln, t, x, y = new_soln()
    a = soln.new_var("a", [t, x, y])
    b = soln.new_var("b", [t, x, y])
    a(t + 1, x, y).EQUALS(a(t, x, y) + b(t, x, y))
    b(t + 1, x, y).EQUALS(a(t + 1, x - 1, y) * 2.0)   # reads new a
    ana = soln.analyze()
    assert len(ana.stages) == 2
    first = ana.stages[0].parts[0].eqs[0].lhs.var_name()
    assert first == "a"
    # b needs fresh ghosts of the newly computed a before stage 2
    # (recorded for the exchange planner)


def test_circular_same_step_dependency_rejected():
    soln, t, x, y = new_soln()
    a = soln.new_var("a", [t, x, y])
    b = soln.new_var("b", [t, x, y])
    a(t + 1, x, y).EQUALS(b(t + 1, x, y) + 1.0)
    b(t + 1, x, y).EQUALS(a(t + 1, x, y) + 1.0)
    with pytest.raises(YaskException):
        soln.analyze()


def test_waw_ordering_preserves_registration_order():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    nfirst = u(t + 1, x, y).EQUALS(u(t, x, y) + 1.0)
    override = u(t + 1, x, y).EQUALS(0.0).IF_DOMAIN(x < 2)
    ana = soln.analyze()
    # the conditional override must be in a later (or same-order later) part
    order = []
    for st in ana.stages:
        for p in st.parts:
            order.extend(p.eqs)
    assert order.index(soln.get_equations()[0]) < \
        order.index(soln.get_equations()[1])


def test_scratch_halo_propagation():
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    s = soln.new_scratch_var("s", [x, y])
    # s computed from u with radius 1; u(t+1) reads s at radius 2
    s(x, y).EQUALS(u(t, x - 1, y) + u(t, x + 1, y))
    u(t + 1, x, y).EQUALS(s(x - 2, y) + s(x + 2, y))
    ana = soln.analyze()
    # s must be computed over domain±2 (write-halo)
    assert ana.scratch_write_halo["s"]["x"] == (2, 2)
    # u's halo must cover write-halo(2) + its own read offset(1) = 3
    assert u.halo["x"][0] >= 3 and u.halo["x"][1] >= 3
    # scratch part runs in the same stage as its consumer
    assert len(ana.stages) == 1
    assert ana.stages[0].parts[0].is_scratch


def test_scratch_rules():
    soln, t, x, y = new_soln()
    with pytest.raises(YaskException):
        soln.new_scratch_var("bad", [t, x, y])  # scratch can't have step dim


def test_misc_dims():
    soln, t, x, y = new_soln()
    c = soln.new_misc_index("c")
    u = soln.new_var("u", [t, x, y])
    k = soln.new_var("k", [c, x, y])
    u(t + 1, x, y).EQUALS(k(0, x, y) * u(t, x - 1, y)
                          + k(2, x, y) * u(t, x + 1, y))
    ana = soln.analyze()
    assert k.misc_range["c"] == (0, 2)
    with pytest.raises(YaskException):
        k(c, x, y)  # misc dim must be a constant index


def test_pointwise_ring_reduction():
    # pure pointwise map needs only 1 ring slot (write-back optimization)
    soln, t, x, y = new_soln()
    u = soln.new_var("u", [t, x, y])
    u(t + 1, x, y).EQUALS(u(t, x, y) * 0.9)
    soln.analyze()
    assert u.get_step_alloc_size() == 1

    # 2nd-order-in-time with pointwise extreme read → 2 slots, not 3
    soln2, t2, x2, y2 = new_soln("s2")
    p = soln2.new_var("p", [t2, x2, y2])
    p(t2 + 1, x2, y2).EQUALS(2.0 * p(t2, x2, y2) - p(t2 - 1, x2, y2)
                             + p(t2, x2 - 1, y2))
    soln2.analyze()
    assert p.get_step_alloc_size() == 2

    # but a spatial read at the extreme offset forces the full span
    soln3, t3, x3, y3 = new_soln("s3")
    q = soln3.new_var("q", [t3, x3, y3])
    q(t3 + 1, x3, y3).EQUALS(q(t3, x3, y3) - q(t3 - 1, x3 - 1, y3))
    soln3.analyze()
    assert q.get_step_alloc_size() == 3


def test_sincos_pairing_counted_once():
    """sin(x)+cos(x) on one argument is charged a single transcendental
    (reference PairingVisitor, ExprUtils.hpp:137); both lowering
    backends materialize the pair in one visit. TTI's ti0-ti3 rotation
    trig is the motivating case."""
    from yask_tpu.compiler.analysis import SolutionAnalysis
    from yask_tpu.compiler.solution_base import create_solution
    from yask_tpu.compiler.expr import CounterVisitor
    soln = create_solution("tti", radius=2).get_soln()
    # with every scratch var in-tile the trig is a step's work
    ana = SolutionAnalysis(soln, hoist=False)
    assert ana.sincos_args, "tti computes paired sin/cos of theta/phi"
    assert ana.counters.num_paired >= 2
    unpaired = CounterVisitor()
    for eq in ana.eqs:
        eq.accept(unpaired)
    assert ana.counters.num_ops == \
        unpaired.num_ops - ana.counters.num_paired
    # hoisted (the default), a step is charged none of it: the four
    # equations are evaluated once, still as pairs
    hoisted = soln.compile().ana
    assert hoisted.sincos_args == ana.sincos_args
    assert hoisted.counters.num_paired == 0
    assert hoisted.counters.num_ops == ana.counters.num_ops - 2
    assert len(hoisted.eqs) == len(ana.eqs) - 4 == len(hoisted.all_eqs) - 4


def test_partial_dim_write_race_rejected():
    """Writing a var that lacks a domain dim while the RHS (or a
    condition) varies along that dim is an intra-step race: every point
    of the missing extent would demand a different stored value.  The
    reference cannot express this (its loop nest is the LHS var's dims,
    Eqs.cpp:364-470); here it must raise."""
    import pytest
    from yask_tpu import YaskException
    from yask_tpu.compiler.solution import yc_factory

    def build(bad):
        soln = yc_factory().new_solution("pw_race")
        t = soln.new_step_index("t")
        x = soln.new_domain_index("x")
        y = soln.new_domain_index("y")
        a = soln.new_var("A", [t, x, y])
        p = soln.new_var("P", [t, y])
        if bad == "rhs":
            p(t + 1, y).EQUALS(a(t, x, y) * 0.5)
        elif bad == "cond":
            p(t + 1, y).EQUALS(p(t, y) * 0.5).IF_DOMAIN(x >= 4)
        else:
            p(t + 1, y).EQUALS(p(t, y) * 0.5)
        a(t + 1, x, y).EQUALS(a(t, x, y) * 0.5 + p(t, y) * 0.1)
        return soln

    build("ok").compile()   # constant along x: fine
    with pytest.raises(YaskException, match="race"):
        build("rhs").compile()
    with pytest.raises(YaskException, match="race"):
        build("cond").compile()


# ---- the reach of a fused step: its longest chain of dependent stages ----

def _registered(name, radius=None):
    from yask_tpu.compiler.solution_base import create_solution
    return lambda: create_solution(name, radius=radius).get_soln()


def _two_writers():
    """``w`` is written under a band by a stage that has consumed 5 and
    under its complement by a LATER stage whose own chain is 3 long: a
    reader of ``w`` starts from the larger writer, not the last."""
    soln, t, x, y = new_soln("two_writers")
    u = soln.new_var("u", [t, x, y])
    a, c, w, b = (soln.new_var(n, [t, x, y]) for n in "acwb")
    a(t + 1, x, y).EQUALS(u(t, x - 3, y))
    c(t + 1, x, y).EQUALS(a(t + 1, x, y) * 2.0)
    w(t + 1, x, y).EQUALS(a(t + 1, x + 2, y)).IF_DOMAIN(x < 4)
    w(t + 1, x, y).EQUALS(c(t + 1, x, y)).IF_DOMAIN(x >= 4)
    b(t + 1, x, y).EQUALS(w(t + 1, x - 1, y))
    return soln


def _same_point():
    """``b`` reads this step's ``a`` where it was written: 0 wide, but
    ``b`` is whole only where ``a`` is."""
    soln, t, x, y = new_soln("same_point")
    u = soln.new_var("u", [t, x, y])
    a, b, c = (soln.new_var(n, [t, x, y]) for n in "abc")
    a(t + 1, x, y).EQUALS(u(t, x - 2, y))
    b(t + 1, x, y).EQUALS(a(t + 1, x, y) * 2.0)
    c(t + 1, x, y).EQUALS(b(t + 1, x + 1, y))
    return soln


def _ring_of_written():
    """Stage 1 reads the OLD slot of ``a`` 2 away, and this step's
    ``a`` at the same point: the old slot is whole on the whole tile,
    so the ring read starts from 0 and the stage stays at 3."""
    soln, t, x, y = new_soln("ring_of_written")
    u = soln.new_var("u", [t, x, y])
    a, b = (soln.new_var(n, [t, x, y]) for n in "ab")
    a(t + 1, x, y).EQUALS(u(t, x - 3, y) + a(t, x, y))
    b(t + 1, x, y).EQUALS(a(t + 1, x, y) + a(t, x + 2, y))
    return soln


@pytest.mark.parametrize("build, consumed, reach", [
    # velocities off the old stresses, both stress stages off the new
    # velocities (stage 2 reads stage 1 at the same point only), the
    # free surface reads nothing and keeps the region it is handed
    (_registered("awp_abc"), [2, 4, 4, 4], {"x": 4, "y": 4, "z": 4}),
    (_registered("ssg", 4), [4, 8], {"x": 8, "y": 8, "z": 8}),
    (_registered("tti", 4), [8], {"x": 8, "y": 8, "z": 8}),
    (_registered("iso3dfd", 8), [8], {"x": 8, "y": 8, "z": 8}),
    # a true chain keeps its sum
    (_registered("test_stages_2d"), None, {"x": 9, "y": 9}),
    # alternatives (a sub-domain and its complement) do not add
    (_registered("test_boundary_2d"), None, {"x": 4, "y": 4}),
    (_two_writers, [3, 5, 5, 6], {"x": 6, "y": 0}),
    (_same_point, [2, 2, 3], {"x": 3, "y": 0}),
    (_ring_of_written, [3, 3], {"x": 3, "y": 0}),
], ids=["awp_abc", "ssg-r4", "tti-r4", "iso3dfd-r8", "test_stages_2d",
        "test_boundary_2d", "two-writers", "same-point",
        "ring-of-written"])
def test_fused_step_reach_is_the_longest_chain(build, consumed, reach):
    ana = build().analyze()
    got = ana.stage_consumed()
    assert len(got) == len(ana.stages)
    if consumed is not None:
        assert [c["x"] for c in got] == consumed
    # regions only shrink through a step, and the step's reach is the
    # largest of what its stages consume
    for d in ana.domain_dims:
        assert [c[d] for c in got] == sorted(c[d] for c in got)
        assert ana.fused_step_radius()[d] == got[-1][d]
    assert ana.fused_step_radius() == reach
