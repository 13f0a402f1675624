"""The program's ``lbm_d3q19`` against the benchmark's plain float64
reference (``benchmark/stencils/lbm_d3q19.py``), over EVERY point of
all nineteen populations -- the benchmark's own comparison reads three
8^3 probe blocks a run; this one leaves no point out.

A small odd box (13 x 18 x 40: no lead extent a multiple of 8, the
minor dim filling no lane count), the constants of the configuration
``lbm-d3q19-ldc-1chip`` through the public var API: ``fluid`` and
``accel``'s x and y profiles as the file lays them (by distance from a
face, so they close any box), ``accel``'s z layers moved to the box's
own index 1 and n - 2.  Seeded by ``check``'s law (every cell at rest
and in equilibrium, the density 1 : 17 from cell to cell, a point
source on ``f0``); 6 steps under ``jit`` and under ``pallas`` (one
device, interpreted) at ``-wf_steps`` 1 and 2 and once more at K=1 on
4 x 8 blocks, the cell's, so that tile seams cross the box.  The tests'
reference IS the benchmark's file: loaded from there, byte for byte.

``TOLERANCE`` is 1e-5 of each field's largest magnitude: float32
against float64 reads 1.2e-6 after 6 steps, the same number in every
mode, at every K and on every blocking; the same reference with every
stored value rounded to bfloat16 reads 3.6e-3 to 8.3e-3 over the
nineteen fields.  The planted faults read, after 6 steps, the largest
over the nineteen fields (the share of the faulty reference's own
largest magnitude), 0.79 to 1.00 each: the seeded state's density
varies 1 : 17 from cell to cell, the flow it drives is fast, and a
step that differs anywhere differs by a field's whole size soon (two
entries of ``opp`` swapped 1.00; a diagonal given the axis weight
0.99; the ``accel`` layer one index off 0.79; 4.5 for 3 in ``feq``
0.83; ``omega`` applied to the obstacle branch 0.97).

``omega`` is the configuration's 1.8, not ``lbm.c``'s 1.95: under
that seeded state float32 at 1.95 is non-finite within 300 steps on a
box that lays the seeding law's pattern as the cell's 256 x 256 x 512
lays it (40 x 52 x 104: the same remainders mod 17), and 1.8 stays
finite (10 000 steps by hand, 600 here); the last case holds both.
"""

import functools
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402  (the benchmark's seeding law and control)

BLOCK = 4
TOLERANCE = 1e-5
BOX = (13, 18, 40)
STEPS = 6
#: (mode, wf_steps, extra options)
ARMS = (("jit", 1, ""), ("pallas", 1, ""), ("pallas", 2, ""),
        ("pallas", 1, "-b_x 4 -b_y 8"))
REFERENCE = os.path.join(BENCH, "stencils", "lbm_d3q19.py")
with open(REFERENCE) as _f:
    SOURCE = _f.read()
with open(os.path.join(BENCH, "configs", "lbm-d3q19-ldc-1chip.json")) as _f:
    CONFIG = json.load(_f)


def _load(name, text=SOURCE):
    """The reference as a module, from ``text`` (the file's own, or
    the file's with one fault written into it)."""
    spec = importlib.util.spec_from_file_location(name, REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    exec(compile(text, REFERENCE, "exec"), mod.__dict__)
    return mod


STENCIL = _load("bench_lbm_reference")
NAMES = tuple(STENCIL.FIELDS)
VEC = [row[1:4] for row in STENCIL.TABLE]
OPP = [row[5] for row in STENCIL.TABLE]
WEIGHT = [row[4] for row in STENCIL.TABLE]


def config_for(domain, off=0):
    """The cell's constants with ``accel``'s z layers on this box's own
    index 1 and n - 2 (half-index shares: ``ceil`` lands there whatever
    n), or ``off`` indices further in."""
    n = domain[2]
    z = {"layers": [[0.0, 0.0], [(0.5 + off) / n, 1.0],
                    [(1.5 + off) / n, 0.0], [(n - 2.5 - off) / n, 1.0],
                    [(n - 1.5 - off) / n, 0.0]]}
    accel = {**CONFIG["consts"]["accel"], "z": z}
    return {**CONFIG, "consts": {**CONFIG["consts"], "accel": accel}}


FILL = check.draw_fill(2147483861, list(BOX), [1, 1, 1], BLOCK)


@functools.lru_cache(maxsize=None)
def arrays(off=0):
    """Every coefficient of the box: the three floats and the float32
    masks the device holds."""
    domain = list(BOX)
    return {name: check.coefficient_box(c, [0, 0, 0], domain)
            for name, c in check.coefficients(
                STENCIL, config_for(domain, off), domain).items()}


def seeded():
    """Field -> its newest seeded level, float32 as the device holds
    it."""
    domain = list(BOX)
    return {name: levels[-1] for name, levels in check.initial_state(
        STENCIL, domain, [0, 0, 0], domain, FILL).items()}


def reference(steps, stencil=STENCIL, rounder=None, coeffs=None):
    """Every field after ``steps`` steps of the plain reference on the
    whole box, in float64."""
    domain, lo = list(BOX), [0, 0, 0]
    state = {name: [a.astype(np.float64)] for name, a in seeded().items()}
    if rounder is not None:
        state = {name: [rounder(a) for a in levels]
                 for name, levels in state.items()}
    coeffs = coeffs or arrays()
    for _ in range(steps):
        state = stencil.step(state, coeffs, None, lo=lo, domain=domain,
                             rounder=rounder)
    return {name: levels[-1] for name, levels in state.items()}


def program(mode, k, extra, steps):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    domain = list(BOX)
    last = [n - 1 for n in domain]
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="lbm_d3q19", radius=None)
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} "
        f"-mode {mode} -wf_steps {k} {extra}")
    ctx.prepare_solution()
    for name, value in arrays().items():
        if name in STENCIL.SCALARS:
            ctx.get_var(name).set_element(value, [])
        else:
            ctx.get_var(name).set_elements_in_slice(value, [0, 0, 0], last)
    for name, level in seeded().items():
        var = ctx.get_var(name)
        newest = var.get_last_valid_step_index()
        assert newest - var.get_first_valid_step_index() + 1 \
            == STENCIL.FIELDS[name]["slots"]
        var.set_elements_in_slice(level, [newest, 0, 0, 0],
                                  [newest] + last)
    ctx.run_solution(0, steps - 1)
    out = {}
    for name in NAMES:
        var = ctx.get_var(name)
        t = var.get_last_valid_step_index()
        out[name] = np.asarray(
            var.get_elements_in_slice([t, 0, 0, 0], [t] + last))
    plans = ctx.compiled_plans()
    ctx.end_solution()
    return out, plans


got = functools.lru_cache(maxsize=None)(program)
want = functools.lru_cache(maxsize=None)(reference)


def gap(out, ref):
    """The largest of the nineteen fields' errors, each a share of its
    own reference's largest magnitude."""
    return max(check.block_error(out[name], ref[name]) for name in NAMES)


def read_at(a, c):
    """``a`` read at ``x + c``: zero where that lies outside."""
    nx, ny, nz = a.shape
    pad = np.pad(a, 1)
    return pad[1 + c[0]:1 + c[0] + nx, 1 + c[1]:1 + c[1] + ny,
               1 + c[2]:1 + c[2] + nz]


#: fault -> the edits ``(the reference's own text, the text with the
#: fault in it)``; the one fault of the constants is planted in
#: ``broken``
EDITS = {
    "two entries of opp swapped": (
        ('("NE",  1,  1,  0, 1.0 / 36.0, 10),',
         '("NE",  1,  1,  0, 1.0 / 36.0,  9),'),
        ('("NW", -1,  1,  0, 1.0 / 36.0,  9),',
         '("NW", -1,  1,  0, 1.0 / 36.0, 10),')),
    "a diagonal given the axis weight": (
        ('("SW", -1, -1,  0, 1.0 / 36.0,  7),',
         '("SW", -1, -1,  0, 1.0 / 18.0,  7),'),),
    "4.5 for 3 in feq": (
        ('(1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * uu)',
         '(1.0 + 4.5 * cu + 4.5 * cu * cu - 1.5 * uu)'),),
    "omega applied to the obstacle branch": (
        ('+ (1.0 - fluid) * g[o])',
         '+ (1.0 - fluid) * omega * g[o])'),),
}
FAULTS = ("none", *EDITS, "the accel layer one index off")


@functools.lru_cache(maxsize=None)
def broken(fault):
    """What the reference reads after ``STEPS`` steps with one fault
    put in."""
    if fault == "none":
        return reference(STEPS)
    if fault == "the accel layer one index off":
        return reference(STEPS, coeffs=arrays(off=1))
    text = SOURCE
    for was, now in EDITS[fault]:
        assert text.count(was) == 1, (fault, was)
        text = text.replace(was, now)
    return reference(STEPS, stencil=_load("bench_lbm_broken", text))


CASES = ([("agrees", arm) for arm in ARMS]
         + [("fault: " + f, arm) for f in FAULTS
            for arm in (ARMS[0], ARMS[2])]
         + [(what, None) for what in (
             "the bf16 control fails",
             "the closed box keeps its mass",
             "an obstacle cell returns what it pulled reversed",
             "an accel cell's equilibrium is the lid's",
             "the fixture is the benchmark's file",
             "the constants are what the configuration says",
             "one step reaches one point; f0 is a ring of one",
             "omega 1.8 stays finite where lbm.c's 1.95 does not")])


def _id(case):
    what, arm = case
    return (f"{what}-{arm[0]}-k{arm[1]}{'-blocks' if arm[2] else ''}"
            if arm else what).replace(" ", "_")


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_lbm_against_its_reference(case):
    what, arm = case
    if what == "agrees":
        out, plans = got(*arm, STEPS)
        error = gap(out, want(STEPS))
        assert error <= TOLERANCE, error
        if arm[0] == "pallas":
            row = max(plans, key=lambda r: r["k"])
            assert (row["k"], row["kernel"]) == (
                arm[1], f"yt_lbm_d3q19_r1_k{arm[1]}")
            assert row["eval"] == "strip" and row["stages"] == 1
            # the eighteen write targets are never read: no DMA for
            # them; f0's one slot is read (at the point) and fetched
            assert row["fetch_skipped"] == sorted(
                f"f{i}/0" for i in range(1, 19))
            assert len(row["fetch_windows"]) == 21 \
                and "f0/0" in row["fetch_windows"]
            assert row["ops_per_point"] > 20 * row["dag_ops_per_point"] \
                > 0
            if arm[2]:
                assert row["block"] == {"x": 4, "y": 8} \
                    and min(row["grid"]) >= 3
        else:
            assert plans == []
    elif what.startswith("fault: "):
        # the program against a reference with the fault: were the
        # program's ``lbm_d3q19`` to differ from the published step by
        # that much, the comparison would say so
        fault = what[len("fault: "):]
        error = gap(got(*arm, STEPS)[0], broken(fault))
        if fault == "none":
            assert error <= TOLERANCE, error
        else:
            assert error > 100 * TOLERANCE, (fault, error)
    elif what == "the bf16 control fails":
        control = reference(STEPS, rounder=check.bf16_round)
        assert min(check.block_error(control[name], want(STEPS)[name])
                   for name in NAMES) > 100 * TOLERANCE
    elif what == "the closed box keeps its mass":
        the_closed_box_keeps_its_mass()
    elif what == "an obstacle cell returns what it pulled reversed":
        start = {n: a.astype(np.float64) for n, a in seeded().items()}
        solid = arrays()["fluid"] == 0.0
        assert solid[0].all() and solid[:, -1].all() \
            and not solid[1:-1, 1:-1, 1:-1].any()
        for out in (reference(1), got("jit", 1, "", 1)[0]):
            for i, c in enumerate(VEC):
                # what it sends along c_i is what came in against it
                back = read_at(start[f"f{OPP[i]}"], c)
                assert np.array_equal(
                    np.asarray(out[f"f{i}"], np.float64)[solid],
                    back[solid]), i
    elif what == "an accel cell's equilibrium is the lid's":
        start = {n: a.astype(np.float64) for n, a in seeded().items()}
        coeffs = arrays()
        lid = coeffs["accel"] == 1.0
        assert lid[2:-2, 2:-2, [1, -2]].all() and lid.sum() \
            == 2 * (BOX[0] - 4) * (BOX[1] - 4)
        omega = coeffs["omega"]
        u = (coeffs["u_lid_x"], coeffs["u_lid_y"], 0.0)
        assert (omega, u) == (1.8, (0.005, 0.002, 0.0))
        g = [read_at(start[f"f{i}"], [-a for a in c])
             for i, c in enumerate(VEC)]
        rho = sum(g)
        for out, tol in ((reference(1), 1e-12),
                         (got("jit", 1, "", 1)[0], 1e-5)):
            for i, c in enumerate(VEC):
                cu = sum(a * b for a, b in zip(c, u))
                feq = WEIGHT[i] * rho * (
                    1.0 + 3.0 * cu + 4.5 * cu * cu
                    - 1.5 * sum(b * b for b in u))
                relaxed = (np.asarray(out[f"f{i}"], np.float64)
                           - (1.0 - omega) * g[i]) / omega
                assert np.abs(relaxed - feq)[lid].max() \
                    <= tol * np.abs(feq[lid]).max(), i
    elif what == "the fixture is the benchmark's file":
        with open(STENCIL.__spec__.origin, "rb") as f:
            assert f.read() == SOURCE.encode()
        assert os.path.samefile(STENCIL.__spec__.origin, os.path.join(
            ROOT, "benchmark", "stencils", CONFIG["stencil"] + ".py"))
        body = SOURCE.split('"""', 2)[2]
        assert "yask_tpu" not in body                  # imports nothing
        assert [ln for ln in body.splitlines()
                if ln.startswith(("import ", "from "))] \
            == ["import numpy as np"]
        assert SOURCE.count("as recalled") >= 1
        # the table is the lattice: nineteen distinct vectors, each
        # with its opposite, the weights 1/3, 1/18, 1/36 by length
        assert len(set(VEC)) == 19 and VEC[0] == (0, 0, 0)
        for i, c in enumerate(VEC):
            assert VEC[OPP[i]] == tuple(-a for a in c)
            assert WEIGHT[i] == (1 / 3, 1 / 18, 1 / 36)[
                sum(1 for a in c if a)]
        assert sum(WEIGHT) == pytest.approx(1.0, abs=1e-15)
    elif what == "the constants are what the configuration says":
        the_constants_are_what_the_configuration_says()
    elif what.startswith("omega 1.8 stays finite"):
        # 40 x 52 x 104 leaves the remainders mod 17 that 256 x 512
        # leaves (52 = 1, 104 = 2: as 256 and 512), so the seeding law
        # lays its 1 : 17 pattern as the cell lays it
        assert [n % 17 for n in STABLE_BOX[1:]] \
            == [n % 17 for n in CONFIG["domain"][1:]]
        assert CONFIG["consts"]["omega"] == 1.8
        assert not finite_after(1.95, 300)
        assert finite_after(CONFIG["consts"]["omega"], 600)
    else:
        from yask_tpu import yk_factory
        fac = yk_factory()
        ctx = fac.new_solution(fac.new_env(), stencil="lbm_d3q19",
                               radius=None)
        assert set(ctx._ana.fused_step_radius().values()) == {1}
        assert len(ctx._ana.stages) == 1
        assert check.reach(STENCIL, CONFIG) == STENCIL.REACH == 1
        # the rest population is read at the point alone: the program
        # writes it where it was read, in a ring of one slot
        assert STENCIL.FIELDS["f0"] == {"slots": 1, "weight": 2.0}
        assert all(STENCIL.FIELDS[f"f{i}"] == {
            "slots": 2, "levels": 1, "weight": 3.0 * WEIGHT[i]}
            for i in range(1, 19))
        assert STENCIL.SOURCE == "f0" and check.probed(STENCIL) == NAMES
        assert STENCIL.ARRAYS == ("fluid", "accel")
        assert STENCIL.SCALARS == ("omega", "u_lid_x", "u_lid_y")
        assert [STENCIL.need_bytes_per_point_step(k) for k in (1, 2)] \
            == [160, 80]
        # seeded at rest and in equilibrium: f_i = w_i rho, every cell
        start = seeded()
        rho = sum(a.astype(np.float64) for a in start.values())
        away = np.ones(BOX, bool)
        away[tuple(FILL["source"])] = False
        for i in range(19):
            assert np.allclose(start[f"f{i}"][away],
                               (WEIGHT[i] * rho)[away], rtol=1e-6)
        assert rho[away].max() / rho[away].min() == pytest.approx(17.0)


STABLE_BOX = (40, 52, 104)


def finite_after(omega, steps):
    """Whether the program (``jit``, float32) still holds finite values
    after ``steps`` steps from the seeded state on ``STABLE_BOX``, the
    masks laid as :func:`config_for` lays them, at ``omega``."""
    from yask_tpu import yk_factory
    domain = list(STABLE_BOX)
    last = [n - 1 for n in domain]
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="lbm_d3q19", radius=None)
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} -mode jit")
    ctx.prepare_solution()
    coeffs = check.coefficients(STENCIL, config_for(domain), domain)
    for name, value in coeffs.items():
        if name in STENCIL.SCALARS:
            ctx.get_var(name).set_element(
                omega if name == "omega" else value, [])
        else:
            ctx.get_var(name).set_elements_in_slice(
                check.coefficient_box(value, [0, 0, 0], domain),
                [0, 0, 0], last)
    fill = check.draw_fill(2147484001, domain, [1, 1, 1], BLOCK)
    for name, levels in check.initial_state(
            STENCIL, domain, [0, 0, 0], domain, fill).items():
        var = ctx.get_var(name)
        newest = var.get_last_valid_step_index()
        var.set_elements_in_slice(levels[-1], [newest, 0, 0, 0],
                                  [newest] + last)
    ctx.run_solution(0, steps - 1)
    ok = True
    for name in NAMES:
        var = ctx.get_var(name)
        t = var.get_last_valid_step_index()
        ok = ok and bool(np.isfinite(np.asarray(
            var.get_elements_in_slice([t, 0, 0, 0], [t] + last))).all())
    ctx.end_solution()
    return ok


def the_closed_box_keeps_its_mass():
    """The sum of all populations over the box: the seeded state's
    populations that point out of the box on its faces leave in the
    first step (nothing pulls them, and the obstacle cell that held
    them pulls zeros from outside in their place); from then on every
    step hands every population to a cell of the box."""
    start = {n: a.astype(np.float64) for n, a in seeded().items()}
    coeffs, domain, lo = arrays(), list(BOX), [0, 0, 0]
    state = {n: [a] for n, a in start.items()}
    mass = [sum(a.sum() for a in start.values())]
    for _ in range(50):
        state = STENCIL.step(state, coeffs, None, lo=lo, domain=domain)
        mass.append(sum(levels[-1].sum() for levels in state.values()))
    leaving = sum((start[f"f{i}"] - read_at(start[f"f{i}"],
                                            [-a for a in c])).sum()
                  for i, c in enumerate(VEC))
    assert leaving > 0
    assert mass[0] - mass[1] == pytest.approx(leaving, rel=1e-12)
    assert max(abs(m - mass[1]) for m in mass[1:]) <= 1e-12 * mass[1]
    assert all(np.isfinite(levels[-1]).all() for levels in state.values())
    # the program, float32: one step, then fifty
    one = sum(np.asarray(a, np.float64).sum()
              for a in got("jit", 1, "", 1)[0].values())
    fifty = sum(np.asarray(a, np.float64).sum()
                for a in got("jit", 1, "", 50)[0].values())
    assert one == pytest.approx(mass[1], rel=1e-6)
    assert fifty == pytest.approx(one, rel=1e-5)


def the_constants_are_what_the_configuration_says():
    assert CONFIG["domain"] == [256, 256, 512] and CONFIG["wf_steps"] == 1
    assert CONFIG["reduced"] == [] and CONFIG["radius"] is None
    assert CONFIG["stencil"] == "lbm_d3q19" and CONFIG["mode"] == "pallas"
    for word in ("lbm.c", "LBM_performStreamCollide",
                 "LBM_initializeSpecialCellsForLDC", "no obstacle file"):
        assert word in CONFIG["source"], word
    assert len(CONFIG["source"]) <= 200
    assert {"what", "pull", "arrays", "float32", "z fastest"} \
        <= set(CONFIG["departures"])
    assert {"domain", "initial state", "state", "tolerance", "masks"} \
        <= set(CONFIG["assumed"])
    for domain in (CONFIG["domain"], CONFIG["tiny_domain"]):
        coeffs = check.coefficients(STENCIL, CONFIG, domain)
        assert (coeffs["omega"], coeffs["u_lid_x"], coeffs["u_lid_y"]) \
            == (1.8, 0.005, 0.002)
        # OBSTACLE on all six faces, fluid between: at the cell's size
        # and at the dry run's
        for p in coeffs["fluid"]:
            assert p[0] == p[-1] == 0.0 and (p[1:-1] == 1.0).all()
        # ACCEL: the layers z == 1 and z == n - 2, x and y from 2 to
        # n - 3 (both sizes keep z's 512, where the shares land)
        ax, ay, az = coeffs["accel"]
        for p in (ax, ay):
            assert (p[:2] == 0.0).all() and (p[-2:] == 0.0).all() \
                and (p[2:-2] == 1.0).all()
        assert np.flatnonzero(az).tolist() == [1, len(az) - 2] \
            and set(az.tolist()) == {0.0, 1.0}
