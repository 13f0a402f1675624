"""The program's ``himeno`` against the benchmark's plain float64
reference (``benchmark/stencils/himeno.py``), over EVERY point of ``p``
-- the benchmark's own comparison reads three 8^3 probe blocks a run;
this one leaves no point out.

Small odd boxes (19 x 21 x 40 and 13 x 17 x 27: no lead extent a
multiple of 8, the minor dim filling no lane count), the constants of
the configuration ``himeno-l-1chip`` through the public var API with
``bnd``'s layer moved to the box's own first and last index, so all
twelve arrays vary with position and every layer boundary is inside.
Seeded by ``check``'s law; 6 sweeps on the first box and the cell's 48
on the second, under ``jit`` and under ``pallas`` (one device,
interpreted) at ``-wf_steps`` 1, 2 and 4 (the cell's), once more at
K=4 on 8 x 8 blocks so that tile seams cross the box.  The tests'
reference IS the benchmark's file: loaded from there, byte for byte.

``TOLERANCE`` is 1e-5 of the field's largest magnitude: float32 against
float64 reads 1.4e-7 after 6 sweeps and 1.0e-7 after 48, the same
number in every mode and at every K; the same reference with every
stored value rounded to bfloat16 reads 2.7e-3 after 48 sweeps.  The
planted faults read, after 6 sweeps (the share of the faulty
reference's own largest magnitude): one ``b`` term's sign flipped
1.4e-2; ``c0`` read at ``i+1`` 1.4e-1; ``a3`` left out 1.0 (the faulty
field has grown past everything); ``omega`` applied to ``s0`` 3.0e-1;
``bnd``'s layer left at 1 2.3.
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
BOXES = {6: (19, 21, 40), 48: (13, 17, 27)}     # sweeps -> domain
#: (mode, wf_steps, extra options)
ARMS = (("jit", 1, ""), ("pallas", 1, ""), ("pallas", 2, ""),
        ("pallas", 4, ""), ("pallas", 4, "-b_x 8 -b_y 8"))
REFERENCE = os.path.join(BENCH, "stencils", "himeno.py")
with open(REFERENCE) as _f:
    SOURCE = _f.read()
with open(os.path.join(BENCH, "configs", "himeno-l-1chip.json")) as _f:
    CONFIG = json.load(_f)


def _load(name, text=SOURCE):
    """The reference as a module, from ``text`` (the file's own, or
    the file's with one fault written into it)."""
    spec = importlib.util.spec_from_file_location(name, REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    exec(compile(text, REFERENCE, "exec"), mod.__dict__)
    return mod


STENCIL = _load("bench_himeno_reference")


def config_for(domain):
    """The cell's constants with ``bnd``'s layer on this box's own
    first and last index (half-index shares: ``ceil`` lands on 1 and on
    n - 1 whatever n)."""
    bnd = {ax: {"layers": [[0.0, 0.0], [0.5 / n, 1.0],
                           [(n - 1.5) / n, 0.0]]}
           for ax, n in zip("xyz", domain)}
    return {**CONFIG, "consts": {**CONFIG["consts"], "bnd": bnd}}


def fill_of(domain):
    return check.draw_fill(2147483861, list(domain), [1, 1, 1], BLOCK)


def arrays(domain):
    """Every coefficient of the box: the float ``omega`` and the
    float32 arrays the device holds."""
    domain = list(domain)
    return {name: check.coefficient_box(c, [0, 0, 0], domain)
            for name, c in check.coefficients(
                STENCIL, config_for(domain), domain).items()}


def reference(steps, stencil=STENCIL, rounder=None, coeffs=None):
    """``p`` after ``steps`` sweeps of the plain reference on the whole
    box, in float64."""
    domain, lo = list(BOXES[steps]), [0, 0, 0]
    state = {name: [a.astype(np.float64) for a in levels]
             for name, levels in check.initial_state(
                 STENCIL, domain, lo, domain, fill_of(domain)).items()}
    if rounder is not None:
        state = {name: [rounder(a) for a in levels]
                 for name, levels in state.items()}
    coeffs = coeffs or arrays(domain)
    for _ in range(steps):
        state = stencil.step(state, coeffs, None, lo=lo, domain=domain,
                             rounder=rounder)
    return state["p"][-1]


def program(mode, k, extra, steps):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    domain = list(BOXES[steps])
    last = [n - 1 for n in domain]
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="himeno", radius=None)
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} "
        f"-mode {mode} -wf_steps {k} {extra}")
    ctx.prepare_solution()
    for name, value in arrays(domain).items():
        if name in STENCIL.SCALARS:
            ctx.get_var(name).set_element(value, [])
        else:
            ctx.get_var(name).set_elements_in_slice(value, [0, 0, 0], last)
    level, = check.initial_state(STENCIL, domain, [0, 0, 0], domain,
                                 fill_of(domain))["p"]
    var = ctx.get_var("p")
    newest = var.get_last_valid_step_index()
    assert newest - var.get_first_valid_step_index() + 1 \
        == STENCIL.FIELDS["p"]["slots"]
    var.set_elements_in_slice(level, [newest, 0, 0, 0], [newest] + last)
    ctx.run_solution(0, steps - 1)
    t = var.get_last_valid_step_index()
    out = np.asarray(var.get_elements_in_slice([t, 0, 0, 0], [t] + last))
    plans = ctx.compiled_plans()
    ctx.end_solution()
    return out, plans


got = functools.lru_cache(maxsize=None)(program)
want = functools.lru_cache(maxsize=None)(reference)

#: fault -> (the reference's own text, the text with the fault in it);
#: the one fault of the constants is planted in ``broken``
EDITS = {
    "one b term's sign flipped":
        ('c["b0"] * (at(1, 1, 0) - at(1, -1, 0)',
         'c["b0"] * (at(1, 1, 0) + at(1, -1, 0)'),
    "c0 read at i+1":
        ('c["c0"] * at(-1, 0, 0)', 'c["c0"] * at(1, 0, 0)'),
    "a3 left out":
        ('ss = (s0 * c["a3"] - cur) * c["bnd"]',
         'ss = (s0 - cur) * c["bnd"]'),
    "omega applied to s0":
        ('new = cur + float(coeffs["omega"]) * ss',
         'new = cur + (float(coeffs["omega"]) * s0 * c["a3"] - cur)'
         ' * c["bnd"]'),
}
FAULTS = ("none", *EDITS, "bnd's layer left at 1")
FAULT_SWEEPS = 6


@functools.lru_cache(maxsize=None)
def broken(fault):
    """What the reference reads after ``FAULT_SWEEPS`` sweeps with one
    fault of place put in."""
    if fault == "none":
        return reference(FAULT_SWEEPS)
    if fault == "bnd's layer left at 1":
        flat = arrays(BOXES[FAULT_SWEEPS])
        flat["bnd"] = np.ones_like(flat["bnd"])
        return reference(FAULT_SWEEPS, coeffs=flat)
    was, now = EDITS[fault]
    assert SOURCE.count(was) == 1, fault
    return reference(FAULT_SWEEPS,
                     stencil=_load("bench_himeno_broken",
                                   SOURCE.replace(was, now)))


CASES = ([("agrees", arm, steps) for steps in BOXES for arm in ARMS]
         + [("fault: " + f, arm, FAULT_SWEEPS)
            for f in FAULTS for arm in (ARMS[0], ARMS[3])]
         + [("the bf16 control fails", None, 48),
            ("the fixture is the benchmark's file", None, 0),
            ("the constants are what the configuration says", None, 0),
            ("one sweep reaches one point of a ring of two", None, 0)])


def _id(case):
    what, arm, steps = case
    return (f"{what}-{arm[0]}-k{arm[1]}{'-blocks' if arm[2] else ''}"
            f"-{steps}" if arm else what).replace(" ", "_")


@pytest.mark.parametrize("case", CASES, ids=_id)
def test_himeno_against_its_reference(case):
    what, arm, steps = case
    if what == "agrees":
        out, plans = got(*arm, steps)
        error = check.block_error(out, want(steps))
        assert error <= TOLERANCE, error
        if arm[0] == "pallas":
            row = max(plans, key=lambda r: r["k"])
            assert (row["k"], row["kernel"]) == (
                arm[1], f"yt_himeno_r1_k{arm[1]}")
            assert row["eval"] == "strip" and row["stages"] == 1
            # the write target is never read: no DMA for it
            assert row["fetch_skipped"] == ["p/0"]
            assert len(row["fetch_windows"]) == 13
            if arm[2]:
                assert row["block"] == {"x": 8, "y": 8} \
                    and min(row["grid"]) >= 2
        else:
            assert plans == []
    elif what.startswith("fault: "):
        # the program against a reference with the fault: were the
        # program's ``himeno`` to differ from the published equation by
        # that much, the comparison would say so
        fault = what[len("fault: "):]
        error = check.block_error(got(*arm, steps)[0], broken(fault))
        if fault == "none":
            assert error <= TOLERANCE, error
        else:
            assert error > 100 * TOLERANCE, (fault, error)
    elif what == "the bf16 control fails":
        control = reference(steps, rounder=check.bf16_round)
        assert check.block_error(control, want(steps)) > 100 * TOLERANCE
        # and 48 sweeps neither blow the field up nor flatten it: the
        # fixed outer layer keeps its values, the inside relaxes
        start = check.initial_state(
            STENCIL, list(BOXES[steps]), [0, 0, 0], list(BOXES[steps]),
            fill_of(BOXES[steps]))["p"][-1]
        inside = (slice(1, -1),) * 3
        assert float(np.abs(want(steps)).max()) \
            <= float(np.abs(start).max())
        assert 0.002 * float(np.abs(start[inside]).mean()) \
            < float(np.abs(want(steps)[inside]).mean()) \
            < float(np.abs(start[inside]).mean())
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
        assert all(SOURCE.count(was) == 1 for was, _now in EDITS.values())
    elif what == "the constants are what the configuration says":
        the_constants_are_what_the_configuration_says()
    else:
        from yask_tpu import yk_factory
        fac = yk_factory()
        ctx = fac.new_solution(fac.new_env(), stencil="himeno",
                               radius=None)
        assert set(ctx._ana.fused_step_radius().values()) == {1}
        assert check.reach(STENCIL, CONFIG) == STENCIL.REACH == 1
        assert STENCIL.FIELDS == {"p": {"slots": 2, "levels": 1}}
        assert len(STENCIL.ARRAYS) == 12 and STENCIL.SCALARS == ("omega",)
        assert [STENCIL.need_bytes_per_point_step(k) for k in (1, 2, 4)] \
            == [56, 28, 14]


def the_constants_are_what_the_configuration_says():
    assert CONFIG["domain"] == [256, 256, 512] and CONFIG["wf_steps"] == 4
    assert CONFIG["reduced"] == [] and CONFIG["radius"] is None
    assert "jacobi()" in CONFIG["source"] and "LARGE" in CONFIG["source"]
    assert set(CONFIG["departures"]) == {
        "what", "bnd layer", "coefficient arrays", "ring", "gosa"}
    for domain in (CONFIG["domain"], CONFIG["tiny_domain"]):
        coeffs = check.coefficients(STENCIL, CONFIG, domain)
        assert coeffs["omega"] == 0.8
        # the fixed outer layer: 0 at the first and the last index of
        # every axis at the cell's size, 1 between; at the dry run's
        # extents the last layer falls off the end (``assumed``)
        full = domain == CONFIG["domain"]
        for p in coeffs["bnd"]:
            assert p[0] == 0.0 and (p[1:-1] == 1.0).all()
            assert p[-1] == (0.0 if full else 1.0)
        # no array is a constant, no two are equal
        profiles = {name: [p.tolist() for p in coeffs[name]]
                    for name in STENCIL.ARRAYS}
        assert all(any(len(set(p)) >= 2 for p in ps)
                   for ps in profiles.values())
        names = sorted(profiles)
        assert not any(profiles[a] == profiles[b]
                       for i, a in enumerate(names) for b in names[i + 1:])
        top = {name: float(np.prod([np.abs(p).max()
                                    for p in coeffs[name]]))
               for name in STENCIL.ARRAYS}
        # within 10 % of the source's 1.0 and 1/6, none above them
        assert all(0.9 <= min(np.prod(np.meshgrid(*map(np.unique, coeffs[n])),
                                      axis=0).ravel()) <= top[n] <= 1.0
                   for n in ("a0", "a1", "a2", "c0", "c1", "c2"))
        assert top["a3"] == pytest.approx(0.95 / 6)
        for b in ("b0", "b1", "b2"):
            layered, = [p for p in coeffs[b] if len(set(p.tolist())) > 1]
            assert 0 < top[b] <= 0.02 \
                and set(np.sign(layered)) == {-1.0, 1.0}
        # the iteration contracts: a window is ~17 000 sweeps, and the
        # first constants (bound 1.0867) ended every one non-finite
        bound = top["a3"] * (
            sum(top[n] for n in ("a0", "a1", "a2", "c0", "c1", "c2"))
            + 4 * sum(top[n] for n in ("b0", "b1", "b2")))
        assert bound < 1.0
        assert f"{bound:.4f}" in CONFIG["assumed"]["growth"]
