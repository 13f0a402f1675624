"""The program's ``ssg`` against the benchmark's plain float64 reference
(``benchmark/stencils/ssg.py``), over EVERY point of all nine stepped
fields -- the benchmark's own comparison reads three 8^3 probe blocks a
run; this one leaves no point out.

A box small enough for the CPU (32 x 24 x 32, four steps) and tall
enough that all three layers of the configuration's earth are inside;
seeded by ``check``'s law with the constants of the configuration
``ssg-r4-1chip`` through the public var API; at radius 4 (the cell's,
upstream's order 8) and radius 2 (the registry's default), under
``jit`` and ``pallas`` (one device, interpreted) at ``-wf_steps 1`` as
the cell runs it.

``TOLERANCE`` is 1e-5 of a field's largest magnitude: float32 against
float64 over four steps reads 1.64e-7 at radius 4 and 1.38e-7 at radius
2 here, the same in both modes; the same reference with every stored
value rounded to bfloat16 reads 6.2e-3 to 1.05e-2 in every field (both
radii) -- 61 times above the first, 620 times below the second.  A
staggered shift off by one reads 0.50 to 1.35 of a field's magnitude,
``rho`` read at the point 0.13 to 0.81.
"""

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

DOMAIN = [32, 24, 32]
STEPS = 4
BLOCK = 4
TOLERANCE = 1e-5
RADII = (4, 2)
MODES = ("jit", "pallas")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STENCIL = _load(os.path.join(BENCH, "stencils", "ssg.py"),
                "bench_ssg_reference")
with open(os.path.join(BENCH, "configs", "ssg-r4-1chip.json")) as _f:
    CONFIG = json.load(_f)
FILL = check.draw_fill(2147483777, DOMAIN, [1, 1, 1], BLOCK)
FIELDS = tuple(STENCIL.FIELDS)
LAST = [n - 1 for n in DOMAIN]


def reference(radius, stencil=STENCIL, rounder=None):
    """Every field's newest level after ``STEPS`` steps of the plain
    reference on the whole domain, in float64."""
    lo = [0, 0, 0]
    state = {name: [a.astype(np.float64) for a in levels]
             for name, levels in check.initial_state(
                 STENCIL, DOMAIN, lo, DOMAIN, FILL).items()}
    if rounder is not None:
        state = {name: [rounder(a) for a in levels]
                 for name, levels in state.items()}
    coeffs = {name: check.coefficient_box(c, lo, DOMAIN)
              for name, c in check.coefficients(
                  STENCIL, CONFIG, DOMAIN).items()}
    for _ in range(STEPS):
        state = stencil.step(state, coeffs, radius, lo=lo, domain=DOMAIN,
                             rounder=rounder)
    return {name: levels[-1] for name, levels in state.items()}


def program(mode: str, radius: int, options: str = ""):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="ssg", radius=radius)
    ctx.apply_command_line_options(
        f"-g_x {DOMAIN[0]} -g_y {DOMAIN[1]} -g_z {DOMAIN[2]} "
        f"-mode {mode} -wf_steps {CONFIG['wf_steps']} {options}")
    ctx.prepare_solution()
    for name, c in check.coefficients(STENCIL, CONFIG, DOMAIN).items():
        ctx.get_var(name).set_elements_in_slice(
            check.coefficient_box(check.as_profiles(c, DOMAIN),
                                  [0, 0, 0], DOMAIN),
            [0, 0, 0], LAST)
    for name, levels in check.initial_state(
            STENCIL, DOMAIN, [0, 0, 0], DOMAIN, FILL).items():
        var = ctx.get_var(name)
        newest = var.get_last_valid_step_index()
        for back, level in enumerate(reversed(levels)):
            var.set_elements_in_slice(level, [newest - back, 0, 0, 0],
                                      [newest - back] + LAST)
    ctx.run_solution(0, STEPS - 1)
    out = {}
    for name in FIELDS:
        var = ctx.get_var(name)
        t = var.get_last_valid_step_index()
        out[name] = np.asarray(var.get_elements_in_slice(
            [t, 0, 0, 0], [t] + LAST))
    ctx.end_solution()
    return out


@pytest.fixture(scope="module")
def want():
    return {r: reference(r) for r in RADII}


@pytest.fixture(scope="module")
def got():
    return {(mode, r): program(mode, r) for mode in MODES for r in RADII}


def errors_of(fields, yardstick):
    return {name: check.block_error(fields[name], yardstick[name])
            for name in FIELDS}


def test_the_box_holds_all_three_layers_and_every_field_moves(want):
    coeffs = check.coefficients(STENCIL, CONFIG, DOMAIN)
    for name in STENCIL.ARRAYS:
        assert len(set(coeffs[name][2].tolist())) == 3, name
    assert CONFIG["radius"] == 4 and STENCIL.REACH == 2 * CONFIG["radius"]
    for r in RADII:
        assert all(np.abs(want[r][name]).max() > 0 for name in FIELDS)


def test_the_weights_are_the_published_staggered_ones():
    assert STENCIL.staggered_weights(2) == pytest.approx(
        [1 / 24, -9 / 8, 9 / 8, -1 / 24], rel=1e-14)
    assert STENCIL.staggered_weights(4)[4:] == pytest.approx(
        [1225 / 1024, -245 / 3072, 49 / 5120, -5 / 7168], rel=1e-14)
    # the program's come from Fornberg's recursion: the same numbers
    from yask_tpu.utils.fd_coeff import get_arbitrary_fd_coefficients
    for r in RADII:
        assert STENCIL.staggered_weights(r) == pytest.approx(
            get_arbitrary_fd_coefficients(
                1, 0.0, [i + 0.5 for i in range(-r, r)]), rel=1e-12)


@pytest.mark.parametrize("radius", RADII)
@pytest.mark.parametrize("mode", MODES)
def test_every_point_of_every_field_agrees_with_the_reference(
        mode, radius, got, want):
    errors = errors_of(got[mode, radius], want[radius])
    assert len(errors) == 9
    assert max(errors.values()) <= TOLERANCE, errors


@pytest.mark.parametrize("radius", RADII)
def test_every_point_agrees_with_the_unfetched_rows_poisoned(
        radius, want, monkeypatch):
    """Blocks of 8 x 8 on 4 x 3 tiles, the input DMAs double-buffered,
    and every input tile buffer NaN before a grid step's own copies
    land in it (``tests/poison.py``): each slot is fetched at the
    window the two stages read of it and the six stresses' evicted
    slots not at all (PR 45), so a read outside a window, or of a slot
    no DMA filled, is a NaN here -- not the stale tile of two grid
    steps before, which looks like the field."""
    from poison import poison_unfetched_rows
    assert poison_unfetched_rows(monkeypatch) == []
    errors = errors_of(program("pallas", radius, "-b_x 8 -b_y 8"),
                       want[radius])
    assert len(errors) == 9
    assert max(errors.values()) <= TOLERANCE, errors


@pytest.mark.parametrize("radius", RADII)
def test_the_bf16_control_fails_in_every_field(radius, want):
    control = reference(radius, rounder=check.bf16_round)
    errors = errors_of(control, want[radius])
    assert min(errors.values()) > 100 * TOLERANCE, errors


def broken(fault, monkeypatch):
    """The reference with one fault of place put in."""
    mod = _load(os.path.join(BENCH, "stencils", "ssg.py"),
                "bench_ssg_reference_broken")
    if fault == "a staggered shift off by one":
        diff = mod.diff
        monkeypatch.setattr(
            mod, "diff", lambda a, axis, shift, radius: diff(
                a, axis, 1 - shift if axis == 1 else shift, radius))
    elif fault == "rho read at the point instead of averaged":
        monkeypatch.setattr(
            mod, "buoyancy", lambda rho, axis, shape: 1.0 / rho)
    elif fault != "none":
        raise ValueError(fault)
    return mod


@pytest.mark.parametrize("fault", [
    "none", "a staggered shift off by one",
    "rho read at the point instead of averaged"])
@pytest.mark.parametrize("mode", MODES)
def test_each_fault_of_place_alone_fails(mode, fault, got, monkeypatch):
    """The program against a reference with the fault: were the
    program's ``ssg`` to differ from the plain equations by that much,
    the comparison would say so."""
    yardstick = reference(4, stencil=broken(fault, monkeypatch))
    errors = errors_of(got[mode, 4], yardstick)
    if fault == "none":
        assert max(errors.values()) <= TOLERANCE, errors
    else:
        assert max(errors.values()) > 100 * TOLERANCE, errors


@pytest.mark.parametrize("reach", [8, 4])
def test_a_cone_of_reach_4_is_too_narrow(reach, got, monkeypatch):
    """``check``'s cone sized by ``REACH``: 8 (two stages of radius 4)
    holds the program's blocks to the tolerance, 4 does not (the
    stresses read velocities that read stresses four further out)."""
    monkeypatch.setattr(STENCIL, "REACH", reach)
    fields = got["pallas", 4]
    blocks = {name: {f: fields[f][tuple(slice(o, o + BLOCK) for o in lo)]
                     for f in FIELDS}
              for name, lo in check.probes(DOMAIN, FILL, BLOCK).items()}
    said = []
    ok = check.compare(STENCIL, CONFIG, DOMAIN, BLOCK, STEPS, FILL,
                       blocks, TOLERANCE, said.append)
    assert len(said) == 27
    assert ok == (reach == 8), said
