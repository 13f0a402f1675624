"""The program's ``tti`` against the benchmark's plain float64 reference
(``benchmark/stencils/tti.py``), over EVERY point of both wavefields --
the benchmark's own comparison reads three 8^3 probe blocks a run; this
one leaves no point out.

A box small enough for the CPU (32 x 24 x 64) with the constants of the
configuration ``tti-r4-1chip`` through the public var API, so every
array varies with position: all three layers of the earth and of the
anisotropy, both damping bands (4 rows each at this height), twelve
dips and nine azimuths are inside.  Seeded by ``check``'s law; at
radius 4 (the cell's, order 8) after 3 and 10 steps and at radius 2
(the registry's default) after 3, under ``jit`` and ``pallas`` (one
device, interpreted) at ``-wf_steps 1`` as the cell runs it.

``TOLERANCE`` is 1e-5 of a field's largest magnitude: float32 against
float64 reads 2.5e-6 at most here (radius 4, 10 steps: 2.47e-6 in
``u``, 2.51e-6 in ``v``, the same under both modes; 5.7e-7 after 3
steps, 5.2e-7 at radius 2); the same reference with every stored value
rounded to bfloat16 reads 4.0e-2 (``u``) and 6.8e-2 (``v``) -- 4 times
above the first, 4000 times below the second.  The planted faults read
(``u``, ``v``): ``Hz`` from ``G`` applied once 0.91, 0.92; ``theta``
and ``phi`` swapped 0.12, 0.10; ``damp`` flattened 0.26, 0.27;
``delta`` = ``epsilon`` 0.013, 0.14; a scratch value outside the domain
set to zero 0.030, 0.029 (the rows within 4 of the z faces alone
differ).
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

DOMAIN = [32, 24, 64]
BLOCK = 4
TOLERANCE = 1e-5
MODES = ("jit", "pallas")
RUNS = ((4, 3), (4, 10), (2, 3))      # (radius, steps)
REFERENCE = os.path.join(BENCH, "stencils", "tti.py")


def _load(name):
    spec = importlib.util.spec_from_file_location(name, REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STENCIL = _load("bench_tti_reference")
with open(os.path.join(BENCH, "configs", "tti-r4-1chip.json")) as _f:
    CONFIG = json.load(_f)
FILL = check.draw_fill(2147483777, DOMAIN, [1, 1, 1], BLOCK)
FIELDS = tuple(STENCIL.FIELDS)
LAST = [n - 1 for n in DOMAIN]


def reference(radius, steps, stencil=STENCIL, rounder=None):
    """Both fields' newest level after ``steps`` steps of the plain
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
    for _ in range(steps):
        state = stencil.step(state, coeffs, radius, lo=lo, domain=DOMAIN,
                             rounder=rounder)
    return {name: levels[-1] for name, levels in state.items()}


def program(mode: str, radius: int, steps: int, options: str = ""):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="tti", radius=radius)
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
    ctx.run_solution(0, steps - 1)
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
    return {run: reference(*run) for run in RUNS}


@pytest.fixture(scope="module")
def got():
    return {(mode,) + run: program(mode, *run)
            for mode in MODES for run in RUNS}


def errors_of(fields, yardstick):
    return {name: check.block_error(fields[name], yardstick[name])
            for name in FIELDS}


def test_the_box_holds_every_layer_both_bands_and_no_plane_has_one_tilt(
        want):
    coeffs = check.coefficients(STENCIL, CONFIG, DOMAIN)
    kinds = {name: [len(set(p.tolist())) for p in coeffs[name]]
             for name in STENCIL.ARRAYS}
    assert kinds["m"] == kinds["epsilon"] == kinds["delta"] == [1, 1, 3]
    assert kinds["theta"] == [4, 1, 3]          # layered in z and in x
    assert kinds["phi"] == [1, 3, 3]            # layered in z and in y
    damp = coeffs["damp"][2]
    band = DOMAIN[2] // 16                      # 32 rows of 512
    assert np.all(damp[:band] > 0) and np.all(damp[-band:] > 0)
    assert np.all(damp[band:-band] == 0)
    assert damp[0] == damp.max()                # the ramps rise outwards
    assert np.all(np.diff(damp[:band]) < 0)
    assert np.all(np.diff(damp[-band:]) > 0)
    eps, dlt = coeffs["epsilon"][2], coeffs["delta"][2]
    assert np.all(eps >= dlt) and np.all(dlt >= 0) and eps.max() <= 0.3
    assert CONFIG["radius"] == 4 and STENCIL.REACH == 2 * CONFIG["radius"]
    for run in RUNS:
        assert all(np.abs(want[run][name]).max() > 0 for name in FIELDS)


def test_the_weights_are_the_centred_ones_of_the_order():
    assert STENCIL.first_diff_weights(4)[5:] == pytest.approx(
        [4 / 5, -1 / 5, 4 / 105, -1 / 280], rel=1e-14)
    assert STENCIL.second_diff_weights(4)[4:] == pytest.approx(
        [-205 / 72, 8 / 5, -1 / 5, 8 / 315, -1 / 560], rel=1e-14)
    # the program's come from Fornberg's recursion: the same numbers
    from yask_tpu.utils.fd_coeff import get_center_fd_coefficients
    for r in (2, 4):
        assert STENCIL.first_diff_weights(r) == pytest.approx(
            list(get_center_fd_coefficients(1, r)), rel=1e-12, abs=1e-15)
        assert STENCIL.second_diff_weights(r) == pytest.approx(
            list(get_center_fd_coefficients(2, r)), rel=1e-12)


def test_one_step_reaches_what_the_program_says():
    from yask_tpu import yk_factory
    from yask_tpu.stencils import tti as program_tti
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="tti",
                           radius=CONFIG["radius"])
    assert set(ctx._ana.fused_step_radius().values()) == {STENCIL.REACH}
    assert (STENCIL.DT, STENCIL.H) == (program_tti.DT, program_tti.H)


@pytest.mark.parametrize("radius,steps", RUNS)
@pytest.mark.parametrize("mode", MODES)
def test_every_point_of_both_fields_agrees_with_the_reference(
        mode, radius, steps, got, want):
    errors = errors_of(got[mode, radius, steps], want[radius, steps])
    assert len(errors) == 2
    assert max(errors.values()) <= TOLERANCE, errors


@pytest.mark.parametrize("radius,steps", RUNS[::2])
def test_every_point_agrees_with_the_unfetched_rows_poisoned(
        radius, steps, want, monkeypatch):
    """Blocks of 8 x 8 on 4 x 3 tiles, the input DMAs double-buffered,
    and every input tile buffer NaN before a grid step's own copies
    land in it (``tests/poison.py``): ``u(t-1)``, ``v(t-1)`` and the
    arrays read at the point are fetched at the block, the two angles
    on the scratch chain's grown region (PR 45), so a read outside a
    window is a NaN here, not the stale tile of two grid steps
    before."""
    from poison import poison_unfetched_rows
    assert poison_unfetched_rows(monkeypatch) == []
    errors = errors_of(program("pallas", radius, steps, "-b_x 8 -b_y 8"),
                       want[radius, steps])
    assert len(errors) == 2
    assert max(errors.values()) <= TOLERANCE, errors


def test_the_bf16_control_fails_in_both_fields(want):
    control = reference(4, 10, rounder=check.bf16_round)
    errors = errors_of(control, want[4, 10])
    assert min(errors.values()) > 100 * TOLERANCE, errors


FAULTS = ("none", "Hz built from G applied once",
          "theta and phi swapped", "damp flattened",
          "delta set to epsilon",
          "a scratch value outside the domain set to zero")


def broken(fault):
    """The reference, loaded anew, with one fault of place put in."""
    mod = _load("bench_tti_reference_broken")
    step, inner = mod.step, mod.inner

    def with_coeffs(change):
        mod.step = lambda state, coeffs, *a, **kw: step(
            state, change(dict(coeffs)), *a, **kw)

    def zero_outside(f, wide, r):
        g = np.zeros(tuple(n + 2 * r for n in f.shape))
        core = (slice(r, -r),) * 3
        g[core] = inner(f, wide, r)[core]
        return g

    if fault == "Hz built from G applied once":
        mod.hz = lambda f, wide, r: inner(f, wide, r)[(slice(r, -r),) * 3]
    elif fault == "theta and phi swapped":
        with_coeffs(lambda c: {**c, "theta": c["phi"], "phi": c["theta"]})
    elif fault == "damp flattened":
        with_coeffs(lambda c: {**c, "damp": 0.0 * c["damp"]})
    elif fault == "delta set to epsilon":
        with_coeffs(lambda c: {**c, "delta": c["epsilon"]})
    elif fault == "a scratch value outside the domain set to zero":
        mod.inner = zero_outside
    elif fault != "none":
        raise ValueError(fault)
    return mod


@functools.lru_cache(maxsize=None)
def yardstick(fault):
    """What the reference with ``fault`` reads after the cell's 10
    steps at its radius (one computation for both modes)."""
    return reference(4, 10, stencil=broken(fault))


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("mode", MODES)
def test_each_fault_of_place_alone_fails(mode, fault, got):
    """The program against a reference with the fault: were the
    program's ``tti`` to differ from the plain equations by that much,
    the comparison would say so."""
    errors = errors_of(got[mode, 4, 10], yardstick(fault))
    if fault == "none":
        assert max(errors.values()) <= TOLERANCE, errors
    else:
        assert max(errors.values()) > 100 * TOLERANCE, errors


@pytest.mark.parametrize("reach", [8, 4])
def test_a_cone_of_reach_4_is_too_narrow(reach, got, monkeypatch):
    """``check``'s cone sized by ``REACH``: 8 (``G`` applied twice at
    radius 4) holds the program's blocks to the tolerance, 4 does not
    (the outer ``G`` reads an inner one that read four further out)."""
    monkeypatch.setattr(STENCIL, "REACH", reach)
    fields = got["pallas", 4, 3]
    blocks = {name: {f: fields[f][tuple(slice(o, o + BLOCK) for o in lo)]
                     for f in FIELDS}
              for name, lo in check.probes(DOMAIN, FILL, BLOCK).items()}
    said = []
    ok = check.compare(STENCIL, CONFIG, DOMAIN, BLOCK, 3, FILL,
                       blocks, TOLERANCE, said.append)
    assert len(said) == 6
    assert ok == (reach == 8), said
