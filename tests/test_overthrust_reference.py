"""The program's ``iso3dfd_sponge`` against the benchmark's plain float64
reference (``benchmark/stencils/iso3dfd_sponge.py``), over EVERY point
of ``pressure`` -- the benchmark's own comparison reads three 8^3 probe
blocks a run; this one leaves no point out.

Odd boxes, no extent a multiple of 8 (27 x 33 x 43 at the cell's radius
8, 13 x 19 x 21 at radius 2), so every lead dim ends in a block that
hangs over the edge and the minor dim fills no lane count, as the
cell's 801 x 801 x 187 does; the constants of the configuration
``overthrust-sponge-1chip`` through the public var API, so both arrays
vary with position: all four layers of the earth, the lateral step and
all five tapered faces are inside.  Seeded by ``check``'s law; after 3
and 10 steps under ``jit`` and ``pallas`` (one device, interpreted) at
``-wf_steps 2`` as the cell runs it (3 steps = a fused pair and a
single step).

``TOLERANCE`` is 1e-5 of the field's largest magnitude: float32 against
float64 reads 9.7e-7 at most here (radius 8, 10 steps: 9.72e-7 under
``jit`` and under ``pallas`` alike; 4.1e-7 after 3 steps, 3.1e-7 at
radius 2); the same reference with every stored value rounded to
bfloat16 reads 1.58e-2 -- 10 times above the first, 1600 times below
the second.  The planted faults read: sponge flattened to 1 1.12;
sponge applied to ``p(t)`` before the update 0.83; ``vel``'s x and z
profiles swapped 0.58; the first row past the end of each dim left
unmasked (a non-zero ghost) 0.55; radius 7 coefficients 3.4e-2.
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
MODES = ("jit", "pallas")
BOXES = {8: [27, 33, 43], 2: [13, 19, 21]}      # radius -> domain
RUNS = ((8, 3), (8, 10), (2, 3))                # (radius, steps)
REFERENCE = os.path.join(BENCH, "stencils", "iso3dfd_sponge.py")


def _load(name):
    spec = importlib.util.spec_from_file_location(name, REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STENCIL = _load("bench_iso3dfd_sponge_reference")
with open(os.path.join(BENCH, "configs",
                       "overthrust-sponge-1chip.json")) as _f:
    CONFIG = json.load(_f)


def fill_of(domain):
    return check.draw_fill(2147483777, domain, [1, 1, 1], BLOCK)


def reference(radius, steps, stencil=STENCIL, config=CONFIG,
              rounder=None, radius_used=None, domain=None):
    """The newest level of ``pressure`` after ``steps`` steps of the
    plain reference on the whole domain, in float64."""
    domain, lo = list(domain or BOXES[radius]), [0, 0, 0]
    state = {name: [a.astype(np.float64) for a in levels]
             for name, levels in check.initial_state(
                 STENCIL, domain, lo, domain, fill_of(domain)).items()}
    if rounder is not None:
        state = {name: [rounder(a) for a in levels]
                 for name, levels in state.items()}
    coeffs = {name: check.coefficient_box(c, lo, domain)
              for name, c in check.coefficients(
                  STENCIL, config, domain).items()}
    for _ in range(steps):
        state = stencil.step(state, coeffs, radius_used or radius, lo=lo,
                             domain=domain, rounder=rounder)
    return state["pressure"][-1]


def program(mode: str, radius: int, steps: int, domain=None, extra=""):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    domain = list(domain or BOXES[radius])
    last = [n - 1 for n in domain]
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="iso3dfd_sponge",
                           radius=radius)
    ctx.apply_command_line_options(
        f"-g_x {domain[0]} -g_y {domain[1]} -g_z {domain[2]} "
        f"-mode {mode} -wf_steps {CONFIG['wf_steps']} {extra}")
    ctx.prepare_solution()
    for name, c in check.coefficients(STENCIL, CONFIG, domain).items():
        ctx.get_var(name).set_elements_in_slice(
            check.coefficient_box(check.as_profiles(c, domain),
                                  [0, 0, 0], domain),
            [0, 0, 0], last)
    levels = check.initial_state(STENCIL, domain, [0, 0, 0], domain,
                                 fill_of(domain))["pressure"]
    var = ctx.get_var("pressure")
    newest = var.get_last_valid_step_index()
    for back, level in enumerate(reversed(levels)):
        var.set_elements_in_slice(level, [newest - back, 0, 0, 0],
                                  [newest - back] + last)
    ctx.run_solution(0, steps - 1)
    t = var.get_last_valid_step_index()
    out = np.asarray(var.get_elements_in_slice([t, 0, 0, 0], [t] + last))
    program.plans = ctx.compiled_plans()
    ctx.end_solution()
    return out


@pytest.fixture(scope="module")
def want():
    return {run: reference(*run) for run in RUNS}


@pytest.fixture(scope="module")
def got():
    return {(mode,) + run: program(mode, *run)
            for mode in MODES for run in RUNS}


def test_no_extent_is_a_multiple_of_8_and_every_layer_and_face_is_inside():
    for radius, domain in BOXES.items():
        assert all(n % 2 == 1 and n % 8 for n in domain)
        assert all(n >= 2 * radius for n in domain)      # K = 2 fits
    assert all(n % 2 == 1 and n % 8 for n in CONFIG["domain"])
    assert all(n % 2 == 1 and n % 8 for n in CONFIG["tiny_domain"])
    for domain in (BOXES[8], CONFIG["domain"]):
        coeffs = check.coefficients(STENCIL, CONFIG, domain)
        vel, sponge = coeffs["vel"], coeffs["sponge"]
        assert [len(set(p.tolist())) for p in vel] == [2, 1, 4]
        # five tapered faces: both ends of x and y, the low end of z
        for axis in (0, 1):
            assert sponge[axis][0] == sponge[axis][-1] < 1.0
        assert sponge[2][0] < 1.0 and sponge[2][-1] == 1.0
    assert all(float(p[20:-20].min()) == 1.0 for p in sponge[:2])
    assert sponge[2][0] == pytest.approx(np.exp(-(0.015 * 20) ** 2))
    # stable: the largest vel times the operator's largest eigenvalue
    top = float(np.prod([p.max() for p in vel]))
    assert top == pytest.approx(0.147456) and top <= 0.15
    assert STENCIL.eigenvalue_bound(8) == pytest.approx(22.2808, abs=1e-4)
    assert top * STENCIL.eigenvalue_bound(8) < 4.0
    assert "22.2808" in CONFIG["assumed"]["dt and h"]


def test_the_weights_are_the_programs():
    from yask_tpu.utils.fd_coeff import get_center_fd_coefficients
    for r in (2, 7, 8):
        c = STENCIL.second_diff_coefficients(r)
        assert c[:0:-1] + c == pytest.approx(
            list(get_center_fd_coefficients(2, r)), rel=1e-12)
    # the two ways the reference sums the Laplacian agree
    a = np.random.default_rng(3).random((9, 11, 13))
    assert STENCIL.laplacian_fast(a, 2) == pytest.approx(
        STENCIL.laplacian(a, 2), rel=1e-12, abs=1e-12)


def test_one_step_reaches_the_radius_and_the_ring_is_two_deep():
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="iso3dfd_sponge",
                           radius=CONFIG["radius"])
    assert set(ctx._ana.fused_step_radius().values()) == {8}
    assert check.reach(STENCIL, CONFIG) == 8
    assert STENCIL.FIELDS == {"pressure": {"slots": 2}}
    assert STENCIL.ARRAYS == ("vel", "sponge")
    assert STENCIL.need_bytes_per_point_step(2) == 12


@pytest.mark.parametrize("radius,steps", RUNS)
@pytest.mark.parametrize("mode", MODES)
def test_every_point_agrees_with_the_reference(mode, radius, steps, got,
                                               want):
    error = check.block_error(got[mode, radius, steps],
                              want[radius, steps])
    assert error <= TOLERANCE, error


@pytest.mark.parametrize("radius,steps,extra", [
    (8, 3, "-b_x 8 -b_y 8"),            # 4 x 5 tiles, both dims windowed
    (8, 10, "-b_x 8 -b_y 24"),          # y skewed: its slab stays whole
    (2, 3, "-b_x 4 -b_y 8")])
def test_every_point_agrees_with_the_unfetched_rows_poisoned(
        radius, steps, extra, want, monkeypatch):
    """Explicit blocks that overshoot every ragged edge, the input DMAs
    double-buffered, and every input tile buffer NaN before a grid
    step's own copies land in it (``tests/poison.py``):
    ``pressure(t-1)``, ``vel`` and ``sponge`` are fetched a radius
    narrower than the slab in a dim that takes the window (PR 45), so a
    read outside a window is a NaN here, not the stale tile of two grid
    steps before."""
    from poison import poison_unfetched_rows
    assert poison_unfetched_rows(monkeypatch) == []
    got = program("pallas", radius, steps, extra=extra)
    row = max(program.plans, key=lambda r: r["k"])
    assert row["pipeline_dmas"] and min(row["grid"]) >= 2
    win = row["fetch_windows"]
    rows = {slot: {d: hi - lo for d, (lo, hi) in w.items()}
            for slot, w in win.items()}
    assert rows["vel/0"] == rows["sponge/0"] == rows["pressure/0"]
    assert rows["vel/0"]["x"] == rows["pressure/1"]["x"] - 2 * radius
    # y under the skew (its carry floor is 24 rows) keeps the slab;
    # at radius 2 the sublane tile's 8 rows round the window out to it
    assert (rows["vel/0"]["y"] == rows["pressure/1"]["y"]) \
        == ("-b_y 24" in extra or radius == 2)
    assert np.isfinite(got).all()
    error = check.block_error(got, want[radius, steps])
    assert error <= TOLERANCE, error


def test_the_bf16_control_fails(want):
    control = reference(8, 10, rounder=check.bf16_round)
    assert check.block_error(control, want[8, 10]) > 100 * TOLERANCE


FAULTS = ("none", "sponge flattened to 1",
          "sponge applied to p(t) instead of the new value",
          "vel's x and z profiles swapped",
          "the first row past each end left unmasked",
          "radius 7 coefficients")


def broken(fault, radius=8, steps=10, domain=None):
    """What the reference reads after the cell's 10 steps at its
    radius (or ``steps`` at ``radius`` on ``domain``), with one fault
    put in."""
    mod = _load("bench_iso3dfd_sponge_broken")
    step = mod.step
    config, radius_used = CONFIG, None
    if fault == "sponge flattened to 1":
        mod.step = lambda state, coeffs, *a, **kw: step(
            state, {**coeffs, "sponge": 1.0}, *a, **kw)
    elif fault == "sponge applied to p(t) instead of the new value":
        def early(state, coeffs, *a, **kw):
            old, cur = state["pressure"]
            new = step({"pressure": [old, cur * coeffs["sponge"]]},
                       {**coeffs, "sponge": 1.0}, *a, **kw)
            return {"pressure": [cur, new["pressure"][-1]]}
        mod.step = early
    elif fault == "vel's x and z profiles swapped":
        vel = CONFIG["consts"]["vel"]
        config = {**CONFIG, "consts": {
            **CONFIG["consts"], "vel": {"x": vel["z"], "z": vel["x"]}}}
    elif fault == "the first row past each end left unmasked":
        # a block that hangs over the edge writes what it computed: the
        # row at index n of each dim holds a copy of row n - 1 when the
        # next step reads it
        def ghost(state, coeffs, radius, **kw):
            grown = {"pressure": [np.pad(a, ((0, 1),) * 3, mode="edge")
                                  for a in state["pressure"]]}
            wide = {k: np.pad(np.asarray(c, np.float64), ((0, 1),) * 3)
                    for k, c in coeffs.items()}
            new = step(grown, wide, radius, **kw)["pressure"][-1]
            return {"pressure": [state["pressure"][-1],
                                 new[:-1, :-1, :-1]]}
        mod.step = ghost
    elif fault == "radius 7 coefficients":
        radius_used = radius - 1
    elif fault != "none":
        raise ValueError(fault)
    return reference(radius, steps, stencil=mod, config=config,
                     radius_used=radius_used, domain=domain)


yardstick = functools.lru_cache(maxsize=None)(broken)


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("mode", MODES)
def test_each_fault_alone_fails(mode, fault, got):
    """The program against a reference with the fault: were the
    program's ``iso3dfd_sponge`` to differ from the plain equation by
    that much, the comparison would say so."""
    error = check.block_error(got[mode, 8, 10], yardstick(fault))
    if fault == "none":
        assert error <= TOLERANCE, error
    else:
        assert error > 100 * TOLERANCE, (fault, error)


# PR 42: a lead block need not divide its extent.  Boxes whose x is
# prime (no block but 1 and the whole divides it; 23 is no multiple of
# 17, so the seeded field varies with x and y too), at radius 2, after
# 6 steps, under the default plan held to 8 x 8 and under explicit
# blocks that divide neither lead extent: before the pad an 8 x 16 ran
# as 1 x 16.  The blocks' last tiles walk 1 to 6 rows past the edge of
# x; the faults are the same five, one radius down for the last.  The
# program reads 3.9e-7 and 5.5e-7 under every block, the faults 0.12
# (radius 1 coefficients) to 1.03.
PRIME_BOXES = ((37, 41, 24), (29, 33, 23))
PRIME_BLOCKS = ("-vmem_mb 1", "-b_x 8 -b_y 16", "-b_x 7 -b_y 16",
                "-b_x 10 -b_y 8")
PRIME_RUN = (2, 6)                              # radius, steps


@functools.lru_cache(maxsize=None)
def prime_yardstick(domain, fault):
    return broken(fault, *PRIME_RUN, domain=domain)


@pytest.mark.parametrize("extra", PRIME_BLOCKS)
@pytest.mark.parametrize("domain", PRIME_BOXES)
def test_a_block_that_overshoots_a_prime_x_agrees_at_every_point(
        domain, extra):
    got = program("pallas", *PRIME_RUN, domain=domain, extra=extra)
    row, = program.plans
    assert domain[0] % row["block"]["x"] and row["overshoot"]["x"] > 0
    if "-b_x" in extra:
        assert f"-b_x {row['block']['x']} -b_y {row['block']['y']}" \
            == extra
    error = check.block_error(got, prime_yardstick(domain, "none"))
    assert error <= TOLERANCE, error
    for fault in FAULTS[1:]:
        error = check.block_error(got, prime_yardstick(domain, fault))
        assert error > 100 * TOLERANCE, (fault, error)
