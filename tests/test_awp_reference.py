"""The program's ``awp_abc`` against the benchmark's plain float64
reference (``benchmark/stencils/awp_abc.py``), over EVERY point of all
twelve stepped fields -- the benchmark's own comparison reads three
8^3 probe blocks a run; this one leaves no point out.

A box small enough for the CPU and tall enough (z = 32) that the
sponge's taper, all three layers of the earth and the free-surface rows
are inside; seeded by ``check``'s law with the constants of the
configuration ``awp-abc-r2-4chip``, through the public var API; run
under ``jit``, ``pallas`` (one device, interpreted) and
``shard_pallas`` with x split four ways (four of conftest's host
devices), at ``-wf_steps 1`` as the cell runs it.

``TOLERANCE`` is 1e-5 of a field's largest magnitude: float32 against
float64 over four steps of ~100 operations a point reads 3.6e-7 here in
every mode, the same reference with every stored value rounded to
bfloat16 reads 6e-3 to 1.1e-2 in every field -- 28 times above the
first, 600 times below the second.
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

DOMAIN = [32, 16, 32]
STEPS = 4
TOLERANCE = 1e-5
#: shard_pallas against one device: the same expression a point, so
#: bit for bit today; the bound is what reassociation could cost
#: (``tests/test_pallas.py _FP_REASSOC`` names ``awp_abc``), far below
#: what a stale or misplaced ghost plane costs (O(1) of the field)
SEAM_BOUND = 1e-6
MODES = {"jit": 1, "pallas": 1, "shard_pallas": 4}      # mode -> x ranks


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STENCIL = _load(os.path.join(BENCH, "stencils", "awp_abc.py"),
                "bench_awp_abc_reference")
with open(os.path.join(BENCH, "configs", "awp-abc-r2-4chip.json")) as _f:
    CONFIG = json.load(_f)
FILL = check.draw_fill(2147483777, DOMAIN, [4, 1, 1], 4)
FIELDS = tuple(STENCIL.FIELDS)
LAST = [n - 1 for n in DOMAIN]


def reference(rounder=None):
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
        state = STENCIL.step(state, coeffs, CONFIG["radius"], lo=lo,
                             domain=DOMAIN, rounder=rounder)
    return {name: levels[-1] for name, levels in state.items()}


def program(mode: str, x_ranks: int, options: str = ""):
    """The same state through the program's normal path."""
    from yask_tpu import yk_factory
    fac = yk_factory()
    ctx = fac.new_solution(fac.new_env(), stencil="awp_abc",
                           radius=CONFIG["radius"])
    ctx.apply_command_line_options(
        f"-g_x {DOMAIN[0]} -g_y {DOMAIN[1]} -g_z {DOMAIN[2]} "
        f"-mode {mode} -wf_steps {CONFIG['wf_steps']} {options}")
    if x_ranks > 1:
        ctx.set_num_ranks("x", x_ranks)
    ctx.prepare_solution()
    for name, c in check.coefficients(STENCIL, CONFIG, DOMAIN).items():
        var = ctx.get_var(name)
        if name in check.scalars(STENCIL):
            var.set_element(c, [])
        else:
            var.set_elements_in_slice(
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
    return reference()


@pytest.fixture(scope="module")
def got():
    return {mode: program(mode, ranks) for mode, ranks in MODES.items()}


def test_the_box_holds_sponge_layers_and_free_surface(want):
    """What the docstring promises of the box, so that the comparisons
    below are of the whole stencil and not of a uniform interior."""
    coeffs = check.coefficients(STENCIL, CONFIG, DOMAIN)
    assert len(set(coeffs["rho"][2].tolist())) == 3        # three layers
    taper = coeffs["sponge"][2]         # over z: the bottom, not the top
    assert taper[0] < 0.95 and taper[-1] == 1.0
    assert DOMAIN[2] >= 24 and STEPS >= 4
    assert all(np.abs(want[name]).max() > 0 for name in FIELDS)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_point_of_every_field_agrees_with_the_reference(
        mode, got, want):
    errors = {name: check.block_error(got[mode][name], want[name])
              for name in FIELDS}
    assert len(errors) == 12
    assert max(errors.values()) <= TOLERANCE, errors


@pytest.mark.parametrize("mode", ["pallas", "shard_pallas"])
def test_every_point_agrees_with_the_unfetched_rows_poisoned(
        mode, want, monkeypatch):
    """Blocks of 8 x 8 (4 x 2 tiles on one device; one tile of x a
    shard, 2 of y), the input DMAs double-buffered where a launch has
    more than one grid step, and every input tile buffer NaN before a
    grid step's own copies land in it (``tests/poison.py``): the six
    stresses' evicted slots are not fetched (the three written under a
    condition each side of the free surface too: the two conditions
    cover the domain) and every other slot at the window the four
    stages read of it (PR 45).  A read outside a window is a NaN here;
    ``tests/test_fetch_windows.py`` runs the same on a box of 5 x 4
    tiles."""
    from poison import poison_unfetched_rows
    assert poison_unfetched_rows(monkeypatch) == []
    fields = program(mode, MODES[mode], "-b_x 8 -b_y 8")
    errors = {name: check.block_error(fields[name], want[name])
              for name in FIELDS}
    assert len(errors) == 12
    assert max(errors.values()) <= TOLERANCE, errors


def test_the_bf16_control_fails_in_every_field(want):
    control = reference(check.bf16_round)
    errors = {name: check.block_error(control[name], want[name])
              for name in FIELDS}
    assert min(errors.values()) > 100 * TOLERANCE, errors


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_free_surface_rows_hold_exactly_zero(mode, got):
    """``stress_zz`` on ``z == last``, ``stress_xz`` and ``stress_yz``
    on ``z >= last - 1``: exactly 0, with live rows right below."""
    fields = got[mode]
    assert not fields["stress_zz"][:, :, -1].any()
    assert fields["stress_zz"][:, :, -2].any()
    for name in ("stress_xz", "stress_yz"):
        assert not fields[name][:, :, -2:].any(), name
        assert fields[name][:, :, -3].any(), name
    # the stresses without z have no such rows
    assert fields["stress_xx"][:, :, -1].any()
    assert fields["stress_xy"][:, :, -1].any()


def test_the_planes_beside_a_shard_seam_agree_with_one_device(got):
    """Six planes each side of every shard boundary (the ghost width a
    round refreshes) hold what the one-device run holds."""
    every = DOMAIN[0] // MODES["shard_pallas"]
    for name in FIELDS:
        one, four = got["pallas"][name], got["shard_pallas"][name]
        scale = float(np.abs(one).max())
        for seam in range(every, DOMAIN[0], every):
            planes = slice(max(0, seam - 6), min(DOMAIN[0], seam + 6))
            gap = np.abs(four[planes].astype(np.float64) - one[planes])
            assert gap.max() <= SEAM_BOUND * scale, (name, seam)


@pytest.mark.parametrize("mode", ["pallas", "shard_pallas"])
@pytest.mark.parametrize("chain", ["whole", "one-short"])
def test_blocks_of_8x8_agree_and_a_chain_counted_short_is_seen(
        chain, mode, want, monkeypatch):
    """At the cell's blocks of 8 x 8 (the default plan of this small
    box is one tile a device in x, which has no seam to get wrong but
    the shards') every point agrees -- and the comparison can fail:
    with the margin the stress stages have consumed forced one short of
    their chain of 4 (old stress -> velocity 2 away -> stress 2 away),
    tiles and shard halos are 3 wide, the velocities are whole one
    plane short of where the stresses read them, and the comparison
    reports it in both kernel modes.  (Stage 2 ALONE forced short
    changes no result: it is then evaluated a plane wider than anything
    reads.)"""
    from yask_tpu.compiler.analysis import SolutionAnalysis
    honest = SolutionAnalysis.stage_consumed

    def one_short(self):
        cons = honest(self)
        assert [c["x"] for c in cons] == [2, 4, 4, 4]
        return cons[:1] + [{d: c - 1 for d, c in stage.items()}
                           for stage in cons[1:]]

    if chain == "one-short":
        monkeypatch.setattr(SolutionAnalysis, "stage_consumed", one_short)
    fields = program(mode, MODES[mode], "-b_x 8 -b_y 8")
    worst = max(check.block_error(fields[name], want[name])
                for name in FIELDS)
    if chain == "whole":
        assert worst <= TOLERANCE
    else:
        assert worst > 100 * TOLERANCE
